"""Median milliseconds a dispatch of the window spends in its fetch
(``acorn.fetch``): the wait for the device's result, the device -> host
copies and the trim."""
from bench.spans import median_ms


def read(ctx):
    return median_ms(ctx, "acorn.fetch")
