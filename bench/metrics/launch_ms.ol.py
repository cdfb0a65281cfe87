"""Median milliseconds a dispatch of the window spends in the executor call
(``acorn.launch``): the host -> device copies and the enqueue."""
from bench.spans import median_ms


def read(ctx):
    return median_ms(ctx, "acorn.launch")
