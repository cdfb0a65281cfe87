"""Median latency of an open-loop window, from scheduled arrival."""
from bench.readers import percentile_ms


def read(ctx):
    v = percentile_ms(ctx, 50)
    return None if v is None else (v, "ms")
