"""Median wait of a request between submit and its dispatch."""
import numpy as np

from bench.readers import answered


def read(ctx):
    a = answered(ctx)
    if not a.any():
        return None
    wait = ctx["t_dispatch"][a] - ctx["t_submit"][a]
    return float(np.median(wait) * 1e3), "ms"
