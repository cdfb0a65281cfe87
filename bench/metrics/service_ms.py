"""Median over dispatches of the executor call and demux: t_done - t_dispatch."""
import numpy as np


def read(ctx):
    d = ctx["dispatches"]
    if not d:
        return None
    return float(np.median([x["t_done"] - x["t_dispatch"] for x in d]) * 1e3), "ms"
