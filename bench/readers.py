"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Each reader reads the context ``run.context`` builds: per-request host-clock
records of the window (``t_submit``/``t_dispatch``/``t_done`` from the
program's ``AsyncResult``), the dispatches they formed, the installs, the
trace summary (``trace.reduce``) and the work count.  A reader that finds
nothing to read returns None, and the metric is left out of the line.
"""
from __future__ import annotations

import numpy as np


def answered(ctx):
    return ~np.isnan(ctx["t_dispatch"])


def percentile_ms(ctx, q):
    """Latency over every request scheduled in an open-loop window, from
    its scheduled arrival to its answer."""
    lat = ctx["latency"]
    if lat is None or not np.isfinite(lat).any():
        return None
    # a failed request misses every limit: it counts as the slowest
    v = float(np.percentile(np.where(np.isnan(lat), np.inf, lat), q) * 1e3)
    return v if np.isfinite(v) else None


def idle_pct(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = float(np.mean(list(tr["busy_s"].values())))
    return 100.0 * (1.0 - busy / tr["window_s"])


def kernel_s(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    k = float(sum(tr["kernel_s"].values()))
    return k if k > 0 else None


def packets_answered(ctx):
    return int(ctx["size"][answered(ctx)].sum())


def least_s(ctx):
    """The window's least time: the bytes its dispatches need over the
    chip's HBM bandwidth (``workcount``)."""
    need = sum(d["bytes"] for d in ctx["dispatches"])
    return need / ctx["peaks"]["hbm_bytes_per_s"]
