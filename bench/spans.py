"""The program's own spans in a traced run, for the per-layer readers.

The program marks each step of a dispatch and of an install with an
``acorn.*`` ``TraceAnnotation`` (``src/repro/core/spans.py``); the profiler
writes them, with their metadata, into the same ``.xplane.pb`` as the device
ops.  ``load`` reads those that start inside the benchmark's window span
from the trace ``run.py`` records under its ``TRACE_DIR``, once a run: the
first reader keeps them in the run's context.  A program without spans
leaves nothing to read: the readers then return None.

* ``per_dispatch`` is a span's time in each dispatch of the window (the
  spans of one dispatch carry its ``dispatch`` id);
* ``slot_waits`` is, per dispatch, the time from the end of its
  ``acorn.coalesce`` (the cut, on the event loop) to the start of its
  ``acorn.pad`` (the host path, on a slot thread);
* ``acorn.pad`` carries the dispatch's real rows and its admission bucket,
  ``acorn.release`` the drain of the hold it ends (``drain_us``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os

import numpy as np

from bench.run import TRACE_DIR
from bench.trace import WINDOW_SPAN

PREFIX = "acorn."


def load(trace_dir: str) -> list:
    """``[[name, start_ns, dur_ns, metadata], ...]`` of the program's spans
    that start inside the window, in start order; [] when the trace holds
    none."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    out.append([name, e.start_ns, e.duration_ns,
                                dict(e.stats)])
                elif name == WINDOW_SPAN and window is None:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        return []
    lo, hi = window
    return sorted((s for s in out if lo <= s[1] < hi), key=lambda s: s[1])


def of(ctx) -> list | None:
    """The window's program spans for a reader: ``ctx["spans"]``, loaded
    from the traced run under ``TRACE_DIR`` by the first reader that asks.
    None for an untraced run or a program without spans."""
    if ctx.get("trace") is None:
        return None
    if ctx.get("spans") is None:
        ctx["spans"] = load(str(TRACE_DIR))
    return ctx["spans"] or None


def per_dispatch(spans, name: str) -> dict[int, float]:
    """Seconds of span ``name`` in each dispatch of the window."""
    out: dict[int, float] = {}
    for n, _, d, meta in spans:
        k = meta.get("dispatch", -1)
        if n == name and k >= 0:
            out[k] = out.get(k, 0.0) + d * 1e-9
    return out


def slot_waits(spans) -> list[float]:
    """Seconds from each dispatch's cut to the start of its host path."""
    cut, start = {}, {}
    for n, s, d, meta in spans:
        k = meta.get("dispatch", -1)
        if n == "acorn.coalesce":
            cut[k] = s + d
        elif n == "acorn.pad":
            start.setdefault(k, s)
    return [(start[k] - cut[k]) * 1e-9 for k in sorted(cut)
            if k >= 0 and k in start]


def median_ms(ctx, name: str):
    """Median milliseconds a dispatch spends in span ``name``."""
    spans = of(ctx)
    got = per_dispatch(spans, name) if spans else {}
    if not got:
        return None
    return float(np.median(list(got.values())) * 1e3), "ms"


def pad_share(ctx):
    """Padding rows as a share of the rows run, over the window's
    dispatches."""
    spans = of(ctx)
    pads = [m for n, _, _, m in spans or () if n == "acorn.pad"
            and m.get("dispatch", -1) >= 0 and "bucket" in m]
    run = sum(m["bucket"] for m in pads)
    if not run:
        return None
    return 100.0 * (run - sum(m["rows"] for m in pads)) / run, "%"


def save(events: dict, spans: list, path: str) -> None:
    """``trace.load``'s events with the program's spans beside them."""
    with gzip.open(path, "wt") as f:
        json.dump(dict(events, spans=spans), f)
