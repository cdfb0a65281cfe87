"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into plain event lists: the
device ops of each chip (the ``XLA Ops`` line of each ``/device:TPU:n``
plane) and the host events (JAX's runtime events and the benchmark's own
``TraceAnnotation`` spans).  ``reduce`` works on those lists only, so it is
tested on a small trace recorded on the chip and committed beside it.

* busy time is the union of a chip's device-op intervals inside the window;
* kernel time is the summed duration of the device ops whose name holds one
  of the kernel's names (the name the chip's trace gives the fused
  ``pallas_call``);
* collective time is the summed duration of collective ops;
* the longest idle gaps are attributed to the host event that overlapped
  them most.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

WINDOW_SPAN = "bench_window"
DEVICE_LINE = "XLA Ops"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "ppermute")


def load(trace_dir: str, device_ids) -> dict:
    """Plain events of the trace under ``trace_dir``: ``{"device": {id:
    [[name, start_ns, dur_ns], ...]}, "host": [[name, start_ns, dur_ns],
    ...]}`` for the chips ``device_ids``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    want = {f"/device:TPU:{i}": i for i in device_ids}
    out = {"device": {i: [] for i in device_ids}, "host": []}
    for plane in pd.planes:
        if plane.name in want:
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    out["device"][want[plane.name]] += [
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.duration_ns > 0]
    return out


def op_name(hlo: str) -> str:
    """A device op's name without its HLO text: the chip's trace names an
    op by its whole instruction (``%name = type op(operands), ...``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    ev["device"] = {int(k): v for k, v in ev["device"].items()}
    return ev


def window(events: dict) -> tuple[float, float]:
    """The measured window, from the benchmark's own span."""
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return spans[0]


def _clip(ev, lo, hi):
    for name, s, d in ev:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _matches(name: str, names) -> bool:
    low = name.lower()
    return any(n in low for n in names)


def reduce(events: dict, kernel_names=(), top: int = 10) -> dict:
    """Per-chip busy, kernel and collective seconds inside the window, the
    window's length, the device ops that took most time and the longest
    idle gaps by what the host was doing."""
    lo, hi = window(events)
    busy, kernel, coll, gaps = {}, {}, {}, []
    by_op: dict[str, float] = {}
    for dev, ev in events["device"].items():
        clipped = list(_clip(ev, lo, hi))
        merged = union((a, b) for _, a, b in clipped)
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        kernel[dev] = sum(b - a for n, a, b in clipped
                          if _matches(n, kernel_names)) * 1e-9
        coll[dev] = sum(b - a for n, a, b in clipped
                        if _matches(n, COLLECTIVES)) * 1e-9
        for n, a, b in clipped:
            by_op[n] = by_op.get(n, 0.0) + (b - a) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(n, s, s + d) for n, s, d in events["host"] if n != WINDOW_SPAN]
    idle = [[_attribute(host, a, b), (b - a) * 1e-9] for a, b in gaps[:top]]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy,
            "kernel_s": kernel, "collective_s": coll,
            "device_ops": [[n, s] for n, s in ops], "idle_gaps": idle}


def _attribute(host, a, b) -> str:
    """The host event that overlapped the gap ``[a, b)`` most."""
    overlap: dict[str, float] = {}
    for n, s, e in host:
        x = min(e, b) - max(s, a)
        if x > 0:
            overlap[n] = overlap.get(n, 0.0) + x
    if not overlap:
        return "host: no traced event"
    return max(overlap.items(), key=lambda kv: kv[1])[0]
