"""Trace reduction, on hand-made events and on traces recorded on a v5e.

The ``data/trace_*.json.gz`` files are ``trace.load`` output of traced runs
on one TPU v5e chip (``ids-v1.mixed-small`` and ``zoo8.zipf-large``), cut
to a short stretch of their windows."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ("classify_fused",)


def _events(device, host=()):
    return {"device": {0: [list(e) for e in device]},
            "host": [[trace.WINDOW_SPAN, 0, 100]] + [list(h) for h in host]}


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) \
        == [(0, 4), (5, 7), (9, 9)]


def test_op_name_drops_the_hlo_text():
    hlo = ("%classify_fused_pallas_v.1 = (u32[256,8]{1,0}) "
           "custom-call(u32[256,8]{1,0} %pad.8), custom_call_target=\"x\"")
    assert trace.op_name(hlo) == "classify_fused_pallas_v.1"
    assert trace.op_name("copy.3") == "copy.3"


def test_reduce_by_hand():
    dev = [("classify_fused_pallas_v.1", 10, 20),     # 10..30
           ("copy.2", 25, 10),                        # 25..35, overlaps
           ("collective-permute.1", 50, 5),           # 50..55
           ("fusion", 95, 20)]                        # clipped to 95..100
    host = [("PjitFunction(_classify_impl)", 30, 25),  # covers gap 35..50
            ("np.asarray(jax.Array)", 55, 45)]         # covers gap 55..95
    r = trace.reduce(_events(dev, host), KERNEL)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"][0] == pytest.approx((25 + 5 + 5) * 1e-9)
    assert r["kernel_s"][0] == pytest.approx(20e-9)
    assert r["collective_s"][0] == pytest.approx(5e-9)
    gaps = [(name, round(s * 1e9)) for name, s in r["idle_gaps"]]
    assert gaps == [("np.asarray(jax.Array)", 40),
                    ("PjitFunction(_classify_impl)", 15),
                    ("host: no traced event", 10)]
    assert r["device_ops"][0][0] == "classify_fused_pallas_v.1"


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce({"device": {0: []}, "host": []})


def _brute_busy(events, lo, hi, step=1000):
    """Busy time by sampling the window every ``step`` ns."""
    t = np.arange(lo, hi, step, dtype=np.float64)
    busy = np.zeros(t.shape, bool)
    for _, s, d in events:
        busy |= (t >= s) & (t < s + d)
    return busy.sum() * step * 1e-9


@pytest.mark.parametrize("name", ["trace_ids_v1", "trace_zoo8"])
def test_reduce_a_trace_recorded_on_the_chip(name):
    ev = trace.read(str(DATA / f"{name}.json.gz"))
    r = trace.reduce(ev, KERNEL)
    lo, hi = trace.window(ev)
    busy, kernel = r["busy_s"][0], r["kernel_s"][0]
    assert busy == pytest.approx(_brute_busy(ev["device"][0], lo, hi),
                                 abs=2e-6 * len(ev["device"][0]))
    assert 0 < kernel <= busy <= r["window_s"]
    assert r["collective_s"][0] == 0.0           # one chip: no exchange
    assert r["device_ops"][0][0].startswith("classify_fused")
    idle = 1 - busy / r["window_s"]
    assert 0 <= idle < 1
    assert all(s > 0 for _, s in r["idle_gaps"])
    assert len(r["idle_gaps"]) <= 10 and len(r["device_ops"]) <= 10


def test_recorded_traces_read_as_their_cells_do():
    """Open-loop small batches leave the chip mostly idle; the V=8 zoo at
    saturation keeps it busy with the fused kernel."""
    ids = trace.reduce(trace.read(str(DATA / "trace_ids_v1.json.gz")), KERNEL)
    zoo = trace.reduce(trace.read(str(DATA / "trace_zoo8.json.gz")), KERNEL)
    assert ids["busy_s"][0] / ids["window_s"] < 0.2
    assert zoo["kernel_s"][0] / zoo["window_s"] > 0.8
