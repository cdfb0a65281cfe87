"""The readers of the program's own spans (``bench/spans.py`` and the
``bench/metrics`` files that use it): on hand-made spans of two slot
threads, on traces recorded on a v5e, and through a traced run of tiny
cells on the CPU.  A trace without program spans leaves every reader
empty, and ``trace.reduce`` gives it the reduction pinned in
``data/reduce_no_spans.json``."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import pytest

from bench import peaks, run, spans, trace
from bench.tests.tiny import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ("classify_fused",)
OL = ["slot_wait_ms", "launch_ms.ol", "fetch_ms.ol", "pad_share.ol"]
SAT = ["fetch_ms.sat", "pad_share.sat", "drain_ms", "install_build_ms"]
NEW = OL + SAT
US = 1000          # ns in a microsecond


def _dispatch(k, t0, rows, bucket, slot_ns=1 * US):
    """One dispatch's spans from its cut at ``t0`` (µs): a 1-µs pad and
    launch, a 12-µs fetch that waits for the device."""
    steps = [("acorn.pad", 1), ("acorn.launch", 1), ("acorn.fetch", 12)]
    out = [["acorn.coalesce", t0 * US, 1 * US, {"dispatch": k}]]
    t = (t0 + 1) * US + slot_ns
    for name, d in steps:
        meta = {"dispatch": k}
        if name == "acorn.pad":
            meta.update(rows=rows, bucket=bucket)
        out.append([name, t, d * US, meta])
        t += d * US
    return out


# two slot threads: dispatch 1 is cut while dispatch 0 waits on the device
# and waits 3 µs for a slot; then a hold with a 40-µs drain and an install
SPANS = sorted(
    _dispatch(0, 0, rows=3, bucket=4)
    + _dispatch(1, 5, rows=5, bucket=8, slot_ns=3 * US)
    + [["acorn.install.translate", 30 * US, 4 * US, {"vid": 7}],
       ["acorn.install.tables", 34 * US, 6 * US, {"vid": 7}],
       ["acorn.install.write", 40 * US, 2 * US, {"vid": 7}],
       ["acorn.release", 43 * US, 1 * US,
        {"hold": 0, "drain_us": 40, "held_us": 43}]],
    key=lambda s: s[1])


def _ctx(spans_):
    return {"trace": {}, "spans": spans_}


def test_two_overlapping_slot_threads_by_dispatch():
    assert spans.per_dispatch(SPANS, "acorn.fetch") == {
        0: pytest.approx(12e-6), 1: pytest.approx(12e-6)}
    assert spans.per_dispatch(SPANS, "acorn.install.write") == {}
    assert spans.slot_waits(SPANS) == [pytest.approx(1e-6),
                                       pytest.approx(3e-6)]


@pytest.mark.parametrize("metric,want", [
    ("slot_wait_ms", 2e-3), ("launch_ms.ol", 1e-3), ("fetch_ms.ol", 12e-3),
    ("fetch_ms.sat", 12e-3), ("pad_share.ol", 100 * 4 / 12),
    ("pad_share.sat", 100 * 4 / 12), ("drain_ms", 40e-3),
    ("install_build_ms", 10e-3)])
def test_readers_on_hand_made_spans(metric, want):
    value, _ = run.reader(metric)(_ctx(SPANS))
    assert value == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_readers_without_program_spans_return_nothing(metric):
    assert run.reader(metric)(_ctx([])) is None
    assert run.reader(metric)({"trace": None}) is None


@pytest.mark.parametrize("host,want", [
    # slot 0 fetches over most of the gap: the span outweighs the copy in it
    ([["acorn.fetch", 18, 30], ["np.asarray(jax.Array)", 20, 26],
      ["acorn.pad", 40, 15]], "acorn.fetch"),
    # slot 0 launches and slot 1 fetches, each in the runtime's host work
    # of one name: summed over both threads, that event outweighs either
    # span, as runtime events do on the chip
    ([["acorn.launch", 18, 24], ["Transpose", 20, 22],
      ["acorn.fetch", 38, 22], ["Transpose", 38, 22]], "Transpose")])
def test_reduce_names_the_host_event_overlapping_a_gap_most(host, want):
    """Today's rule, unchanged by the spans: a gap goes to the host event
    name that overlaps it most, summed over threads, be it a program span
    or a runtime event (preferring ``acorn.*`` needs ``bench/trace.py``)."""
    events = {"device": {0: [["classify_fused_pallas_v.1", 0, 20],
                             ["classify_fused_pallas_v.1", 60, 20]]},
              "host": [[trace.WINDOW_SPAN, 0, 100]] + host}
    r = trace.reduce(events, KERNEL)
    assert r["idle_gaps"][0] == [want, pytest.approx(40e-9)]


@pytest.mark.parametrize("name", ["trace_ids_v1", "trace_zoo8"])
def test_traces_without_program_spans_reduce_as_before(name):
    ev = trace.read(str(DATA / f"{name}.json.gz"))
    got = json.loads(json.dumps(trace.reduce(ev, KERNEL)))
    want = json.loads((DATA / "reduce_no_spans.json").read_text())[name]
    assert got == want
    assert not any(n.startswith(spans.PREFIX) for n, _, _ in ev["host"])


@pytest.mark.parametrize("name,metrics", [
    ("trace_ids_v1_spans", OL), ("trace_zoo8_spans", SAT)])
def test_readers_on_a_trace_recorded_on_the_chip(name, metrics):
    """A stretch of each cell's traced window on a v5e, with the program's
    spans (``spans.save``): every reader of the cell finds its value."""
    ev = trace.read(str(DATA / f"{name}.json.gz"))
    ctx = {"trace": trace.reduce(ev, KERNEL), "spans": ev["spans"]}
    for m in metrics:
        got = run.reader(m)(ctx)
        assert got is not None and got[0] >= 0, m


@pytest.mark.parametrize("name", ["trace_ids_v1_spans", "trace_zoo8_spans"])
def test_every_long_gap_lies_under_a_program_span(name):
    """The spans cover the host path: every idle gap of 5 ms or more in
    the chip's stretch overlaps one, so a rule that prefers program spans
    can name each of them (``reduce`` names the runtime event that
    overlapped most, which is often the jitted call inside
    ``acorn.launch``)."""
    ev = trace.read(str(DATA / f"{name}.json.gz"))
    lo, hi = trace.window(ev)
    busy = trace.union((a, b) for _, a, b in trace._clip(ev["device"][0],
                                                         lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= 5e6]
    assert gaps
    for a, b in gaps:
        assert any(s < b and s + d > a for _, s, d, _ in ev["spans"])


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


@pytest.mark.parametrize("config,traffic,metrics", [
    ("ids-v1", "mixed-small", OL),
    ("zoo8", "zipf-large", SAT)])
def test_traced_tiny_run_reports_the_span_metrics(cpu_peaks, config, traffic,
                                                   metrics):
    """A traced run through ``run.run_cell``: the readers find the
    program's spans in the trace it recorded under ``.bench_trace``."""
    cell = tiny_cell(config, traffic)
    if traffic == "zipf-large":
        cell["traffic"]["swaps"] = {"every_s": 0.2, "pipeline": "tree",
                                    "vid": 7}
    cell["per_layer"] = metrics
    out = run.run_cell(cell, 2**31 + 7, 1.0, True, jax.devices())
    assert out["correct"]
    assert set(out["metrics"]) == set(metrics)
    for m in metrics:
        assert out["metrics"][m]["value"] >= 0, m
