"""The reader of the kernel's grid fill (``bench/metrics/grid_fill.sat.py``):
on hand-made ``acorn.pad`` spans with and without ``grid_rows``, and
through a traced run of the tiny zoo8 cell on the CPU.  Spans without
``grid_rows``, as a program that does not report it records them, leave
the metric out; ``rows``, ``bucket`` and ``pad_share`` read as before."""
from __future__ import annotations

import jax
import pytest

from bench import peaks, run
from bench.tests.tiny import tiny_cell

US = 1000          # ns in a microsecond


def _spans(grid=None):
    """Two dispatches' cut and pad spans (rows 3 of a 4-row bucket, then 5
    of 8), with ``grid_rows`` from ``grid`` by dispatch where it is given."""
    out = []
    for k, (t0, rows, bucket) in enumerate([(0, 3, 4), (5, 5, 8)]):
        meta = {"dispatch": k, "rows": rows, "bucket": bucket}
        if grid is not None:
            meta["grid_rows"] = grid[k]
        out += [["acorn.coalesce", t0 * US, 1 * US, {"dispatch": k}],
                ["acorn.pad", (t0 + 2) * US, 1 * US, meta]]
    return out


def _ctx(spans_):
    return {"trace": {}, "spans": spans_}


@pytest.mark.parametrize("grid,want", [
    (None, None),                     # a program without grid_rows
    ({0: 16, 1: 16}, 100 * 8 / 32),   # both dispatches one 16-row block
    ({0: 4, 1: 8}, 100 * 8 / 12),     # the grid runs just the buckets
])
def test_grid_fill_on_hand_made_spans(grid, want):
    """``grid_fill.sat`` is the real rows over the grid's rows; rows,
    bucket and ``pad_share`` read as before, with or without them."""
    spans_ = _spans(grid)
    got = run.reader("grid_fill.sat")(_ctx(spans_))
    assert (got is None) if want is None else got[0] == pytest.approx(want)
    assert run.reader("grid_fill.sat")(_ctx([])) is None
    assert run.reader("grid_fill.sat")({"trace": None}) is None
    for m in ("pad_share.ol", "pad_share.sat"):
        assert run.reader(m)(_ctx(spans_))[0] == pytest.approx(100 * 4 / 12)
    pads = [m for n, _, _, m in spans_ if n == "acorn.pad"]
    assert [(m["rows"], m["bucket"]) for m in pads] == [(3, 4), (5, 8)]


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def test_traced_tiny_zoo8_run_reports_grid_fill(cpu_peaks, own_trace_dir):
    """A traced run of the tiny zoo8 cell through ``run.run_cell``: every
    dispatch's ``acorn.pad`` carries ``grid_rows``, and the grid runs at
    least the real rows."""
    cell = tiny_cell("zoo8", "zipf-large")
    cell["per_layer"] = ["grid_fill.sat", "pad_share.sat"]
    out = run.run_cell(cell, 2**31 + 11, 1.0, True, jax.devices())
    assert out["correct"]
    assert set(out["metrics"]) == {"grid_fill.sat", "pad_share.sat"}
    assert 0 < out["metrics"]["grid_fill.sat"]["value"] <= 100
