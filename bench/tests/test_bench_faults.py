"""The comparison that decides ``correct``, driven on the CPU at a tiny
size with the chip check skipped: sound runs pass, the control and each
fault the single-chip cells can have come out as not correct."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from bench import peaks, run
from bench.tests.tiny import tiny_cell

SEED = 2**31 + 101


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def _run(cell, **kw):
    return run.run_cell(cell, SEED, 1.0, False, jax.devices(), **kw)


def test_sound_run_passes_and_control_fails():
    out = _run(tiny_cell("ids-v1", "mixed-small"), control_bits=4)
    checks = out["checks"]
    assert out["correct"] and checks["wrong_answers"]["value"] == 0
    assert checks["lost_requests"]["value"] == 0
    assert checks["reference_vs_learner"]["value"] == 0
    assert checks["control_wrong_answers"]["value"] > 0
    assert out["attempted"] == 200 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "p50_ms", "pkts_per_s"}
    assert list(out)[-1] == "checks"


def test_answer_altered_where_produced_fails(monkeypatch):
    from repro.runtime.facade import DataplaneRuntime

    real = DataplaneRuntime.run_host
    calls = {"n": 0}

    def altered(self, batch):
        out = real(self, batch)
        calls["n"] += 1
        if calls["n"] == 40:      # one dispatch inside the window
            rslt = out.rslt.copy()
            rslt[0] += 1
            out = dataclasses.replace(out, rslt=rslt)
        return out

    monkeypatch.setattr(DataplaneRuntime, "run_host", altered)
    out = _run(tiny_cell("ids-v1", "mixed-small"))
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] == 1


def test_answer_that_never_comes_fails(monkeypatch):
    from repro.serving.async_server import AsyncZooServer

    real = AsyncZooServer._classify_flat
    calls = {"n": 0}

    def dropped(self, flat):
        calls["n"] += 1
        if calls["n"] == 40:
            raise RuntimeError("dispatch lost")
        return real(self, flat)

    monkeypatch.setattr(AsyncZooServer, "_classify_flat", dropped)
    out = _run(tiny_cell("ids-v1", "mixed-small"))
    assert not out["correct"]
    assert out["checks"]["lost_requests"]["value"] >= 1


def test_live_install_that_leaves_the_slot_unchanged_fails(monkeypatch):
    from repro.serving.async_server import AsyncZooServer

    cell = tiny_cell("zoo8", "zipf-large",
                     swaps={"every_s": 0.2, "pipeline": "tree", "vid": 7})
    sound = _run(cell)
    assert sound["correct"] and "swap_ms" in sound["metrics"]
    real = AsyncZooServer.install
    calls = {"n": 0}

    def unchanged(self, model, *, vid, tag=""):
        calls["n"] += 1
        if calls["n"] > 1:        # the warm-up install goes through
            return vid
        return real(self, model, vid=vid, tag=tag)

    monkeypatch.setattr(AsyncZooServer, "install", unchanged)
    out = _run(cell)
    assert calls["n"] >= 2
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("misread", ["leaf_label", "svm_pair_order"])
def test_reference_that_misreads_a_model_fails(monkeypatch, misread):
    """The reference's reading of a fitted model is held against the
    learner's own predict: a leaf label or a one-vs-one pair read wrongly
    comes out as not correct, although the program installs what the
    learner made."""
    from bench import reference

    real = reference.describe

    def misread_desc(model):
        d = real(model)
        if misread == "leaf_label" and d["kind"] == "dt":
            t = d["trees"][0]
            leaf = int(np.flatnonzero(t["feature"] < 0)[0])
            t["label"] = t["label"].copy()
            t["label"][leaf] = 1 - t["label"][leaf]
        if misread == "svm_pair_order" and d["kind"] == "svm":
            d["pairs"] = [(j, i) for i, j in d["pairs"]]
        return d

    monkeypatch.setattr(reference, "describe", misread_desc)
    out = _run(tiny_cell("ids-v1", "mixed-small"))
    assert not out["correct"]
    assert out["checks"]["reference_vs_learner"]["value"] > 0
