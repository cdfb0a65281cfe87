"""The harness's sharded branch on the CPU: a planned two-hop deployment
served on a 2x2 mesh of four emulated devices, at a tiny size, with the
chip check skipped.  A sound run is correct and the control is not; hop
programs in the wrong order, a hop's tables emptied, the exchange between
hop chips left out and an answer altered where it is produced each come
out as not correct; a plan of another hop count is refused before serving.

Every case runs in one child process, which has the four devices
(``--xla_force_host_platform_device_count=4``) that the test process,
already started on one, cannot get.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 211
CASES = ("sound", "reversed", "emptied", "no_exchange", "altered",
         "one_hop", "three_hops")


def _cell(stage_budget=None):
    from bench.tests.tiny import tiny_cell

    cell = tiny_cell("dt-2hop-mesh", "large")
    cell["chips"] = cell["config"]["chips"]
    cell["end_to_end"] = ["setup_s", "pkts_per_s"]
    if stage_budget is not None:
        cell["config"]["executor"]["stage_budget"] = stage_budget
    return cell


def _stages(cell) -> int:
    from bench import run
    from repro.core.translator import translate

    (m,) = run.fit_models(cell["config"], SEED)
    return len(translate(m.model).stages())


def _case(name: str, stages: int) -> dict:
    """Run one case in this (child) process; returns what the test reads."""
    import dataclasses
    from unittest import mock

    import jax

    from bench import run
    from repro.core import distributed_plane
    from repro.core.plane import empty_program
    from repro.runtime import ShardedExecutor
    from repro.runtime.facade import DataplaneRuntime

    budget = {"one_hop": stages, "three_hops": math.ceil(stages / 3)}.get(
        name, math.ceil(stages / 2))
    cell = _cell(budget)
    real_build = distributed_plane.build_device_programs
    real_classify = ShardedExecutor.classify
    real_run_host = DataplaneRuntime.run_host
    seen = {"hops": [], "sharded_calls": 0, "run_host": 0}

    def build(prog, plan, profile):
        names, dps = real_build(prog, plan, profile)
        seen["hops"] = list(names)
        if name == "reversed":
            dps = dps[::-1]
        if name == "emptied":
            dps = [dps[0], empty_program(profile)] + dps[2:]
        return names, dps

    def classify(self, batch):
        seen["sharded_calls"] += 1
        return real_classify(self, batch)

    def run_host(self, batch):
        out = real_run_host(self, batch)
        seen["run_host"] += 1
        if name == "altered" and seen["run_host"] == 10:
            rslt = out.rslt.copy()
            rslt[0] += 1
            out = dataclasses.replace(out, rslt=rslt)
        return out

    patches = [
        mock.patch.object(distributed_plane, "build_device_programs", build),
        mock.patch.object(ShardedExecutor, "classify", classify),
        mock.patch.object(DataplaneRuntime, "run_host", run_host),
    ]
    if name == "no_exchange":
        patches.append(mock.patch.object(
            jax.lax, "ppermute", lambda x, axis_name, perm: x))
    for p in patches:
        p.start()
    try:
        out = run.run_cell(cell, SEED, 1.0, False, jax.devices(),
                           control_bits=4 if name == "sound" else None)
    except ValueError as e:
        return {"refused": str(e), **seen}
    finally:
        for p in patches:
            p.stop()
    return {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": sorted(out["metrics"]),
            "checks": {k: c["value"] for k, c in out["checks"].items()},
            **seen}


def main() -> None:
    """Child process: every case, one JSON line."""
    from bench import peaks

    peaks.PEAKS["cpu"] = dict(peaks.PEAKS["TPU v5 lite"])
    stages = _stages(_cell())
    print(json.dumps({"stages": stages,
                      **{c: _case(c, stages) for c in CASES}}))


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run(
        [sys.executable, "-c",
         "from bench.tests.test_bench_mesh import main; main()"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_plans_two_hops_serves_the_mesh_and_the_control_fails(
        cases):
    got = cases["sound"]
    assert len(got["hops"]) == 2, got["hops"]
    assert got["sharded_calls"] > 0
    assert got["correct"], got["checks"]
    assert got["checks"]["wrong_answers"] == 0
    assert got["checks"]["lost_requests"] == 0
    assert got["checks"]["reference_vs_learner"] == 0
    assert got["checks"]["control_wrong_answers"] > 0
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["metrics"] == ["pkts_per_s", "setup_s"]


@pytest.mark.parametrize("fault", ["reversed", "emptied", "no_exchange",
                                   "altered"])
def test_broken_mesh_is_not_correct(cases, fault):
    got = cases[fault]
    assert len(got["hops"]) == 2 and got["sharded_calls"] > 0
    assert not got["correct"]
    assert got["checks"]["wrong_answers"] > 0
    if fault == "altered":
        assert got["checks"]["wrong_answers"] == 1


@pytest.mark.parametrize("case,hops", [("one_hop", 1), ("three_hops", 3)])
def test_plan_of_another_hop_count_is_refused(cases, case, hops):
    got = cases[case]
    assert len(got["hops"]) == hops
    assert "hops" in got["refused"]
    assert got["sharded_calls"] == 0 and got["run_host"] == 0
