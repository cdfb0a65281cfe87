"""Chip smoke: drive ACORN's main classify path once on a TPU, at paper width.

One chip (the default) runs the path a user of the serving front takes:

1. the device check — a TPU or nothing, no CPU fallback;
2. workload 1 (``nsl-kdd`` DT) and workload 2 (``nsl-kdd`` SVM) fitted from a
   fixed seed and installed into one ``ZooServer(PlaneProfile())``, the
   paper-width default profile, with the kernel path left to auto-select;
3. the kernel check — the compiled classify holds the fused Pallas kernel;
4. a few hundred ragged requests, DT and SVM mixed by MID, through
   ``ContinuousZooServer`` with ``SizeOrDeadlinePolicy``, driven open-loop;
5. every answer checked bit for bit against ``mode="ref"`` on the same chip
   and against the model's own ``predict`` on the quantized features;
6. a hot swap of workload 3 (``unsw-iot`` RF) into the tree slot, with no
   new trace, then the same serving and checks again.

``--chips 4`` runs only the multi-hop path across four chips: workload 1
planned across fat-tree hops, served by ``ShardedExecutor`` at 2x2 and 1x4
and by the 4x1 pipeline, and compared with ``SequentialPathExecutor`` on one
device and with ``predict``.

Compile times, request counts and latencies printed on the way are smoke
output, not metrics.  The last line of a passing run is one JSON object
naming the device; a failing run raises before printing it.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
N_REQUESTS = 300       # per serving round
MAX_REQ_PACKETS = 8    # ragged requests of 1..8 packets
RATE_RPS = 1000.0      # offered open-loop load per round


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")
    log(f"ok: {what}")


def require_tpu(n_chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} chips, JAX found {len(devices)}")
    log(f"device: {devices[0].device_kind} x {len(devices)} "
        f"({devices[0].platform})")
    return devices


# --------------------------------------------------------------- one chip
def _requests(rng, pools):
    """Ragged requests: each picks one (mid, features, model) pool and
    1..MAX_REQ_PACKETS random rows of its test set."""
    reqs = []
    for _ in range(N_REQUESTS):
        mid, X, model = pools[rng.integers(len(pools))]
        rows = rng.integers(0, X.shape[0], rng.integers(1, MAX_REQ_PACKETS + 1))
        reqs.append((mid, X[rows], model))
    return reqs


async def _serve(srv, reqs):
    answers = [None] * len(reqs)

    async def submit(i):
        mid, X, _ = reqs[i]
        answers[i] = (await srv.submit(X, mid=mid, vid=0)).rslt

    from repro.serving.loadgen import open_loop
    report = await open_loop(submit, rate_rps=RATE_RPS,
                             n_requests=len(reqs), seed=SEED)
    return report, answers


def _check_round(name, report, answers, reqs, zoo_ref, max_features):
    log(f"{name}: {report.requests} requests, {report.errors} errors, "
        f"p50 {report.p50_ms} ms, p99 {report.p99_ms} ms "
        "(smoke output, not a metric)")
    check(report.errors == 0, f"{name}: zero open-loop errors")
    n = sum(len(X) for _, X, _ in reqs)
    feats = np.zeros((n, max_features), np.int32)
    mids = np.zeros((n,), np.int32)
    lo = 0
    for mid, X, _ in reqs:
        feats[lo:lo + len(X), :X.shape[1]] = X
        mids[lo:lo + len(X)] = mid
        lo += len(X)
    got = np.concatenate(answers)
    ref = zoo_ref.runtime.results(zoo_ref.make_request(feats, mid=mids))
    want = np.concatenate([model.predict(X) for _, X, model in reqs])
    check(np.array_equal(got, ref),
          f"{name}: all {n} answers bit-identical to mode='ref' on the chip")
    check(np.array_equal(got, want),
          f"{name}: all {n} answers bit-identical to the models' predict")


async def _serve_and_swap(zoo, zoo_ref, pools1, pools2, rf):
    from repro.runtime import SizeOrDeadlinePolicy
    from repro.serving import ContinuousZooServer

    rng = np.random.default_rng(SEED)
    prof = zoo.profile
    policy = SizeOrDeadlinePolicy(max_batch=64, max_wait_us=2_000)
    t0 = time.perf_counter()
    async with ContinuousZooServer(zoo, policy=policy) as srv:
        log(f"warmed buckets {srv.warmed_buckets} in "
            f"{time.perf_counter() - t0:.1f} s (compile included)")
        reqs = _requests(rng, pools1)
        report, answers = await _serve(srv, reqs)
        _check_round("dt+svm", report, answers, reqs, zoo_ref,
                     prof.max_features)
        traces = zoo.cache_size()
        check(traces == len(srv.warmed_buckets),
              f"one trace per admission bucket before the swap ({traces})")

        t0 = time.perf_counter()
        await srv.drain()
        srv.install(rf.model, vid=0, tag="iot-rf")
        srv.release()
        zoo_ref.install(rf.model, vid=0)
        log(f"hot swap to the unsw-iot RF in "
            f"{time.perf_counter() - t0:.2f} s")
        reqs = _requests(rng, pools2)
        report, answers = await _serve(srv, reqs)
        _check_round("rf+svm", report, answers, reqs, zoo_ref,
                     prof.max_features)
        check(zoo.cache_size() == traces,
              f"the swap added no trace ({zoo.cache_size()} before and "
              "after: one per bucket)")


def smoke_one_chip() -> None:
    from benchmarks.common import FEATURE_BUDGET, fit_workload
    from repro.core.plane import PlaneProfile
    from repro.core.translator import MID_DT, MID_RF, MID_SVM
    from repro.kernels import ops
    from repro.serving import ZooServer

    nf = FEATURE_BUDGET["acorn"]
    t0 = time.perf_counter()
    dt = fit_workload("nsl-kdd", "dt", nf, seed=SEED)
    svm = fit_workload("nsl-kdd", "svm", nf, seed=SEED)
    rf = fit_workload("unsw-iot", "rf", nf, seed=SEED)
    log(f"fitted workloads 1-3 in {time.perf_counter() - t0:.1f} s")

    prof = PlaneProfile()
    zoo = ZooServer(prof)                   # kernel path auto-selected
    zoo_ref = ZooServer(prof, mode="ref")   # plain XLA, same chip
    for z in (zoo, zoo_ref):
        z.install(dt.model, vid=0, tag="ids-dt")
        z.install(svm.model, vid=0, tag="ids-svm")

    probe = zoo.make_request(dt.Xte[:64], mid=MID_DT)
    t0 = time.perf_counter()
    got = zoo.runtime.run_host(probe).rslt
    log(f"first classify (bucket 64) compiled and ran in "
        f"{time.perf_counter() - t0:.1f} s")
    check(np.array_equal(got, dt.model.predict(dt.Xte[:64])),
          "first classify matches the DT's predict")
    hlo = zoo.engine.lower(zoo.packed, probe).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled classify holds the Pallas kernel (tpu_custom_call)")
    launches = ops.count_pallas_launches(zoo.engine.classify, zoo.packed,
                                         probe)
    check(launches == 1, f"one pallas_call per classify ({launches})")

    pools1 = [(MID_DT, dt.Xte, dt.model), (MID_SVM, svm.Xte, svm.model)]
    pools2 = [(MID_RF, rf.Xte, rf.model), (MID_SVM, svm.Xte, svm.model)]
    asyncio.run(_serve_and_swap(zoo, zoo_ref, pools1, pools2, rf))


# ------------------------------------------------------------- four chips
def _placement(name, ex) -> None:
    """Print which device holds each switch's tables; fail if the mesh does
    not span four distinct devices."""
    mesh_ids = [[d.id for d in row] for row in ex.mesh.devices]
    log(f"{name}: mesh (switch x port) device ids {mesh_ids}")
    held: dict[int, set[int]] = {}
    for shard in ex.packed.dt_cv.addressable_shards:
        held.setdefault(shard.index[0].start or 0, set()).add(shard.device.id)
    for sw, ids in sorted(held.items()):
        log(f"{name}: switch {sw} tables on devices {sorted(ids)}")
    used = set().union(*held.values())
    check(len(used) == 4 and len({i for r in mesh_ids for i in r}) == 4,
          f"{name}: program shards span 4 distinct devices")


def smoke_four_chips() -> None:
    from benchmarks.common import FEATURE_BUDGET, fit_workload
    from repro.core.distributed_plane import build_device_programs
    from repro.core.plane import PlaneProfile
    from repro.core.planner import DeviceModel, plan_program
    from repro.core.topology import fat_tree
    from repro.core.translator import translate
    from repro.runtime import (
        DataplaneRuntime,
        PipelinedExecutor,
        SequentialPathExecutor,
        ShardedExecutor,
    )
    from repro.serving import ZooServer

    dt = fit_workload("nsl-kdd", "dt", FEATURE_BUDGET["acorn"], seed=SEED)
    prog = translate(dt.model)
    prof = PlaneProfile()
    net = fat_tree(4)
    hosts = net.hosts()
    n_stages = len(prog.stages())

    def hops(n: int):
        """Plan the DT across ``n`` switches of the fat-tree path."""
        dev = DeviceModel(n_stages=math.ceil(n_stages / n))
        plan = plan_program(prog, net, hosts[0], hosts[-1],
                            default_device=dev, solver="dp")
        names, dps = build_device_programs(prog, plan, prof)
        check(len(dps) == n, f"{n_stages} stages planned onto {n} hops "
              f"{names}")
        return dps

    by_hops = {n: hops(n) for n in (1, 2, 4)}
    X = dt.Xte
    want = dt.model.predict(X)
    pb = ZooServer(prof, mode="ref").make_request(X, mid=prog.mid)
    C = prof.max_classes

    t0 = time.perf_counter()
    ref = DataplaneRuntime(SequentialPathExecutor(by_hops[4], n_classes=C))
    seq = ref.results(pb)
    log(f"4-hop SequentialPathExecutor on device "
        f"{jax.devices()[0].id}: {time.perf_counter() - t0:.1f} s "
        "(compile included)")
    check(np.array_equal(seq, want),
          f"sequential path: all {len(X)} answers match the DT's predict")

    layouts = [
        ("2x2 sharded", ShardedExecutor(by_hops[2], n_classes=C, n_ports=2)),
        ("1x4 sharded", ShardedExecutor(by_hops[1], n_classes=C, n_ports=4)),
        ("4x1 pipeline", PipelinedExecutor(by_hops[4], n_classes=C)),
    ]
    for name, ex in layouts:
        _placement(name, ex)
        t0 = time.perf_counter()
        got = DataplaneRuntime(ex).results(pb)
        log(f"{name}: {time.perf_counter() - t0:.1f} s (compile included)")
        check(np.array_equal(got, seq),
              f"{name}: bit-identical to the sequential path")
        check(np.array_equal(got, want),
              f"{name}: bit-identical to the DT's predict")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: only the "
                         "multi-hop mesh executors across four chips")
    args = ap.parse_args()
    devices = require_tpu(args.chips)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.common import use_compile_cache

    use_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 4:
        smoke_four_chips()
    else:
        smoke_one_chip()
    log(f"passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
