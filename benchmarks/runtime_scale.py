"""Runtime scaling: ``ShardedExecutor`` throughput vs port count.

Weak scaling along the data-parallel port axis: the per-port batch is fixed
at ``B_PORT`` and total traffic is ``B_PORT * P`` for ``P ∈ {1, 2, 4, 8}``
port lanes — the "many ingress ports feeding one line-rate switch" model.
Reported per row: total batch, best-of-``REPS`` wall time per classified
batch, packets/sec, and the throughput speedup vs the 1-port lane.

Acceptance pin (ISSUE 4): throughput scales ≥ 1.5x from 1 → 4 ports on an
8-device host.  The emulated devices share the host's cores, so the floor is
asserted only where 4 lanes can actually run in parallel
(``os.cpu_count() >= 4``); below that the rows still print, with a comment
naming the host's parallel ceiling (a 2-core box tops out around the
1->2-core speedup of a plain matmul, ~1.3x).  Override the floor with
``RUNTIME_SCALE_MIN_SPEEDUP``; ``RUNTIME_SCALE_SMOKE=1`` shrinks the batch,
drops to 2 timing reps, and skips the assertion — the CI smoke row.

It measures in the calling process, over whatever ``jax.devices()`` holds:
a port count above the device count is skipped with a comment row.  One
process owns the devices (a child process could not reach a chip its parent
already holds), so the 8-lane CPU emulation comes from the caller's
environment:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.run --only runtime_scale
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import fit_workload
from repro.core.packets import PacketBatch
from repro.core.plane import PlaneProfile, SwitchEngine
from repro.core.translator import translate
from repro.runtime import DataplaneRuntime, ShardedExecutor

PORTS = (1, 2, 4, 8)
HEADER = "runtime_scale,ports,batch,ms_per_batch,kpps,speedup_vs_1port"


def run() -> list[str]:
    smoke = os.environ.get("RUNTIME_SCALE_SMOKE") == "1"
    b_port = 512 if smoke else 2048
    reps = 2 if smoke else 5

    f = fit_workload("satdap", "dt", 36)
    prof = PlaneProfile(max_features=36, max_trees=4, max_layers=12,
                        max_entries_per_layer=128, max_leaves=128,
                        max_classes=8, max_hyperplanes=8)
    eng = SwitchEngine(prof)
    packed = eng.install(eng.empty(), translate(f.model))
    n_dev = len(jax.devices())

    out = [HEADER]
    speedups = {}
    base_kpps = None
    for P in PORTS:
        if P > n_dev:
            out.append(f"# runtime_scale: skipping P={P} ({n_dev} devices)")
            continue
        rt = DataplaneRuntime(ShardedExecutor(
            [packed], n_classes=prof.max_classes, n_ports=P, n_micro=1))
        B = b_port * P
        X = np.tile(f.Xte, (B // f.Xte.shape[0] + 1, 1))[:B]
        pb = PacketBatch.make_request(
            X, mid=0, max_features=36, n_trees=prof.max_trees,
            n_hyperplanes=prof.max_hyperplanes)
        res = rt.run(pb)
        res.rslt.block_until_ready()          # compile + warm
        assert (np.asarray(res.rslt) == f.model.predict(X)).all(), \
            "sharded answers must match the model"
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            rt.run(pb).rslt.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        kpps = B / best / 1e3
        if base_kpps is None:
            base_kpps = kpps
        speedups[P] = kpps / base_kpps
        out.append(f"runtime_scale,{P},{B},{best*1e3:.2f},{kpps:.1f},"
                   f"{speedups[P]:.2f}")

    floor = float(os.environ.get("RUNTIME_SCALE_MIN_SPEEDUP", "1.5"))
    cores = os.cpu_count() or 1
    if smoke or 4 not in speedups:
        pass
    elif cores < 4:
        out.append(f"# runtime_scale: host has {cores} cores — 4 port lanes "
                   f"cannot run in parallel, speedup floor {floor} not "
                   f"asserted (measured 1->4: {speedups[4]:.2f}x)")
    elif speedups[4] < floor:
        raise AssertionError(
            f"1 -> 4 port throughput speedup {speedups[4]:.2f} < {floor} "
            "(set RUNTIME_SCALE_MIN_SPEEDUP to relax on constrained hosts)")
    return out


if __name__ == "__main__":
    for line in run():
        print(line)
