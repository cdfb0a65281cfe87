"""Pluggable classify executors behind one ``Executor`` protocol.

Each executor turns the same jit-once classify step (``_classify_impl``)
into a different execution substrate; all four are bit-identical on the same
zoo and traffic (pinned in ``tests/test_runtime.py``):

* ``SingleSwitchExecutor``   — one ``SwitchEngine``: the paper's single
  programmable switch, compile-once per batch shape.
* ``SequentialPathExecutor`` — partial programs applied in path order on one
  device: the functional reference every distributed layout must match.
* ``PipelinedExecutor``      — the GPipe-style shard_map ring over a
  ``("switch",)`` mesh axis (microbatch m enters switch 0 at step m, hops via
  ``ppermute``, exits switch n-1 at step m+n-1).  Compiled pipelines are
  memoized **per n_micro** — revisiting a previous microbatch count reuses
  its pipeline instead of rebuilding (the old ``PipelinedPlane`` kept one
  ``_run`` slot and thrashed it).
* ``ShardedExecutor``        — the 2D ``("switch", "port")`` mesh:
  pipeline-parallel along the path axis *and* data-parallel across ports.
  ``PackedProgram``/``ExecImage`` leaves are sharded over "switch" and
  replicated over "port"; ``PacketBatch`` leaves are sharded over "port" —
  each port lane serves its slice of the aggregate traffic, so throughput
  scales with port count at fixed latency (``benchmarks/runtime_scale.py``).

This module is the only place in ``src/repro`` that may construct a
``shard_map`` classify loop (pinned by ``tests/test_runtime.py``).
"""
from __future__ import annotations

import functools
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.packets import PacketBatch
from repro.core.plane import (
    PackedProgram,
    PlaneProfile,
    SwitchEngine,
    _classify_impl,
    fused_grid_rows,
)
from repro.core.translator import TableProgram

__all__ = [
    "Executor",
    "SingleSwitchExecutor",
    "SequentialPathExecutor",
    "PipelinedExecutor",
    "ShardedExecutor",
]


@runtime_checkable
class Executor(Protocol):
    """What ``DataplaneRuntime`` needs from an execution substrate.

    ``granularity`` is the batch divisibility the executor's layout requires
    (admission rounds buckets up to a multiple of it); ``classify`` maps a
    flat ``[B]`` batch to the classified flat batch in the same packet order;
    ``swap`` reprograms the plane(s) with zero retrace; ``cache_size`` counts
    compiled traces (the compile-once/bucketing assertions); ``grid_rows``
    is the rows the fused kernel's grid runs for a batch (numpy leaves), or
    None where the executor does not count them.
    """

    @property
    def granularity(self) -> int: ...

    def classify(self, batch: PacketBatch) -> PacketBatch: ...

    def swap(self, device_programs: list[PackedProgram]) -> None: ...

    def cache_size(self) -> int: ...

    def grid_rows(self, batch: PacketBatch) -> int | None: ...


class SingleSwitchExecutor:
    """One programmable switch — wraps the ``SwitchEngine`` jit cache.

    Also carries the control-plane write interface (``install``/``evict``)
    so a serving front can treat the executor as the owning plane.
    """

    granularity = 1

    def __init__(self, profile: PlaneProfile | None = None, *,
                 engine: SwitchEngine | None = None,
                 packed: PackedProgram | None = None,
                 mode: str | None = None, use_image: bool = True) -> None:
        if engine is None:
            if profile is None:
                raise ValueError("need a PlaneProfile or an existing engine")
            engine = SwitchEngine(profile, mode=mode, use_image=use_image)
        self.engine = engine
        self.packed = packed if packed is not None else engine.empty()

    @property
    def profile(self) -> PlaneProfile:
        return self.engine.profile

    def classify(self, batch: PacketBatch) -> PacketBatch:
        return self.engine.classify(self.packed, batch)

    def grid_rows(self, batch: PacketBatch) -> int:
        """Rows the fused kernel's grid runs for ``batch`` (numpy leaves)."""
        return fused_grid_rows(self.packed, batch)

    def install(self, program: TableProgram, *, vid: int | None = None,
                stages: set[int] | None = None) -> "SingleSwitchExecutor":
        self.packed = self.engine.install(self.packed, program, stages,
                                          vid=vid)
        return self

    def evict(self, *, vid: int, kind: str = "all") -> "SingleSwitchExecutor":
        self.packed = self.engine.evict(self.packed, vid=vid, kind=kind)
        return self

    def swap(self, device_programs) -> None:
        if isinstance(device_programs, PackedProgram):
            device_programs = [device_programs]
        (packed,) = device_programs
        self.packed = packed

    def cache_size(self) -> int:
        return self.engine.cache_size()


def _chain(programs: tuple[PackedProgram, ...], batch: PacketBatch, *,
           n_classes: int, mode: str | None) -> PacketBatch:
    for packed in programs:
        batch = _classify_impl(packed, batch, n_classes=n_classes, mode=mode)
    return batch


class SequentialPathExecutor:
    """Apply each hop's partial program in path order on one device.

    The functional reference for every distributed decomposition: status
    codes and SVM partial sums ride the batch between "hops" exactly as they
    ride the wire.  ``jit=False`` keeps the eager op-by-op semantics (used by
    the deprecated ``run_sequential`` shim and semantics tests); the default
    jits the whole chain into one trace.
    """

    granularity = 1

    def __init__(self, device_programs: list[PackedProgram], *,
                 n_classes: int, mode: str | None = None,
                 jit: bool = True) -> None:
        self.programs = tuple(device_programs)
        if not self.programs:
            raise ValueError("need at least one device program")
        impl = functools.partial(_chain, n_classes=n_classes, mode=mode)
        self._jit = jit
        self._fn = jax.jit(impl) if jit else impl

    def classify(self, batch: PacketBatch) -> PacketBatch:
        return self._fn(self.programs, batch)

    def swap(self, device_programs: list[PackedProgram]) -> None:
        if len(device_programs) != len(self.programs):
            raise ValueError("device count changed — replan instead")
        self.programs = tuple(device_programs)

    def cache_size(self) -> int:
        return self._fn._cache_size() if self._jit else 0

    def grid_rows(self, batch: PacketBatch) -> None:
        return None


class ShardedExecutor:
    """2D ``("switch", "port")`` mesh: pipeline the path, shard the traffic.

    Device layout (``n_switch * n_ports`` devices):

    * program state (``PackedProgram`` + its ``ExecImage``) is stacked on a
      leading switch axis, sharded ``P("switch")`` — replicated across the
      port axis (every port lane holds the full path's tables);
    * the packet batch ``[n_micro, B_mb, ...]`` is sharded ``P(None,
      "port")`` — each port lane carries ``B_mb / n_ports`` packets of every
      microbatch, the "many ingress ports" of a real switch;
    * inside the shard_map the ring pipeline runs along "switch" exactly as
      the 1D pipeline (``ppermute`` = the wire); the port axis needs no
      collective at all — port lanes are independent traffic.

    Compiled pipelines are memoized per ``n_micro``; batch-shape variation
    within one ``n_micro`` is handled by the jit cache (admission keeps that
    to O(log B) buckets).
    """

    def __init__(self, device_programs: list[PackedProgram], *,
                 n_classes: int, mode: str | None = None, n_ports: int = 1,
                 n_micro: int | None = None, devices=None) -> None:
        device_programs = list(device_programs)
        self.n_switch = len(device_programs)
        if self.n_switch < 1:
            raise ValueError("need at least one device program")
        self.n_ports = int(n_ports)
        if self.n_ports < 1:
            raise ValueError("need at least one port lane")
        self.n_micro = int(n_micro) if n_micro is not None else self.n_switch
        if self.n_micro < 1:
            raise ValueError("need at least one microbatch")
        need = self.n_switch * self.n_ports
        if devices is None:
            devices = jax.devices()[:need]
        if len(devices) < need:
            raise ValueError(
                f"need {need} devices ({self.n_switch} switches x "
                f"{self.n_ports} ports), have {len(devices)}")
        self.mesh = Mesh(
            np.asarray(devices[:need]).reshape(self.n_switch, self.n_ports),
            ("switch", "port"))
        self.n_classes = n_classes
        self.mode = mode
        self._runs: dict[int, object] = {}   # n_micro -> jitted pipeline
        self._put(device_programs)

    def _put(self, device_programs: list[PackedProgram]) -> None:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *device_programs)
        sharding = NamedSharding(self.mesh, P("switch"))
        self.packed = jax.tree.map(
            lambda x: jax.device_put(x, sharding), stacked)

    @property
    def granularity(self) -> int:
        # bucket must split into n_micro microbatches, each into n_ports shards
        return self.n_micro * self.n_ports

    def _build(self, n_micro: int):
        n_switch, n_classes, mode = self.n_switch, self.n_classes, self.mode
        n_steps = n_micro + n_switch - 1
        perm = [(i, (i + 1) % n_switch) for i in range(n_switch)]

        @functools.partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(P("switch"), P(None, "port")),
            out_specs=P(None, "switch", "port"),
            check_vma=False,
        )
        def pipeline(packed_stack, micro):
            packed = jax.tree.map(lambda x: x[0], packed_stack)
            idx = jax.lax.axis_index("switch")

            def step(state, s):
                inj = jax.tree.map(
                    lambda x: jnp.take(x, jnp.minimum(s, n_micro - 1), axis=0),
                    micro)
                mb = jax.tree.map(
                    lambda a, b: jnp.where(idx == 0, a, b), inj, state)
                out = _classify_impl(packed, mb, n_classes=n_classes,
                                     mode=mode)
                nxt = jax.tree.map(
                    lambda x: jax.lax.ppermute(x, "switch", perm), out)
                return nxt, out

            init = jax.tree.map(lambda x: jnp.zeros_like(x[0]), micro)
            _, outs = jax.lax.scan(step, init, jnp.arange(n_steps))
            # leading axis: steps; switch axis added on axis 1 by out_specs;
            # the port shards of each microbatch re-concatenate on axis 2.
            return jax.tree.map(lambda x: x[:, None], outs)

        return pipeline

    def _run_for(self, n_micro: int):
        fn = self._runs.get(n_micro)
        if fn is None:
            # jit at the memo-store site: one compiled pipeline per n_micro,
            # never rebuilt (PL005 retrace-hazard discipline).
            fn = self._runs[n_micro] = jax.jit(self._build(n_micro))
        return fn

    def run(self, microbatches: PacketBatch) -> PacketBatch:
        """Pipeline pre-split microbatches ``[n_micro, B_mb, ...]``; returns
        the classified packets as one flat ``[n_micro * B_mb]`` batch in the
        input packet order."""
        n_micro = int(microbatches.packet_id.shape[0])
        B_mb = int(microbatches.packet_id.shape[1])
        if B_mb % self.n_ports:
            raise ValueError(
                f"microbatch size {B_mb} not divisible by {self.n_ports} "
                "port lanes — admit through DataplaneRuntime")
        outs = self._run_for(n_micro)(self.packed, microbatches)
        # microbatch m exits the last switch at step m + n_switch - 1
        sel = jax.tree.map(
            lambda x: x[self.n_switch - 1:, self.n_switch - 1], outs)
        return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), sel)

    def classify(self, batch: PacketBatch) -> PacketBatch:
        B = batch.batch
        if B % self.granularity:
            raise ValueError(
                f"batch {B} not a multiple of granularity "
                f"{self.granularity} — admit through DataplaneRuntime")
        n_micro = self.n_micro
        mbs = jax.tree.map(
            lambda x: x.reshape((n_micro, B // n_micro) + x.shape[1:]), batch)
        return self.run(mbs)

    def swap(self, device_programs: list[PackedProgram]) -> None:
        """Runtime reprogram: restack + reshard the new entry arrays (and
        their install-time exec images); every compiled pipeline is reused."""
        device_programs = list(device_programs)
        if len(device_programs) != self.n_switch:
            raise ValueError("device count changed — replan instead")
        self._put(device_programs)

    def cache_size(self) -> int:
        return sum(fn._cache_size() for fn in self._runs.values())

    def grid_rows(self, batch: PacketBatch) -> None:
        return None


class PipelinedExecutor(ShardedExecutor):
    """The 1D pipeline: a ``ShardedExecutor`` with the port axis pinned to 1.

    Absorbs the old ``PipelinedPlane`` with its compile thrash fixed: the
    compiled pipeline for each ``n_micro`` lives in a memo table from
    ``__init__`` on, so alternating microbatch counts never rebuilds.
    """

    def __init__(self, device_programs: list[PackedProgram], *,
                 n_classes: int, mode: str | None = None,
                 n_micro: int | None = None, devices=None) -> None:
        super().__init__(device_programs, n_classes=n_classes, mode=mode,
                         n_ports=1, n_micro=n_micro, devices=devices)
