"""Whole-topology fleet serving: the planner's network as a live data plane.

Everything below ``ZooServer`` so far drove *one* path of devices; this
module drives the full ``core/topology.py`` graph the ILP planner optimizes
over (paper §5, §7.5).  ``FleetRuntime`` plans a model zoo onto a topology
with ``planner.plan_zoo``, slices per-device partial zoos with
``distributed_plane.build_zoo_device_programs``, and serves requests
hop-by-hop along the plan's wire path — each hosting switch applying its own
``PackedProgram`` (tables + exec image), intermediates riding in the packet
between hops, exactly the paper's in-packet transport.

One compiled template serves the whole fleet: ``SwitchEngine.classify``
takes the program as an *argument*, so every switch in the topology shares
one jitted trace and differs only in its table entries — the reproduction's
analogue of flashing one P4 binary to every switch and differing only in
entries (§6).  ``FleetExecutor.cache_size()`` therefore stays O(1) however
many devices the plan uses (at a fixed batch shape: one trace, at most two
cached executables — the host-resident first hop vs device-resident later
hops — never one per device).

Failure story (the self-healing loop, ``repro.runtime.control``):
``kill()`` marks a device dead; a dispatch whose wire path crosses a dead
device raises ``DeviceFailure`` instead of classifying through it; the
``ControlLoop`` detects, replans the zoo on the surviving topology
(capacity carry-over intact), drains the async server, and ``reinstall``s
the new per-device programs — submits retried through ``submit_batch``
return answers bit-identical to the pre-fault oracle (pinned by the
fault-schedule lane of ``tests/test_conformance.py``).

``FleetExecutor`` implements the ``repro.runtime`` ``Executor`` protocol,
so the whole fleet sits behind the same ``DataplaneRuntime`` admission seam
(power-of-two buckets, O(log B) traces) and ``ZooServer``/``AsyncZooServer``
fronts as every other substrate — no new entry points.
"""
from __future__ import annotations

import contextlib

import numpy as np

from repro.core.distributed_plane import build_zoo_device_programs
from repro.core.netsim import acorn_serving_time, simulate_serving
from repro.core.packets import PacketBatch
from repro.core.plane import PackedProgram, PlaneProfile, SwitchEngine
from repro.core.planner import (
    DeploymentPlan,
    DeviceModel,
    plan_zoo,
    replan_zoo,
)
from repro.core.topology import Network
from repro.core.translator import TableProgram
from repro.runtime import SizeOrDeadlinePolicy
from repro.runtime.control import ControlLoop, DeviceFailure
from repro.runtime.policies import BatchingPolicy
from repro.serving.async_server import AsyncResult, AsyncZooServer
from repro.serving.serve import ZooServer

__all__ = ["FleetExecutor", "FleetRuntime"]


class FleetExecutor:
    """``Executor`` over a deployment plan's wire path.

    Holds the shared template ``SwitchEngine``, the hosting hops' partial
    zoos in path order, and a live ``down`` set shared with the owning
    ``FleetRuntime``.  ``classify`` walks the hosting hops in order — the
    same chain-of-partial-programs semantics as ``SequentialPathExecutor``
    — after checking every switch on the wire path (hosting or not) is
    alive; a dead one raises ``DeviceFailure`` for the control loop.
    """

    granularity = 1

    def __init__(self, engine: SwitchEngine, wire_path: list[str],
                 devices: list[str], programs: list[PackedProgram], *,
                 down: set[str]) -> None:
        self.engine = engine
        self._down = down             # shared with FleetRuntime.kill()
        self.retarget(wire_path, devices, programs)

    def retarget(self, wire_path: list[str], devices: list[str],
                 programs: list[PackedProgram]) -> None:
        """Point the executor at a (possibly different-length) deployment —
        the control loop's reinstall step.  Unlike ``swap``, the device set
        may change: that is exactly what a post-fault replan produces."""
        if len(devices) != len(programs):
            raise ValueError("one program per hosting device required")
        missing = [d for d in devices if d not in wire_path]
        if missing:
            raise ValueError(f"hosting device(s) {missing} not on wire path")
        self.wire_path = list(wire_path)
        self.devices = list(devices)
        self.programs: dict[str, PackedProgram] = dict(zip(devices, programs))

    def classify(self, batch: PacketBatch) -> PacketBatch:
        dead = [d for d in self.wire_path if d in self._down]
        if dead:
            raise DeviceFailure(dead[0], path=self.wire_path)
        for d in self.devices:
            batch = self.engine.classify(self.programs[d], batch)
        # a kill that lands mid-chain: the answers are still correct (tables
        # were intact), but real hardware would have dropped the packet at
        # the dead hop — model the drop so the retry path is exercised
        dead = [d for d in self.wire_path if d in self._down]
        if dead:
            raise DeviceFailure(dead[0], path=self.wire_path)
        return batch

    def swap(self, device_programs: list[PackedProgram]) -> None:
        """Same-device-set reprogram (the ``Executor`` protocol's swap).
        A changed device count means the deployment changed — that is a
        control-plane ``retarget``, not a swap."""
        if len(device_programs) != len(self.devices):
            raise ValueError("device count changed — retarget (replan) instead")
        self.programs = dict(zip(self.devices, list(device_programs)))

    def cache_size(self) -> int:
        return self.engine.cache_size()

    def grid_rows(self, batch: PacketBatch) -> None:
        return None


class FleetRuntime:
    """Plan, serve, and heal a model zoo on a whole topology.

    Construction plans ``programs`` from ``src`` to ``dst`` with
    ``plan_zoo`` and builds the fleet executor behind a ``ZooServer``.
    Synchronous ``classify`` works immediately; ``async with
    fleet.serving():`` adds the ``AsyncZooServer`` front plus the
    ``ControlLoop`` heal cycle, and ``submit``/``submit_batch`` retry
    through heals on ``DeviceFailure``.
    """

    def __init__(self, network: Network, profile: PlaneProfile,
                 programs: list[TableProgram], *, src: str, dst: str,
                 mode: str | None = None, solver: str = "dp",
                 default_device: DeviceModel = DeviceModel(),
                 n_candidate_paths: int = 4,
                 engine: SwitchEngine | None = None) -> None:
        if not programs:
            raise ValueError("need at least one program to deploy")
        self.network = network
        self.profile = profile
        self.programs = list(programs)
        self.src, self.dst = src, dst
        self.solver = solver
        self.default_device = default_device
        self.n_candidate_paths = n_candidate_paths
        self.down: set[str] = set()
        # one jitted template for the entire fleet (see module docstring)
        self.engine = engine if engine is not None \
            else SwitchEngine(profile, mode=mode)
        plans, devices, progs = self._plan()
        self.plans: list[DeploymentPlan] = plans
        self.executor = FleetExecutor(self.engine, plans[0].path, devices,
                                      progs, down=self.down)
        self.zoo = ZooServer(profile, executor=self.executor)
        self.counters = None          # last serving session's ControlCounters
        self._server: AsyncZooServer | None = None
        self._control: ControlLoop | None = None

    # ------------------------------------------------------------- planning
    def _plan(self):
        kw = dict(solver=self.solver, default_device=self.default_device,
                  n_candidate_paths=self.n_candidate_paths)
        if self.down:
            plans = replan_zoo(self.programs, self.network, self.src,
                               self.dst, set(self.down), **kw)
        else:
            plans = plan_zoo(self.programs, self.network, self.src,
                             self.dst, **kw)
        devices, progs = build_zoo_device_programs(
            self.programs, plans, self.profile)
        return plans, devices, progs

    @property
    def path(self) -> list[str]:
        """The current serving wire path (all plans share it)."""
        return self.plans[0].path

    @property
    def runtime(self):
        return self.zoo.runtime

    # ------------------------------------------------------ fault injection
    def kill(self, device: str) -> None:
        """Mark a switch dead (scripted fault injection / chaos schedule)."""
        if self.network.kind.get(device) != "switch":
            raise ValueError(f"{device!r} is not a switch of this network")
        self.down.add(device)

    def revive(self, device: str) -> None:
        self.down.discard(device)

    # ------------------------------------- control-plane seam (HealableFleet)
    def failed_on_path(self) -> set[str]:
        return self.down & set(self.executor.wire_path)

    def replan_sync(self):
        """Re-solve the zoo on the surviving topology (blocking CPU work —
        the control loop runs this on a worker thread).  Raises
        ``RuntimeError`` when no feasible deployment survives."""
        return self._plan()

    def reinstall(self, plans, devices, programs) -> None:
        """Retarget the executor to a post-replan deployment (called by the
        control loop between drain and release — never under traffic)."""
        self.plans = list(plans)
        self.executor.retarget(plans[0].path, devices, programs)

    # -------------------------------------------------------------- serving
    def classify(self, features, *, mid: int = 0, vid=0) -> np.ndarray:
        """Synchronous classify through the fleet (admission-bucketed)."""
        return self.zoo.classify(features, mid=mid, vid=vid)

    def make_request(self, features, *, mid: int = 0, vid=0) -> PacketBatch:
        return self.zoo.make_request(features, mid=mid, vid=vid)

    @contextlib.asynccontextmanager
    async def serving(self, *, policy: BatchingPolicy | None = None,
                      probe_interval_s: float = 0.02):
        """Live-traffic session: ``AsyncZooServer`` front + ``ControlLoop``
        heal cycle.  Control counters flow through ``latency_stats()``."""
        if self._server is not None:
            raise RuntimeError("fleet is already serving")
        if policy is None:
            policy = SizeOrDeadlinePolicy(max_batch=64, max_wait_us=500.0)
        server = AsyncZooServer(self.zoo, policy=policy)
        control = ControlLoop(self, server,
                              probe_interval_s=probe_interval_s)
        self.counters = control.counters
        async with server:
            await control.start()
            self._server, self._control = server, control
            try:
                yield self
            finally:
                self._server = self._control = None
                await control.stop()

    @property
    def control(self) -> ControlLoop | None:
        return self._control

    async def submit(self, features, *, mid: int = 0, vid=0) -> AsyncResult:
        if self._server is None:
            raise RuntimeError(
                "fleet is not serving — use 'async with fleet.serving()'")
        return await self.submit_batch(
            self.make_request(features, mid=mid, vid=vid))

    async def submit_batch(self, pb: PacketBatch) -> AsyncResult:
        """Submit with self-healing: a dispatch that hits a dead device
        fails with ``DeviceFailure``; we heal (replan + drain + reinstall)
        and retry — the answer the caller finally sees is computed entirely
        on one consistent deployment, so it stays oracle-identical."""
        if self._server is None:
            raise RuntimeError(
                "fleet is not serving — use 'async with fleet.serving()'")
        # every retry heals at least one dead device off the path, so the
        # switch count bounds the retries a hostile schedule can force
        retries = self.network.n_switches + 1
        while True:
            try:
                return await self._server.submit_batch(pb)
            except DeviceFailure:
                if retries <= 0:
                    raise
                retries -= 1
                self._control.note_retry()
                await self._control.heal()

    def latency_stats(self) -> dict:
        if self._server is None:
            raise RuntimeError(
                "fleet is not serving — use 'async with fleet.serving()'")
        return self._server.latency_stats()

    # ----------------------------------------------------- netsim integration
    def serving_time(self) -> float:
        """Modeled per-request J_L of the current deployment (s)."""
        return acorn_serving_time(self.plans[0])

    def modeled_latencies(self, *, n: int = 1000,
                          arrival_rate_rps: float | None = None,
                          seed: int = 0) -> np.ndarray:
        """``netsim.simulate_serving`` samples for the current deployment,
        with the last serving session's heal windows applied as downtime —
        the availability model ``benchmarks/fleet_serve.py`` records."""
        windows = tuple(self.counters.downtime_windows) \
            if self.counters is not None else ()
        return simulate_serving(
            self.serving_time(), n=n, seed=seed,
            arrival_rate_rps=arrival_rate_rps, downtime_windows=windows)
