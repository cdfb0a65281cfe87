"""``DataplaneRuntime`` — the facade every serving surface classifies through.

One object, two responsibilities:

* **admission** — pad each ragged request batch into its power-of-two bucket
  of passthrough packets (``admission.py``), run the executor on the bucket
  shape, slice the padding back off.  Arbitrary traffic sizes therefore cost
  at most O(log B) compiled traces per executor, and every caller — the
  ``ZooServer`` serving front, examples, benchmarks — shares the same
  bucketed shapes.
* **delegation** — execution goes to the pluggable ``Executor``
  (``executors.py``); swapping substrates (single switch → pipelined path →
  2D switch x port mesh) changes *which executor is plugged in*, never the
  caller.

Control-plane writes (``install``/``evict``) pass through to executors that
own a plane (``SingleSwitchExecutor``); mesh executors are constructed from
pre-built device programs and reprogrammed wholesale via ``swap``.

Counters (``counters()``, merged into the serving fronts' ``latency_stats``
under ``"runtime"``): ``rows_real`` packets classified, ``rows_run`` the
admission buckets they ran at, and ``compiles``, the traces the executor
gained across a launch.  ``run_host`` marks its steps with the spans of
``repro.core.spans``.
"""
from __future__ import annotations

import threading
from typing import Sequence

import jax
import numpy as np

from repro.core.packets import PacketBatch
from repro.core.plane import PlaneProfile
from repro.core.spans import current_dispatch, recording, span
from repro.runtime.admission import (
    bucket_ladder,
    bucket_size,
    coalesce,
    pad_to_bucket,
    split,
    trim,
)
from repro.runtime.executors import Executor, SingleSwitchExecutor

__all__ = ["DataplaneRuntime"]


class DataplaneRuntime:
    """Admission-controlled front over one pluggable executor."""

    def __init__(self, executor: Executor) -> None:
        self.executor = executor
        # slot threads launch concurrently: the counters move under a lock
        self._lock = threading.Lock()
        self._rows_real = 0
        self._rows_run = 0
        self._compiles = 0
        self._traces: dict[Executor, int] = {}   # traces seen per executor

    @classmethod
    def for_profile(cls, profile: PlaneProfile, *,
                    mode: str | None = None) -> "DataplaneRuntime":
        """Single-switch runtime over a fresh engine — the quickstart path."""
        return cls(SingleSwitchExecutor(profile, mode=mode))

    # ---------------------------------------------------------- admission
    def bucket(self, batch: int) -> int:
        """The padded shape a batch of ``batch`` packets executes at."""
        return bucket_size(batch, self.executor.granularity)

    def run(self, batch: PacketBatch) -> PacketBatch:
        """Classify a flat request batch of any size.

        Pads to the bucket shape (passthrough tail), executes, trims — the
        result stays on device (callers needing host values convert
        explicitly, e.g. ``np.asarray(out.rslt)``).  An empty batch (B = 0,
        the async front's empty submit) short-circuits: nothing to classify,
        nothing traced.
        """
        B = batch.batch
        if B == 0:
            return batch
        padded = pad_to_bucket(batch, self.bucket(B))
        return trim(self._launch(self.executor, padded, B,
                                 current_dispatch()), B)

    def results(self, batch: PacketBatch) -> np.ndarray:
        """``run`` + the one host round-trip serving fronts usually want."""
        return np.asarray(self.run(batch).rslt)

    def run_host(self, batch: PacketBatch) -> PacketBatch:
        """``run`` variant that lands the result on host (numpy leaves).

        Same classification, different trim: the padded device result is
        transferred once and the admission tail sliced off in numpy.  A
        device-side trim (``run``) lazily compiles one slice kernel per
        (bucket, batch) shape pair per leaf — fine for a handful of batch
        shapes, but a live serving front sees a new ragged size on nearly
        every coalesced dispatch and would stall ~tens of ms of glue compile
        each time.  The async server always wants host values anyway, so it
        trims here for free.

        Each step is a span tagged with the dispatch it runs under: pad
        (with ``grid_rows=``, the rows the fused kernel's grid runs, while
        a profiler records and the executor counts them), the launch (the jitted call copies the host leaves to the device
        and enqueues the step) and the fetch (the wait for the device and
        the copies back).  The copies stay inside those calls: with the
        transfer and the wait as steps of their own (a ``device_put``
        before the call, ``block_until_ready`` after it) a v5e served the
        open-loop ids cell's median latency 9-13% slower.
        """
        B = batch.batch
        if B == 0:
            return batch
        dispatch = current_dispatch()
        ex = self.executor
        bucket = self.bucket(B)
        with span("acorn.pad", dispatch=dispatch, rows=B,
                  bucket=bucket) as s:
            # normalize leaves to host first so padding takes admission's
            # numpy branch unconditionally — a lone device-leaf request (the
            # single-batch coalesce fast path returns its input untouched)
            # must not fall back to the per-ragged-shape jnp glue
            batch = jax.tree.map(np.asarray, batch)
            padded = pad_to_bucket(batch, bucket)
            grid = ex.grid_rows(padded) if recording() else None
            if grid is not None:
                s.set_metadata(grid_rows=grid)
        out = self._launch(ex, padded, B, dispatch)
        with span("acorn.fetch", dispatch=dispatch):
            return jax.tree.map(lambda x: np.asarray(x)[:B], out)

    def _launch(self, ex: Executor, padded: PacketBatch, rows: int,
                dispatch: int) -> PacketBatch:
        """The executor call on one admitted bucket, counted: ``rows`` real
        packets in ``padded.batch`` rows, and the traces ``ex`` gained.  A
        launch that traced is tagged ``compiled=1``.  Traces are counted
        against the most this runtime has seen of ``ex``, so two slots
        launching at once never count one trace twice."""
        with self._lock:
            self._traces.setdefault(ex, ex.cache_size())
        with span("acorn.launch", dispatch=dispatch) as s:
            out = ex.classify(padded)
            n = ex.cache_size()
            with self._lock:
                grew = max(n - self._traces[ex], 0)
                self._traces[ex] += grew
                self._compiles += grew
                self._rows_real += rows
                self._rows_run += padded.batch
            if grew:
                s.set_metadata(compiled=1)
        return out

    def counters(self) -> dict:
        """Lifetime ``rows_real`` / ``rows_run`` / ``compiles``."""
        with self._lock:
            return {"rows_real": self._rows_real, "rows_run": self._rows_run,
                    "compiles": self._compiles}

    def warm(self, make_batch, max_batch: int) -> tuple[int, ...]:
        """Pre-trace every admission bucket up to ``bucket(max_batch)``.

        ``make_batch(b)`` must build a ``PacketBatch`` of exactly ``b``
        packets (serving fronts pass zero-filled FORWARD passthrough
        traffic — semantically invisible, same compiled shapes); each
        bucket runs once through ``run_host``'s steps (host leaves, the
        bucket's padding, the executor call, the fetch), so the executable
        cache is warmed against exactly the shapes a batching policy can
        dispatch into.  Returns the warmed bucket ladder.  Blocking
        compile work — serving fronts call this off-loop.

        Warming is not a dispatch: it makes no span and moves no counter.
        Through ``run_host``'s spans and counters a bucket took ~0.8 s
        longer to lower on a v5e than through the plain call (PERF.md).
        """
        ladder = bucket_ladder(max_batch, self.executor.granularity)
        for b in ladder:
            batch = jax.tree.map(np.asarray, make_batch(b))
            out = self.executor.classify(pad_to_bucket(batch, self.bucket(b)))
            jax.tree.map(np.asarray, out)
        return ladder

    # ------------------------------------------------------------ coalesce
    # The multi-client seam batching policies dispatch through: several
    # per-client request batches run as ONE admitted batch (one bucket, one
    # executor call), then split back per client.  Policies thereby reuse
    # the power-of-two bucketing — and its O(log B) trace bound — instead of
    # inventing shapes of their own.
    @staticmethod
    def coalesce(batches: Sequence[PacketBatch]) -> tuple[PacketBatch, tuple[int, ...]]:
        """Concatenate per-client batches; returns (flat batch, demux offsets)."""
        return coalesce(batches)

    def run_coalesced(self, batches: Sequence[PacketBatch]) -> list[PacketBatch]:
        """Classify several per-client batches as one admitted batch.

        Equivalent to ``[self.run(b) for b in batches]`` packet-for-packet
        (classification is per-packet; pinned in ``tests/test_conformance.py``)
        but costs one executor dispatch for the whole group.
        """
        flat, offsets = coalesce(batches)
        return split(self.run(flat), offsets)

    # ------------------------------------------------------ control plane
    def install(self, program, *, vid: int | None = None,
                stages: set[int] | None = None) -> None:
        ex = self.executor
        if not hasattr(ex, "install"):
            raise NotImplementedError(
                f"{type(ex).__name__} is built from pre-installed device "
                "programs — reprogram it wholesale via swap()")
        ex.install(program, vid=vid, stages=stages)

    def evict(self, *, vid: int, kind: str = "all") -> None:
        ex = self.executor
        if not hasattr(ex, "evict"):
            raise NotImplementedError(
                f"{type(ex).__name__} is built from pre-installed device "
                "programs — reprogram it wholesale via swap()")
        ex.evict(vid=vid, kind=kind)

    def swap(self, device_programs) -> None:
        self.executor.swap(device_programs)

    def cache_size(self) -> int:
        """Compiled traces across the executor — with admission on, at most
        one per (n_micro, bucket) shape."""
        return self.executor.cache_size()
