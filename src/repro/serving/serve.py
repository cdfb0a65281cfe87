"""Serving fronts: LM prefill/decode steps + the in-network classifier zoo.

Same runtime-programmability discipline throughout: each step compiles once
per fixed shape; swapping model *weights* or *table entries* (new checkpoint,
new tenant, new model version) is an array update, zero retrace.
``ZooServer`` is the classifier-side serving front — a ``DataplaneRuntime``
hosting ``profile.max_versions`` resident versions per pipeline, with
install / evict / A-B traffic-split rollout as control-plane operations and
admission bucketing on every classify (ragged traffic costs at most one
trace per power-of-two bucket).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packets import PacketBatch
from repro.core.plane import PackedProgram, PlaneProfile, SwitchEngine
from repro.core.spans import span
from repro.core.translator import TableProgram, translate
from repro.models.common import ArchConfig
from repro.models.transformer import decode_step, forward
from repro.runtime import DataplaneRuntime, Executor, SingleSwitchExecutor

__all__ = ["make_prefill_step", "make_decode_step", "ZooServer"]


def make_prefill_step(cfg: ArchConfig, *, q_chunk: int = 1024, unroll: bool = False):
    """prefill(params, tokens[, enc_inputs]) -> logits [B, S, V].

    q-chunked attention bounds the logits working set for 32k prefill."""

    def prefill(params, tokens, enc_inputs=None):
        return forward(params, tokens, cfg, enc_inputs=enc_inputs,
                       q_chunk=q_chunk, remat=False, unroll=unroll)

    return prefill


def make_decode_step(cfg: ArchConfig, *, unroll: bool = False):
    """step(params, state, tokens [B,1], pos) -> (logits [B,1,V], state)."""

    def step(params, state, tokens, pos):
        return decode_step(params, state, tokens, pos, cfg, unroll=unroll)

    return step


class ZooServer:
    """Stateful serving front over one ``DataplaneRuntime`` model zoo.

    The data plane compiles once per admission bucket (lazily); every
    subsequent ``install`` / ``evict`` / traffic shift is an entry-array
    update — the paper's §6 runtime reprogrammability, extended along the
    Appendix A VID axis.  Each install/evict also recompiles the exec image
    of *only the written slot* (``core/plane.py``), so serving classifies
    against precomputed kernel operands while the control-plane cost stays
    per-slot.  ``classify_split`` implements A/B rollout: the *request
    writer* shifts a traffic fraction to a new version by rewriting ``vid``
    in the requests; the plane — tables and image alike — is untouched.

    Execution is pluggable: the default is a ``SingleSwitchExecutor`` (one
    engine), but any ``repro.runtime`` executor already holding this zoo's
    programs can be passed in — the serving API is unchanged on top of a
    pipelined path or a 2D switch x port mesh.
    """

    def __init__(self, profile: PlaneProfile, *, mode: str | None = None,
                 executor: Executor | None = None) -> None:
        if executor is None:
            executor = SingleSwitchExecutor(profile, mode=mode)
        self.runtime = DataplaneRuntime(executor)
        self._profile = profile
        self.versions: dict[tuple[str, int], str] = {}  # (pipeline, vid) -> tag

    @property
    def executor(self) -> Executor:
        return self.runtime.executor

    @property
    def engine(self) -> SwitchEngine:
        """The owning plane (single-switch executors only) — compat accessor."""
        return self.executor.engine

    @property
    def packed(self) -> PackedProgram:
        return self.executor.packed

    @property
    def profile(self) -> PlaneProfile:
        return self._profile

    def install(self, model_or_program, *, vid: int, tag: str = "") -> int:
        """Install a trained model (or pre-translated program) into slot
        ``vid`` of its pipeline.  Returns the vid for chaining."""
        if isinstance(model_or_program, TableProgram):
            prog = model_or_program
            if prog.vid != vid:
                raise ValueError(
                    f"program targets vid {prog.vid} but install asked for "
                    f"slot {vid} — requests built from the program's metadata "
                    "would dispatch to the wrong slot"
                )
        else:
            with span("acorn.install.translate", vid=vid):
                prog = translate(model_or_program, vid=vid)
        self.runtime.install(prog, vid=vid)
        pipeline = "svm" if prog.kind == "svm" else "tree"
        self.versions[(pipeline, vid)] = tag or f"{prog.kind}-v{vid}"
        return vid

    def evict(self, *, vid: int, kind: str = "all") -> None:
        self.runtime.evict(vid=vid, kind=kind)
        for pipeline in ("tree", "svm"):
            if kind in (pipeline, "all"):
                self.versions.pop((pipeline, vid), None)

    def make_request(self, features, *, mid: int = 0, vid=0) -> PacketBatch:
        """Build a REQUEST batch sized to this zoo's plane profile.

        The one request-construction path shared by the synchronous
        ``classify`` and the async front (``AsyncZooServer.submit``), so
        both serve bit-identical packets by construction."""
        prof = self.profile
        return PacketBatch.make_request(
            features, mid=mid, vid=vid, max_features=prof.max_features,
            n_trees=prof.max_trees, n_hyperplanes=prof.max_hyperplanes,
            max_versions=prof.max_versions)

    def classify(self, features, *, mid: int, vid: int | np.ndarray,
                 device_out: bool = False) -> np.ndarray | PacketBatch:
        """Classify one request batch (admission-bucketed, any size).

        ``device_out=True`` returns the classified on-device ``PacketBatch``
        instead of forcing the per-batch host round-trip — runtime-stacked
        callers (and sharded executors, whose results live across port
        devices) keep results on device and convert only at the edge."""
        out = self.runtime.run(self.make_request(features, mid=mid, vid=vid))
        if device_out:
            return out
        return np.asarray(out.rslt)

    def classify_coalesced(self, requests) -> list[np.ndarray]:
        """Classify several per-client request batches as ONE dispatch.

        ``requests`` is a sequence of ``(features, mid, vid)`` triples; the
        batches are coalesced through the runtime's admission seam (one
        bucket, one executor call) and split back per client — the
        synchronous twin of one ``AsyncZooServer`` batch dispatch, with the
        same per-client results as calling ``classify`` once per triple
        (pinned in ``tests/test_async_serving.py``)."""
        pbs = [self.make_request(f, mid=m, vid=v) for f, m, v in requests]
        return [np.asarray(out.rslt) for out in self.runtime.run_coalesced(pbs)]

    def classify_split(self, features, *, mid: int,
                       split: dict[int, float]) -> tuple[np.ndarray, np.ndarray]:
        """A/B rollout step: route a deterministic fraction of requests to
        each version in ``split`` (vid -> fraction, summing to ~1).  Returns
        (results, per-packet vid) so callers can track cohort metrics."""
        if not split:
            raise ValueError("split needs at least one vid -> fraction entry")
        B = np.asarray(features).shape[0]
        vids_sorted = sorted(split)
        bounds = np.cumsum([split[v] for v in vids_sorted])
        if not np.isclose(bounds[-1], 1.0, atol=1e-6):
            raise ValueError(f"traffic fractions sum to {bounds[-1]}, not 1")
        # deterministic low-discrepancy assignment by packet index; clip so
        # a fraction sum of 1-eps (within isclose tolerance) can't index past
        # the last version
        u = (np.arange(B) + 0.5) / B
        idx = np.minimum(np.searchsorted(bounds, u), len(vids_sorted) - 1)
        vids = np.asarray(vids_sorted, np.int32)[idx]
        return self.classify(features, mid=mid, vid=vids), vids

    def cache_size(self) -> int:
        return self.runtime.cache_size()


def greedy_decode(params, state, first_token, pos0, cfg: ArchConfig, n_steps: int):
    """Serve-loop helper for examples/tests: greedy argmax continuation."""

    def body(carry, _):
        state, tok, pos = carry
        logits, state = decode_step(params, state, tok, pos, cfg)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(tok.dtype)
        return (state, nxt, pos + 1), nxt[:, 0]

    (_, _, _), toks = jax.lax.scan(body, (state, first_token, pos0), None,
                                   length=n_steps)
    return toks.T  # [B, n_steps]
