"""The ACORN data plane engine: compile once, reprogram at runtime (paper §6).

A physical switch compiles the *template* P4 program once; afterwards every
model (re)deployment only rewrites match-action entries.  The TPU-native
equivalent: ``SwitchEngine`` jits one fixed-shape classification step whose
table entries are **inputs** (a ``PackedProgram`` pytree), so installing or
swapping a model is an array update — zero retrace (asserted by tests via
``cache_size() == 1``).

Like the paper's Fig. 5 data plane, one engine hosts *both* pipelines
simultaneously — a tree pipeline (fused single-launch dt_layer walk →
dt_predict → multitree_voting; ``mode="layerwise[-*]"`` selects the
pre-fusion per-layer kernel scan) and an SVM pipeline (svm_mul partials →
native adds → svm_predict) — and each packet selects its result by MID.
Non-request packets pass through untouched (forwarding is unaffected):
their rslt *and* their codes/svm_acc intermediates come out bit-identical.

Model zoo (the VID axis, paper Appendix A): every table array carries a
leading version axis ``V = profile.max_versions``, so one engine hosts ``V``
tree-pipeline programs and ``V`` SVM programs *simultaneously*, and each
packet selects its tables by ``(MID, VID)`` at classify time.
``install_program(..., vid=k)`` writes one version slot and preserves the
rest; ``evict_program`` empties a slot.  Install, swap, and evict are all
array updates against the same compiled trace.  A packet addressing an empty
or out-of-range version slot gets ``rslt == -1`` (no match) — it never reads
another version's tables.

Install-time program compilation (the exec image): the paper's control plane
"updates the entries in predefined tables" (§6.2) and the hot path stays pure
match-action.  Mirroring that boundary, program state splits into **source
tables** (what ``install_program`` writes — the swappable flow-table state)
and a derived, device-resident **``ExecImage``** — the kernel-ready operands
(flattened one-hot ``fsel``, no-match-padded entry blocks, chunked SVM LUTs,
Pallas-dtype predict tables) that classify binds straight into each
``pallas_call``.  The image is recomputed once per install/evict/swap, and
only for the written version slot; classify does **zero** per-call operand
prep (pinned by the exec-image jaxpr test).  ``docs/ARCHITECTURE.md`` pins
the full contract.

Distribution hooks: a ``PackedProgram`` can be *partial* — only the tables of
the program stages assigned to this device are installed; status codes and
SVM partial sums travel in the ``PacketBatch`` intermediates, so a packet
finishes classification after visiting every assigned device in path order
(see ``distributed_plane.py``).  Partial programs carry their own (partial)
exec image, built from exactly the entries this device owns.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packets import PacketBatch, PacketType
from repro.core.spans import span
from repro.core.translator import MID_SVM, TableProgram
from repro.kernels import classify_fused, ops, tiling

__all__ = [
    "PlaneProfile",
    "PackedProgram",
    "ExecImage",
    "SwitchEngine",
    "build_exec_image",
    "empty_program",
    "install_program",
    "evict_program",
]

_SENTINEL = np.uint32(0xFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class PlaneProfile:
    """Fixed template shapes — the operator's compile-time knobs (paper §3.2:
    "the size of the data part is decided by the maximum number of supported
    features, which can be configured by the network operator")."""

    max_features: int = 60       # paper: up to 60 features
    feature_width: int = 8       # quantization bits
    max_trees: int = 8
    max_layers: int = 32         # paper: tree depth up to 32
    max_entries_per_layer: int = 128   # 2 * nodes per layer
    max_leaves: int = 256        # dt_predict entries per tree
    max_classes: int = 32
    max_hyperplanes: int = 12    # svm_predict direct table = 2^H entries
    levels: int = 256
    # Model-zoo slots per pipeline (the VID range).  An operator knob like the
    # rest: table memory scales with V, so the default is a single-slot plane
    # and zoos opt in explicitly.
    max_versions: int = 1

    def __post_init__(self):
        if self.max_hyperplanes > 16:
            raise ValueError("svm_predict direct table capped at 2^16 entries")
        if self.max_layers > 32:
            raise ValueError("status code is 32-bit (paper: 16-32 bit bitstring)")
        if self.max_versions < 1:
            raise ValueError("need at least one model-zoo version slot")
        if self.feature_width > 15:
            raise ValueError(
                "feature values are int16 in the quantized fused-classify "
                "operand layout: feature_width must be <= 15")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExecImage:
    """Derived, device-resident kernel operands — the installed *executable*.

    Everything here is a pure function of the ``PackedProgram`` source tables
    (``build_exec_image``), precomputed at install/evict/swap time so
    ``_classify_impl`` binds operands straight into each ``pallas_call`` with
    zero per-call prep.  Field groups are the kernels' ``*Operands`` tuples
    (see ``kernels/tiling.py`` for shapes, dtypes, and the no-match padding
    convention):

    * ``walk``   — fused tree walk: flattened one-hot ``fsel``
      ``[V, T, L*E_pad, F_pad]`` + no-match-padded entry blocks
      ``[V, L, T, E_pad]``.
    * ``svm``    — chunked f32 LUT ``[V, n_chunks, chunk_f*levels, H_pad]``.
      Its bias block is **zeros**: the plane adds ``svm_bias`` *outside* the
      kernel so distributed partial sums compose (bias once, on the owning
      device).
    * ``forest`` — dt_predict validity/weights in Pallas block dtypes
      (``pred_codes``/``pred_labels`` bind as-is from the source tables).
    * ``fused``  — the whole-classify megakernel's quantized operand layout
      (int16 feature ids / range bounds, int8 leaf labels, bit-packed
      set_bit / valid / pred_valid words, chunked f32 LUT) — what the
      default single-launch classify binds; the three groups above serve
      the ``unfused`` / ``layerwise`` fallback modes.  Its bias block is
      zeros for the same distributed-compose reason as ``svm``'s.

    Residency trade-off: the image lives on the *program*, not the engine,
    so one ``PackedProgram`` serves any engine mode — at the cost of holding
    the image (≈ ``image_mib`` in ``benchmarks/zoo_swap.py``, linear in V)
    even under a ``mode="ref"`` or ``use_image=False`` engine that never
    dereferences it.  On the TPU target the image IS the working set; if
    ref-only deployments ever matter, carry ``image=None`` and let the next
    install heal it.
    """

    walk: tiling.TreeWalkOperands
    svm: tiling.SvmOperands
    forest: tiling.ForestOperands
    fused: tiling.ClassifyFusedOperands


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedProgram:
    """Entry arrays for one engine — the runtime-swappable 'flow table' state.

    All table arrays carry a leading version axis V (the model zoo); a
    packet's VID selects its slot at classify time.  Tree layouts use a
    layer axis [V, L, T, E]; since the PR-2 fusion the engine walks all
    layers inside **one** kernel launch (``kernels/tree_walk.py``), the
    per-layer kernel scan surviving only as the ``layerwise`` fallback mode.

    ``image`` is the derived exec image (kernel-ready operands) kept in sync
    by ``install_program``/``evict_program`` — the *source tables* here are
    the control plane's write interface, the image is what classify reads.
    """

    # tree pipeline
    dt_cv: jax.Array       # uint32 [V, L, T, E]
    dt_cm: jax.Array       # uint32 [V, L, T, E]
    dt_fid: jax.Array      # int32 [V, L, T, E]
    dt_flo: jax.Array      # int32 [V, L, T, E]
    dt_fhi: jax.Array      # int32 [V, L, T, E]
    dt_bit: jax.Array      # uint32 [V, L, T, E]
    dt_valid: jax.Array    # bool [V, L, T, E]
    layer_shift: jax.Array  # int32 [L] status-code bit per scan step (shared)
    pred_codes: jax.Array  # uint32 [V, T, P] sorted per (v, t)
    pred_labels: jax.Array  # int32 [V, T, P]
    pred_valid: jax.Array  # bool [V, T, P]
    pred_enable: jax.Array  # bool [V] — this device owns v's dt_predict/voting
    vote_weights: jax.Array  # float32 [V, T]
    # svm pipeline
    svm_lut: jax.Array     # int32 [V, H, F, levels]
    svm_bias: jax.Array    # int32 [V, H]
    svm_hvalid: jax.Array  # bool [V, H] — which hyperplanes each version defines
    svm_pred_table: jax.Array  # int32 [V, 2^H]
    svm_pred_enable: jax.Array  # bool [V]
    # derived exec image — kernel-ready operands, rebuilt per slot write
    image: ExecImage | None = None

    @property
    def n_versions(self) -> int:
        return self.pred_enable.shape[0]


def _fused_quantize(profile: PlaneProfile) -> bool:
    """Whether the quantized fused-operand widths are lossless for this
    profile: int8 labels need <= 127 classes, int16 feature values /
    range bounds need feature_width <= 15 (enforced in the profile) and
    levels <= 32768.  Profiles outside that envelope fall back to the f32
    width of the same layout — still one launch, same bits."""
    return profile.max_classes <= 127 and profile.levels <= 32768


def build_exec_image(packed: PackedProgram, profile: PlaneProfile) -> ExecImage:
    """Full (all-slot) source-tables -> exec-image compile.

    ``install_program``/``evict_program`` use the per-slot incremental path
    instead; this is the from-scratch build (``empty_program``, recovery of a
    legacy ``image=None`` program, and the image-consistency tests).
    """
    f_pad = tiling.lane_pad(profile.max_features)
    walk = tiling.prep_tree_walk(
        packed.dt_cv, packed.dt_cm, packed.dt_fid, packed.dt_flo,
        packed.dt_fhi, packed.dt_bit, packed.dt_valid, f_pad)
    # Zero bias by design: _classify_impl adds svm_bias outside the kernel so
    # distributed partial sums compose (see ExecImage docstring).
    svm = tiling.prep_svm_lookup(packed.svm_lut,
                                 jnp.zeros_like(packed.svm_bias))
    forest = tiling.prep_forest_vote(packed.pred_valid, packed.vote_weights)
    fused = tiling.prep_classify_fused(
        packed.dt_cv, packed.dt_cm, packed.dt_fid, packed.dt_flo,
        packed.dt_fhi, packed.dt_bit, packed.dt_valid, packed.pred_codes,
        packed.pred_labels, packed.pred_valid, packed.vote_weights,
        packed.svm_lut, jnp.zeros_like(packed.svm_bias),
        quantize=_fused_quantize(profile))
    return ExecImage(walk=walk, svm=svm, forest=forest, fused=fused)


def _prep_fused_slot(packed: PackedProgram, vid: int,
                     profile: PlaneProfile,
                     **written) -> tiling.ClassifyFusedOperands:
    """V=1 fused-operand slice for one slot's source tables.

    The fused group spans both pipelines, so a tree install must fold in the
    slot's resident svm tables (and vice versa) — this reads whichever side
    the caller is writing from ``written`` (V=1 host tables, by
    ``PackedProgram`` field) and the other side from what is installed.
    """
    s = slice(vid, vid + 1)

    def table(name):
        return written[name] if name in written else getattr(packed, name)[s]

    return tiling.prep_classify_fused(
        *(table(n) for n in ("dt_cv", "dt_cm", "dt_fid", "dt_flo", "dt_fhi",
                             "dt_bit", "dt_valid", "pred_codes",
                             "pred_labels", "pred_valid", "vote_weights",
                             "svm_lut")),
        jnp.zeros_like(packed.svm_bias[s]),
        quantize=_fused_quantize(profile))


def _set_image_slot(image_group, slot_group, vid: int):
    """Write one version slot of an operand group (V=1 prep) into the full
    image group — the incremental install/evict image update."""
    return jax.tree.map(lambda full, s: full.at[vid].set(s[0]),
                        image_group, slot_group)


def empty_program(profile: PlaneProfile) -> PackedProgram:
    V = profile.max_versions
    L, T, E = profile.max_layers, profile.max_trees, profile.max_entries_per_layer
    P, H, F = profile.max_leaves, profile.max_hyperplanes, profile.max_features
    packed = PackedProgram(
        dt_cv=jnp.zeros((V, L, T, E), jnp.uint32),
        dt_cm=jnp.full((V, L, T, E), _SENTINEL, jnp.uint32),
        dt_fid=jnp.zeros((V, L, T, E), jnp.int32),
        dt_flo=jnp.ones((V, L, T, E), jnp.int32),
        dt_fhi=jnp.zeros((V, L, T, E), jnp.int32),
        dt_bit=jnp.zeros((V, L, T, E), jnp.uint32),
        dt_valid=jnp.zeros((V, L, T, E), bool),
        layer_shift=jnp.arange(L, dtype=jnp.int32),
        pred_codes=jnp.full((V, T, P), _SENTINEL, jnp.uint32),
        pred_labels=jnp.zeros((V, T, P), jnp.int32),
        pred_valid=jnp.zeros((V, T, P), bool),
        pred_enable=jnp.zeros((V,), bool),
        vote_weights=jnp.zeros((V, T), jnp.float32),
        svm_lut=jnp.zeros((V, H, F, profile.levels), jnp.int32),
        svm_bias=jnp.zeros((V, H), jnp.int32),
        svm_hvalid=jnp.zeros((V, H), bool),
        svm_pred_table=jnp.zeros((V, 2**H), jnp.int32),
        svm_pred_enable=jnp.zeros((V,), bool),
    )
    return dataclasses.replace(packed, image=build_exec_image(packed, profile))


def _check_vid(vid: int, profile: PlaneProfile) -> int:
    if not 0 <= vid < profile.max_versions:
        raise ValueError(
            f"vid {vid} out of range: profile hosts {profile.max_versions} "
            f"model-zoo versions (0..{profile.max_versions - 1})"
        )
    return vid


def install_program(
    packed: PackedProgram,
    program: TableProgram,
    profile: PlaneProfile,
    *,
    stages: set[int] | None = None,
    vid: int | None = None,
) -> PackedProgram:
    """Write a TableProgram's entries into one model-zoo version slot (the
    control plane's 'update the entries in predefined tables', paper §6.2).

    ``vid`` selects the slot (default: the program's own ``vid``); every other
    slot — and the *other* pipeline's state in ``packed`` — is preserved, so
    V tree models and V SVMs can coexist (paper Fig. 5 + Appendix A VID).
    ``stages`` restricts installation to a subset of program stages (the
    planner's per-device assignment); ``None`` installs everything.

    Two spans: ``acorn.install.tables`` builds the slot's host tables and
    its exec-image operands, touching nothing installed;
    ``acorn.install.write`` writes them into the slot.
    """
    vid = _check_vid(program.vid if vid is None else vid, profile)
    with span("acorn.install.tables", vid=vid):
        tables, image = _slot_tables(packed, program, profile, stages, vid)
    with span("acorn.install.write", vid=vid):
        new = dataclasses.replace(packed, **{
            k: getattr(packed, k).at[vid].set(v) for k, v in tables.items()})
        if packed.image is None:  # legacy program: recover with a full build
            return dataclasses.replace(
                new, image=build_exec_image(new, profile))
        return dataclasses.replace(new, image=dataclasses.replace(
            packed.image, **{k: _set_image_slot(getattr(packed.image, k),
                                                slot, vid)
                             for k, slot in image.items()}))


def _slot_tables(packed: PackedProgram, program: TableProgram,
                 profile: PlaneProfile, stages: set[int] | None,
                 vid: int) -> tuple[dict, dict]:
    """One slot's tables for ``install_program``: the ``PackedProgram``
    fields it writes (host arrays, or a bool for the enable flags) and the
    V=1 exec-image operand groups, by ``ExecImage`` field (empty for a
    legacy program without an image)."""
    specs = program.stages()
    if stages is None:
        stages = set(range(len(specs)))
    own = [specs[i] for i in sorted(stages)]

    if program.kind in ("dt", "rf"):
        L, T, E = profile.max_layers, profile.max_trees, profile.max_entries_per_layer
        P = profile.max_leaves
        if program.n_trees > T:
            raise ValueError(f"{program.n_trees} trees > profile max {T}")
        cv = np.zeros((L, T, E), np.uint32)
        cm = np.full((L, T, E), _SENTINEL, np.uint32)
        fid = np.zeros((L, T, E), np.int32)
        flo = np.ones((L, T, E), np.int32)
        fhi = np.zeros((L, T, E), np.int32)
        bit = np.zeros((L, T, E), np.uint32)
        valid = np.zeros((L, T, E), bool)
        owned_pairs = {
            (tab.tree, tab.layer) for s in own for tab in s.tables if tab.kind == "dt_layer"
        }
        for t, layers in enumerate(program.dt_layers):
            for lt in layers:
                if (t, lt.layer) not in owned_pairs:
                    continue
                n = lt.n_entries
                if lt.layer >= L:
                    raise ValueError(f"layer {lt.layer} > profile max {L}")
                if n > E:
                    raise ValueError(f"{n} entries at layer {lt.layer} > profile max {E}")
                cv[lt.layer, t, :n] = lt.code_value
                cm[lt.layer, t, :n] = lt.code_mask
                fid[lt.layer, t, :n] = lt.fid
                flo[lt.layer, t, :n] = lt.f_lo
                fhi[lt.layer, t, :n] = lt.f_hi
                bit[lt.layer, t, :n] = lt.set_bit
                valid[lt.layer, t, :n] = True
        own_predict = any(tab.kind == "dt_predict" for s in own for tab in s.tables)
        pc = np.full((T, P), _SENTINEL, np.uint32)
        pl_ = np.zeros((T, P), np.int32)
        pv = np.zeros((T, P), bool)
        w = np.zeros((T,), np.float32)
        if own_predict:
            for p in program.dt_predicts:
                n = p.n_entries
                if n > P:
                    raise ValueError(f"{n} leaves > profile max {P}")
                pc[p.tree, :n] = p.codes
                pl_[p.tree, :n] = p.labels
                pv[p.tree, :n] = True
            if program.voting is not None:
                w[: program.n_trees] = program.voting.weights
            else:
                w[0] = 1.0
        tables = dict(
            dt_cv=cv, dt_cm=cm, dt_fid=fid, dt_flo=flo, dt_fhi=fhi,
            dt_bit=bit, dt_valid=valid, pred_codes=pc, pred_labels=pl_,
            pred_valid=pv, vote_weights=w)
        image = {}
        if packed.image is not None:
            # Install-time compile of the written slot only: prep the new
            # entries as a V=1 image slice, spliced in by the write.
            f_pad = tiling.lane_pad(profile.max_features)
            image = dict(
                walk=tiling.prep_tree_walk(
                    cv[None], cm[None], fid[None], flo[None], fhi[None],
                    bit[None], valid[None], f_pad),
                forest=tiling.prep_forest_vote(pv[None], w[None]),
                fused=_prep_fused_slot(
                    packed, vid, profile,
                    **{k: v[None] for k, v in tables.items()}))
        tables["pred_enable"] = own_predict
        return tables, image

    if program.kind == "svm":
        H, F, Lev = profile.max_hyperplanes, profile.max_features, profile.levels
        if program.n_hyperplanes > H:
            raise ValueError(f"{program.n_hyperplanes} hyperplanes > profile max {H}")
        if program.n_features > F:
            raise ValueError(f"{program.n_features} features > profile max {F}")
        lut = np.zeros((H, F, Lev), np.int32)
        # Ownership by stage (matches TableProgram.stages()/svm_stage_muls()).
        stage_muls = program.svm_stage_muls()
        owned_flat = set()
        for si in sorted(stages):
            if si < len(stage_muls):
                owned_flat.update(stage_muls[si])
        for k in owned_flat:
            m = program.svm_muls[k]
            lut[m.hyperplane, m.feature, : m.n_entries] = m.lut
        own_pred = any(tab.kind == "svm_predict" for s in own for tab in s.tables)
        bias = np.zeros((H,), np.int32)
        tbl = np.zeros((2**H,), np.int32)
        if own_pred:
            bias[: program.n_hyperplanes] = program.svm_bias
            sp = program.svm_predict
            if sp.table is None:
                raise ValueError("svm_predict table too large for direct materialization")
            tbl[: sp.table.shape[0]] = sp.table
        hvalid = np.zeros((H,), bool)
        hvalid[: program.n_hyperplanes] = True
        tables = dict(svm_lut=lut, svm_bias=bias, svm_hvalid=hvalid,
                      svm_pred_table=tbl, svm_pred_enable=own_pred)
        image = {}
        if packed.image is not None:
            image = dict(
                svm=tiling.prep_svm_lookup(
                    lut[None], np.zeros((1, H), np.int32)),  # zero bias
                fused=_prep_fused_slot(packed, vid, profile,
                                       svm_lut=lut[None]))
        return tables, image

    raise ValueError(f"unknown program kind {program.kind}")


@functools.lru_cache(maxsize=8)
def _blank_slot_program(profile: PlaneProfile) -> PackedProgram:
    """One-slot blank (V=1) program *and* its image, memoized per profile: the
    empty fills live only in empty_program, and eviction splices these
    constant slices instead of re-running the (image-sized) blank build per
    call."""
    return empty_program(dataclasses.replace(profile, max_versions=1))


def evict_program(
    packed: PackedProgram,
    profile: PlaneProfile,
    *,
    vid: int,
    kind: str = "all",
) -> PackedProgram:
    """Empty one model-zoo version slot (``kind``: "tree" | "svm" | "all").

    Packets addressing an evicted slot get ``rslt == -1`` — same as a slot
    that was never installed.  Eviction is an array update, zero retrace.
    """
    vid = _check_vid(vid, profile)
    if kind not in ("tree", "svm", "all"):
        raise ValueError(f"unknown evict kind {kind!r}")
    blank = _blank_slot_program(profile)
    upd = {}
    tree_fields = ("dt_cv", "dt_cm", "dt_fid", "dt_flo", "dt_fhi", "dt_bit",
                   "dt_valid", "pred_codes", "pred_labels", "pred_valid",
                   "pred_enable", "vote_weights")
    svm_fields = ("svm_lut", "svm_bias", "svm_hvalid", "svm_pred_table",
                  "svm_pred_enable")
    fields = (tree_fields if kind == "tree"
              else svm_fields if kind == "svm"
              else tree_fields + svm_fields)
    for f in fields:
        upd[f] = getattr(packed, f).at[vid].set(getattr(blank, f)[0])
    new = dataclasses.replace(packed, **upd)
    if packed.image is None:  # legacy program: recover with a full build
        return dataclasses.replace(new, image=build_exec_image(new, profile))
    # Evicted slots get the blank slot's image slice (all-invalid operands).
    img = {}
    if kind in ("tree", "all"):
        img["walk"] = _set_image_slot(packed.image.walk, blank.image.walk, vid)
        img["forest"] = _set_image_slot(packed.image.forest,
                                        blank.image.forest, vid)
    if kind in ("svm", "all"):
        img["svm"] = _set_image_slot(packed.image.svm, blank.image.svm, vid)
    # The fused group spans both pipelines: rebuild its slot from the slot's
    # post-evict source tables (for kind="all" this equals the blank slice).
    img["fused"] = _set_image_slot(
        packed.image.fused, _prep_fused_slot(new, vid, profile), vid)
    return dataclasses.replace(
        new, image=dataclasses.replace(packed.image, **img))


# --------------------------------------------------------------------------
# The jitted classification step
# --------------------------------------------------------------------------
def kernel_vid(vid, ptype, n_versions: int, xp=jnp):
    """The version each packet runs under in the classify kernel: its VID;
    slot 0 for a REQUEST whose VID is out of range (its result is forced
    to -1); -1, no version, for a packet that is not a REQUEST, whose
    codes, svm sums and result the step leaves as they came.  ``xp`` is
    ``jnp`` in the step and ``np`` on the host."""
    ok = (vid >= 0) & (vid < n_versions)
    return xp.where(ptype == PacketType.REQUEST, xp.where(ok, vid, 0), -1)


def fused_grid_rows(packed: PackedProgram, batch: PacketBatch) -> int:
    """Rows the fused kernel's grid runs to classify ``batch`` (numpy
    leaves) on ``packed``: its packets grouped by version into blocks."""
    V, _, T, E = packed.dt_cv.shape
    P = packed.pred_codes.shape[2]
    levels = packed.svm_lut.shape[3]
    vid = kernel_vid(batch.vid, batch.ptype, V, np)
    return classify_fused.grid_rows(
        vid, V, classify_fused.block_rows(T, P, E, levels))


def _classify_impl(packed: PackedProgram, pb: PacketBatch, *, n_classes: int,
                   mode: str | None, use_image: bool = True) -> PacketBatch:
    feats = pb.features
    V = packed.n_versions
    # Classify-boundary VID validation: out-of-range packets are processed
    # against slot 0's tables (shape-stable) but their result is forced to -1.
    vid_ok = (pb.vid >= 0) & (pb.vid < V)
    vid = jnp.where(vid_ok, pb.vid, 0)
    # Bind the install-time exec image: the kernel launch reads precomputed
    # operands, zero per-call prep.  use_image=False forces the per-call prep
    # path (the pre-image behavior, kept for the install-vs-classify split
    # benchmark); the ref oracle and the fallback modes rebuild from source
    # tables, so unused operands drop out of the trace either way.
    img = packed.image if use_image else None

    # ---- both pipelines in ONE launch: walk -> vote codes stay VMEM-resident
    # and feed the svm LUT contraction in the same grid program.
    # mode="unfused[-*]" restores the pre-fusion three-launch classify;
    # mode="layerwise[-*]" additionally scans per-layer walk kernels.
    # Zero bias into the kernel: svm_bias is added below, outside, so
    # distributed partial sums compose (bias once, on the owning device).
    # Packets that are not REQUESTs run under no version: the fused kernel
    # groups only the rows it classifies.
    codes, tree_label, partial = ops.classify_fused_v(
        pb.codes, feats, kernel_vid(pb.vid, pb.ptype, V), packed.dt_cv,
        packed.dt_cm, packed.dt_fid, packed.dt_flo, packed.dt_fhi,
        packed.dt_bit, packed.dt_valid, packed.layer_shift,
        packed.pred_codes, packed.pred_labels, packed.pred_valid,
        packed.vote_weights, packed.svm_lut,
        jnp.zeros_like(packed.svm_bias), n_classes, mode=mode,
        prep=img.fused if img else None,
        unfused_prep=(img.walk, img.forest, img.svm) if img else None)
    tree_result = jnp.where(packed.pred_enable[vid], tree_label, -1)

    # ---- svm predict: native adds on the kernel's LUT partials ----
    acc = pb.svm_acc + partial
    sums = acc + packed.svm_bias[vid]
    signs = ((sums >= 0) & packed.svm_hvalid[vid]).astype(jnp.int32)
    sign_code = (signs << jnp.arange(signs.shape[1])[None, :]).sum(axis=1)
    svm_label = packed.svm_pred_table[vid, sign_code]
    svm_result = jnp.where(packed.svm_pred_enable[vid], svm_label, -1)

    # ---- result select + forwarding passthrough ----
    # Non-REQUEST packets come out bit-identical: their codes / svm_acc
    # intermediates and rslt are never overwritten (classification must not
    # disturb forwarded traffic, paper §6.1).
    is_req = pb.ptype == PacketType.REQUEST
    codes = jnp.where(is_req[:, None], codes, pb.codes)
    acc = jnp.where(is_req[:, None], acc, pb.svm_acc)
    result = jnp.where(pb.mid == MID_SVM, svm_result, tree_result)
    result = jnp.where(vid_ok, result, -1)
    rslt = jnp.where(is_req & (result >= 0), result, pb.rslt)
    return dataclasses.replace(pb, codes=codes, svm_acc=acc, rslt=rslt)


class SwitchEngine:
    """One programmable data plane: jit-compiled once per (profile, batch shape).

    Hosts a model zoo: ``profile.max_versions`` tree programs and as many
    SVMs, resident simultaneously, dispatched per packet by (MID, VID).
    """

    def __init__(self, profile: PlaneProfile, *, mode: str | None = None,
                 use_image: bool = True) -> None:
        """``mode`` picks the kernel path: ``None`` auto-selects (pallas on
        TPU, ref elsewhere); ``"ref"`` / ``"interpret"`` / ``"pallas"`` force
        one and run classify as a single fused walk→vote→svm launch; an
        ``"unfused[-<kernel mode>]"`` prefix restores the pre-fusion
        three-launch classify, and ``"layerwise[-<kernel mode>]"``
        additionally swaps the fused tree walk for the per-layer kernel scan
        (L + 2 launches instead of 1).

        ``use_image=False`` disables exec-image binding, so every classify
        reruns the operand prep the image precomputes — the pre-image
        behavior, kept so ``benchmarks/zoo_swap.py`` can report the
        install-vs-classify cost split."""
        self.profile = profile
        self.mode = mode
        self.use_image = use_image
        self._fn = jax.jit(
            functools.partial(
                _classify_impl, n_classes=profile.max_classes, mode=mode,
                use_image=use_image,
            )
        )

    def classify(self, packed: PackedProgram, batch: PacketBatch) -> PacketBatch:
        return self._fn(packed, batch)

    def lower(self, packed: PackedProgram, batch: PacketBatch):
        """The jitted classify lowered for these shapes (``jax.stages``):
        ``.compile().as_text()`` shows which kernel path the backend built,
        and reuses the executable a matching ``classify`` already compiled."""
        return self._fn.lower(packed, batch)

    def cache_size(self) -> int:
        """Number of distinct traces — must stay 1 across model swaps."""
        return self._fn._cache_size()

    def empty(self) -> PackedProgram:
        return empty_program(self.profile)

    def install(self, packed: PackedProgram, program: TableProgram,
                stages: set[int] | None = None, *,
                vid: int | None = None) -> PackedProgram:
        return install_program(packed, program, self.profile, stages=stages,
                               vid=vid)

    def evict(self, packed: PackedProgram, *, vid: int,
              kind: str = "all") -> PackedProgram:
        return evict_program(packed, self.profile, vid=vid, kind=kind)
