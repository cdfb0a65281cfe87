"""Public kernel API: jit'd wrappers that dispatch Pallas vs the jnp oracle.

On TPU the Pallas path compiles natively; on CPU (this container) the default
is the XLA-compiled ``ref`` oracle, with ``mode="interpret"`` available to
execute the actual Pallas kernel bodies in the interpreter (the kernel-sweep
tests do exactly that and ``assert_allclose`` against ``ref``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.classify_fused import classify_fused_pallas_v
from repro.kernels.decode_attn import decode_attn_pallas
from repro.kernels.forest_vote import (
    forest_predict_vote_pallas,
    forest_predict_vote_pallas_v,
)
from repro.kernels.svm_lookup import svm_lookup_pallas, svm_lookup_pallas_v
from repro.kernels.tcam_match import tcam_match_pallas, tcam_match_pallas_v
from repro.kernels.tree_walk import tree_walk_pallas_v

__all__ = [
    "tcam_match", "svm_lookup", "forest_predict_vote", "decode_attn",
    "tcam_match_v", "svm_lookup_v", "forest_predict_vote_v", "tree_walk_v",
    "classify_fused_v",
    "base_mode", "count_pallas_launches", "count_operand_prep_ops",
]


def _resolve(mode: str | None) -> str:
    if mode is not None:
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def base_mode(mode: str | None) -> str | None:
    """Strip a ``layerwise``/``unfused`` prefix down to the underlying kernel
    mode.

    ``"layerwise"`` selects the scan-of-``tcam_match_v`` tree-walk fallback
    and ``"unfused"`` the pre-megakernel three-launch classify; an optional
    suffix pins the per-stage kernel mode (``"layerwise-ref"``,
    ``"unfused-interpret"``, ...).  Kernels beneath the prefixed path only
    understand the base mode, so dispatchers route them through this.
    """
    if mode is None:
        return mode
    for prefix in ("layerwise", "unfused"):
        if mode.startswith(prefix):
            return mode[len(prefix):].lstrip("-") or None
    return mode


def _sum_jaxpr_eqns(fn, args, kwargs, visit) -> int:
    """Trace ``fn`` and sum counts over its equations, walking nested
    sub-jaxprs (pjit, scan bodies, ...).  ``visit(eqn, mult)`` returns
    ``(count, descend)``; ``mult`` is the iteration multiplier accumulated
    from enclosing ``scan``s.  Both jaxpr counters below share this traversal
    so a fix to it (e.g. a new higher-order primitive) cannot silently reach
    only one of them."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)

    def walk(jaxpr, mult) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            count, descend = visit(eqn, mult)
            n += count
            if not descend:
                continue
            sub_mult = mult * (eqn.params.get("length", 1)
                               if eqn.primitive.name == "scan" else 1)
            for p in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                    p, is_leaf=lambda x: hasattr(x, "jaxpr") or hasattr(x, "eqns")
                ):
                    if hasattr(sub, "jaxpr"):
                        sub = sub.jaxpr
                    if hasattr(sub, "eqns"):
                        n += walk(sub, sub_mult)
        return n

    return walk(closed.jaxpr, 1)


def count_pallas_launches(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` launches one invocation of ``fn`` issues.

    Traces ``fn`` and walks the jaxpr; a kernel under ``lax.scan`` counts
    once per iteration (a scanned kernel *launches* every step — exactly the
    per-layer overhead the fused tree walk removes).  Benchmarks and the
    single-launch acceptance test both use this.
    """
    def visit(eqn, mult):
        if eqn.primitive.name == "pallas_call":
            return mult, False   # nothing beneath launches separately
        return 0, True

    return _sum_jaxpr_eqns(fn, args, kwargs, visit)


def count_operand_prep_ops(fn, *args, **kwargs) -> int:
    """Number of table-shaped (ndim >= 3) intermediate ops one invocation of
    ``fn`` computes *outside* of ``pallas_call`` kernel bodies.

    Per-packet arrays are at most 2-D (``[B, T]`` codes, ``[B, F]`` features),
    so any >= 3-D equation in the traced jaxpr is operand prep — one-hot
    ``fsel`` construction, no-match entry padding, LUT re-layout.  With the
    install-time ``ExecImage`` bound, classify must trace to **zero** such
    equations: every table operand flows from the jaxpr inputs straight into
    the kernel launches.  The exec-image acceptance test pins this.

    A prep op inside a ``lax.scan`` body reruns every iteration, so it
    multiplies through the accumulated scan length — the same convention as
    ``count_pallas_launches`` (both counters share ``_sum_jaxpr_eqns``, and
    the fused-path unit test pins the multiplied counts).
    """
    def visit(eqn, mult):
        if eqn.primitive.name == "pallas_call":
            return 0, False   # in-kernel math is not per-call HBM-side prep
        return mult * int(any(getattr(v.aval, "ndim", 0) >= 3
                              for v in eqn.outvars)), True

    return _sum_jaxpr_eqns(fn, args, kwargs, visit)


def tcam_match(codes, features, code_value, code_mask, fid, f_lo, f_hi,
               set_bit, valid, shift, *, mode: str | None = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.tcam_match(codes, features, code_value, code_mask, fid,
                              f_lo, f_hi, set_bit, valid, shift)
    return tcam_match_pallas(codes, features, code_value, code_mask, fid,
                             f_lo, f_hi, set_bit, valid, shift,
                             interpret=(m == "interpret"))


def svm_lookup(features, lut, bias, *, mode: str | None = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.svm_lookup(features, lut, bias)
    return svm_lookup_pallas(features, lut, bias, interpret=(m == "interpret"))


def forest_predict_vote(codes, pred_codes, pred_labels, pred_valid, weights,
                        n_classes, *, mode: str | None = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.forest_predict_vote(codes, pred_codes, pred_labels,
                                       pred_valid, weights, n_classes)
    return forest_predict_vote_pallas(codes, pred_codes, pred_labels,
                                      pred_valid, weights, n_classes,
                                      interpret=(m == "interpret"))


def tcam_match_v(codes, features, vid, code_value, code_mask, fid, f_lo, f_hi,
                 set_bit, valid, shift, *, mode: str | None = None, prep=None):
    """Version-indexed tcam_match: tables are [V, T, E], packet b uses vid[b].

    ``prep`` binds install-time operands (``tiling.prep_tcam_match``); the
    ref oracle rebuilds from the source tables and ignores it.
    """
    m = _resolve(mode)
    if m == "ref":
        return ref.tcam_match_v(codes, features, vid, code_value, code_mask,
                                fid, f_lo, f_hi, set_bit, valid, shift)
    return tcam_match_pallas_v(codes, features, vid, code_value, code_mask,
                               fid, f_lo, f_hi, set_bit, valid, shift,
                               prep=prep, interpret=(m == "interpret"))


def tree_walk_v(codes, features, vid, code_value, code_mask, fid, f_lo, f_hi,
                set_bit, valid, layer_shift, *, mode: str | None = None,
                prep=None):
    """Fused multi-layer tree walk: tables are [V, L, T, E], packet b walks
    all L layers of version ``vid[b]`` in one kernel launch.

    ``prep`` binds install-time operands (``tiling.prep_tree_walk``, the
    plane's ``ExecImage``) so the launch does zero per-call operand prep.
    The ref oracle and the layerwise fallback work from the source tables
    and ignore ``prep``.

    ``mode="layerwise[-<kernel mode>]"`` selects the pre-fusion fallback — a
    ``lax.scan`` of ``tcam_match_v`` over the layer axis (L launches) — for
    deployments where per-layer staging still matters (e.g. partial
    per-layer device placements that want layer-granular kernels).
    """
    m = _resolve(mode)
    if m.startswith("layerwise"):
        sub = base_mode(m)

        def step(c, x):
            cv, cm, fd, lo, hi, bit, vld, shift = x
            return tcam_match_v(c, features, vid, cv, cm, fd, lo, hi, bit,
                                vld, shift, mode=sub), None

        per_layer = lambda a: jnp.moveaxis(a, 1, 0)
        xs = (per_layer(code_value), per_layer(code_mask), per_layer(fid),
              per_layer(f_lo), per_layer(f_hi), per_layer(set_bit),
              per_layer(valid), layer_shift)
        out, _ = jax.lax.scan(step, codes, xs)
        return out
    if m == "ref":
        return ref.tree_walk_v(codes, features, vid, code_value, code_mask,
                               fid, f_lo, f_hi, set_bit, valid, layer_shift)
    return tree_walk_pallas_v(codes, features, vid, code_value, code_mask,
                              fid, f_lo, f_hi, set_bit, valid, layer_shift,
                              prep=prep, interpret=(m == "interpret"))


def svm_lookup_v(features, vid, lut, bias, *, mode: str | None = None,
                 prep=None):
    """Version-indexed svm_lookup: lut is [V, H, F, L], packet b uses vid[b].

    ``prep`` binds the install-time chunked LUT layout
    (``tiling.prep_svm_lookup``); the ref oracle ignores it.
    """
    m = _resolve(mode)
    if m == "ref":
        return ref.svm_lookup_v(features, vid, lut, bias)
    return svm_lookup_pallas_v(features, vid, lut, bias, prep=prep,
                               interpret=(m == "interpret"))


def forest_predict_vote_v(codes, vid, pred_codes, pred_labels, pred_valid,
                          weights, n_classes, *, mode: str | None = None,
                          prep=None):
    """Version-indexed dt_predict + voting: tables are [V, T, P].

    ``prep`` binds the install-time validity/weight layouts
    (``tiling.prep_forest_vote``); the ref oracle ignores it.
    """
    m = _resolve(mode)
    if m == "ref":
        return ref.forest_predict_vote_v(codes, vid, pred_codes, pred_labels,
                                         pred_valid, weights, n_classes)
    return forest_predict_vote_pallas_v(codes, vid, pred_codes, pred_labels,
                                        pred_valid, weights, n_classes,
                                        prep=prep,
                                        interpret=(m == "interpret"))


def classify_fused_v(codes, features, vid, code_value, code_mask, fid, f_lo,
                     f_hi, set_bit, valid, layer_shift, pred_codes,
                     pred_labels, pred_valid, weights, lut, bias, n_classes,
                     *, mode: str | None = None, prep=None,
                     unfused_prep=None):
    """Whole-classify megakernel: walk -> vote -> svm in **one**
    ``pallas_call`` (``kernels/classify_fused.py``), returning (final codes
    [B, T], vote label [B], svm sums [B, H]).

    ``prep`` binds the install-time quantized operand layout
    (``tiling.prep_classify_fused``, the plane's ``ExecImage.fused``); the
    ref oracle and the fallback paths ignore it.

    ``mode="unfused[-<kernel mode>]"`` selects the pre-fusion three-launch
    classify — the individual stage dispatchers above, binding
    ``unfused_prep`` = (walk, forest, svm) operand groups when given — and
    ``mode="layerwise[-<kernel mode>]"`` additionally swaps the fused walk
    for the per-layer kernel scan (L + 2 launches).

    A ``vid`` of -1 marks a row with no version.  The fused kernel leaves
    it as it came (codes passed through, label 0, svm sums 0); the ref
    oracle and the fallback paths run it under slot 0, and the caller
    discards what they compute for it.
    """
    m = _resolve(mode)
    if m == "ref" or m.startswith(("layerwise", "unfused")):
        vid = jnp.maximum(vid, 0)
    if m == "ref":
        return ref.classify_fused_v(
            codes, features, vid, code_value, code_mask, fid, f_lo, f_hi,
            set_bit, valid, layer_shift, pred_codes, pred_labels, pred_valid,
            weights, lut, bias, n_classes)
    if m.startswith(("layerwise", "unfused")):
        sub = base_mode(m)
        walk_prep, forest_prep, svm_prep = unfused_prep or (None, None, None)
        codes_out = tree_walk_v(
            codes, features, vid, code_value, code_mask, fid, f_lo, f_hi,
            set_bit, valid, layer_shift,
            mode=m if m.startswith("layerwise") else sub, prep=walk_prep)
        label, _per_tree = forest_predict_vote_v(
            codes_out, vid, pred_codes, pred_labels, pred_valid, weights,
            n_classes, mode=sub, prep=forest_prep)
        sums = svm_lookup_v(features, vid, lut, bias, mode=sub, prep=svm_prep)
        return codes_out, label, sums
    return classify_fused_pallas_v(
        codes, features, vid, code_value, code_mask, fid, f_lo, f_hi,
        set_bit, valid, layer_shift, pred_codes, pred_labels, pred_valid,
        weights, lut, bias, n_classes, prep=prep,
        interpret=(m == "interpret"))


def decode_attn(q, k, v, kv_len, *, mode: str | None = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.decode_attn(q, k, v, kv_len)
    return decode_attn_pallas(q, k, v, kv_len, interpret=(m == "interpret"))
