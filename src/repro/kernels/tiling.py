"""Shared MXU/VPU tiling helpers + the install-time operand-prep entry points.

``tcam_match`` (per-layer) and ``tree_walk`` (fused multi-layer) pad their
entry tables with one no-match convention; it lives here once so a change to
the padding contract cannot silently diverge between the kernels:

  * padded entries mask **all** code bits against value 0,
  * and carry an empty feature range [1, 0],

so a padded entry can never match any packet.  The one-hot feature-select
matrix likewise zeroes invalid entries' rows (they select no feature).

The ``prep_*`` functions are the **single install-time entry point** for
turning source tables into the kernel-ready operands a ``pallas_call`` binds
directly (the plane's ``ExecImage``, see ``docs/ARCHITECTURE.md``).  Each
kernel wrapper accepts the matching ``*Operands`` tuple via ``prep=`` and,
when it is absent, falls back to calling the same ``prep_*`` function per
call — so the prepped and unprepped paths cannot diverge semantically.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LANES", "pad_to", "lane_pad", "bitpack_last", "pad_entry_tables",
    "feature_select_matrix",
    "TreeWalkOperands", "TcamOperands", "SvmOperands", "ForestOperands",
    "ClassifyFusedOperands",
    "prep_tree_walk", "prep_tcam_match", "prep_svm_lookup", "prep_forest_vote",
    "prep_classify_fused",
]

LANES = 128
SVM_CHUNK_F = 8     # feature chunk per svm_lookup grid step
SVM_SUBLANES = 8    # hyperplane-axis padding multiple


def pad_to(x: jax.Array, axis: int, mult: int, fill=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def lane_pad(n: int) -> int:
    """Smallest multiple of the 128-lane dimension >= n."""
    return ((n + LANES - 1) // LANES) * LANES


def bitpack_last(x: jax.Array) -> jax.Array:
    """Pack a 0/1 array into uint32 words along its last axis (length must be
    a multiple of 32): word ``w`` bit ``j`` holds ``x[..., 32*w + j]``.

    Inputs are collapsed through ``!= 0`` first, so this is lossless exactly
    for {0, 1}-valued tables — which ``set_bit`` / ``valid`` / ``pred_valid``
    are by the translator contract (each dt_layer writes one status bit).
    """
    *lead, n = x.shape
    if n % 32:
        raise ValueError(f"bitpack_last needs a 32-multiple last axis, got {n}")
    bits = (x != 0).astype(jnp.uint32).reshape(*lead, n // 32, 32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=-1, dtype=jnp.uint32)


def pad_entry_tables(axis: int, code_value, code_mask, f_lo, f_hi, set_bit,
                     valid):
    """Pad the entry axis to a 128-lane multiple with the no-match fills;
    range tables are cast to f32 (the in-kernel compare dtype) and ``valid``
    to int32 (Pallas block dtype)."""
    pad_e = lambda a, fill=0: pad_to(a, axis, LANES, fill)
    return (pad_e(code_value),
            pad_e(code_mask, fill=np.uint32(0xFFFFFFFF)),  # mask all, value 0
            pad_e(f_lo.astype(jnp.float32), fill=1.0),
            pad_e(f_hi.astype(jnp.float32), fill=0.0),     # empty range
            pad_e(set_bit.astype(jnp.uint32)),
            pad_e(valid.astype(jnp.int32)))


def feature_select_matrix(fid: jax.Array, valid: jax.Array,
                          f_pad: int) -> jax.Array:
    """One-hot feature selector for the MXU ``feats @ fsel^T`` indirection,
    entry axis (``fid``'s last) padded to 128 lanes; invalid entries select
    nothing (all-zero row)."""
    fsel = jax.nn.one_hot(fid, f_pad, dtype=jnp.float32) * valid[..., None]
    return pad_to(fsel, fid.ndim - 1, LANES)


# --------------------------------------------------------------------------
# Install-time operand prep (the ExecImage building blocks)
# --------------------------------------------------------------------------
class TreeWalkOperands(NamedTuple):
    """Kernel-ready operands for the fused ``tree_walk_pallas_v`` launch."""

    fsel: jax.Array    # f32  [V, T, L*E_pad, F_pad] flattened one-hot selector
    cv: jax.Array      # u32  [V, L, T, E_pad]
    cm: jax.Array      # u32  [V, L, T, E_pad]  (pad: mask all vs value 0)
    flo: jax.Array     # f32  [V, L, T, E_pad]  (pad: 1.0 — empty range)
    fhi: jax.Array     # f32  [V, L, T, E_pad]  (pad: 0.0)
    bit: jax.Array     # u32  [V, L, T, E_pad]
    valid: jax.Array   # i32  [V, L, T, E_pad]


class TcamOperands(NamedTuple):
    """Kernel-ready operands for one per-layer ``tcam_match_pallas_v`` launch."""

    fsel: jax.Array    # f32  [V, T, E_pad, F_pad]
    cv: jax.Array      # u32  [V, T, E_pad]
    cm: jax.Array      # u32  [V, T, E_pad]
    flo: jax.Array     # f32  [V, T, E_pad]
    fhi: jax.Array     # f32  [V, T, E_pad]
    bit: jax.Array     # u32  [V, T, E_pad]
    valid: jax.Array   # i32  [V, T, E_pad]


class SvmOperands(NamedTuple):
    """Kernel-ready operands for ``svm_lookup_pallas_v``."""

    lut: jax.Array     # f32  [V, n_chunks, chunk_f*levels, H_pad]
    bias: jax.Array    # i32  [V, H_pad]


class ForestOperands(NamedTuple):
    """Kernel-ready operands for ``forest_predict_vote_pallas_v`` (the
    ``pred_codes``/``pred_labels`` tables bind as-is and need no prep)."""

    valid: jax.Array    # i32 [V, T, P]
    weights: jax.Array  # f32 [V, 1, T]


def prep_tree_walk(code_value, code_mask, fid, f_lo, f_hi, set_bit, valid,
                   f_pad: int) -> TreeWalkOperands:
    """Source ``[V, L, T, E]`` dt_layer tables -> fused-walk operands.

    ``f_pad`` is the lane-padded feature width the classify path will present
    (``lane_pad(max_features)``) — the fsel matmul operand must match it.
    """
    V, L, T, E = fid.shape
    fsel = feature_select_matrix(fid, valid, f_pad)   # [V, L, T, E_pad, F_pad]
    cv, cm, flo, fhi, bit, vld = pad_entry_tables(
        3, code_value, code_mask, f_lo, f_hi, set_bit, valid)
    e_pad = cv.shape[3]
    # [V, L, T, E_pad, F_pad] -> [V, T, L*E_pad, F_pad]: one matmul operand
    # covering every layer's entries.
    fsel = fsel.transpose(0, 2, 1, 3, 4).reshape(V, T, L * e_pad, f_pad)
    return TreeWalkOperands(fsel, cv, cm, flo, fhi, bit, vld)


def prep_tcam_match(code_value, code_mask, fid, f_lo, f_hi, set_bit, valid,
                    f_pad: int) -> TcamOperands:
    """Source ``[V, T, E]`` single-layer tables -> per-layer kernel operands."""
    fsel = feature_select_matrix(fid, valid, f_pad)   # [V, T, E_pad, F_pad]
    padded = pad_entry_tables(2, code_value, code_mask, f_lo, f_hi, set_bit,
                              valid)
    return TcamOperands(fsel, *padded)


def prep_svm_lookup(lut, bias, *, chunk_f: int = SVM_CHUNK_F) -> SvmOperands:
    """Source ``[V, H, F, levels]`` product LUTs -> chunked f32 MXU operand.

    Feature axis padded to ``chunk_f`` (padded columns match feature value
    -1, never a real level, so they contribute 0), hyperplane axis padded to
    the sublane multiple, then laid out ``[V, n_chunks, chunk_f*levels,
    H_pad]`` so each grid step streams one (version, chunk) slice.
    """
    V, H, F, levels = lut.shape
    lut_p = pad_to(pad_to(lut, 1, SVM_SUBLANES), 2, chunk_f)
    bias_p = pad_to(bias, 1, SVM_SUBLANES)
    h_pad = lut_p.shape[1]
    n_chunks = lut_p.shape[2] // chunk_f
    lut_r = (
        lut_p.transpose(0, 2, 3, 1)
        .reshape(V, n_chunks, chunk_f * levels, h_pad)
        .astype(jnp.float32)
    )
    return SvmOperands(lut_r, bias_p)


def prep_forest_vote(pred_valid, weights) -> ForestOperands:
    """Source ``[V, T, P]`` validity + ``[V, T]`` vote weights -> Pallas block
    dtypes/layouts (int32 validity, ``[V, 1, T]`` f32 weights)."""
    V, T = weights.shape
    return ForestOperands(pred_valid.astype(jnp.int32),
                          weights.reshape(V, 1, T).astype(jnp.float32))


class ClassifyFusedOperands(NamedTuple):
    """Kernel-ready operands for the whole-classify megakernel
    (``classify_fused_pallas_v``): walk -> vote -> svm in one launch.

    Quantized widths (``prep_classify_fused(..., quantize=True)``) shrink
    what the launch streams per grid step without changing a single output
    bit: feature ids and range bounds are int16 (lossless for
    ``feature_width <= 15``), leaf labels int8 (``n_classes <= 127``), and
    the three {0,1} tables (``set_bit``/``valid``/``pred_valid``) are
    bit-packed into uint32 words — 32 entries per lane.  The f32 width
    (``quantize=False``) keeps i32/f32 element types in the identical layout;
    both compile against the same kernel, which upcasts in VMEM.  SVM LUT
    *values* stay f32 in both widths: per-chunk partials must remain
    integer-exact (< 2**24, see ``svm_lookup.py``).

    Unlike ``TreeWalkOperands`` there is no precomputed one-hot ``fsel``
    matmul operand: the fused kernel rebuilds the per-(layer, tree) one-hot
    selector from ``fid`` in VMEM, so the dominant f32 ``[V, T, L*E_pad,
    F_pad]`` stream of the unfused path disappears entirely.
    """

    # tree walk, [V, L, T, E_pad] (WP = E_pad // 32)
    fid: jax.Array       # i16 (quantized) | i32
    cv: jax.Array        # u32
    cm: jax.Array        # u32  (pad: mask all vs value 0)
    flo: jax.Array       # i16 (quantized) | f32  (pad: 1 — empty range)
    fhi: jax.Array       # i16 (quantized) | f32  (pad: 0)
    bitpk: jax.Array     # u32 [V, L, T, WP] bit-packed set_bit
    validpk: jax.Array   # u32 [V, L, T, WP] bit-packed valid
    # forest vote, [V, T, P] (PW = ceil32(P) // 32)
    pred_codes: jax.Array  # u32
    plab: jax.Array        # i8 (quantized) | i32
    pvalidpk: jax.Array    # u32 [V, T, PW] bit-packed pred_valid
    weights: jax.Array     # f32 [V, 1, T]
    # svm
    lut: jax.Array       # f32 [V, n_chunks, chunk_f*levels, H_pad]
    bias: jax.Array      # i32 [V, 1, H_pad]


def prep_classify_fused(code_value, code_mask, fid, f_lo, f_hi, set_bit,
                        valid, pred_codes, pred_labels, pred_valid, weights,
                        lut, bias, *, chunk_f: int = SVM_CHUNK_F,
                        quantize: bool = True) -> ClassifyFusedOperands:
    """Source tables of all three classify stages -> megakernel operands.

    Walk tables are ``[V, L, T, E]`` dt_layer state, predict tables
    ``[V, T, P]`` + ``[V, T]`` weights, svm ``[V, H, F, levels]`` + bias.
    ``quantize`` selects the narrow widths (see ``ClassifyFusedOperands``);
    it is a pure layout choice — both widths decode bit-identically.
    """
    V, L, T, E = fid.shape
    cv, cm, flo, fhi, bit, vld = pad_entry_tables(
        3, code_value, code_mask, f_lo, f_hi, set_bit, valid)
    # fid pad fill 0 is harmless: padded entries are masked out via the
    # bit-packed valid words before any match can use their selected feature.
    fid_p = pad_to(fid, 3, LANES)
    bitpk = bitpack_last(bit)
    validpk = bitpack_last(vld)
    if quantize:
        fid_p = fid_p.astype(jnp.int16)
        flo = flo.astype(jnp.int16)
        fhi = fhi.astype(jnp.int16)
        plab = pred_labels.astype(jnp.int8)
    else:
        fid_p = fid_p.astype(jnp.int32)
        plab = pred_labels.astype(jnp.int32)
    pvalidpk = bitpack_last(pad_to(pred_valid.astype(jnp.uint32), 2, 32))
    w_r = weights.reshape(V, 1, T).astype(jnp.float32)
    lut_r, bias_p = prep_svm_lookup(lut, bias, chunk_f=chunk_f)
    return ClassifyFusedOperands(
        fid=fid_p, cv=cv, cm=cm, flo=flo, fhi=fhi, bitpk=bitpk,
        validpk=validpk, pred_codes=pred_codes.astype(jnp.uint32), plab=plab,
        pvalidpk=pvalidpk, weights=w_r, lut=lut_r,
        bias=bias_p.reshape(V, 1, -1))
