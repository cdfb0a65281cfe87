"""Pallas TPU kernel: the whole-classify megakernel (walk -> vote -> svm).

Pre-fusion, one classify issued three launches — ``tree_walk`` produced the
per-packet status codes, which round-tripped through HBM into
``forest_vote``'s compare-reduce and (independently) ``svm_lookup`` streamed
the feature tile a second time.  This kernel runs all three stages inside
**one** grid program, so classify drops from 3 ``pallas_call``s to 1:

  1. *walk* — the multi-layer ternary walk of ``tree_walk.py``, per tree: a
     ``fori_loop`` over L layer-indexed table slices with the same masked
     code equality + range compare, and a priority encode that takes the
     lowest matching entry as a min over the masked entry iota.  The
     per-(layer, tree) one-hot feature selector is rebuilt in VMEM from the
     int16 ``fid`` table (an iota compare + MXU matmul), which deletes the
     precomputed f32 ``[V, T, L*E_pad, F_pad]`` ``fsel`` stream entirely —
     the largest operand of the unfused path.
  2. *vote* — the resulting ``[Bb, T]`` codes never leave VMEM; they feed the
     exact compare-reduce + weighted one-hot voting of ``forest_vote.py``
     (identical accumulation shapes and order, so no new float divergence).
  3. *svm* — the feature tile, already VMEM-resident from the walk, drives
     the chunked one-hot LUT contraction of ``svm_lookup.py`` as a static
     chunk loop; per-chunk f32 partials stay integer-exact (< 2**24) and are
     rounded once by the wrapper.

Quantized operand layouts (``tiling.prep_classify_fused``): feature ids and
range bounds stream as int16, leaf labels as int8, and the three {0,1}
tables (``set_bit``/``valid``/``pred_valid``) as bit-packed uint32 words
unpacked per layer in VMEM — all lossless, upcast in-kernel, so quantized
and f32 layouts decode bit-identical classifications (pinned by the
round-trip property tests).

Model-zoo dispatch is a grouped grid: the wrapper sorts the rows that need
classifying by ``vid`` into blocks that each hold one version's rows
(``group_rows``, in XLA inside the same jit), and a scalar-prefetched block
-> version map picks each grid step's tables.  The kernel call has a jit of
its own (``_classify_fused_grouped``, the kernel's name in a device trace):
the buckets that group into the same number of blocks share one trace of
the kernel.  A step runs one version over one block, so a batch costs
``sum_v ceil(n_v / block_b)`` steps, not ``ceil(B / block_b) * V``, and
consecutive blocks of a version reuse its resident tables.  Rows with no
version (``vid`` outside ``[0, V)``: the plane's padding and forwarded
packets) join no block and come out as before: codes passed through,
label 0, svm sums 0.  The same grouping runs
at every V; at V = 1 it only drops the rows of no version.  ``grid_rows``
is the same count in numpy, for the host.

The body is written to what Mosaic lowers for the TPU: no cumsum (both
first-match and first-best are a min over a masked iota), no reduction over
unsigned types, no minor-dim reshape in the bit unpack, the per-layer shift
read as a scalar from SMEM, and blocks that are legal at any V.
``tests/test_tpu_compile.py`` compiles it for a described v5e.

Per-step VMEM at the reference config (block_b=256, L=32, T=8, E_pad=128,
F_pad=128, P=256, levels=256, H_pad=16): quantized operands ~1.6 MiB +
in-kernel transients (svm one-hot 2 MiB, vote compare 2 MiB, walk selector
~0.2 MiB) ~ 6.0 MiB — under the 16 MiB ceiling and independent of V, so
V=8 zoos fit the same plan (see ``kernels/budgets.py``: ``classify_fused``
vs the f32-width counterfactual ``classify_fused_f32``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (
    LANES,
    SVM_CHUNK_F,
    SVM_SUBLANES,
    ClassifyFusedOperands,
    pad_to,
    prep_classify_fused,
)

__all__ = ["classify_fused_pallas_v", "block_rows", "grid_rows",
           "group_rows"]

# Both MXU contractions carry integers (feature values, LUT products) whose
# f32 sums must stay exact; a default-precision f32 matmul on the TPU rounds
# its operands to bf16.
_EXACT = jax.lax.Precision.HIGHEST
# Rows in a grid step's batch block before ``block_rows`` halves it.
BLOCK_B = 256
# The grouping around the kernel is written in ``lax``: a ``jnp`` call such
# as ``where``, ``sum`` or an index traces a function of its own, again for
# every batch shape, and a serving front warms a dozen bucket shapes.
_I32 = jnp.int32
_ROW_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))
_ROW_SCATTER = lax.ScatterDimensionNumbers(
    update_window_dims=(), inserted_window_dims=(0,),
    scatter_dims_to_operand_dims=(0,))


def _unpack_bits(words, n_words: int, out_len: int):
    """uint32 words [..., W] -> {0,1} uint32 [..., out_len] (little-endian
    within each word, matching ``tiling.bitpack_last``).

    Built lane-wise with no minor-dim reshape (Mosaic refuses the
    ``[W, 32] -> [W*32]`` shape cast): output lane ``j`` selects word
    ``j >> 5`` by a static compare-select over the ``W`` words, then shifts
    out bit ``j & 31``."""
    shape = words.shape[:-1] + (out_len,)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    word_of = lane >> 5
    word = jnp.zeros(shape, jnp.uint32)
    for w in range(n_words):
        word = jnp.where(word_of == w, words[..., w:w + 1], word)
    return (word >> (lane & 31).astype(jnp.uint32)) & jnp.uint32(1)


def block_rows(n_trees: int, n_leaves: int, n_entries: int, levels: int,
               block_b: int = BLOCK_B) -> int:
    """Rows in one grid step's batch block.  The largest in-kernel
    transients scale with it: the svm one-hot [block_b, chunk_f*levels],
    the vote compare [block_b, T, P] and the walk's [block_b, E_pad]
    compares; halve the tile before any would crowd VMEM."""
    e_pad = -(-n_entries // LANES) * LANES
    while block_b > 8 and \
            block_b * max(SVM_CHUNK_F * levels, n_trees * n_leaves,
                          4 * e_pad) * 4 > 4 * 1024 * 1024:
        block_b //= 2
    return block_b


def _rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]``: the whole rows of ``x`` at row indices ``idx``, each in
    range."""
    return lax.gather(x, lax.expand_dims(idx, (1,)), _ROW_GATHER,
                      (1,) + x.shape[1:],
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _sum(x: jax.Array, axis: int) -> jax.Array:
    return lax.reduce(x, np.int32(0), lax.add, (axis,))


def _spread(x: jax.Array, shape: tuple[int, ...], dim: int) -> jax.Array:
    """``x`` broadcast to ``shape`` along ``dim``."""
    return lax.broadcast_in_dim(x, shape, (dim,))


def group_rows(vid: jax.Array, n_versions: int, block_b: int):
    """Sort rows by version into blocks of ``block_b`` that each hold one
    version's rows.

    ``vid`` [B] int32; a row outside ``[0, V)`` joins no block.  Returns
    ``(src, dest, block_vid, n_used)``: grouped slot -> source row
    (``[n_blocks * block_b]``, filler slots read row 0), row -> grouped slot
    (``n_blocks * block_b`` for a row in no block), each block's version,
    and the blocks in use (``[1]``).  ``n_blocks = ceil(B / block_b) + V -
    1`` bounds ``sum_v ceil(n_v / block_b)``; blocks from ``n_used`` on
    carry the last used block's version, so their steps move no tables.
    """
    B = vid.shape[0]
    V = n_versions
    n_blocks = -(-B // block_b) + V - 1
    onehot = lax.convert_element_type(lax.eq(
        _spread(vid, (B, V), 0),
        lax.broadcasted_iota(jnp.int32, (B, V), 1)), jnp.int32)  # [B, V]
    blocks = lax.div(_sum(onehot, 0) + (block_b - 1), _I32(block_b))
    ends = lax.cumsum(blocks)                                    # [V]
    starts = ends - blocks
    # rank of a row among its version's rows, in row order (stable)
    rank = _sum((lax.cumsum(onehot, 0) - 1) * onehot, 1)
    start = _sum(_spread(starts, (B, V), 1) * onehot, 1)         # [B]
    dest = lax.select(lax.gt(_sum(onehot, 1), _I32(0)),
                      start * block_b + rank,
                      lax.full((B,), n_blocks * block_b, jnp.int32))
    src = lax.scatter(
        lax.full((n_blocks * block_b,), 0, jnp.int32),
        lax.expand_dims(dest, (1,)), lax.iota(jnp.int32, B), _ROW_SCATTER,
        mode=lax.GatherScatterMode.FILL_OR_DROP)
    n_used = lax.slice(ends, (V - 1,), (V,))                     # [1]
    j = lax.min(lax.iota(jnp.int32, n_blocks),
                _spread(lax.max(n_used - 1, _I32(0)), (n_blocks,), 0))
    after = lax.le(_spread(ends, (n_blocks, V), 1),
                   _spread(j, (n_blocks, V), 0))
    block_vid = lax.min(_sum(lax.convert_element_type(after, jnp.int32), 1),
                        _I32(V - 1))
    return src, dest, block_vid, n_used


def grid_rows(vid: np.ndarray, n_versions: int, block_b: int) -> int:
    """Rows the fused kernel's grid runs for ``vid`` (numpy, for the host):
    ``sum_v ceil(n_v / block_b) * block_b`` over the rows in ``[0, V)``, as
    ``group_rows`` blocks them."""
    vid = np.asarray(vid)
    ok = (vid >= 0) & (vid < n_versions)
    counts = np.bincount(vid[ok], minlength=n_versions)
    return int((-(-counts // block_b)).sum()) * block_b


def _kernel(block_vid_ref, n_used_ref, *refs, **dims):
    del block_vid_ref                   # read by the tables' index maps
    # Blocks past the used ones hold no rows: their steps do nothing.
    @pl.when(pl.program_id(0) < n_used_ref[0])
    def _run():
        _classify_block(*refs, **dims)


def _classify_block(codes_ref, feats_ref, fid_ref, cv_ref, cm_ref, flo_ref,
                    fhi_ref, bitpk_ref, validpk_ref, shift_ref, pc_ref,
                    plab_ref, pvpk_ref, w_ref, lut_ref, bias_ref,
                    out_codes_ref, out_label_ref, out_svm_ref, *,
                    n_layers: int, n_trees: int, e_pad: int, f_pad: int,
                    n_leaves: int, n_classes: int, n_chunks: int,
                    chunk_f: int, levels: int):
    """Walk, vote and svm for one block, every row under the block's
    version (the tables' index maps picked it)."""
    codes0 = codes_ref[...]                     # [Bb, T] uint32
    feats = feats_ref[...]                      # [Bb, F_pad] i16|i32
    feats_f = feats.astype(jnp.float32)
    Bb = feats.shape[0]
    wp = e_pad // 32
    entry = jax.lax.broadcasted_iota(jnp.int32, (Bb, e_pad), 1)

    # ---- stage 1: multi-layer walk, all T trees, codes stay in VMEM ----
    def walk_tree(t):
        row = slice(t, t + 1)                   # this tree's [1, E_pad] row

        def layer(l, codes):                    # codes [Bb, 1] uint32
            # One-hot feature selector rebuilt from the int16 fid row: the
            # MXU indirection of tree_walk without its precomputed f32 fsel.
            fid_l = fid_ref[0, l, row].astype(jnp.int32)    # [1, E_pad]
            fsel = (
                fid_l == jax.lax.broadcasted_iota(jnp.int32, (f_pad, e_pad), 0)
            ).astype(jnp.float32)               # [F_pad, E_pad]
            fv = jnp.dot(feats_f, fsel, precision=_EXACT,
                         preferred_element_type=jnp.float32)  # [Bb, E_pad]
            cv = cv_ref[0, l, row]
            cm = cm_ref[0, l, row]
            flo = flo_ref[0, l, row].astype(jnp.float32)
            fhi = fhi_ref[0, l, row].astype(jnp.float32)
            bit = _unpack_bits(bitpk_ref[0, l, row], wp, e_pad)
            valid = _unpack_bits(validpk_ref[0, l, row], wp, e_pad)
            code_ok = (codes & cm) == cv        # [Bb, E_pad]
            ok = code_ok & (fv >= flo) & (fv <= fhi) & (valid != 0)
            # Priority encode: the lowest matching entry wins (TCAM order),
            # as a min over the masked entry iota.
            first = jnp.min(jnp.where(ok, entry, e_pad), axis=1,
                            keepdims=True)      # [Bb, 1], e_pad = no hit
            b = jnp.max(jnp.where((entry == first) & (bit != 0), 1, 0),
                        axis=1, keepdims=True)
            shift = shift_ref[l].astype(jnp.uint32)
            new = codes | (b.astype(jnp.uint32) << shift)
            return jnp.where(first < e_pad, new, codes)

        return jax.lax.fori_loop(0, n_layers, layer, codes0[:, t:t + 1])

    codes = jnp.concatenate([walk_tree(t) for t in range(n_trees)], axis=1)

    # ---- stage 2: forest vote (forest_vote.py compare-reduce, verbatim) ----
    pc = pc_ref[0]                              # [T, P] uint32 (this version)
    plab = plab_ref[0].astype(jnp.int32)        # [T, P]
    pvalid = _unpack_bits(pvpk_ref[0], pvpk_ref.shape[-1], n_leaves
                          ).astype(jnp.int32)   # [T, P]
    eq = (codes[:, :, None] == pc[None]) & (pvalid[None] != 0)   # [Bb, T, P]
    per_tree = jnp.sum(jnp.where(eq, plab[None], 0), axis=2)     # [Bb, T]
    w = w_ref[0]                                # [1, T] f32
    onehot = (per_tree[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (Bb, n_trees, n_classes), 2)).astype(jnp.float32)
    scores = jnp.sum(onehot * w[0][None, :, None], axis=1)       # [Bb, C]
    best = jnp.max(scores, axis=1, keepdims=True)
    # Ties break to the lowest class (argmax order): min over the masked
    # class iota.
    classes = jax.lax.broadcasted_iota(jnp.int32, (Bb, n_classes), 1)
    label = jnp.min(jnp.where(scores >= best, classes, n_classes), axis=1,
                    keepdims=True)

    # ---- stage 3: svm LUT contraction (svm_lookup.py chunk loop, bias
    # first then chunks ascending — the int-exact accumulation order) ----
    feats_i = feats.astype(jnp.int32)
    acc = jnp.zeros(out_svm_ref.shape, jnp.float32) \
        + bias_ref[0].astype(jnp.float32)
    level = jax.lax.broadcasted_iota(jnp.int32, (Bb, chunk_f, levels), 2)
    for c in range(n_chunks):
        fc = feats_i[:, c * chunk_f:(c + 1) * chunk_f]   # [Bb, chunk_f]
        onehot_s = (fc[:, :, None] == level).astype(jnp.float32)
        acc = acc + jnp.dot(
            onehot_s.reshape(Bb, chunk_f * levels), lut_ref[0, c],
            precision=_EXACT,
            preferred_element_type=jnp.float32)          # [Bb, H_pad]

    out_codes_ref[...] = codes
    out_label_ref[...] = label
    out_svm_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("n_classes", "levels",
                                             "block_b", "interpret"))
def _classify_fused_grouped(block_vid, n_used, codes, feats, layer_shift,
                            prep: ClassifyFusedOperands, *, n_classes: int,
                            levels: int, block_b: int, interpret: bool):
    """The kernel over grouped rows: block ``j`` of ``codes``/``feats``
    under version ``block_vid[j]``, the first ``n_used`` blocks.  A jit of
    its own: its shapes depend on the batch only through the block count,
    so the buckets of one block count trace the kernel once."""
    n_blocks = block_vid.shape[0]
    T = codes.shape[1]
    F_pad = feats.shape[1]
    _, L, _, E_pad = prep.cv.shape
    WP = prep.bitpk.shape[3]
    P = prep.pred_codes.shape[2]
    PW = prep.pvalidpk.shape[2]
    _, n_chunks, _, H_pad = prep.lut.shape
    chunk_f = SVM_CHUNK_F

    def rows(j, bv, nu):
        # steps past the used blocks stay on the last one: no copy
        return jnp.minimum(j, jnp.maximum(nu[0] - 1, 0)), 0

    def table(ndim):
        return lambda j, bv, nu: (bv[j],) + (0,) * (ndim - 1)

    return pl.pallas_call(
        functools.partial(
            _kernel, n_layers=L, n_trees=T, e_pad=E_pad, f_pad=F_pad,
            n_leaves=P, n_classes=n_classes, n_chunks=n_chunks,
            chunk_f=chunk_f, levels=levels),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((block_b, T), rows),                  # codes
                pl.BlockSpec((block_b, F_pad), rows),              # feats
                pl.BlockSpec((1, L, T, E_pad), table(4)),          # fid
                pl.BlockSpec((1, L, T, E_pad), table(4)),          # cv
                pl.BlockSpec((1, L, T, E_pad), table(4)),          # cm
                pl.BlockSpec((1, L, T, E_pad), table(4)),          # flo
                pl.BlockSpec((1, L, T, E_pad), table(4)),          # fhi
                pl.BlockSpec((1, L, T, WP), table(4)),             # bitpk
                pl.BlockSpec((1, L, T, WP), table(4)),             # validpk
                pl.BlockSpec(memory_space=pltpu.SMEM),             # shift
                pl.BlockSpec((1, T, P), table(3)),                 # pred_codes
                pl.BlockSpec((1, T, P), table(3)),                 # plab
                pl.BlockSpec((1, T, PW), table(3)),                # pvalidpk
                pl.BlockSpec((1, 1, T), table(3)),                 # weights
                pl.BlockSpec((1, n_chunks, chunk_f * levels, H_pad),
                             table(4)),                            # lut
                pl.BlockSpec((1, 1, H_pad), table(3)),             # bias
            ],
            out_specs=[
                pl.BlockSpec((block_b, T), rows),
                pl.BlockSpec((block_b, 1), rows),
                pl.BlockSpec((block_b, H_pad), rows),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks * block_b, T), codes.dtype),
            jax.ShapeDtypeStruct((n_blocks * block_b, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks * block_b, H_pad), jnp.float32),
        ],
        interpret=interpret,
    )(block_vid, n_used, codes, feats, prep.fid, prep.cv, prep.cm,
      prep.flo, prep.fhi, prep.bitpk, prep.validpk,
      layer_shift, prep.pred_codes,
      prep.plab, prep.pvalidpk, prep.weights, prep.lut, prep.bias)


@functools.partial(jax.jit, static_argnames=("n_classes", "quantize",
                                             "block_b", "interpret"))
def classify_fused_pallas_v(
    codes: jax.Array,        # uint32 [B, T]
    features: jax.Array,     # int32 [B, F]
    vid: jax.Array,          # int32 [B] model version per packet, -1: none
    code_value: jax.Array,   # uint32 [V, L, T, E]
    code_mask: jax.Array,
    fid: jax.Array,          # int32 [V, L, T, E]
    f_lo: jax.Array,
    f_hi: jax.Array,
    set_bit: jax.Array,      # uint32 [V, L, T, E], {0, 1}
    valid: jax.Array,        # bool [V, L, T, E]
    layer_shift: jax.Array,  # int32 [L] status-code bit per layer
    pred_codes: jax.Array,   # uint32 [V, T, P]
    pred_labels: jax.Array,  # int32 [V, T, P]
    pred_valid: jax.Array,   # bool [V, T, P]
    weights: jax.Array,      # float32 [V, T]
    lut: jax.Array,          # int32 [V, H, F, levels]
    bias: jax.Array,         # int32 [V, H]
    n_classes: int,
    *,
    prep: ClassifyFusedOperands | None = None,
    quantize: bool = True,
    block_b: int = BLOCK_B,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One launch for the whole classify: returns (codes [B, T] uint32,
    vote label [B] int32, svm sums [B, H] int32).  A row whose ``vid`` is
    outside ``[0, V)`` is classified under no version: codes passed
    through, label 0, svm sums 0."""
    T = codes.shape[1]
    V, L, _, _ = code_value.shape
    _, H, F_svm, levels = lut.shape
    P = pred_codes.shape[2]
    if prep is None:
        # Per-call fallback (standalone/test path): the same prep the plane
        # runs once per install and binds via ``prep=``.
        prep = prep_classify_fused(
            code_value, code_mask, fid, f_lo, f_hi, set_bit, valid,
            pred_codes, pred_labels, pred_valid, weights, lut, bias,
            quantize=quantize)
    E_pad = prep.cv.shape[3]
    H_pad = prep.bias.shape[2]
    chunk_f = SVM_CHUNK_F
    n_chunks = -(-F_svm // chunk_f)
    # Source-derived shape validation: a prep built for a different profile
    # cannot slip through (same stance as tree_walk / svm_lookup).
    if prep.cv.shape != (V, L, T, E_pad) or \
            prep.lut.shape != (V, n_chunks, chunk_f * levels, H_pad) or \
            H_pad != -(-H // SVM_SUBLANES) * SVM_SUBLANES or \
            prep.pred_codes.shape != (V, T, P):
        raise ValueError(
            f"prepped operand shapes {prep.cv.shape}/{prep.lut.shape}/"
            f"{prep.pred_codes.shape} do not match this launch — the exec "
            "image was built for a different profile")

    feat_dtype = jnp.int16 if prep.fid.dtype == jnp.int16 else jnp.int32
    # -1 fill: svm chunk columns beyond F match no quantization level (zero
    # contribution); walk entries never select a padded column (fid < F).
    feats = pad_to(features.astype(feat_dtype), 1, LANES, fill=-1)
    F_pad = feats.shape[1]
    if n_chunks * chunk_f > F_pad:
        raise ValueError(
            f"svm chunk span {n_chunks * chunk_f} exceeds the lane-padded "
            f"feature width {F_pad}")

    block_b = block_rows(T, P, E_pad, levels, block_b)
    vid = vid.astype(jnp.int32)
    src, back, block_vid, n_used = group_rows(vid, V, block_b)
    back = lax.min(back, _I32(block_vid.shape[0] * block_b - 1))

    out_codes, out_label, out_svm = _classify_fused_grouped(
        block_vid, n_used, _rows(codes, src), _rows(feats, src),
        layer_shift.astype(jnp.int32), prep, n_classes=n_classes,
        levels=levels, block_b=block_b, interpret=interpret)
    # Back to row order; a row with no version keeps its codes, label 0
    # and svm sums 0.  Whole rows are gathered, then sliced: a gather of
    # part rows lowers to a loop over the rows on the TPU.
    B = vid.shape[0]
    mine = lax.bitwise_and(lax.ge(vid, _I32(0)), lax.lt(vid, _I32(V)))
    codes_out = lax.select(_spread(mine, codes.shape, 0),
                           _rows(out_codes, back), codes)
    label = lax.select(mine, lax.reshape(_rows(out_label, back), (B,)),
                       lax.full((B,), 0, jnp.int32))
    sums = lax.convert_element_type(lax.round(
        lax.slice(_rows(out_svm, back), (0, 0), (B, H)),
        lax.RoundingMethod.TO_NEAREST_EVEN), jnp.int32)
    sums = lax.select(_spread(mine, sums.shape, 0), sums,
                      lax.full(sums.shape, 0, jnp.int32))
    return codes_out, label, sums
