"""Fused classify megakernel: parity sweeps, quantization round-trips,
launch/prep-op count pins.

The quantized operand layouts (int16 feature ids / range bounds, int8 leaf
labels, bit-packed masks) are pure *layout* choices — every narrow operand
is upcast in-kernel before arithmetic — so quantized and f32 layouts must
decode **bit-identical** classifications.  These tests pin that, the
3-launches -> 1 fusion, and the jaxpr counters' scan-multiplier convention
the pins rely on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tiling
from repro.kernels.classify_fused import classify_fused_pallas_v


def _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels, empty_slots=()):
    """Random source tables for one whole-classify call — the same
    distributions as the per-stage sweeps in ``test_kernels.py`` (a random
    ``PackedProgram`` without the plane around it)."""
    codes = jnp.asarray(rng.integers(0, 2**12, (B, T)), jnp.uint32)
    feats = jnp.asarray(rng.integers(0, levels, (B, F)), jnp.int32)
    vid = jnp.asarray(rng.integers(0, V, (B,)), jnp.int32)
    shape = (V, L, T, E)
    cv = jnp.asarray(rng.integers(0, 2**6, shape), jnp.uint32)
    cm = jnp.asarray(rng.integers(0, 2**6, shape), jnp.uint32)
    fid = jnp.asarray(rng.integers(0, F, shape), jnp.int32)
    flo = jnp.asarray(rng.integers(0, levels - 1, shape), jnp.int32)
    fhi = flo + jnp.asarray(rng.integers(0, levels // 2, shape), jnp.int32)
    bit = jnp.asarray(rng.integers(0, 2, shape), jnp.uint32)
    valid = np.asarray(rng.random(shape) < 0.9)
    shift = jnp.asarray(rng.permutation(L), jnp.int32)
    pc = np.sort(rng.choice(2**16, size=(V * T * P,), replace=False)
                 .astype(np.uint32).reshape(V, T, P), axis=2)
    plab = rng.integers(0, C, (V, T, P)).astype(np.int32)
    pv = np.asarray(rng.random((V, T, P)) < 0.9)
    w = rng.random((V, T)).astype(np.float32)
    lut = rng.integers(-60_000, 60_000, (V, H, F, levels)).astype(np.int32)
    bias = jnp.zeros((V, H), jnp.int32)
    for v in empty_slots:
        valid[v] = False
        pv[v] = False
        lut[v] = 0           # an evicted slot's LUT is blanked too
    return (codes, feats, vid, cv, cm, fid, flo, fhi, bit,
            jnp.asarray(valid), shift, jnp.asarray(pc), jnp.asarray(plab),
            jnp.asarray(pv), jnp.asarray(w), jnp.asarray(lut), bias)


def _assert_triple_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# V sweep covers the acceptance range {1, 4, 8}; B=300 exercises the
# off-block_b tail, E=130 pads past one 128-lane tile.
@pytest.mark.parametrize("B,T,E,F,V,L,P,C,H,levels,empty", [
    (7, 1, 3, 4, 1, 1, 4, 2, 1, 16, ()),
    (64, 4, 17, 13, 4, 5, 32, 5, 3, 64, ()),
    (300, 2, 130, 20, 2, 3, 16, 3, 2, 32, ()),
    (257, 3, 33, 21, 8, 8, 64, 6, 4, 64, (1, 5)),
    (33, 5, 64, 40, 1, 32, 128, 8, 8, 128, ()),
])
def test_classify_fused_sweep(rng, B, T, E, F, V, L, P, C, H, levels, empty):
    """Megakernel (interpret) vs jnp oracle vs the pre-fusion three-launch
    fallback — all bit-identical, including evicted zoo slots."""
    args = _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels,
                       empty_slots=empty)
    r = ops.classify_fused_v(*args, C, mode="ref")
    p = ops.classify_fused_v(*args, C, mode="interpret")
    u = ops.classify_fused_v(*args, C, mode="unfused-interpret")
    _assert_triple_equal(r, p)
    _assert_triple_equal(r, u)
    # packets addressing an evicted slot keep their incoming codes untouched
    codes, vid = args[0], args[2]
    for v in empty:
        sel = np.asarray(vid) == v
        np.testing.assert_array_equal(np.asarray(p[0])[sel],
                                      np.asarray(codes)[sel])


@pytest.mark.parametrize("V", [1, 4, 8])
def test_quantized_round_trip(rng, V):
    """Property: quantized prep layouts decode bit-identical classifications
    vs the f32 layouts, and both match the oracle."""
    B, T, E, F, L, P, C, H, levels = 90, 3, 20, 11, 4, 32, 5, 3, 64
    args = _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels)
    q = classify_fused_pallas_v(*args, C, quantize=True, interpret=True)
    f = classify_fused_pallas_v(*args, C, quantize=False, interpret=True)
    r = ref.classify_fused_v(*args, C)
    _assert_triple_equal(q, f)
    _assert_triple_equal(q, r)
    # the layouts really are narrow: this is what the round-trip is *of*
    prep = tiling.prep_classify_fused(*args[3:10], *args[11:17],
                                      quantize=True)
    assert prep.fid.dtype == jnp.int16
    assert prep.flo.dtype == jnp.int16 and prep.fhi.dtype == jnp.int16
    assert prep.plab.dtype == jnp.int8
    assert prep.bitpk.dtype == jnp.uint32 and prep.validpk.dtype == jnp.uint32


def test_quantized_int16_boundary_features(rng):
    """Feature values at the int16 ceiling (2^15 - 1, the feature_width=15
    profile limit): the i16 feature stream must compare exactly like the i32
    one through the walk's range compare.  (The svm stage is compared
    kernel-width vs kernel-width: values >= levels select no LUT level by
    the one-hot construction in *both* widths.)"""
    B, T, E, F, V, L, P, C, H, levels = 40, 2, 8, 6, 2, 3, 16, 3, 2, 32
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels))
    top = 2**15 - 1
    feats = np.asarray(rng.integers(0, levels, (B, F)), np.int32)
    feats[::3] = top                       # boundary packets
    args[1] = jnp.asarray(feats)
    flo = np.asarray(rng.integers(0, top, (V, L, T, E)), np.int32)
    flo[..., ::2] = top                    # boundary entry rows
    fhi = np.minimum(flo + np.asarray(
        rng.integers(0, 100, (V, L, T, E)), np.int32), top)
    args[6], args[7] = jnp.asarray(flo), jnp.asarray(fhi)
    q = classify_fused_pallas_v(*args, C, quantize=True, interpret=True)
    f = classify_fused_pallas_v(*args, C, quantize=False, interpret=True)
    _assert_triple_equal(q, f)
    # the walk itself (boundary compares included) still matches the oracle
    np.testing.assert_array_equal(
        np.asarray(q[0]), np.asarray(ref.tree_walk_v(*args[:11])))


def test_all_masked_tcam_rows(rng):
    """Entry rows carrying the no-match padding convention (mask all bits
    against value 0) and fully-wildcarded rows (mask 0) survive bit-packing
    and quantization: parity with the oracle on both extremes."""
    B, T, E, F, V, L, P, C, H, levels = 50, 2, 8, 6, 2, 3, 16, 3, 2, 32
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels))
    cv = np.zeros((V, L, T, E), np.uint32)
    cm = np.full((V, L, T, E), 0xFFFFFFFF, np.uint32)   # match nothing
    cm[..., ::2] = 0                                    # match everything
    args[3], args[4] = jnp.asarray(cv), jnp.asarray(cm)
    r = ops.classify_fused_v(*args, C, mode="ref")
    p = ops.classify_fused_v(*args, C, mode="interpret")
    _assert_triple_equal(r, p)


def test_empty_zoo_slot_round_trip(rng):
    """A fully-evicted slot (all-invalid entries and leaves) yields the
    no-model outputs in every width: codes pass through, label 0, sums 0."""
    B, T, E, F, V, L, P, C, H, levels = 30, 2, 8, 6, 3, 3, 16, 3, 2, 32
    args = _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels,
                       empty_slots=(1,))
    q = classify_fused_pallas_v(*args, C, quantize=True, interpret=True)
    f = classify_fused_pallas_v(*args, C, quantize=False, interpret=True)
    r = ref.classify_fused_v(*args, C)
    _assert_triple_equal(q, f)
    _assert_triple_equal(q, r)
    codes, vid = args[0], args[2]
    sel = np.asarray(vid) == 1
    assert sel.any()
    np.testing.assert_array_equal(np.asarray(q[0])[sel],
                                  np.asarray(codes)[sel])
    assert (np.asarray(q[1])[sel] == 0).all()
    assert (np.asarray(q[2])[sel] == 0).all()


def test_fused_single_launch_and_fallback_counts(rng):
    """The acceptance pin: one classify = one ``pallas_call``.  The unfused
    fallback restores the pre-fusion 3 launches; layerwise restores L + 2."""
    B, T, E, F, V, L, P, C, H, levels = 16, 2, 8, 6, 2, 5, 16, 3, 2, 32
    args = _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels)
    fused = ops.count_pallas_launches(
        lambda *a: ops.classify_fused_v(*a, C, mode="interpret"), *args)
    unfused = ops.count_pallas_launches(
        lambda *a: ops.classify_fused_v(*a, C, mode="unfused-interpret"),
        *args)
    layerwise = ops.count_pallas_launches(
        lambda *a: ops.classify_fused_v(*a, C, mode="layerwise-interpret"),
        *args)
    assert fused == 1
    assert unfused == 3
    assert layerwise == L + 2


def test_fused_prep_ops_zero_with_bound_image(rng):
    """With the install-time operand layout bound via ``prep=``, the fused
    classify traces to ZERO table-shaped (>= 3-D) prep equations — every
    operand flows from the jaxpr inputs straight into the launch."""
    B, T, E, F, V, L, P, C, H, levels = 16, 2, 8, 6, 2, 3, 16, 3, 2, 32
    args = _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels)
    prep = tiling.prep_classify_fused(*args[3:10], *args[11:17],
                                      quantize=True)
    bound = ops.count_operand_prep_ops(
        lambda *a: classify_fused_pallas_v(*a, C, prep=prep, interpret=True),
        *args)
    unbound = ops.count_operand_prep_ops(
        lambda *a: classify_fused_pallas_v(*a, C, interpret=True), *args)
    assert bound == 0
    assert unbound > 0


def test_counters_multiply_through_scan_consistently(rng):
    """Both jaxpr counters share one traversal and the same convention: an
    equation (or launch) inside a ``lax.scan`` body counts once per
    iteration, through nested ``pjit`` too.  Pinned here because the fused
    launch/prep pins above are meaningless if the counters disagree."""
    x = jnp.asarray(rng.random((4, 4)), jnp.float32)

    def body(c, _):
        t = c[None, :, :] * jnp.ones((3, 4, 4), jnp.float32)   # 3-D prep op
        return c + t.sum(axis=0), None

    def once(c):
        return body(c, None)[0]

    def scanned(c):
        out, _ = jax.lax.scan(body, c, None, length=5)
        return out

    single = ops.count_operand_prep_ops(once, x)
    assert single > 0
    assert ops.count_operand_prep_ops(scanned, x) == 5 * single
    # nested pjit neither loses nor double-counts
    assert ops.count_operand_prep_ops(jax.jit(scanned), x) == 5 * single
    assert ops.count_operand_prep_ops(
        jax.jit(lambda c: scanned(c) + scanned(c)), x) == 10 * single


def test_bitpack_round_trip(rng):
    """``tiling.bitpack_last`` packs {0,1} tables 32/word little-endian; the
    kernel-side unpack is its exact inverse."""
    from repro.kernels.classify_fused import _unpack_bits
    bits = jnp.asarray(rng.integers(0, 2, (3, 5, 64)), jnp.uint32)
    packed = tiling.bitpack_last(bits)
    assert packed.shape == (3, 5, 2) and packed.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(_unpack_bits(packed, 2, 64)), np.asarray(bits))
    with pytest.raises(ValueError):
        tiling.bitpack_last(jnp.zeros((4, 33), jnp.uint32))


# ---- regression cases for the iota-min priority encode and the chip-legal
# operand layouts (no cumsum, no unsigned reductions, SMEM layer shift,
# [V, 1, H_pad] bias blocks) ----
def _all_match_walk(args, V, L, T, E):
    """Make every entry of every layer match every packet: wildcard code
    mask, full feature range."""
    shape = (V, L, T, E)
    args[3] = jnp.zeros(shape, jnp.uint32)                # code_value
    args[4] = jnp.zeros(shape, jnp.uint32)                # code_mask: any
    args[6] = jnp.zeros(shape, jnp.int32)                 # f_lo
    args[7] = jnp.full(shape, 2**15 - 1, jnp.int32)       # f_hi


def test_first_match_lowest_entry_wins(rng):
    """Several entries match in one layer: the lowest entry index sets the
    status bit, whatever the later matches carry."""
    B, T, E, F, V, L, P, C, H, levels = 48, 3, 40, 6, 2, 1, 16, 3, 2, 32
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels))
    _all_match_walk(args, V, L, T, E)
    valid = np.ones((V, L, T, E), bool)
    valid[:, :, 1, :5] = False          # tree 1: entry 5 is the first match
    bit = np.zeros((V, L, T, E), np.uint32)
    bit[:, :, 0, 0] = 1                 # tree 0: first match sets the bit
    bit[:, :, 1, 6:] = 1                # tree 1: only later matches would
    bit[:, :, 2, 1:] = 1                # tree 2: every match but the first
    args[8], args[9] = jnp.asarray(bit), jnp.asarray(valid)
    args[10] = jnp.asarray([7], jnp.int32)
    codes0 = np.zeros((B, T), np.uint32)
    args[0] = jnp.asarray(codes0)
    r = ops.classify_fused_v(*args, C, mode="ref")
    p = ops.classify_fused_v(*args, C, mode="interpret")
    _assert_triple_equal(r, p)
    np.testing.assert_array_equal(np.asarray(p[0]),
                                  np.tile([1 << 7, 0, 0], (B, 1)))


def test_tied_vote_lowest_class_wins(rng):
    """Equal-weight trees split evenly between two classes: the vote goes to
    the lower class id, whichever trees voted for it."""
    B, T, E, F, V, L, P, C, H, levels = 64, 4, 8, 6, 2, 2, 32, 6, 2, 32
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels))
    args[9] = jnp.zeros((V, L, T, E), bool)         # walk leaves codes as-is
    pc = np.tile(np.arange(P, dtype=np.uint32) * 3, (V, T, 1))
    leaf = rng.integers(0, P, (B,))
    args[0] = jnp.asarray(np.tile(pc[0, 0][leaf][:, None], (1, T)))
    first = np.arange(P) % C
    second = (first + 1 + np.arange(P) % 3) % C      # never equal to first
    plab = np.empty((V, T, P), np.int32)
    plab[:, :T // 2] = first
    plab[:, T // 2:] = second
    args[11], args[12] = jnp.asarray(pc), jnp.asarray(plab)
    args[13] = jnp.ones((V, T, P), bool)
    args[14] = jnp.ones((V, T), jnp.float32)
    r = ops.classify_fused_v(*args, C, mode="ref")
    p = ops.classify_fused_v(*args, C, mode="interpret")
    _assert_triple_equal(r, p)
    np.testing.assert_array_equal(
        np.asarray(p[1]), np.minimum(first, second)[leaf])


def test_top_bit_words_and_shift_31(rng):
    """Only entries at bit 31 of each packed word are valid and set, and the
    layer writes status bit 31: the uint32 unpack and shift at the top bit
    match the oracle (interpret mode sees the same uint32 semantics the
    chip must keep)."""
    from repro.kernels.classify_fused import _unpack_bits
    words = jnp.asarray([[0x80000000, 0xFFFFFFFF]], jnp.uint32)
    bits = np.asarray(_unpack_bits(words, 2, 64))
    np.testing.assert_array_equal(bits[0, :32], [0] * 31 + [1])
    np.testing.assert_array_equal(bits[0, 32:], [1] * 32)

    B, T, E, F, V, L, P, C, H, levels = 40, 2, 64, 6, 2, 2, 64, 4, 2, 32
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels))
    _all_match_walk(args, V, L, T, E)
    top = (np.arange(E) % 32) == 31
    args[8] = jnp.asarray(np.broadcast_to(top, (V, L, T, E)), jnp.uint32)
    args[9] = jnp.asarray(np.broadcast_to(top, (V, L, T, E)))
    args[10] = jnp.asarray([31, 30], jnp.int32)
    args[13] = jnp.asarray(np.broadcast_to((np.arange(P) % 32) == 31,
                                           (V, T, P)))
    r = ops.classify_fused_v(*args, C, mode="ref")
    p = ops.classify_fused_v(*args, C, mode="interpret")
    _assert_triple_equal(r, p)
    assert (np.asarray(p[0]) >> 31 == 1).all()


def test_zoo8_nonzero_bias_every_slot(rng):
    """V=8 with a distinct non-zero bias in every slot: the per-version
    ``[1, 1, H_pad]`` bias block reaches exactly the packets of its slot."""
    B, T, E, F, V, L, P, C, H, levels = 200, 2, 16, 12, 8, 3, 16, 4, 5, 32
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels))
    bias = rng.integers(1, 5_000, (V, H)) * rng.choice([-1, 1], (V, H))
    args[16] = jnp.asarray(bias, jnp.int32)
    r = ops.classify_fused_v(*args, C, mode="ref")
    p = ops.classify_fused_v(*args, C, mode="interpret")
    _assert_triple_equal(r, p)
    zero = list(args)
    zero[16] = jnp.zeros((V, H), jnp.int32)
    z = ops.classify_fused_v(*zero, C, mode="interpret")
    vid = np.asarray(args[2])
    np.testing.assert_array_equal(np.asarray(p[2]) - np.asarray(z[2]),
                                  bias[vid])


# ---- the grouped grid: rows sorted by version into blocks, one version a
# grid step.  A small block_b makes many blocks from a few rows. ----
def _vid_case(rng, case, B, V, bb):
    """The ``vid`` column of one grouping case (-1: no version)."""
    if case == "one_vid":
        return np.full(B, V - 1)
    if case == "every_vid":
        return rng.permutation(np.arange(B) % V)
    if case == "exact_and_single":
        # v0 fills exactly one block, v1 is a one-row group, the rest v2
        # with rows of no version interleaved
        vid = np.concatenate([np.zeros(bb), [1], np.full(B - bb - 1, 2)])
        vid[rng.choice(np.arange(bb + 1, B), 3, replace=False)] = -1
        return rng.permutation(vid)
    if case == "none_and_out_of_range":
        return rng.choice(np.asarray([-1, V, V + 5] + list(range(V))), B)
    if case == "no_rows":
        return np.full(B, -1)
    if case == "v1_tail":
        return np.where(rng.random(B) < 0.3, -1, 0)
    return rng.integers(0, V, B)


@pytest.mark.parametrize("case,B,V,empty", [
    ("v1_tail", 21, 1, ()),
    ("one_vid", 30, 3, ()),
    ("every_vid", 50, 8, ()),
    ("every_vid", 19, 3, ()),
    ("exact_and_single", 27, 3, ()),
    ("none_and_out_of_range", 40, 3, ()),
    ("random", 33, 4, (2,)),
    ("no_rows", 12, 3, ()),
])
def test_grouped_kernel_matches_ref(rng, case, B, V, empty):
    """The grouped kernel (interpret) against the ``ref`` oracle, bit for
    bit on codes, label and svm sums: rows of a version match the oracle;
    rows of no version (-1, out of range: the plane's padding and
    forwarded packets) keep their codes with label 0 and sums 0."""
    T, E, F, L, P, C, H, levels, bb = 2, 16, 10, 3, 16, 4, 3, 32, 8
    args = list(_rand_fused(rng, B, T, E, F, V, L, P, C, H, levels,
                            empty_slots=empty))
    vid = np.asarray(_vid_case(rng, case, B, V, bb), np.int32)
    args[2] = jnp.asarray(vid)
    got = classify_fused_pallas_v(*args, C, block_b=bb, interpret=True)
    mine = (vid >= 0) & (vid < V)
    args[2] = jnp.asarray(np.where(mine, vid, 0))
    want = ref.classify_fused_v(*args, C)
    codes = np.asarray(args[0])
    for g, w, none in zip(got, want, (codes, 0, 0)):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_array_equal(g[mine], w[mine])
        np.testing.assert_array_equal(
            g[~mine], np.broadcast_to(none, g.shape)[~mine])
    for v in empty:
        np.testing.assert_array_equal(np.asarray(got[0])[vid == v],
                                      codes[vid == v])


@pytest.mark.parametrize("V,B,bb", [(1, 300, 256), (3, 100, 8),
                                    (8, 4096, 256), (8, 777, 64)])
def test_grid_rows_matches_device_grouping(rng, V, B, bb):
    """The host's ``grid_rows`` equals the blocks the device grouping uses
    times ``block_b``, on skewed (Zipf) VIDs with rows of no version; each
    used block holds rows of its own version only, each row once."""
    from repro.kernels.classify_fused import grid_rows, group_rows
    vid = np.minimum(rng.zipf(1.3, B) - 1, V + 1).astype(np.int32)
    vid[rng.random(B) < 0.2] = -1
    src, dest, block_vid, n_used = (np.asarray(x) for x in jax.jit(
        group_rows, static_argnums=(1, 2))(jnp.asarray(vid), V, bb))
    n = int(n_used[0])
    assert grid_rows(vid, V, bb) == n * bb
    assert block_vid.shape == (-(-B // bb) + V - 1,)
    mine = np.flatnonzero((vid >= 0) & (vid < V))
    assert (dest[vid < 0] == src.shape[0]).all()
    assert sorted(dest[mine]) == sorted(set(dest[mine].tolist()))
    np.testing.assert_array_equal(src[dest[mine]], mine)
    np.testing.assert_array_equal(block_vid[dest[mine] // bb], vid[mine])
    assert (dest[mine] < n * bb).all()
    assert (block_vid[n:] == block_vid[max(n - 1, 0)]).all()


def test_buckets_of_one_block_count_share_the_kernel_trace(rng,
                                                          monkeypatch):
    """The kernel call is traced once per block count, not once per batch
    shape: batches of 3, 5 and 8 rows group into the same blocks, 9 rows
    into one more (block_b 8, V 3)."""
    from repro.kernels import classify_fused
    traced = []
    real = classify_fused.pl.pallas_call
    monkeypatch.setattr(classify_fused.pl, "pallas_call",
                        lambda *a, **k: traced.append(1) or real(*a, **k))
    T, E, F, L, P, C, H, levels, bb, V = 3, 16, 10, 3, 16, 4, 3, 32, 8, 3
    grew = []
    for B in (3, 5, 8, 9):
        args = _rand_fused(rng, B, T, E, F, V, L, P, C, H, levels)
        n = len(traced)
        classify_fused_pallas_v(*args, C, block_b=bb, interpret=True)
        grew.append(len(traced) - n)
    assert grew == [1, 0, 0, 1]
