"""Machine-readable VMEM memory plans for every Pallas kernel.

This is the single source of truth behind the "Kernel memory plans" table in
``docs/ARCHITECTURE.md``: one :class:`KernelBudget` per kernel module, pinning
the **reference-config** per-grid-step VMEM footprint that the prose table
quotes.  Three consumers read it:

* ``repro.analysis.lint`` rule **PL003** re-derives each kernel's footprint
  straight from the ``BlockSpec``/``scratch_shapes`` AST under ``bindings``
  and fails the lint if the recomputed bytes drift more than ``tolerance``
  from ``pinned_bytes`` (someone grew a block without re-budgeting) or
  exceed ``budget_bytes`` (16 MiB/core, the TPU VMEM ceiling);
* ``tools/check_doc_refs.py`` cross-checks the doc table's kernel names
  against ``BUDGETS`` keys, so the prose and the manifest cannot diverge
  silently;
* tests recompute the KiB numbers quoted in the doc from this manifest.

**This module must stay importable without jax** — the lint CLI and the doc
checker both run in environments where importing jax (or anything that
initializes a TPU runtime) is off the table.  Plain stdlib only.

``bindings`` give the reference values for every free variable appearing in
the kernel's ``BlockSpec`` shape tuples (the doc's parenthetical "block_b=256,
L=32, ..." config).  ``intermediates`` are VMEM-resident arrays *created
inside the kernel body* — invisible to BlockSpec accounting but real VMEM
(e.g. ``tree_walk``'s ``fv_all = feats @ fsel.T`` product, which the doc's
6.2 MiB explicitly counts) — declared here as name -> bytes.
"""
from __future__ import annotations

import dataclasses

__all__ = ["KernelBudget", "BUDGETS", "VMEM_BYTES"]

# Per-core VMEM ceiling (TPU v4/v5 class): 16 MiB.
VMEM_BYTES = 16 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class KernelBudget:
    """Reference-config VMEM plan for one kernel module."""

    kernel: str
    # Reference value for every free variable in the BlockSpec shape tuples.
    bindings: dict
    # In-kernel VMEM-resident arrays (name -> bytes) that BlockSpec
    # accounting cannot see.
    intermediates: dict
    # Recomputed per-grid-step footprint at the reference config:
    # sum(prod(block shape) * itemsize over in/out specs) + scratch bytes
    # + sum(intermediates).  PL003 must reproduce this within `tolerance`.
    pinned_bytes: int
    # Operand element size when every block shares one width (f32/i32/u32).
    itemsize: int = 4
    # Kernel module stem this entry budgets (defaults to the manifest key).
    # Lets one module carry several entries — e.g. ``classify_fused`` pins
    # both the quantized and the f32 operand widths of the same launch.
    module: str = ""
    # Per-BlockSpec element sizes, in pallas_call source order (in_specs
    # first, then out_specs).  Empty means uniform ``itemsize``.  PL003
    # refuses to guess: a length mismatch with the parsed spec list fails
    # the lint rather than silently misbudgeting.
    spec_itemsizes: tuple = ()
    budget_bytes: int = VMEM_BYTES
    tolerance: float = 0.01
    note: str = ""


BUDGETS = {
    "tree_walk": KernelBudget(
        kernel="tree_walk",
        bindings={"block_b": 256, "F_pad": 128, "L": 32, "E_pad": 128},
        intermediates={
            # fv_all = feats @ fsel.T stays resident across the whole walk:
            # [block_b, L * E_pad] f32 = 256 * 32 * 128 * 4.
            "fv_all": 256 * 32 * 128 * 4,
        },
        pinned_bytes=6_524_032,
        note="feats 128 KiB + fsel 2 MiB + fv_all 4 MiB + entry blocks; "
             "block_b auto-halves when L*E_pad would overflow",
    ),
    "tcam_match": KernelBudget(
        kernel="tcam_match",
        bindings={"block_b": 256, "F_pad": 128, "E_pad": 128},
        intermediates={
            # fv = feats @ fsel.T: [block_b, E_pad] f32 = 256 * 128 * 4.
            "fv": 256 * 128 * 4,
        },
        pinned_bytes=333_828,
        note="feats 128 KiB + f_sel 64 KiB + fv 128 KiB + entry rows; "
             "independent of V (one version's block per step)",
    ),
    "forest_vote": KernelBudget(
        kernel="forest_vote",
        bindings={"block_b": 256, "T": 8, "P": 1024},
        intermediates={},
        pinned_bytes=116_768,
        note="leaf tables [T, P] fully resident (T<=8, P<=1024 -> 32 KiB "
             "per table); independent of V",
    ),
    "svm_lookup": KernelBudget(
        kernel="svm_lookup",
        bindings={"block_b": 128, "chunk_f": 8, "L": 256, "H_pad": 8},
        intermediates={},
        pinned_bytes=74_272,
        note="one (version, chunk) LUT slice [chunk_f*L, H_pad] = 64 KiB "
             "streamed per step; L is the quantization level count",
    ),
    "classify_fused": KernelBudget(
        kernel="classify_fused",
        module="classify_fused",
        bindings={"block_b": 256, "T": 8, "L": 32, "E_pad": 128, "WP": 4,
                  "F_pad": 128, "P": 256, "PW": 8, "n_chunks": 8,
                  "chunk_f": 8, "levels": 256, "H_pad": 16},
        # VMEM in_specs order: codes, feats(i16), fid(i16), cv, cm,
        # flo(i16), fhi(i16), bitpk, validpk, pred_codes, plab(i8),
        # pvalidpk, weights, lut, bias; out: codes, label, svm.  The layer
        # shift and the block -> version map are read from SMEM, outside
        # this budget.
        spec_itemsizes=(4, 2, 2, 4, 4, 2, 2, 4, 4, 4, 1, 4, 4, 4, 4,
                        4, 4, 4),
        intermediates={
            # svm one-hot [block_b, chunk_f*levels] f32, live per chunk.
            "svm_onehot": 256 * 8 * 256 * 4,
            # vote select jnp.where(eq, plab, 0): [block_b, T, P] i32.
            "vote_select": 256 * 8 * 256 * 4,
            # walk selector [F_pad, E_pad] f32 + fv [block_b, E_pad] f32.
            "walk_select": 128 * 128 * 4 + 256 * 128 * 4,
        },
        pinned_bytes=6_016_352,
        note="quantized widths (i16 feats/fid/range bounds, i8 labels, "
             "bit-packed masks): the whole classify in one launch at ~6.0 "
             "MiB/step, independent of V — V=8 zoos fit the same plan",
    ),
    "classify_fused_f32": KernelBudget(
        kernel="classify_fused",
        module="classify_fused",
        bindings={"block_b": 256, "T": 8, "L": 32, "E_pad": 128, "WP": 4,
                  "F_pad": 128, "P": 256, "PW": 8, "n_chunks": 8,
                  "chunk_f": 8, "levels": 256, "H_pad": 16},
        intermediates={
            "svm_onehot": 256 * 8 * 256 * 4,
            "vote_select": 256 * 8 * 256 * 4,
            "walk_select": 128 * 128 * 4 + 256 * 128 * 4,
        },
        pinned_bytes=6_284_640,
        note="full-width counterfactual of the same launch (quantize=False: "
             "i32 feats/fid/labels, f32 range bounds) — the +268 KiB the "
             "quantized layouts buy back per grid step",
    ),
    "decode_attn": KernelBudget(
        kernel="decode_attn",
        bindings={"Hq": 32, "D": 128, "block_s": 512, "Hkv": 8},
        intermediates={},
        pinned_bytes=4_243_716,
        note="k/v chunks dominate (2 x 2 MiB at f32 accounting; bf16 "
             "operands halve them) + f32 online-softmax scratch",
    ),
}
