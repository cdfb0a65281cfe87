"""PL003 — vmem-budget.

``docs/ARCHITECTURE.md`` ("Kernel memory plans") pins a per-grid-step VMEM
footprint for every Pallas kernel; ``repro/kernels/budgets.py`` holds the
machine-readable copy.  This rule closes the loop **statically**: it parses
each kernel module's ``pl.pallas_call``, evaluates every ``BlockSpec`` block
shape and ``scratch_shapes`` entry under the manifest's reference bindings
(no jax import, no tracing), adds the manifest-declared in-kernel
intermediates (e.g. ``tree_walk``'s VMEM-resident ``fv_all`` matmul product),
and fails when the recomputed bytes

* exceed ``budget_bytes`` (16 MiB/core — the kernel cannot fit), or
* drift more than ``tolerance`` (1%) from ``pinned_bytes`` — someone resized
  a block without re-budgeting the doc table and manifest.

It also flags kernels with no manifest entry, shapes it cannot statically
evaluate (add the free variable to ``bindings``), and — on ``budgets.py``
itself — manifest entries whose kernel module no longer exists.
"""
from __future__ import annotations

import ast
import pathlib

from repro.analysis.lint.core import FileContext, Finding, register
from repro.kernels.budgets import BUDGETS, KernelBudget

__all__ = ["VmemBudget", "kernel_footprints"]

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
}


class _Unknown(Exception):
    """A BlockSpec dim references a name with no reference binding."""

    def __init__(self, name: str):
        super().__init__(name)
        self.name = name


def _eval_dim(node: ast.AST, bindings: dict) -> int:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in bindings:
            return int(bindings[node.id])
        raise _Unknown(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_dim(node.operand, bindings)
    if isinstance(node, ast.BinOp):
        lhs = _eval_dim(node.left, bindings)
        rhs = _eval_dim(node.right, bindings)
        if isinstance(node.op, ast.Add):
            return lhs + rhs
        if isinstance(node.op, ast.Sub):
            return lhs - rhs
        if isinstance(node.op, ast.Mult):
            return lhs * rhs
        if isinstance(node.op, (ast.FloorDiv, ast.Div)):
            return lhs // rhs
        if isinstance(node.op, ast.Pow):
            return lhs ** rhs
    raise _Unknown(ast.dump(node))


def _call_name(node: ast.Call) -> str | None:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _block_elems(spec: ast.Call, bindings: dict) -> int:
    """Element count of one ``pl.BlockSpec((d0, d1, ...), index_map)``."""
    if not spec.args:
        raise _Unknown("<BlockSpec with no block shape>")
    shape = spec.args[0]
    dims = shape.elts if isinstance(shape, ast.Tuple) else [shape]
    n = 1
    for d in dims:
        n *= _eval_dim(d, bindings)
    return n


def _in_smem(spec: ast.Call) -> bool:
    """``BlockSpec(memory_space=pltpu.SMEM)``: scalars outside VMEM."""
    return any(kw.arg == "memory_space" and isinstance(kw.value, ast.Attribute)
               and kw.value.attr == "SMEM" for kw in spec.keywords)


def _specs_of(kw_value: ast.AST):
    """VMEM BlockSpec calls from an ``in_specs=[...]`` / ``out_specs=...``
    value; SMEM specs live outside the VMEM budget, like SMEM scratch."""
    nodes = kw_value.elts if isinstance(kw_value, (ast.List, ast.Tuple)) \
        else [kw_value]
    return [n for n in nodes
            if isinstance(n, ast.Call) and _call_name(n) == "BlockSpec"
            and not _in_smem(n)]


def _scratch_bytes(kw_value: ast.AST, bindings: dict) -> int:
    """Bytes of VMEM ``scratch_shapes`` (``pltpu.VMEM(shape, dtype)``)."""
    nodes = kw_value.elts if isinstance(kw_value, (ast.List, ast.Tuple)) \
        else [kw_value]
    total = 0
    for n in nodes:
        if not (isinstance(n, ast.Call) and _call_name(n) == "VMEM"):
            continue   # SMEM/semaphores live outside the VMEM budget
        shape = n.args[0] if n.args else None
        dims = shape.elts if isinstance(shape, ast.Tuple) else [shape]
        elems = 1
        for d in dims:
            elems *= _eval_dim(d, bindings)
        dt = n.args[1] if len(n.args) > 1 else None
        dt_name = dt.attr if isinstance(dt, ast.Attribute) else (
            dt.id if isinstance(dt, ast.Name) else "")
        total += elems * _DTYPE_BYTES.get(dt_name, 4)
    return total


def _spec_keywords(call: ast.Call):
    """The keywords of a ``pl.pallas_call``, with those of its
    ``grid_spec=<GridSpec>(...)`` (a scalar-prefetch grid) in its place."""
    for kw in call.keywords:
        if kw.arg == "grid_spec" and isinstance(kw.value, ast.Call):
            yield from kw.value.keywords
        else:
            yield kw


def _footprint(call: ast.Call, entry: KernelBudget) -> int:
    """Static per-grid-step VMEM bytes of one ``pl.pallas_call``."""
    specs, scratch = [], 0
    for kw in _spec_keywords(call):
        if kw.arg in ("in_specs", "out_specs"):
            specs.extend(_specs_of(kw.value))
        elif kw.arg == "scratch_shapes":
            scratch += _scratch_bytes(kw.value, entry.bindings)
    sizes = entry.spec_itemsizes or (entry.itemsize,) * len(specs)
    if len(sizes) != len(specs):
        raise _Unknown(
            f"spec_itemsizes has {len(sizes)} entries for {len(specs)} "
            "parsed BlockSpecs")
    total = sum(_block_elems(spec, entry.bindings) * size
                for spec, size in zip(specs, sizes))
    return total + scratch + sum(entry.intermediates.values())


def _pallas_calls(tree: ast.AST) -> list[ast.Call]:
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Call) and _call_name(n) == "pallas_call"]


def _entries_for(stem: str, budgets: dict) -> dict[str, KernelBudget]:
    """Manifest entries budgeting the module ``stem`` — usually one, keyed
    by the stem itself, but a module may carry several (e.g. the quantized
    and f32 operand widths of ``classify_fused``)."""
    return {k: e for k, e in budgets.items() if (e.module or k) == stem}


def kernel_footprints(path: pathlib.Path | str,
                      budgets: dict | None = None) -> dict[str, int]:
    """Recompute the static footprint of every budgeted ``pallas_call`` in
    ``path`` — the same arithmetic PL003 runs, exposed so tests can check the
    KiB numbers quoted in ``docs/ARCHITECTURE.md``.  Returns
    ``{budget_key: bytes}`` (one entry per manifest row matching the
    module; multi-row modules yield one footprint per operand width)."""
    path = pathlib.Path(path)
    budgets = BUDGETS if budgets is None else budgets
    entries = _entries_for(path.stem, budgets)
    if not entries:
        return {}
    tree = ast.parse(path.read_text(encoding="utf-8"))
    calls = _pallas_calls(tree)
    if not calls:
        return {}
    return {key: max(_footprint(c, entry) for c in calls)
            for key, entry in entries.items()}


@register
class VmemBudget:
    id = "PL003"
    name = "vmem-budget"
    description = ("static BlockSpec/scratch footprint of every kernel must "
                   "match the pinned budget in kernels/budgets.py "
                   "(ARCHITECTURE 'Kernel memory plans')")

    def check(self, ctx: FileContext) -> list[Finding]:
        if not ctx.modpath.startswith("kernels/"):
            return []
        out = []
        if ctx.path.name == "budgets.py":
            # Reverse direction: every manifest entry names a live module.
            for key in sorted(BUDGETS):
                mod = BUDGETS[key].module or key
                if not (ctx.path.parent / f"{mod}.py").exists():
                    out.append(ctx.finding(
                        self, 1,
                        f"budget entry '{key}' has no kernels/{mod}.py — "
                        "remove the stale manifest row"))
            return out
        calls = _pallas_calls(ctx.tree)
        if not calls:
            return []
        entries = _entries_for(ctx.path.stem, BUDGETS)
        if not entries:
            out.append(ctx.finding(
                self, calls[0],
                f"pallas_call in unbudgeted kernel '{ctx.path.stem}' — add "
                "a KernelBudget entry to kernels/budgets.py and a row to "
                "the ARCHITECTURE 'Kernel memory plans' table"))
            return out
        for key, entry in entries.items():
            for call in calls:
                try:
                    got = _footprint(call, entry)
                except _Unknown as e:
                    out.append(ctx.finding(
                        self, call,
                        f"cannot statically evaluate block shape: '{e.name}'"
                        f" has no reference binding in BUDGETS['{key}']"
                        ".bindings"))
                    continue
                if got > entry.budget_bytes:
                    out.append(ctx.finding(
                        self, call,
                        f"static VMEM footprint {got} B of '{key}' exceeds "
                        f"the {entry.budget_bytes} B per-core budget at the "
                        "reference config — shrink the batch/block tiles"))
                elif abs(got - entry.pinned_bytes) > \
                        entry.tolerance * entry.pinned_bytes:
                    out.append(ctx.finding(
                        self, call,
                        f"static VMEM footprint {got} B of '{key}' drifted >"
                        f"{entry.tolerance:.0%} from the pinned "
                        f"{entry.pinned_bytes} B — re-budget "
                        "kernels/budgets.py and the ARCHITECTURE 'Kernel "
                        "memory plans' table"))
        return out
