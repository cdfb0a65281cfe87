"""Kernel grid fill: the window's real rows as a share of the rows the
fused kernel's grid ran for them (rows and grid_rows from each dispatch's
``acorn.pad``).  None where the program does not report grid_rows."""
from bench.spans import of


def read(ctx):
    pads = [m for n, _, _, m in of(ctx) or () if n == "acorn.pad"
            and m.get("dispatch", -1) >= 0 and "grid_rows" in m]
    grid = sum(m["grid_rows"] for m in pads)
    if not grid:
        return None
    return 100.0 * sum(m["rows"] for m in pads) / grid, "%"
