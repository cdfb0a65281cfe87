"""Mean over the window's control-plane holds of the drain: from the hold
to the moment every in-flight dispatch had landed (the program's hold
record, carried by the ``acorn.release`` span that ends the hold)."""
from bench.spans import of


def read(ctx):
    spans = of(ctx)
    drains = [m["drain_us"] for n, _, _, m in spans or ()
              if n == "acorn.release" and "drain_us" in m]
    if not drains:
        return None
    return sum(drains) / len(drains) / 1e3, "ms"
