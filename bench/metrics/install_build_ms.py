"""Mean over the window's installs of the work that needs no hold: the
model's translation (``acorn.install.translate``) and the slot's table and
image build (``acorn.install.tables``)."""
from bench.spans import of


def read(ctx):
    spans = of(ctx)
    t = {"acorn.install.translate": 0, "acorn.install.tables": 0}
    n = 0
    for name, _, d, _ in spans or ():
        if name in t:
            t[name] += d
            n += name == "acorn.install.tables"
    if not n:
        return None
    return sum(t.values()) * 1e-6 / n, "ms"
