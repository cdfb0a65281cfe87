"""Median over the window's dispatches of the wait between the cut (end of
its ``acorn.coalesce``) and the start of its host path (``acorn.pad``, on a
slot thread): the wait for a free slot and the hand-off to its thread."""
import numpy as np

from bench.spans import of, slot_waits


def read(ctx):
    spans = of(ctx)
    waits = slot_waits(spans) if spans else []
    if not waits:
        return None
    return float(np.median(waits) * 1e3), "ms"
