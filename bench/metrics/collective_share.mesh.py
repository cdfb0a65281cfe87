"""Share of each chip's busy time spent in collective ops (the switch
axis's ``ppermute`` between hop chips), averaged over the chips used.
None where the trace holds no collective."""
import numpy as np


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not any(tr["collective_s"].values()):
        return None
    shares = [tr["collective_s"][d] / b for d, b in tr["busy_s"].items()
              if b > 0]
    return 100.0 * float(np.mean(shares)), "%"
