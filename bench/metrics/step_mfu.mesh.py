"""The whole classify step's share of the mesh's peak: the window's least
time (bytes over HBM bandwidth, ``workcount``; the whole DT's bytes once a
packet, never once a hop) over the traced window's wall time times the
chips used.  It bounds any implementation of the split step."""
from bench.readers import least_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * least_s(ctx) / (tr["window_s"] * ctx["chips"]), "%"
