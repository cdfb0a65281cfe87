"""The whole classify step's share of the chip's peak: the window's least
time (bytes over HBM bandwidth, ``workcount``) over the traced window's wall
time, per chip.  It bounds any implementation of the step."""
from bench.readers import least_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * least_s(ctx) / (tr["window_s"] * ctx["chips"]), "%"
