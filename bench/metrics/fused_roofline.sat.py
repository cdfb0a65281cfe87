"""The fused kernel's share of its roofline: the least time the
window's dispatches need (bytes over HBM bandwidth, ``workcount``) over the
kernel's device time."""
from bench.readers import kernel_s, least_s


def read(ctx):
    k = kernel_s(ctx)
    if k is None:
        return None
    return 100.0 * least_s(ctx) / k, "%"
