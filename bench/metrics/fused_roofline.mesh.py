"""The fused kernel's share of its roofline on the mesh: the least time the
window's dispatches need (bytes over one chip's HBM bandwidth,
``workcount``; the whole DT's bytes once a packet, never once a hop) over
the kernel's device time summed over the chips."""
from bench.readers import kernel_s, least_s


def read(ctx):
    k = kernel_s(ctx)
    if k is None:
        return None
    return 100.0 * least_s(ctx) / k, "%"
