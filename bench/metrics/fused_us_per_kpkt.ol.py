"""Device time of the fused classify kernel per 1,000 real packets
answered in the traced window (summed over chips)."""
from bench.readers import kernel_s, packets_answered


def read(ctx):
    k, n = kernel_s(ctx), packets_answered(ctx)
    if k is None or n == 0:
        return None
    return k * 1e6 / (n / 1e3), "us/kpkt"
