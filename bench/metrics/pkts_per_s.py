"""Correctly answered packets completed inside the window, per second."""


def read(ctx):
    done = ctx["ok"] & (ctx["t_done"] <= ctx["t_end"])
    return float(ctx["size"][done].sum()) / ctx["seconds"], "pkts/s"
