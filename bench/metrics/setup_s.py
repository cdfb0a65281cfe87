"""Set-up seconds: process start to the first timed request."""


def read(ctx):
    return ctx["setup_s"], "s"
