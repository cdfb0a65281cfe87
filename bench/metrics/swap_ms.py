"""Mean serving stall of a live install: hold, drain, install, release."""


def read(ctx):
    ins = ctx["installs"]
    if not ins:
        return None
    stall = [x["t_release"] - x["t_hold"] for x in ins]
    return 1e3 * sum(stall) / len(stall), "ms"
