"""Mean real packets per dispatch."""


def read(ctx):
    d = ctx["dispatches"]
    if not d:
        return None
    return sum(x["packets"] for x in d) / len(d), "pkts"
