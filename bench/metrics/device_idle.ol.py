"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""
from bench.readers import idle_pct


def read(ctx):
    v = idle_pct(ctx)
    return None if v is None else (v, "%")
