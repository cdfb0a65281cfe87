"""Admission padding: the rows run that carry no packet, as a share of all
rows run over the window's dispatches (rows and bucket from each
dispatch's ``acorn.pad``)."""
from bench.spans import pad_share


def read(ctx):
    return pad_share(ctx)
