"""The program's own trace spans, at the layer boundaries of the hot path.

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the same
``.xplane.pb`` as the device ops, on the same clock, so a trace names the
host step that held the device idle.  With no profiler running a span costs
about a microsecond, and there are a handful per dispatch, so spans are
always on: a profiler that is not running is the off state.

Spans are made once per dispatch or per install, never per request.  Every
per-dispatch span carries ``dispatch=<id>``: the serving front's dispatch
record id, which it runs the executor call under (``in_dispatch``), -1
outside a serving front.  Install spans carry ``vid``.  No span is held
across an ``await``: a wait on the event loop is a pair of stamps, such as
the drain that ``acorn.release`` carries.

The module lives in ``core`` because the deepest span, the install's table
build, is in ``core/plane.py``; the runtime and serving layers import it
from here.
"""
from __future__ import annotations

import contextvars

import jax

__all__ = ["SPANS", "span", "recording", "in_dispatch", "current_dispatch"]

SPANS = (
    # serving front (event loop): the cut and coalesce of one dispatch
    "acorn.coalesce",
    # host path (slot thread), ``DataplaneRuntime.run_host`` in order
    "acorn.pad",        # host leaves + admission padding; rows=, bucket=,
                        # grid_rows= (the fused kernel's grid rows)
    "acorn.launch",     # the executor call: host -> device copies, enqueue;
                        # compiled=1 if it traced
    "acorn.fetch",      # the wait for the device, the copies back, the trim
    # control plane (caller's thread)
    "acorn.install.translate",  # trained model -> TableProgram
    "acorn.install.tables",     # host tables + the slot's image operands
    "acorn.install.write",      # the slot's device writes
    "acorn.release",    # a hold ends; drain_us= if it was drained
)
_NAMES = frozenset(SPANS)
_DISPATCH = contextvars.ContextVar("acorn_dispatch", default=-1)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A trace span ``name`` (one of ``SPANS``) with metadata ``meta``."""
    if name not in _NAMES:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(name, **meta)


def recording() -> bool:
    """Whether a profiler is recording spans: span metadata that takes work
    to compute is computed only then."""
    return jax.profiler.TraceAnnotation.is_enabled()


def in_dispatch(dispatch: int, fn, *args):
    """``fn(*args)`` with ``dispatch`` as the id its spans carry."""
    token = _DISPATCH.set(dispatch)
    try:
        return fn(*args)
    finally:
        _DISPATCH.reset(token)


def current_dispatch() -> int:
    """The dispatch the calling code runs under (-1 outside one)."""
    return _DISPATCH.get()
