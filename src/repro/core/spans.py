"""Host spans on the profiler's clock.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, the span lands in that trace beside the device's
events, on the same clock, so an idle gap on the device can be named by
the host work around it. Nothing is recorded otherwise; a span then
costs about a microsecond. Stats (ints or short strings) are given when
the span opens; ``set_metadata`` on the open span adds more.

Names carry the layer as a prefix: ``serve.`` for ``Server.generate``,
``ntx.`` for the descriptor-program ``Executor``, ``py.`` for the
interpreter itself (``py.gc``, a garbage collection, with its
generation as ``gen``).
"""
from __future__ import annotations

import gc
from typing import List, Union

import jax


def span(name: str, **stats: Union[int, str]) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` with ``stats`` in the
    running profiler trace."""
    return jax.profiler.TraceAnnotation(name, **stats)


#: the ``py.gc`` span of the collection under way (collections do not
#: nest: the interpreter runs one at a time)
_open_gc: List[jax.profiler.TraceAnnotation] = []


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("py.gc", gen=info["generation"])
        s.__enter__()
        _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


def install_gc_span() -> None:
    """Record each garbage collection as a ``py.gc`` span (idempotent)."""
    if _gc_callback not in gc.callbacks:
        gc.callbacks.append(_gc_callback)
