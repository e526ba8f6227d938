"""Host spans and collector spans on the profiler's clock.

``span(name, **meta)`` is ``jax.profiler.TraceAnnotation``: entering and
leaving one costs well under a microsecond while no profiler runs, and
while one does it records a host event on the same clock as the device's
ops. Span names are fixed strings (``serve.*``, ``train.*``, ``host.gc``);
metadata goes in keyword arguments, which the profiler formats only while
it traces.

Inside jitted programs the same layers are marked with ``jax.named_scope``
(``gather``/``scatter``, ``draft``/``verify``/``accept``/``commit``,
``taps``/``drafter``/``update``), which costs nothing at run time: the scope
becomes part of each op's ``op_name`` metadata, which the device trace
carries as the ``tf_op`` stat of the op's event metadata.

``GcSpans`` puts every garbage collection on the same clock as a
``host.gc`` span, and counts the collections and their seconds.
"""
from __future__ import annotations

import gc
import time
from typing import Optional

from jax.profiler import TraceAnnotation as span

__all__ = ["GcSpans", "span"]


class GcSpans:
    """A ``gc.callbacks`` hook, installed by ``install`` and removed by
    ``remove``, that opens a ``host.gc`` span when a collection starts and
    closes it when the collection stops."""

    def __init__(self):
        self.collections = 0
        self.seconds = 0.0
        self._open: Optional[span] = None
        self._t0 = 0.0

    def install(self) -> "GcSpans":
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        return self

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            self._open = span("host.gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
            self.collections += 1
            self.seconds += time.perf_counter() - self._t0
