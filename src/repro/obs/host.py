"""Host activity that a device trace cannot explain by itself.

:class:`HostWatch` counts, while it is open, the two host stalls that
leave the device idle between steps without any span of the caller's
own: garbage collections and backend compiles. Each collection also
becomes a host span ``gc`` in a running profiler trace, so a trace
reader can label an idle gap with it::

    with HostWatch() as hw:
        for step in ...:
            ...
            rec.update(hw.delta())   # gc_pauses, gc_s, compiles, compile_s

Off (no watch open) nothing is hooked: the compile listener is
registered once per process and returns at once when no watch is open.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax

# jax._src.dispatch.BACKEND_COMPILE_EVENT: one event per XLA compile (a
# persistent-cache hit records none)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COUNTERS = ("gc_pauses", "gc_s", "compiles", "compile_s")

_open: List["HostWatch"] = []
_listening = False


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    for w in _open:
        w.counts["compiles"] += 1
        w.counts["compile_s"] += duration_s


class HostWatch:
    """Counts GC pauses and backend compiles while open (``counts``);
    :meth:`delta` returns what was added since its last call."""

    def __init__(self):
        self.counts: Dict[str, float] = dict.fromkeys(COUNTERS, 0)
        self._last = dict(self.counts)
        self._gc_t0 = None
        self._gc_span = None

    def __enter__(self) -> "HostWatch":
        global _listening
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
        _open.append(self)
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)
        _open.remove(self)
        if self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            self._gc_span = jax.profiler.TraceAnnotation("gc")
            self._gc_span.__enter__()
        elif self._gc_t0 is not None:
            self._gc_span.__exit__(None, None, None)
            self.counts["gc_pauses"] += 1
            self.counts["gc_s"] += time.perf_counter() - self._gc_t0
            self._gc_t0 = self._gc_span = None

    def delta(self) -> Dict[str, float]:
        out = {k: self.counts[k] - self._last[k] for k in COUNTERS}
        self._last = dict(self.counts)
        return out
