"""Compile counters over JAX's monitoring events (DESIGN.md §8).

JAX reports each jaxpr trace, each lowering to an MLIR module and each
backend compile as a timed event, and each persistent-cache hit as an
event. A cache hit also reports a backend-compile time: the time to look
the executable up and load it. A jitted function traced inside another
reports its own trace within the outer one's, so the seconds count the
outermost events only; the counts count every event.
:func:`compile_counter` registers one listener pair per process
(``jax.monitoring`` cannot unregister) and tallies them; callers take
:meth:`CompileCounter.snapshot` before and after a stretch of work and
subtract.

Events before the first call of :func:`compile_counter` are not counted,
so an entry point calls it before its first compile.
"""
from __future__ import annotations

import dataclasses
import threading

import jax

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class CompileStats:
    """Counts and summed seconds of the compile events."""

    traces: int = 0
    trace_s: float = 0.0
    lowerings: int = 0
    lower_s: float = 0.0
    backend_compiles: int = 0   # XLA compiles and cache loads
    compile_s: float = 0.0
    cache_hits: int = 0

    @property
    def compiles(self) -> int:
        """Backend compiles not served from the persistent cache."""
        return self.backend_compiles - self.cache_hits

    def __sub__(self, other: "CompileStats") -> "CompileStats":
        return CompileStats(*(a - b for a, b in zip(
            dataclasses.astuple(self), dataclasses.astuple(other))))


#: timed event -> (count field, seconds field)
_TIMED = {TRACE_EVENT: ("traces", "trace_s"),
          LOWER_EVENT: ("lowerings", "lower_s"),
          COMPILE_EVENT: ("backend_compiles", "compile_s")}


class CompileCounter:
    """The process's running tallies; get it from :func:`compile_counter`."""

    def __init__(self):
        self._lock = threading.Lock()   # compiles may run on any thread
        self._totals = dataclasses.asdict(CompileStats())
        self._outermost: dict = {}    # event -> [(start, end)] so far

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        fields = _TIMED.get(event)
        if fields is None:
            return
        count, seconds = fields
        with self._lock:
            self._totals[count] += 1
            outer = self._outermost.setdefault(event, [])
            # a nested event ends, and is reported, before the one
            # around it
            while outer and outer[-1][0] >= start:
                s, e = outer.pop()
                self._totals[seconds] -= e - s
            outer.append((start, end))
            self._totals[seconds] += end - start

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self._totals["cache_hits"] += 1

    def snapshot(self) -> CompileStats:
        with self._lock:
            return CompileStats(**self._totals)


_COUNTER: list = []


def compile_counter() -> CompileCounter:
    """The process's compile counter, registered with JAX on first use."""
    if not _COUNTER:
        counter = CompileCounter()
        jax.monitoring.register_event_time_span_listener(counter._on_span)
        jax.monitoring.register_event_listener(counter._on_event)
        _COUNTER.append(counter)
    return _COUNTER[0]
