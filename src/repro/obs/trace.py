"""Span tracing (DESIGN.md §8): device-program phase names and host
spans, both on the profiler's clock.

* :func:`named_phase` wraps a region of *traced* code in
  ``jax.named_scope``. The name lands in the ``op_name`` metadata of
  every instruction the region compiles to, so the compiled program's
  text, and with it a ``jax.profiler`` capture, tells which phase
  (rollout, gradient, aggregate, agree, ...) each device operation
  belongs to. Names are metadata: the optimized program is the same
  with or without them, so they cost nothing at run time, and JAX's
  persistent-cache key strips them (unless
  ``jax_compilation_cache_include_metadata_in_key`` is set). So they are
  always on; ``cfg.telemetry`` gates only the in-loop taps.
* :func:`host_span` enters a ``jax.profiler.TraceAnnotation`` while host
  instrumentation is on (:func:`repro.obs.enable` /
  :func:`repro.obs.telemetry`): engine builds, ``run_grid`` lane groups,
  ``launch/train.py`` windows. A profiler trace then holds the host
  spans and the device operations on one clock.
* :func:`gc_spans` — while host instrumentation is on, every Python
  garbage collection is wrapped in a ``host.gc`` annotation carrying its
  generation, so a device idle gap can be put down to a collection, or
  ruled out.

Off, :func:`host_span` is a shared ``nullcontext`` and no GC callback is
registered: the hot loops pay for nothing.
"""
from __future__ import annotations

import contextlib
import gc

import jax

from repro.obs import metrics as _metrics

_NULL = contextlib.nullcontext()

#: the host span around each garbage collection
GC_SPAN = "host.gc"


def named_phase(name: str):
    """``jax.named_scope(name)``: the phase's name on the ``op_name`` of
    every instruction traced inside it."""
    return jax.named_scope(name)


def host_span(name: str, **args):
    """A profiler annotation (``args`` as its metadata) while host
    instrumentation is on, else a free no-op. The single guard the hot
    host paths (``engine.compiled``, ``run_grid``) call — one list
    lookup when off."""
    if not _metrics.enabled():
        return _NULL
    return jax.profiler.TraceAnnotation(name, **args)


#: the annotation of the collection under way (collections do not nest)
_GC_OPEN: list = []


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        ann = jax.profiler.TraceAnnotation(
            GC_SPAN, generation=info["generation"])
        ann.__enter__()
        _GC_OPEN.append(ann)
    elif _GC_OPEN:
        _GC_OPEN.pop().__exit__(None, None, None)


def gc_spans(on: bool) -> None:
    """Register (``on``) or remove the ``host.gc`` callback; the
    host-instrumentation switch calls this."""
    registered = _gc_callback in gc.callbacks
    if on and not registered:
        gc.callbacks.append(_gc_callback)
    elif not on and registered:
        gc.callbacks.remove(_gc_callback)
