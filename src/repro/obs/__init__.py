"""repro.obs — zero-overhead-off telemetry (DESIGN.md §8).

Four planes:

* **In-loop metric taps** (:mod:`repro.obs.metrics`): fused loops built
  with a static ``cfg.telemetry=True`` stream per-iteration scalars and
  small vectors (honest gradient norm, agreement diameter Δ₂, per-round
  rejected-agent masks) through ``jax.debug.callback`` into host ring
  buffers and attached sinks. Off (the default) the compiled program
  holds no callback.
* **Span tracing** (:mod:`repro.obs.trace`): ``jax.named_scope`` phase
  names in every program (metadata only: free at run time and outside
  the persistent-cache key), plus host spans around engine
  compiles/dispatches and garbage collections as ``jax.profiler``
  annotations, on the device operations' clock (``--profile`` on
  ``repro.launch.train``).
* **Compile counters** (:mod:`repro.obs.compiles`): traces, lowerings,
  backend compiles and persistent-cache hits, with their seconds.
* **Sinks + manifest** (:mod:`repro.obs.sinks` /
  :mod:`repro.obs.manifest`): JSONL / in-memory / stdout-progress sinks
  and a per-run manifest including the kernel backend-dispatch counters.

Typical use::

    from repro import obs
    with obs.telemetry(obs.JsonlSink("metrics.jsonl")):
        out = run_decbyzpg(env, dataclasses.replace(cfg, telemetry=True), T)
    print(out["aggregator_confusion"]["recall"])
"""
from repro.obs.compiles import CompileStats, compile_counter
from repro.obs.manifest import build_manifest, write_manifest
from repro.obs.metrics import (RingBuffer, Recorder, capture,
                               confusion_tally, disable, enable, enabled,
                               get_recorder, progress, record, tap,
                               telemetry)
from repro.obs.sinks import (JsonlSink, MemorySink, Sink,
                             StdoutProgressSink)
from repro.obs.trace import host_span, named_phase

__all__ = [
    "RingBuffer", "Recorder", "Sink", "MemorySink", "JsonlSink",
    "StdoutProgressSink", "CompileStats", "compile_counter",
    "enabled", "enable", "disable", "telemetry", "capture",
    "get_recorder", "record", "progress", "tap", "confusion_tally",
    "named_phase", "host_span", "build_manifest", "write_manifest",
]
