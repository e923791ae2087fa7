"""In-loop metric taps and the host-side recorder (DESIGN.md §8).

Two switches govern telemetry, by design:

* ``cfg.telemetry`` (a *static* config field participating in
  ``engine.static_key``) decides whether a fused loop's compiled program
  contains tap callbacks at all. Off (the default) the program is the
  exact seed program — no dead ``debug_callback`` in the jaxpr, same
  compile-cache entries.
* :func:`enable` / :func:`disable` (process-global) gate the *host-side*
  instrumentation — engine spans, ``run_grid`` progress events, manifest
  records, the ``host.gc`` spans — which must cost nothing when off.

A telemetry-enabled program emits per-iteration records through
:func:`tap`, which lowers to one ``jax.debug.callback`` per scan step;
records land in per-stream ring buffers on the :class:`Recorder` and fan
out to any attached sinks. Under a vmapped lane batch the callback fires
once per batch row per iteration (JAX's batching rule unrolls it), so the
stream interleaves rows; the stacked histories returned by the loop remain
the per-scenario source of truth — the stream is for live observation.

Taps never consume PRNG keys and never perturb the numerics: a run with
``telemetry=True`` returns bit-identical histories to the same run with
``telemetry=False`` (asserted in ``tests/test_obs.py``).
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import Optional

import jax
import numpy as np

from repro.obs.sinks import MemorySink, Sink, StdoutProgressSink

#: default per-stream ring-buffer capacity (records, not bytes)
RING_CAPACITY = 4096

_ENABLED = [False]


def enabled() -> bool:
    """Is host-side instrumentation (spans, progress, records) on?"""
    return _ENABLED[0]


def _set_enabled(on: bool) -> None:
    from repro.obs.trace import gc_spans   # trace imports this module
    _ENABLED[0] = on
    gc_spans(on)


def enable() -> None:
    _set_enabled(True)


def disable() -> None:
    _set_enabled(False)


class RingBuffer:
    """Bounded per-stream record store (newest ``capacity`` records)."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self._q = collections.deque(maxlen=capacity)
        self.dropped = 0          # records evicted since creation

    def append(self, record: dict) -> None:
        if len(self._q) == self._q.maxlen:
            self.dropped += 1
        self._q.append(record)

    def __len__(self) -> int:
        return len(self._q)

    def __iter__(self):
        return iter(self._q)

    def latest(self) -> Optional[dict]:
        return self._q[-1] if self._q else None


class Recorder:
    """Per-stream ring buffers + attached sinks.

    ``record(stream, payload)`` always lands in the stream's ring buffer
    (cheap, bounded) and fans out to every attached sink. The default
    recorder carries one :class:`StdoutProgressSink` so ``progress``
    lines reach the terminal with no setup.
    """

    def __init__(self, capacity: int = RING_CAPACITY,
                 sinks: Optional[list] = None):
        self.capacity = capacity
        self.streams: dict = {}
        self.sinks: list = list(sinks) if sinks is not None \
            else [StdoutProgressSink()]

    def record(self, stream: str, payload: dict) -> None:
        buf = self.streams.get(stream)
        if buf is None:
            buf = self.streams[stream] = RingBuffer(self.capacity)
        rec = {"stream": stream, **payload}
        buf.append(rec)
        for sink in self.sinks:
            sink.emit(rec)

    def add_sink(self, sink: Sink) -> Sink:
        self.sinks.append(sink)
        return sink

    def remove_sink(self, sink: Sink) -> None:
        self.sinks.remove(sink)

    def stream(self, name: str) -> list:
        """Snapshot of one stream's ring buffer (oldest first)."""
        return list(self.streams.get(name, ()))

    def clear(self) -> None:
        self.streams.clear()

    def close(self) -> None:
        for sink in self.sinks:
            sink.close()


_RECORDER = Recorder()


def get_recorder() -> Recorder:
    return _RECORDER


def record(stream: str, **payload) -> None:
    """Host-side record (engine spans, grid progress, manifests)."""
    _RECORDER.record(stream, payload)


def progress(message: str, **fields) -> None:
    """One progress line through the recorder's stdout sink — the single
    reporting path for benchmarks/examples entry points (replaces their
    historical ad-hoc ``print`` calls)."""
    _RECORDER.record("progress", {"message": message, **fields})


@contextlib.contextmanager
def telemetry(*sinks: Sink, keep: bool = False):
    """Enable host-side instrumentation and attach ``sinks`` for the
    scope; yields the recorder. ``keep=True`` leaves attached sinks in
    place on exit (callers manage ``close``)."""
    prev = _ENABLED[0]
    _set_enabled(True)
    for s in sinks:
        _RECORDER.add_sink(s)
    try:
        yield _RECORDER
    finally:
        _set_enabled(prev)
        if not keep:
            for s in sinks:
                _RECORDER.remove_sink(s)
                s.close()


@contextlib.contextmanager
def capture(stream: Optional[str] = None):
    """Collect records into a fresh :class:`MemorySink` for the scope
    (optionally filtered to one stream); yields the sink."""
    sink = MemorySink()
    with telemetry(sink):
        yield sink
    if stream is not None:
        sink.records = [r for r in sink.records
                        if r.get("stream") == stream]


def _tap_host(stream: str, **values) -> None:
    """Host target of the in-loop tap callback: route by stream name at
    call time (the compiled program is cached and shared across runs, so
    it must not capture a recorder instance)."""
    _RECORDER.record(stream, {k: np.asarray(v) for k, v in values.items()})


def tap(stream: str, **values) -> None:
    """Emit per-iteration values from inside a traced fused loop.

    Only call under a static ``cfg.telemetry`` check — the callback is
    baked into the compiled program, which is exactly why the off path
    must never reach this function. Values must not include PRNG keys
    (taps are observers, not consumers of the key stream)."""
    jax.debug.callback(functools.partial(_tap_host, stream), **values)


# ---------------------------------------------------------------------------
# Byzantine forensics: rejected-mask confusion tally
# ---------------------------------------------------------------------------


def confusion_tally(rejected, n_byz: int) -> dict:
    """Confusion tally of per-round rejected-agent masks vs the ground
    truth Byzantine set (agents ``0..n_byz-1`` by construction).

    ``rejected``: bool array ``(..., K)`` — any number of leading axes
    (rounds, seeds, lanes) is summed over. Returns counts plus
    precision/recall of the aggregator viewed as a Byzantine detector
    (the ``Experiment.summary()`` ``aggregator_precision/recall``
    metric)."""
    rej = np.asarray(rejected).astype(bool)
    K = rej.shape[-1]
    truth = np.arange(K) < n_byz
    flat = rej.reshape(-1, K)
    tp = int(np.sum(flat & truth))
    fp = int(np.sum(flat & ~truth))
    fn = int(np.sum(~flat & truth))
    tn = int(np.sum(~flat & ~truth))
    return {
        "rounds": int(flat.shape[0]), "n_byz": int(n_byz), "K": int(K),
        "tp": tp, "fp": fp, "fn": fn, "tn": tn,
        "precision": tp / (tp + fp) if tp + fp else 0.0,
        "recall": tp / (tp + fn) if tp + fn else 0.0,
    }
