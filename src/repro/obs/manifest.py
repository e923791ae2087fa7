"""Per-run manifest (DESIGN.md §8): the static facts a telemetry stream
needs to be interpretable after the fact — versions, devices, mesh,
compile counts, and the kernel-dispatch counters (which backend each
traced kernel call baked in).
"""
from __future__ import annotations

import json
import time
from typing import Optional

import jax


def build_manifest(extra: Optional[dict] = None) -> dict:
    """Snapshot the run environment. ``extra`` merges caller-provided
    facts (spec strings, CLI args, mesh axis names)."""
    from repro.core import engine
    from repro.kernels import dispatch
    m = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "devices": [str(d) for d in jax.devices()],
        "compiled_loop_cache_entries": engine.compile_count(),
        "kernel_dispatch_counts": {
            f"{name}:{backend}:{reason}": n
            for (name, backend, reason), n
            in sorted(dispatch.dispatch_counts().items())},
    }
    if extra:
        m.update(extra)
    return m


def write_manifest(path: str, extra: Optional[dict] = None) -> dict:
    doc = build_manifest(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    return doc
