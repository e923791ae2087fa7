"""Which device operations of a trace are which kernel, and a kernel's
share of its roofline.

The trace names a TPU op by its HLO instruction; the compiled program's
HLO text says which instructions call a Pallas kernel and, in their
``op_name`` metadata, through which jitted functions. A kernel is known
by a module ``flops/kernels/<kernel>.py`` that lists those functions
(``WRAPPERS``) and counts a call's operations and bytes (``ops_bytes``);
the outermost function on the path that some kernel lists decides.
"""
from __future__ import annotations

import functools
import importlib
import os
import pkgutil
import re

from bench.flops import kernels as kernel_pkg


@functools.lru_cache(maxsize=None)
def kernel_module(name: str):
    return importlib.import_module(f"bench.flops.kernels.{name}")


def wrappers() -> dict:
    """jitted function name -> kernel, over every kernel module."""
    out = {}
    for info in pkgutil.iter_modules([os.path.dirname(kernel_pkg.__file__)]):
        for fn in kernel_module(info.name).WRAPPERS:
            out[fn] = info.name
    return out


def classify_hlo(text: str) -> dict:
    """HLO instruction -> kernel name (``"other"`` when no kernel module
    knows it) for every Pallas call in a compiled program's text."""
    by_fn, out = wrappers(), {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        instr = re.match(r"\s*(?:ROOT )?%?([^\s=]+) = ", line).group(1)
        m = re.search(r'op_name="([^"]*)"', line)
        fns = re.findall(r"jit\((\w+)\)", m.group(1) if m else "")
        out[instr] = next((by_fn[f] for f in fns if f in by_fn), "other")
    return out


def kernel_time(trace, kernels: dict, kernel=None) -> float:
    """Device seconds of the calls of ``kernel`` (of every kernel when
    None)."""
    return trace.time_of(i for i, k in kernels.items()
                         if kernel is None or k == kernel)


def roofline_share(trace, ctx, kernel: str):
    """Least time the chip could take for the kernel's calls in the
    window (the larger of operations over peak FLOP/s and bytes over
    peak bandwidth, per call) over the kernel's device time, in %.
    None when the trace shows none of its operations."""
    calls = ctx["kernel_calls"].get(kernel)
    spent = kernel_time(trace, ctx["kernels"], kernel)
    if not calls or spent <= 0:
        return None
    peak, least = ctx["peak"], 0.0
    for n, shape in calls:
        ops, nbytes = kernel_module(kernel).ops_bytes(*shape)
        least += n * max(ops / peak["flops_per_s"],
                         nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / spent
