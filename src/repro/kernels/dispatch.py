"""Unified kernel-backend dispatch (DESIGN.md §6).

Every compute kernel in ``repro/kernels/`` is a :class:`Kernel`: one name,
three backends —

* ``"pallas"``           — the compiled Pallas TPU kernel,
* ``"pallas-interpret"`` — the *same* kernel body run by the Pallas
  interpreter (any backend; this is how CPU CI exercises the real kernel
  code instead of only the oracle),
* ``"jnp"``              — the pure-jnp oracle from the kernel's ``ref.py``.

Backend selection, most specific wins:

1. per-call ``backend=`` keyword,
2. the process-global override (:func:`set_backend` / :func:`use_backend`),
3. the ``REPRO_KERNEL_BACKEND`` environment variable,
4. auto: ``"pallas"`` on TPU at every operand size, ``"jnp"`` elsewhere.

The d-tiled kernels accept a ``block_d=`` slab width (the VMEM tile along
the parameter dimension). It is a Pallas tiling knob only, so the
dispatcher strips it before calling the ``jnp`` oracle — parity across
backends holds by construction for every ``block_d``.

Selection is a trace-time (Python-level) decision, so a jitted caller bakes
the chosen backend into the compiled program; re-jit (a fresh closure) to
switch backends.

Kernels register here via :func:`register_kernel` and are *also* exposed as
the ``kernel`` registry namespace, so ``resolve("kernel", "trimmed_mean")``
returns the same dispatching callable as :func:`get_kernel` and
``REGISTRY.names("kernel")`` lists the suite.
"""
from __future__ import annotations

import collections
import contextlib
import os
from typing import Callable, Dict, Optional

import jax

BACKENDS = ("pallas", "pallas-interpret", "jnp")

#: process-global backend override; ``None`` defers to env var / auto.
_GLOBAL_BACKEND: Optional[str] = None

_KERNELS: Dict[str, "Kernel"] = {}

#: backend-selection tally keyed ``(kernel_name, backend, reason)`` with
#: reason in {"call", "global", "env", "auto"} — one increment per
#: trace-time dispatch decision, so which backend a traced program baked
#: in shows up here (and in the obs manifest). Always on: selection
#: happens at trace time, never inside a compiled program.
_DISPATCH_COUNTS: collections.Counter = collections.Counter()


def dispatch_counts() -> Dict[tuple, int]:
    """Snapshot of the backend-selection tally (see above)."""
    return dict(_DISPATCH_COUNTS)


def reset_dispatch_counts() -> None:
    _DISPATCH_COUNTS.clear()


def _check_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    return backend


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_backend() -> str:
    """The backend used when nothing overrides: env var, else auto."""
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return _check_backend(env)
    return "pallas" if on_tpu() else "jnp"


def current_backend() -> str:
    """The backend a kernel call would use right now (without a per-call
    override)."""
    return _GLOBAL_BACKEND or default_backend()


def set_backend(backend: Optional[str]) -> None:
    """Set (or with ``None`` clear) the process-global backend override."""
    global _GLOBAL_BACKEND
    _GLOBAL_BACKEND = _check_backend(backend) if backend else None


@contextlib.contextmanager
def use_backend(backend: Optional[str]):
    """Scoped :func:`set_backend`; applies to traces entered in the scope."""
    prev = _GLOBAL_BACKEND
    set_backend(backend)
    try:
        yield
    finally:
        set_backend(prev)


class Kernel:
    """A named kernel dispatching to one of its backend implementations.

    ``pallas`` and ``pallas-interpret`` share one implementation taking an
    ``interpret`` keyword; ``jnp`` is the oracle. All other arguments pass
    through unchanged — except the Pallas tiling knob ``block_d``, which
    is dropped for the oracle — so a Kernel is call-compatible with its
    oracle plus optional ``backend=`` / ``block_d=`` keywords.
    """

    __slots__ = ("name", "_jnp", "_pallas")

    def __init__(self, name: str, jnp_impl: Callable, pallas_impl: Callable):
        self.name = name
        self._jnp = jnp_impl
        self._pallas = pallas_impl

    def impl(self, backend: Optional[str] = None) -> Callable:
        b = _check_backend(backend) if backend else current_backend()
        if b == "jnp":
            return lambda *a, **kw: self._jnp(
                *a, **{k: v for k, v in kw.items() if k != "block_d"})
        if b == "pallas-interpret":
            return lambda *a, **kw: self._pallas(*a, interpret=True, **kw)
        return lambda *a, **kw: self._pallas(*a, interpret=False, **kw)

    def resolve_backend(self, backend: Optional[str] = None) -> str:
        """The backend this call would dispatch to (trace-time decision),
        most specific choice first: per-call, global, env var, auto."""
        if backend:
            return self._tally(_check_backend(backend), "call")
        if _GLOBAL_BACKEND:
            return self._tally(_GLOBAL_BACKEND, "global")
        if os.environ.get("REPRO_KERNEL_BACKEND"):
            return self._tally(default_backend(), "env")
        return self._tally(default_backend(), "auto")

    def _tally(self, backend: str, reason: str) -> str:
        _DISPATCH_COUNTS[(self.name, backend, reason)] += 1
        return backend

    def __call__(self, *args, backend: Optional[str] = None, **kwargs):
        return self.impl(self.resolve_backend(backend))(*args, **kwargs)

    def __repr__(self) -> str:
        return f"Kernel({self.name!r})"


def register_kernel(name: str, *, jnp_impl: Callable, pallas_impl: Callable,
                    **meta) -> Kernel:
    """Create a :class:`Kernel` and file it under the ``kernel`` registry
    namespace (metadata lands in ``REGISTRY.meta("kernel", name)``)."""
    from repro.core.registry import REGISTRY
    k = Kernel(name, jnp_impl, pallas_impl)
    _KERNELS[name] = k
    REGISTRY.register("kernel", name, **meta)(lambda _k=k: _k)
    return k


def get_kernel(name: str) -> Kernel:
    """Look up a registered kernel, importing providers on first use."""
    if name not in _KERNELS:
        from repro.core.registry import resolve
        # imports the kernel providers, raises KeyError with the
        # registered names on a miss, and returns the Kernel itself
        return resolve("kernel", name)
    return _KERNELS[name]

