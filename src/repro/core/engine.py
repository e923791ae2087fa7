"""Fused experiment engine (DESIGN.md §2): compiled-loop cache, the
Common-Sample coin stream, and vmapped multi-seed scenario grids.

The training loops in :mod:`repro.core.decbyzpg` / :mod:`repro.core.byzpg`
are single ``jax.lax.scan`` programs over iterations (one fixed-shape step,
coin drawn *inside* the scan from a folded PRNG stream, stacked on-device
histories).  This module supplies the layers above them:

* ``compiled(key, build)`` — process-wide cache of jitted loops keyed by
  the static configuration, so scenario sweeps compile each loop shape
  exactly once (the legacy per-step harness re-jitted on every call);
* ``seed_keys(seed)`` — the canonical PRNG split shared by single runs,
  legacy loops, and grid lanes, so a grid lane for seed *s* replays the
  exact key stream of ``run_*(cfg(seed=s))``;
* ``ScenarioGrid`` / ``run_grid`` — declare a scenario product over
  **any** config fields (``axes={"K": (1, 5), "eta": (1e-3, 5e-3),
  "attack": ("none", "large_noise(sigma=10)")}``) and a seed batch; seeds
  are ``jax.vmap``-ed through the fused loop in one device program per
  scenario, and results come back keyed by a per-grid ``Scenario`` tuple
  with mean ± CI summaries;
* ``Experiment`` — the declarative front door
  (``Experiment(algo=..., env=..., T=..., seeds=..., axes=..., **base)``
  with ``.run()``, ``.summary()``, ``.to_json()``), built on the component
  registry (DESIGN.md §4) so every string is a parseable component spec.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import json
from typing import Callable, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.registry import Spec, resolve

# ---------------------------------------------------------------------------
# Common-Sample coin + canonical key derivation
# ---------------------------------------------------------------------------


class SeedKeys(NamedTuple):
    init: jnp.ndarray     # policy initialization
    loop: jnp.ndarray     # per-iteration step keys (split into T)
    coin: jnp.ndarray     # PAGE coin stream (folded per iteration)


def seed_keys(seed) -> SeedKeys:
    """Canonical (init, loop, coin) key split from an integer seed.

    Traceable: ``seed`` may be a traced int32, so per-seed streams can be
    derived *inside* a vmapped grid lane.
    """
    base = jax.random.PRNGKey(seed)
    return SeedKeys(*jax.random.split(base, 3))


def page_coin(coin_key, t, p: float):
    """Common-Sample coin c_t ~ Be(p), forced to 1 at t=0, drawn from the
    per-iteration fold of the shared coin key (identical for every honest
    agent — the paper's shared-PRNG Common-Sample primitive)."""
    draw = jax.random.bernoulli(jax.random.fold_in(coin_key, t), p)
    return (t == 0) | draw


# ---------------------------------------------------------------------------
# Compiled-loop cache
# ---------------------------------------------------------------------------

_COMPILED: dict = {}


def compiled(key, build: Callable):
    """Return the cached compiled callable for ``key``, building (and
    jitting) it on first use.  Keys must capture everything static about
    the loop: algorithm, env identity, config minus seed, T, batch size.

    When host telemetry is on (:func:`repro.obs.enable`) each lookup
    emits a hit/miss record on the ``engine.cache`` stream and the build
    runs under a trace span; off, the only cost is one ``enabled()``
    check."""
    fn = _COMPILED.get(key)
    if fn is not None:
        if obs.enabled():
            obs.record("engine.cache", event="hit", key=repr(key))
        return fn
    if obs.enabled():
        obs.record("engine.cache", event="miss", key=repr(key))
        with obs.host_span("engine.build", key=repr(key)):
            fn = build()
    else:
        fn = build()
    _COMPILED[key] = fn
    return fn


def clear_cache() -> None:
    _COMPILED.clear()


def compile_count() -> int:
    """Number of cached compiled programs (one per static signature) —
    the stable surface for benchmarks/tests asserting compile counts."""
    return len(_COMPILED)


def static_key(cfg):
    """Config hashed without its seed (seeds are data, not program)."""
    return dataclasses.replace(cfg, seed=0)


def donate_args(*argnums):
    """Carry-donation argnums, empty on CPU by policy: donation is a
    device-memory play, and host allocations are cheap enough that the
    reuse is not worth coupling callers to invalidated input buffers.
    (The ``repro.analysis`` donation audit compiles each site with its
    donation *forced* so aliasing is still validated on CPU CI.)"""
    return argnums if jax.default_backend() != "cpu" else ()


# ---------------------------------------------------------------------------
# Algorithm definitions (registry namespace "algo")
# ---------------------------------------------------------------------------


class AlgoDef(NamedTuple):
    """What the engine needs from an algorithm: its config dataclass, the
    fused-loop/carry builders, and the single-run entry points. Algorithm
    modules register one under ``register("algo", name)``.

    ``traced_fields`` names the config scalars the algorithm's builders
    accept as *traced operands* (via their ``traced=`` mapping) instead of
    baked-in Python constants — the static/traced split behind lane
    batching.  Entries may be derived properties (``switch_p``); only
    real dataclass fields are blanked in the static representative.

    ``build_window`` is the windowed form of ``build_loop`` (DESIGN.md
    §12): ``build_window(env, cfg, traced=...)`` returns
    ``window(carry, ts, step_keys, coin_key) -> (carry, hist_chunk)``
    scanning an arbitrary contiguous slice of the iteration stream with
    an explicit carry, so chained windows replay the uninterrupted loop
    bit for bit.  ``carry_hist`` names the history key the one-shot loop
    fills from the final ``carry[0]`` (``"theta"`` for DecByzPG's agent
    stack, ``"vec"`` for ByzPG's server iterate) — window assembly puts
    it back."""
    config_cls: type
    build_loop: Callable
    init_carry: Callable
    run: Callable
    run_legacy: Callable
    traced_fields: Tuple[str, ...] = ()
    build_window: Optional[Callable] = None
    carry_hist: str = "theta"


def _algo(name) -> AlgoDef:
    return resolve("algo", name)


# ---------------------------------------------------------------------------
# Static/traced config split (lane batching)
# ---------------------------------------------------------------------------


def traced_value(traced, name: str, default):
    """The traced operand for ``name`` when lane batching supplies one,
    else the config's plain value (builders call this for every scalar in
    their algorithm's ``traced_fields``)."""
    if traced is None:
        return default
    return traced.get(name, default)


def traced_spec_kwargs(traced, namespace: str) -> dict:
    """Traced component kwargs for ``namespace`` (stored under
    ``"<namespace>.<kwarg>"``), ready to pass as ``resolve`` context so a
    factory receives them as array operands."""
    prefix = namespace + "."
    return {k[len(prefix):]: v for k, v in (traced or {}).items()
            if k.startswith(prefix)}


def lane_split(cfg, traced_fields):
    """Split a config into ``(static_cfg, traced_names, traced_values)``.

    ``static_cfg`` is the *lane-group representative*: the config with its
    seed zeroed, every traced dataclass field blanked, and batchable
    attack kwargs stripped from the attack Spec — two scenarios that
    differ only in traced scalars map to the same (hashable) static
    representative and therefore share one compiled program.
    ``traced_names``/``traced_values`` are the matching flat operand
    vector: the algorithm's ``traced_fields`` (derived properties like
    ``switch_p`` read but not blanked) followed by each batchable
    component field's traced-marked kwargs as ``"<namespace>.<kwarg>"``
    (attacks and aggregators — e.g. ``rfa(nu=…)`` sweeps lane-batch).
    """
    from repro.core.registry import REGISTRY
    traced = {name: float(getattr(cfg, name)) for name in traced_fields}
    fields = {f.name for f in dataclasses.fields(cfg)}
    repl = {name: 0.0 for name in traced_fields if name in fields}
    if "switch_p" in traced and "p" in fields:
        # p reaches the program only through the traced switch_p, so
        # p=None (default B/N) and an explicit equal p share a signature
        repl["p"] = None
    # component spec fields whose registry namespace marks traced_kwargs;
    # field name == namespace for both of them
    for ns in ("attack", "aggregator"):
        if ns in fields:
            static_spec, kw = REGISTRY.split_traced(ns, getattr(cfg, ns))
            repl[ns] = static_spec
            for k, v in sorted(kw.items()):
                traced[f"{ns}.{k}"] = v
    static_cfg = dataclasses.replace(cfg, seed=0, **repl)
    names = tuple(traced)
    return static_cfg, names, tuple(traced[n] for n in names)


# ---------------------------------------------------------------------------
# Scenario grids over arbitrary config axes
# ---------------------------------------------------------------------------


class Scenario(NamedTuple):
    """Legacy five-axis scenario key. Grids with other axes key results by
    a dynamically built namedtuple (``scenario_key``); namedtuples compare
    and hash as plain tuples, so positional lookups interoperate."""
    K: int
    n_byz: int
    attack: str
    aggregator: str
    agreement: str


_LEGACY_AXES = ("K", "n_byz", "attack", "aggregator", "agreement")
_LEGACY_DEFAULTS = {"K": (13,), "n_byz": (0,), "attack": ("none",),
                    "aggregator": ("rfa",), "agreement": ("mda",)}


def scenario_key(names) -> type:
    """Keyed-tuple class for one grid's axis names. Equality/hashing are
    tuple-based, so keys from different grids (or plain tuples) with the
    same values in the same order compare equal."""
    return collections.namedtuple("Scenario", tuple(names))


def _as_axis(values) -> tuple:
    return values if isinstance(values, tuple) else \
        tuple(values) if isinstance(values, (list, range)) else (values,)


@dataclasses.dataclass(frozen=True)
class ScenarioGrid:
    """Cartesian scenario axes × a vmapped seed batch.

    Axes sweep **any** config field: ``axes={"eta": (1e-3, 5e-3),
    "attack": ("none", "large_noise(sigma=10)")}``. The five historical
    axes remain available as keyword fields; constructing a grid with only
    those (or with none) reproduces the historical five-axis product with
    its old defaults. When an ``axes`` mapping is given, it alone defines
    the sweep unless legacy fields are also set, in which case the five
    legacy axes (defaults filled) are extended/overridden by ``axes``.

    Every axis combination becomes one compiled device program (cached per
    static shape); the ``seeds`` axis is vmapped inside it.
    """
    seeds: Tuple[int, ...] = (0, 1, 2)
    K: Optional[Tuple[int, ...]] = None
    n_byz: Optional[Tuple[int, ...]] = None
    attack: Optional[Tuple] = None
    aggregator: Optional[Tuple] = None
    agreement: Optional[Tuple] = None
    axes: Optional[Mapping] = None

    def resolved_axes(self) -> dict:
        """Axis name -> tuple of values, in scenario-key order."""
        legacy = {n: _as_axis(getattr(self, n)) for n in _LEGACY_AXES
                  if getattr(self, n) is not None}
        extra = {k: _as_axis(v) for k, v in dict(self.axes or {}).items()}
        if self.axes is not None and not legacy:
            return extra
        return {**_LEGACY_DEFAULTS, **legacy, **extra}

    def explicit_axes(self) -> set:
        """Axis names the caller actually asked for (vs legacy defaults
        filled in for the historical five-axis grid shape)."""
        return ({n for n in _LEGACY_AXES if getattr(self, n) is not None}
                | set(dict(self.axes or {})))

    def scenarios(self):
        """Yield one keyed Scenario tuple per axis combination. For a
        legacy-style grid this unpacks exactly like the historical
        ``(K, n_byz, attack, aggregator, agreement)`` 5-tuple; use
        ``._asdict()`` for the ``{axis: value}`` mapping."""
        axes = self.resolved_axes()
        key_cls = scenario_key(axes)
        for combo in itertools.product(*axes.values()):
            yield key_cls(*combo)


def seed_batch_loop(env, cfg, T: int, n_seeds: int, algo="decbyzpg"):
    """Compiled ``seeds (S,) int32 -> history dict`` with every per-seed
    run (init + full T-iteration fused loop) vmapped into one program."""
    algo = Spec.of(algo)
    a = _algo(algo)
    key = ("grid", algo, env.name, env.horizon, static_key(cfg), T, n_seeds)

    def build():
        loop = a.build_loop(env, cfg, T)

        def one_seed(seed):
            ks = seed_keys(seed)
            carry = a.init_carry(env, cfg, ks.init)
            return loop(*carry, jax.random.split(ks.loop, T), ks.coin)

        return jax.jit(jax.vmap(one_seed))

    return compiled(key, build)


def lane_jit(batched, sharding, n_args: int, replicated=()):
    """Jit a row-batched program over the flattened lane×seed batch.

    ``sharding`` is the caller's :func:`lane_sharding` of the rows.
    Without one this is a plain ``jax.jit``. Otherwise every device of
    its mesh runs ``batched`` on its own rows under ``shard_map``: rows
    are independent, and the Pallas kernels inside cannot be partitioned
    automatically. Argument indices in ``replicated`` are passed whole to
    every device. Outputs stay row-sharded on a local mesh and come back
    replicated on a process-spanning one, so every host can read whole
    histories."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as _P

    from repro.distributed.sharding import spans_processes
    if sharding is None:
        return jax.jit(batched)
    mesh = sharding.mesh
    specs = tuple(_P() if i in replicated else _P("lane")
                  for i in range(n_args))
    local = jax.shard_map(batched, mesh=mesh, in_specs=specs,
                          out_specs=_P("lane"), check_vma=False)
    out = NamedSharding(mesh, _P()) if spans_processes(mesh) else sharding
    return jax.jit(local,
                   in_shardings=tuple(NamedSharding(mesh, sp)
                                      for sp in specs),
                   out_shardings=out)


def lane_batch_loop(env, static_cfg, T: int, traced_names, n_rows: int,
                    algo="decbyzpg"):
    """Compiled flattened lane×seed batch: ``(vals (R, n_traced), seeds
    (R,) int32) -> history dict`` with leading axis R = lanes × seeds.

    One program serves every scenario that shares ``static_cfg``'s static
    signature: each row derives its own PRNG streams from its seed and
    overrides the traced scalars (eta, gamma, switch_p, batchable attack
    kwargs, ...) with its slice of ``vals``, so an L-point scalar sweep ×
    S seeds is a single compile and a single dispatch. The flattened
    batch axis is sharded over the ``lane_mesh`` when the row count
    divides the device count (single device: identity layout).
    """
    from repro.distributed.sharding import lane_mesh, lane_sharding
    algo = Spec.of(algo)
    a = _algo(algo)
    names = tuple(traced_names)
    mesh = lane_mesh()
    sharding = lane_sharding(mesh, n_rows)
    key = ("lanes", algo, env.name, env.horizon, static_key(static_cfg),
           names, T, n_rows, None if sharding is None else mesh.size)

    def build():
        def one(vals, seed):
            # an algorithm with no traced fields keeps the historical
            # build_loop(env, cfg, T) contract — don't pass traced=
            loop = a.build_loop(env, static_cfg, T,
                                traced=dict(zip(names, vals))) \
                if names else a.build_loop(env, static_cfg, T)
            ks = seed_keys(seed)
            carry = a.init_carry(env, static_cfg, ks.init)
            return loop(*carry, jax.random.split(ks.loop, T), ks.coin)

        return lane_jit(jax.vmap(one), sharding, 2)

    return compiled(key, build)


# ---------------------------------------------------------------------------
# Windowed execution (sweep service, DESIGN.md §12)
# ---------------------------------------------------------------------------


def window_slices(T: int, windows: int) -> tuple:
    """Split ``[0, T)`` into ``windows`` contiguous ``(start, stop)``
    slices, near-equal with the remainder spread over the leading
    windows — at most two distinct widths, so a windowed run compiles at
    most two window programs regardless of W."""
    if not 1 <= windows <= T:
        raise ValueError(f"windows must be in [1, T={T}], got {windows}")
    base, rem = divmod(T, windows)
    out, start = [], 0
    for w in range(windows):
        stop = start + base + (1 if w < rem else 0)
        out.append((start, stop))
        start = stop
    return tuple(out)


def lane_init_loop(env, static_cfg, n_rows: int, algo="decbyzpg"):
    """Compiled ``seeds (R,) int32 -> carry stack``: each row's algorithm
    carry (theta stack, optimizer state, ...) from its canonical init
    key, vmapped over the flattened lane×seed batch.  Row r's carry is
    exactly what :func:`lane_batch_loop` builds internally for the same
    seed — the entry point of the windowed execution path."""
    from repro.distributed.sharding import lane_mesh, lane_sharding
    algo = Spec.of(algo)
    a = _algo(algo)
    mesh = lane_mesh()
    sharding = lane_sharding(mesh, n_rows)
    key = ("lanes_init", algo, env.name, env.horizon,
           static_key(static_cfg), n_rows,
           None if sharding is None else mesh.size)

    def build():
        def one(seed):
            return a.init_carry(env, static_cfg, seed_keys(seed).init)

        return lane_jit(jax.vmap(one), sharding, 1)

    return compiled(key, build)


def lane_window_loop(env, static_cfg, T: int, traced_names, W: int,
                     n_rows: int, algo="decbyzpg"):
    """Compiled window step ``(carry, vals (R, n), seeds (R,), ts (W,))
    -> (carry, hist chunk)`` over the flattened lane×seed batch.

    ``ts`` holds the window's *absolute* iteration indices as traced
    data, so the cache key carries no offset: every width-W window of a
    T-iteration run shares one compiled program.  Each row re-derives
    its full-T step-key stream from its seed and gathers the ``ts``
    slice, so chaining the windows of :func:`window_slices` replays the
    exact key stream of the uninterrupted :func:`lane_batch_loop` scan —
    bit for bit (``T`` stays in the key because the stream length is
    baked into the split)."""
    from repro.distributed.sharding import lane_mesh, lane_sharding
    algo = Spec.of(algo)
    a = _algo(algo)
    if a.build_window is None:
        raise ValueError(
            f"algorithm {algo.canonical()!r} registers no build_window; "
            f"windowed execution needs the explicit-carry builder")
    names = tuple(traced_names)
    mesh = lane_mesh()
    sharding = lane_sharding(mesh, n_rows)
    key = ("lanes_window", algo, env.name, env.horizon,
           static_key(static_cfg), names, T, W, n_rows,
           None if sharding is None else mesh.size)

    def build():
        def one(carry, vals, seed, ts):
            window = a.build_window(env, static_cfg,
                                    traced=dict(zip(names, vals))) \
                if names else a.build_window(env, static_cfg)
            ks = seed_keys(seed)
            step_keys = jax.random.split(ks.loop, T)[ts]
            return window(carry, ts, step_keys, ks.coin)

        return lane_jit(jax.vmap(one, in_axes=(0, 0, 0, None)), sharding,
                        4, replicated=(3,))

    return compiled(key, build)


def seed_init_loop(env, cfg, n_seeds: int, algo="decbyzpg"):
    """Windowed counterpart of :func:`seed_batch_loop`'s init half:
    compiled ``seeds (S,) -> carry stack`` for one scenario config."""
    algo = Spec.of(algo)
    a = _algo(algo)
    key = ("grid_init", algo, env.name, env.horizon, static_key(cfg),
           n_seeds)

    def build():
        def one_seed(seed):
            return a.init_carry(env, cfg, seed_keys(seed).init)

        return jax.jit(jax.vmap(one_seed))

    return compiled(key, build)


def seed_window_loop(env, cfg, T: int, W: int, n_seeds: int,
                     algo="decbyzpg"):
    """Windowed counterpart of :func:`seed_batch_loop`: compiled
    ``(carry, seeds (S,), ts (W,)) -> (carry, hist chunk)`` — the
    per-scenario (lanes=False) form of :func:`lane_window_loop`, with
    the same offset-free cache key and bit-identical chaining."""
    algo = Spec.of(algo)
    a = _algo(algo)
    if a.build_window is None:
        raise ValueError(
            f"algorithm {algo.canonical()!r} registers no build_window; "
            f"windowed execution needs the explicit-carry builder")
    key = ("grid_window", algo, env.name, env.horizon, static_key(cfg),
           T, W, n_seeds)

    def build():
        window = a.build_window(env, cfg)

        def one_seed(carry, seed, ts):
            ks = seed_keys(seed)
            return window(carry, ts, jax.random.split(ks.loop, T)[ts],
                          ks.coin)

        return jax.jit(jax.vmap(one_seed, in_axes=(0, 0, None)))

    return compiled(key, build)


def lane_carry_struct(env, static_cfg, n_rows: int, algo="decbyzpg"):
    """Shape/dtype skeleton of the lane carry stack via ``jax.eval_shape``
    — no compile, no cache entry — for use as a checkpoint restore
    template when resuming a window mid-T."""
    a = _algo(Spec.of(algo))

    def one(seed):
        return a.init_carry(env, static_cfg, seed_keys(seed).init)

    return jax.eval_shape(jax.vmap(one),
                          jax.ShapeDtypeStruct((n_rows,), jnp.int32))


def assemble_hist(carry, chunks, algo="decbyzpg") -> dict:
    """Stitch window hist chunks (leading row axis, time axis 1) and the
    final carry back into the one-shot loop's history dict: concatenated
    per-iteration histories plus the algorithm's ``carry_hist`` key
    (final ``carry[0]``, e.g. the theta stack) — :func:`summarize`-ready
    and bit-identical to the uninterrupted loop's output."""
    a = _algo(Spec.of(algo))
    hist = {a.carry_hist: np.asarray(carry[0])}
    for k in chunks[0]:
        hist[k] = np.concatenate([np.asarray(c[k]) for c in chunks],
                                 axis=1)
    return hist


def _pad_rows(x, n_pad: int):
    """Pad a leading row axis to ``n_pad`` by repeating the last row:
    pad rows are valid (redundant) programs whose outputs are sliced off
    before summaries, letting uneven lane×seed batches still shard over
    the lane mesh (DESIGN.md §12)."""
    n = x.shape[0]
    if n == n_pad:
        return x
    pad = jnp.broadcast_to(x[-1:], (n_pad - n,) + x.shape[1:])
    return jnp.concatenate([x, pad], axis=0)


def summarize(hist: dict, cfg) -> dict:
    """Host-side statistics for one scenario's (S, T) seed batch."""
    out = {k: np.asarray(v) for k, v in hist.items()}
    coins = out.pop("coins")
    out["samples"] = np.cumsum(np.where(coins, cfg.N, cfg.B), axis=-1)
    rets = out["returns"]
    S = rets.shape[0]
    sem = (rets.std(axis=0, ddof=1) / np.sqrt(S)) if S > 1 \
        else np.zeros(rets.shape[-1])
    out["returns_mean"] = rets.mean(axis=0)
    out["returns_ci95"] = 1.96 * sem
    final = rets[:, -3:].mean(axis=-1)
    out["final_return_mean"] = float(final.mean())
    out["final_return_ci95"] = float(
        1.96 * final.std(ddof=1) / np.sqrt(S)) if S > 1 else 0.0
    if "diameter" in out:
        # the paper's Δ₂ agreement diagnostic, reported alongside returns
        diam = out["diameter"]
        out["diameter_mean"] = diam.mean(axis=0)
        out["final_diameter_mean"] = float(diam[:, -1].mean())
    if "rejected" in out:
        # telemetry plane (cfg.telemetry): aggregator-as-detector tally
        # of per-round rejected masks vs the configured Byzantine set
        out["grad_norm_mean"] = out["grad_norm"].mean(axis=0)
        out["aggregator_confusion"] = obs.confusion_tally(
            out["rejected"], getattr(cfg, "n_byz", 0))
    return out


def _check_override(cfg_before, cfg_after, assign: dict) -> None:
    """An ``override`` hook may derive non-axis fields from axis values,
    but must not mutate an axis field itself — the result would silently
    diverge from the Scenario key it is filed under."""
    changed = [n for n in assign
               if getattr(cfg_after, n) != getattr(cfg_before, n)]
    if changed:
        raise ValueError(
            f"override mutated swept axis field(s) {changed}: the config "
            f"would no longer match its Scenario key {assign}; sweep the "
            f"desired values as an axis instead")


def grid_scenarios(grid: ScenarioGrid, algo="decbyzpg",
                   override: Optional[Callable] = None,
                   base: Optional[dict] = None):
    """Resolve a grid into ``(axes, [(scenario_key, cfg), ...])`` — the
    scenario construction shared by :func:`run_grid` and ``repro.sweep``:
    axis validation, base-field merging, and the ``override`` hook with
    its axis-mutation check.  Deterministic order (itertools.product over
    the axis mapping), so a resumed sweep re-derives the identical
    scenario list."""
    a = _algo(Spec.of(algo))
    base = dict(base or {})
    cfg_cls = a.config_cls
    fields = {f.name for f in dataclasses.fields(cfg_cls)}
    axes = grid.resolved_axes()
    # legacy-default axes a config doesn't know (e.g. "agreement" for
    # ByzPG) stay in the key but are dropped from the config, as the
    # historical five-axis grid did; explicitly requested axes must exist.
    unknown = ((set(base) | (set(axes) & grid.explicit_axes()))
               - fields)
    if unknown:
        raise TypeError(f"unknown {cfg_cls.__name__} fields: "
                        f"{sorted(unknown)}")
    overlap = set(base) & set(axes)
    explicit_overlap = overlap & grid.explicit_axes()
    if explicit_overlap:
        raise TypeError(f"fields both swept and fixed: "
                        f"{sorted(explicit_overlap)}")
    # base may pin an axis the grid only holds as a legacy default — the
    # pinned value becomes that axis's single point (and its key value)
    for n in overlap:
        axes[n] = (base.pop(n),)
    key_cls = scenario_key(axes)
    scenarios = []
    for combo in itertools.product(*axes.values()):
        assign = {k: v for k, v in zip(axes, combo) if k in fields}
        cfg = cfg_cls(**{**base, **assign})
        if override is not None:
            cfg2 = override(cfg)
            _check_override(cfg, cfg2, assign)
            cfg = cfg2
        scenarios.append((key_cls(*combo), cfg))
    return axes, scenarios


def lane_groups(scenarios, algo="decbyzpg") -> dict:
    """Group ``(scenario, cfg)`` pairs by lane-static signature
    (:func:`lane_split`): ``{(static_cfg, names): [(scn, cfg, vals)]}``
    in first-appearance order.  One group is both the unit of
    compilation for lane batching and the unit of checkpointing for the
    sweep service (``repro.sweep``)."""
    a = _algo(Spec.of(algo))
    groups: dict = {}
    for scn, cfg in scenarios:
        static_cfg, names, vals = lane_split(cfg, a.traced_fields)
        groups.setdefault((static_cfg, names), []).append((scn, cfg, vals))
    return groups


def lane_operands(members, seeds, n_pad: int):
    """Flattened ``(vals (R, n), seeds (R,))`` device operands for one
    lane group's members × the seed batch, padded to ``n_pad`` rows
    (:func:`_pad_rows`).  Traced values go float64 host-side and are
    canonicalized by ``jnp.asarray`` to the ambient float dtype (f32 by
    default, f64 under jax_enable_x64) so the operands match what
    ``lanes=False`` bakes in as Python constants."""
    S = len(seeds)
    vals = np.asarray([m[2] for m in members], np.float64)
    vals_flat = _pad_rows(jnp.asarray(np.repeat(vals, S, axis=0)), n_pad)
    seeds_flat = _pad_rows(jnp.tile(seeds, len(members)), n_pad)
    return vals_flat, seeds_flat


def run_grid(env, grid: ScenarioGrid, T: int, algo="decbyzpg",
             override: Optional[Callable] = None, lanes: bool = True,
             **base) -> dict:
    """Run every scenario in ``grid`` for ``T`` iterations.

    ``base`` sets non-axis config fields (N, B, eta, kappa, ...);
    ``override(cfg) -> cfg`` applies per-scenario adjustments to
    *non-axis* fields derived from axis values (e.g. fig2's kappa=0 naive
    baseline) — mutating a swept axis field raises, since the config would
    silently diverge from its Scenario key. Returns ``{Scenario: summary
    dict}`` with per-seed histories plus mean ± 95% CI curves, keyed by
    the grid's keyed tuple over its axis names.

    With ``lanes=True`` (default) scenarios are grouped by static
    signature (:func:`lane_split`) and each group runs as **one** compiled
    lane-batched program over the flattened lane×seed batch — an L-point
    scalar sweep (eta, gamma, a batchable attack sigma, ...) is one
    compile and one dispatch instead of L. When the flattened row count
    does not divide the lane-mesh device count, the batch is padded with
    masked duplicate rows (sliced off before summaries) so uneven groups
    still shard. ``lanes=False`` keeps the historical per-scenario
    dispatch (one :func:`seed_batch_loop` per combination) — the baseline
    ``bench_engine`` measures against.
    """
    _, scenarios = grid_scenarios(grid, algo=algo, override=override,
                                  base=base)
    seeds = jnp.asarray(grid.seeds, jnp.int32)
    if not lanes:
        results = {}
        for si, (scn, cfg) in enumerate(scenarios):
            if obs.enabled():
                obs.progress(f"run_grid {si + 1}/{len(scenarios)}: "
                             f"{dict(scn._asdict())}",
                             scenario=si, total=len(scenarios))
            loop = seed_batch_loop(env, cfg, T, len(grid.seeds), algo)
            with obs.host_span("run_grid.scenario", scenario=si):
                hist = jax.block_until_ready(loop(seeds))
            results[scn] = summarize(hist, cfg)
        return results
    # group scenario lanes by static signature: scalar-only axes collapse
    # into one compiled program per group, seeds stay vmapped inside
    from repro.distributed.sharding import lane_mesh, padded_rows
    groups = lane_groups(scenarios, algo=algo)
    mesh = lane_mesh()
    S = len(grid.seeds)
    results = {}
    for gi, ((static_cfg, names), members) in enumerate(groups.items()):
        L = len(members)
        rows = L * S
        n_pad = padded_rows(mesh, rows)
        before = compile_count()
        loop = lane_batch_loop(env, static_cfg, T, names, n_pad, algo)
        fresh = compile_count() > before    # first dispatch will compile
        if obs.enabled():
            obs.progress(f"run_grid group {gi + 1}/{len(groups)}: "
                         f"{L} lane(s) x {S} seed(s)"
                         + (" [compiling]" if fresh else " [cached]"),
                         group=gi, lanes=L, seeds=S, fresh_compile=fresh)
        vals_flat, seeds_flat = lane_operands(members, seeds, n_pad)
        with obs.host_span("run_grid.group", group=gi, lanes=L,
                           rows=rows, fresh_compile=fresh):
            hist = jax.block_until_ready(loop(vals_flat, seeds_flat))
        for i, (scn, cfg, _) in enumerate(members):
            # the per-scenario slice never reaches the pad rows (i < L)
            lane = {k: v[i * S:(i + 1) * S] for k, v in hist.items()}
            results[scn] = summarize(lane, cfg)
    return {scn: results[scn] for scn, _ in scenarios}


# ---------------------------------------------------------------------------
# Declarative Experiment API
# ---------------------------------------------------------------------------


def _axis_str(v) -> str:
    """Canonical display form of one scenario-axis value."""
    return v.canonical() if isinstance(v, Spec) else str(v)


def _axis_eq(a, b) -> bool:
    """Axis-value equality with Spec/string interchangeability: a Spec
    matches its spec string (and vice versa)."""
    if a == b:
        return True
    if isinstance(a, Spec) or isinstance(b, Spec):
        try:
            return Spec.of(a) == Spec.of(b)
        except Exception:
            return False
    return False


class ExperimentResult:
    """Results of one :class:`Experiment` run: a mapping from scenario key
    (keyed tuple over the experiment's axis names) to summary dict, plus
    JSON/plaintext reporting."""

    def __init__(self, meta: dict, axes: dict, results: dict):
        self.meta = meta
        self.axes = axes
        self.results = results

    def __getitem__(self, key):
        return self.results[key]

    def __iter__(self):
        return iter(self.results)

    def __len__(self):
        return len(self.results)

    def items(self):
        return self.results.items()

    def keys(self):
        return self.results.keys()

    def sel(self, **axes):
        """The unique scenario matching the given axis values, e.g.
        ``res.sel(aggregator="rfa")``. Spec-valued axes match their
        string/canonical forms interchangeably. Under-specified queries
        raise a ``KeyError`` naming the still-free axes (and their
        values) instead of dumping every scenario tuple."""
        names = list(self.axes)
        bad = set(axes) - set(names)
        if bad:
            raise KeyError(f"{sorted(bad)} are not sweep axes of this "
                           f"experiment; axes: {sorted(names)}")
        matches = [s for s in self.results
                   if all(_axis_eq(getattr(s, k), v)
                          for k, v in axes.items())]
        if len(matches) == 1:
            return self.results[matches[0]]
        query = ", ".join(f"{k}={_axis_str(v)}" for k, v in axes.items())
        if not matches:
            raise KeyError(
                f"sel({query}) matches no scenario; axis values: "
                + "; ".join(f"{k} in {[_axis_str(v) for v in vals]}"
                            for k, vals in self.axes.items()))
        free = [k for k in names if k not in axes and
                len({_axis_str(getattr(s, k)) for s in matches}) > 1]
        raise KeyError(
            f"sel({query}) is under-specified: {len(matches)} scenarios "
            f"match; also constrain the free axis(es) "
            + "; ".join(f"{k} in {sorted({_axis_str(getattr(s, k)) for s in matches})}"
                        for k in free))

    @staticmethod
    def scenario_name(scn) -> str:
        """Stable ``"axis=value,..."`` name: Spec-valued entries render as
        their canonical spec string, so the name is identical whether the
        axis value was given as a Spec or its string form."""
        if not scn:
            return "base"
        return ",".join(f"{k}={_axis_str(v)}"
                        for k, v in zip(scn._fields, scn))

    def summary(self) -> dict:
        """Compact per-scenario statistics keyed by ``"axis=value,..."``."""
        out = {}
        for scn, r in self.results.items():
            entry = {
                "final_return_mean": r["final_return_mean"],
                "final_return_ci95": r["final_return_ci95"],
                "samples_per_agent": float(
                    np.asarray(r["samples"])[:, -1].mean()),
            }
            # Δ₂ diagnostic; absent for algos without agreement (ByzPG)
            if "final_diameter_mean" in r:
                entry["honest_diameter_final"] = r["final_diameter_mean"]
            # aggregator-as-Byzantine-detector forensics (cfg.telemetry)
            if "aggregator_confusion" in r:
                conf = r["aggregator_confusion"]
                entry["aggregator_precision"] = conf["precision"]
                entry["aggregator_recall"] = conf["recall"]
            out[self.scenario_name(scn)] = entry
        return out

    def to_json(self, path=None, curves: bool = True):
        """JSON document (written to ``path`` when given) with experiment
        metadata and per-scenario summaries; ``curves`` includes the
        mean ± CI return curves (per-seed parameter arrays are omitted)."""
        doc = {"experiment": self.meta, "scenarios": []}
        summ = self.summary()
        for scn, r in self.results.items():
            entry = {"scenario": dict(zip(scn._fields, [
                v.canonical() if isinstance(v, Spec) else v for v in scn])),
                **summ[self.scenario_name(scn)]}
            if curves:
                entry["returns_mean"] = np.asarray(
                    r["returns_mean"]).tolist()
                entry["returns_ci95"] = np.asarray(
                    r["returns_ci95"]).tolist()
                entry["samples_mean"] = np.asarray(
                    r["samples"]).mean(axis=0).tolist()
            doc["scenarios"].append(entry)
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f, indent=2)
        return doc


class Experiment:
    """Declarative experiment over the fused engine (DESIGN.md §4).

    ::

        Experiment(algo="decbyzpg", env="cartpole(horizon=100)", T=40,
                   seeds=4, axes={"eta": (1e-2, 2e-2),
                                  "attack": ("none",
                                             "large_noise(sigma=10)")},
                   K=13, n_byz=3, N=20, B=4).run()

    ``axes`` sweeps any config fields (values are component spec strings,
    Specs, or plain values); remaining keyword arguments fix base config
    fields. ``seeds`` is a tuple of seeds or an int (``range(seeds)``);
    ``env`` is an ``Env`` or an env spec resolved through the registry.
    ``override(cfg) -> cfg`` derives non-axis fields per scenario and is
    validated against axis mutation exactly like :func:`run_grid` (it is
    the same check — ``run()`` executes through ``run_grid``).
    """

    def __init__(self, algo="decbyzpg", env="cartpole", T: int = 50,
                 seeds=(0, 1, 2), axes: Optional[Mapping] = None,
                 override: Optional[Callable] = None, lanes: bool = True,
                 **base):
        self.algo = Spec.of(algo)
        self.env_spec = env
        self.T = int(T)
        self.seeds = tuple(range(seeds)) if isinstance(seeds, int) \
            else tuple(seeds)
        self.axes = {k: _as_axis(v) for k, v in dict(axes or {}).items()}
        self.override = override
        self.lanes = lanes
        self.base = base
        self._result: Optional[ExperimentResult] = None

    @property
    def env(self):
        from repro.rl.envs import make_env
        return make_env(self.env_spec)

    def run(self, force: bool = False) -> ExperimentResult:
        """Execute (or return the cached) run. Compiled loops are cached
        process-wide, so ``run(force=True)`` re-executes without
        recompiling."""
        if self._result is not None and not force:
            return self._result
        env = self.env
        grid = ScenarioGrid(seeds=self.seeds, axes=self.axes)
        results = run_grid(env, grid, self.T, algo=self.algo,
                           override=self.override, lanes=self.lanes,
                           **self.base)
        meta = {"algo": self.algo.canonical(),
                "env": (Spec.of(self.env_spec).canonical()
                        if isinstance(self.env_spec, (str, Spec))
                        else env.name),
                "T": self.T, "seeds": list(self.seeds),
                "axes": {k: [v.canonical() if isinstance(v, Spec) else v
                             for v in vals]
                         for k, vals in self.axes.items()},
                "base": {k: (v.canonical() if isinstance(v, Spec) else
                             repr(v) if not isinstance(
                                 v, (int, float, bool, str, type(None)))
                             else v)
                         for k, v in self.base.items()},
                # marker only: the hook itself is code and can't round-trip
                "override": (getattr(self.override, "__qualname__",
                                     repr(self.override))
                             if self.override is not None else None)}
        self._result = ExperimentResult(meta, self.axes, results)
        return self._result

    def summary(self) -> dict:
        return self.run().summary()

    def to_json(self, path=None, curves: bool = True):
        return self.run().to_json(path, curves=curves)
