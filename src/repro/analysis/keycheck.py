"""PRNG-key discipline over the real compiled-program builders.

The paper's variance-reduction and robustness arguments assume the K
per-agent trajectory batches are sampled i.i.d. — a reused PRNG key across
agents (or across the attack/aggregation/agreement draws of one round)
silently correlates the streams without failing any numeric test.  This
pass traces the actual fused loop builders (``build_decbyzpg_loop``,
``build_byzpg_loop``, ``lane_batch_loop``, ``fed_train_step[_flat]``,
``fed_train_window``) and walks the resulting ClosedJaxpr with a key-identity
dataflow analysis:

* every key-typed input / ``random_seed`` output gets a fresh identity;
* ``random_wrap``/``random_unwrap``/reshapes propagate the identity;
* static slices of a split batch derive *distinct* sub-stream identities
  (so ``unwrap → slice → squeeze → wrap`` subkey extraction is clean);
* ``random_split``/``random_fold_in`` consume the parent and produce fresh
  children; ``random_bits`` (the sink under ``normal``/``bernoulli``/...)
  is a *sample* of its operand.

Contracts checked per key identity:

* ``key-reuse`` — sampled by ≥2 primitives that can both execute
  (events in sibling ``lax.cond`` branches are mutually exclusive);
* ``sample-then-derive`` — sampled *and* split/folded (children of a
  sampled key correlate with the sample);
* ``double-split`` — split twice (identical child streams);
* ``scan-invariant-sample`` — sampled inside a ``scan``/``while`` body
  while originating outside the loop (same draw every iteration; fold in
  the loop index first);
* ``per-agent-fanout`` — the algo loops must contain a K-wide
  ``random_split`` feeding the per-agent sampling streams.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding

try:
    from jax._src.core import Literal as _Literal
except Exception:                                   # pragma: no cover
    _Literal = type(None)

SAMPLE = "sample"
SPLIT = "split"
FOLD = "fold_in"

# identity-preserving prims: same key stream, new layout
_PASSTHROUGH = {
    "random_wrap", "random_unwrap", "reshape", "squeeze", "expand_dims",
    "broadcast_in_dim", "transpose", "copy", "convert_element_type",
    "stop_gradient", "device_put",
}
# static reslicing of a key batch: derived sub-stream, distinct per params
_SLICING = {"slice", "gather", "dynamic_slice"}


class _Key:
    """One PRNG stream identity flowing through a jaxpr."""

    __slots__ = ("label", "depth")

    def __init__(self, label: str, depth: int):
        self.label = label
        self.depth = depth      # number of enclosing loop bodies at creation


@dataclasses.dataclass(frozen=True)
class _Event:
    kind: str
    path: str
    line: int
    ctx: tuple          # ((cond_uid, branch_idx), ...) enclosing cond path
    loop_invariant: bool


def _conflicts(a: _Event, b: _Event) -> bool:
    """Can both events execute in one evaluation?  Events diverging at a
    common ``lax.cond`` into different branches are mutually exclusive."""
    for x, y in zip(a.ctx, b.ctx):
        if x != y:
            return not (x[0] == y[0] and x[1] != y[1])
    return True


def _any_conflicting_pair(evs_a, evs_b) -> Optional[tuple]:
    for a in evs_a:
        for b in evs_b:
            if a is not b and _conflicts(a, b):
                return a, b
    return None


def _is_key_aval(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        if jnp.issubdtype(dtype, jax.dtypes.prng_key):
            return True
    except Exception:
        pass
    shape = getattr(aval, "shape", ())
    return dtype == jnp.uint32 and len(shape) >= 1 and shape[-1] == 2


def _src(eqn) -> tuple:
    from jax._src import source_info_util
    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is not None:
        return fr.file_name, fr.start_line
    return "<unknown>", 0


class _Walker:
    """Key-identity dataflow over a ClosedJaxpr (recursing into jit,
    scan, while, cond and custom-call sub-jaxprs)."""

    def __init__(self):
        self.events: dict = {}          # _Key -> list[_Event]
        self.split_fanouts: list = []   # leading dim of each split output

    # -- bookkeeping -------------------------------------------------------

    def _record(self, env, var, kind, eqn, ctx, depth):
        kid = env.get(var)
        if kid is None:
            return
        path, line = _src(eqn)
        self.events.setdefault(kid, []).append(
            _Event(kind, path, line, ctx, depth > kid.depth))

    @staticmethod
    def _get(env, v):
        return None if isinstance(v, _Literal) else env.get(v)

    def _fresh_outs(self, env, eqn, depth, label):
        for ov in eqn.outvars:
            if _is_key_aval(ov.aval):
                env[ov] = _Key(label, depth)

    # -- recursion helpers -------------------------------------------------

    def _enter(self, sub_jaxpr, operands, consts, env, ctx, depth,
               outer_env_ids=True):
        """Build a child env binding sub-jaxpr invars/constvars to the
        operand identities (fresh for key-typed binders with no tracked
        operand)."""
        sub_env = {}
        for cv, cval in zip(sub_jaxpr.constvars, consts):
            if _is_key_aval(cv.aval):
                sub_env[cv] = _Key("const", depth)
        for bv, op in zip(sub_jaxpr.invars, operands):
            kid = self._get(env, op) if outer_env_ids else None
            if kid is not None:
                sub_env[bv] = kid
            elif _is_key_aval(bv.aval):
                sub_env[bv] = _Key("binder", depth)
        return sub_env

    def _propagate_outs(self, sub_jaxpr, sub_env, eqn, env):
        for ov, sv in zip(eqn.outvars, sub_jaxpr.outvars):
            kid = self._get(sub_env, sv)
            if kid is not None:
                env[ov] = kid

    # -- the walk ----------------------------------------------------------

    def walk(self, closed, env=None, ctx=(), depth=0):
        jaxpr = getattr(closed, "jaxpr", closed)
        consts = getattr(closed, "consts", ())
        if env is None:
            env = {}
            for cv in jaxpr.constvars:
                if _is_key_aval(cv.aval):
                    env[cv] = _Key("const", depth)
            for iv in jaxpr.invars:
                if _is_key_aval(iv.aval):
                    env[iv] = _Key("input", depth)
        for eqn in jaxpr.eqns:
            self._eqn(jaxpr, eqn, env, ctx, depth)
        return env

    def _eqn(self, jaxpr, eqn, env, ctx, depth):
        name = eqn.primitive.name

        if name == "random_seed":
            self._fresh_outs(env, eqn, depth, "seed")
        elif name in _PASSTHROUGH:
            kid = self._get(env, eqn.invars[0]) if eqn.invars else None
            if kid is not None:
                for ov in eqn.outvars:
                    env[ov] = kid
        elif name in _SLICING:
            kid = self._get(env, eqn.invars[0])
            if kid is not None:
                # static slice params make a reproducible sub-stream id;
                # traced indices (dynamic_slice/gather operands) make each
                # eqn its own stream (can't distinguish runtime indices)
                label = f"{name}:{id(eqn)}"
                for ov in eqn.outvars:
                    env[ov] = _Key(label, kid.depth)
        elif name == "random_split":
            self._record(env, eqn.invars[0], SPLIT, eqn, ctx, depth)
            out_shape = getattr(eqn.outvars[0].aval, "shape", ())
            if out_shape:
                self.split_fanouts.append(out_shape[0])
            self._fresh_outs(env, eqn, depth, "split-child")
        elif name == "random_fold_in":
            self._record(env, eqn.invars[0], FOLD, eqn, ctx, depth)
            self._fresh_outs(env, eqn, depth, "fold-child")
        elif name == "random_bits":
            self._record(env, eqn.invars[0], SAMPLE, eqn, ctx, depth)
        elif name == "jit":
            sub = eqn.params["jaxpr"]
            sub_env = self._enter(sub.jaxpr, eqn.invars, sub.consts, env,
                                  ctx, depth)
            self.walk(sub, sub_env, ctx, depth)
            self._propagate_outs(sub.jaxpr, sub_env, eqn, env)
        elif name in ("closed_call", "core_call", "custom_jvp_call",
                      "custom_vjp_call", "remat2", "checkpoint"):
            sub = eqn.params.get("call_jaxpr") or eqn.params.get("jaxpr")
            if sub is not None:
                inner = getattr(sub, "jaxpr", sub)
                consts = getattr(sub, "consts", ())
                if len(inner.invars) == len(eqn.invars):
                    sub_env = self._enter(inner, eqn.invars, consts, env,
                                          ctx, depth)
                    self.walk(sub, sub_env, ctx, depth)
                    self._propagate_outs(inner, sub_env, eqn, env)
        elif name == "cond":
            branches = eqn.params["branches"]
            operands = eqn.invars[1:]
            for idx, br in enumerate(branches):
                inner = getattr(br, "jaxpr", br)
                sub_env = self._enter(inner, operands,
                                      getattr(br, "consts", ()), env, ctx,
                                      depth)
                self.walk(br, sub_env, ctx + ((id(eqn), idx),), depth)
            self._fresh_outs(env, eqn, depth, "cond-out")
        elif name == "scan":
            sub = eqn.params["jaxpr"]
            inner = getattr(sub, "jaxpr", sub)
            n_consts = eqn.params["num_consts"]
            n_carry = eqn.params.get("num_carry",
                                     eqn.params.get("num_carries", 0))
            sub_env = {}
            for cv in inner.constvars:
                if _is_key_aval(cv.aval):
                    sub_env[cv] = _Key("const", depth)
            for i, bv in enumerate(inner.invars):
                op = eqn.invars[i] if i < len(eqn.invars) else None
                kid = self._get(env, op) if op is not None else None
                if i < n_consts + n_carry:
                    # consts/carries keep the outer identity: sampling one
                    # inside the body is a loop-invariant draw
                    if kid is not None:
                        sub_env[bv] = kid
                    elif _is_key_aval(bv.aval):
                        sub_env[bv] = _Key("binder", depth)
                else:
                    # xs rows: each iteration sees a distinct element
                    if kid is not None or _is_key_aval(bv.aval):
                        sub_env[bv] = _Key("scan-xs", depth + 1)
            self.walk(sub, sub_env, ctx, depth + 1)
            self._fresh_outs(env, eqn, depth, "scan-out")
        elif name == "while":
            for pkey, nconsts, c0 in (
                    ("cond_jaxpr", eqn.params["cond_nconsts"], 0),
                    ("body_jaxpr", eqn.params["body_nconsts"],
                     eqn.params["cond_nconsts"])):
                sub = eqn.params[pkey]
                inner = getattr(sub, "jaxpr", sub)
                n_carry_start = (eqn.params["cond_nconsts"]
                                 + eqn.params["body_nconsts"])
                operands = (eqn.invars[c0:c0 + nconsts]
                            + eqn.invars[n_carry_start:])
                sub_env = self._enter(inner, operands,
                                      getattr(sub, "consts", ()), env, ctx,
                                      depth)
                self.walk(sub, sub_env, ctx, depth + 1)
            self._fresh_outs(env, eqn, depth, "while-out")
        else:
            # unknown prim: opaque — key-typed outputs become fresh streams
            self._fresh_outs(env, eqn, depth, name)


# ---------------------------------------------------------------------------
# Contract evaluation
# ---------------------------------------------------------------------------


def check_jaxpr(closed, program: str,
                expect_fanout: Optional[int] = None) -> list:
    """Walk one ClosedJaxpr and return the Finding list."""
    w = _Walker()
    w.walk(closed)
    findings = []

    def _report(rule, ev, msg):
        findings.append(Finding("keycheck", rule, ev.path, ev.line,
                                f"[{program}] {msg}"))

    for evs in w.events.values():
        samples = [e for e in evs if e.kind == SAMPLE]
        splits = [e for e in evs if e.kind == SPLIT]
        derives = splits + [e for e in evs if e.kind == FOLD]
        pair = _any_conflicting_pair(samples, samples)
        if pair:
            _report("key-reuse", pair[1],
                    f"PRNG key sampled by ≥2 random primitives without an "
                    f"intervening split/fold_in (also sampled at "
                    f"{pair[0].path}:{pair[0].line})")
        pair = _any_conflicting_pair(samples, derives)
        if pair:
            _report("sample-then-derive", pair[0],
                    f"PRNG key is both sampled and split/folded "
                    f"(derived at {pair[1].path}:{pair[1].line}); derive "
                    f"a sub-key for the sample instead")
        pair = _any_conflicting_pair(splits, splits)
        if pair:
            _report("double-split", pair[1],
                    f"PRNG key split twice — the two child batches are "
                    f"identical streams (also split at "
                    f"{pair[0].path}:{pair[0].line})")
        for e in samples:
            if e.loop_invariant:
                _report("scan-invariant-sample", e,
                        "key originating outside a scan/while body is "
                        "sampled inside it — the same value is drawn "
                        "every iteration; fold_in the loop index first")
    if expect_fanout is not None and expect_fanout not in w.split_fanouts:
        findings.append(Finding(
            "keycheck", "per-agent-fanout", program, 0,
            f"[{program}] no {expect_fanout}-wide random_split found: the "
            f"K per-agent sampling streams must derive from one split of "
            f"the step key"))
    return findings


# ---------------------------------------------------------------------------
# Program inventory — the real builders, traced small
# ---------------------------------------------------------------------------

_K = 4          # agents in the RL programs
_FED_K = 3      # agents in the federated programs


def _rl_setup(algo: str):
    from repro.core import engine
    from repro.rl.envs import make_env
    env = make_env("cartpole(horizon=16)")
    if algo == "decbyzpg":
        from repro.core.decbyzpg import (DecByzPGConfig,
                                         build_decbyzpg_loop,
                                         init_decbyzpg_carry)
        cfg = DecByzPGConfig(K=_K, n_byz=1, attack="large_noise(sigma=1.0)",
                             aggregator="rfa", agreement="gda", kappa=2,
                             N=3, B=2, hidden=(8,))
        build, init = build_decbyzpg_loop, init_decbyzpg_carry
    else:
        from repro.core.byzpg import (ByzPGConfig, build_byzpg_loop,
                                      init_byzpg_carry)
        cfg = ByzPGConfig(K=_K, n_byz=1, attack="sign_flip",
                          aggregator="rfa", N=3, B=2, hidden=(8,))
        build, init = build_byzpg_loop, init_byzpg_carry
    return engine, env, cfg, build, init


def _trace_algo_loop(algo: str):
    T = 2
    engine, env, cfg, build, init = _rl_setup(algo)
    ks = engine.seed_keys(0)
    carry = init(env, cfg, ks.init)
    loop = build(env, cfg, T)
    return jax.make_jaxpr(loop)(*carry, jax.random.split(ks.loop, T),
                                ks.coin)


def _trace_lane_batch():
    engine, env, cfg, _, _ = _rl_setup("decbyzpg")
    fn = engine.lane_batch_loop(env, cfg, 2, ("eta",), 2, algo="decbyzpg")
    vals = jnp.array([[1e-2], [2e-2]], jnp.float32)
    seeds = jnp.arange(2, dtype=jnp.int32)
    return jax.make_jaxpr(fn)(vals, seeds)


def _fed_setup():
    from repro.configs import get_config, reduced
    from repro.distributed.fed_trainer import FedConfig
    cfg = reduced(get_config("llama3_2_1b"))
    fed = FedConfig(aggregator="rfa", kappa=2, n_byz=1,
                    attack="large_noise(sigma=1.0)")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    mask = jax.ShapeDtypeStruct((_FED_K,), jnp.bool_)
    batch = {
        "tokens": jax.ShapeDtypeStruct((_FED_K, 2, 16), jnp.int32),
        "labels": jax.ShapeDtypeStruct((_FED_K, 2, 16), jnp.int32),
    }
    return cfg, fed, key, mask, batch


def _trace_fed_step():
    from repro.distributed.fed_trainer import fed_train_step, init_fed_state
    cfg, fed, key, mask, batch = _fed_setup()
    state = jax.eval_shape(
        lambda k: init_fed_state(cfg, fed, _FED_K, k), key)
    coin = jax.ShapeDtypeStruct((), jnp.bool_)
    return jax.make_jaxpr(
        lambda s, b, m, k, c: fed_train_step(cfg, fed, s, b, m, k, large=c)
    )(state, batch, mask, key, coin)


def _trace_fed_step_flat():
    from repro.distributed.fed_trainer import (fed_train_step_flat,
                                               init_flat_fed_state)
    from repro.core import engine
    cfg, fed, key, mask, batch = _fed_setup()
    state, unravel = init_flat_fed_state(cfg, fed, _FED_K,
                                         engine.seed_keys(0).init)
    coin = jax.ShapeDtypeStruct((), jnp.bool_)
    return jax.make_jaxpr(
        lambda s, b, m, k, c: fed_train_step_flat(cfg, fed, s, unravel, b,
                                                  m, k, large=c)
    )(state, batch, mask, key, coin)


def _trace_fed_window():
    from repro.distributed.fed_trainer import fed_train_window, init_fed_state
    cfg, fed, key, mask, batch = _fed_setup()
    W = 2
    state = jax.eval_shape(
        lambda k: init_fed_state(cfg, fed, _FED_K, k), key)
    batches = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((W,) + s.shape, s.dtype), batch)
    ts = jax.ShapeDtypeStruct((W,), jnp.int32)
    return jax.make_jaxpr(
        lambda s, bs, m, t, k: fed_train_window(cfg, fed, s, bs, m, t, k)
    )(state, batches, mask, ts, key)


def programs() -> list:
    """(name, thunk -> ClosedJaxpr, expected per-agent fanout | None)."""
    return [
        ("decbyzpg_loop", lambda: _trace_algo_loop("decbyzpg"), _K),
        ("byzpg_loop", lambda: _trace_algo_loop("byzpg"), _K),
        ("lane_batch_loop", _trace_lane_batch, None),
        ("fed_train_step", _trace_fed_step, None),
        ("fed_train_step_flat", _trace_fed_step_flat, None),
        ("fed_train_window", _trace_fed_window, None),
    ]


def run(selected: Optional[Iterable[str]] = None) -> list:
    """Trace every inventory program and return all findings (deduped on
    (rule, path, line) so one bad helper reported through several
    programs surfaces once)."""
    findings, seen = [], set()
    for name, thunk, fanout in programs():
        if selected is not None and name not in selected:
            continue
        for f in check_jaxpr(thunk(), name, expect_fanout=fanout):
            dk = (f.rule, f.path, f.line)
            if dk not in seen:
                seen.add(dk)
                findings.append(f)
    return findings
