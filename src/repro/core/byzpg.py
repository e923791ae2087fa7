"""ByzPG — centralized Byzantine fault-tolerant federated PG (Algorithm 1).

Faithful simulator of the paper's Algorithm 1 over K agents:

* coin ``c_t ~ Be(p)``; on c=1 (or t=0) all workers sample N trajectories at
  θ_t and send PG estimates, robustly aggregated at the server;
* on c=0 the **server alone** samples B trajectories and applies the PAGE
  correction ``v_t = ĝ_B(θ_t) + v_{t-1} − ĝ_B^{ω_{θ_t}}(θ_{t-1})`` with
  importance sampling (the paper's key deviation from Byz-VR-MARINA);
* Byzantine agents' messages are replaced by the configured attack
  (RandomAction corrupts their environment interaction instead).

The paper's experiments apply Adam to the PAGE direction (App. D) — we
support both plain ascent (`optimizer="sgd"`, faithful to Algorithm 1 line
12) and Adam.

Like DecByzPG, the T-loop is one fused ``lax.scan`` (DESIGN.md §2): the
coin comes from a folded PRNG stream inside the scan and every step keeps
the fixed (K, max(N, B)) trajectory shape, with estimator weights masking
small steps down to B.  The server's small-batch stream is the last agent
slot (slot K-1 is honest for any tolerated n_byz < K; in the centralized
protocol all workers hold the same θ_t, so slot K-1's trajectories are
exactly a fresh server sample).  ``run_byzpg_legacy`` keeps the per-step
dispatch harness.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import attacks as attacks_lib
from repro.core import engine
from repro.core.aggregators import rejection_mask
from repro.core.registry import normalize_spec_fields, register, resolve
from repro.core.tree import ravel
from repro.optim.optimizers import get_optimizer
from repro.rl.gradient import grad_estimate, weighted_grad_estimate
from repro.rl.policy import policy_unraveler, resolve_policy
from repro.rl.rollout import batch_return, sample_batch

_SPEC_FIELDS = ("attack", "aggregator", "estimator", "optimizer", "policy")


@dataclasses.dataclass(frozen=True)
class ByzPGConfig:
    K: int = 13
    n_byz: int = 0
    attack: object = "none"         # str | Spec, normalized to Spec
    aggregator: object = "rfa"
    N: int = 50                 # large batch
    B: int = 4                  # small batch
    p: Optional[float] = None   # switch prob; default B/N
    eta: float = 5e-3
    gamma: float = 0.999
    estimator: object = "gpomdp"
    policy: object = "mlp"      # policy spec (see repro.rl.policy)
    activation: str = "relu"
    hidden: tuple = (16, 16)
    optimizer: object = "adam"
    baseline: float = 0.0
    seed: int = 0
    telemetry: bool = False     # static (in static_key): in-loop obs taps
    # + per-round rejected-agent masks; off = no callback in the program

    def __post_init__(self):
        normalize_spec_fields(self, _SPEC_FIELDS)

    @property
    def switch_p(self) -> float:
        return self.p if self.p is not None else self.B / self.N


def _optimizer(cfg):
    return get_optimizer(cfg.optimizer, cfg.eta)


def init_byzpg_carry(env, cfg: ByzPGConfig, k_init):
    """(θ (d,), θ_prev, v_prev, opt_state) — traceable for grid lanes."""
    with obs.named_phase("byzpg.init"):
        vec0 = ravel(resolve_policy(cfg, env).init(k_init))[0]
        opt_state = _optimizer(cfg).init(vec0)
        return vec0, jnp.array(vec0), jnp.zeros_like(vec0), opt_state


def build_byzpg_step(env, cfg: ByzPGConfig, traced=None):
    """One fixed-shape iteration ``step(carry, (t, key), coin_key)``.

    ``traced`` maps lane-traced scalar names (see
    :func:`repro.core.decbyzpg.build_decbyzpg_step`) to array operands
    overriding the config's baked-in floats.
    """
    eta = engine.traced_value(traced, "eta", cfg.eta)
    gamma = engine.traced_value(traced, "gamma", cfg.gamma)
    baseline = engine.traced_value(traced, "baseline", cfg.baseline)
    switch_p = engine.traced_value(traced, "switch_p", cfg.switch_p)
    policy = resolve_policy(cfg, env)
    unravel, _ = policy_unraveler(policy)
    logits_spec = policy.logits
    byz_mask = jnp.asarray(np.arange(cfg.K) < cfg.n_byz)
    env_level = attacks_lib.is_env_level(cfg.attack)
    attack = resolve("attack", cfg.attack,
                     **engine.traced_spec_kwargs(traced, "attack"))
    agg = resolve("aggregator", cfg.aggregator, K=cfg.K, n_byz=cfg.n_byz,
                  **engine.traced_spec_kwargs(traced, "aggregator"))
    opt = get_optimizer(cfg.optimizer, eta)
    scales = jnp.where(byz_mask & env_level, 0.0, 1.0)

    M = max(cfg.N, cfg.B)
    idx = jnp.arange(M)
    w_large = jnp.where(idx < cfg.N, 1.0 / cfg.N, 0.0)
    w_small = jnp.where(idx < cfg.B, 1.0 / cfg.B, 0.0)
    server = cfg.K - 1          # honest slot backing the server's stream

    def step(carry, xs, coin_key):
        vec, prev_vec, v_prev, opt_state = carry
        t, key = xs
        coin = engine.page_coin(coin_key, t, switch_p)
        w = jnp.where(coin, w_large, w_small)
        k_traj, k_att, k_agg = jax.random.split(key, 3)
        params = unravel(vec)
        prev = unravel(prev_vec)

        def one(k, scale):
            with obs.named_phase("byzpg.rollout"):
                traj = sample_batch(env, params, k, M, logits_spec,
                                    logit_scale=scale)
                ret = jnp.sum(w * batch_return(traj))
            with obs.named_phase("byzpg.gradient"):
                g = ravel(grad_estimate(params, traj, gamma, baseline,
                                        cfg.estimator, logits_spec,
                                        sample_weights=w))[0]
                g_old = ravel(weighted_grad_estimate(
                    prev, params, traj, gamma, baseline,
                    cfg.estimator, logits_spec,
                    sample_weights=w_small))[0]
            return g, g_old, ret

        with obs.named_phase("byzpg.estimate"):
            g, g_old, rets = jax.vmap(one)(jax.random.split(k_traj, cfg.K),
                                           scales)
        with obs.named_phase("byzpg.aggregate"):
            msgs = attack(g, byz_mask, k_att)
            v_large = agg(msgs, k_agg)
        with obs.named_phase("byzpg.update"):
            # small step: w == w_small, so g[server] is exactly ĝ_B(θ_t)
            # on the server's fresh batch and g_old[server] the IS
            # estimate at θ_prev.
            v_page = g[server] + v_prev - g_old[server]
            v = jnp.where(coin, v_large, v_page)
            new_vec, opt_state = opt.update(v, opt_state, vec)
        with obs.named_phase("byzpg.diagnostics"):
            honest_ret = jnp.sum(jnp.where(byz_mask, 0.0, rets)) \
                / jnp.maximum(jnp.sum(~byz_mask), 1)
            ret = jnp.where(coin, honest_ret, rets[server])
            if not cfg.telemetry:
                return (new_vec, vec, v, opt_state), (ret, coin)
            # observers only (no extra PRNG consumption): the aggregation
            # is live on large rounds; small rounds still score the
            # attacked worker messages the server would have received
            norms = jnp.linalg.norm(g, axis=1)
            grad_norm = jnp.sum(jnp.where(byz_mask, 0.0, norms)) \
                / jnp.maximum(jnp.sum(~byz_mask), 1)
            rejected = rejection_mask(cfg.aggregator, msgs, cfg.n_byz)
            obs.tap("byzpg", t=t, coin=coin, honest_return=ret,
                    grad_norm=grad_norm, rejected=rejected)
            return (new_vec, vec, v, opt_state), (ret, coin, grad_norm,
                                                  rejected)

    return step


def build_byzpg_window(env, cfg: ByzPGConfig, traced=None):
    """Window program (DESIGN.md §12): scan the step over an arbitrary
    slice of the iteration stream with the explicit
    ``(θ, θ_prev, v_prev, opt_state)`` carry; ``ts`` are absolute
    iteration indices, ``step_keys`` the matching slice of the full
    ``split(loop_key, T)`` stream. Chained windows reproduce the
    uninterrupted scan bit for bit."""
    step = build_byzpg_step(env, cfg, traced)

    def window(carry, ts, step_keys, coin_key):
        carry, ys = jax.lax.scan(
            lambda c, xs: step(c, xs, coin_key), carry, (ts, step_keys))
        hist = {"returns": ys[0], "coins": ys[1]}
        if cfg.telemetry:
            hist["grad_norm"], hist["rejected"] = ys[2], ys[3]
        return carry, hist

    return window


def build_byzpg_loop(env, cfg: ByzPGConfig, T: int, traced=None):
    """Pure fused loop: one ``lax.scan`` over T iterations — the
    single-window [0, T) instance of :func:`build_byzpg_window`."""
    window = build_byzpg_window(env, cfg, traced)

    def loop(vec0, prev_vec0, v0, opt_state0, step_keys, coin_key):
        (vec, _, _, _), hist = window((vec0, prev_vec0, v0, opt_state0),
                                      jnp.arange(T), step_keys, coin_key)
        return {"vec": vec, **hist}

    return loop


def fused_byzpg(env, cfg: ByzPGConfig, T: int):
    # only vec_0 aliases an output (the final vec); the other carries have
    # no same-shaped output, so donating them would be dead weight — the
    # repro.analysis donation audit enforces full aliasing
    key = ("byzpg", env.name, env.horizon, engine.static_key(cfg), T)
    return engine.compiled(key, lambda: jax.jit(
        build_byzpg_loop(env, cfg, T),
        donate_argnums=engine.donate_args(0)))


def _finalize(cfg, unravel, hist, eval_every: int) -> dict:
    coins = np.asarray(hist["coins"])
    samples = np.cumsum(np.where(coins, cfg.N, cfg.B))
    out = {"returns": np.asarray(hist["returns"])[::eval_every],
           "samples": samples[::eval_every],
           "params": unravel(hist["vec"])}
    if "rejected" in hist:
        out["grad_norm"] = np.asarray(hist["grad_norm"])
        out["rejected"] = np.asarray(hist["rejected"])
        out["aggregator_confusion"] = obs.confusion_tally(
            out["rejected"], cfg.n_byz)
    return out


def run_byzpg(env, cfg: ByzPGConfig, T: int, eval_every: int = 1):
    """Returns dict(history of honest mean returns, sampled trajectories per
    agent, final params)."""
    ks = engine.seed_keys(cfg.seed)
    unravel, _ = policy_unraveler(resolve_policy(cfg, env))
    carry = init_byzpg_carry(env, cfg, ks.init)
    loop = fused_byzpg(env, cfg, T)
    hist = jax.block_until_ready(
        loop(*carry, jax.random.split(ks.loop, T), ks.coin))
    return _finalize(cfg, unravel, hist, eval_every)


def run_byzpg_legacy(env, cfg: ByzPGConfig, T: int, eval_every: int = 1):
    """Per-step dispatch harness over the same step function (fresh jit per
    call, host sync per iteration) — kept for equivalence tests and the
    ``bench_engine`` baseline."""
    ks = engine.seed_keys(cfg.seed)
    unravel, _ = policy_unraveler(resolve_policy(cfg, env))
    carry = init_byzpg_carry(env, cfg, ks.init)
    step = jax.jit(build_byzpg_step(env, cfg))
    step_keys = jax.random.split(ks.loop, T)
    rets, coins = [], []
    for t in range(T):
        # ys grows telemetry entries under cfg.telemetry; the first two
        # are always (return, coin)
        carry, ys = step(carry, (jnp.int32(t), step_keys[t]), ks.coin)
        rets.append(float(ys[0]))
        coins.append(bool(ys[1]))
    hist = {"vec": carry[0], "returns": np.asarray(rets),
            "coins": np.asarray(coins)}
    return _finalize(cfg, unravel, hist, eval_every)


register("algo", "byzpg")(lambda: engine.AlgoDef(
    ByzPGConfig, build_byzpg_loop, init_byzpg_carry,
    run_byzpg, run_byzpg_legacy,
    traced_fields=("eta", "gamma", "baseline", "switch_p"),
    build_window=build_byzpg_window, carry_hist="vec"))
