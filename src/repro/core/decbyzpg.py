"""DecByzPG — decentralized Byzantine fault-tolerant federated PG
(Algorithm 2), full-fidelity K-agent simulator.

Per iteration t, every agent k:
  1. draws the common coin c_t (Common-Sample; PRNG from the shared init);
  2. samples M ∈ {N, B} local trajectories at its own θ_t^(k);
  3. forms ṽ_t^(k): a plain estimate (c=1) or the PAGE correction using its
     *realized* previous step (θ_t^(k) − θ_{t-1}^(k))/η and an
     importance-weighted estimate at θ_{t-1}^(k) (c=0);
  4. robustly aggregates everyone's (possibly Byzantine) messages;
  5. takes the step θ̃_{t+1}^(k) = θ_t^(k) + η v_t^(k);
  6. runs Avg-Agree_κ (MDA/GDA) to contract the parameter diameter.

``aggregator="mean", kappa=0`` recovers the naive Dec-PAGE-PG baseline;
``K=1`` recovers PAGE-PG — exactly the baselines of the paper's Figures 2-3.

The T-iteration loop is one fused ``jax.lax.scan`` program (DESIGN.md §2):
the coin is drawn inside the scan from a folded PRNG stream, every step
samples a fixed max(N, B)-shaped trajectory batch masked down to B by
sample weights on small steps (one compiled step, no dual-jit), the
(θ, θ_prev, opt) carry is donated, and histories come back stacked
on-device.  ``run_decbyzpg_legacy`` keeps the per-step dispatch harness
(fresh jit per call, host sync per iteration) for equivalence tests and
the ``bench_engine`` comparison.
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
from repro.core.agreement import avg_agree, honest_diameter
from repro.core.registry import normalize_spec_fields, register, resolve
from repro.core.tree import ravel
from repro.optim.optimizers import get_optimizer
from repro.rl.gradient import grad_estimate, weighted_grad_estimate
from repro.rl.policy import policy_unraveler, resolve_policy
from repro.rl.rollout import batch_return, sample_batch
from repro.topology import resolve_topology

_SPEC_FIELDS = ("attack", "aggregator", "agreement", "estimator",
                "optimizer", "topology", "policy")


@dataclasses.dataclass(frozen=True)
class DecByzPGConfig:
    K: int = 13
    n_byz: int = 0
    attack: object = "none"         # str | Spec, normalized to Spec
    aggregator: object = "rfa"
    agreement: object = "mda"   # mda (alpha_max=1/4, exact, K<=16) | gda
    kappa: int = 6              # Θ(log NK) agreement rounds
    per_receiver: bool = False  # Byzantines send per-receiver values
    topology: object = "complete"   # gossip graph spec (DESIGN.md §5):
    # complete | ring(k=) | torus | erdos_renyi(p=, seed=) |
    # small_world(k=, beta=, seed=) | star — static, part of static_key
    N: int = 50
    B: int = 4
    p: Optional[float] = None
    eta: float = 5e-3
    gamma: float = 0.999
    estimator: object = "gpomdp"
    policy: object = "mlp"      # policy spec: mlp(hidden=, activation=) |
    # transformer(arch=, n_layers=, ...) — resolves against env plus the
    # activation/hidden fields below (which stay the mlp defaults)
    activation: str = "relu"
    hidden: tuple = (16, 16)
    baseline: float = 0.0
    optimizer: object = "adam"  # paper App. D applies Adam to the PAGE
    seed: int = 0               # direction; "sgd" = Algorithm 2 line 8
    telemetry: bool = False     # static (in static_key): in-loop obs taps
    # + per-round rejected-agent masks; off = no callback in the program

    def __post_init__(self):
        normalize_spec_fields(self, _SPEC_FIELDS)

    @property
    def switch_p(self) -> float:
        return self.p if self.p is not None else self.B / self.N


def _optimizer(cfg: DecByzPGConfig):
    return get_optimizer(cfg.optimizer, cfg.eta)


def init_decbyzpg_carry(env, cfg: DecByzPGConfig, k_init):
    """(θ_0 (K,d) common init, θ_prev, per-agent optimizer state) —
    traceable, so a grid lane can build its own carry under vmap."""
    with obs.named_phase("decbyzpg.init"):
        vec0 = ravel(resolve_policy(cfg, env).init(k_init))[0]
        theta0 = jnp.tile(vec0, (cfg.K, 1))
        opt0 = jax.vmap(_optimizer(cfg).init)(theta0)
        return theta0, jnp.array(theta0), opt0


def build_decbyzpg_step(env, cfg: DecByzPGConfig, traced=None):
    """One fixed-shape iteration ``step(carry, (t, key), coin_key)``.

    Both coin branches run through the same compiled body: every agent
    samples max(N, B) trajectories and the estimator weights select the
    first N (large) or first B (small PAGE) of them, so there is exactly
    one program regardless of the coin.

    ``traced`` (lane batching, DESIGN.md §2) maps traced scalar names —
    the ``traced_fields`` registered for this algorithm plus batchable
    attack kwargs as ``"attack.<kwarg>"`` — to array operands that
    override the config's baked-in Python floats, so one compiled program
    serves every lane of a scalar sweep. ``None`` keeps the historical
    constant-folding behavior.
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
    agr_attack = (attacks_lib.per_receiver(attack, cfg.K)
                  if cfg.per_receiver else attack)
    # traced aggregator kwargs (e.g. rfa's nu) arrive as array operands so
    # an aggregator-scalar sweep shares this compiled program
    agg = resolve("aggregator", cfg.aggregator, K=cfg.K, n_byz=cfg.n_byz,
                  **engine.traced_spec_kwargs(traced, "aggregator"))
    scales = jnp.where(byz_mask & env_level, 0.0, 1.0)
    opt = get_optimizer(cfg.optimizer, eta)
    topo = resolve_topology(cfg.topology, cfg.K)

    M = max(cfg.N, cfg.B)
    idx = jnp.arange(M)
    w_large = jnp.where(idx < cfg.N, 1.0 / cfg.N, 0.0)
    w_small = jnp.where(idx < cfg.B, 1.0 / cfg.B, 0.0)

    def agent_estimate(theta_vec, theta_prev_vec, key, w, scale):
        params = unravel(theta_vec)
        prev = unravel(theta_prev_vec)
        with obs.named_phase("decbyzpg.rollout"):
            traj = sample_batch(env, params, key, M, logits_spec,
                                logit_scale=scale)
            ret = jnp.sum(w * batch_return(traj))
        with obs.named_phase("decbyzpg.gradient"):
            g = ravel(grad_estimate(params, traj, gamma, baseline,
                                    cfg.estimator, logits_spec,
                                    sample_weights=w))[0]
            # IS-corrected estimate at θ_prev on the small-batch slice;
            # masked out on large steps by the coin select below.
            g_old = ravel(weighted_grad_estimate(
                prev, params, traj, gamma, baseline,
                cfg.estimator, logits_spec, sample_weights=w_small))[0]
        return g, g_old, ret

    def step(carry, xs, coin_key):
        theta, theta_prev, opt_state = carry  # theta: (K, d)
        t, key = xs
        coin = engine.page_coin(coin_key, t, switch_p)
        w = jnp.where(coin, w_large, w_small)
        k_traj, k_att, k_agg, k_agr = jax.random.split(key, 4)
        # every region is a named phase (DESIGN.md §8); the estimate's
        # own ops are the PAGE correction
        with obs.named_phase("decbyzpg.estimate"):
            g, g_old, rets = jax.vmap(
                lambda tv, tp, k, s: agent_estimate(tv, tp, k, w, s)
            )(theta, theta_prev, jax.random.split(k_traj, cfg.K), scales)
            page = (theta - theta_prev) / eta - g_old
            tilde_v = jnp.where(coin, g, g + page)
        with obs.named_phase("decbyzpg.aggregate"):
            msgs = attack(tilde_v, byz_mask, k_att)
            # every agent aggregates the same broadcast set (v^(k));
            # per-receiver inconsistency is exercised inside Avg-Agree.
            v = jax.vmap(lambda k: agg(msgs, k))(
                jax.random.split(k_agg, cfg.K))
        with obs.named_phase("decbyzpg.update"):
            theta_tilde, opt_state = jax.vmap(opt.update)(v, opt_state,
                                                          theta)
        with obs.named_phase("decbyzpg.agree"):
            if cfg.kappa > 0:
                theta_new = avg_agree(theta_tilde, cfg.kappa, cfg.n_byz,
                                      byz_mask, cfg.agreement, agr_attack,
                                      k_agr, topology=topo)
            else:
                theta_new = theta_tilde
        with obs.named_phase("decbyzpg.diagnostics"):
            honest_ret = jnp.sum(jnp.where(byz_mask, 0.0, rets)) \
                / jnp.maximum(jnp.sum(~byz_mask), 1)
            diam = honest_diameter(theta_new, ~byz_mask)
            if not cfg.telemetry:
                return (theta_new, theta, opt_state), \
                    (honest_ret, coin, diam)
            # telemetry plane: observers only — no extra PRNG consumption,
            # so the returns/diameter histories are identical to the off
            # path
            norms = jnp.linalg.norm(tilde_v, axis=1)
            grad_norm = jnp.sum(jnp.where(byz_mask, 0.0, norms)) \
                / jnp.maximum(jnp.sum(~byz_mask), 1)
            rejected = rejection_mask(cfg.aggregator, msgs, cfg.n_byz)
            obs.tap("decbyzpg", t=t, coin=coin, honest_return=honest_ret,
                    diameter=diam, grad_norm=grad_norm, rejected=rejected)
            return (theta_new, theta, opt_state), \
                (honest_ret, coin, diam, grad_norm, rejected)

    return step


def build_decbyzpg_window(env, cfg: DecByzPGConfig, traced=None):
    """Window program (DESIGN.md §12): scan the step over an arbitrary
    contiguous slice of the iteration stream, taking and returning the
    explicit ``(θ, θ_prev, opt_state)`` carry.

    ``window(carry, ts (W,), step_keys (W, 2), coin_key) -> (carry, ys)``
    where ``ts`` are *absolute* iteration indices and ``step_keys`` the
    matching slice of the full ``split(loop_key, T)`` stream — chaining
    windows over ``[0, T)`` is the uninterrupted scan, bit for bit, and
    the compiled shape depends only on W (never on the window offset)."""
    step = build_decbyzpg_step(env, cfg, traced)

    def window(carry, ts, step_keys, coin_key):
        carry, ys = jax.lax.scan(
            lambda c, xs: step(c, xs, coin_key), carry, (ts, step_keys))
        hist = {"returns": ys[0], "coins": ys[1], "diameter": ys[2]}
        if cfg.telemetry:
            hist["grad_norm"], hist["rejected"] = ys[3], ys[4]
        return carry, hist

    return window


def build_decbyzpg_loop(env, cfg: DecByzPGConfig, T: int, traced=None):
    """Pure fused loop: one ``lax.scan`` over T iterations returning
    stacked on-device histories (no per-step host traffic) — the
    single-window [0, T) instance of :func:`build_decbyzpg_window`."""
    window = build_decbyzpg_window(env, cfg, traced)

    def loop(theta0, theta_prev0, opt0, step_keys, coin_key):
        (theta, _, _), hist = window((theta0, theta_prev0, opt0),
                                     jnp.arange(T), step_keys, coin_key)
        return {"theta": theta, **hist}

    return loop


def fused_decbyzpg(env, cfg: DecByzPGConfig, T: int):
    """Jitted fused loop, cached per static config shape; the θ_0 carry
    buffer is donated (it aliases the final θ output — θ_prev/opt have no
    matching output to alias, so donating them would only be dead weight;
    the ``repro.analysis`` donation audit enforces this)."""
    key = ("decbyzpg", env.name, env.horizon, engine.static_key(cfg), T)
    return engine.compiled(key, lambda: jax.jit(
        build_decbyzpg_loop(env, cfg, T),
        donate_argnums=engine.donate_args(0)))


def _finalize(cfg, unravel, hist) -> dict:
    coins = np.asarray(hist["coins"])
    theta = hist["theta"]
    honest_idx = min(cfg.n_byz, cfg.K - 1)
    out = {"returns": np.asarray(hist["returns"]),
           "samples": np.cumsum(np.where(coins, cfg.N, cfg.B)),
           "diameter": np.asarray(hist["diameter"]),
           "params": unravel(theta[honest_idx]),
           "theta": theta}
    if "rejected" in hist:
        out["grad_norm"] = np.asarray(hist["grad_norm"])
        out["rejected"] = np.asarray(hist["rejected"])
        out["aggregator_confusion"] = obs.confusion_tally(
            out["rejected"], cfg.n_byz)
    return out


def run_decbyzpg(env, cfg: DecByzPGConfig, T: int):
    """Returns history of honest mean returns, per-agent sample counts, and
    the honest parameter diameter trace (Lemma 1/2 diagnostic)."""
    ks = engine.seed_keys(cfg.seed)
    unravel, _ = policy_unraveler(resolve_policy(cfg, env))
    carry = init_decbyzpg_carry(env, cfg, ks.init)
    loop = fused_decbyzpg(env, cfg, T)
    hist = jax.block_until_ready(
        loop(*carry, jax.random.split(ks.loop, T), ks.coin))
    return _finalize(cfg, unravel, hist)


def run_decbyzpg_legacy(env, cfg: DecByzPGConfig, T: int):
    """Per-step dispatch harness over the *same* step function: a Python
    T-loop, a fresh jit per call, and a host sync per iteration — the
    pre-engine execution model, kept for the scan-vs-dispatch equivalence
    test and the ``bench_engine`` baseline."""
    ks = engine.seed_keys(cfg.seed)
    unravel, _ = policy_unraveler(resolve_policy(cfg, env))
    theta, theta_prev, opt = init_decbyzpg_carry(env, cfg, ks.init)
    step = jax.jit(build_decbyzpg_step(env, cfg), static_argnums=())
    step_keys = jax.random.split(ks.loop, T)
    rets, coins, diams = [], [], []
    for t in range(T):
        # ys grows telemetry entries under cfg.telemetry; the first three
        # are always (return, coin, diameter)
        (theta, theta_prev, opt), ys = step(
            (theta, theta_prev, opt), (jnp.int32(t), step_keys[t]), ks.coin)
        rets.append(float(ys[0]))
        coins.append(bool(ys[1]))
        diams.append(float(ys[2]))
    hist = {"theta": theta, "returns": np.asarray(rets),
            "coins": np.asarray(coins), "diameter": np.asarray(diams)}
    return _finalize(cfg, unravel, hist)


register("algo", "decbyzpg")(lambda: engine.AlgoDef(
    DecByzPGConfig, build_decbyzpg_loop, init_decbyzpg_carry,
    run_decbyzpg, run_decbyzpg_legacy,
    traced_fields=("eta", "gamma", "baseline", "switch_p"),
    build_window=build_decbyzpg_window, carry_hist="theta"))
