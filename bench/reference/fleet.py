"""Plain reference of the federated policy-gradient fleet.

DecByzPG (arXiv:2401.03489, Algorithm 2) and its naive Dec-PAGE-PG
baseline, written from the paper in straightforward ``jax.numpy``,
independent of the system under test: a categorical MLP policy, the
GPOMDP estimator with the PAGE correction and its importance-weighted
term at the previous parameters, Adam on the ascent direction, and the
configuration's environment, attack, aggregator and agreement, each a
module of its own found by name (``env/<name>.py``, ``attack/<name>.py``,
``aggregator/<name>.py``, ``agreement/<name>.py``).

Random numbers follow the seed by the system's documented key layout
(``PRNGKey(seed)`` split into init, loop and coin keys; one loop key per
iteration out of ``split(loop, T)``, split four ways into trajectory,
attack, aggregation and agreement keys; the common coin folded per
iteration), so that a row of the reference samples the same trajectories
as the row of the system that started from the same seed.

``dtype`` selects the arithmetic: float32 with matrix products at
"highest" is the reference; bfloat16 at the default precision is the
control. ``fault`` plants one of the faults the comparison must catch.
"""
from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
IS_CLIP = 10.0
FAULTS = ("frozen", "half_batch", "altered")


def component(namespace: str, name: str):
    return importlib.import_module(f"bench.reference.{namespace}.{name}")


def layer_shapes(env, hidden) -> list:
    sizes = (env.OBS_DIM, *hidden, env.N_ACTIONS)
    return list(zip(sizes[:-1], sizes[1:]))


def leaf_slices(cfg) -> list:
    """(name, start, stop) of each parameter leaf in the flat vector:
    per layer its bias, then its weight matrix (row-major)."""
    out, at = [], 0
    env = component("env", cfg["env"])
    for i, (din, dout) in enumerate(layer_shapes(env, cfg["hidden"])):
        out.append((f"b{i}", at, at + dout))
        at += dout
        out.append((f"w{i}", at, at + din * dout))
        at += din * dout
    return out


def init_theta(key, shapes):
    parts = []
    for din, dout in shapes:
        k, key = jax.random.split(key)
        w = jax.random.normal(k, (din, dout), jnp.float32) * (din ** -0.5)
        parts += [jnp.zeros((dout,), jnp.float32), w.reshape(-1)]
    return jnp.concatenate(parts)


def policy_logits(theta, obs, shapes, activation):
    act = jnp.tanh if activation == "tanh" else jax.nn.relu
    x, at = obs, 0
    for i, (din, dout) in enumerate(shapes):
        b = theta[at:at + dout]
        w = theta[at + dout:at + dout + din * dout].reshape(din, dout)
        at += dout + din * dout
        x = x @ w + b
        if i < len(shapes) - 1:
            x = act(x)
    return x


class Fleet:
    """The reference fleet for one configuration and run length T."""

    def __init__(self, cfg: dict, T: int, dtype=jnp.float32, fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.cfg, self.T, self.dtype, self.fault = cfg, int(T), dtype, fault
        self.env = component("env", cfg["env"])
        self.shapes = layer_shapes(self.env, cfg["hidden"])
        self.K, self.n_byz = cfg["K"], cfg["n_byz"]
        self.N, self.B = cfg["N"], cfg["B"]
        self.byz = jnp.arange(self.K) < self.n_byz
        attack = component("attack", cfg["attack"])
        self.attack = lambda x, byz, key: attack.attack(
            x, byz, key, cfg["attack_args"])
        self.aggregator = component("aggregator", cfg["aggregator"])
        self.agreement = (component("agreement", cfg["agreement"])
                          if cfg["kappa"] > 0 else None)

    # -- one agent's estimate ----------------------------------------------

    def _logits(self, theta, obs):
        return policy_logits(theta, obs, self.shapes, self.cfg["activation"])

    def _trajectory(self, theta, key):
        """One episode: observations, actions, rewards and alive mask."""
        k_reset, k_steps = jax.random.split(key)
        s0 = self.env.reset(k_reset, self.dtype)

        def body(carry, k):
            s, alive = carry
            a = jax.random.categorical(k, self._logits(theta, s))
            s2, r, done = self.env.step(s, a)
            s_next = jnp.where(alive > 0, s2, s)
            return ((s_next, alive * (1 - done.astype(self.dtype))),
                    (s, a, r * alive, alive))

        _, traj = jax.lax.scan(
            body, (s0, jnp.ones((), self.dtype)),
            jax.random.split(k_steps, self.cfg["env_args"]["horizon"]))
        return traj

    def _log_probs(self, theta, obs, acts, alive):
        lp = jax.nn.log_softmax(self._logits(theta, obs))
        return jnp.take_along_axis(lp, acts[:, None], axis=1)[:, 0] * alive

    def _surrogate(self, theta, traj):
        """sum_h (sum_{t<=h} log pi(a_t|s_t)) gamma^h r_h: its gradient is
        the GPOMDP estimate."""
        obs, acts, rew, alive = traj
        lp = self._log_probs(theta, obs, acts, alive)
        gamma = jnp.asarray(self.cfg["gamma"], self.dtype)
        disc = rew * gamma ** jnp.arange(lp.shape[0]).astype(lp.dtype)
        return jnp.sum(jnp.cumsum(lp) * jax.lax.stop_gradient(disc))

    def _estimate(self, theta, prev, key, w_mix, w_small):
        M = max(self.N, self.B)
        traj = jax.vmap(lambda k: self._trajectory(theta, k))(
            jax.random.split(key, M))

        def sur(th):
            return jax.vmap(lambda t: self._surrogate(th, t))(traj)

        def logp(th):
            return jax.vmap(lambda t: jnp.sum(self._log_probs(
                th, t[0], t[1], t[3])))(traj)

        g = jax.grad(lambda th: jnp.sum(w_mix * sur(th)))(theta)
        log_ratio = jnp.clip(logp(prev) - logp(theta),
                             -math.log(IS_CLIP), math.log(IS_CLIP))
        iw = jax.lax.stop_gradient(jnp.exp(log_ratio))
        g_old = jax.grad(lambda th: jnp.sum(w_small * iw * sur(th)))(prev)
        ret = jnp.sum(w_mix * jnp.sum(traj[2], axis=1))
        return g, g_old, ret

    # -- one iteration of the fleet ----------------------------------------

    def _iteration(self, state, t, step_key, coin_key):
        c, dt = self.cfg, self.dtype
        theta, prev, (count, m, v) = state
        eta = jnp.asarray(c["eta"], dt)
        coin = (t == 0) | jax.random.bernoulli(
            jax.random.fold_in(coin_key, t), self.B / self.N)
        M = max(self.N, self.B)
        n_large, n_small = self.N, self.B
        if self.fault == "half_batch":
            n_large, n_small = self.N // 2, self.B // 2
        idx = jnp.arange(M)
        w_large = jnp.where(idx < n_large, 1.0 / n_large, 0.0).astype(dt)
        w_small = jnp.where(idx < n_small, 1.0 / n_small, 0.0).astype(dt)
        w_mix = jnp.where(coin, w_large, w_small)
        k_traj, k_att, k_agg, k_agr = jax.random.split(step_key, 4)
        g, g_old, rets = jax.vmap(
            lambda th, pr, k: self._estimate(th, pr, k, w_mix, w_small)
        )(theta, prev, jax.random.split(k_traj, self.K))
        direction = jnp.where(coin, g, g + (theta - prev) / eta - g_old)
        msgs = self.attack(direction, self.byz, k_att)
        agg = jax.vmap(lambda k: self.aggregator.aggregate(
            msgs, k, self.K, self.n_byz, c["aggregator_args"]))(
                jax.random.split(k_agg, self.K))
        count = count + 1
        m = ADAM_B1 * m + (1 - ADAM_B1) * agg
        v = ADAM_B2 * v + (1 - ADAM_B2) * agg * agg
        cf = count.astype(jnp.float32)
        bc1 = (1 - ADAM_B1 ** cf).astype(dt)
        bc2 = (1 - ADAM_B2 ** cf).astype(dt)
        new = theta + eta * (m / bc1) / (jnp.sqrt(v / bc2) + ADAM_EPS)
        if self.agreement is not None:
            new = self.agreement.agree(new, k_agr, c["kappa"], self.n_byz,
                                       self.byz, self.attack,
                                       c["agreement_args"], c["topology"])
        honest = ~self.byz
        ret = jnp.sum(jnp.where(honest, rets, 0)) / jnp.sum(honest)
        d2 = jnp.sum((new[:, None] - new[None]) ** 2, axis=-1)
        diam = jnp.sqrt(jnp.max(jnp.where(honest[:, None] & honest[None],
                                          d2, 0)))
        if self.fault == "frozen":
            return state, (ret, coin, diam)
        if self.fault == "altered":
            ret = ret + 1
        return (new, theta, (count, m, v)), (ret, coin, diam)

    # -- whole rows ---------------------------------------------------------

    def _row(self, seed, n_windows, W):
        dt = self.dtype
        k_init, k_loop, k_coin = jax.random.split(jax.random.PRNGKey(seed), 3)
        theta0 = jnp.tile(init_theta(k_init, self.shapes).astype(dt),
                          (self.K, 1))
        zeros = jnp.zeros_like(theta0)
        state = (theta0, theta0, (jnp.zeros((), jnp.int32), zeros, zeros))
        step_keys = jax.random.split(k_loop, self.T)

        def window(state, w):
            def it(s, t):
                return self._iteration(s, t, step_keys[t], k_coin)
            state, ys = jax.lax.scan(it, state, w * W + jnp.arange(W))
            return state, (ys, state[0], state[2][1])

        _, ((rets, coins, diam), thetas, ms) = jax.lax.scan(
            window, state, jnp.arange(n_windows))
        return {"theta0": theta0, "returns": rets.reshape(-1),
                "coins": coins.reshape(-1), "diameter": diam.reshape(-1),
                "theta": thetas, "adam_m": ms}

    def run(self, seeds, n_windows: int, W: int) -> dict:
        """Rows for ``seeds`` over the first ``n_windows`` windows of W
        iterations: per-iteration honest returns, coins and diameters,
        and the parameters and Adam's first moment at the end of each
        window."""
        precision = "highest" if self.dtype == jnp.float32 else "default"
        fn = jax.jit(jax.vmap(lambda s: self._row(s, n_windows, W)))
        with jax.default_matmul_precision(precision):
            out = fn(jnp.asarray(seeds, jnp.int32))
        return jax.tree.map(np.asarray, out)
