"""Policy-gradient estimators: REINFORCE and GPOMDP (paper App. A.1),
with the importance-weighted estimator ``g^{ω_θt}(τ | θ_{t-1})`` used by the
PAGE correction (Assumption 5 / SVRPG-style, weight not differentiated).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.registry import register, resolve
from repro.rl.policy import policy_logits
from repro.rl.rollout import Trajectory


def step_log_probs(params, traj: Trajectory, activation="tanh"):
    """(H,) log π_θ(a_h | s_h), masked. ``activation`` is a policy logits
    spec — an MLP activation string or a callable (params, obs) ->
    logits.

    The taken action's entry is selected by compare-and-sum, never by
    ``take_along_axis``: under the fleet's vmaps XLA lowers that to an
    element-by-element gather, which took most of a TPU fleet
    iteration. The select fuses into the log-softmax, gives the same
    values and gradients bit for bit, and, unlike a one-hot product,
    keeps a ``-inf`` on an action not taken out of the sum."""
    logits = policy_logits(params, traj.obs, activation)    # (H, A)
    lp = jax.nn.log_softmax(logits)
    iota = jax.lax.broadcasted_iota(jnp.int32, lp.shape, lp.ndim - 1)
    lp = jnp.sum(jnp.where(iota == traj.actions[..., None], lp, 0.0),
                 axis=-1)
    return lp * traj.mask


def _gpomdp_surrogate(params, traj, gamma, baseline, activation):
    """Σ_h (Σ_{t<=h} log π_t) (γ^h r_h − b_h)  — gradient = GPOMDP."""
    lp = step_log_probs(params, traj, activation)
    H = lp.shape[-1]
    disc_r = traj.rewards * gamma ** jnp.arange(H) - baseline * traj.mask
    cum_lp = jnp.cumsum(lp, axis=-1)
    return jnp.sum(cum_lp * jax.lax.stop_gradient(disc_r), axis=-1)


def _reinforce_surrogate(params, traj, gamma, baseline, activation):
    lp = step_log_probs(params, traj, activation)
    H = lp.shape[-1]
    g_return = jnp.sum(traj.rewards * gamma ** jnp.arange(H), axis=-1)
    return jnp.sum(lp, axis=-1) * jax.lax.stop_gradient(g_return - baseline)


register("estimator", "gpomdp")(lambda: _gpomdp_surrogate)
register("estimator", "reinforce")(lambda: _reinforce_surrogate)


def _surrogate(estimator):
    """Resolve an estimator spec (name string or Spec) to its surrogate."""
    return resolve("estimator", estimator)


def grad_estimate(params, traj: Trajectory, gamma: float,
                  baseline: float = 0.0, estimator: str = "gpomdp",
                  activation: str = "tanh", sample_weights=None):
    """(1/M) Σ_i g(τ_i | θ): mean PG over a (M, H, ...) trajectory batch.

    ``sample_weights`` (M,), summing to 1, replaces the uniform 1/M mean —
    the fused engine uses it to mask a fixed max(N, B)-shaped batch down to
    the B trajectories a small PAGE step actually consumes.
    """
    sur = _surrogate(estimator)

    def loss(p):
        s = jax.vmap(lambda t: sur(p, t, gamma, baseline, activation)
                     )(traj)
        if sample_weights is None:
            return jnp.mean(s)
        return jnp.sum(sample_weights * s)

    return jax.grad(loss)(params)


def importance_weights(params_old, params_new, traj: Trajectory,
                       activation="tanh", clip: float = 10.0):
    """ω(τ | θ_new, θ_old) = p(τ|θ_old)/p(τ|θ_new), τ ~ p(·|θ_new).

    Clipped for numerical stability (standard SVRPG practice).
    """
    lp_old = jax.vmap(lambda t: jnp.sum(step_log_probs(params_old, t,
                                                       activation)))(traj)
    lp_new = jax.vmap(lambda t: jnp.sum(step_log_probs(params_new, t,
                                                       activation)))(traj)
    w = jnp.exp(jnp.clip(lp_old - lp_new, -jnp.log(clip), jnp.log(clip)))
    return jax.lax.stop_gradient(w)


def weighted_grad_estimate(params_old, params_new, traj: Trajectory,
                           gamma: float, baseline: float = 0.0,
                           estimator: str = "gpomdp", activation="tanh",
                           sample_weights=None,
                           self_normalized: bool = False):
    """(1/M) Σ_i g^{ω_θnew}(τ_i | θ_old): IS-corrected PG at θ_old from
    trajectories sampled at θ_new. ``sample_weights`` as in
    :func:`grad_estimate`.

    ``self_normalized=True`` divides by the realized weight mass
    (Σ w_i s_i / Σ w_i instead of (1/M) Σ w_i s_i): the classic
    self-normalized IS estimator — biased O(1/M) but consistent, with
    much lower variance when the weights are spread out. The PAGE
    correction keeps the plain (unbiased) form per Assumption 5; the
    normalizer is treated as a constant (not differentiated), matching
    the non-differentiated weights.
    """
    w = importance_weights(params_old, params_new, traj, activation)
    if self_normalized:
        mass = jnp.sum(sample_weights * w) if sample_weights is not None \
            else jnp.mean(w)
        w = w / jnp.maximum(mass, 1e-12)
    sur = _surrogate(estimator)

    def loss(p):
        s = jax.vmap(lambda t: sur(p, t, gamma, baseline, activation))(traj)
        if sample_weights is None:
            return jnp.mean(w * s)
        return jnp.sum(sample_weights * w * s)

    return jax.grad(loss)(params_old)
