"""Federated DecByzPG trainer for the assigned architectures.

State layout: every agent's parameters/optimizer state carry a leading K
axis sharded over the federation axes (DESIGN.md §3), so this *is* the
decentralized algorithm — no chip holds another agent's state; the robust
aggregation and GDA agreement are the only cross-agent collectives.

Per step (the PAGE coin is drawn host-side by Common-Sample and selects one
of two compiled programs):
  large (c=1): ṽ^(k) = ∇CE(θ^(k); batch_k)
  small (c=0): ṽ^(k) = ∇CE(θ^(k); b_k) − ∇CE(θ_prev^(k); b_k) + v_prev^(k)
then: attack → robust-aggregate → per-agent optimizer step → Avg-Agree_κ.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.aggregators import rejection_mask
from repro.core.registry import normalize_spec_fields
from repro.distributed import aggregation as agg_lib
from repro.distributed.sharding import (batch_spec, fed_axes, n_agents,
                                        param_shardings)
from repro.models.model import init_params, lm_loss, lm_loss_labeled
from repro.optim.optimizers import get_optimizer


@dataclasses.dataclass(frozen=True)
class FedConfig:
    aggregator: object = "rfa"       # str | Spec, normalized to Spec
    kappa: int = 4
    alpha_bar: float = 0.2
    n_byz: int = 0
    attack: object = "none"
    lr: float = 1e-4
    optimizer: object = "adam"
    page_p: float = 0.1              # Common-Sample coin probability
    mix_dtype: Optional[str] = None  # None | "bfloat16" (§Perf opt)
    mix_block: int = 0               # stream agreement in K-blocks (§Perf)
    seed: int = 0
    telemetry: bool = False          # static: in-step obs taps;
    # off = no debug_callback in the jaxpr

    def __post_init__(self):
        normalize_spec_fields(self, ("aggregator", "attack", "optimizer"))


class FedState(NamedTuple):
    params: object       # agent-stacked (K, ...)
    prev_params: object
    v: object            # running PAGE direction, agent-stacked
    opt_state: object
    step: jnp.ndarray


def init_fed_state(cfg: ModelConfig, fed: FedConfig, K: int, key,
                   dtype=jnp.float32) -> FedState:
    p0 = init_params(cfg, key, dtype)
    stack = jax.tree.map(lambda l: jnp.broadcast_to(l[None], (K,) + l.shape),
                         p0)                      # common init θ_0
    opt = get_optimizer(fed.optimizer, fed.lr, maximize=False)
    opt_state = jax.vmap(opt.init)(stack)
    v = jax.tree.map(jnp.zeros_like, stack)
    return FedState(stack, stack, v, opt_state, jnp.zeros((), jnp.int32))


def _loss(cfg, params, batch):
    if "labels" in batch:
        return lm_loss_labeled(cfg, params, batch["tokens"],
                               batch["labels"], batch.get("prefix_embeds"))
    return lm_loss(cfg, params, batch["tokens"],
                   batch.get("prefix_embeds"))


def fed_train_step(cfg: ModelConfig, fed: FedConfig, state: FedState,
                   batch, byz_mask, key, *, large) -> tuple:
    """batch: {'tokens': (K, b, S)[, 'prefix_embeds': (K, b, P, D)]}.

    ``large`` is either a Python bool (static — two compiled programs, the
    PAGE switch resolved by the host-side Common-Sample coin, the legacy
    driver) or a traced boolean scalar (one compiled program with a
    ``lax.cond`` PAGE switch — the fused-window driver, where the coin is
    drawn inside the scan).
    Returns (new_state, metrics).
    """
    grad_fn = jax.grad(lambda p, b: _loss(cfg, p, b))
    loss_fn = jax.value_and_grad(lambda p, b: _loss(cfg, p, b))

    with obs.named_phase("fed.estimate"):
        losses, g_new = jax.vmap(loss_fn)(state.params, batch)

        def _page(_):
            g_old = jax.vmap(grad_fn)(state.prev_params, batch)
            return jax.tree.map(lambda a, b, c: a - b + c,
                                g_new, g_old, state.v)

        if isinstance(large, (bool, int)):
            tilde_v = g_new if large else _page(None)
        else:
            tilde_v = jax.lax.cond(large, lambda _: g_new, _page, None)

    K = byz_mask.shape[0]
    k_att, k_agg = jax.random.split(key)
    with obs.named_phase("fed.aggregate"):
        if K == 1:
            v = tilde_v    # single-agent federation: aggregation is identity
        else:
            tilde_v = agg_lib.attack_stacked(fed.attack, tilde_v, byz_mask,
                                             k_att)
            v = agg_lib.aggregate(fed.aggregator, tilde_v, fed.n_byz, k_agg)

    opt = get_optimizer(fed.optimizer, fed.lr, maximize=False)
    with obs.named_phase("fed.update"):
        new_params, new_opt = jax.vmap(opt.update)(v, state.opt_state,
                                                   state.params)
    mix_dtype = jnp.bfloat16 if fed.mix_dtype == "bfloat16" else None
    with obs.named_phase("fed.agree"):
        new_params = agg_lib.gda_agree(new_params, fed.kappa, fed.alpha_bar,
                                       mix_dtype=mix_dtype,
                                       block=fed.mix_block)

    with obs.named_phase("fed.diagnostics"):
        metrics = {
            "loss": jnp.mean(jnp.where(byz_mask, 0.0, losses))
            * byz_mask.shape[0] / jnp.maximum(jnp.sum(~byz_mask), 1),
            # K=1: diameter is identically 0 (and the pairwise tensordot would
            # force an all-gather of the full parameter stack)
            "diameter": (jnp.zeros(()) if K == 1 else jnp.sqrt(jnp.max(
                agg_lib.stacked_sq_dists(new_params)))),
        }
        if fed.telemetry:
            # observers only: per-agent honest gradient norms, computed
            # leaf-wise so the model-sharded stacks are never gathered
            sq = sum(jnp.sum(jnp.reshape(l, (K, -1)) ** 2, axis=1)
                     for l in jax.tree.leaves(tilde_v))
            metrics["grad_norm"] = jnp.sum(
                jnp.where(byz_mask, 0.0, jnp.sqrt(sq))) \
                / jnp.maximum(jnp.sum(~byz_mask), 1)
            obs.tap("fed", step=state.step, loss=metrics["loss"],
                    diameter=metrics["diameter"],
                    grad_norm=metrics["grad_norm"])
    new_state = FedState(new_params, state.params, v, new_opt,
                         state.step + 1)
    return new_state, metrics


def fed_state_shardings(cfg: ModelConfig, state_shape: FedState, mesh):
    """NamedShardings for a FedState shape tree (opt_state m/v mirror the
    stacked parameter rules; scalar counters are replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    pshard = lambda tree: param_shardings(cfg, tree, mesh, stacked=True)
    opt = state_shape.opt_state
    if hasattr(opt, "m") and hasattr(opt, "v"):          # AdamState
        opt_sh = type(opt)(rep, pshard(opt.m), pshard(opt.v))
    elif hasattr(opt, "m"):                              # MomentumState
        opt_sh = type(opt)(pshard(opt.m))
    else:
        opt_sh = jax.tree.map(lambda _: rep, opt)
    return FedState(pshard(state_shape.params),
                    pshard(state_shape.prev_params),
                    pshard(state_shape.v), opt_sh, rep)


def make_fed_step(cfg: ModelConfig, fed: FedConfig, mesh, *, large: bool,
                  dtype=jnp.float32, per_agent_batch: int = 8,
                  seq_len: int = 512, key=None):
    """jit'd federated step with mesh shardings (used by launch + dry-run).

    Returns (jitted_step, state_shape, batch_shape, shardings dict).

    ``key`` shapes the FedState tree (consumed only under
    ``jax.eval_shape``): pass the caller's init key — or a
    ``ShapeDtypeStruct`` — to make the stream explicit; ``None`` uses an
    abstract key struct, so no literal PRNG key is baked in here.
    """
    from jax.sharding import NamedSharding
    K = n_agents(cfg, mesh)
    if key is None:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state_shape = jax.eval_shape(
        lambda k: init_fed_state(cfg, fed, K, k, dtype), key)
    state_sh = fed_state_shardings(cfg, state_shape, mesh)
    b_sh = NamedSharding(mesh, batch_spec(cfg, mesh, stacked=True))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())

    batch = {"tokens": jax.ShapeDtypeStruct((K, per_agent_batch, seq_len),
                                            jnp.int32),
             "labels": jax.ShapeDtypeStruct((K, per_agent_batch, seq_len),
                                            jnp.int32)}
    batch_sh = {"tokens": b_sh, "labels": b_sh}
    if cfg.frontend != "none":
        batch["prefix_embeds"] = jax.ShapeDtypeStruct(
            (K, per_agent_batch, cfg.n_prefix_embeds, cfg.d_model), dtype)
        batch["tokens"] = jax.ShapeDtypeStruct(
            (K, per_agent_batch, seq_len - cfg.n_prefix_embeds), jnp.int32)
        batch["labels"] = batch["tokens"]
        batch_sh["prefix_embeds"] = b_sh

    step = jax.jit(
        lambda state, b, mask, key: fed_train_step(
            cfg, fed, state, b, mask, key, large=large),
        in_shardings=(state_sh, batch_sh, rep, rep),
        out_shardings=(state_sh, None),
        donate_argnums=(0,))
    return step, state_shape, batch, (state_sh, batch_sh, rep)


# ---------------------------------------------------------------------------
# Flat (K, D) federated trainer — transformer-scale robust aggregation
# ---------------------------------------------------------------------------
# The tree-shaped trainer above keeps each leaf model-sharded by the leaf
# rules. The flat trainer instead ravels every agent's parameters into one
# (K, D) stack with D sharded over the "model" axis — the layout the
# registry aggregators' sharded execution layer (DESIGN.md §3,
# ``repro.distributed.aggregation``) operates on: robust aggregation costs
# one K² psum plus shard-local weighted sums, never a parameter gather.


class FlatFedState(NamedTuple):
    theta: jnp.ndarray   # (K, D) flat agent-stacked params (D-sharded)
    prev: jnp.ndarray
    v: jnp.ndarray       # running PAGE direction, (K, D)
    opt_state: object
    step: jnp.ndarray


def flat_param_sharding(mesh):
    """NamedSharding splitting the trailing D axis of (K, D) stacks over
    the mesh's "model" axis (agents replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, "model"))


def init_flat_fed_state(cfg: ModelConfig, fed: FedConfig, K: int, key,
                        dtype=jnp.float32, mesh=None):
    """Common-init flat state. Returns ``(state, unravel)`` where
    ``unravel(row) -> params tree`` recovers one agent's parameters.

    With a mesh whose "model" axis spans >1 device, the (K, D) stacks are
    placed D-sharded, which is what routes the registry aggregators onto
    the sharded Gram path.
    """
    from jax.flatten_util import ravel_pytree
    vec0, unravel = ravel_pytree(init_params(cfg, key, dtype))
    opt = get_optimizer(fed.optimizer, fed.lr, maximize=False)

    def build(vec0):
        theta = jnp.tile(vec0, (K, 1))
        return FlatFedState(theta, jnp.array(theta), jnp.zeros_like(theta),
                            jax.vmap(opt.init)(theta),
                            jnp.zeros((), jnp.int32))

    if mesh is not None and mesh.shape.get("model", 1) > 1:
        # built in place: eagerly, the stacks would first exist whole on
        # one device (a vmapped optimizer init does not follow theta's
        # sharding)
        shardings = flat_fed_state_shardings(mesh,
                                             jax.eval_shape(build, vec0))
        return jax.jit(build, out_shardings=shardings)(vec0), unravel
    return build(vec0), unravel


def flat_fed_state_shardings(mesh, state_shape: FlatFedState):
    """NamedShardings for a FlatFedState shape tree: every (K, D) stack
    D-sharded, scalar counters replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    sh = flat_param_sharding(mesh)
    opt_sh = jax.tree.map(
        lambda l: sh if getattr(l, "ndim", 0) == 2 else rep,
        state_shape.opt_state)
    return FlatFedState(sh, sh, sh, opt_sh, rep)


def fed_train_step_flat(cfg: ModelConfig, fed: FedConfig,
                        state: FlatFedState, unravel, batch, byz_mask,
                        key, *, large, sharded: Optional[bool] = None
                        ) -> tuple:
    """One federated step on the flat (K, D) stack.

    Same protocol as :func:`fed_train_step` (PAGE → attack → robust
    aggregate → per-agent optimizer → GDA agreement), but the aggregation
    runs the *registry* aggregators (``repro.core.aggregators``) over the
    flat stack. ``sharded=True`` forces their sharded Gram path from
    inside jit (detection is eager-only); the aggregate is broadcast back
    to all K rows, matching the broadcast-consistent adversary of the
    tree path.
    """
    from repro.core.registry import resolve as _resolve

    def loss_vec(vec, b):
        return _loss(cfg, unravel(vec), b)

    with obs.named_phase("fed.estimate"):
        losses, g_new = jax.vmap(jax.value_and_grad(loss_vec))(state.theta,
                                                               batch)

        def _page(_):
            g_old = jax.vmap(jax.grad(loss_vec))(state.prev, batch)
            return g_new - g_old + state.v

        if isinstance(large, (bool, int)):
            tilde_v = g_new if large else _page(None)
        else:
            tilde_v = jax.lax.cond(large, lambda _: g_new, _page, None)

    K = byz_mask.shape[0]
    k_att, k_agg = jax.random.split(key)
    with obs.named_phase("fed.aggregate"):
        if K == 1:
            v = tilde_v
        else:
            tilde_v = agg_lib.attack_stacked(fed.attack, tilde_v, byz_mask,
                                             k_att)
            agg = _resolve("aggregator", fed.aggregator, K=K,
                           n_byz=fed.n_byz, sharded=sharded)
            v = jnp.broadcast_to(agg(tilde_v, k_agg)[None],
                                 state.theta.shape)

    opt = get_optimizer(fed.optimizer, fed.lr, maximize=False)
    with obs.named_phase("fed.update"):
        new_theta, new_opt = jax.vmap(opt.update)(v, state.opt_state,
                                                  state.theta)
    mix_dtype = jnp.bfloat16 if fed.mix_dtype == "bfloat16" else None
    with obs.named_phase("fed.agree"):
        new_theta = agg_lib.gda_agree(new_theta, fed.kappa, fed.alpha_bar,
                                      mix_dtype=mix_dtype,
                                      block=fed.mix_block)
    with obs.named_phase("fed.diagnostics"):
        metrics = {
            "loss": jnp.mean(jnp.where(byz_mask, 0.0, losses))
            * K / jnp.maximum(jnp.sum(~byz_mask), 1),
            "diameter": (jnp.zeros(()) if K == 1 else jnp.sqrt(jnp.max(
                agg_lib.stacked_sq_dists(new_theta)))),
        }
        if fed.telemetry:
            norms = jnp.linalg.norm(tilde_v, axis=1)
            metrics["grad_norm"] = jnp.sum(jnp.where(byz_mask, 0.0, norms)) \
                / jnp.maximum(jnp.sum(~byz_mask), 1)
            # the flat (K, D) stack is what the suspicion scores operate on —
            # the tree-shaped trainer has no rejected-mask plane
            metrics["rejected"] = (jnp.zeros((K,), bool) if K == 1 else
                                   rejection_mask(fed.aggregator, tilde_v,
                                                  fed.n_byz))
            obs.tap("fed", step=state.step, loss=metrics["loss"],
                    diameter=metrics["diameter"],
                    grad_norm=metrics["grad_norm"],
                    rejected=metrics["rejected"])
    return FlatFedState(new_theta, state.theta, v, new_opt,
                        state.step + 1), metrics


def fed_coin_key(fed: FedConfig):
    """Coin key of the fused window's in-scan Common-Sample stream (the
    per-step replay in tests derives identical coins from it)."""
    return jax.random.fold_in(jax.random.PRNGKey(fed.seed), 0x0C01)


def fed_train_window(cfg: ModelConfig, fed: FedConfig, state: FedState,
                     batches, byz_mask, ts, key) -> tuple:
    """Fused multi-step driver: ``lax.scan`` a window of W federated steps
    in one program (DESIGN.md §2).

    batches: the per-step batch tree stacked on a leading W axis
    ((W, K, b, S) tokens/labels); ts: (W,) global step indices.  The PAGE
    coin is drawn inside the scan from the fold of a seed-derived coin key
    (``engine.page_coin``), so the window needs no host round-trip per
    iteration.  Returns (final state, metrics stacked (W,)).
    """
    from repro.core import engine
    coin_key = fed_coin_key(fed)

    def body(st, xs):
        batch, t = xs
        coin = engine.page_coin(coin_key, t, fed.page_p)
        st, metrics = fed_train_step(cfg, fed, st, batch, byz_mask,
                                     jax.random.fold_in(key, t), large=coin)
        return st, dict(metrics, coin=coin)

    return jax.lax.scan(body, state, (batches, ts))


def common_sample_coin(step: int, seed: int, p: float) -> bool:
    """Common-Sample: the paper's shared PRNG coin (host-level, derived from
    the common initialization seed; the legacy per-step driver — the fused
    window draws its coin in-scan via ``repro.core.engine.page_coin``)."""
    rng = np.random.default_rng(np.uint64(seed) * np.uint64(1_000_003)
                                + np.uint64(step))
    return bool(step == 0 or rng.random() < p)
