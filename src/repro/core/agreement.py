"""Averaging agreement (paper Def. 3, App. A.3): MDA and GDA over
arbitrary gossip graphs.

``Avg-Agree_κ`` runs κ rounds of message passing; each agent selects a
large low-diameter subset of what it received and averages it. MDA (exact
minimum-diameter subset, exponential in the neighborhood size — capped at
:data:`MDA_MAX_AGENTS`) tolerates α_max = 1/4; GDA (greedy: the
⌈(1-ᾱ)·deg⌉ closest to the agent's own vector, O(deg)) tolerates
α_max = 1/5 and is the production path.

The paper's Algorithm 3 is the complete-graph case. The core here
generalizes it to any static directed topology (DESIGN.md §5): round ``r``
delivers messages only along edges, Byzantine senders may equivocate
per-receiver-edge, and selection runs over the padded fixed-shape
neighbor gather ``(K, deg_max, d)`` so everything vmaps/jits. On the
complete graph the gather table is ``arange(K)`` per row, making the
masked core *identical* to the historical all-to-all broadcast — same
ops, same PRNG stream, same numerics.

The simulator models the full Byzantine adversary including per-receiver
inconsistent messages (a ``(K_recv, K_send, d)`` attack tensor): each
receiver observes its own adversarial version only along its in-edges.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregators import pairwise_sq_dists
from repro.core.registry import REGISTRY, Spec, register, resolve
from repro.kernels import dispatch
from repro.kernels.dispatch import get_kernel
from repro.topology import Topology, resolve_topology

#: Largest neighbor-multiset size ``mda_mean`` will enumerate subsets for.
#: C(n, n_keep) subsets materialize as a trace-time constant — beyond this
#: the enumeration blows up combinatorially. Note the limit applies to the
#: *neighborhood*, not K: MDA on a sparse graph (ring(k=4) has deg 5)
#: stays usable at federation sizes where the complete graph cannot.
MDA_MAX_AGENTS = 16


def _subsets(K: int, size: int) -> np.ndarray:
    """All index-subsets of [K] with given size, as a (n_subsets, size)
    static numpy array (trace-time constant)."""
    return np.array(list(itertools.combinations(range(K), size)),
                    dtype=np.int32)


def subset_diameters(d2: jnp.ndarray, n_keep: int) -> jnp.ndarray:
    """(n, n) squared distances -> (n_sub,) squared diameter of each
    ``n_keep``-subset, in :func:`_subsets` order.

    A masked max over a trace-time membership table ``pair[i, j, s] =
    i, j ∈ S_s``, subsets in the minor axis: no gather of scalars, which
    under the fleet's vmaps runs element by element. Masked entries read
    ``-inf``, so every member pair, the diagonal included, counts exactly
    as it would in a max over the subset's own entries.
    """
    n = d2.shape[0]
    subs = _subsets(n, n_keep)
    member = np.zeros((n, subs.shape[0]), bool)
    member[subs, np.arange(subs.shape[0])[:, None]] = True
    pair = member[:, None, :] & member[None, :, :]       # (n, n, n_sub)
    return jnp.max(jnp.where(pair, d2[:, :, None], -jnp.inf), axis=(0, 1))


def mda_mean(received: jnp.ndarray, n_keep: int) -> jnp.ndarray:
    """Exact Minimum-Diameter Averaging: received (n, d) -> (d,).

    Enumerates subsets (static at trace time) — exponential in n, per the
    paper usable only for small multisets; raises beyond
    :data:`MDA_MAX_AGENTS` instead of silently materializing C(n, n_keep)
    subset tables.
    """
    K = received.shape[0]
    if K > MDA_MAX_AGENTS:
        raise ValueError(
            f"mda_mean enumerates C(n, n_keep) subsets at trace time and "
            f"received a multiset of size {K} > MDA_MAX_AGENTS="
            f"{MDA_MAX_AGENTS}; use method='gda' or a sparser topology "
            f"(the limit applies to the neighborhood size, not K)")
    subs = jnp.asarray(_subsets(K, n_keep))              # (n_sub, n_keep)
    best = jnp.argmin(subset_diameters(pairwise_sq_dists(received), n_keep))
    return jnp.mean(received[subs[best]], axis=0)


def gda_mean(received: jnp.ndarray, own: jnp.ndarray,
             n_keep: int) -> jnp.ndarray:
    """Greedy Diameter Averaging: mean of the n_keep vectors closest to the
    agent's own vector. O(n) selection."""
    d2 = jnp.sum((received - own[None]) ** 2, axis=1)
    _, idx = jax.lax.top_k(-d2, n_keep)
    return jnp.mean(received[idx], axis=0)


class AgreementMethod(NamedTuple):
    """A resolved agreement rule. Selection methods (MDA/GDA) carry
    ``select(received, own, n_keep) -> (d,)`` plus the tolerated
    ``alpha_bar``; coordinate-wise methods instead carry ``reduce`` (a
    gossip-reduce mode) and run through the fused ``gossip_reduce``
    kernel."""
    select: Optional[Callable]
    alpha_bar: float
    reduce: Optional[str] = None
    n_trim: int = 0


@register("agreement", "mda", max_agents=MDA_MAX_AGENTS)
def _mda_factory(alpha_bar: float = 0.25):
    return AgreementMethod(lambda recv, own, n_keep: mda_mean(recv, n_keep),
                           alpha_bar)


@register("agreement", "gda")
def _gda_factory(alpha_bar: float = 0.2):
    return AgreementMethod(gda_mean, alpha_bar)


@register("agreement", "cwmean")
def _cwmean_factory():
    """Plain lazy-gossip averaging (no Byzantine tolerance) — the α = 0
    baseline, and the fastest contraction on honest graphs."""
    return AgreementMethod(None, 0.0, reduce="mean")


@register("agreement", "cwmed")
def _cwmed_factory():
    """Coordinate-wise median over each neighbor multiset (α_max = 1/2
    per coordinate under bounded dispersion)."""
    return AgreementMethod(None, 0.5, reduce="median")


@register("agreement", "cwtm")
def _cwtm_factory(n_byz: int = 0, n_trim: Optional[int] = None):
    """Coordinate-wise trimmed mean over each neighbor multiset; trims
    ``n_trim`` (default: the config's ``n_byz``) from each tail, so it
    needs ``deg_max > 2·n_trim``."""
    nt = n_byz if n_trim is None else n_trim
    return AgreementMethod(None, 0.25, reduce="trimmed", n_trim=nt)


def avg_agree(theta: jnp.ndarray, kappa: int, n_byz: int,
              byz_mask: Optional[jnp.ndarray] = None,
              method="gda",
              attack: Optional[Callable] = None,
              key: Optional[jnp.ndarray] = None,
              alpha_bar: Optional[float] = None,
              topology=None,
              kernel_backend: Optional[str] = None,
              sharded: Optional[bool] = None) -> jnp.ndarray:
    """Simulate Avg-Agree_κ over K agents (paper Algorithm 3, generalized
    to gossip graphs).

    theta: (K, d) current parameters (honest agents' entries are real; the
    Byzantine entries are ignored — Byzantines send whatever ``attack``
    produces, possibly per-receiver).
    method: agreement spec — "mda" | "gda" | "gda(alpha_bar=0.25)" |
    "cwmean" | "cwmed" | "cwtm(n_trim=2)" | Spec. The cw* methods reduce
    each neighbor multiset coordinate-wise through the fused
    ``gossip_reduce`` kernel. ``kernel_backend`` scopes the dispatch
    backend over the whole multi-round core (trace-time), so it governs
    every kernel inside — the gossip reduces and MDA's pairwise-distance
    kernel alike. When ``theta`` is D-sharded (detected eagerly, or forced
    with ``sharded=True`` from inside jit) and no backend was requested,
    the rounds run on the ``jnp`` oracles: the cw reduces are
    coordinate-wise and therefore shard-local, whereas a Pallas call would
    gather the full (K, d) stack to one device.
    attack: fn(broadcast (K,d), byz_mask, key) -> (K_recv, K_send, d) or
    (K_send, d) messages. None = honest broadcast. An active attack
    requires an explicit ``key`` — there is no silent PRNGKey(0) fallback
    (it would make attacks deterministic and identical across calls).
    topology: None (complete broadcast) | spec string/Spec | resolved
    :class:`~repro.topology.Topology`. Messages travel only along the
    graph's edges; selection runs over the padded fixed-shape neighbor
    gather, so low-degree agents see extra copies of their own value.
    Returns the (K, d) post-agreement parameters (Byzantine rows carry the
    value an honest agent in that slot would compute; callers mask them).
    Each gossip round's body carries the ``agree.round`` phase name.
    """
    K, d = theta.shape
    if kernel_backend is None:
        from repro.distributed.aggregation import dim_sharded
        if dim_sharded(theta) if sharded is None else sharded:
            kernel_backend = "jnp"     # shard-local coordinate-wise rounds
    m = resolve("agreement", method, n_byz=n_byz)
    topo = resolve_topology(topology, K)
    nbr = jnp.asarray(topo.nbr_idx)                      # (K, P)
    P = topo.deg_max
    alpha_bar = alpha_bar if alpha_bar is not None else m.alpha_bar
    # never forced to include a Byzantine: n_keep <= P - n_byz (agents know
    # the tolerance bound f, as in any BFT protocol). With GDA's
    # alpha_max = 1/5 this is what makes 3-of-13 (alpha ~ 0.23) behave.
    n_keep = min(int(np.ceil((1.0 - alpha_bar) * P)), P - n_byz)
    n_keep = max(n_keep, 1)
    limit = REGISTRY.meta("agreement", method).get("max_agents")
    if limit is not None and P > limit:
        raise ValueError(
            f"agreement method {Spec.of(method).name!r} supports neighbor "
            f"multisets up to {limit}, but topology {topo.name!r} has "
            f"deg_max={P}; use 'gda' or a sparser topology")
    if byz_mask is None:
        byz_mask = jnp.zeros((K,), bool)
    if key is None:
        if attack is not None:
            raise ValueError(
                "avg_agree: an active attack requires an explicit PRNG "
                "`key` (thread one from the caller's key stream); the old "
                "silent key=None -> PRNGKey(0) fallback made attacks "
                "deterministic and identical across calls")
        # honest rounds never consume a key (attack is None), so the
        # placeholder is a raw zero key, not a PRNG stream
        key = jnp.zeros((2,), jnp.uint32)
    rows = jnp.arange(K)[:, None]

    def one_round(th, k):
        with obs.named_phase("agree.round"):
            return _round_body(th, k)

    def _round_body(th, k):
        if attack is None:
            if m.reduce is not None:
                # honest broadcast: gather + reduce fused in one kernel
                return get_kernel("gossip_reduce")(
                    th, nbr, mode=m.reduce, n_trim=m.n_trim), None
            recv = th[nbr]                               # (K, P, d)
        else:
            a = attack(th, byz_mask, k)
            if a.ndim == 3:
                # per-receiver-edge equivocation: receiver r observes its
                # own adversarial slice a[r] along its in-edges only;
                # honest senders always deliver their true value
                recv = jnp.where(byz_mask[nbr][:, :, None],
                                 a[rows, nbr], th[nbr])
            else:
                sent = jnp.where(byz_mask[:, None], a, th)
                if m.reduce is not None:
                    # consistent attack: still one shared message matrix,
                    # so the fused gather applies
                    return get_kernel("gossip_reduce")(
                        sent, nbr, mode=m.reduce, n_trim=m.n_trim), None
                recv = sent[nbr]
        if m.reduce is not None:
            return get_kernel("neighbor_reduce")(
                recv, mode=m.reduce, n_trim=m.n_trim), None
        new = jax.vmap(lambda rv, own: m.select(rv, own, n_keep)
                       )(recv, th)
        return new, None

    # backend dispatch is a trace-time decision, so scoping the scan is
    # enough to reroute every kernel the rounds touch
    ctx = (dispatch.use_backend(kernel_backend) if kernel_backend
           else contextlib.nullcontext())
    with ctx:
        theta, _ = jax.lax.scan(one_round, theta,
                                jax.random.split(key, kappa))
    return theta


def honest_diameter(theta: jnp.ndarray, honest_mask: jnp.ndarray) -> jnp.ndarray:
    """max_{i,l honest} ||θ_i - θ_l|| — the paper's Δ₂ diagnostic."""
    d2 = pairwise_sq_dists(theta)
    m = honest_mask[:, None] & honest_mask[None, :]
    return jnp.sqrt(jnp.max(jnp.where(m, d2, 0.0)))
