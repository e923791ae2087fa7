"""Averaging agreement (paper Def. 3, Algorithm 3) tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks as attacks_lib
from repro.core.aggregators import pairwise_sq_dists
from repro.core.agreement import (_subsets, avg_agree, gda_mean,
                                  honest_diameter, mda_mean,
                                  subset_diameters)


def test_mda_picks_min_diameter_subset():
    x = jnp.array([[0.0], [0.1], [0.2], [10.0]])
    out = mda_mean(x, n_keep=3)
    np.testing.assert_allclose(out, [0.1], atol=1e-6)


def _gather_mda(received, n_keep):
    """MDA's subset search as a gather of each subset's n_keep x n_keep
    distances: the oracle for the masked max over the membership table.
    Returns (diameters, chosen subset index, mean)."""
    subs = jnp.asarray(_subsets(received.shape[0], n_keep))
    d2 = pairwise_sq_dists(received)
    sub_d = d2[subs[:, :, None], subs[:, None, :]]
    diam = jnp.max(sub_d.reshape(subs.shape[0], -1), axis=1)
    best = jnp.argmin(diam)
    return diam, best, jnp.mean(received[subs[best]], axis=0)


def _mda_inputs(n, n_keep, case, d=7):
    key = jax.random.PRNGKey(n * 100 + n_keep)
    if case == "outliers":
        # the last n - n_keep rows are Byzantine-scale noise (sigma 100)
        x = jax.random.normal(key, (n, d))
        return x.at[n_keep:].multiply(100.0)
    # n_keep + 1 rows alternate between two vectors, the rest are
    # outliers: every n_keep-subset of those rows has the same least
    # diameter, so argmin's first-index rule picks the subset
    k1, k2 = jax.random.split(key)
    pair = jax.random.normal(k1, (2, d))[jnp.arange(n_keep + 1) % 2]
    return jnp.concatenate(
        [pair, 100.0 * jax.random.normal(k2, (n - n_keep - 1, d))])


@pytest.mark.parametrize("case", ["outliers", "ties"])
@pytest.mark.parametrize("n,n_keep", [(4, 3), (5, 4), (8, 6), (13, 10),
                                      (16, 12)])
def test_mda_masked_search_matches_gather(n, n_keep, case):
    """The masked max over the membership table gives the gather form's
    diameters, chosen subset and mean, bit for bit."""
    x = _mda_inputs(n, n_keep, case)
    diam_ref, best_ref, out_ref = jax.jit(_gather_mda, static_argnums=1)(
        x, n_keep)
    diam = jax.jit(lambda v: subset_diameters(pairwise_sq_dists(v),
                                              n_keep))(x)
    out = jax.jit(mda_mean, static_argnums=1)(x, n_keep)
    np.testing.assert_array_equal(np.asarray(diam).view(np.uint32),
                                  np.asarray(diam_ref).view(np.uint32))
    assert int(jnp.argmin(diam)) == int(best_ref)
    np.testing.assert_array_equal(np.asarray(out).view(np.uint32),
                                  np.asarray(out_ref).view(np.uint32))
    if case == "ties":
        assert int(jnp.sum(diam == diam[best_ref])) == n_keep + 1


def test_gda_mean_closest_to_own():
    x = jnp.array([[0.0], [1.0], [2.0], [50.0]])
    out = gda_mean(x, own=x[0], n_keep=3)
    np.testing.assert_allclose(out, [1.0], atol=1e-6)


@pytest.mark.parametrize("method", ["gda", "mda"])
def test_contraction_honest(method):
    """Def. 3 first property: honest diameter halves per round (>=2^k)."""
    K, d = 8, 5
    theta = jax.random.normal(jax.random.PRNGKey(0), (K, d))
    hmask = jnp.ones((K,), bool)
    d0 = float(honest_diameter(theta, hmask))
    for kappa in (1, 3):
        out = avg_agree(theta, kappa=kappa, n_byz=0, method=method)
        dk = float(honest_diameter(out, hmask))
        assert dk <= d0 / 2 ** kappa + 1e-5, (method, kappa, dk, d0)


@pytest.mark.parametrize("method", ["gda", "mda"])
def test_contraction_under_per_receiver_attack(method):
    """Byzantines send inconsistent per-receiver garbage; honest agents
    must still contract and stay near the honest hull."""
    K, d, n_byz = 8, 4, 1
    key = jax.random.PRNGKey(1)
    theta = jax.random.normal(key, (K, d))
    byz_mask = jnp.asarray(np.arange(K) < n_byz)
    attack = attacks_lib.per_receiver(
        attacks_lib.get_attack("large_noise", sigma=50.0), K)
    hmask = ~byz_mask
    d0 = float(honest_diameter(theta, hmask))
    out = avg_agree(theta, kappa=4, n_byz=n_byz, byz_mask=byz_mask,
                    method=method, attack=attack, key=key)
    dk = float(honest_diameter(out, hmask))
    assert dk <= d0 / 2 + 1e-4
    # honest outputs remain within the (slightly inflated) honest range
    lo = jnp.min(theta[n_byz:], axis=0) - 0.3 * d0
    hi = jnp.max(theta[n_byz:], axis=0) + 0.3 * d0
    assert bool(jnp.all((out[n_byz:] >= lo) & (out[n_byz:] <= hi)))


def test_mean_preservation_honest_case():
    """Def. 3 second property with alpha=0: agreed mean stays close to the
    input mean."""
    K, d = 8, 6
    theta = jax.random.normal(jax.random.PRNGKey(2), (K, d))
    out = avg_agree(theta, kappa=6, n_byz=0, method="gda")
    drift = jnp.linalg.norm(jnp.mean(out, 0) - jnp.mean(theta, 0))
    diam0 = float(honest_diameter(theta, jnp.ones((K,), bool)))
    assert float(drift) <= diam0  # C_avg = O(1)


def test_avg_zero_attack_defeated_by_agreement():
    K, n_byz = 9, 2
    theta = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (K, 3)) + 5.0
    byz_mask = jnp.asarray(np.arange(K) < n_byz)
    attack = attacks_lib.get_attack("avg_zero")
    # alpha_bar must satisfy n_byz/K < alpha_bar for the guarantee to hold
    out = avg_agree(theta, kappa=4, n_byz=n_byz, byz_mask=byz_mask,
                    method="gda", attack=attack, key=jax.random.PRNGKey(4),
                    alpha_bar=0.25)
    # honest agents stay near 5.0, not dragged to 0
    assert float(jnp.min(out[n_byz:])) > 4.0
