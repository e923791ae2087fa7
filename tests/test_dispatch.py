"""Kernel-backend dispatch layer tests (DESIGN.md §6): backend selection
precedence, the ``kernel`` registry namespace, and jnp-vs-interpret
agreement inside a jitted ``avg_agree`` round."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks as attacks_lib
from repro.core.agreement import avg_agree
from repro.core.registry import REGISTRY, resolve
from repro.kernels import dispatch

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _clean_backend():
    yield
    dispatch.set_backend(None)


def test_kernel_namespace_lists_suite():
    names = REGISTRY.names("kernel")
    for expected in ("pairwise_dist", "trimmed_mean", "gossip_reduce",
                     "neighbor_reduce", "rfa", "krum_score",
                     "flash_attention"):
        assert expected in names


def test_registry_resolve_returns_dispatching_kernel():
    k = resolve("kernel", "trimmed_mean")
    assert k is dispatch.get_kernel("trimmed_mean")
    x = jax.random.normal(KEY, (8, 64))
    np.testing.assert_allclose(k(x, 1, backend="jnp"),
                               k(x, 1, backend="pallas-interpret"),
                               atol=1e-6)


def test_unknown_kernel_and_backend_raise():
    with pytest.raises(KeyError, match="unknown kernel"):
        dispatch.get_kernel("nope")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.get_kernel("trimmed_mean")(jnp.ones((4, 8)), 1,
                                            backend="cuda")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.set_backend("tpu")


def test_backend_precedence(monkeypatch):
    # auto: jnp off-TPU
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    if not dispatch.on_tpu():
        assert dispatch.current_backend() == "jnp"
    # env var overrides auto
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-interpret")
    assert dispatch.current_backend() == "pallas-interpret"
    # global override beats env var
    dispatch.set_backend("jnp")
    assert dispatch.current_backend() == "jnp"
    # scoped override restores the previous global
    with dispatch.use_backend("pallas-interpret"):
        assert dispatch.current_backend() == "pallas-interpret"
    assert dispatch.current_backend() == "jnp"


def test_env_var_validated(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "fast-please")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.default_backend()


@pytest.mark.parametrize("method", ["cwmean", "cwmed", "cwtm"])
def test_avg_agree_backends_agree_honest(method):
    """backend="jnp" vs backend="pallas-interpret" inside a jitted
    avg_agree round (fused gather + reduce path, ring topology)."""
    theta = jax.random.normal(KEY, (9, 130))         # crosses one d-block
    outs = {}
    for backend in ("jnp", "pallas-interpret"):
        fn = jax.jit(lambda th, b=backend: avg_agree(
            th, kappa=2, n_byz=1, method=method, topology="ring(k=4)",
            kernel_backend=b))
        outs[backend] = fn(theta)
    np.testing.assert_allclose(outs["jnp"], outs["pallas-interpret"],
                               atol=1e-5, rtol=1e-5)


def test_avg_agree_backends_agree_under_equivocation():
    """Per-receiver equivocation exercises the neighbor_reduce path; both
    backends must agree inside the same jitted round on the same keys."""
    K, n_byz = 8, 1
    theta = jax.random.normal(KEY, (K, 70))
    byz_mask = jnp.asarray(np.arange(K) < n_byz)
    attack = attacks_lib.per_receiver(
        attacks_lib.get_attack("large_noise", sigma=10.0), K)
    outs = {}
    for backend in ("jnp", "pallas-interpret"):
        fn = jax.jit(lambda th, k, b=backend: avg_agree(
            th, kappa=3, n_byz=n_byz, byz_mask=byz_mask, method="cwtm",
            attack=attack, key=k, topology="ring(k=4)", kernel_backend=b))
        outs[backend] = fn(theta, jax.random.PRNGKey(7))
    np.testing.assert_allclose(outs["jnp"], outs["pallas-interpret"],
                               atol=1e-5, rtol=1e-5)


def test_avg_agree_cwtm_contracts_under_attack():
    """The kernel-routed coordinate-wise methods are real agreement rules:
    trimmed gossip shrinks the honest diameter under a consistent attack."""
    from repro.core.agreement import honest_diameter
    K, n_byz = 10, 1
    theta = jax.random.normal(KEY, (K, 16))
    byz_mask = jnp.asarray(np.arange(K) < n_byz)
    hmask = ~byz_mask
    attack = attacks_lib.get_attack("large_noise", sigma=50.0)
    d0 = float(honest_diameter(theta, hmask))
    out = avg_agree(theta, kappa=4, n_byz=n_byz, byz_mask=byz_mask,
                    method="cwtm", attack=attack, key=jax.random.PRNGKey(3))
    assert float(honest_diameter(out, hmask)) < d0 / 2


def test_global_backend_reroutes_aggregator():
    """aggregators.* route through the dispatcher: flipping the global
    backend changes the executed path but not the value."""
    from repro.core.aggregators import rfa, trimmed_mean
    x = jax.random.normal(KEY, (8, 200))
    with dispatch.use_backend("jnp"):
        tm_j, rfa_j = trimmed_mean(x, 1), rfa(x, n_iter=8)
    with dispatch.use_backend("pallas-interpret"):
        tm_p, rfa_p = trimmed_mean(x, 1), rfa(x, n_iter=8)
    np.testing.assert_allclose(tm_j, tm_p, atol=1e-6)
    np.testing.assert_allclose(rfa_j, rfa_p, atol=1e-4)


def _auto_backends(kernel_name, shapes):
    """Backends auto mode bakes into a trace of ``kernel_name`` at each
    (K, d) stack shape — traced abstractly, so no kernel runs."""
    k = dispatch.get_kernel(kernel_name)
    picked = []
    for K, d in shapes:
        dispatch.reset_dispatch_counts()
        jax.eval_shape(lambda x: k(x, 1), jax.ShapeDtypeStruct((K, d),
                                                              jnp.float32))
        (name, backend, reason), = dispatch.dispatch_counts()
        assert (name, reason) == (kernel_name, "auto")
        picked.append(backend)
    return picked


# from a 4-element stack to a transformer-sized one
_SIZES = ((2, 2), (13, 386), (8, 512), (64, 4096), (13, 1 << 20))


def test_auto_picks_pallas_at_every_size_on_tpu(monkeypatch):
    """On TPU auto dispatches the compiled kernel whatever the operand
    size: no size cutoff sends a small stack to the oracle. Explicit
    choices still win over auto."""
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    assert _auto_backends("trimmed_mean", _SIZES) == ["pallas"] * len(_SIZES)
    k = dispatch.get_kernel("trimmed_mean")
    assert k.resolve_backend("jnp") == "jnp"
    with dispatch.use_backend("pallas-interpret"):
        assert k.resolve_backend() == "pallas-interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp")
    assert k.resolve_backend() == "jnp"
    assert "auto_jnp_below" not in REGISTRY.meta("kernel", "gossip_reduce")


def test_auto_picks_jnp_off_tpu(monkeypatch):
    """Off TPU auto dispatches the jnp oracle at every size."""
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: False)
    assert _auto_backends("trimmed_mean", _SIZES) == ["jnp"] * len(_SIZES)


def test_block_d_stripped_for_jnp_oracle():
    """``block_d`` is a Pallas tiling knob: the oracle path drops it, so
    one call site can pass it unconditionally across backends."""
    from repro.kernels.rfa import ref as rfa_ref
    x = jax.random.normal(KEY, (6, 130))
    k = dispatch.get_kernel("rfa")
    out_j = k(x, n_iter=4, block_d=64, backend="jnp")
    np.testing.assert_array_equal(out_j, rfa_ref.rfa(x, n_iter=4))
    out_p = k(x, n_iter=4, block_d=64, backend="pallas-interpret")
    np.testing.assert_allclose(out_j, out_p, atol=1e-5, rtol=1e-5)
