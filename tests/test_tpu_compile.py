"""The RL-path Pallas kernels and the fused fleet programs compile for TPU
v5e chips that are described, not attached (no chip needed): one chip,
and a four-chip lane mesh.

Interpret-mode tests cannot see what only the TPU compiler refuses: slices
not aligned to the tiling, kernels over the fast-memory budget. Each test
compiles at a real width and asserts the kernel is in the program
(``tpu_custom_call``). The topology is described inside a module fixture,
never at import, because only one process at a time may load the TPU
compiler library.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.registry import resolve
from repro.kernels import dispatch
from repro.kernels.dispatch import get_kernel
from repro.topology import resolve_topology

K, N_BYZ, SEEDS = 13, 3, 2          # the paper's Fig. 2 fleet, 2 seeds
PAPER_D = 386                        # cartpole, 16-16 MLP
TRANSFORMER_D = 1 << 20
GOSSIP_TOPOLOGY = "ring(k=6)"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the TPU compiler ships in libtpu; a CPU-only install cannot
    # describe a chip, and any other failure to describe one is a fault
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed")
    from jax.experimental import topologies
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _gossip(x, backend="pallas"):
    m = resolve("agreement", "cwtm", n_byz=N_BYZ)
    nbr = jnp.asarray(resolve_topology(GOSSIP_TOPOLOGY, K).nbr_idx)
    return get_kernel("gossip_reduce")(x, nbr, mode=m.reduce,
                                       n_trim=m.n_trim, backend=backend)


#: kernel -> (call on one (K', d) stack, rows K', vmap depth on the
#: fleet path: aggregators per agent per seed, the rest per seed)
KERNELS = {
    "rfa": (lambda x: get_kernel("rfa")(x, n_iter=32, nu=1e-6,
                                        backend="pallas"),
            -(-K // 2), 2),
    "krum_score": (lambda x: get_kernel("krum_score")(
        x, K - N_BYZ - 2, backend="pallas"), K, 2),
    "trimmed_mean": (lambda x: get_kernel("trimmed_mean")(
        x, N_BYZ, backend="pallas"), K, 2),
    "pairwise_dist": (lambda x: get_kernel("pairwise_dist")(
        x, backend="pallas"), K, 1),
    "gossip_reduce": (_gossip, K, 1),
}


def _hlo(fn, shape, sharding):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return jax.jit(fn).lower(x).compile().as_text()


@pytest.mark.parametrize("d", (PAPER_D, TRANSFORMER_D),
                         ids=("paper", "transformer"))
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_tpu(one_chip, name, d):
    fn, rows, _ = KERNELS[name]
    assert "tpu_custom_call" in _hlo(fn, (rows, d), one_chip)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_under_fleet_vmap(one_chip, name):
    """The batched form the fused fleet traces: vmapped over agents
    and seeds for the aggregators, over seeds for the others."""
    fn, rows, depth = KERNELS[name]
    lead = (SEEDS, K)[:depth]
    for _ in range(depth):
        fn = jax.vmap(fn)
    assert "tpu_custom_call" in _hlo(fn, lead + (rows, PAPER_D), one_chip)


def _fleet_group(seeds):
    """The lane group of the paper fleet's trimmed-mean arm, which
    agrees by cwtm gossip on a ring: ``(env, static_cfg, names, rows)``."""
    from repro.core import engine
    from repro.rl.envs import make_env

    grid = engine.ScenarioGrid(seeds=seeds,
                               axes={"aggregator": ("trimmed_mean",)})
    _, scenarios = engine.grid_scenarios(
        grid, base=dict(K=K, n_byz=N_BYZ, N=20, B=4, eta=2e-2, kappa=4,
                        attack="large_noise", agreement="cwtm",
                        topology=GOSSIP_TOPOLOGY))
    (static_cfg, names), members = next(iter(
        engine.lane_groups(scenarios).items()))
    return (make_env("cartpole(horizon=100)"), static_cfg, names,
            len(members) * len(seeds))


def test_fused_fleet_window_compiles_for_tpu(one_chip):
    """One window program of the lane-batched fleet with Pallas forced,
    at the paper's widths."""
    from repro.core import engine

    env, static_cfg, names, rows = _fleet_group((0, 1))
    W = 5

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with dispatch.use_backend("pallas"):
        carry = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                             engine.lane_carry_struct(env, static_cfg, rows))
        fn = engine.lane_window_loop(env, static_cfg, 2 * W, names, W, rows)
        hlo = fn.lower(carry, sds((rows, len(names)), jnp.float32),
                       sds((rows,), jnp.int32),
                       sds((W,), jnp.int32)).compile().as_text()
    engine.clear_cache()       # the program was built for a described chip
    assert "tpu_custom_call" in hlo


def test_fleet_on_lane_mesh_compiles_for_four_chips(topo):
    """The one-shot fleet program on a four-chip lane mesh: every chip
    runs the kernels on its own rows (a Pallas kernel cannot be
    partitioned automatically), so no collective is left."""
    from jax.sharding import Mesh
    from repro.core import engine
    from repro.distributed.sharding import lane_sharding, use_lane_mesh

    mesh = Mesh(list(topo.devices), ("lane",))
    env, static_cfg, names, rows = _fleet_group((0, 1, 2, 3))
    rows_sh = lane_sharding(mesh, rows)
    with dispatch.use_backend("pallas"), use_lane_mesh(mesh):
        fn = engine.lane_batch_loop(env, static_cfg, 4, names, rows)
        hlo = fn.lower(
            jax.ShapeDtypeStruct((rows, len(names)), jnp.float32,
                                 sharding=rows_sh),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=rows_sh),
        ).compile().as_text()
    engine.clear_cache()
    assert "tpu_custom_call" in hlo
    assert "all-gather(" not in hlo and "all-reduce(" not in hlo
