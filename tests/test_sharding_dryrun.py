"""Sharding rules + small-mesh lower/compile (subprocess: the fake-device
XLA flag must be set before jax initializes, so these run out-of-process).
The full production-mesh sweep is ``python -m repro.launch.dryrun --all``
(results in EXPERIMENTS.md §Dry-run)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_spec_rules():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import param_spec, param_shardings
from repro.models.model import init_params
mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = get_config("qwen2_7b")
shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
sh = param_shardings(cfg, shapes, mesh)
specs = {"/".join(str(getattr(p, "key", p)) for p in path): s.spec
         for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
assert specs["embed"] == jax.sharding.PartitionSpec("model", None), specs["embed"]
assert specs["blocks/attn/wq"][-1] == "model"
assert specs["blocks/attn/wo"][-2] == "model"
assert specs["blocks/mlp/w_down"][-2] == "model"
# hymba vocab 32001 not divisible -> replicated embed
cfg2 = get_config("hymba_1_5b")
shapes2 = jax.eval_shape(lambda k: init_params(cfg2, k), jax.random.PRNGKey(0))
sh2 = param_shardings(cfg2, shapes2, mesh)
assert sh2["embed"].spec == jax.sharding.PartitionSpec(None, None)
print("SPEC_OK")
"""
    assert "SPEC_OK" in _run_subprocess(code)


@pytest.mark.slow
def test_small_mesh_train_and_decode_compile():
    """Full system lower+compile on an 8-device (2 data x 4 model... sic:
    2x2x2 multi-pod) mesh for a dense and an MoE arch, exercising the same
    code path as the production dry-run."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.distributed.fed_trainer import FedConfig, make_fed_step
from repro.distributed.serving import make_serve_fns
import dataclasses

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
for arch in ["llama3_2_1b", "deepseek_v2_lite_16b"]:
    cfg = reduced(get_config(arch))
    cfg = dataclasses.replace(cfg, fed_axis="data")
    fed = FedConfig(aggregator="rfa", kappa=2, n_byz=1)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step, state_shape, batch, _ = make_fed_step(
        cfg, fed, mesh, large=True, per_agent_batch=2, seq_len=32, key=key)
    K = jax.tree.leaves(state_shape.params)[0].shape[0]
    mask = jax.ShapeDtypeStruct((K,), jnp.bool_)
    compiled = step.lower(state_shape, batch, mask, key).compile()
    assert compiled.cost_analysis()["flops"] > 0
    fns = make_serve_fns(cfg, mesh, batch=4, seq_len=64, key=key)
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    fns.decode.lower(fns.params_shape, tok, fns.cache_shape).compile()
    print(arch, "COMPILE_OK")
"""
    out = _run_subprocess(code)
    assert out.count("COMPILE_OK") == 2


def test_lane_grid_shards_over_fake_devices():
    """The lane-batched grid on a forced 4-device host mesh: the
    flattened lane×seed batch (4 etas × 2 seeds = 8 rows) divides the
    device count, so `lane_sharding` shards it — and the sharded run
    must reproduce the per-scenario loop's traces on the same machine."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core.engine import ScenarioGrid, run_grid
from repro.distributed.sharding import lane_mesh, lane_sharding
from repro.rl.envs import make_cartpole

mesh = lane_mesh()
assert mesh is not None and mesh.size == 4, mesh
assert lane_sharding(mesh, 8) is not None     # 8 rows over 4 devices
assert lane_sharding(mesh, 6) is None         # uneven -> identity layout

env = make_cartpole(horizon=10)
grid = ScenarioGrid(seeds=(0, 1),
                    axes={"eta": (1e-3, 5e-3, 1e-2, 2e-2)})
kw = dict(algo="decbyzpg", K=3, n_byz=1, attack="large_noise(sigma=10)",
          N=4, B=2, kappa=1, hidden=(4,))
lanes = run_grid(env, grid, 3, lanes=True, **kw)
per = run_grid(env, grid, 3, lanes=False, **kw)
for scn in per:
    np.testing.assert_allclose(lanes[scn]["returns"],
                               per[scn]["returns"], atol=1e-5)
    np.testing.assert_array_equal(lanes[scn]["samples"],
                                  per[scn]["samples"])
print("LANE_SHARD_OK")
"""
    assert "LANE_SHARD_OK" in _run_subprocess(code)


def test_lane_grid_pads_uneven_rows_onto_mesh():
    """An uneven lane×seed batch (3 lanes × 2 seeds = 6 rows on a
    4-device mesh) pads to the device multiple, shards, and still
    reproduces the per-scenario loop — pad rows never leak into
    summaries."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core.engine import ScenarioGrid, run_grid
from repro.distributed.sharding import lane_mesh, lane_sharding, padded_rows
from repro.rl.envs import make_cartpole

mesh = lane_mesh()
assert lane_sharding(mesh, 6) is None         # uneven rows can't shard...
assert padded_rows(mesh, 6) == 8              # ...so the grid pads to 8
assert padded_rows(mesh, 8) == 8

env = make_cartpole(horizon=10)
grid = ScenarioGrid(seeds=(0, 1), axes={"eta": (1e-3, 5e-3, 1e-2)})
kw = dict(algo="decbyzpg", K=3, n_byz=1, attack="sign_flip",
          N=4, B=2, kappa=1, hidden=(4,))
lanes = run_grid(env, grid, 3, lanes=True, **kw)
per = run_grid(env, grid, 3, lanes=False, **kw)
for scn in per:
    assert lanes[scn]["returns"].shape == per[scn]["returns"].shape
    np.testing.assert_allclose(lanes[scn]["returns"],
                               per[scn]["returns"], atol=1e-5)
    np.testing.assert_array_equal(lanes[scn]["samples"],
                                  per[scn]["samples"])
print("LANE_PAD_OK")
"""
    assert "LANE_PAD_OK" in _run_subprocess(code)


def test_lane_mesh_rows_equal_one_device():
    """Each two-row group (one lane × two seeds) on a forced 4-device
    mesh pads to two rows per device, and every row is bit-identical to
    the same grid run on one device: a device holding a single row would
    run the
    program with a unit batch axis, which XLA compiles to different
    arithmetic."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core.engine import ScenarioGrid, run_grid
from repro.distributed.sharding import lane_mesh, padded_rows, use_lane_mesh
from repro.rl.envs import make_cartpole

mesh = lane_mesh()
assert padded_rows(mesh, 2) == 8              # two rows on every device
assert padded_rows(mesh, 9) == 12
assert padded_rows(None, 1) == 1

env = make_cartpole(horizon=10)
grid = ScenarioGrid(seeds=(0, 1), axes={"aggregator": ("krum", "rfa")})
kw = dict(algo="decbyzpg", K=5, n_byz=1, attack="large_noise",
          N=4, B=2, kappa=1, hidden=(4,))
spread = run_grid(env, grid, 5, **kw)
with use_lane_mesh(None):
    single = run_grid(env, grid, 5, **kw)
for scn in single:
    for k in ("returns", "samples", "diameter", "theta"):
        np.testing.assert_array_equal(spread[scn][k], single[scn][k])
print("LANE_ROWS_OK")
"""
    assert "LANE_ROWS_OK" in _run_subprocess(code)


def test_flat_fed_state_built_sharded():
    """On a model mesh every (K, D) stack of the flat federated state,
    the optimizer moments included, is built D-sharded and no whole copy
    stays on one device; the values equal the dense build."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import gc
import jax, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs.base import get_config, reduced
from repro.distributed.fed_trainer import FedConfig, init_flat_fed_state
mesh = Mesh(np.asarray(jax.devices()), ("model",))
cfg = reduced(get_config("xlstm-350m"))
fed = FedConfig(aggregator="rfa", optimizer="adam")
key = jax.random.PRNGKey(0)
state, _ = init_flat_fed_state(cfg, fed, 2, key, mesh=mesh)
dense, _ = init_flat_fed_state(cfg, fed, 2, key)
stacks = [x for x in jax.tree.leaves(state) if x.ndim == 2]
assert len(stacks) == 5, len(stacks)
assert all(x.sharding.spec == P(None, "model") for x in stacks)
gc.collect()
whole = [a for a in jax.live_arrays() if a.ndim == 2
         and len(a.sharding.device_set) == 1 and a.shape == stacks[0].shape]
assert len(whole) == 5, len(whole)          # the dense build only
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(dense)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("FLAT_SHARDED_OK")
"""
    assert "FLAT_SHARDED_OK" in _run_subprocess(code)


def test_dryrun_results_if_present():
    """When the production sweep has run, every recorded pair must have
    lowered+compiled OK."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun_single_pod.json")
    if not os.path.exists(path):
        pytest.skip("production dry-run sweep not yet executed")
    results = json.load(open(path))
    bad = [f"{r['arch']}/{r['shape']}" for r in results if not r["ok"]]
    assert not bad, bad
    assert len(results) >= 40
