"""The fleet driver end to end on the CPU, and the correctness check
against its control and against faults planted in the timed path.

Runs go through ``run_cell``, the harness's run without its look for a
chip, at the cells' configurations with 2 lane rows instead of 6.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as harness
from bench.calibrate import as_program_outputs

CELLS = ("fleet-k13-rfa", "fleet-k13-mean")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 41 + 17


def small_cell(workload, tmp_path):
    bench = harness.load_json(harness.ROOT + "/BENCHMARK.json")
    cell = harness.resolve_cell(bench, workload)
    traffic = harness.load_json(cell["paths"]["traffic"])
    traffic["rows"] = 2
    path = tmp_path / "traffic.json"
    path.write_text(json.dumps(traffic))
    cell["paths"]["traffic"] = str(path)
    return cell


def run_small(workload, tmp_path, trace=0, seconds=0.3):
    result, record = harness.run_cell(
        small_cell(workload, tmp_path), workload, SEED, seconds, trace,
        jax.devices()[:1], PEAK)
    return result, record


@pytest.fixture
def fresh_programs():
    from repro.core import engine
    engine.clear_cache()
    yield
    engine.clear_cache()


def decides(result) -> list:
    """Names of the numbers that failed their limits."""
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("workload", CELLS)
def test_a_cell_runs_end_to_end_and_is_correct(workload, tmp_path):
    result, record = run_small(workload, tmp_path)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"fleet_iters_per_s", "setup_s"}
    assert record["window_compiles"] == 0
    assert list(result)[-1] == "checks"


def test_a_traced_run_reports_the_layers(tmp_path, monkeypatch):
    import functools

    from bench import trace
    # on the CPU the window's operations run on host threads
    monkeypatch.setattr(trace, "reduce_dir", functools.partial(
        trace.reduce_dir, host_ops=True))
    result, _ = run_small("fleet-k13-rfa", tmp_path, trace=1)
    m = result["metrics"]
    assert 0 <= m["idle_share.fleet"]["value"] < 100
    assert 0 < m["mfu.fleet"]["value"] < 100
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _frozen(real):
    def window(*a, **kw):
        fn = real(*a, **kw)
        return lambda carry, *xs: (carry, fn(carry, *xs)[1])
    return window


def _altered(real):
    def window(*a, **kw):
        fn = real(*a, **kw)

        def call(*xs):
            carry, chunk = fn(*xs)
            return carry, {**chunk, "returns": chunk["returns"] + 1.0}
        return call
    return window


def _half_batch(real):
    """Each agent keeps the first half of its episodes, each twice: every
    mean over the batch (gradient, correction, return) is over the rest."""
    def sample_batch(env, params, key, n, *a, **kw):
        return jax.tree.map(lambda x: jnp.repeat(x[:n // 2], 2, axis=0),
                            real(env, params, key, n, *a, **kw))
    return sample_batch


FAULTS = {
    "state_unchanged": ("repro.core.engine", "lane_window_loop", _frozen),
    "answer_altered": ("repro.core.engine", "lane_window_loop", _altered),
    "half_batch": ("repro.core.decbyzpg", "sample_batch", _half_batch),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_a_fault_in_the_timed_path_is_not_correct(workload, fault, tmp_path,
                                                  monkeypatch,
                                                  fresh_programs):
    import importlib
    module, name, plant = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, plant(getattr(mod, name)))
    result, _ = run_small(workload, tmp_path)
    assert not result["correct"]
    assert decides(result)


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(workload, tmp_path):
    """The reference in bfloat16, put in the program's place, fails."""
    cell = small_cell(workload, tmp_path)
    mod = harness.load_module(cell["paths"]["driver"], "bench_driver")
    drv = mod.Driver(cell["config"], harness.load_json(
        cell["paths"]["traffic"]), SEED)
    drv.setup()
    drv.free()
    want = drv.reference_rows()
    got = as_program_outputs(drv.reference_rows(dtype=jnp.bfloat16))
    limits = harness.load_json(cell["paths"]["limits"])
    checks = harness.check(mod.compare(got, want, cell["config"]), limits)
    assert decides({"checks": checks}), checks
    assert np.isfinite([c["value"] for c in checks.values()]).all()
