"""BENCHMARK.json holds to its contract, and every cell finds its files
by name."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import run as harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_references():
    b = bench()
    configs = {c["name"] for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    names = [*configs, *cells, *e2e, *(m["name"] for m in b["per_layer"])]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in b["workloads"]}
    assert len(pairs) == len(b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_its_files_by_name(workload):
    cell = harness.resolve_cell(bench(), workload)
    for key in ("config", "traffic", "driver", "limits"):
        assert os.path.isfile(cell["paths"][key])
    for m in cell["per_layer"]:
        assert os.path.isfile(cell["paths"]["metric:" + m["name"]])
    harness.load_module(cell["paths"]["driver"], "bench_driver").Driver
    assert cell["config"]["name"] == cell["cell"]["config"]


def test_unknown_cell_is_refused():
    with pytest.raises(harness.CellError):
        harness.resolve_cell(bench(), "no-such-cell")


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet-k13-rfa",
         "--seed", str(2 ** 40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _last_line_is_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_no_chip_means_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert not _last_line_is_result(out.stdout)
    assert "not a TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not _last_line_is_result(out.stdout)
