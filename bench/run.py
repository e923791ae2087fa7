#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
that belongs to it is found by name: its configuration file, its traffic
mix ``bench/traffic/<traffic>.json``, the driver named by the
configuration's ``driver`` (``bench/drivers/<driver>.py``), the limits
of its correctness check ``bench/limits/<cell>.json``, and, in a traced
run, one reader per per-layer metric (``bench/metrics/<metric>.py``).

A run sets up the cell (programs compiled or loaded from the persistent
cache, state made on the device from the seed, the first windows run
and kept for the check), measures for ``--seconds`` with the profiler
off (``--trace 0``, end-to-end metrics) or on (``--trace 1``, per-layer
metrics), frees the program's state, compares what the timed path
produced with the plain reference, and prints one JSON line last.
Diagnostics go to standard error and to ``results/bench/``.

Exits with 3, printing no result, when JAX finds no TPU, fewer chips
than the cell asks for, or a chip missing from ``bench/peaks.json``;
with 2 when the cell or the system's sources cannot be found.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
RESULTS = os.path.join(ROOT, "results", "bench")
#: longest stretch a traced run records: a trace of the fleet window
#: program takes about 17 MB a window
TRACE_SECONDS = 2.0


class CellError(Exception):
    """The cell, or a file it names, cannot be found."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise CellError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(bench: dict, workload: str) -> dict:
    """The cell's entries and the paths of every file it names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_path = os.path.join(ROOT, configs[cell["config"]]["file"])
    if not os.path.isfile(config_path):
        raise CellError(f"no configuration file {config_path}")
    config = load_json(config_path)
    paths = {
        "config": config_path,
        "traffic": os.path.join(BENCH, "traffic", cell["traffic"] + ".json"),
        "driver": os.path.join(BENCH, "drivers", config["driver"] + ".py"),
        "limits": os.path.join(BENCH, "limits", workload + ".json"),
    }
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    for m in per_layer:
        paths["metric:" + m["name"]] = os.path.join(
            BENCH, "metrics", m["name"] + ".py")
    for key, path in paths.items():
        if not os.path.isfile(path):
            raise CellError(f"{key}: no file {os.path.relpath(path, ROOT)}")
    return {"cell": cell, "config": config, "paths": paths,
            "end_to_end": end_to_end, "per_layer": per_layer}


class CompileCounter:
    """XLA compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit also reports a backend-compile duration)."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def compiles(self) -> int:
        return self.requests - self.hits


def enable_compile_cache() -> str:
    """JAX's persistent cache in ``$JAX_COMPILATION_CACHE_DIR`` or the
    checkout's fixed ``.jax_cache``, keeping every program."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_report(devices) -> dict:
    """The devices as JAX reports them; the memory peak is the fullest
    chip's."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def check(numbers: dict, limits: dict) -> dict:
    """Each number that the cell's limits file holds a limit for, beside
    that limit (the file's other keys say what the limit was set from)."""
    return {name: {"value": numbers[name], "limit": spec["limit"]}
            for name, spec in limits.items()}


def traced_measure(driver, seconds: float, trace_dir: str):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        # the first call after the profiler starts runs slow while it sets
        # up; it runs before the spans that bound the traced window
        driver.measure(0.0)
        stats = driver.measure(seconds, spans=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = resolve_cell(bench, args.workload)
    except (CellError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the system's sources (src/repro) are missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    devices = jax.devices()
    chips = int(cell["cell"]["chips"])
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if devices[0].platform != "tpu":
        print(f"bench: the first device is {devices[0].platform!r}, not a "
              f"TPU", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    if devices[0].device_kind not in peaks:
        print(f"bench: no peaks for {devices[0].device_kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return 3
    cache_dir = enable_compile_cache()
    result, record = run_cell(cell, args.workload, args.seed, args.seconds,
                              args.trace, devices[:chips],
                              peaks[devices[0].device_kind])
    record["cache_dir"] = cache_dir
    out_dir = os.path.join(RESULTS, args.workload)
    with open(os.path.join(out_dir, f"run-{args.seed}-{args.trace}.json"),
              "w") as f:
        json.dump(record, f)
    print(json.dumps({k: record[k] for k in (
        "setup_compiles", "window_compiles", "dispatch_counts")}))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


def run_cell(cell: dict, workload: str, seed: int, seconds: float,
             trace: int, devices: list, peak: dict) -> tuple:
    """Set up, measure, check and report one run on ``devices``; returns
    the result line's object and the run's record for ``results/``."""
    from repro.kernels import dispatch
    counter = CompileCounter()
    dispatch.reset_dispatch_counts()
    traffic = load_json(cell["paths"]["traffic"])
    driver_mod = load_module(cell["paths"]["driver"], "bench_driver")
    driver = driver_mod.Driver(cell["config"], traffic, seed)
    driver.setup()
    setup_s = time.perf_counter() - T_START
    setup_compiles = counter.compiles()

    out_dir = os.path.join(RESULTS, workload)
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        stats = traced_measure(driver, min(seconds, TRACE_SECONDS),
                               trace_dir)
    else:
        stats = driver.measure(seconds)
    window_compiles = counter.compiles() - setup_compiles
    device = device_report(devices)
    kernels = driver.kernel_instructions() if trace else {}
    driver.free()

    t_ref = time.perf_counter()
    numbers = driver.compare(driver.reference_rows())
    reference_s = time.perf_counter() - t_ref
    checks = check(numbers, load_json(cell["paths"]["limits"]))
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and stats["failed"] == 0

    metrics, breakdown = {}, None
    if trace:
        from bench import trace as trace_lib
        reduced = trace_lib.reduce_dir(trace_dir, len(devices))
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = {"peak": peak, "chips": len(devices), "kernels": kernels,
               **driver.layer_context(stats)}
        for m in cell["per_layer"]:
            reader = load_module(cell["paths"]["metric:" + m["name"]],
                                 "bench_metric")
            value = reader.read(reduced, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = reduced.breakdown()
    else:
        measured = driver.end_to_end(stats)
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] in measured:
                metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}

    record = {
        "workload": workload, "seed": seed, "trace": trace,
        "setup_s": setup_s, "setup_compiles": setup_compiles,
        "window_compiles": window_compiles, "reference_s": reference_s,
        "numbers": numbers, "kernels": kernels,
        "dispatch_counts": [[*k, n] for k, n in
                            sorted(dispatch.dispatch_counts().items())],
        "window": stats}
    result = {"correct": bool(correct), "attempted": stats["attempted"],
              "failed": stats["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, record


if __name__ == "__main__":
    sys.exit(main())
