#!/usr/bin/env python3
"""Readings that a fleet cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --seed0 <n>

For each of ``--seeds`` seeds the cell's set-up runs the system's timed
path as a benchmark run does (the same driver, the same programs, the
same first windows) and its outputs are compared with the plain
reference. Then, for the same rows, the reference is put in the
system's place in lower precision (the control) and with each planted
fault, and compared in the same way. Prints one JSON line per variant
and seed, and writes them all to ``results/bench/calibrate-<cell>.json``
(or ``--out``). The limits in ``bench/limits/<cell>.json`` lie between
the largest sound reading and the smallest control or fault reading
that separates.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def as_program_outputs(ref: dict) -> dict:
    """Reference rows in the layout the driver keeps of the program's
    first windows."""
    return {"returns": ref["returns"], "coins": ref["coins"],
            "theta0": ref["theta0"], "theta": ref["theta"],
            "adam_m": ref["adam_m"][:, 0]}


def rows_of(tree: dict, i: int, rows: int) -> dict:
    return {k: v[i * rows:(i + 1) * rows] for k, v in tree.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed0", type=int, default=1)
    ap.add_argument("--controls", type=int, default=None,
                    help="seeds for the control and the faults "
                         "(default: all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax.numpy as jnp
    import numpy as np

    from bench import run as harness
    from bench.reference import fleet as reference
    cell = harness.resolve_cell(
        harness.load_json(os.path.join(ROOT, "BENCHMARK.json")),
        args.workload)
    harness.enable_compile_cache()
    traffic = harness.load_json(cell["paths"]["traffic"])
    mod = harness.load_module(cell["paths"]["driver"], "bench_driver")

    seeds = [args.seed0 + i for i in range(args.seeds)]
    early, firsts, drv = [], [], None
    for s in seeds:
        drv = mod.Driver(cell["config"], traffic, s)
        drv.setup()
        early.append(drv.early)
        firsts.append(drv.first_seeds)
        drv.free()
    rows = len(firsts[0])
    all_rows = np.concatenate(firsts)
    n_ctl = args.controls or len(seeds)
    variants = {"sound": (jnp.float32, None, len(seeds)),
                "control_bf16": (jnp.bfloat16, None, n_ctl)}
    for fault in reference.FAULTS:
        variants["fault_" + fault] = (jnp.float32, fault, n_ctl)
    records = []
    ref32 = None
    for name, (dtype, fault, n) in variants.items():
        fleet = reference.Fleet(cell["config"], drv.T, dtype=dtype,
                                fault=fault)
        out = fleet.run(all_rows[:n * rows], drv.n_check, drv.W)
        if name == "sound":
            ref32 = out
        for i in range(n):
            want = rows_of(ref32, i, rows)
            got = early[i] if name == "sound" else as_program_outputs(
                rows_of(out, i, rows))
            rec = {"variant": name, "seed": seeds[i],
                   **mod.compare(got, want, cell["config"]),
                   "return_gap_by_iter": np.mean(
                       mod.return_gaps(got, want), axis=0).tolist()}
            records.append(rec)
            print(json.dumps({k: v for k, v in rec.items()
                              if k != "return_gap_by_iter"}), flush=True)
    out = args.out or os.path.join(ROOT, "results", "bench",
                                   f"calibrate-{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
