"""Telemetry-overhead benchmark (DESIGN.md §8).

Measures the fused DecByzPG loop warm us/iteration with telemetry **off**
(the default — no callback in the program) and **on** (in-loop taps
streaming to a JSONL sink), at the smoke and fig1 sweep points. Rows land
in ``benchmarks/BENCH_obs.json``:

* ``fused_off`` — the gated baseline: ``check_regress.py`` asserts the
  off path stays within tolerance of the committed numbers, i.e. adding
  the telemetry layer cost the default path nothing;
* ``fused_on``  — the same loop compiled with ``telemetry=True``
  (ungated: callback cost is host-scheduler noise at smoke sizes, and
  the on path is opt-in by design); carries ``overhead_vs_off``.

The doc declares its row identity via the generic ``key_fields``
fallback in ``check_regress.py`` instead of a hard-coded schema branch.

  PYTHONPATH=src python -m benchmarks.bench_obs [--smoke]

``--smoke`` runs only the smoke point and doubles as the CI telemetry
artifact run: the on-path JSONL stream is written to the untracked
``benchmarks/TELEMETRY_smoke.jsonl`` and a ``jax.profiler`` trace of the
on path (host spans and device ops on one clock) into the untracked
``benchmarks/TRACE_smoke/`` (both uploaded by CI; the ``.xplane.pb``
loads in TensorBoard / XProf).
"""
import argparse
import contextlib
import dataclasses
import json
import os
import tempfile
import time

import jax

N_REP = 3
HERE = os.path.dirname(__file__)

# (env_spec, T, base config kwargs); the first entry is the smoke point.
# sign_flip keeps every Byzantine message adversarial on every round, so
# the rejected-mask stream in the telemetry artifacts is non-trivial.
SIZES = (
    ("cartpole(horizon=20)", 5,
     dict(K=3, n_byz=1, attack="sign_flip", aggregator="krum", N=4, B=2,
          kappa=2, hidden=(8,))),
    ("cartpole(horizon=100)", 10,
     dict(K=13, n_byz=3, attack="sign_flip", aggregator="krum", N=20, B=4,
          kappa=4, hidden=(16, 16))),
)


def _warm_us_per_iter(run, env, cfg, T) -> float:
    run(env, cfg, T)                         # compile + warm-up
    t0 = time.perf_counter()
    for _ in range(N_REP):
        run(env, cfg, T)
    return (time.perf_counter() - t0) * 1e6 / (N_REP * T)


def measure(env_spec: str, T: int, base: dict, jsonl_path: str,
            trace_dir=None) -> list:
    from repro import obs
    from repro.core.decbyzpg import DecByzPGConfig, run_decbyzpg
    from repro.rl.envs import make_env

    env = make_env(env_spec)
    cfg = DecByzPGConfig(**base, seed=0)
    off_us = _warm_us_per_iter(run_decbyzpg, env, cfg, T)

    cfg_on = dataclasses.replace(cfg, telemetry=True)
    profile = (jax.profiler.trace(trace_dir) if trace_dir is not None
               else contextlib.nullcontext())
    with obs.telemetry(obs.JsonlSink(jsonl_path)), profile:
        with obs.host_span("bench_obs.fused_on", env=env_spec, T=T):
            on_us = _warm_us_per_iter(run_decbyzpg, env, cfg_on, T)

    shared = {"env": env_spec, "K": base["K"], "T": T}
    obs.progress(f"bench_obs {env_spec} K={base['K']} T={T}: "
                 f"off={off_us:.1f}us/iter on={on_us:.1f}us/iter "
                 f"({on_us / off_us:.2f}x)")
    return [
        {"name": "fused_off", "us_per_call": off_us, **shared},
        # wall_us_per_iter (not us_per_call) so the on path never gates
        {"name": "fused_on", "wall_us_per_iter": on_us,
         "overhead_vs_off": on_us / off_us, **shared},
    ]


def run(smoke: bool = False) -> dict:
    from repro import obs
    rows = []
    sizes = SIZES[:1] if smoke else SIZES
    with tempfile.TemporaryDirectory() as tmp:
        for i, (env_spec, T, base) in enumerate(sizes):
            if smoke:
                jsonl = os.path.join(HERE, "TELEMETRY_smoke.jsonl")
                trace = os.path.join(HERE, "TRACE_smoke")
            else:
                jsonl = os.path.join(tmp, f"metrics_{i}.jsonl")
                trace = None
            rows += measure(env_spec, T, base, jsonl, trace)
    doc = {"bench": "obs", "backend": jax.default_backend(),
           "smoke": smoke,
           # generic check_regress row identity (no hard-coded branch)
           "key_fields": ["name", "env", "K", "T"],
           "rows": rows}
    name = "BENCH_obs_smoke.json" if smoke else "BENCH_obs.json"
    path = os.path.join(HERE, name)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    obs.progress(f"# wrote {path}")
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI run (smoke point only); also "
                         "writes the TELEMETRY_smoke.jsonl / "
                         "TRACE_smoke/ CI artifacts")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
