#!/usr/bin/env python3
"""Bring-up check: drive the system's main paths once on TPU and verify them.

    python chip_smoke.py             # one chip: phases "fleet" and "serve"
    python chip_smoke.py --chips 4   # four chips: "lanes" and "dsharded"

One chip:

* ``fleet`` — the paper's Fig. 2 fleet at its widths (DecByzPG on
  ``cartpole(horizon=100)``, K=13 with 3 Byzantine agents, N=20, B=4)
  through ``repro.Experiment``, then the same grid through
  ``repro.SweepRunner`` in two windows. Its arms make every RL-path kernel
  run. Checks: finite returns, identical results from both runs, every
  kernel dispatched to Pallas and never to jnp, and each kernel's output
  against its jnp oracle on the same chip at the shapes the path used.
* ``serve`` — ``qwen2.5-3b`` at its published width with seeded random
  weights, served offline by ``DecodeEngine``/``PolicyServer``. Checks:
  every request gets its ``max_new`` tokens, and one request's greedy
  tokens equal the argmax of the model's full-sequence ``forward``.
  The traffic is served as ``launch/serve.py`` serves it: bf16 weights
  and cache at the default matmul precision. The token comparison
  decodes that request again, and runs ``forward``, with f32 weights at
  "highest".

Four chips (``--chips 4``), only the paths that exist across chips:

* ``lanes`` — the fleet grid on the four-device lane mesh against the
  same grid on one device; every row must be identical.
* ``dsharded`` — ``fed_train_step_flat`` with the (K, D) stack D-sharded
  over a ``("model",)`` mesh of the four chips, at ``xlstm-350m``'s
  published width for a few steps, with no all-gather of the stack; its
  first step is compared with the dense path on one chip at the reduced
  width.

Prints one JSON line per phase (name, seconds with compilation, XLA
compiles, persistent-cache hits, checks with their values), then as its
last line ``{"ok": true, "device": {...}}``. Exits nonzero without that
line when the repository's sources are missing, when JAX finds no TPU,
when a kernel-backend override is set, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
OUT_DIR = os.path.join(ROOT, "results", "chip_smoke")

# -- fleet: benchmarks/run.py's fig2 configuration ---------------------------
FLEET_ENV = "cartpole(horizon=100)"
FLEET_BASE = dict(K=13, n_byz=3, N=20, B=4, eta=2e-2, kappa=4,
                  attack="large_noise")
FLEET_AXES = {"aggregator": ("bucketing(inner=rfa)", "krum", "trimmed_mean")}
FLEET_T, FLEET_SEEDS, FLEET_WINDOWS = 10, (0, 1), 2
RL_KERNELS = ("rfa", "krum_score", "trimmed_mean", "pairwise_dist",
              "gossip_reduce")
# the trimmed_mean arm agrees by cwtm on a sparse ring, which runs the
# gossip_reduce kernel; cwtm trims n_byz values from each tail of a
# neighbourhood, so it needs deg_max > 2 * n_byz: ring(k=6) has deg_max 7
GOSSIP_ARM, GOSSIP_AGREEMENT, GOSSIP_TOPOLOGY = \
    "trimmed_mean", "cwtm", "ring(k=6)"
# relative error allowed between a Pallas kernel and its oracle run at
# "highest": a Mosaic f32 contraction may take one bf16 pass (unit
# roundoff 2^-9 ~ 2e-3 per operand), while a wrong kernel is off by O(1)
PARITY_RTOL = 1e-2

# -- serve: launch/serve.py's default model ----------------------------------
SERVE_ARCH = "qwen2.5-3b"
SERVE_SLOTS, SERVE_REQUESTS, SERVE_MAX_NEW, SERVE_MAX_PROMPT = 4, 8, 8, 8

# -- dsharded: the largest K whose step fits a v5e chip per memory_analysis
DSHARD_ARCH, DSHARD_K, DSHARD_STEPS, DSHARD_SEQ = "xlstm-350m", 2, 3, 64
# the dense path aggregates with the Pallas rfa kernel, the sharded one
# with jnp on the psum'd Gram, hence the kernel-parity bound
DSHARD_RTOL = PARITY_RTOL


def gossip_arm(cfg):
    """Scenario override: one arm gossips by cwtm on a sparse ring, so
    that agreement runs through the ``gossip_reduce`` kernel; the others
    keep the paper's MDA on the complete graph."""
    if cfg.aggregator.name == GOSSIP_ARM:
        return dataclasses.replace(cfg, agreement=GOSSIP_AGREEMENT,
                                   topology=GOSSIP_TOPOLOGY)
    return cfg


def check(ok, value):
    return {"ok": bool(ok), "value": value}


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


# ---------------------------------------------------------------------------
# fleet
# ---------------------------------------------------------------------------

def fleet_grid(**kw):
    return dict(algo="decbyzpg", env=FLEET_ENV, T=FLEET_T, seeds=FLEET_SEEDS,
                axes=FLEET_AXES, override=gossip_arm, **FLEET_BASE,
                **kw)


ROW_KEYS = ("returns", "samples", "diameter", "theta")


def row_diffs(a, b) -> dict:
    """Largest absolute difference per per-seed array over all scenarios
    of two results of one grid (0.0 everywhere when bit-identical)."""
    import numpy as np
    if set(map(tuple, a.keys())) != set(map(tuple, b.keys())):
        raise ValueError("the two results hold different scenarios")
    out = dict.fromkeys(ROW_KEYS, 0.0)
    for scn in a.keys():
        ra, rb = a[scn], b[tuple(scn)]
        for k in ROW_KEYS:
            x, y = np.asarray(ra[k]), np.asarray(rb[k])
            out[k] = max(out[k], float(np.max(np.abs(
                x.astype(np.float64) - y)))) if x.shape == y.shape \
                else float("inf")
    return out


def kernel_parity(d: int) -> dict:
    """Each RL-path kernel against its oracle on this chip, at the shapes
    the fleet path gives it: the aggregators run per agent (K) per row
    (seeds), the distance and gossip kernels once per row."""
    import jax
    import jax.numpy as jnp
    from repro.core.registry import resolve
    from repro.kernels.dispatch import get_kernel
    from repro.topology import resolve_topology

    K, n_byz, R = FLEET_BASE["K"], FLEET_BASE["n_byz"], len(FLEET_SEEDS)
    n_buckets = -(-K // 2)            # bucketing(inner=rfa): s = 2
    gossip = resolve("agreement", GOSSIP_AGREEMENT, n_byz=n_byz)
    nbr = jnp.asarray(resolve_topology(GOSSIP_TOPOLOGY, K).nbr_idx)
    cases = {
        "rfa": ((R, K, n_buckets, d), 2,
                lambda x, b: get_kernel("rfa")(x, n_iter=32, nu=1e-6,
                                               backend=b)),
        "krum_score": ((R, K, K, d), 2,
                       lambda x, b: get_kernel("krum_score")(
                           x, K - n_byz - 2, backend=b)),
        "trimmed_mean": ((R, K, K, d), 2,
                         lambda x, b: get_kernel("trimmed_mean")(
                             x, n_byz, backend=b)),
        "pairwise_dist": ((R, K, d), 1,
                          lambda x, b: get_kernel("pairwise_dist")(
                              x, backend=b)),
        "gossip_reduce": ((R, K, d), 1,
                          lambda x, b: get_kernel("gossip_reduce")(
                              x, nbr, mode=gossip.reduce,
                              n_trim=gossip.n_trim, backend=b)),
    }
    out = {}
    for i, (name, (shape, depth, fn)) in enumerate(cases.items()):
        x = jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)

        def batched(b, fn=fn, depth=depth):
            f = functools.partial(fn, b=b)
            for _ in range(depth):
                f = jax.vmap(f)
            return jax.jit(f)

        got = batched("pallas")(x)
        with jax.default_matmul_precision("highest"):
            want = batched("jnp")(x)
        out[name] = rel_err(got, want)
    return out


def phase_fleet() -> tuple:
    import numpy as np
    from repro import Experiment, SweepRunner
    from repro.kernels import dispatch

    dispatch.reset_dispatch_counts()
    exp = Experiment(**fleet_grid()).run()
    sweep_dir = os.path.join(OUT_DIR, "sweep")
    shutil.rmtree(sweep_dir, ignore_errors=True)
    swept = SweepRunner(**fleet_grid(windows=FLEET_WINDOWS,
                                     out_dir=sweep_dir)).run()
    tally = {}
    for (name, backend, _), n in dispatch.dispatch_counts().items():
        tally.setdefault(name, {}).setdefault(backend, 0)
        tally[name][backend] += n
    finite = all(np.isfinite(r["returns"]).all()
                 for res in (exp, swept) for r in res.results.values())
    d = next(iter(exp.results.values()))["theta"].shape[-1]
    parity = kernel_parity(d)
    summary = exp.summary()
    return {}, {
        "returns_finite": check(finite, {k: v["final_return_mean"]
                                         for k, v in summary.items()}),
        "experiment_equals_sweep": check(
            summary == swept.summary()
            and not any(row_diffs(exp, swept).values()),
            row_diffs(exp, swept)),
        "kernels_on_pallas": check(
            all(tally.get(k, {}).get("pallas", 0) > 0 for k in RL_KERNELS)
            and not any("jnp" in v for v in tally.values()), tally),
        "kernel_parity": check(
            all(e <= PARITY_RTOL for e in parity.values()), parity),
    }


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_traffic(cfg, traffic):
    """The traffic served as ``launch/serve.py`` serves it (bf16 weights
    and cache, default matmul precision); the weights are freed after."""
    import jax
    from repro.launch.serve import build_engine
    from repro.serving import PolicyServer

    engine = build_engine(cfg, 0, slots=SERVE_SLOTS, max_new=SERVE_MAX_NEW,
                          max_prompt=SERVE_MAX_PROMPT)
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    report = PolicyServer(engine).run_offline(traffic)
    for x in jax.tree.leaves(engine.params):
        x.delete()
    return report, int(n_params)


def decode_vs_forward(cfg, req):
    """One request decoded by the engine and the argmax of the full
    forward over prompt plus output, both with f32 weights at "highest".
    At the default precision decode and forward round to bf16 at
    different points, which flips the argmax wherever two random-weight
    logits are close."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import forward, init_params
    from repro.serving import DecodeEngine, PolicyServer

    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(1))
    with jax.default_matmul_precision("highest"):
        engine = DecodeEngine(cfg, params, slots=SERVE_SLOTS,
                              max_new=SERVE_MAX_NEW,
                              max_prompt=SERVE_MAX_PROMPT)
        out = PolicyServer(engine, warmup=False).run_offline(
            [req]).results[0].tokens
        seq = np.concatenate([req.tokens, np.asarray(out, np.int32)])
        logits = jax.jit(lambda p, t: forward(cfg, p, t)[0])(
            params, jnp.asarray(seq)[None])
    P = len(req.tokens)
    want = np.asarray(jnp.argmax(logits[0, P - 1:P - 1 + len(out)], -1))
    return list(out), want.tolist()


def phase_serve() -> tuple:
    from repro.configs.base import get_config
    from repro.serving import make_traffic

    cfg = get_config(SERVE_ARCH)
    traffic = make_traffic(SERVE_REQUESTS, seed=0, max_new=SERVE_MAX_NEW,
                           vocab=cfg.vocab_size, prompt_lens=(1, 4, 8))
    report, n_params = serve_traffic(cfg, traffic)
    budget = {r.uid: r.max_new for r in traffic}
    got = {r.uid: len(r.tokens) for r in report.results}
    req = max(traffic, key=lambda r: len(r.tokens))
    out, want = decode_vs_forward(cfg, req)
    return {}, {
        "published_width": check(
            cfg.d_model == 2048 and cfg.n_layers == 36, int(n_params)),
        "all_answered": check(got == budget and len(got) == SERVE_REQUESTS,
                              {"weights": "bf16", "precision": "default",
                               **report.summary()}),
        "greedy_equals_forward": check(
            want == out, {"weights": "f32", "precision": "highest",
                          "uid": req.uid, "served": out, "forward": want}),
    }


# ---------------------------------------------------------------------------
# four chips: lanes, dsharded
# ---------------------------------------------------------------------------

def phase_lanes() -> tuple:
    import jax
    from repro import Experiment
    from repro.distributed.sharding import lane_mesh, use_lane_mesh

    mesh = lane_mesh()
    spread = Experiment(**fleet_grid()).run()
    with use_lane_mesh(None):
        single = Experiment(**fleet_grid()).run()
    diffs = row_diffs(spread, single)
    return {}, {
        "lane_mesh": check(mesh is not None and mesh.size == 4,
                           None if mesh is None else mesh.size),
        "rows_identical": check(not any(diffs.values()), diffs),
        "devices": check(len(jax.devices()) == 4, len(jax.devices())),
    }


def fed_step(cfg, fed, unravel, shardings=None, sharded=False):
    """Jitted flat federated step; ``large`` is traced, so both PAGE
    branches share one program."""
    import jax
    from repro.distributed.fed_trainer import fed_train_step_flat

    def step(state, batch, mask, key, large):
        return fed_train_step_flat(cfg, fed, state, unravel, batch, mask,
                                   key, large=large, sharded=sharded)
    if shardings is None:
        return jax.jit(step)
    return jax.jit(step, in_shardings=(shardings, None, None, None, None),
                   out_shardings=(shardings, None), donate_argnums=(0,))


def phase_dsharded() -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.base import get_config, reduced
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.distributed.fed_trainer import (
        FedConfig, common_sample_coin, flat_fed_state_shardings,
        init_flat_fed_state)

    mesh = Mesh(np.asarray(jax.devices()), ("model",))
    fed = FedConfig(aggregator="rfa", kappa=2, lr=1e-4, page_p=0.25)
    K = DSHARD_K
    mask = jnp.zeros((K,), bool)
    key = jax.random.PRNGKey(0)

    def run(cfg, on_mesh, steps):
        state, unravel = init_flat_fed_state(
            cfg, fed, K, key, mesh=mesh if on_mesh else None)
        pipe = TokenPipeline(DataConfig(cfg.vocab_size, DSHARD_SEQ, 1, K))
        sh = flat_fed_state_shardings(mesh, jax.eval_shape(lambda: state)) \
            if on_mesh else None
        step = fed_step(cfg, fed, unravel, sh, sharded=on_mesh)
        args = (pipe.batch(0), mask, key, jnp.asarray(True))
        # one compile: the steps run the executable whose HLO is checked
        step = step.lower(state, *args).compile()
        hlo = step.as_text() if on_mesh else ""
        D = state.theta.shape[1]
        metrics = []
        for t in range(steps):
            large = jnp.asarray(common_sample_coin(t, fed.seed, fed.page_p))
            state, m = step(state, pipe.batch(t), mask,
                            jax.random.fold_in(key, t), large)
            metrics.append({k: float(v) for k, v in m.items()})
        return state, metrics, hlo, D

    full = get_config(DSHARD_ARCH)
    state, metrics, hlo, D = run(full, True, DSHARD_STEPS)
    stack_gathers = re.findall(
        rf"f32\[{K},{D}\]\{{[^}}]*\}} all-gather\(", hlo)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    del state

    small = reduced(full)
    with jax.default_matmul_precision("highest"):
        s_sh, m_sh, _, _ = run(small, True, 1)
        s_de, m_de, _, _ = run(small, False, 1)
    parity = {"v": rel_err(s_sh.v, s_de.v),
              "loss": rel_err(m_sh[0]["loss"], m_de[0]["loss"])}
    return {"peak_bytes_in_use": peak}, {
        "published_width": check(full.d_model == 1024 and full.n_layers == 24,
                                 {"K": K, "D": D}),
        "finite": check(all(np.isfinite(list(m.values())).all()
                            for m in metrics), metrics),
        "no_stack_all_gather": check(not stack_gathers and hlo != "",
                                     {"all_gathers": hlo.count(
                                         " all-gather("),
                                      "of_stack": len(stack_gathers)}),
        "dense_parity": check(all(e <= DSHARD_RTOL for e in parity.values()),
                              parity),
    }


# ---------------------------------------------------------------------------
# phases and exit status
# ---------------------------------------------------------------------------

def run_phase(name, fn, counter) -> bool:
    s0 = counter.snapshot()
    t0 = time.perf_counter()
    try:
        values, checks = fn()
        error = None
    except Exception as e:          # report the phase, then go on
        traceback.print_exc()
        values, checks, error = {}, {}, f"{type(e).__name__}: {e}"
    d = counter.snapshot() - s0
    ok = error is None and all(c["ok"] for c in checks.values())
    line = {"phase": name, "ok": ok,
            "seconds": time.perf_counter() - t0,
            "compiles": d.compiles, "cache_hits": d.cache_hits, **values,
            "checks": checks}
    if error is not None:
        line["error"] = error
    print(json.dumps(line, default=str), flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro sources under {SRC}", file=sys.stderr)
        return 2
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        print("chip_smoke: REPRO_KERNEL_BACKEND is set; the check runs "
              "the automatic kernel choice only", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro import obs
    from repro.kernels import dispatch
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    if dispatch.current_backend() != "pallas":
        print(f"chip_smoke: kernels would run on "
              f"{dispatch.current_backend()!r}, not pallas", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)
    counter = obs.compile_counter()
    phases = ({"lanes": phase_lanes, "dsharded": phase_dsharded}
              if args.chips == 4 else
              {"fleet": phase_fleet, "serve": phase_serve})
    ok = [run_phase(name, fn, counter) for name, fn in phases.items()]
    if not all(ok):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
