"""End-to-end federated training driver (deliverable (b)).

Runs DecByzPG over any ``--arch`` with the synthetic token pipeline:
Common-Sample PAGE coin -> per-agent gradients -> Byzantine attack (opt.)
-> robust aggregation -> per-agent Adam -> Avg-Agree_κ.

Steps execute through the fused experiment engine (DESIGN.md §2): windows
of ``--window`` iterations run as one ``lax.scan`` program with the PAGE
coin drawn in-scan, so the host only touches the device once per window.
``--no-fused`` falls back to the legacy per-step dispatch driver (two
compiled programs selected by the host-side coin).

CPU-runnable with ``--reduced`` (the 2-layer family variant); on a real
cluster drop ``--reduced`` and launch one process per host with the
production mesh.

Telemetry (DESIGN.md §8): ``--telemetry-out DIR`` turns on the obs layer —
``fed.telemetry=True`` in-step taps streamed to ``DIR/metrics.jsonl`` plus
a ``DIR/manifest.json`` run manifest; ``--profile`` additionally records
a ``jax.profiler`` trace into ``DIR`` (``DIR/plugins/profile/<run>/
*.xplane.pb``; TensorBoard/XProf-loadable): the host-side window spans,
engine builds and garbage collections on the same clock as the device
operations, each named by its program phase.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --agents 4 --steps 30 --byz 1 --attack large_noise
"""
import argparse
import contextlib
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint import save
from repro.configs.base import get_config, reduced
from repro.core import engine
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.distributed.fed_trainer import (FedConfig, common_sample_coin,
                                           fed_train_step, fed_train_window,
                                           init_fed_state)
from repro.launch.compile_cache import enable_compile_cache


def _stack_batches(batches: list) -> dict:
    """List of per-step batch dicts -> one tree with a leading W axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--byz", type=int, default=0)
    ap.add_argument("--attack", default="none",
                    help="attack spec, e.g. none | large_noise(sigma=10)")
    ap.add_argument("--aggregator", default="rfa",
                    help="aggregator spec, e.g. rfa | rfa(n_iter=16)")
    ap.add_argument("--optimizer", default="adam",
                    help="optimizer spec, e.g. adam | sgd(momentum=0.9)")
    ap.add_argument("--kappa", type=int, default=3)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--page-p", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--window", type=int, default=5,
                    help="steps fused into one scanned device program")
    ap.add_argument("--no-fused", action="store_true",
                    help="legacy per-step dispatch (two compiled programs)")
    ap.add_argument("--telemetry-out", default=None, metavar="DIR",
                    help="enable telemetry; write metrics.jsonl + "
                         "manifest.json (and a profiler trace with "
                         "--profile) under DIR")
    ap.add_argument("--profile", action="store_true",
                    help="record a jax.profiler trace (host spans and "
                         "device ops) into DIR (implies telemetry; "
                         "default DIR: telemetry/)")
    args = ap.parse_args()
    enable_compile_cache()

    out_dir = args.telemetry_out or ("telemetry" if args.profile else None)
    telemetry_on = out_dir is not None

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    # CLI strings are component specs; FedConfig normalizes them to frozen
    # Spec values resolved through the registry inside the train step.
    fed = FedConfig(aggregator=args.aggregator, kappa=args.kappa,
                    n_byz=args.byz, attack=args.attack, lr=args.lr,
                    optimizer=args.optimizer,
                    page_p=args.page_p, seed=args.seed,
                    telemetry=telemetry_on)
    K = args.agents
    key = jax.random.PRNGKey(args.seed)
    state = init_fed_state(cfg, fed, K, key)

    pipe = TokenPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        per_agent_batch=args.batch, n_agents=K,
        n_prefix_embeds=cfg.n_prefix_embeds if cfg.frontend != "none" else 0,
        d_model=cfg.d_model, seed=args.seed))
    byz_mask = jnp.asarray(np.arange(K) < args.byz)

    if telemetry_on:
        os.makedirs(out_dir, exist_ok=True)
        tele = obs.telemetry(
            obs.JsonlSink(os.path.join(out_dir, "metrics.jsonl")))
    else:
        tele = contextlib.nullcontext()
    profile = (jax.profiler.trace(out_dir) if args.profile
               else contextlib.nullcontext())

    obs.progress(
        f"arch={cfg.name} K={K} byz={args.byz} attack={fed.attack} "
        f"agg={fed.aggregator} opt={fed.optimizer} kappa={args.kappa} "
        f"mode={'legacy' if args.no_fused else 'fused'}")
    t0 = time.time()

    def report(step_i, coin, metrics):
        obs.progress(f"step {step_i:4d} c={int(coin)} "
                     f"loss={float(metrics['loss']):.4f} "
                     f"diam={float(metrics['diameter']):.3e} "
                     f"({time.time() - t0:.1f}s)", step=step_i)

    with tele, profile:
        if args.no_fused:
            steps = {True: jax.jit(lambda s, b, m, k: fed_train_step(
                         cfg, fed, s, b, m, k, large=True)),
                     False: jax.jit(lambda s, b, m, k: fed_train_step(
                         cfg, fed, s, b, m, k, large=False))}
            for step_i in range(args.steps):
                c = common_sample_coin(step_i, args.seed, fed.page_p)
                key, k_step = jax.random.split(key)
                with obs.host_span("train.step", step=step_i, coin=int(c)):
                    state, metrics = steps[c](state, pipe.batch(step_i),
                                              byz_mask, k_step)
                if step_i % max(args.steps // 10, 1) == 0 \
                        or step_i == args.steps - 1:
                    report(step_i, c, metrics)
        else:
            wstep = jax.jit(
                lambda s, b, ts, k: fed_train_window(cfg, fed, s, b,
                                                     byz_mask, ts, k),
                donate_argnums=engine.donate_args(0))
            key, k_loop = jax.random.split(key)
            n_windows = -(-args.steps // args.window)
            report_every = max(n_windows // 10, 1)
            for w_i, w0 in enumerate(range(0, args.steps, args.window)):
                ts = np.arange(w0, min(w0 + args.window, args.steps))
                batches = _stack_batches([pipe.batch(int(t)) for t in ts])
                with obs.host_span("train.window", window=w_i,
                                   steps=len(ts)):
                    state, metrics = jax.block_until_ready(
                        wstep(state, batches, jnp.asarray(ts), k_loop))
                if w_i % report_every == 0 or w_i == n_windows - 1:
                    last = jax.tree.map(lambda m: m[-1], metrics)
                    report(int(ts[-1]),
                           bool(np.asarray(metrics["coin"][-1])), last)

        if args.ckpt:
            save(jax.tree.map(lambda l: l[0], state.params), args.ckpt)
            obs.progress(f"saved honest-agent-0 params to {args.ckpt}")

        if telemetry_on:
            obs.write_manifest(
                os.path.join(out_dir, "manifest.json"),
                extra={"arch": cfg.name, "K": K, "n_byz": args.byz,
                       "attack": str(fed.attack),
                       "aggregator": str(fed.aggregator),
                       "steps": args.steps, "window": args.window,
                       "mode": "legacy" if args.no_fused else "fused"})
            obs.progress(f"telemetry written to {out_dir}/")


if __name__ == "__main__":
    main()
