"""Serving driver: continuous-batching decode CLI (deliverable (b)).

Two modes share the `repro.serving` engine:

* **LM traffic** (default) — any registered arch (reduced or full),
  token-prompt requests over its vocab:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --reduced --requests 32 --slots 4 --gen 16

* **policy traffic** (``--policy``) — the aggregated transformer policy
  via the ``repro.serving.serve`` front door (observation requests
  through the prefix-embedding frontend):

    PYTHONPATH=src python -m repro.launch.serve \
        --policy "transformer(arch='llama3.2-1b', n_layers=2, \
d_model=64, n_heads=2)" --checkpoint results/policy.npz
"""
import argparse
import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.serving import DecodeEngine, PolicyServer, make_traffic, serve


def build_engine(cfg, seed: int, *, slots: int, max_new: int,
                 max_prompt: int) -> DecodeEngine:
    """Seeded random weights for ``cfg`` behind a :class:`DecodeEngine`.

    Weights and KV cache are bf16, the dtype the published checkpoints
    ship in. An f32 ``qwen2.5-3b`` (11.5 GiB) leaves too little of a 16 GiB
    TPU v5e for the bf16 weight copies XLA hoists out of the layer scan
    at the default matmul precision, which rounds f32 operands to bf16
    anyway.
    """
    key_init, _ = jax.random.split(jax.random.PRNGKey(seed))
    # jitted, so a published-width init holds only its outputs on the
    # device (eagerly, each layer stack would briefly exist twice)
    params = jax.jit(functools.partial(init_params, cfg,
                                       dtype=jnp.bfloat16))(key_init)
    return DecodeEngine(cfg, params, slots=slots, max_new=max_new,
                        max_prompt=max_prompt, dtype=jnp.bfloat16)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default=None,
                    help="policy spec string — serve observation traffic "
                         "through repro.serving.serve instead of LM "
                         "token traffic")
    ap.add_argument("--env", default="cartpole(horizon=32)")
    ap.add_argument("--checkpoint", default=None,
                    help="aggregated-policy checkpoint (policy mode)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--rate", type=float, default=100.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--offline", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.policy is not None:
        kw = {"checkpoint": args.checkpoint} if args.checkpoint else \
            {"key": jax.random.PRNGKey(args.seed)}
        report = serve(policy=args.policy, env=args.env,
                       n_requests=args.requests, rate_rps=args.rate,
                       slots=args.slots, max_new=args.gen,
                       seed=args.seed, realtime=not args.offline, **kw)
        obs.progress("policy serve", **report.summary())
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    server = PolicyServer(build_engine(cfg, args.seed, slots=args.slots,
                                       max_new=args.gen,
                                       max_prompt=args.prompt_len))
    traffic = make_traffic(
        args.requests, seed=args.seed, rate_rps=args.rate,
        max_new=args.gen, vocab=cfg.vocab_size,
        prompt_lens=tuple(p for p in (1, 4, 8, args.prompt_len)
                          if p <= args.prompt_len))
    report = server.run_offline(traffic) if args.offline \
        else server.run(traffic)
    obs.progress(f"lm serve arch={cfg.name}", **report.summary())
    for r in report.results[:2]:
        obs.progress(f"  uid={r.uid}: {r.tokens[:12]}")


if __name__ == "__main__":
    main()
