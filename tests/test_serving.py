"""Serving subsystem: batching invariance, slot lifecycle boundaries,
zero-recompile guarantee, the ServeFns shim, and the public API surface.

The load-bearing contract is *batching invariance*: the tokens a request
receives must not depend on how many slots the engine has, which slot it
landed in, or what other requests were in flight — continuous batching
is a scheduling optimization, never a numerics change.
"""
import warnings

import numpy as np
import pytest

import jax

from repro.core.registry import resolve
from repro.rl.envs import make_env
from repro.serving import (DecodeEngine, PolicyServer, Request,
                           SlotScheduler, engine_for_policy, make_traffic)


@pytest.fixture(scope="module")
def env():
    return make_env("cartpole(horizon=16)")


@pytest.fixture(scope="module")
def policy(env):
    return resolve(
        "policy",
        "transformer(arch='qwen2.5-3b', n_layers=2, d_model=64, "
        "n_heads=2)", env=env)


@pytest.fixture(scope="module")
def params(policy):
    return policy.init(jax.random.PRNGKey(42))


def _tokens_by_uid(policy, params, traffic, slots, **kw):
    eng = engine_for_policy(policy, params, slots=slots, max_new=8,
                            max_prompt=4, **kw)
    report = PolicyServer(eng, warmup=False).run_offline(traffic)
    assert len(report.results) == len(traffic)
    return {r.uid: r.tokens for r in report.results}


def test_slot_count_invariance(policy, params, env):
    """Same stream, 1 vs 2 vs 4 slots: identical greedy tokens per uid."""
    traffic = make_traffic(10, seed=7, rate_rps=500.0, max_new=8,
                           obs_dim=env.obs_dim)
    t1 = _tokens_by_uid(policy, params, traffic, slots=1)
    t2 = _tokens_by_uid(policy, params, traffic, slots=2)
    t4 = _tokens_by_uid(policy, params, traffic, slots=4)
    assert t1 == t2 == t4
    # degenerate streams (all-identical tokens) can't catch cross-slot
    # leakage — the fixture params must produce varied outputs
    assert any(len(set(t)) > 1 for t in t1.values())


def test_arrival_order_invariance(policy, params, env):
    """Admission order must not change any request's tokens."""
    traffic = make_traffic(8, seed=3, rate_rps=500.0, max_new=8,
                           obs_dim=env.obs_dim)
    base = _tokens_by_uid(policy, params, traffic, slots=3)
    rng = np.random.default_rng(0)
    for _ in range(2):
        shuffled = list(traffic)
        rng.shuffle(shuffled)
        for i, r in enumerate(shuffled):    # arrival stamps follow order
            r.arrival_s = i * 1e-3
        assert _tokens_by_uid(policy, params, shuffled, slots=3) == base


def test_prompt_padding_invariance(policy, params):
    """A bucketed (padded) prefill must yield the same tokens as an
    exact-length prefill: padded ring entries are invalidated on insert."""
    req = [Request(uid=0, max_new=6, tokens=np.array([3, 1, 2], np.int32))]

    def run(buckets):
        eng = DecodeEngine(policy.model_cfg, params, slots=1, max_new=6,
                           max_prompt=8, prompt_buckets=buckets,
                           n_logits=None)
        sch = SlotScheduler(eng)
        assert sch.admit(req[0]) is None
        (res,) = sch.drain()
        return res.tokens

    assert run(buckets=(3,)) == run(buckets=(8,))


def test_matches_unbatched_reference(policy, params, env):
    """Engine output == the seed-era prefill + decode_step loop, exactly."""
    import jax.numpy as jnp
    from repro.models.model import decode_step, prefill

    cfg = policy.model_cfg
    obs_v = np.linspace(-0.5, 0.5, env.obs_dim).astype(np.float32)
    max_new = 6

    # reference: batch-1, exact length, scalar-pos cache
    pe = jnp.zeros((1, cfg.n_prefix_embeds, cfg.d_model))
    pe = pe.at[0, 0, :env.obs_dim].set(obs_v)
    toks = jnp.zeros((1, 1), jnp.int32)                  # BOS anchor
    W = cfg.n_prefix_embeds + 1 + max_new
    logits, cache = prefill(cfg, params, toks, pe, cache_len=W)
    tok = jnp.argmax(logits[0, -1])
    ref = [int(tok)]
    for _ in range(max_new - 1):
        logits, cache = decode_step(cfg, params, tok[None], cache)
        tok = jnp.argmax(logits[0, 0])
        ref.append(int(tok))

    eng = DecodeEngine(cfg, params, slots=3, max_new=max_new, max_prompt=4)
    sch = SlotScheduler(eng)
    assert sch.admit(Request(uid=0, max_new=max_new, obs=obs_v)) is None
    (res,) = sch.drain()
    assert res.tokens == ref


def test_single_slot_and_same_tick_refill(policy, params, env):
    """1 slot serializes correctly; equal budgets all finish on the same
    tick, free their slots, and the next admissions reuse them."""
    eng = engine_for_policy(policy, params, slots=3, max_new=4,
                            max_prompt=4)
    sch = SlotScheduler(eng)
    obs_dim = env.obs_dim
    first = [Request(uid=i, max_new=3, obs=np.full(obs_dim, 0.1 * i,
                                                   np.float32))
             for i in range(3)]
    for r in first:
        assert sch.admit(r) is None
    assert not sch.has_free() and sch.busy() == 3
    done = []
    while not done:                      # all three retire on one tick
        done = sch.tick()
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert sch.idle() and len(sch.free) == 3
    second = [Request(uid=10 + i, max_new=2,
                      obs=np.full(obs_dim, -0.2 * i, np.float32))
              for i in range(3)]
    for r in second:
        assert sch.admit(r) is None
    got = sch.drain()
    assert sorted(r.uid for r in got) == [10, 11, 12]
    assert all(len(r.tokens) == 2 for r in got)


def test_budget_one_completes_at_prefill(policy, params, env):
    """max_new=1 never occupies a slot: prefill already made the token."""
    eng = engine_for_policy(policy, params, slots=1, max_new=4,
                            max_prompt=4)
    sch = SlotScheduler(eng)
    res = sch.admit(Request(uid=5, max_new=1,
                            obs=np.zeros(env.obs_dim, np.float32)))
    assert res is not None and len(res.tokens) == 1
    assert sch.idle() and sch.has_free()


def test_token_budgets_respected(policy, params, env):
    traffic = make_traffic(6, seed=11, rate_rps=500.0, max_new=8,
                           obs_dim=env.obs_dim)
    eng = engine_for_policy(policy, params, slots=2, max_new=8,
                            max_prompt=4)
    report = PolicyServer(eng, warmup=False).run_offline(traffic)
    budgets = {r.uid: r.max_new for r in traffic}
    for r in report.results:
        assert len(r.tokens) == budgets[r.uid]


def test_lm_serving_builds_bf16_engine():
    """The LM serving launcher keeps weights and KV cache in bf16, the
    published checkpoints' dtype, and answers every request within its
    budget with them."""
    import jax.numpy as jnp
    from repro.configs.base import get_config, reduced
    from repro.launch.serve import build_engine
    cfg = reduced(get_config("qwen2.5-3b"))
    eng = build_engine(cfg, 0, slots=2, max_new=4, max_prompt=4)
    assert {x.dtype for x in jax.tree.leaves(eng.params)} == \
        {jnp.dtype(jnp.bfloat16)}
    assert {x.dtype for x in jax.tree.leaves(eng.init_state().cache)
            if jnp.issubdtype(x.dtype, jnp.floating)} == \
        {jnp.dtype(jnp.bfloat16)}
    traffic = make_traffic(4, seed=3, max_new=4, vocab=cfg.vocab_size,
                           prompt_lens=(1, 4))
    report = PolicyServer(eng, warmup=False).run_offline(traffic)
    assert {r.uid: len(r.tokens) for r in report.results} == \
        {r.uid: r.max_new for r in traffic}


def test_no_recompile_per_request(policy, params, env):
    """After warmup, an entire request stream (mixed budgets, mixed
    arrival patterns, slot churn) triggers zero XLA compiles."""
    from repro.analysis.retrace import CompileLog
    eng = engine_for_policy(policy, params, slots=2, max_new=6,
                            max_prompt=4)
    server = PolicyServer(eng, warmup=True)     # compiles everything here
    traffic = make_traffic(9, seed=5, rate_rps=500.0, max_new=6,
                           obs_dim=env.obs_dim)
    with CompileLog() as log:
        report = server.run_offline(traffic)
    assert len(report.results) == 9
    assert log.compiles() == [], log.compiles()


def test_realtime_matches_offline(policy, params, env):
    """The threaded realtime loop returns the same tokens per uid as the
    offline loop — scheduling differs, numerics must not."""
    traffic = make_traffic(8, seed=9, rate_rps=2000.0, max_new=6,
                           obs_dim=env.obs_dim)
    offline = _tokens_by_uid(policy, params, traffic, slots=2)
    eng = engine_for_policy(policy, params, slots=2, max_new=6,
                            max_prompt=4)
    report = PolicyServer(eng, warmup=False).run(traffic)
    assert {r.uid: r.tokens for r in report.results} == offline
    assert all(r.latency_s >= 0 for r in report.results)


def test_servefns_dataclass_and_shim():
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.distributed.serving import make_serve_fns

    cfg = reduced(get_config("llama3_2_1b"))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    fns = make_serve_fns(cfg, mesh, batch=2, seq_len=16, key=key)
    assert callable(fns.prefill) and callable(fns.decode)
    assert set(fns.shardings) == {"params", "cache", "batch_spec"}
    assert fns.specs["params_shape"] is fns.params_shape
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        pf, dc, specs = fns              # legacy tuple unpacking
    assert pf is fns.prefill and dc is fns.decode
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)


def test_public_api_surface():
    import repro
    for name in ("Experiment", "ScenarioGrid", "run_grid", "register",
                 "resolve", "Spec", "save", "restore", "serve",
                 "get_config", "reduced", "make_env"):
        assert name in repro.__all__, name
        assert getattr(repro, name) is not None
    import repro.serving
    assert repro.serve is repro.serving.serve
    assert repro.obs.progress is not None
    with pytest.raises(AttributeError):
        repro.not_a_real_name


def test_deep_import_lint_rule(tmp_path):
    import ast
    from repro.analysis.lint import DeepImport, FileCtx, LintConfig

    cfg = LintConfig(root=tmp_path)
    rule = DeepImport()

    def findings(src, rel="examples/demo.py"):
        ctx = FileCtx(rel, ast.parse(src), src.splitlines())
        assert rule.wants(ctx, cfg) == rel.startswith("examples/")
        return list(rule.visit(ctx, cfg)) if rule.wants(ctx, cfg) else []

    hit = findings("from repro.core.engine import Experiment\n")
    assert len(hit) == 1 and "Experiment" in hit[0].message
    assert not findings("from repro import Experiment\n")
    assert not findings("from repro.models.model import decode_step\n")
    assert not findings("# analysis: deep-import\n"
                        "from repro.core.engine import Experiment\n")
    # src/ files may deep-import freely — the rule is examples-scoped
    assert not findings("from repro.core.engine import Experiment\n",
                        rel="src/repro/launch/x.py")


def test_serving_obs_telemetry(policy, params, env):
    """Per-request records and gauges only under obs.enabled()."""
    from repro import obs
    traffic = make_traffic(4, seed=2, rate_rps=500.0, max_new=4,
                           obs_dim=env.obs_dim)
    eng = engine_for_policy(policy, params, slots=2, max_new=4,
                            max_prompt=4)
    with obs.capture() as sink:
        PolicyServer(eng, warmup=False).run_offline(traffic)
    reqs = [r for r in sink.records if r.get("stream") == "serve.request"]
    gauges = [r for r in sink.records if r.get("stream") == "serve.gauge"]
    assert len(reqs) == 4
    assert all({"uid", "latency_ms", "ttft_ms", "tokens"} <= set(r)
               for r in reqs)
    assert gauges and all(0 <= g["slots_busy"] <= 2 for g in gauges)
    # off by default: the same run emits nothing
    eng2 = engine_for_policy(policy, params, slots=2, max_new=4,
                             max_prompt=4)
    from repro.obs.sinks import MemorySink
    sink2 = obs.get_recorder().add_sink(MemorySink())
    try:
        PolicyServer(eng2, warmup=False).run_offline(traffic)
    finally:
        obs.get_recorder().remove_sink(sink2)
    assert not [r for r in sink2.records
                if r.get("stream", "").startswith("serve.")
                and r["stream"] != "serve.done"]
