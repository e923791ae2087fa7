"""repro.obs telemetry layer (DESIGN.md §8).

The two contracts under test:

* zero-overhead-off: with ``telemetry=False`` (the default) the built
  programs are the exact seed programs — no ``debug_callback`` in the
  jaxpr, identical compiled-loop cache keys, bit-identical histories;
* forensics-on: with ``telemetry=True`` a run under an attack yields the
  tap stream (per-iteration Δ₂ included), stacked ``grad_norm`` /
  ``rejected`` histories, and an ``aggregator_confusion`` tally whose
  precision/recall surface in ``Experiment.summary()``.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine
from repro.core.aggregators import rejection_mask, suspicion_scores
from repro.core.decbyzpg import (DecByzPGConfig, build_decbyzpg_loop,
                                 run_decbyzpg)
from repro.core.engine import Experiment
from repro.kernels import dispatch
from repro.rl.envs import make_env


def _cfg(**kw):
    base = dict(K=4, n_byz=1, attack="sign_flip", aggregator="krum",
                N=4, B=2, kappa=2, hidden=(4,))
    base.update(kw)
    return DecByzPGConfig(**base)


def _loop_jaxpr(cfg, T=3):
    env = make_env("cartpole(horizon=10)")
    loop = build_decbyzpg_loop(env, cfg, T)
    ks = engine.seed_keys(0)
    from repro.core.decbyzpg import init_decbyzpg_carry
    carry = init_decbyzpg_carry(env, cfg, ks.init)
    return jax.make_jaxpr(loop)(*carry, jax.random.split(ks.loop, T),
                                ks.coin)


def _has_primitive(jaxpr, name: str) -> bool:
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == name:
            return True
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") and _has_primitive(v, name):
                return True
    return False


class TestZeroOverheadOff:
    def test_off_jaxpr_has_no_debug_callback(self):
        assert not _has_primitive(_loop_jaxpr(_cfg()), "debug_callback")

    def test_on_jaxpr_has_debug_callback(self):
        assert _has_primitive(_loop_jaxpr(_cfg(telemetry=True)),
                              "debug_callback")

    def test_histories_bit_identical_on_off(self):
        env = make_env("cartpole(horizon=10)")
        off = run_decbyzpg(env, _cfg(seed=2), 4)
        on = run_decbyzpg(env, _cfg(seed=2, telemetry=True), 4)
        np.testing.assert_array_equal(off["returns"], on["returns"])
        np.testing.assert_array_equal(off["diameter"], on["diameter"])

    def test_off_run_reuses_one_cache_entry(self):
        env = make_env("cartpole(horizon=10)")
        engine.clear_cache()
        run_decbyzpg(env, _cfg(), 3)
        n_off = engine.compile_count()
        run_decbyzpg(env, _cfg(seed=5), 3)      # seed is data, not program
        assert engine.compile_count() == n_off
        # telemetry is static: the on path is a *separate* entry and the
        # off entry is untouched
        run_decbyzpg(env, _cfg(telemetry=True), 3)
        assert engine.compile_count() == n_off + 1
        run_decbyzpg(env, _cfg(), 3)
        assert engine.compile_count() == n_off + 1

    def test_taps_silent_without_recorder_noise(self, capsys):
        # default recorder only prints the progress stream: a telemetry
        # run must not spam stdout through the tap streams
        env = make_env("cartpole(horizon=10)")
        run_decbyzpg(env, _cfg(telemetry=True, seed=7), 3)
        assert capsys.readouterr().out == ""


class TestForensicsOn:
    def test_jsonl_stream_under_sign_flip(self, tmp_path):
        env = make_env("cartpole(horizon=10)")
        path = str(tmp_path / "metrics.jsonl")
        with obs.telemetry(obs.JsonlSink(path)):
            out = run_decbyzpg(env, _cfg(telemetry=True, seed=4), 5)
        taps = [json.loads(l) for l in open(path)
                if json.loads(l)["stream"] == "decbyzpg"]
        assert len(taps) == 5
        assert all("diameter" in r and "grad_norm" in r
                   and "rejected" in r for r in taps)
        # the stream replays the stacked histories, in order
        np.testing.assert_allclose([r["diameter"] for r in taps],
                                   np.asarray(out["diameter"]), rtol=1e-6)

    def test_confusion_tally_and_summary_recall(self):
        # sign_flip rescales by -4x: krum reliably rejects the Byzantine
        # agent, so recall on the true set must be high
        exp = Experiment(algo="decbyzpg", env="cartpole(horizon=10)", T=4,
                         seeds=2, K=4, n_byz=1, attack="sign_flip",
                         aggregator="krum", N=4, B=2, kappa=2,
                         hidden=(4,), telemetry=True)
        summ = exp.summary()["base"]
        assert 0.0 <= summ["aggregator_precision"] <= 1.0
        assert summ["aggregator_recall"] >= 0.5
        res = exp.run()
        conf = next(iter(res.items()))[1]["aggregator_confusion"]
        assert conf["tp"] + conf["fn"] == conf["rounds"] * conf["n_byz"]

    def test_summary_without_telemetry_has_no_forensics(self):
        exp = Experiment(algo="decbyzpg", env="cartpole(horizon=10)", T=3,
                         seeds=2, K=3, n_byz=1, attack="sign_flip",
                         N=4, B=2, kappa=2, hidden=(4,))
        summ = exp.summary()["base"]
        assert "aggregator_precision" not in summ

    def test_confusion_tally_counts(self):
        rej = np.array([[True, False, False], [False, False, True]])
        c = obs.confusion_tally(rej, n_byz=1)
        assert (c["tp"], c["fp"], c["fn"], c["tn"]) == (1, 1, 1, 3)
        assert c["precision"] == 0.5 and c["recall"] == 0.5
        z = obs.confusion_tally(np.zeros((4, 3), bool), n_byz=0)
        assert z["precision"] == 0.0 and z["recall"] == 0.0


class TestRejectionMask:
    def _stack(self):
        # agent 0 is a gross outlier of an otherwise tight cluster
        x = np.ones((5, 8), np.float32)
        x += np.arange(5, dtype=np.float32)[:, None] * 1e-3
        x[0] = 100.0
        return jnp.asarray(x)

    @pytest.mark.parametrize("spec", ["krum", "trimmed_mean", "rfa",
                                      "cwmed"])
    def test_outlier_rejected(self, spec):
        mask = np.asarray(rejection_mask(spec, self._stack(), 1))
        assert mask.tolist() == [True, False, False, False, False]

    def test_cardinality_pinned_to_n_byz(self):
        mask = np.asarray(rejection_mask("krum", self._stack(), 2))
        assert int(mask.sum()) == 2 and bool(mask[0])

    def test_n_byz_zero_rejects_nobody(self):
        mask = np.asarray(rejection_mask("krum", self._stack(), 0))
        assert not mask.any()

    def test_scores_jit_and_vmap(self):
        x = self._stack()
        s = jax.jit(lambda a: suspicion_scores("trimmed_mean", a, 1))(x)
        assert s.shape == (5,) and float(s[0]) == max(map(float, s))
        batched = jax.vmap(lambda a: rejection_mask("krum", a, 1))(
            jnp.stack([x, x]))
        assert np.asarray(batched).shape == (2, 5)


class TestHostPlane:
    def test_ring_buffer_bounded(self):
        rb = obs.RingBuffer(capacity=3)
        for i in range(5):
            rb.append({"i": i})
        assert len(rb) == 3 and rb.dropped == 2
        assert rb.latest()["i"] == 4

    def test_capture_and_streams(self):
        with obs.capture() as sink:
            obs.record("a", x=1)
            obs.record("b", x=2)
        assert {r["stream"] for r in sink.records} == {"a", "b"}
        with obs.capture("a") as sink:
            obs.record("a", x=1)
            obs.record("b", x=2)
        assert [r["stream"] for r in sink.records] == ["a"]

    def test_telemetry_scope_restores_enabled(self):
        assert not obs.enabled()
        with obs.telemetry():
            assert obs.enabled()
        assert not obs.enabled()

    def test_progress_prints(self, capsys):
        obs.progress("hello world", step=3)
        assert "hello world" in capsys.readouterr().out

    def test_stdout_sink_filters_streams(self, capsys):
        s = obs.StdoutProgressSink()
        s.emit({"stream": "decbyzpg", "t": 0})
        assert capsys.readouterr().out == ""
        s.emit({"stream": "progress", "message": "msg"})
        assert "msg" in capsys.readouterr().out
        everything = obs.StdoutProgressSink(streams=None)
        everything.emit({"stream": "decbyzpg", "t": 0})
        assert "decbyzpg" in capsys.readouterr().out

    def test_jsonl_sink_plain_python(self, tmp_path):
        path = str(tmp_path / "x.jsonl")
        sink = obs.JsonlSink(path)
        sink.emit({"stream": "s", "arr": np.arange(3),
                   "scalar": np.float32(1.5)})
        sink.close()
        rec = json.loads(open(path).read())
        assert rec["arr"] == [0, 1, 2] and rec["scalar"] == 1.5

    def test_engine_cache_events(self):
        engine.clear_cache()
        with obs.capture("engine.cache") as sink:
            engine.compiled("k1", lambda: "fn")
            engine.compiled("k1", lambda: "fn")
        events = [r["event"] for r in sink.records]
        assert events == ["miss", "hit"]

    def test_tracer_chrome_format(self, tmp_path):
        tr = obs.Tracer()
        with tr.span("phase", n=3):
            pass
        tr.instant("marker")
        doc = tr.to_chrome()
        assert {e["ph"] for e in doc["traceEvents"]} == {"X", "i"}
        x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert x["name"] == "phase" and x["dur"] >= 0 \
            and x["args"] == {"n": 3}
        path = str(tmp_path / "trace.json")
        tr.to_chrome(path)
        assert json.load(open(path))["displayTimeUnit"] == "ms"

    def test_host_span_noop_when_disabled(self):
        tr = obs.get_tracer()
        tr.clear()
        with obs.host_span("nope"):
            pass
        assert tr.events == []
        with obs.telemetry():
            with obs.host_span("yes"):
                pass
        assert [e["name"] for e in tr.events] == ["yes"]
        tr.clear()


class TestDispatchCounters:
    def test_resolve_backend_tallies(self):
        from repro.kernels.dispatch import get_kernel
        k = get_kernel("krum_score")
        dispatch.reset_dispatch_counts()
        x = jnp.ones((4, 8))
        k(x, 2)
        counts = dispatch.dispatch_counts()
        assert sum(counts.values()) == 1
        ((name, backend, reason),) = counts
        assert name == "krum_score" and backend in dispatch.BACKENDS
        assert reason == "auto"
        k(x, 2, backend="jnp")
        assert dispatch.dispatch_counts()[
            ("krum_score", "jnp", "call")] == 1
        with dispatch.use_backend("jnp"):
            k(x, 2)
        assert dispatch.dispatch_counts()[
            ("krum_score", "jnp", "global")] == 1
        dispatch.reset_dispatch_counts()
        assert dispatch.dispatch_counts() == {}

    def test_manifest_includes_counters(self):
        m = obs.build_manifest(extra={"note": "t"})
        assert m["jax_version"] == jax.__version__
        assert "kernel_dispatch_counts" in m
        assert m["compiled_loop_cache_entries"] == engine.compile_count()
        assert m["note"] == "t"
