"""repro.obs telemetry layer (DESIGN.md §8).

The contracts under test:

* zero-overhead-off: with ``telemetry=False`` (the default) the built
  programs hold no ``debug_callback`` in the jaxpr, identical
  compiled-loop cache keys, bit-identical histories;
* phase names: every program carries its phases in the compiled
  ``op_name`` metadata, and nothing else in the program changes;
* host plane: host spans, ``host.gc`` spans and ``--profile`` land in a
  ``jax.profiler`` trace; the compile counter tallies JAX's events;
* forensics-on: with ``telemetry=True`` a run under an attack yields the
  tap stream (per-iteration Δ₂ included), stacked ``grad_norm`` /
  ``rejected`` histories, and an ``aggregator_confusion`` tally whose
  precision/recall surface in ``Experiment.summary()``.
"""
import contextlib
import dataclasses
import gc
import glob
import json
import os
import re
import subprocess
import sys

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


#: every phase of the DecByzPG window program (DESIGN.md §8)
WINDOW_PHASES = ("decbyzpg.estimate", "decbyzpg.rollout",
                 "decbyzpg.gradient", "decbyzpg.aggregate",
                 "decbyzpg.update", "decbyzpg.agree", "agree.round",
                 "decbyzpg.diagnostics")


def _window_text(cfg, rows=2, W=2, T=4) -> str:
    """Compiled text of the lane window program (as the sweep service
    builds it) for one scenario of ``cfg``; the engine's program cache
    is cleared first, so the build traces the code as it is now."""
    env = make_env("cartpole(horizon=10)")
    engine.clear_cache()
    (static_cfg, names), members = next(iter(
        engine.lane_groups([("s", cfg)]).items()))
    vals, seeds = engine.lane_operands(
        members, jnp.arange(rows, dtype=jnp.int32), rows)
    carry = engine.lane_init_loop(env, static_cfg, rows)(seeds)
    win = engine.lane_window_loop(env, static_cfg, T, names, W, rows)
    text = win.lower(carry, vals, seeds, np.arange(W)).compile().as_text()
    engine.clear_cache()
    return text


def _instruction_lines(text: str) -> list:
    """The HLO instruction lines with their metadata stripped."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines() if " = " in line]


class TestPhaseNames:
    CFG = dict(attack="large_noise", aggregator="rfa", agreement="mda")

    def test_window_program_names_every_phase(self):
        text = _window_text(_cfg(**self.CFG))
        op_names = "\n".join(re.findall(r'op_name="([^"]*)"', text))
        missing = [p for p in WINDOW_PHASES if p not in op_names]
        assert not missing, missing

    def test_names_change_only_metadata(self, monkeypatch):
        named = _window_text(_cfg(**self.CFG))
        monkeypatch.setattr(obs, "named_phase",
                            lambda name: contextlib.nullcontext())
        plain = _window_text(_cfg(**self.CFG))
        assert not re.search(r'op_name="[^"]*decbyzpg\.', plain)
        assert _instruction_lines(named) == _instruction_lines(plain)


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

    def test_host_span_noop_when_disabled(self, tmp_path):
        assert isinstance(obs.host_span("nope"), contextlib.nullcontext)
        with jax.profiler.trace(str(tmp_path)):
            with obs.host_span("test.nope"):
                pass
            with obs.telemetry():
                with obs.host_span("test.yes", n=3):
                    pass
        names = _host_event_names(tmp_path)
        assert "test.yes" in names and "test.nope" not in names

    def test_gc_spans_only_while_enabled(self, tmp_path):
        with jax.profiler.trace(str(tmp_path / "off")):
            gc.collect()
        assert "host.gc" not in _host_event_names(tmp_path / "off")
        with obs.telemetry(), jax.profiler.trace(str(tmp_path / "on")):
            gc.collect()
        assert "host.gc" in _host_event_names(tmp_path / "on")
        assert not any(getattr(cb, "__module__", "") == "repro.obs.trace"
                       for cb in gc.callbacks)

    def test_compile_counter_sees_compile_then_cache_hit(self, tmp_path):
        """In a fresh process with a persistent cache in ``tmp_path``: the
        first call compiles, the second (in-memory caches cleared) loads
        the executable from the cache. The traces of the inner jitted
        function count, but their seconds lie inside the outer trace's,
        so the seconds add up to no more than the call took."""
        code = (
            "import json, time, jax, jax.numpy as jnp\n"
            "from repro import obs\n"
            "for k, v in (('jax_compilation_cache_dir', %r),\n"
            "             ('jax_persistent_cache_min_compile_time_secs', 0),\n"
            "             ('jax_persistent_cache_min_entry_size_bytes', 0)):\n"
            "    jax.config.update(k, v)\n"
            "c = obs.compile_counter()\n"
            "g = jax.jit(lambda x: jnp.tanh(x @ x))\n"
            "f = lambda x: g(g(g(x))).sum()\n"
            "x = jax.device_put(1.0 + jnp.arange(4096.0).reshape(64, 64))\n"
            "s0, t0 = c.snapshot(), time.time()\n"
            "jax.jit(f)(x).block_until_ready()\n"
            "s1, wall = c.snapshot(), time.time() - t0\n"
            "jax.clear_caches()\n"
            "jax.jit(f)(x).block_until_ready()\n"
            "s2 = c.snapshot()\n"
            "print(json.dumps([vars(s1 - s0), vars(s2 - s1), wall]))\n"
        ) % str(tmp_path / "cache")
        first, second, wall = json.loads(
            _run_python(code).splitlines()[-1])
        assert first["backend_compiles"] == 1 and first["cache_hits"] == 0
        assert first["traces"] >= 2 and first["lowerings"] == 1
        assert first["trace_s"] + first["lower_s"] + first["compile_s"] \
            <= wall
        assert second["backend_compiles"] == 1 and second["cache_hits"] == 1
        for d in (first, second):
            assert d["trace_s"] > 0 and d["lower_s"] > 0 \
                and d["compile_s"] > 0

    def test_profile_flag_writes_a_profiler_trace(self, tmp_path):
        out = tmp_path / "run"
        _run_python(
            "from repro.launch import train\n"
            "train.main()\n",
            "--arch", "llama3.2-1b", "--reduced", "--agents", "2",
            "--steps", "2", "--window", "2", "--seq", "16", "--batch", "1",
            "--telemetry-out", str(out), "--profile",
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        assert glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
        assert "train.window" in _host_event_names(out)
        assert (out / "manifest.json").is_file()


def _host_event_names(trace_dir) -> set:
    """Names of the events on the host planes of the newest profiler
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def _run_python(code: str, *args, **env_extra) -> str:
    """Run ``code`` in a fresh CPU process with ``src`` on the path;
    returns its standard output."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra,
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run([sys.executable, "-c", code, *args], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


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
