"""Device time by program phase (``bench/phases.py``): the instruction
table on hand-made HLO, the reduction of a trace of the fleet window
program recorded here on the CPU, and the GC cover of idle gaps."""
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import phases, trace
from repro import obs
from repro.core import engine
from repro.core.decbyzpg import DecByzPGConfig
from repro.rl.envs import make_env

HLO = """HloModule jit_one, entry_computation_layout={()->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %sin.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(one)/decbyzpg.estimate/vmap(decbyzpg.rollout)/sin"}
  ROOT %mul.1 = f32[8]{0} multiply(%sin.1, %sin.1), metadata={op_name="jit(one)/decbyzpg.estimate/transpose(jvp(decbyzpg.gradient))/mul"}
}

%bitcast_fusion.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %bitcast.2 = f32[8]{0} bitcast(%param_0.1)
}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %cos.3 = f32[8]{0} cosine(%param_0.2), metadata={op_name="jit(one)/decbyzpg.agree/agree.round/cos"}
  ROOT %copy.3 = f32[8]{0} copy(%cos.3)
}

%body.5 (carry: f32[8]) -> f32[8] {
  %carry = f32[8]{0} parameter(0)
  ROOT %copy.5 = f32[8]{0} copy(%carry)
}

%cond.6 (carry.1: f32[8]) -> pred[] {
  %carry.1 = f32[8]{0} parameter(0)
  ROOT %constant.6 = pred[] constant(false)
}

ENTRY %main.4 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(one)/decbyzpg.estimate/transpose(jvp(decbyzpg.gradient))/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%bitcast_fusion.2
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %while.7 = f32[8]{0} while(%fusion.3), condition=%cond.6, body=%body.5, metadata={op_name="jit(one)/decbyzpg.estimate/vmap(decbyzpg.rollout)/while"}
  ROOT %copy.4 = f32[8]{0} copy(%while.7)
}
"""


def test_instruction_table_by_hand():
    t = phases.instruction_table(HLO)
    # own metadata: the innermost phase, through transform wrappers
    assert t["sin.1"] == ("sine", "decbyzpg.rollout")
    assert t["fusion.1"] == ("fusion", "decbyzpg.gradient")
    # no metadata: the called computation's root has none either, so an
    # instruction before it decides
    assert t["fusion.3"] == ("fusion", "agree.round")
    # no metadata inside a loop's body: the loop's phase
    assert t["copy.5"] == ("copy", "decbyzpg.rollout")
    assert t["while.7"] == ("while", "decbyzpg.rollout")
    # a bare bitcast and a copy with no metadata at the top stay
    # unattributed
    assert t["fusion.2"] == ("fusion", None)
    assert t["copy.4"] == ("copy", None)
    assert phases.module_name(HLO) == "jit_one"
    assert phases.group("decbyzpg.rollout") == "rollout"
    assert phases.group("agree.round") == "agree"


def test_gc_cover_and_labels_by_hand():
    spans = [trace.Span("bench.dispatch", 0, 10),
             trace.Span("bench.readback", 10, 90),
             trace.Span("host.gc", 50, 20)]
    ops = [trace.Op("%a.1 = f32[8]{0} add()", 0, 0, 40),
           trace.Op("%a.1 = f32[8]{0} add()", 0, 60, 40)]
    # idle 40-60, of which 50-60 under the collection
    assert phases.gc_idle_s(ops, spans, 1) == pytest.approx(10e-9)
    assert phases.idle_gaps(ops, spans, 1) == pytest.approx(
        [("host.gc", 20e-9)])


def _fleet(rows=2, W=2, T=4):
    cfg = DecByzPGConfig(K=4, n_byz=1, attack="large_noise",
                         aggregator="rfa", agreement="mda", N=8, B=2,
                         kappa=2, hidden=(16, 16))
    env = make_env("cartpole(horizon=25)")
    (static_cfg, names), members = next(iter(
        engine.lane_groups([("s", cfg)]).items()))
    vals, seeds = engine.lane_operands(
        members, jnp.arange(rows, dtype=jnp.int32), rows)
    init = engine.lane_init_loop(env, static_cfg, rows)
    win = engine.lane_window_loop(env, static_cfg, T, names, W, rows)
    return init, win, vals, seeds, np.arange(W)


@pytest.fixture(scope="module")
def fleet_trace(tmp_path_factory):
    """Three window calls of the fleet program, each followed by a call
    of the init program (another program, also ``jit_one``) and a
    collection inside the readback, with host instrumentation on."""
    tmp = str(tmp_path_factory.mktemp("trace"))
    init, win, vals, seeds, ts = _fleet()
    carry = init(seeds)
    jax.block_until_ready(win(carry, vals, seeds, ts))
    with obs.telemetry(), jax.profiler.trace(tmp):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = win(carry, vals, seeds, ts)
            with jax.profiler.TraceAnnotation("bench.readback"):
                jax.block_until_ready(out)
                gc.collect()
            with jax.profiler.TraceAnnotation("bench.host_prep"):
                jax.block_until_ready(init(seeds))
    text = win.lower(carry, vals, seeds, ts).compile().as_text()
    path = trace.find_xplane(tmp)
    ops, spans = trace.extract(path, 1, host_ops=True)
    progs, all_spans = phases.extract_programs(path, 1, host_ops=True)
    return trace.reduce(ops, spans, 1), progs, all_spans, text


def test_phases_of_a_cpu_trace(fleet_trace):
    reduced, progs, _, text = fleet_trace
    got = phases.phase_seconds(reduced.ops, progs, text)
    assert got["program"].startswith("jit_one(")
    assert got["unattributed_s"] <= 0.05 * got["op_s"]
    assert sum(got["phases_s"].values()) + got["unattributed_s"] \
        == pytest.approx(got["op_s"])
    assert {phases.group(p) for p in got["phases_s"]} >= {
        "rollout", "gradient", "aggregate", "update", "agree"}
    # the init program's operations are its own, not the window's
    (other, other_s), = got["other_programs_s"].items()
    assert other.startswith("jit_one(") and other != got["program"]
    assert other_s > 0
    assert not any(p.endswith(".init") for p in got["phases_s"])


def test_no_phase_names_reads_none(fleet_trace):
    reduced, progs, _, text = fleet_trace
    bare = re.sub(r'op_name="[^"]*"', 'op_name="jit(one)/sin"', text)
    assert phases.phase_seconds(reduced.ops, progs, bare) is None


def test_gc_spans_cover_idle_time(fleet_trace):
    reduced, _, spans, _ = fleet_trace
    assert sum(s.name == "host.gc" for s in spans) >= 3
    assert phases.gc_idle_s(reduced.ops, spans, 1) > 0
    assert "host.gc" in {label for label, _ in
                         phases.idle_gaps(reduced.ops, spans, 1)}
