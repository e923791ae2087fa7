"""The trace reduction: busy time, idle gaps and their labels, per-op
time; on hand-made intervals, on a trace recorded here on the CPU, and
on a trimmed trace recorded on a TPU v5e."""
import os

import pytest

from bench import trace

HERE = os.path.dirname(__file__)


def op(name, start, dur, chip=0):
    return trace.Op(name, chip, start, dur)


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert trace.union_ns(iv) == 30
    assert trace.gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)]
    assert trace.gaps_ns(iv, -5, 25) == [(-5, 0), (20, 25)]
    assert trace.gaps_ns([], 0, 7) == [(0, 7)]


def test_reduce_by_hand():
    spans = [trace.Span("bench.dispatch", 0, 10),
             trace.Span("bench.readback", 10, 90),
             trace.Span("bench.host_prep", 100, 20),
             trace.Span("bench.dispatch", 120, 30)]
    ops = [op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 5,
              40),
           op("%wsum.2 = f32[1,512]{1,0} custom-call(f32[8,512]{1,0} %x)",
              50, 30),
           op("%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
              120, 25),
           op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 130,
              10),
           op("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", 500, 10)]
    r = trace.reduce(ops, spans, 1)
    assert r.window_s == pytest.approx(150e-9)
    # the while loop counts as busy but not as an op of its own
    assert r.busy_s == pytest.approx(95e-9)
    assert r.op_s == pytest.approx({"fusion.1 fusion f32[8]{0}": 50e-9,
                                    "wsum.2 custom-call f32[1,512]{1,0}":
                                        30e-9})
    # gaps 0-5 (dispatch), 45-50 (readback), 80-120 (middle 100 lies in
    # host_prep and readback; host_prep is the shorter), 145-150
    assert sorted(r.gaps) == pytest.approx(sorted([
        ("bench.dispatch", 5e-9), ("bench.readback", 5e-9),
        ("bench.host_prep", 40e-9), ("bench.dispatch", 5e-9)]))
    assert r.time_of({"wsum.2"}) == pytest.approx(30e-9)
    b = r.breakdown(2)
    assert b["device_ops"][0][0].startswith("fusion.1")
    assert len(b["idle_gaps"]) == 2


def test_busy_is_averaged_over_chips():
    spans = [trace.Span("bench.dispatch", 0, 100)]
    ops = [op("a.1", 0, 100, chip=0), op("a.1", 0, 50, chip=1)]
    r = trace.reduce(ops, spans, 2)
    assert r.busy_s == pytest.approx(75e-9)
    assert r.op_s["a.1"] == pytest.approx(150e-9)


def test_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            y = f(x)
        with jax.profiler.TraceAnnotation("bench.readback"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no device operations"):
        trace.reduce_dir(str(tmp_path), 1)
    r = trace.reduce_dir(str(tmp_path), 1, host_ops=True)
    assert 0 < r.busy_s <= r.window_s
    assert r.op_s and all(v > 0 for v in r.op_s.values())
    assert {g[0] for g in r.gaps} <= {"bench.dispatch", "bench.readback",
                                      "other"}


def test_a_trimmed_chip_trace():
    """7 ms around the boundary of two windows of the fleet-k13-mean
    program on one TPU v5 lite. The expected numbers were counted from
    the same file by a second, independent route (a 1 ns occupancy
    grid)."""
    path = os.path.join(HERE, "data", "fleet-mean-v5e.xplane.pb")
    r = trace.reduce(*trace.extract(path, 1), 1)
    assert r.window_s == pytest.approx(7.0e-3, rel=1e-6)
    assert r.busy_s == pytest.approx(5.069377e-3, rel=1e-5)
    assert len(r.ops) == 1567
    # the device waits between the windows: it ran dry before the host
    # saw the readback finish, and stays idle through the next dispatch
    label, seconds = max(r.gaps, key=lambda g: g[1])
    assert label == "bench.dispatch"
    assert seconds == pytest.approx(1.930602e-3, rel=1e-5)
    (op1, t1), (op2, t2) = r.breakdown(2)["device_ops"]
    assert {op1.split()[0], op2.split()[0]} == {"fusion.439", "fusion.440"}
    assert t1 == pytest.approx(2.918e-3, rel=1e-3)
