#!/usr/bin/env python3
"""Device time by program phase, set-up compile time and GC stalls, from
one traced run of a cell.

    python3 bench/phases.py --workload <cell> --seed <n> [--seconds 2]

The system names every phase of its programs (``repro.obs.named_phase``:
``decbyzpg.rollout``, ``decbyzpg.gradient``, ``decbyzpg.aggregate``,
``decbyzpg.agree`` with ``agree.round`` inside, ...); XLA keeps the name
in the ``op_name`` metadata of each compiled instruction. The trace
names a device operation by its HLO instruction only, so the compiled
text of the window program is what says which phase an operation
belongs to, as it says which kernel (``bench/kernel_ops.py``):

* an instruction goes to the innermost phase on its own ``op_name``
  (transform wrappers such as ``vmap(...)`` or ``transpose(jvp(...))``
  included); a fusion's metadata is its root's;
* an instruction with no ``op_name`` of its own (layout copies,
  bitcasts) takes the phase of the computation it ``calls``, root
  first, then the computation's other instructions in order;
* one still without a phase that lives in a loop's body or condition
  (a loop-carry copy) takes the phase of the loop instruction;
* what is left is unattributed.

Operations are keyed by program as well: on a chip, the ``XLA Modules``
line of the device plane says which program (``jit_<fn>(<id>)``) ran
when; on the CPU each operation carries its module and program id. The
window program is the program of the compiled text's module name that
took the most device time; the operations of every other program are
reported as such, never in the window's phases.

A window program served from a persistent cache filled by a build
without phase names carries none: JAX's cache key strips metadata. So
this tool sets ``jax_compilation_cache_include_metadata_in_key``, and a
compiled text with no phase name gives no phase seconds (said on
standard error), never zeros.

The run sets the cell up as ``bench/run.py`` does and records the set-up
split (imports, TPU start, tracing and lowering, backend compiles and
cache loads, the rest), then measures four stretches of ``--seconds``:
traced with host instrumentation on (``repro.obs``: host spans and a
``host.gc`` span around each garbage collection), traced with it off,
and untraced with it off and on, on the TPU (a CPU is refused, exit 3).
It writes
``results/bench/<cell>/phases-<seed>.json`` and prints one JSON line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import trace  # noqa: E402
from repro.obs.trace import GC_SPAN  # noqa: E402

#: a phase name on an op_name path: ``<program>.<phase>``
PHASE = re.compile(r"\b(?:decbyzpg|byzpg|fed|agree)\.[a-z_]+")
#: host spans of the program and of the benchmark
SPAN_PREFIXES = ("bench.", "host.", "engine.", "sweep.", "run_grid.",
                 "train.", "serve.")
_HEADER = re.compile(r"^(?:ENTRY )?%?([^\s(]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
#: the computations a loop, conditional or call runs as part of itself
_BODIES = re.compile(r"(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([^\s,}]+)|"
                     r"branch_computations=\{([^}]*)\}")


def instruction_table(text: str) -> dict:
    """HLO instruction -> (opcode, innermost phase or None) for every
    instruction of a compiled program's text."""
    comps, own, calls, opcode, home, caller = {}, {}, {}, {}, {}, {}
    current = None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith(" "):
            current = h.group(1)
            comps.setdefault(current, [])
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        name = m.group(2)
        if m.group(1):
            comps[current].insert(0, name)   # the root, looked at first
        else:
            comps[current].append(name)
        home[name] = current
        _, opcode[name], _ = trace.parse_hlo(line.strip().removeprefix(
            "ROOT "))
        op_name = re.search(r'op_name="([^"]*)"', line)
        phases = PHASE.findall(op_name.group(1)) if op_name else []
        own[name] = phases[-1] if phases else None
        called = re.search(r"calls=%?([^\s,}]+)", line)
        if op_name is None and called:
            calls[name] = called.group(1)
        for body, branches in _BODIES.findall(line):
            for comp in ([body] if body else
                         re.findall(r"%?([^\s,]+)", branches)):
                caller.setdefault(comp, name)

    memo: dict = {}

    def of_computation(comp: str, seen=()):
        if comp in memo:
            return memo[comp]
        found = None
        for name in comps.get(comp, ()):            # root first
            found = own_or_called(name, seen + (comp,))
            if found:
                break
        memo[comp] = found
        return found

    def own_or_called(name: str, seen=()):
        if own.get(name):
            return own[name]
        comp = calls.get(name)
        if comp is None or comp in seen:
            return None
        return of_computation(comp, seen)

    def phase_of(name: str, seen=()):
        found = own_or_called(name)
        comp = home[name]
        if found or comp not in caller or comp in seen:
            return found
        return phase_of(caller[comp], seen + (comp,))

    return {name: (opcode[name], phase_of(name)) for name in own}


def module_name(text: str) -> str:
    m = re.search(r"^HloModule ([^\s,]+)", text, re.M)
    if m is None:
        raise ValueError("no HloModule line in the compiled text")
    return m.group(1)


@dataclasses.dataclass
class Program:
    name: str                  # jit_<fn>(<id>)
    chip: int
    start_ns: float
    dur_ns: float


def extract_programs(path: str, chips: int, host_ops: bool = False
                     ) -> tuple:
    """(program runs, host spans) of a profiler trace: the ``XLA
    Modules`` events of chips 0..chips-1 (with ``host_ops``, one run per
    host-thread XLA operation, named by its module and program id), and
    every host span named with one of :data:`SPAN_PREFIXES`."""
    from jax.profiler import ProfileData
    progs, spans, on_host = [], [], []
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    progs += [Program(e.name, int(m.group(1)), e.start_ns,
                                      e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append(trace.Span(e.name, e.start_ns,
                                                e.duration_ns))
                    elif host_ops and e.duration_ns > 0:
                        st = dict(e.stats)
                        if "hlo_module" in st:
                            on_host.append(Program(
                                f"{st['hlo_module']}({st['program_id']})",
                                int(st.get("device_ordinal", 0)),
                                e.start_ns, e.duration_ns))
    return (progs or on_host), spans


def program_of(ops: list, progs: list) -> list:
    """The program each op ran in (None when no run holds its start)."""
    by_chip = {}
    for p in sorted(progs, key=lambda p: p.start_ns):
        by_chip.setdefault(p.chip, []).append(p)
    starts = {c: [p.start_ns for p in ps] for c, ps in by_chip.items()}
    out = []
    for o in ops:
        ps = by_chip.get(o.chip, [])
        k = bisect.bisect_right(starts.get(o.chip, []), o.start_ns) - 1
        hit = ps[k] if k >= 0 and o.start_ns <= ps[k].start_ns \
            + ps[k].dur_ns else None
        out.append(hit.name if hit else None)
    return out


def phase_seconds(ops: list, progs: list, text: str):
    """Device seconds of the window program's operations by innermost
    phase; None when the compiled text names no phase."""
    table = instruction_table(text)
    if not any(phase for _, phase in table.values()):
        return None
    prefix = module_name(text) + "("
    names = program_of(ops, progs)
    leaf = [(o, n) for o, n in zip(ops, names)
            if table.get(o.instr, (o.opcode, None))[0]
            not in trace.CONTROL_FLOW]
    per_program: dict = {}
    for o, n in leaf:
        per_program[n] = per_program.get(n, 0.0) + o.dur_ns / 1e9
    ours = [n for n in per_program if n and n.startswith(prefix)]
    if not ours:
        raise ValueError(f"no run of {prefix[:-1]} in the trace")
    window = max(ours, key=per_program.get)
    phases, unattributed = {}, 0.0
    for o, n in leaf:
        if n != window:
            continue
        phase = table.get(o.instr, (None, None))[1]
        if phase is None:
            unattributed += o.dur_ns / 1e9
        else:
            phases[phase] = phases.get(phase, 0.0) + o.dur_ns / 1e9
    return {"program": window, "op_s": per_program[window],
            "phases_s": phases, "unattributed_s": unattributed,
            "other_programs_s": {str(n): s for n, s in per_program.items()
                                 if n != window}}


def group(phase: str) -> str:
    """The metric a phase feeds: ``decbyzpg.rollout`` -> rollout; the
    gossip rounds count to agreement."""
    return "agree" if phase == "agree.round" else phase.split(".", 1)[1]


def gc_idle_s(ops: list, spans: list, chips: int) -> float:
    """Device-idle seconds inside the traced window (the benchmark's
    spans) that a ``host.gc`` span covers, averaged over the chips."""
    bench = [s for s in spans if s.name.startswith("bench.")]
    lo = min(s.start_ns for s in bench)
    hi = max(s.start_ns + s.dur_ns for s in bench)
    gcs = [(s.start_ns, s.start_ns + s.dur_ns) for s in spans
           if s.name == GC_SPAN]
    covered = 0.0
    for chip in range(chips):
        iv = [(o.start_ns, o.start_ns + o.dur_ns) for o in ops
              if o.chip == chip]
        covered += trace.union_ns(
            (max(a, c), min(b, d)) for a, b in trace.gaps_ns(iv, lo, hi)
            for c, d in gcs if min(b, d) > max(a, c))
    return covered / chips / 1e9


def idle_gaps(ops: list, spans: list, chips: int) -> list:
    """(innermost host span at the middle, seconds) of each idle gap in
    the traced window; the program's spans are labels too."""
    bench = [s for s in spans if s.name.startswith("bench.")]
    lo = min(s.start_ns for s in bench)
    hi = max(s.start_ns + s.dur_ns for s in bench)
    out = []
    for chip in range(chips):
        iv = [(o.start_ns, o.start_ns + o.dur_ns) for o in ops
              if o.chip == chip]
        out += [(trace.label_at(spans, (a + b) / 2), (b - a) / 1e9)
                for a, b in trace.gaps_ns(iv, lo, hi)]
    return out


# -- one traced run of a cell ------------------------------------------------

def _gc_collections() -> int:
    return sum(s["collections"] for s in gc.get_stats())


def _rate(driver, seconds: float, obs_on: bool, trace_dir=None) -> dict:
    from bench import run
    from repro import obs
    n0 = _gc_collections()
    with obs.telemetry() if obs_on else contextlib.nullcontext():
        stats = (run.traced_measure(driver, seconds, trace_dir)
                 if trace_dir else driver.measure(seconds))
    return {"host_obs": obs_on, "traced": trace_dir is not None,
            "iters_per_s": stats["row_iters"] / stats["elapsed_s"],
            "row_iters": stats["row_iters"],
            "gc_collections": _gc_collections() - n0, "stats": stats}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from bench import run
    cell = run.resolve_cell(run.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), args.workload)
    import jax
    t_jax = time.perf_counter()
    from repro import obs
    counter = obs.compile_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    chips = int(cell["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"phases: the cell needs {chips} TPU chips, JAX sees "
              f"{len(devices)} {devices[0].platform} devices",
              file=sys.stderr)
        return 3
    run.enable_compile_cache()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    driver = run.load_module(cell["paths"]["driver"], "bench_driver").Driver(
        cell["config"], run.load_json(cell["paths"]["traffic"]), args.seed)
    driver.setup()
    t_setup = time.perf_counter()
    setup = counter.snapshot()

    out_dir = os.path.join(run.RESULTS, args.workload)
    os.makedirs(out_dir, exist_ok=True)
    on_dir, off_dir = (os.path.join(out_dir, "trace-obs-" + s)
                       for s in ("on", "off"))
    runs = [_rate(driver, args.seconds, True, on_dir),
            _rate(driver, args.seconds, False, off_dir),
            _rate(driver, args.seconds, False),
            _rate(driver, args.seconds, True)]
    text = driver.win.lower(driver.carry, driver.vals, driver.seeds,
                            jax.numpy.arange(driver.W)).compile().as_text()

    path = trace.find_xplane(on_dir)
    ops, _ = trace.extract(path, chips)
    progs, spans = extract_programs(path, chips)
    reduced = trace.reduce(ops, [s for s in spans
                                 if s.name.startswith("bench.")], chips)
    phases = phase_seconds(reduced.ops, progs, text)
    row_iters = runs[0]["row_iters"]
    metrics = {}
    if phases is None:
        print("phases: the compiled window program carries no phase "
              "names (a stale persistent cache?)", file=sys.stderr)
    else:
        by_group: dict = {}
        for phase, s in phases["phases_s"].items():
            by_group[group(phase)] = by_group.get(group(phase), 0.0) + s
        phases["groups_s"] = by_group
        phases["attributed_share"] = 1.0 - phases["unattributed_s"] \
            / phases["op_s"]
        for g in ("rollout", "gradient", "aggregate", "agree"):
            if g in by_group:
                metrics[f"{g}_us_per_iter"] = 1e6 * by_group[g] / row_iters
    gc_s = gc_idle_s(reduced.ops, spans, chips)
    gaps = idle_gaps(reduced.ops, spans, chips)
    idle_by_span: dict = {}
    for label, seconds in gaps:
        idle_by_span[label] = idle_by_span.get(label, 0.0) + seconds
    metrics["gc_idle_share"] = 100.0 * gc_s / reduced.window_s
    metrics["setup_trace_s"] = setup.trace_s + setup.lower_s
    metrics["setup_compile_s"] = setup.compile_s
    record = {
        "workload": args.workload, "seed": args.seed,
        "device": run.device_report(devices[:chips]),
        "setup": {"setup_s": t_setup - T_START,
                  "imports_s": t_jax - T_START,
                  "tpu_start_s": t_devices - t_jax,
                  **dataclasses.asdict(setup)},
        "window_s": reduced.window_s, "busy_s": reduced.busy_s,
        "row_iters": row_iters, "phases": phases, "gc_idle_s": gc_s,
        "gc_spans": sum(s.name == GC_SPAN for s in spans),
        "idle_by_span": idle_by_span,
        "longest_gaps": sorted(gaps, key=lambda g: -g[1])[:5],
        "rates": [{k: v for k, v in r.items() if k != "stats"}
                  for r in runs],
        "metrics": metrics}
    with open(os.path.join(out_dir, f"phases-{args.seed}.json"), "w") as f:
        json.dump(record, f)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
