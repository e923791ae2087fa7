"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Two stages:

* :func:`extract` takes from it the device operations of the chips in
  use (planes ``/device:TPU:<n>``, line ``XLA Ops``: the HLO
  instruction's text, start and duration) and the benchmark's own
  host spans (``bench.*`` annotations on the host plane), as plain
  lists on one clock.
* :func:`reduce` turns those lists into the traced window (first span
  start to last span end), the time in which some operation ran on each
  chip (the union of their intervals), device time per operation, and
  the idle gaps, each labelled by the benchmark span the host was in at
  the gap's middle.

A trace with no device operations for the chips in use is an error.
Only a caller that asks for it with ``host_ops=True`` (the tests, on the
CPU, where operations run on host threads and carry an ``hlo_op`` stat)
gets those host operations in their place.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: HLO opcodes whose device event spans the ops of their body
CONTROL_FLOW = frozenset({"while", "conditional", "call"})


def parse_hlo(text: str) -> tuple:
    """(instruction, opcode, short form) of a TPU op event's name, which
    is the HLO instruction's text: ``%name = shape opcode(operands), ...``.
    A name in another form is its own instruction, with no opcode."""
    m = re.match(r"%?([^\s=]+) = ", text)
    if not m:
        return text, "", text
    rest = text[m.end():]
    if rest.startswith("("):                 # a tuple shape
        depth = 0
        for k, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[:k + 1], rest[k + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0]
    if len(shape) > 48:
        shape = shape[:45] + "..."
    return m.group(1), opcode, f"{m.group(1)} {opcode} {shape}"


@dataclasses.dataclass
class Op:
    name: str
    chip: int
    start_ns: float
    dur_ns: float

    def __post_init__(self):
        self.instr, self.opcode, self.short = parse_hlo(self.name)


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str, chips: int, host_ops: bool = False) -> tuple:
    """(device ops of chips 0..chips-1, benchmark host spans); with
    ``host_ops``, a trace with no device plane gives its host threads'
    XLA operations instead."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, on_host = [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append(Op(e.name, chip, e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns, e.duration_ns))
                    elif e.duration_ns > 0:
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            on_host.append(Op(
                                e.name, int(st.get("device_ordinal", 0)),
                                e.start_ns, e.duration_ns))
    if not ops and host_ops:
        ops = [o for o in on_host if o.chip < chips]
    found = {o.chip for o in ops}
    if found != set(range(chips)):
        missing = sorted(set(range(chips)) - found)
        raise ValueError(f"{path}: no device operations for chips "
                         f"{missing}")
    return ops, spans


def union_ns(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list:
    """(start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                  # mean over the chips in use
    op_s: dict                     # op -> device seconds, all chips, with
    #                                control flow left out (its body's ops
    #                                are there)
    ops: list                      # the Op records inside the window
    gaps: list                     # (label, seconds) of each idle gap
    chips: int

    def time_of(self, instrs) -> float:
        """Device seconds, summed over chips, of the ops whose HLO
        instruction is one of ``instrs``."""
        instrs = set(instrs)
        return sum(o.dur_ns for o in self.ops if o.instr in instrs) / 1e9

    def breakdown(self, n: int = 10) -> dict:
        top_ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        top_gaps = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return {"device_ops": [[k, v] for k, v in top_ops],
                "idle_gaps": [[k, v] for k, v in top_gaps]}


def label_at(spans: list, t: float) -> str:
    """The innermost benchmark span that holds time t, else 'other'."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.start_ns + s.dur_ns and (
                best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "other"


def reduce(ops: list, spans: list, chips: int) -> Reduced:
    if not spans:
        raise ValueError("the trace holds no benchmark spans")
    lo = min(s.start_ns for s in spans)
    hi = max(s.start_ns + s.dur_ns for s in spans)
    inside = [o for o in ops
              if o.start_ns < hi and o.start_ns + o.dur_ns > lo]
    busy, gaps = 0.0, []
    for chip in range(chips):
        iv = [(max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi))
              for o in inside if o.chip == chip]
        busy += union_ns(iv)
        gaps += [(label_at(spans, (a + b) / 2), (b - a) / 1e9)
                 for a, b in gaps_ns(iv, lo, hi)]
    op_s = {}
    for o in inside:
        if o.opcode not in CONTROL_FLOW:
            op_s[o.short] = op_s.get(o.short, 0.0) + o.dur_ns / 1e9
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / chips / 1e9,
                   op_s=op_s, ops=inside, gaps=gaps,
                   chips=chips)


def reduce_dir(trace_dir: str, chips: int, host_ops: bool = False
               ) -> Reduced:
    return reduce(*extract(find_xplane(trace_dir), chips, host_ops), chips)
