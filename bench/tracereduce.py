"""Reduce a profiler trace to the numbers the per-layer metrics read.

A ``Trace`` keeps two things from the ``.xplane.pb`` that
``jax.profiler`` writes, on the one clock the profiler gives them:

* ``ops``: the "XLA Ops" line of one TPU, each op as
  ``<instruction> <shape> <opcode>`` (``fusion.127 pred[93696] fusion``,
  ``segment_spmm_pallas.1 f32[131072,1] custom-call``).  On a v5e the
  trace gives an op no name stack, only its HLO text; a Pallas kernel's
  custom call takes its jitted wrapper's name.  Control flow
  (``while``, ``conditional``) encloses the ops it runs, so ops nest;
* ``host``: the events of the host thread that drove the run, the
  harness's own annotations (``bench.run``) among them.

Times are nanoseconds.  ``to_json`` / ``from_json`` keep a trace as plain
data, which is how the recorded trace the tests read is stored.
"""

from __future__ import annotations

import gzip
import json
import re
from dataclasses import asdict, dataclass, field

RUN_SPAN = "bench.run"


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def instruction(self) -> str:
        return self.name.split(" ", 1)[0]


@dataclass
class Trace:
    device: str
    ops: list[Event] = field(default_factory=list)
    host: list[Event] = field(default_factory=list)

    def runs(self) -> list[Event]:
        return [e for e in self.host if e.name == RUN_SPAN]

    def to_json(self, path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"device": self.device,
                       "ops": [asdict(e) for e in self.ops],
                       "host": [asdict(e) for e in self.host]}, f)

    @classmethod
    def from_json(cls, path) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["device"], [Event(**e) for e in d["ops"]],
                   [Event(**e) for e in d["host"]])


def op_label(hlo: str) -> str:
    """``%fusion.127 = pred[93696]{0:T(1024)} fusion(...), ...`` ->
    ``fusion.127 pred[93696] fusion``: the instruction, its shape without
    layout (``tuple`` for a tuple) and its opcode."""
    instr, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo[:80]
    opcode = re.search(r"\s([a-z][\w-]*)\(", rest)
    shape = "tuple" if rest.startswith("(") else re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{instr.lstrip('%')} {shape} {opcode.group(1) if opcode else '?'}"


def load_xplane(path, device: int = 0) -> Trace:
    """The ops of ``/device:TPU:<device>`` and the events of the host
    thread that holds the harness's run annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, host, plane_name = None, [], f"/device:TPU:{device}"
    for plane in data.planes:
        if plane.name == plane_name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Event(op_label(e.name), e.start_ns, e.duration_ns)
                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [Event(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if any(e.name == RUN_SPAN for e in events):
                    host = events
    if ops is None:
        raise RuntimeError(f"the trace has no XLA Ops line on {plane_name}")
    return Trace(plane_name, ops, host)


# -------------------------------------------------------------- intervals

def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the (merged) intervals cover."""
    return sum(e - s for s, e in clip(union(intervals), lo, hi))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in clip(union(intervals), lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


# --------------------------------------------------------------- reductions

def busy(ops, lo: float, hi: float) -> float:
    return covered([(e.start, e.end) for e in ops], lo, hi)


def named(ops, prefixes) -> list[Event]:
    """Ops whose instruction name starts with one of ``prefixes``."""
    prefixes = tuple(prefixes)
    return [e for e in ops if e.instruction.startswith(prefixes)]


def self_times(ops) -> list[tuple[Event, float]]:
    """Each op with its own time: its duration less that of the ops it
    encloses (a ``while`` less its body's ops)."""
    out, stack = [], []
    for e in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= e.dur
        stack.append([e, e.dur])
    return out + [tuple(x) for x in reversed(stack)]


def labels(host, points) -> dict[float, str]:
    """What the host thread was doing at each time in ``points``: the
    innermost host event that covers it, under the run span if one does.
    One sweep over the host events, which nest on one thread."""
    events = sorted(host, key=lambda e: (e.start, -e.dur))
    out, open_, i = {}, [], 0
    for at in sorted(points):
        while i < len(events) and events[i].start <= at:
            open_.append(events[i])
            i += 1
        open_ = [e for e in open_ if e.end > at]
        where = RUN_SPAN if any(e.name == RUN_SPAN for e in open_) else "between runs"
        inner = [e for e in open_ if e.name != RUN_SPAN]
        out[at] = f"{where} > {min(inner, key=lambda e: e.dur).name}" if inner else where
    return out


def idle_gaps(trace: Trace, lo: float, hi: float) -> list[tuple[str, float]]:
    """Every device-idle gap in [lo, hi], longest first, as (label, ns)."""
    found = gaps([(e.start, e.end) for e in trace.ops], lo, hi)
    named = labels(trace.host, [(s + e) / 2 for s, e in found])
    return sorted(((named[(s + e) / 2], e - s) for s, e in found), key=lambda x: -x[1])


def top_ops(ops, k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` ops with the most self time, summed over their calls."""
    total: dict[str, float] = {}
    for e, own in self_times(ops):
        total[e.name] = total.get(e.name, 0.0) + own
    return sorted(total.items(), key=lambda x: -x[1])[:k]


def summary(trace: Trace, k: int = 10):
    """Busy seconds and length of the traced runs' span, and the
    breakdown: the ``k`` device op kinds that took most time, and the
    idle time under each host activity, most first."""
    spans = trace.runs()
    lo, hi = spans[0].start, spans[-1].end
    idle: dict[str, float] = {}
    for where, ns in idle_gaps(trace, lo, hi):
        idle[where] = idle.get(where, 0.0) + ns
    inside = [e for e in trace.ops if lo <= e.start < hi]
    return busy(trace.ops, lo, hi) / 1e9, (hi - lo) / 1e9, {
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops(inside, k)],
        "idle_gaps": [[where, ns / 1e9] for where, ns in
                      sorted(idle.items(), key=lambda x: -x[1])[:k]],
    }


# ------------------------------------------------ the program's own names

def scope_share(trace: Trace | None, pick) -> float | None:
    """Percent of the traced runs' device busy time whose op the program
    maps to a named scope (``repro.obs.scopes``) that ``pick(scopes,
    scope)`` selects.  Each op counts its own time (less the ops it
    encloses) under the innermost scope of its HLO instruction in the
    programs the run compiled.  None without a trace, without a
    registered program, or from a program that names no scopes.  The
    shares by scope are kept on the trace, so the scope metrics of one
    run reduce it once."""
    try:
        from repro.obs import scopes
    except ImportError:  # a program that names no device scopes
        return None
    if trace is None:
        return None
    memo = vars(trace)
    if "scope_shares" not in memo:
        spans = trace.runs()
        inside = [e for s in spans for e in trace.ops if s.start <= e.start < s.end]
        times = scopes.scope_times(((e.name, t) for e, t in self_times(inside)), op_label)
        total = sum(busy(inside, s.start, s.end) for s in spans)
        memo["scope_shares"] = {s: 100.0 * t / total for s, t in times.items()} or None
    shares = memo["scope_shares"]
    return None if shares is None else sum(v for s, v in shares.items() if pick(scopes, s))


def phase_idle_ms(trace: Trace | None, phases) -> float | None:
    """Device-idle time under the program's host spans named in
    ``phases``, in ms per traced run; None where no run has such a span."""
    if trace is None or not trace.ops:
        return None
    ops = [(e.start, e.end) for e in trace.ops]
    runs, idle, found = trace.runs(), 0.0, False
    for run in runs:
        spans = [(e.start, e.end) for e in trace.host
                 if e.name in phases and run.start <= e.start < run.end]
        found = found or bool(spans)
        idle += sum(covered(gaps(ops, run.start, run.end), lo, hi) for lo, hi in spans)
    return idle / len(runs) / 1e6 if found else None
