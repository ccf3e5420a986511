"""Reduction of a profiler trace to device busy time, program times, idle
gaps and device time by named scope.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX, into a small ``Trace``: per device, the operations
(``XLA Ops`` line) with each one's HLO ``op_name`` where the trace gives it
(the event's ``tf_op`` stat), and the programs (``XLA Modules`` line),
with their start and end in nanoseconds; and the host spans
(``TraceAnnotation``) on the same clock.  Where the trace names no
``op_name`` (the v5e's does not), ``name_ops`` takes it from the compiled
HLO text of the programs (``hlo_op_names``).  ``Trace`` round-trips
through JSON, so a recorded trace can be kept as test data.

Busy time is the union of the intervals in which an operation ran on a
device, inside the window, averaged over the devices.  An idle gap is a
stretch of the window in which no operation ran; it is labelled by the
innermost host span open at its midpoint (the latest-starting one),
however many spans opened and closed inside it.

Device time by scope: each operation's own time (its duration less that
of the operations nested in it: on the TPU a loop is an operation around
its body's operations) counts toward the program it ran in, once under
every distinct component of its ``op_name`` path.  So scopes nest: an
operation under ``attn/core`` counts under ``attn`` and under ``core``,
and an outer scope's seconds include its inner scopes'.  JAX's own
components (``jit(f)``, ``while``, ``body``) and the primitive's name are
keys too; a reader takes the scope it names.  A fused operation counts
under the ``op_name`` of its root, the metadata XLA gives the fusion.  An
operation with no ``op_name`` counts under ``NO_SCOPE`` alone; one whose
``op_name`` names no model scope counts under JAX's components only, so a
program's time outside its model's scopes is its time less theirs.

``name_ops`` is given the HLO of the programs that can be compiled again
after the window (the serving benchmark gives the fused decode's,
``bench.run.Serving.fused_decode_op_names``); on a trace without
``op_name``s every other program's operations read ``NO_SCOPE``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import json
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "engine.", "hsa.")
#: the program's own spans (the benchmark's are ``bench.*`` and its
#: ``engine.step`` around each step)
PROGRAM_SPANS = ("engine.", "hsa.")
NO_SCOPE = "no scope"
NO_SPAN = "no span"


@dataclasses.dataclass
class Trace:
    ops: list[list[tuple[str, float, float]]]        # per device
    programs: list[list[tuple[str, float, float]]]   # per device
    spans: list[tuple[str, float, float]]            # host
    #: per device, each operation's ``op_name`` (``None`` where unknown);
    #: empty when the trace names none
    op_names: list[list[str | None]] = dataclasses.field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([[tuple(e) for e in dev] for dev in d["ops"]],
                   [[tuple(e) for e in dev] for dev in d["programs"]],
                   [tuple(e) for e in d["spans"]],
                   d.get("op_names", []))


def program_name(event_name: str) -> str:
    """``jit_decode_fused_k4_paged(17)`` -> ``jit_decode_fused_k4_paged``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def load(trace_dir: str, span_prefixes=SPAN_PREFIXES) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, programs, spans, op_names = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines and PROGRAMS_LINE not in lines:
                continue
            dev_ops, dev_names = [], []
            for e in (lines.get(OPS_LINE) or lines[PROGRAMS_LINE]).events:
                dev_ops.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                dev_names.append(dict(e.stats).get("tf_op") or None)
            ops.append(dev_ops)
            op_names.append(dev_names)
            mods = lines.get(PROGRAMS_LINE)
            programs.append([(program_name(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in (mods.events if mods else ())])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(span_prefixes)]
    return Trace(ops, programs, spans, op_names)


def named(trace: Trace) -> bool:
    """Whether any operation of the trace carries an ``op_name``."""
    return any(n for dev in trace.op_names for n in dev)


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata of a compiled HLO text."""
    return dict(re.findall(r'%?([\w.\-]+) = [^\n]*?metadata=\{[^}]*?op_name="([^"]*)"',
                           hlo_text))


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), ...`` (an operation's event
    name on the TPU: its HLO instruction) -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def name_ops(trace: Trace, hlo: dict[str, dict[str, str]]) -> Trace:
    """``trace`` with each operation that has no ``op_name`` named from
    ``hlo`` (program -> instruction -> ``op_name``, see
    :func:`hlo_op_names`), for the program that started last before it."""
    names = []
    for d, (ops, progs) in enumerate(zip(trace.ops, trace.programs)):
        have = trace.op_names[d] if d < len(trace.op_names) else [None] * len(ops)
        progs = sorted(progs, key=lambda p: p[1])
        starts = [a for _, a, _ in progs]
        dev = []
        for (name, a, _), known in zip(ops, have):
            if not known:
                i = bisect.bisect_right(starts, a) - 1
                known = hlo.get(progs[i][0] if i >= 0 else "", {}).get(instruction(name))
            dev.append(known)
        names.append(dev)
    return dataclasses.replace(trace, op_names=names)


def window_of(trace: Trace) -> tuple[float, float] | None:
    spans = [(a, b) for name, a, b in trace.spans if name == WINDOW_SPAN]
    return (min(a for a, _ in spans), max(b for _, b in spans)) if spans else None


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for _, a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class _Spans:
    """Host spans sorted by start, for the innermost span open at a time."""

    def __init__(self, spans):
        self.spans = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                            key=lambda s: s[1])

    def at(self, times) -> list[str]:
        """For each of ``times`` (ascending), the latest-starting span that
        is open at it, or ``NO_SPAN``."""
        out, open_, i = [], [], 0
        for t in times:
            while i < len(self.spans) and self.spans[i][1] <= t:
                heapq.heappush(open_, -i)          # the latest start on top
                i += 1
            while open_ and self.spans[-open_[0]][2] < t:
                heapq.heappop(open_)               # closed, and stays so
            out.append(self.spans[-open_[0]][0] if open_ else NO_SPAN)
        return out


def self_times(ops: list[tuple[str, float, float]]) -> list[float]:
    """Each operation's duration less that of the operations nested in it
    (on the TPU a loop is an operation whose body's operations run inside
    its interval), so that the times add up to the busy time."""
    out = [0.0] * len(ops)
    stack: list[int] = []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        _, a, b = ops[i]
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        out[i] = b - a
        if stack and b <= ops[stack[-1]][2]:
            out[stack[-1]] -= b - a
        stack.append(i)
    return out


def _by_scope(ops, names, progs, lo: float, hi: float) -> dict[str, dict[str, float]]:
    """One device's own time (ns) of the operations of each program inside
    ``[lo, hi]``, by ``op_name`` (``None`` where unknown)."""
    mine = sorted((a, b, name) for name, a, b in progs if a >= lo and b <= hi)
    starts = [a for a, _, _ in mine]
    inside = [i for i, (_, a, b) in enumerate(ops) if a >= lo and b <= hi]
    own = self_times([ops[i] for i in inside])
    out: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for i, t in zip(inside, own):
        _, a, b = ops[i]
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and b <= mine[k][1]:
            out[mine[k][2]][names[i] if names else None] += t
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over devices
    device_ops: list[list]            # [[program, seconds]], top 10
    programs: dict[str, tuple[int, float]]   # program -> (launches, seconds)
    idle_by_span: dict[str, float]    # host span -> idle seconds, longest first
    #: program -> scope -> device seconds (see the module docstring)
    scopes: dict[str, dict[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def idle_gaps(self) -> list[list]:
        """``[[host span, seconds]]`` of the 10 longest idle totals."""
        return [[k, s] for k, s in list(self.idle_by_span.items())[:10]]


def reduce(trace: Trace, window: tuple[float, float] | None = None) -> Reduced | None:
    """The trace's numbers inside ``window`` (default: the
    ``bench.window`` span); ``None`` when there is no window or no device
    activity to read.  Seconds are means over the devices."""
    window = window or window_of(trace)
    if window is None or not trace.ops or not any(trace.ops):
        return None
    lo, hi = window
    n_dev = len(trace.ops)
    busy, gaps = [], defaultdict(float)
    spans = _Spans(trace.spans)
    scoped: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for d, (dev, dev_progs) in enumerate(zip(trace.ops, trace.programs)):
        merged = _union(dev, lo, hi)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), label in zip(idle, spans.at([(a + b) / 2 for a, b in idle])):
            gaps[label] += (b - a) * 1e-9
        names = trace.op_names[d] if d < len(trace.op_names) else []
        for prog, by_name in _by_scope(dev, names, dev_progs, lo, hi).items():
            for op_name, t in by_name.items():
                for scope in set(op_name.split("/")) if op_name else (NO_SCOPE,):
                    scoped[prog][scope] += t * 1e-9 / n_dev
    programs: dict[str, list] = defaultdict(lambda: [0, 0.0])
    for dev in trace.programs:
        for name, a, b in dev:
            if a >= lo and b <= hi:
                programs[name][0] += 1
                programs[name][1] += (b - a) * 1e-9
    progs = {k: (n // n_dev, s / n_dev) for k, (n, s) in programs.items()}
    top = sorted(progs.items(), key=lambda kv: -kv[1][1])[:10]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / n_dev * 1e-9,
        device_ops=[[k, s] for k, (_, s) in top],
        programs=progs,
        idle_by_span={k: s / n_dev for k, s in sorted(gaps.items(), key=lambda kv: -kv[1])},
        scopes={p: dict(sorted(by.items(), key=lambda kv: -kv[1]))
                for p, by in scoped.items()},
    )


def launches(reduced: Reduced, pattern: str) -> tuple[int, float]:
    """Launches and device seconds of the programs whose name matches
    ``pattern`` (a regular expression)."""
    n, s = 0, 0.0
    for name, (k, sec) in reduced.programs.items():
        if re.search(pattern, name):
            n, s = n + k, s + sec
    return n, s


def program_idle(reduced: Reduced) -> float | None:
    """Idle seconds under the program's own spans (``PROGRAM_SPANS``, the
    benchmark's ``engine.step`` around them included); ``None`` when the
    program opened no span of its own."""
    idle = reduced.idle_by_span
    if not any(k.startswith(PROGRAM_SPANS) and k != "engine.step" for k in idle):
        return None
    return sum(v for k, v in idle.items() if k.startswith(PROGRAM_SPANS))
