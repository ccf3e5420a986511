"""Reduction of a profiler trace to device busy time, program times and
idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX, into a small ``Trace``: per device, the operations
(``XLA Ops`` line) and the programs (``XLA Modules`` line) with their
start and end in nanoseconds, and the host spans (``TraceAnnotation``)
on the same clock.  ``Trace`` round-trips through JSON, so a recorded
trace can be kept as test data.

Busy time is the union of the intervals in which an operation ran on a
device, inside the window, averaged over the devices.  An idle gap is a
stretch of the window in which no operation ran; it is labelled by the
innermost host span open at its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: list[list[tuple[str, float, float]]]        # per device
    programs: list[list[tuple[str, float, float]]]   # per device
    spans: list[tuple[str, float, float]]            # host

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([[tuple(e) for e in dev] for dev in d["ops"]],
                   [[tuple(e) for e in dev] for dev in d["programs"]],
                   [tuple(e) for e in d["spans"]])


def program_name(event_name: str) -> str:
    """``jit_decode_fused_k4_paged(17)`` -> ``jit_decode_fused_k4_paged``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def load(trace_dir: str, span_prefixes=("bench.", "engine.")) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, programs, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines and PROGRAMS_LINE not in lines:
                continue
            dev_ops = lines.get(OPS_LINE) or lines[PROGRAMS_LINE]
            ops.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in dev_ops.events])
            mods = lines.get(PROGRAMS_LINE)
            programs.append([(program_name(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in (mods.events if mods else ())])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(span_prefixes)]
    return Trace(ops, programs, spans)


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
        self.starts = [a for _, a, _ in self.spans]

    def at(self, t: float) -> str:
        """The latest-starting span that is open at ``t``."""
        i = bisect.bisect_right(self.starts, t)
        for name, a, b in reversed(self.spans[max(0, i - 64):i]):
            if b >= t:
                return name
        return "no span"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over devices
    device_ops: list[list]            # [[program, seconds]], top 10
    idle_gaps: list[list]             # [[host span, seconds]], top 10
    programs: dict[str, tuple[int, float]]   # program -> (launches, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace: Trace, window: tuple[float, float] | None = None) -> Reduced | None:
    """The trace's numbers inside ``window`` (default: the
    ``bench.window`` span); ``None`` when there is no window or no device
    activity to read."""
    window = window or window_of(trace)
    if window is None or not trace.ops or not any(trace.ops):
        return None
    lo, hi = window
    busy, gaps = [], defaultdict(float)
    spans = _Spans(trace.spans)
    for dev in trace.ops:
        merged = _union(dev, lo, hi)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[spans.at((a + b) / 2)] += (b - a) * 1e-9
    n_dev = len(trace.ops)
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
        idle_gaps=[[k, s / n_dev] for k, s in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        programs=progs,
    )


def launches(reduced: Reduced, pattern: str) -> tuple[int, float]:
    """Launches and device seconds of the programs whose name matches
    ``pattern`` (a regular expression)."""
    n, s = 0, 0.0
    for name, (k, sec) in reduced.programs.items():
        if re.search(pattern, name):
            n, s = n + k, s + sec
    return n, s
