#!/usr/bin/env python3
"""Inside one traced run: the program's own spans, counters and scopes.

    python3 bench/inside.py --workload <name> --seed <n> --seconds <s> \
        [--out FILE] [--excerpt FILE]

Serves a cell once as ``bench/run.py --trace 1`` does, keeps the profiler
trace, and splits what the benchmark's breakdown reports whole:

* TTFT into queue wait (due time to ``Request.admit_t``, when a slot took
  the request) and prefill (``admit_t`` to the first token);
* the device's idle time by every host span open at its midpoint: the
  benchmark's (``bench.*``, ``engine.step``) and the program's own
  (``engine.<phase>``, ``hsa.*``);
* the fused decode's device time by the ``jax.named_scope`` of its
  operations (``attn.qkv``, ``attn.kv_write``, ``attn.core``, ``attn.out``,
  ``mlp``, ``unembed``, ``sample``), each operation's own time (a loop's
  less its body's).  A fused operation counts under the scope of its root,
  the metadata XLA gives the fusion;
* per engine step, the host syncs the ledgers counted and the idle
  milliseconds under program spans.

The last line of standard output is one JSON object (also written to
``--out``); the maps go to standard error as well.  ``--excerpt`` keeps one
engine step of the trace, scopes included, as test data.  Needs a TPU, as
``bench/run.py`` does.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)                  # the bench modules are imported as bench.*
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import devtrace  # noqa: E402

SCOPES = ("attn.qkv", "attn.kv_write", "attn.core", "attn.out", "mlp",
          "unembed", "sample")
NO_SCOPE = "no scope"
SPAN_PREFIXES = ("bench.", "engine.", "hsa.")
FUSED_DECODE = r"decode_fused_k\d+"
TRACE_DIR = ROOT / ".bench_trace" / "inside"


@dataclasses.dataclass
class Scoped:
    """A device trace whose operations carry their named scope
    (``scopes[d][i]`` for ``trace.ops[d][i]``; ``None`` outside every
    scope)."""

    trace: devtrace.Trace
    scopes: list[list[str | None]]

    def to_json(self) -> str:
        return json.dumps({"trace": json.loads(self.trace.to_json()),
                           "scopes": self.scopes})

    @classmethod
    def from_json(cls, text: str) -> "Scoped":
        d = json.loads(text)
        return cls(devtrace.Trace.from_json(json.dumps(d["trace"])), d["scopes"])


def scope_of(op_name: str | None) -> str | None:
    """The innermost of ``SCOPES`` in an HLO ``op_name`` path."""
    parts = (op_name or "").split("/")
    return next((p for p in reversed(parts) if p in SCOPES), None)


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata of a compiled HLO text."""
    return dict(re.findall(r'%?([\w.\-]+) = [^\n]*?metadata=\{[^}]*?op_name="([^"]*)"',
                           hlo_text))


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), ...`` (an operation's event
    name on the TPU: its HLO instruction) -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(trace_dir: str, hlo: dict[str, dict[str, str]] | None = None) -> Scoped:
    """The trace under ``trace_dir`` with every span of ``SPAN_PREFIXES``
    and each device operation's scope: from the operation's ``tf_op``
    stat (its HLO ``op_name``), else from ``hlo`` (program -> instruction
    -> ``op_name``, see :func:`hlo_op_names`) for the program the
    operation ran in."""
    from jax.profiler import ProfileData

    trace = devtrace.load(trace_dir, span_prefixes=SPAN_PREFIXES)
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    scopes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = {line.name: line for line in plane.lines}
        if devtrace.OPS_LINE not in lines and devtrace.PROGRAMS_LINE not in lines:
            continue
        progs = sorted(trace.programs[len(scopes)], key=lambda p: p[1])
        starts = [a for _, a, _ in progs]
        dev = []
        for e in (lines.get(devtrace.OPS_LINE) or lines[devtrace.PROGRAMS_LINE]).events:
            name = dict(e.stats).get("tf_op")
            if not name and hlo:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = progs[i][0] if i >= 0 else ""
                name = hlo.get(module, {}).get(instruction(e.name))
            dev.append(scope_of(name))
        scopes.append(dev)
    if [len(d) for d in scopes] != [len(d) for d in trace.ops]:
        raise RuntimeError("device operations and their scopes do not align")
    return Scoped(trace, scopes)


class Innermost:
    """Host spans sorted by start, for the innermost span open at a time."""

    def __init__(self, spans):
        self.spans = sorted((s for s in spans if s[0] != devtrace.WINDOW_SPAN),
                            key=lambda s: s[1])
        self.starts = [a for _, a, _ in self.spans]

    def at(self, t: float, lookback: int = 4096) -> str:
        """The latest-starting span open at ``t``."""
        i = bisect.bisect_right(self.starts, t)
        for name, _, b in reversed(self.spans[max(0, i - lookback):i]):
            if b >= t:
                return name
        return "no span"


def idle_by_span(trace: devtrace.Trace, window=None) -> dict[str, float] | None:
    """Seconds in which no operation ran on the device, inside the window
    (default ``bench.window``), by the innermost host span open at the
    midpoint of each gap; mean over the devices.  Unlike the benchmark's
    ``breakdown``, every span is kept, and a span is found however many
    spans opened and closed inside it."""
    window = window or devtrace.window_of(trace)
    if window is None or not any(trace.ops):
        return None
    lo, hi = window
    spans = Innermost(trace.spans)
    gaps: dict[str, float] = defaultdict(float)
    for dev in trace.ops:
        merged = devtrace._union(dev, lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[spans.at((a + b) / 2)] += (b - a) * 1e-9 / len(trace.ops)
    return dict(sorted(gaps.items(), key=lambda kv: -kv[1]))


def in_program(idle: dict[str, float] | None) -> float | None:
    """Idle seconds under the program's own spans (``engine.*``,
    ``hsa.*``); ``None`` when the program opened no span of its own (the
    benchmark's ``engine.step`` alone)."""
    if not idle or not any(k.startswith(("engine.", "hsa.")) and k != "engine.step"
                           for k in idle):
        return None
    return sum(v for k, v in idle.items() if k.startswith(("engine.", "hsa.")))


def phase_share(idle: dict[str, float] | None) -> float | None:
    """Of the idle seconds inside ``engine.step``, the share that a phase
    of the step (``engine.<phase>``, ``hsa.*``) carries."""
    total = in_program(idle)
    if not total:
        return None
    return (total - idle.get("engine.step", 0.0)) / total


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


def scope_seconds(scoped: Scoped, pattern: str,
                  window=None) -> tuple[int, dict[str, float]] | None:
    """Launches of the programs matching ``pattern`` inside the window, and
    their operations' device seconds (self time, see :func:`self_times`)
    by scope (``NO_SCOPE`` for the rest); mean over the devices.  ``None``
    when no operation carries a scope."""
    trace = scoped.trace
    window = window or devtrace.window_of(trace)
    if window is None or not any(s for dev in scoped.scopes for s in dev):
        return None
    lo, hi = window
    launches, seconds = 0, defaultdict(float)
    n_dev = len(trace.ops)
    for ops, scopes, progs in zip(trace.ops, scoped.scopes, trace.programs):
        mine = sorted((a, b) for name, a, b in progs
                      if re.search(pattern, name) and a >= lo and b <= hi)
        launches += len(mine)
        starts = [a for a, _ in mine]
        for (_, a, b), own, scope in zip(ops, self_times(ops), scopes):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= mine[i][1]:
                seconds[scope or NO_SCOPE] += own * 1e-9 / n_dev
    return launches // n_dev, dict(sorted(seconds.items(), key=lambda kv: -kv[1]))


def attention_ms(split: tuple[int, dict[str, float]] | None) -> float | None:
    """Device ms of ``attn.kv_write`` + ``attn.core`` per launch."""
    if not split or not split[0]:
        return None
    n, by_scope = split
    return 1e3 * (by_scope.get("attn.kv_write", 0.0) + by_scope.get("attn.core", 0.0)) / n


def ttft_split(records, admitted: dict[int, float],
               drain_end: float) -> dict[str, float] | None:
    """Nearest-rank p95 of queue wait (admission minus due time; drain end
    minus due time for a request never admitted) and of prefill (first
    token minus admission) over the window's requests."""
    from bench.records import percentile

    if not records or not admitted:
        return None
    wait = [admitted.get(r.uid, drain_end) - r.due for r in records]
    prefill = [r.first_token - admitted[r.uid] for r in records
               if r.first_token is not None and r.uid in admitted]
    out = {"queue_wait_p95_s": percentile(wait, 0.95),
           "queue_wait_p50_s": percentile(wait, 0.5)}
    if prefill:
        out["prefill_p95_s"] = percentile(prefill, 0.95)
        out["prefill_p50_s"] = percentile(prefill, 0.5)
    return out


def excerpt(scoped: Scoped, steps: int = 1) -> Scoped:
    """``steps`` of the program's engine steps from the middle of the
    window, with a ``bench.window`` span around them; each operation named
    by its HLO instruction alone."""
    trace = scoped.trace
    nested = [s for s in trace.spans if s[0] == "engine.step"]
    own = [s for s in nested
           if any(o[1] < s[1] and s[2] <= o[2] for o in nested if o is not s)]
    mid = own[len(own) // 2:len(own) // 2 + steps]
    lo, hi = mid[0][1] - 1e6, mid[-1][2] + 1e6

    def inside(a, b):
        return lo <= a and b <= hi

    keep = [[i for i, (_, a, b) in enumerate(dev) if inside(a, b)] for dev in trace.ops]
    return Scoped(
        devtrace.Trace(
            [[(instruction(dev[i][0]), *dev[i][1:]) for i in k]
             for dev, k in zip(trace.ops, keep)],
            [[p for p in dev if inside(p[1], p[2])] for dev in trace.programs],
            [(devtrace.WINDOW_SPAN, lo, hi)]
            + [s for s in trace.spans
               if s[0] != devtrace.WINDOW_SPAN and inside(s[1], s[2])],
        ),
        [[sc[i] for i in k] for sc, k in zip(scoped.scopes, keep)],
    )


def _fused_hlo(engine, ks) -> dict[str, dict[str, str]]:
    """The compiled fused decode programs' instruction -> ``op_name``, for
    a trace whose operations name no ``tf_op``."""
    import numpy as np

    n = engine.slots
    args = (engine.params, engine._cache["segments"], np.asarray(engine._table),
            np.zeros(n, np.int32), np.zeros(n, np.int32), engine._slot_key,
            np.zeros(n, np.int32), np.zeros(n, bool), np.zeros(n, np.int32))
    out = {}
    for k in ks:
        fn = engine._fused_decode_fn(k)
        out[f"jit_{fn.__name__}"] = hlo_op_names(fn.lower(*args).compile().as_text())
    return out


def split_cell(cell, seed: int, seconds: float, *, trace_dir: Path = TRACE_DIR,
               excerpt_path: str | None = None) -> dict:
    """Serve ``cell`` once with the profiler on; the split (see the module
    docstring)."""
    from bench import loadgen, records, run

    srv = run.Serving(cell, seed)
    mix = srv.mix()
    srv.warm(mix, seconds)
    specs = loadgen.generate(mix, seed, seconds, cell.config["vocab_size"])
    eng = srv.engine
    admitted: dict[int, float] = {}
    engine_step = eng.step

    def step():
        done = engine_step()
        admitted.update((r.uid, r.admit_t) for r in done)
        return done

    eng.step = step
    ledgers = (srv.engine_log, srv.system.ledger)
    before = [led.sync_split() for led in ledgers]
    window, stats = srv.window(mix, specs, seconds, trace_dir=trace_dir)
    after = [led.sync_split() for led in ledgers]
    steps = after[0]["engine_steps"] - before[0]["engine_steps"]
    syncs = sum(a["host_syncs"] - b["host_syncs"] for a, b in zip(after, before))
    run.describe(window, stats)

    scoped = load(str(trace_dir))
    if any(scoped.trace.ops) and not any(s for dev in scoped.scopes for s in dev):
        ks = sorted({int(m.group(1)) for dev in scoped.trace.programs
                     for name, _, _ in dev
                     if (m := re.search(r"decode_fused_k(\d+)", name))})
        run.log(f"inside: no tf_op stat on the operations; reading the compiled "
                f"fused decode (k={ks}) instead")
        scoped = load(str(trace_dir), _fused_hlo(eng, ks))
    idle = idle_by_span(scoped.trace)
    fused = scope_seconds(scoped, FUSED_DECODE)
    gap = in_program(idle)
    out = {
        "steps": steps, "window_steps": window.steps, "host_syncs": syncs,
        "tails": records.tails(window), **records.end_to_end(window),
        **(ttft_split(window.records, admitted, window.drain_end) or {}),
        "step_host_gap_ms": None if gap is None or not steps else 1e3 * gap / steps,
        "host_syncs_per_step": syncs / steps if steps else None,
        "decode_attention_ms": attention_ms(fused),
        "phase_share_of_step_idle": phase_share(idle),
        "idle_by_span_s": idle,
        "fused_decode": (None if fused is None else
                         {"launches": fused[0], "seconds_by_scope": fused[1]}),
    }
    for key in ("idle_by_span_s", "fused_decode"):
        run.log(f"{key}: {json.dumps(out[key])}")
    if excerpt_path:
        Path(excerpt_path).write_text(excerpt(scoped).to_json())
    srv.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--excerpt", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import run, spec

    cell = spec.cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        run.log(f"inside: needs {cell.chips} TPU chip(s)")
        return 2
    run.log(f"compile cache: {run.enable_compile_cache()}")
    out = split_cell(cell, args.seed, args.seconds, excerpt_path=args.excerpt)
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
