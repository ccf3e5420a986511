#!/usr/bin/env python3
"""Inside one traced run: the program's own spans, counters and scopes.

    python3 bench/inside.py --workload <name> --seed <n> --seconds <s> \
        [--out FILE] [--excerpt FILE]

Serves a cell once as ``bench/run.py --trace 1`` does, keeps the profiler
trace, and prints, beside the cell's per-layer metrics (among them the
TTFT split into queue wait and prefill, the host gap and syncs per engine
step, the fused decode's attention) and the two parts of TTFT at p50, the
maps they are read from:

* the device's idle time by every host span open at its midpoint: the
  benchmark's (``bench.*``, ``engine.step``) and the program's own
  (``engine.<phase>``, ``hsa.*``), and the share of the in-step idle that
  a phase of the step carries;
* the fused decode's device time by every named scope of its operations
  (``attn.qkv``, ``attn.kv_write``, ``attn.core``, ``attn.out``, ``mlp``,
  ``unembed``, ``sample``; see ``bench.devtrace``);
* the window's ledger counters (``bench.run.counters``).

The last line of standard output is one JSON object (also written to
``--out``); the maps go to standard error as well.  ``--excerpt`` keeps one
engine step of the trace, ``op_name``s included, as test data.  Needs a
TPU, as ``bench/run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)                  # the bench modules are imported as bench.*
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import devtrace  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace" / "inside"


def phase_share(reduced: devtrace.Reduced | None) -> float | None:
    """Of the idle seconds inside ``engine.step``, the share that a phase
    of the step (``engine.<phase>``, ``hsa.*``) carries."""
    total = None if reduced is None else devtrace.program_idle(reduced)
    if not total:
        return None
    return (total - reduced.idle_by_span.get("engine.step", 0.0)) / total


def ttft_p50(window) -> dict[str, float]:
    """Nearest-rank p50 of queue wait and of prefill over the window's
    requests, taken as ``queue_wait_p95_s`` and ``prefill_p95_s`` take
    their p95 (a request never admitted waits to the end of the drain)."""
    from bench.records import percentile

    recs = window.records
    if not recs or all(r.admit is None for r in recs):
        return {}
    out = {"queue_wait_p50_s": percentile(
        [(window.drain_end if r.admit is None else r.admit) - r.due for r in recs], 0.5)}
    prefill = [r.first_token - r.admit for r in recs
               if r.first_token is not None and r.admit is not None]
    if prefill:
        out["prefill_p50_s"] = percentile(prefill, 0.5)
    return out


def excerpt(trace: devtrace.Trace, steps: int = 1) -> devtrace.Trace:
    """``steps`` of the program's engine steps from the middle of the
    window, with a ``bench.window`` span around them; each operation named
    by its HLO instruction alone."""
    nested = [s for s in trace.spans if s[0] == "engine.step"]
    own = [s for s in nested
           if any(o[1] < s[1] and s[2] <= o[2] for o in nested if o is not s)]
    mid = own[len(own) // 2:len(own) // 2 + steps]
    lo, hi = mid[0][1] - 1e6, mid[-1][2] + 1e6

    def inside(a, b):
        return lo <= a and b <= hi

    keep = [[i for i, (_, a, b) in enumerate(dev) if inside(a, b)] for dev in trace.ops]
    names = trace.op_names or [[None] * len(dev) for dev in trace.ops]
    return devtrace.Trace(
        [[(devtrace.instruction(dev[i][0]), *dev[i][1:]) for i in k]
         for dev, k in zip(trace.ops, keep)],
        [[p for p in dev if inside(p[1], p[2])] for dev in trace.programs],
        [(devtrace.WINDOW_SPAN, lo, hi)]
        + [s for s in trace.spans
           if s[0] != devtrace.WINDOW_SPAN and inside(s[1], s[2])],
        [[dev[i] for i in k] for dev, k in zip(names, keep)],
    )


def split_cell(cell, seed: int, seconds: float, *, device_kind: str,
               trace_dir: Path = TRACE_DIR, excerpt_path: str | None = None) -> dict:
    """Serve ``cell`` once with the profiler on; the split (see the module
    docstring)."""
    import jax

    from bench import loadgen, records, run, work

    srv = run.Serving(cell, seed)
    mix = srv.mix()
    srv.warm(mix, seconds)
    specs = loadgen.generate(mix, seed, seconds, cell.config["vocab_size"])
    window, stats = srv.window(mix, specs, seconds, trace_dir=trace_dir)
    memory = jax.devices()[0].memory_stats() or {}
    run.describe(window, stats)
    trace = srv.load_trace(trace_dir)
    reduced = devtrace.reduce(trace)
    metrics = run.per_layer(cell, window, stats, reduced, device_kind, memory)
    out = {
        "steps": window.steps + window.drain_steps, "window_steps": window.steps,
        "tails": records.tails(window), **records.end_to_end(window),
        **{k: m["value"] for k, m in metrics.items()}, **ttft_p50(window),
        "phase_share_of_step_idle": phase_share(reduced),
        "counters": stats["counters"],
        "idle_by_span_s": None if reduced is None else reduced.idle_by_span,
        "fused_decode": None if reduced is None else {
            p: {"launches": reduced.programs[p][0], "seconds_by_scope": by}
            for p, by in reduced.scopes.items() if re.search(work.DECODE, p)},
    }
    for key in ("idle_by_span_s", "fused_decode", "counters"):
        run.log(f"{key}: {json.dumps(out[key])}")
    if excerpt_path:
        Path(excerpt_path).write_text(excerpt(trace).to_json())
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
    out = split_cell(cell, args.seed, args.seconds, device_kind=devices[0].device_kind,
                     excerpt_path=args.excerpt)
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
