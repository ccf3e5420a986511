#!/usr/bin/env python3
"""Find the knee of an open-loop cell: serve its mix at several fixed
rates in one process, and print per rate the load served and whether a
backlog grew.

    python3 bench/sweep.py --workload yi6b.chat-shared --rates 2,3,4 --seconds 20 --seed 7

One JSON line per rate: offered and completed requests, output tokens/s,
TTFT p50/p95, TPOT p95, the requests still unfinished when the window
closed, how long the drain took, and the mean TTFT of the last third of the
window's requests over the first third (a growing backlog reads well
above 1).  The knee is the highest rate without a growing backlog.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--drain", type=float, default=30.0,
                    help="seconds to drain after each window (past the knee "
                         "the backlog need not finish)")
    args = ap.parse_args(argv)

    import jax

    from bench import loadgen, records, spec

    if jax.devices()[0].platform != "tpu":
        run.log("sweep: needs a TPU")
        return 2
    run.enable_compile_cache()
    cell = spec.cell(run.ROOT, args.workload)
    srv = run.Serving(cell, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    # each rate's multiset is the first part of the highest rate's
    srv.warm(srv.mix({**cell.mix, "rate_per_s": max(rates)}), args.seconds)
    for rate in rates:
        mix = srv.mix({**cell.mix, "rate_per_s": rate, "drain_s": args.drain})
        specs = loadgen.generate(mix, args.seed, args.seconds, cell.config["vocab_size"])
        window, stats = srv.window(mix, specs, args.seconds)
        run.describe(window, stats)
        recs = sorted(window.records, key=lambda r: r.due)
        end = window.start + window.seconds
        ttft = records.ttfts(recs, window.drain_end)
        third = max(1, len(ttft) // 3)
        line = {
            "rate_per_s": rate, "offered": len(recs),
            "completed": sum(r.done for r in recs),
            "unfinished_at_close": sum(not r.done or r.finish > end for r in recs),
            "drain_s": window.drain_end - end,
            "ttft_late_over_early": (sum(ttft[-third:]) / third) / (sum(ttft[:third]) / third),
            **records.end_to_end(window),
            "ttft_p50_s": records.percentile(ttft, 0.5),
        }
        print(json.dumps(line), flush=True)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
