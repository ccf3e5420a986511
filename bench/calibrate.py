#!/usr/bin/env python3
"""Readings that set the limit of ``correct``: the program's widest logit
gap over many seeds, and the fp8 control's on the same samples, in one
process.

    python3 bench/calibrate.py --workload yi6b.chat-shared --seeds 1,2,3 --seconds 15

Each seed is a whole run of the cell (weights, engine, warm-up, a window
at the cell's own load, the drain), followed by the reference over the
run's sample and the control over the same prompts and tokens.  One JSON
line per seed.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=1 << 30,
                    help="run the control on the first this many seeds only")
    args = ap.parse_args(argv)

    import jax

    from bench import spec

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.log("calibrate: needs a TPU")
        return 2
    run.enable_compile_cache()
    cell = spec.cell(run.ROOT, args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, device_kind=dev.device_kind,
                           control=i < args.control_seeds)
        line = {"seed": seed, "correct": res["correct"],
                **{k: v["value"] for k, v in res["checks"].items()},
                **{k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
