"""Serving launcher: batched generation with the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \\
      --requests 6 --max-new 16
"""

from __future__ import annotations

import argparse

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_arch, reduced
from repro.models import build_model
from repro.models.params import init_params
from repro.serve.engine import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.key(0))
    engine = ServeEngine(model, params, batch_slots=args.slots,
                         max_len=args.max_len, temperature=args.temperature)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(2, 10))
        prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
        engine.submit(prompt, max_new_tokens=args.max_new)

    done = engine.run_to_completion()
    for req in sorted(done, key=lambda r: r.uid):
        print(f"req {req.uid}: prompt[{len(req.prompt)}] -> {req.generated}")
    print(f"[serve] completed {len(done)}/{args.requests} requests")


if __name__ == "__main__":
    main()
