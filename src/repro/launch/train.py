"""Training launcher.

Production entry point: picks the mesh (or a reduced one for local runs),
builds the model + sharded train step, runs the fault-tolerant loop.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \\
      --steps 100 --global-batch 8 --seq 256 --reduced

``--reduced`` swaps in the smoke-scale config of the same family so the
launcher is exercisable on one CPU; on a pod, omit it and pass
``--mesh 16x16``/``--mesh 2x16x16``.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_arch, reduced
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.dist.sharding import ShardingRules
from repro.launch.mesh import make_mesh
from repro.launch.specs import pick_opt
from repro.models import build_model
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.step import (
    auto_microbatches,
    init_train_state,
    make_train_step,
)


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 3:
        return make_mesh(dims, ("pod", "data", "model"))
    return make_mesh(dims, ("data", "model"))


def train(cfg, mesh, *, steps: int, global_batch: int, seq: int,
          lr: float = 3e-4, microbatches: int = 0, seed: int = 0,
          ckpt_dir: str | None = None, ckpt_every: int = 100):
    """Build ``cfg``'s sharded train step on ``mesh`` and run ``steps`` steps
    of synthetic tokens from parameters drawn from ``seed``.

    Returns ``(params, opt_state, report)``; ``report.losses`` holds the loss
    of every step run.
    """
    rules = ShardingRules.for_arch(cfg, mesh)
    model = build_model(cfg)
    opt = dataclasses.replace(pick_opt(cfg), lr=lr,
                              decay_steps=max(steps, 10))
    mb = microbatches or auto_microbatches(global_batch, seq, rules, cfg=cfg)
    data = SyntheticTokens(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=global_batch,
    ))
    shape = "x".join(str(n) for n in mesh.devices.shape)

    with jax.set_mesh(mesh):
        step, *_ = make_train_step(model, opt, rules,
                                   global_batch=global_batch,
                                   microbatches=mb)
        params, opt_state = init_train_state(model, opt, rules,
                                             jax.random.key(seed))
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"[train] {cfg.name}: {n/1e6:.1f}M params, mesh={shape}, "
              f"microbatches={mb}, opt={opt.kind}", flush=True)

        def batch_at(s: int):
            return {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}

        loop = TrainLoop(step, batch_at, LoopConfig(
            total_steps=steps, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
            log_every=10,
        ))
        return loop.run(params, opt_state)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config of the same family (CPU runs)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = auto (activation-budget heuristic)")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    _, _, report = train(
        cfg, parse_mesh(args.mesh), steps=args.steps,
        global_batch=args.global_batch, seq=args.seq, lr=args.lr,
        microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
    )
    print(f"[train] done: {report.steps_run} steps, "
          f"loss={report.last_metrics.get('loss', float('nan')):.4f}")


if __name__ == "__main__":
    main()
