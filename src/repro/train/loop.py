"""Fault-tolerant training loop.

Production behaviours implemented and tested:

  - **checkpoint/restart**: atomic checkpoints every ``ckpt_every`` steps;
    on start, auto-resume from the newest valid checkpoint (data pipeline
    regenerates its stream from the step counter — no loader state).
  - **preemption**: SIGTERM/SIGINT trigger a final checkpoint before exit
    (the TPU-pod eviction contract).
  - **straggler watchdog**: per-step wall time tracked with an EWMA; steps
    slower than ``straggler_factor ×`` the EWMA are logged with their step
    index.  At real scale the hook re-routes to the pod scheduler; here it
    feeds the metrics log so tests can assert detection.
  - **NaN guard**: non-finite loss aborts with the last good checkpoint
    intact (never checkpoints a poisoned state).
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np

from repro.checkpoint.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_dir: str | None = None
    ckpt_every: int = 100
    keep: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    ewma_alpha: float = 0.2


@dataclasses.dataclass
class LoopReport:
    steps_run: int = 0
    resumed_from: int = 0
    stragglers: list[int] = dataclasses.field(default_factory=list)
    last_metrics: dict = dataclasses.field(default_factory=dict)
    losses: list[float] = dataclasses.field(default_factory=list)
    step_times_s: list[float] = dataclasses.field(default_factory=list)
    preempted: bool = False


class TrainLoop:
    def __init__(
        self,
        step_fn: Callable[..., tuple[Any, Any, dict]],
        batch_at: Callable[[int], dict],
        cfg: LoopConfig,
        *,
        log: Callable[[str], None] = print,
    ):
        self.step_fn = step_fn
        self.batch_at = batch_at
        self.cfg = cfg
        self.log = log
        self._preempt = False

    def _install_handlers(self):
        def handler(signum, frame):
            self._preempt = True
        prev = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev[sig] = signal.signal(sig, handler)
            except ValueError:          # non-main thread (tests)
                pass
        return prev

    def _restore_handlers(self, prev):
        for sig, h in prev.items():
            signal.signal(sig, h)

    def run(self, params, opt_state) -> tuple[Any, Any, LoopReport]:
        cfg = self.cfg
        report = LoopReport()
        start_step = 0

        if cfg.ckpt_dir:
            path = latest_checkpoint(cfg.ckpt_dir)
            if path is not None:
                (params, opt_state), manifest = restore_checkpoint(
                    path, (params, opt_state)
                )
                start_step = int(manifest["step"])
                report.resumed_from = start_step
                self.log(f"[loop] resumed from {path} at step {start_step}")

        prev_handlers = self._install_handlers()
        ewma = None
        try:
            for step in range(start_step, cfg.total_steps):
                t0 = time.perf_counter()
                batch = self.batch_at(step)
                params, opt_state, metrics = self.step_fn(params, opt_state, batch)
                loss = float(jax.device_get(metrics["loss"]))
                dt = time.perf_counter() - t0
                report.step_times_s.append(dt)
                report.losses.append(loss)
                report.steps_run += 1
                report.last_metrics = {
                    k: float(np.asarray(jax.device_get(v)).mean())
                    for k, v in metrics.items()
                }

                if not np.isfinite(loss):
                    raise FloatingPointError(
                        f"non-finite loss at step {step}; last checkpoint intact"
                    )

                # straggler watchdog
                if ewma is None:
                    ewma = dt
                elif dt > cfg.straggler_factor * ewma and step > start_step + 2:
                    report.stragglers.append(step)
                    self.log(f"[loop] straggler suspected: step {step} took "
                             f"{dt:.3f}s vs EWMA {ewma:.3f}s")
                ewma = dt if ewma is None else (
                    cfg.ewma_alpha * dt + (1 - cfg.ewma_alpha) * ewma
                )

                if cfg.log_every and step % cfg.log_every == 0:
                    self.log(f"[loop] step {step} loss {loss:.4f} "
                             f"({dt*1e3:.0f} ms)")

                done = step + 1
                if cfg.ckpt_dir and (
                    done % cfg.ckpt_every == 0 or done == cfg.total_steps
                    or self._preempt
                ):
                    save_checkpoint(cfg.ckpt_dir, done, (params, opt_state),
                                    keep=cfg.keep)
                if self._preempt:
                    report.preempted = True
                    self.log(f"[loop] preemption: checkpointed at step {done}")
                    break
        finally:
            self._restore_handlers(prev_handlers)
        return params, opt_state, report
