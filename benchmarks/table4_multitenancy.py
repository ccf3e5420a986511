"""Table IV (extension): multi-tenant scheduling — async multi-queue vs sync.

Reproduces the paper's co-residency scenario end-to-end: the serving engine's
decode launches land on one HSA soft queue while a synthetic "OpenCL-style"
background producer cycles fixed-weight conv roles through the reconfigurable
regions on a second queue.  Two schedules of the *same* packet workload:

  sync   — single queue, reconfiguration occupies the device
           (the seed's blocking executor),
  async  — two queues, round-robin grants, reconfiguration engine overlapped
           so only the missing queue stalls.

Costs are calibrated from real measured loads/executions, then both schedules
run on the deterministic virtual clock, so the reported device-idle fractions
are exact properties of the schedule (not timer noise).  The async idle
fraction must be strictly lower.  Per-queue wait/exec/reconfig comes from the
overhead ledger's queue breakdown.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import calibrate_costs, make_paper_roles
from repro.configs import ARCHS, reduced
from repro.core import ledger as L
from repro.core.hsa.clock import VirtualClock
from repro.core.hsa.queue import Queue
from repro.core.hsa.scheduler import Scheduler
from repro.core.ledger import OverheadLedger
from repro.core.reconfig import RegionManager
from repro.core.roles import RoleLibrary
from repro.models import build_model
from repro.models.params import init_params
from repro.serve.engine import ServeEngine

# producer-cycle roles: 4 roles through 2 regions -> reconfig on every packet
BG_ORDER = ("role3_conv5x5", "role4_conv3x3", "role1_fc", "role3_conv5x5")


def _decode_workload(engine_steps: int):
    """The decode tenant: a ServeEngine driving real decode steps."""
    model = build_model(
        reduced(ARCHS["llama3.2-1b"], layers=2, d_model=64, vocab=128)
    )
    params = init_params(model.param_specs(), jax.random.key(0))

    def make(queue, scheduler):
        eng = ServeEngine(model, params, batch_slots=2, max_len=32,
                          hsa_queue=queue, hsa_scheduler=scheduler)
        eng.submit(list(np.arange(4) + 1), max_new_tokens=engine_steps)
        eng.submit([7, 9], max_new_tokens=engine_steps)
        return eng

    return make


def _run_schedule(roles, costs, *, nbg: int, engine_steps: int,
                  multi_queue: bool) -> tuple[Scheduler, OverheadLedger]:
    ledger = OverheadLedger()
    lib = RoleLibrary(ledger=ledger)
    # re-register this run's roles in a fresh library (fresh residency state)
    run_roles = {}
    for name, (role, args) in roles.items():
        run_roles[name] = (lib.add(role), args)
        role.unload()
    regions = RegionManager(2, ledger=ledger)
    clock = VirtualClock()
    sched = Scheduler(
        regions, lib, ledger=ledger, clock=clock,
        cost_model=lambda kind, what, measured: costs.get((kind, what), measured),
        overlap_reconfig=multi_queue,
    )
    q_serve = sched.add_queue(Queue(None, 4096, name="serve"))
    q_bg = (
        sched.add_queue(Queue(None, 4096, name="opencl")) if multi_queue else q_serve
    )

    # background producer: submit everything up front (a saturating tenant)
    for i in range(nbg):
        role, args = run_roles[BG_ORDER[i % len(BG_ORDER)]]
        q_bg.dispatch(role.key, *args, producer="opencl")

    engine = _decode_workload(engine_steps)(q_serve, sched)
    engine.run_to_completion(max_steps=engine_steps + 8)
    sched.run_until_idle()
    return sched, ledger


def run(n: int = 64) -> list[str]:
    probe_ledger = OverheadLedger()
    probe_lib = RoleLibrary(ledger=probe_ledger)
    roles = make_paper_roles(probe_lib)
    costs = calibrate_costs(roles)

    engine_steps = max(4, min(16, n // 8))
    sync_sched, _ = _run_schedule(
        roles, costs, nbg=n, engine_steps=engine_steps, multi_queue=False
    )
    async_sched, async_ledger = _run_schedule(
        roles, costs, nbg=n, engine_steps=engine_steps, multi_queue=True
    )

    t_sync = sync_sched.timeline()
    t_async = async_sched.timeline()
    rows = [
        f"table4,device_idle_fraction_sync,{t_sync['idle_fraction']:.4f},"
        f"makespan_us={t_sync['makespan_s']*1e6:.0f}",
        f"table4,device_idle_fraction_async,{t_async['idle_fraction']:.4f},"
        f"makespan_us={t_async['makespan_s']*1e6:.0f};"
        f"overlap_wins={t_async['idle_fraction'] < t_sync['idle_fraction']}",
    ]
    for qname, rep in sorted(async_sched.queue_report().items()):
        rows.append(
            f"table4,queue_{qname},{rep['exec_s']*1e6:.0f},"
            f"wait_us={rep['wait_s']*1e6:.0f};reconfig_us={rep['reconfig_s']*1e6:.0f};"
            f"dispatched={int(rep['dispatched'])}"
        )
    for qname, cats in sorted(async_ledger.queue_breakdown().items()):
        parts = ";".join(
            f"{c}={s.total_s*1e6:.0f}us/n{s.count}" for c, s in sorted(cats.items())
        )
        rows.append(f"table4,ledger_{qname},0,{parts}")
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
