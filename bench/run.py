#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model, with the architecture module its configuration
names (``bench/archs/<a>.py``) and weights drawn from ``--seed``, sets up
``ServeEngine`` as users get it by default (paged KV, chunked prefill,
prefix sharing, fused decode, every launch on the HSA queue of the default
agent), warms up every shape the cell's traffic uses, then serves the
traffic for ``--seconds`` seconds and drains what the window left running.
Afterwards it checks the served tokens against that module's float32
reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown`` of the device trace, and last ``checks``:
each number compared beside its limit.  Diagnostics go to standard error,
and end with the same checks.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import shutil
import sys
import time
import typing
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)                  # the bench modules are imported as bench.*
for p in (str(SRC), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
TRACE_DIR = ROOT / ".bench_trace"


def process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_PROCESS = process_start()


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache for every compile, at a fixed path inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _group(field_type):
    """The dataclass among the types of an ``ArchConfig`` field
    (``MoEConfig`` of ``MoEConfig | None``), or ``None``."""
    kinds = typing.get_args(field_type) or (field_type,)
    return next((k for k in kinds if dataclasses.is_dataclass(k)), None)


def build_model(cfg: dict, arch):
    """The program's model of ``cfg``: every key of its ``model`` is passed
    to ``ArchConfig``, with ``family`` from the architecture module.  A key
    whose field is a group (``moe``, ``mla``, ``ssm``) holds an object of
    that group's keys and is built into its dataclass.  A key the program
    has no field for, at the top or inside a group, is refused by name."""
    from bench import spec
    from repro.configs.base import ArchConfig
    from repro.models import build_model as build

    model = spec.model(cfg)
    types = typing.get_type_hints(ArchConfig)
    fields = set(types) - {"name", "family"}
    unknown = sorted(set(model) - fields)
    for key, value in model.items():
        group = _group(types.get(key))
        if group is not None and isinstance(value, dict):
            have = {f.name for f in dataclasses.fields(group)}
            unknown += sorted(f"{key}.{k}" for k in set(value) - have)
            model[key] = group(**value) if set(value) <= have else value
    if unknown:
        raise ValueError(f"configuration {cfg['name']!r}: the program's ArchConfig "
                         f"has no field {', '.join(unknown)}")
    return build(ArchConfig(name=cfg["name"], family=arch.FAMILY, **model))


def check_layout(model, params) -> None:
    """The drawn weights have the program's parameter tree and shapes."""
    import jax

    import numpy as np

    want = jax.tree.map(lambda s: (tuple(s.shape), np.dtype(s.dtype).name),
                        model.param_specs(), is_leaf=lambda x: hasattr(x, "logical"))
    got = jax.tree.map(lambda a: (tuple(a.shape), np.dtype(a.dtype).name), params)
    if want != got:
        raise RuntimeError(f"weight layout differs from the program's: {want} vs {got}")


def _engine_log():
    """An ``OverheadLedger`` that also keeps every prefix lookup, in the
    order the engine admits requests, and the uids whose first token came."""
    from repro.core import ledger as L

    class EngineLog(L.OverheadLedger):
        def __init__(self):
            super().__init__()
            self.lookups: list[tuple[bool, int]] = []
            self.first_token: set[int] = set()

        def record(self, category: str, seconds: float, **meta) -> None:
            if category == L.TTFT:
                self.first_token.add(meta.get("uid"))
            super().record(category, seconds, **meta)

        def record_prefix_lookup(self, *, hit: bool, pages_saved: int = 0) -> None:
            self.lookups.append((hit, pages_saved))
            super().record_prefix_lookup(hit=hit, pages_saved=pages_saved)

    return EngineLog()


def _hsa_stats(ledger) -> dict:
    from repro.core import ledger as L

    return {c: (ledger.stat(c).count, ledger.stat(c).total_s)
            for c in (L.DISPATCH_SUBMIT, L.DISPATCH_GRANT, L.DISPATCH_WAIT, L.EXEC)}


def counters(*ledgers) -> dict[str, float]:
    """The counters of ``ledgers`` (``OverheadLedger``s), under the
    ledger's own names and summed over the ledgers, each event being
    recorded in one of them: every key of ``sync_split()`` but its ratio,
    and each category's ``<category>.count`` and ``<category>.total_s``."""
    from repro.core import ledger as L

    out: dict[str, float] = defaultdict(float)
    for led in ledgers:
        for k, v in led.sync_split().items():
            if k != "host_syncs_per_step":
                out[k] += v
        for c in L.CATEGORIES:
            st = led.stat(c)
            out[f"{c}.count"] += st.count
            out[f"{c}.total_s"] += st.total_s
    return dict(out)


class Context:
    """What a per-layer metric reader may read (see ``bench/metrics``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Serving:
    """The system under test, set up for one cell: the model with weights
    drawn from the seed, and ``ServeEngine`` on the HSA queue of the
    default agent."""

    def __init__(self, cell, seed: int):
        import jax

        from bench import weights
        from bench.compiles import CompileCounter
        from repro.core.hsa import hsa_init, hsa_shut_down
        from repro.core.ledger import OverheadLedger
        from repro.serve.engine import ServeEngine

        self.cell, self.seed, sv = cell, seed, cell.serving
        self.warmed: set[int] = set()
        self.compiles = CompileCounter()
        self.model = build_model(cell.config, cell.arch)
        params = weights.make(cell.arch, cell.config, seed)
        check_layout(self.model, params)
        jax.block_until_ready(params)
        log(f"weights: {weights.nbytes(cell.arch, cell.config) / 1e9:.3f} GB drawn, "
            f"{time.time() - T_PROCESS:.1f} s after process start")
        hsa_shut_down()
        self.system = hsa_init(num_regions=2, ledger=OverheadLedger())
        agent = self.system.default_agent
        self.engine_log = _engine_log()
        self.engine = ServeEngine(
            self.model, params, batch_slots=sv["batch_slots"],
            max_len=sv["max_len"], temperature=sv["temperature"],
            seed=seed % (1 << 31), decode_fusion=sv["decode_fusion"],
            paged=sv["paged"], page_size=sv["page_size"],
            prefill_chunk=sv["prefill_chunk"], prefix=sv["prefix"],
            hsa_queue=self.system.queue_of(agent),
            hsa_scheduler=self.system.scheduler_of(agent), ledger=self.engine_log,
        )

    def mix(self, mix: dict | None = None) -> dict:
        """The cell's mix with its closed-loop client count resolved."""
        mix = dict(mix or self.cell.mix)
        if mix["loop"] == "closed":
            mix["clients"] = mix["clients_per_slot"] * self.cell.serving["batch_slots"]
        return mix

    def warm(self, mix: dict, seconds: float) -> None:
        """Compile, before the window, what serving ``mix`` for ``seconds``
        uses (and what a shorter multiset of the same mix uses)."""
        from bench import driver, loadgen

        sv, c = self.cell.serving, self.compiles
        rows = loadgen.shapes(mix, seconds)
        counts = driver.chunk_rows(rows, sv["page_size"], sv["prefill_chunk"]) - self.warmed
        if counts:
            # only a speed-up of the first run: a program without this eager
            # scatter is warmed up by the serving below all the same
            try:
                t = driver.precompile_pool_scatters(self.engine, self.model, counts,
                                                    sv["page_size"], sv["max_len"])
            except (ImportError, AttributeError, TypeError) as e:
                log(f"warm-up: pool-scatter pre-compile skipped: {e!r}")
            else:
                log(f"warm-up: pool scatter for {len(counts)} chunk row counts in "
                    f"{t:.1f} s; {c.n} executables so far ({c.cache_hits} from the "
                    f"persistent cache, {c.seconds:.1f} s compiling)")
            self.warmed |= counts
        n0, t0 = c.n, time.time()
        warm = driver.Warmup(self.engine, self.engine_log, rows,
                             self.cell.config["vocab_size"], self.seed, sv["decode_fusion"])
        served = warm.unshared() + warm.shared() + warm.decode_depths()
        log(f"warm-up: served {served} requests in {warm.steps} steps, "
            f"{time.time() - t0:.1f} s, "
            f"{c.n - n0} executables compiled or loaded; {c.n} so far "
            f"({c.cache_hits} from the persistent cache, {c.seconds:.1f} s compiling)")

    def window(self, mix: dict, specs: list, seconds: float, *, trace_dir=None):
        """Serve ``specs`` for ``seconds`` and drain; ``(window, stats)``."""
        import jax
        from jax.profiler import TraceAnnotation

        from bench import devtrace, driver

        eng = self.engine
        ledgers = (self.engine_log, self.system.ledger)
        c0, hsa0 = self.compiles.snapshot(), _hsa_stats(self.system.ledger)
        counts0 = counters(*ledgers)
        saved0, n0 = eng.prefix_pages_saved, len(self.engine_log.lookups)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0        # host spans, not every call
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        setup_s = time.time() - T_PROCESS
        with TraceAnnotation(devtrace.WINDOW_SPAN):
            if mix["loop"] == "open":
                window = driver.run_open(eng, specs, seconds, mix["drain_s"])
            else:
                window = driver.run_closed(eng, specs, mix["clients"], seconds,
                                           mix["drain_s"])
        if trace_dir is not None:
            jax.profiler.stop_trace()
        c1, hsa1 = self.compiles.snapshot(), _hsa_stats(self.system.ledger)
        counts1 = counters(*ledgers)
        lookups = self.engine_log.lookups[n0:]
        stats = {
            "setup_s": setup_s,
            "window_compiles": c1[0] - c0[0],
            "prefix_pages_saved": eng.prefix_pages_saved - saved0,
            "lookups": lookups if len(lookups) == len(window.records) else None,
            "hsa": {k: (hsa1[k][0] - hsa0[k][0], hsa1[k][1] - hsa0[k][1]) for k in hsa1},
            "counters": {k: v - counts0.get(k, 0.0) for k, v in counts1.items()},
        }
        return window, stats

    def fused_decode_op_names(self, trace) -> dict[str, dict[str, str]]:
        """Instruction -> ``op_name`` of the compiled fused decode programs
        that ran in ``trace``, for a trace whose operations name none."""
        import numpy as np

        from bench import devtrace, work

        ks = sorted({int(m.group(1)) for dev in trace.programs for name, _, _ in dev
                     if (m := re.search(work.DECODE, name))})
        eng, n = self.engine, self.engine.slots
        args = (eng.params, eng._cache["segments"], np.asarray(eng._table),
                np.zeros(n, np.int32), np.zeros(n, np.int32), eng._slot_key,
                np.zeros(n, np.int32), np.zeros(n, bool), np.zeros(n, np.int32))
        out = {}
        for k in ks:
            fn = eng._fused_decode_fn(k)
            out[f"jit_{fn.__name__}"] = devtrace.hlo_op_names(
                fn.lower(*args).compile().as_text())
        return out

    def load_trace(self, trace_dir):
        """The trace under ``trace_dir``, its operations named by their
        ``op_name``: from the trace, else from the compiled fused decode.
        Call it after the window, with the engine still alive."""
        from bench import devtrace

        t0, (n0, s0, hits0) = time.time(), self.compiles.snapshot()
        trace = devtrace.load(str(trace_dir))
        if any(trace.ops) and not devtrace.named(trace):
            try:
                hlo = self.fused_decode_op_names(trace)
            except (AttributeError, KeyError, TypeError, ValueError) as e:
                log(f"trace: no op_name on the operations, and the fused decode's "
                    f"HLO could not be read: {e!r}")
            else:
                n, s, hits = self.compiles.snapshot()
                log(f"trace: no op_name on the operations; read from the compiled "
                    f"{sorted(hlo)} in {time.time() - t0:.1f} s ({n - n0} executables, "
                    f"{hits - hits0} from the persistent cache, {s - s0:.1f} s compiling)")
                trace = devtrace.name_ops(trace, hlo)
        return trace

    def close(self) -> None:
        """Free the program's state (the reference needs the memory)."""
        from repro.core.hsa import hsa_shut_down

        del self.engine
        hsa_shut_down()
        self.system = None
        gc.collect()
        self.compiles.close()


def describe(window, stats) -> None:
    from bench import records

    recs = window.records
    late = records.lateness(recs)
    log("tails: " + json.dumps(records.tails(window)))
    log(f"window: {window.seconds} s, {window.steps} engine steps, {len(recs)} "
        f"requests ({sum(r.done for r in recs)} completed, drain ended "
        f"{window.drain_end - window.start - window.seconds:.2f} s after the "
        f"close); submitted late by p50 {1e3 * records.percentile(late, 0.5):.2f} ms, "
        f"max {1e3 * max(late):.2f} ms; {stats['window_compiles']} executables "
        f"compiled or loaded in the window; samples: ttft "
        f"{len(records.ttfts(recs, window.drain_end))}, tpot "
        f"{len(records.tpots(recs, window.drain_end))}")


def per_layer(cell, window, stats, reduced, device_kind: str, memory: dict) -> dict:
    from bench import spec
    from bench.peaks import peaks

    flops, bw = peaks(device_kind)
    ctx = Context(cfg=cell.config, serving=cell.serving, shapes=cell.arch.shapes(cell.config),
                  window=window, reduced=reduced, memory=memory,
                  peak_flops=flops, peak_bw=bw, **{k: v for k, v in stats.items()
                                                   if k != "setup_s"})
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"], cell.bench)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def correctness(cell, seed: int, window, *, control: bool = False) -> dict:
    """The checks of ``correct``, each number beside its limit; with
    ``control`` also the fp8 control's reading on the same sample."""
    from bench import check, weights

    cfg, sv = cell.config, cell.serving
    t0 = time.time()
    recs = window.records
    done = [r for r in recs if r.done]
    picked = check.sample(recs, seed, sv["check_tokens"], sv["check_requests"])
    params = weights.make(cell.arch, cfg, seed)
    gap, compared = (check.widest_gap(cell.arch, cfg, params, picked, sv["max_len"])
                     if picked else (float("inf"), 0))
    out = {
        "logit_gap": {"value": gap, "limit": cfg["check"]["logit_gap_limit"]},
        "tokens_compared": {"value": compared, "limit": sv["check_tokens"]},
        "unfinished": {"value": len(recs) - len(done), "limit": 0},
        "short_outputs": {"value": sum(len(r.generated) != r.spec.max_new_tokens
                                       for r in done), "limit": 0},
    }
    if control and picked:
        out["control_gap"] = {"value": check.control_gap(cell.arch, cfg, params, picked,
                                                         sv["max_len"]),
                              "limit": cfg["check"]["logit_gap_limit"]}
    log(f"reference over {len(picked)} requests ({compared} served tokens): "
        f"{time.time() - t0:.1f} s")
    return out


def passes(checks: dict) -> bool:
    return bool(checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
                and checks["tokens_compared"]["value"] >= checks["tokens_compared"]["limit"]
                and checks["unfinished"]["value"] == 0
                and checks["short_outputs"]["value"] == 0)


def run_cell(cell, seed: int, seconds: float, trace: bool, *, device_kind: str,
             trace_dir: Path = TRACE_DIR, keep_trace: bool = False,
             control: bool = False) -> dict:
    """One run of ``cell``: the result object (not printed)."""
    import jax

    from bench import devtrace, loadgen, records

    srv = Serving(cell, seed)
    mix = srv.mix()
    srv.warm(mix, seconds)
    specs = loadgen.generate(mix, seed, seconds, cell.config["vocab_size"])
    window, stats = srv.window(mix, specs, seconds,
                               trace_dir=trace_dir if trace else None)
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    describe(window, stats)
    recs = window.records
    done = sum(r.done for r in recs)
    result: dict = {"correct": False, "attempted": len(recs), "failed": len(recs) - done}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    if trace:
        t0 = time.time()
        reduced = devtrace.reduce(srv.load_trace(trace_dir))
        log(f"trace read in {time.time() - t0:.1f} s")
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = per_layer(cell, window, stats, reduced, device_kind, mem)
        if reduced is not None:
            device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
            result["breakdown"] = {"device_ops": reduced.device_ops,
                                   "idle_gaps": reduced.idle_gaps}
            log("device programs (s): " + json.dumps(reduced.device_ops))
            log("idle gaps by host span (s): " + json.dumps(reduced.idle_gaps))
    else:
        e2e = {**records.end_to_end(window), "setup_s": stats["setup_s"]}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    for k, v in result["metrics"].items():
        log(f"{k}: {v['value']} {v['unit']}")
    srv.close()
    checks = correctness(cell, seed, window, control=control)
    result["correct"] = passes(checks)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        log(f"bench: no repro package under {SRC}")
        return 2
    from bench import spec

    cell = spec.cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: needs {cell.chips} TPU chip(s), JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}; cell {cell.name}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    trace_dir = Path(args.trace_dir) if args.trace_dir else TRACE_DIR
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device_kind=devices[0].device_kind, trace_dir=trace_dir,
                      keep_trace=args.trace_dir is not None)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
