"""The per-layer readers of the program's own spans, counters, scopes and
admission times, and what the harness hands them: ``Record.admit``, the
window's ledger counters, and a reader of a scope or counter that is new
needing no edit of the harness."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import devtrace, driver, loadgen, run, spec
from bench.tests.test_bench_devtrace import DATA, MS, scoped_synthetic
from bench.tests.test_bench_run import MIX, SEED, SERVING, TINY, _cell


def _read(name: str, ctx):
    return spec.metric_reader(name)(ctx)


def _record(uid: int, due: float, first: float | None, admit: float | None):
    spec_ = loadgen.RequestSpec(uid, np.zeros(4, np.int32), 2, due=due)
    return driver.Record(spec_, due, due, uid, first, first, [0, 1] if first else None,
                         admit=admit)


def _ctx(records=(), reduced=None, counters=None, steps=(2, 1), drain_end=9.0):
    window = driver.Window(0.0, 5.0, list(records), drain_end, *steps)
    return run.Context(window=window, reduced=reduced, counters=counters or {})


def test_ttft_splits_into_queue_wait_and_prefill():
    recs = [_record(1, 0.0, 1.0, 0.25), _record(2, 0.0, 2.0, 0.5),
            _record(3, 0.0, None, None)]
    ctx = _ctx(recs)
    assert _read("queue_wait_p95_s", ctx) == 9.0      # never admitted: to the drain end
    assert _read("prefill_p95_s", ctx) == 1.5
    ctx = _ctx([_record(1, 0.0, 1.0, None)])          # no admission time given
    assert _read("queue_wait_p95_s", ctx) is None
    assert _read("prefill_p95_s", ctx) is None


def test_step_readers_on_a_traced_step():
    red = devtrace.reduce(scoped_synthetic())
    ctx = _ctx(reduced=red, counters={"host_syncs": 12.0}, steps=(2, 1))
    # 30 ms of idle under the program's spans over 3 steps
    assert _read("step_host_gap_ms", ctx) == pytest.approx(10.0)
    assert _read("host_syncs_per_step", ctx) == pytest.approx(4.0)
    # attn.core 6 ms + attn.kv_write 2 ms in the one launch inside the window
    assert _read("decode_attention_ms", ctx) == pytest.approx(8.0)


def test_a_trace_without_the_programs_spans_or_scopes_reads_nothing():
    old = devtrace.reduce(devtrace.Trace.from_json(
        (DATA / "trace_chip_excerpt.json").read_text()))
    for reduced in (old, None):
        ctx = _ctx(reduced=reduced)
        assert _read("step_host_gap_ms", ctx) is None
        assert _read("decode_attention_ms", ctx) is None


def test_the_recorded_step_reads_as_before():
    """``decode_attention_ms`` of the recorded v5e step as ``bench/inside.py``
    read it before its reduction moved to ``bench/devtrace.py``."""
    trace = devtrace.Trace.from_json((DATA / "trace_chip_scoped_excerpt.json").read_text())
    ctx = _ctx(reduced=devtrace.reduce(trace))
    assert _read("decode_attention_ms", ctx) == pytest.approx(49.04754499999999, rel=1e-9)


NEW_READERS = {
    "moe_experts_ms": '''
def read(ctx):
    by = ctx.reduced.scopes["jit_decode_fused_k4_paged"]
    return 1e3 * by["moe.experts"]
''',
    "routed_per_step": '''
def read(ctx):
    return ctx.counters["moe.routed_tokens"] / ctx.window.steps
''',
}


def test_a_new_scope_and_counter_need_only_a_reader(tmp_path):
    """A reader of a named scope and of a ledger counter that no reader had
    before: adding its file and its entry is all it takes."""
    bench = tmp_path / "bench"
    (bench / "metrics").mkdir(parents=True)
    for name, source in NEW_READERS.items():
        (bench / "metrics" / f"{name}.py").write_text(source)
    entries = [{"name": n, "unit": "ms", "better": "lower", "source": "device_trace",
                "layer": "Model step", "moves": "tpot_p95_ms"} for n in NEW_READERS]
    cell = _cell()
    cell.per_layer, cell.bench = entries, bench
    ops = [[("fusion.1", 0.0, 3 * MS), ("fusion.2", 3 * MS, 4 * MS)]]
    names = [["jit(decode_fused_k4_paged)/while/body/moe.experts/dot_general",
              "jit(decode_fused_k4_paged)/while/body/moe.router/dot_general"]]
    trace = devtrace.Trace(ops, [[("jit_decode_fused_k4_paged", 0.0, 4 * MS)]],
                           [(devtrace.WINDOW_SPAN, 0.0, 5 * MS)], names)
    stats = {"window_compiles": 0, "prefix_pages_saved": 0, "lookups": None, "hsa": {},
             "counters": {"moe.routed_tokens": 48.0}}
    window = driver.Window(0.0, 5.0, [], 5.0, 4, 0)
    out = run.per_layer(cell, window, stats, devtrace.reduce(trace), "TPU v5 lite", {})
    assert out == {"moe_experts_ms": {"value": pytest.approx(3.0), "unit": "ms"},
                   "routed_per_step": {"value": 12.0, "unit": "ms"}}


def test_counters_of_two_ledgers_by_their_names():
    from repro.core import ledger as L

    engine, hsa = L.OverheadLedger(), L.OverheadLedger()
    engine.record_engine_step()
    engine.record_host_syncs(3)
    hsa.record_host_syncs(2)
    hsa.record(L.DISPATCH_SUBMIT, 0.25)
    hsa.record(L.DISPATCH_SUBMIT, 0.5)
    out = run.counters(engine, hsa)
    assert out["engine_steps"] == 1 and out["host_syncs"] == 5
    assert out["dispatch_submit.count"] == 2
    assert out["dispatch_submit.total_s"] == pytest.approx(0.75)
    assert "host_syncs_per_step" not in out          # a ratio, not a counter


@pytest.fixture
def routed_tokens(monkeypatch):
    """A ledger counter that the program does not have today: two tokens
    routed per engine step."""
    from repro.core import ledger as L

    monkeypatch.setattr(L.OverheadLedger, "_SYNC_ZERO",
                        {**L.OverheadLedger._SYNC_ZERO, "routed_tokens": 0.0})
    step = L.OverheadLedger.record_engine_step

    def record_engine_step(self):
        step(self)
        with self._lock:
            self._sync["routed_tokens"] += 2.0

    monkeypatch.setattr(L.OverheadLedger, "record_engine_step", record_engine_step)


@pytest.mark.parametrize("prefill_chunk", [SERVING["prefill_chunk"], None],
                         ids=["chunked", "whole-prompt"])
def test_a_window_counts_its_own_and_each_request_its_admission(prefill_chunk,
                                                                routed_tokens):
    """On the CPU at a tiny size: every request of the window records when
    a slot took it, whether its prompt is prefilled in chunks or whole;
    the counters are what the window and its drain added (a count made
    before the window does not show), a new counter among them."""
    cell = _cell()
    cell.serving = {**SERVING, "prefill_chunk": prefill_chunk}
    srv = run.Serving(cell, SEED)
    try:
        srv.engine_log.record_host_syncs(10**6)
        mix = srv.mix()
        specs = loadgen.generate(mix, SEED, 1.0, TINY["vocab_size"])
        window, stats = srv.window(mix, specs, 1.0)
    finally:
        srv.close()
    recs = window.records
    assert len(recs) == round(MIX["rate_per_s"]) and all(r.done for r in recs)
    for r in recs:
        assert r.due <= r.submitted <= r.admit <= r.first_token <= r.finish
    counts = stats["counters"]
    steps = window.steps + window.drain_steps
    assert window.steps > 0 and counts["engine_steps"] == steps
    assert counts["routed_tokens"] == 2 * steps
    assert 0 < counts["host_syncs"] < 10**6
    json.dumps(counts)


def test_the_fused_decode_names_its_operations():
    """Where the trace gives no ``op_name`` (the v5e's), it is read from the
    compiled fused decode of the programs that ran: every scope the
    attention reader takes is there.  Only the fused decode's: another
    program's operations read ``NO_SCOPE``."""
    srv = run.Serving(_cell(), SEED)
    try:
        uid = srv.engine.submit(list(range(1, 40)), max_new_tokens=6)
        for _ in range(50):                 # the engine makes its pool on first use
            if any(r.uid == uid for r in srv.engine.step()):
                break
        trace = devtrace.Trace([[]], [[("jit_decode_fused_k4_paged", 0.0, 1.0)]], [])
        hlo = srv.fused_decode_op_names(trace)
    finally:
        srv.close()
    assert set(hlo) == {"jit_decode_fused_k4_paged"}
    names = hlo["jit_decode_fused_k4_paged"]
    scopes = {c for name in names.values() for c in name.split("/")}
    assert {"attn.kv_write", "attn.core", "mlp"} <= scopes
    # through the reduction: an operation of the fused decode counts under
    # every component of its op_name; one of another program, whose HLO
    # was not read, under NO_SCOPE alone
    instr, op_name = next((k, v) for k, v in sorted(names.items()) if "attn.core" in v)
    trace = devtrace.Trace(
        [[(f"%{instr} = bf16[8] fusion(...)", 1 * MS, 3 * MS),
          (f"%{instr} = bf16[8] fusion(...)", 5 * MS, 6 * MS)]],
        [[("jit_decode_fused_k4_paged", 0.0, 4 * MS), ("jit_prefill_chunk", 4 * MS, 7 * MS)]],
        [(devtrace.WINDOW_SPAN, 0.0, 8 * MS)])
    red = devtrace.reduce(devtrace.name_ops(trace, hlo))
    assert red.scopes["jit_decode_fused_k4_paged"] == pytest.approx(
        {c: 0.002 for c in op_name.split("/")})
    assert red.scopes["jit_prefill_chunk"] == pytest.approx({devtrace.NO_SCOPE: 0.001})
