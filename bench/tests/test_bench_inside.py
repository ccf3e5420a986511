"""``bench/inside.py``: the split of a traced run by the program's own
spans, counters and named scopes."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench import devtrace, inside
from bench.tests.test_bench_run import SEED, _cell

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6    # ns


def _synthetic() -> inside.Scoped:
    ops = [[("fusion.1", 10 * MS, 14 * MS), ("fusion.2", 14 * MS, 20 * MS),
            ("fusion.3", 20 * MS, 22 * MS), ("fusion.4", 22 * MS, 30 * MS),
            ("fusion.5", 50 * MS, 60 * MS),
            ("fusion.6", 95 * MS, 100 * MS), ("copy.7", 100 * MS, 120 * MS)]]
    scopes = [["attn.qkv", "attn.core", "attn.kv_write", "mlp", "attn.core",
               "attn.core", None]]
    programs = [[("jit_decode_fused_k4_paged", 10 * MS, 30 * MS),
                 ("jit_prefill_chunk", 50 * MS, 60 * MS),
                 ("jit_decode_fused_k4_paged", 95 * MS, 120 * MS)]]
    spans = [(devtrace.WINDOW_SPAN, 0.0, 100 * MS),
             ("engine.step", 5 * MS, 45 * MS),          # the benchmark's
             ("engine.step", 6 * MS, 44 * MS),          # the program's
             ("engine.decode.launch", 8 * MS, 31 * MS),
             ("hsa.exec", 9 * MS, 31 * MS),
             ("engine.decode.commit", 31 * MS, 40 * MS),
             ("bench.idle", 70 * MS, 90 * MS)]
    return inside.Scoped(devtrace.Trace(ops, programs, spans), scopes)


def _old_excerpt() -> inside.Scoped:
    trace = devtrace.Trace.from_json((DATA / "trace_chip_excerpt.json").read_text())
    return inside.Scoped(trace, [[None] * len(dev) for dev in trace.ops])


def test_idle_by_every_span():
    idle = inside.idle_by_span(_synthetic().trace)
    assert idle == pytest.approx({"bench.idle": 0.035,               # [60, 95]
                                  "engine.decode.commit": 0.020,     # [30, 50]
                                  "engine.step": 0.010})             # [0, 10]
    assert inside.in_program(idle) == pytest.approx(0.030)
    assert inside.phase_share(idle) == pytest.approx(2 / 3)


def test_a_span_is_found_behind_many_closed_ones():
    """The benchmark's breakdown looks back 64 spans; the split looks as far
    as it must."""
    spans = [("engine.step", 0.0, 1000.0)] + [
        ("hsa.submit", 1.0 + i, 1.5 + i) for i in range(100)]
    assert inside.Innermost(spans).at(500.0) == "engine.step"


def test_fused_decode_by_scope():
    scoped = _synthetic()
    n, by_scope = inside.scope_seconds(scoped, inside.FUSED_DECODE)
    assert n == 1                    # the second launch ends after the window
    assert by_scope == pytest.approx({"mlp": 0.008, "attn.core": 0.006,
                                      "attn.qkv": 0.004, "attn.kv_write": 0.002})
    assert inside.attention_ms((n, by_scope)) == pytest.approx(8.0)
    n, by_scope = inside.scope_seconds(scoped, inside.FUSED_DECODE,
                                       window=(0.0, 200 * MS))
    assert n == 2
    assert by_scope["attn.core"] == pytest.approx(0.011)
    assert by_scope[inside.NO_SCOPE] == pytest.approx(0.020)
    assert inside.scope_seconds(scoped, "prefill_chunk")[1] == pytest.approx(
        {"attn.core": 0.010})


def test_a_trace_without_the_programs_spans_or_scopes_reads_nothing():
    old = _old_excerpt()
    idle = inside.idle_by_span(old.trace)
    assert set(idle) <= {"engine.step", "bench.idle", "bench.submit", "no span"}
    assert inside.in_program(idle) is None
    assert inside.phase_share(idle) is None
    assert inside.scope_seconds(old, inside.FUSED_DECODE) is None
    assert inside.attention_ms(None) is None


def test_json_round_trip():
    scoped = _synthetic()
    assert inside.Scoped.from_json(scoped.to_json()) == scoped


def test_ttft_splits_into_queue_wait_and_prefill():
    recs = [types.SimpleNamespace(uid=u, due=0.0, first_token=f)
            for u, f in ((1, 1.0), (2, 2.0), (3, None))]
    split = inside.ttft_split(recs, {1: 0.25, 2: 0.5}, drain_end=9.0)
    assert split["queue_wait_p95_s"] == 9.0      # never admitted: to the drain end
    assert split["queue_wait_p50_s"] == 0.5
    assert split["prefill_p95_s"] == 1.5
    assert inside.ttft_split(recs, {}, drain_end=9.0) is None


def test_scopes_are_read_from_the_compiled_hlo():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attn.core"):
            y = x @ x
        with jax.named_scope("mlp"):
            return jnp.tanh(y) * 2

    names = inside.hlo_op_names(f.lower(jnp.ones((8, 8))).compile().as_text())
    assert {inside.scope_of(v) for v in names.values()} >= {"attn.core", "mlp"}
    assert inside.scope_of("jit(f)/while/body/attn.core/mlp/dot") == "mlp"
    assert inside.scope_of("jit(f)/dot_general") is None


def test_split_of_a_tiny_run_on_the_cpu(tmp_path):
    """The program's counters and admission times reach the split; the CPU
    has no device plane, so the trace's parts read nothing."""
    out = inside.split_cell(_cell(), SEED, 2.0, trace_dir=tmp_path / "trace")
    assert out["steps"] >= out["window_steps"] > 0
    assert out["host_syncs_per_step"] > 1
    assert 0.0 <= out["queue_wait_p50_s"] <= out["queue_wait_p95_s"]
    assert 0.0 < out["prefill_p50_s"] <= out["prefill_p95_s"]
    assert out["prefill_p95_s"] <= out["tails"]["ttft_p95_s"]
    assert out["idle_by_span_s"] is None and out["fused_decode"] is None
    assert out["step_host_gap_ms"] is None and out["decode_attention_ms"] is None


def test_an_excerpt_keeps_the_programs_steps():
    cut = inside.excerpt(_synthetic(), steps=1)
    assert devtrace.window_of(cut.trace) == (5 * MS, 45 * MS)
    assert [op[0] for op in cut.trace.ops[0]] == ["fusion.1", "fusion.2",
                                                  "fusion.3", "fusion.4"]
    assert cut.scopes == [["attn.qkv", "attn.core", "attn.kv_write", "mlp"]]
    # the program's step and the benchmark's around it
    assert [s[0] for s in cut.trace.spans].count("engine.step") == 2


def test_a_loop_counts_only_its_own_time():
    """On the TPU a loop is an operation around its body's operations: the
    split counts each operation's time less what ran inside it."""
    ops = [("while.1", 0.0, 100.0), ("while.2", 10.0, 90.0),
           ("fusion.3", 20.0, 50.0), ("fusion.4", 50.0, 80.0),
           ("copy.5", 95.0, 100.0)]
    assert inside.self_times(ops) == [15.0, 20.0, 30.0, 30.0, 5.0]
    scoped = inside.Scoped(
        devtrace.Trace([ops], [[("jit_decode_fused_k4_paged", 0.0, 100.0)]],
                       [(devtrace.WINDOW_SPAN, 0.0, 100.0)]),
        [[None, None, "attn.core", "mlp", None]])
    n, by_scope = inside.scope_seconds(scoped, inside.FUSED_DECODE)
    assert n == 1 and sum(by_scope.values()) == pytest.approx(100e-9)
    assert by_scope == pytest.approx({inside.NO_SCOPE: 40e-9, "attn.core": 30e-9,
                                      "mlp": 30e-9})


def test_one_step_on_the_chip():
    """One engine step of ``yi6b.chat-shared`` on a v5e (a prefill chunk, a
    first token, a fused decode), recorded with the program's spans.  Its
    scopes were read from the fused decode compiled for a described v5e:
    every one of the traced operations' instructions, shapes included, is
    in that program."""
    scoped = inside.Scoped.from_json(
        (DATA / "trace_chip_scoped_excerpt.json").read_text())
    idle = inside.idle_by_span(scoped.trace)
    assert {"engine.chunk", "engine.decode.commit", "hsa.exec"} <= set(idle)
    assert inside.phase_share(idle) > 0.8
    assert inside.in_program(idle) == pytest.approx(
        sum(v for k, v in idle.items() if k != "no span"))
    n, by_scope = inside.scope_seconds(scoped, inside.FUSED_DECODE)
    (launch,) = [b - a for name, a, b in scoped.trace.programs[0]
                 if "decode_fused" in name]
    assert n == 1 and sum(by_scope.values()) == pytest.approx(launch * 1e-9, rel=1e-4)
    assert set(by_scope) == set(inside.SCOPES) - {"sample"} | {inside.NO_SCOPE}
    assert inside.attention_ms((n, by_scope)) == pytest.approx(49.05, abs=0.01)
    # the prefill chunk was traced without its scopes: it reads as unscoped
    assert set(inside.scope_seconds(scoped, "prefill_chunk")[1]) == {inside.NO_SCOPE}
