"""The reduction from a device trace to busy time, program times, idle
gaps, idle by host span and device time by named scope."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import devtrace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6    # ns


def _synthetic():
    ops = [[("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 15 * MS, 30 * MS),
            ("copy.3", 50 * MS, 60 * MS), ("fusion.4", 95 * MS, 120 * MS)]]
    programs = [[("jit_decode_fused_k4_paged", 10 * MS, 30 * MS),
                 ("jit_prefill_chunk", 50 * MS, 60 * MS),
                 ("jit_decode_fused_k4_paged", 95 * MS, 120 * MS)]]
    spans = [(devtrace.WINDOW_SPAN, 0.0, 100 * MS),
             ("engine.step", 6 * MS, 45 * MS),
             ("bench.idle", 70 * MS, 90 * MS)]
    return devtrace.Trace(ops, programs, spans)


def test_busy_union_and_idle_gaps():
    red = devtrace.reduce(_synthetic())
    assert red.window_s == pytest.approx(0.100)
    # [10, 30] and [50, 60] and [95, 100] (clipped at the window's end)
    assert red.busy_s == pytest.approx(0.035)
    assert red.idle_share == pytest.approx(0.65)
    gaps = dict(red.idle_gaps)
    # gaps are labelled at their midpoints: 5 ms opens no span yet
    assert gaps["engine.step"] == pytest.approx(0.020)     # [30, 50]
    assert gaps["no span"] == pytest.approx(0.010)         # [0, 10]
    assert gaps["bench.idle"] == pytest.approx(0.035)      # [60, 95]
    assert sum(gaps.values()) == pytest.approx(0.065)


def test_programs_inside_the_window_only():
    red = devtrace.reduce(_synthetic())
    assert red.programs["jit_decode_fused_k4_paged"] == (1, pytest.approx(0.020))
    assert devtrace.launches(red, "prefill_chunk") == (1, pytest.approx(0.010))
    assert red.device_ops[0][0] == "jit_decode_fused_k4_paged"


def test_no_window_or_no_device_reads_nothing():
    t = _synthetic()
    assert devtrace.reduce(devtrace.Trace(t.ops, t.programs, [])) is None
    assert devtrace.reduce(devtrace.Trace([], [], t.spans)) is None


def test_program_name_drops_the_launch_id():
    assert devtrace.program_name("jit_prefill_chunk(42)") == "jit_prefill_chunk"


@pytest.mark.skipif(not (DATA / "trace_chip_excerpt.json").exists(),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    t = devtrace.Trace.from_json((DATA / "trace_chip_excerpt.json").read_text())
    red = devtrace.reduce(t)
    assert 0.0 < red.busy_s <= red.window_s
    assert sum(s for _, s in red.idle_gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    # a prefill chunk and the eager whole-pool scatters that follow it
    assert red.device_ops[0][0] == "jit_prefill_chunk"
    assert devtrace.launches(red, "prefill_chunk")[0] == 1
    assert dict(red.device_ops)["jit_scatter"] > 0


# -- the program's spans, named scopes and own times --------------------------


def scoped_synthetic() -> devtrace.Trace:
    """Two fused decode launches and a prefill chunk whose operations
    carry their ``op_name``, under the program's spans nested in the
    benchmark's ``engine.step``."""
    ops = [[("fusion.1", 10 * MS, 14 * MS), ("fusion.2", 14 * MS, 20 * MS),
            ("fusion.3", 20 * MS, 22 * MS), ("fusion.4", 22 * MS, 30 * MS),
            ("fusion.5", 50 * MS, 60 * MS),
            ("fusion.6", 95 * MS, 100 * MS), ("copy.7", 100 * MS, 120 * MS)]]
    op_names = [["attn.qkv", "attn.core", "attn.kv_write", "mlp", "attn.core",
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
    return devtrace.Trace(ops, programs, spans, op_names)


@pytest.mark.parametrize("make", [_synthetic, scoped_synthetic])
def test_json_round_trip(make):
    t = make()
    assert devtrace.Trace.from_json(t.to_json()) == t


def test_idle_by_every_span():
    red = devtrace.reduce(scoped_synthetic())
    assert red.idle_by_span == pytest.approx({"bench.idle": 0.035,            # [60, 95]
                                              "engine.decode.commit": 0.020,  # [30, 50]
                                              "engine.step": 0.010})          # [0, 10]
    assert [k for k, _ in red.idle_gaps] == list(red.idle_by_span)
    assert devtrace.program_idle(red) == pytest.approx(0.030)


def test_a_span_is_found_behind_many_closed_ones():
    """A gap is labelled by the innermost span open at its midpoint, however
    many spans opened and closed inside that span before it."""
    spans = [("engine.step", 0.0, 1000.0)] + [
        ("hsa.submit", 1.0 + i, 1.5 + i) for i in range(100)]
    assert devtrace._Spans(spans).at([0.5, 50.2, 500.0, 1001.0]) == [
        "engine.step", "hsa.submit", "engine.step", devtrace.NO_SPAN]
    trace = devtrace.Trace([[("fusion.1", 0.0, 101.0), ("fusion.2", 999.0, 1000.0)]],
                           [[]], [(devtrace.WINDOW_SPAN, 0.0, 1000.0)] + spans)
    assert devtrace.reduce(trace).idle_by_span == pytest.approx({"engine.step": 898e-9})


def test_fused_decode_by_scope():
    trace = scoped_synthetic()
    red = devtrace.reduce(trace)
    # the second launch ends after the window
    assert red.scopes["jit_decode_fused_k4_paged"] == pytest.approx(
        {"mlp": 0.008, "attn.core": 0.006, "attn.qkv": 0.004, "attn.kv_write": 0.002})
    assert red.scopes["jit_prefill_chunk"] == pytest.approx({"attn.core": 0.010})
    red = devtrace.reduce(trace, window=(0.0, 200 * MS))
    assert red.programs["jit_decode_fused_k4_paged"][0] == 2
    fused = red.scopes["jit_decode_fused_k4_paged"]
    assert fused["attn.core"] == pytest.approx(0.011)
    assert fused[devtrace.NO_SCOPE] == pytest.approx(0.020)


def test_nested_scopes_count_under_each_component():
    ops = [("fusion.1", 0.0, 30.0), ("fusion.2", 30.0, 40.0), ("fusion.3", 40.0, 100.0)]
    names = ["jit(f)/while/body/attn/core/dot_general",
             "jit(f)/while/body/attn/out/dot_general", "jit(f)/while/body/mlp/mul"]
    trace = devtrace.Trace([ops], [[("jit_f", 0.0, 100.0)]],
                           [(devtrace.WINDOW_SPAN, 0.0, 100.0)], [names])
    by = devtrace.reduce(trace).scopes["jit_f"]
    assert by["attn"] == pytest.approx(40e-9)
    assert by["core"] == pytest.approx(30e-9) and by["mlp"] == pytest.approx(60e-9)
    assert by["jit(f)"] == by["while"] == pytest.approx(100e-9)
    assert by["dot_general"] == pytest.approx(40e-9)


def test_an_op_name_path_of_the_fused_decode():
    """An ``op_name`` as JAX writes it in the fused decode (its layer scan
    and the scan of its K steps each a ``while``): each component counts
    once, and the time outside the model's scopes is the launch less
    theirs, not ``NO_SCOPE``."""
    path = "jit(decode_fused_k4_paged)/while/body/closed_call/while/body/closed_call/"
    ops = [("fusion.1", 0.0, 30.0), ("copy.2", 30.0, 40.0), ("fusion.3", 40.0, 50.0)]
    names = [path + "attn.core/bkgt,bktd->bkgd/dot_general",
             "jit(decode_fused_k4_paged)/while/body/dynamic_update_slice", None]
    trace = devtrace.Trace([ops], [[("jit_decode_fused_k4_paged", 0.0, 50.0)]],
                           [(devtrace.WINDOW_SPAN, 0.0, 100.0)], [names])
    by = devtrace.reduce(trace).scopes["jit_decode_fused_k4_paged"]
    assert by == pytest.approx({
        "jit(decode_fused_k4_paged)": 40e-9, "while": 40e-9, "body": 40e-9,
        "closed_call": 30e-9, "attn.core": 30e-9, "bkgt,bktd->bkgd": 30e-9,
        "dot_general": 30e-9, "dynamic_update_slice": 10e-9, devtrace.NO_SCOPE: 10e-9})
    launch = devtrace.reduce(trace).programs["jit_decode_fused_k4_paged"][1]
    assert launch - by["attn.core"] == pytest.approx(20e-9)


def test_a_trace_without_the_programs_spans_or_scopes_reads_nothing():
    red = devtrace.reduce(devtrace.Trace.from_json(
        (DATA / "trace_chip_excerpt.json").read_text()))
    assert set(red.idle_by_span) <= {"engine.step", "bench.idle", "bench.submit",
                                     devtrace.NO_SPAN}
    assert devtrace.program_idle(red) is None
    assert all(set(by) == {devtrace.NO_SCOPE} for by in red.scopes.values())


def test_scopes_are_read_from_the_compiled_hlo():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attn.core"):
            y = x @ x
        with jax.named_scope("mlp"):
            return jnp.tanh(y) * 2

    names = devtrace.hlo_op_names(f.lower(jnp.ones((8, 8))).compile().as_text())
    components = {c for v in names.values() for c in v.split("/")}
    assert components >= {"attn.core", "mlp"}
    # a trace that names no op_name takes it from the program's HLO
    instr, op_name = next((k, v) for k, v in names.items() if "attn.core" in v)
    trace = devtrace.Trace([[(f"%{instr} = f32[8,8] fusion(...)", 1.0, 2.0),
                             ("copy.9", 3.0, 4.0)]],
                           [[("jit_f", 0.0, 5.0)]], [(devtrace.WINDOW_SPAN, 0.0, 5.0)])
    assert not devtrace.named(trace)
    named = devtrace.name_ops(trace, {"jit_f": names})
    assert named.op_names == [[op_name, None]] and devtrace.named(named)
    assert devtrace.reduce(named).scopes["jit_f"]["attn.core"] == pytest.approx(1e-9)


def test_a_loop_counts_only_its_own_time():
    """On the TPU a loop is an operation around its body's operations: each
    operation counts its time less what ran inside it."""
    ops = [("while.1", 0.0, 100.0), ("while.2", 10.0, 90.0),
           ("fusion.3", 20.0, 50.0), ("fusion.4", 50.0, 80.0),
           ("copy.5", 95.0, 100.0)]
    assert devtrace.self_times(ops) == [15.0, 20.0, 30.0, 30.0, 5.0]
    trace = devtrace.Trace([ops], [[("jit_decode_fused_k4_paged", 0.0, 100.0)]],
                           [(devtrace.WINDOW_SPAN, 0.0, 100.0)],
                           [[None, None, "attn.core", "mlp", None]])
    by = devtrace.reduce(trace).scopes["jit_decode_fused_k4_paged"]
    assert sum(by.values()) == pytest.approx(100e-9)
    assert by == pytest.approx({devtrace.NO_SCOPE: 40e-9, "attn.core": 30e-9,
                                "mlp": 30e-9})


def test_one_step_on_the_chip():
    """One engine step of ``yi6b.chat-shared`` on a v5e (a prefill chunk, a
    first token, a fused decode) with the program's spans; of each
    operation's ``op_name`` it keeps the model's scope alone.  Read by
    scope as ``bench/inside.py`` read it before the reduction moved here
    (its split, pinned)."""
    red = devtrace.reduce(devtrace.Trace.from_json(
        (DATA / "trace_chip_scoped_excerpt.json").read_text()))
    assert {"engine.chunk", "engine.decode.commit", "hsa.exec"} <= set(red.idle_by_span)
    assert devtrace.program_idle(red) == pytest.approx(
        sum(v for k, v in red.idle_by_span.items() if k != devtrace.NO_SPAN))
    n, launch = red.programs["jit_decode_fused_k4_paged"]
    by = red.scopes["jit_decode_fused_k4_paged"]
    assert n == 1 and sum(by.values()) == pytest.approx(launch, rel=1e-4)
    assert by == pytest.approx({
        devtrace.NO_SCOPE: 0.06623133299999835, "attn.core": 0.04890096199999999,
        "mlp": 0.023178775, "attn.qkv": 0.0033036770000000023,
        "attn.out": 0.0029394710000000004, "unembed": 0.002779256,
        "attn.kv_write": 0.0001465829999999997}, rel=1e-9)
    # the prefill chunk was traced without its scopes: it reads as unscoped
    assert red.scopes["jit_prefill_chunk"] == pytest.approx(
        {devtrace.NO_SCOPE: 0.02160024199999999}, rel=1e-9)
