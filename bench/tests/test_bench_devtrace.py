"""The reduction from a device trace to busy time, program times and idle
gaps."""

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


def test_json_round_trip():
    t = _synthetic()
    assert devtrace.Trace.from_json(t.to_json()) == t


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
