"""``bench/inside.py``: the split of one traced run by the program's own
spans, counters and named scopes (the reduction itself is
``bench/devtrace.py``'s, the metrics the readers')."""

from __future__ import annotations

import pytest

from bench import devtrace, inside
from bench.tests.test_bench_devtrace import DATA, MS, scoped_synthetic
from bench.tests.test_bench_run import SEED, _cell


def _recorded(name: str) -> devtrace.Reduced:
    return devtrace.reduce(devtrace.Trace.from_json((DATA / name).read_text()))


def test_phase_share_of_the_step_idle():
    red = devtrace.reduce(scoped_synthetic())
    # 20 ms under engine.decode.commit, 10 under the bare engine.step
    assert inside.phase_share(red) == pytest.approx(2 / 3)
    assert inside.phase_share(None) is None


def test_phase_share_of_the_recorded_steps():
    """The v5e step with the program's spans: a phase of the step carries
    most of its idle; the older trace has no span of the program's."""
    assert inside.phase_share(_recorded("trace_chip_scoped_excerpt.json")) > 0.8
    assert inside.phase_share(_recorded("trace_chip_excerpt.json")) is None


def test_split_of_a_tiny_run_on_the_cpu(tmp_path):
    """The program's counters and admission times reach the split; the CPU
    has no device plane, so the trace's parts read nothing."""
    out = inside.split_cell(_cell(), SEED, 2.0, device_kind="TPU v5 lite",
                            trace_dir=tmp_path / "trace")
    assert out["steps"] >= out["window_steps"] > 0
    assert out["counters"]["engine_steps"] == out["steps"]
    assert out["host_syncs_per_step"] > 1
    assert 0.0 <= out["queue_wait_p50_s"] <= out["queue_wait_p95_s"]
    assert 0.0 < out["prefill_p50_s"] <= out["prefill_p95_s"]
    assert out["prefill_p95_s"] <= out["tails"]["ttft_p95_s"]
    assert out["idle_by_span_s"] is None and out["fused_decode"] is None
    assert "step_host_gap_ms" not in out and "decode_attention_ms" not in out


def test_an_excerpt_keeps_the_programs_steps():
    cut = inside.excerpt(scoped_synthetic(), steps=1)
    assert devtrace.window_of(cut) == (5 * MS, 45 * MS)
    assert [op[0] for op in cut.ops[0]] == ["fusion.1", "fusion.2",
                                            "fusion.3", "fusion.4"]
    assert cut.op_names == [["attn.qkv", "attn.core", "attn.kv_write", "mlp"]]
    # the program's step and the benchmark's around it
    assert [s[0] for s in cut.spans].count("engine.step") == 2
