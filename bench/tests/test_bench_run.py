"""``bench/run.py`` end to end on the CPU at a tiny size.

The harness's look for a chip is skipped; the rest of a run (weights from
the seed, the engine on the HSA queue, warm-up, an open-loop window, the
drain, the per-layer readers and the check against the float32
reference) runs as on the chip.  The check must pass on the program as it
is, and must fail when a token is altered where the fused decode produces
it, when the fp8 control is put in the program's place, and when the
configuration names an architecture module whose reference is wrong.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run, spec, weights

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 5

TINY = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 1,
        "head_dim": 32, "d_ff": 256, "vocab_size": 512, "rope_theta": 10000.0,
        "norm_eps": 1e-5, "tie_embeddings": False, "dtype": "bfloat16",
        "name": "tiny", "arch": "dense_gqa",
        # the tiny model's own limit, between its readings on the CPU (widest
        # gap at most 0.030 over nine seeds) and the fp8 control's (at least
        # 0.19 on the same samples; see test_fp8_control_fails_the_check)
        "check": {"logit_gap_limit": 0.08}}
MIX = {"loop": "open", "rate_per_s": 8.0, "shape_seed": 1, "drain_s": 60,
       "shared_prefix": {"count": 2, "zipf_s": 1.0,
                         "length": {"median": 55, "sigma": 0.3, "min": 40, "max": 70}},
       "prompt": {"median": 24, "sigma": 0.2, "min": 20, "max": 28},
       "output": {"median": 12, "sigma": 0.3, "min": 6, "max": 20}}
SERVING = {**spec.ENGINE_DEFAULTS, "batch_slots": 4, "max_len": 512,
           "check_tokens": 40, "check_requests": 6}


def _cell():
    bm = spec.benchmark(ROOT)
    return spec.Cell("tiny", 1, TINY, spec.arch(TINY["arch"]), MIX, SERVING,
                     bm["end_to_end"], bm["per_layer"])


def _run(tmp_path, trace=False, cell=None):
    return run.run_cell(cell or _cell(), SEED, 2.0, trace, device_kind="TPU v5 lite",
                        trace_dir=tmp_path / "trace", control=True)


def _json_lines(text: str) -> list[str]:
    return [ln for ln in text.splitlines() if ln.startswith("{")]


def test_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b.chat-shared",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert _json_lines(out.stdout) == []
    assert "needs 1 TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b.chat-shared",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert _json_lines(out.stdout) == []


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("sound"), trace=True)


def test_a_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["attempted"] == round(MIX["rate_per_s"] * 2.0)
    assert sound["failed"] == 0
    assert list(sound)[-1] == "checks"
    checks = sound["checks"]
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    assert checks["tokens_compared"]["value"] >= SERVING["check_tokens"]
    json.dumps(sound)


def test_warm_up_leaves_nothing_to_compile_in_the_window(sound):
    metrics = sound["metrics"]
    assert metrics["window_compiles"]["value"] == 0
    # the CPU has no device trace: the readers of the trace stay silent
    assert "device_idle_share" not in metrics
    assert "decode_launch_ms" not in metrics
    assert 0 < metrics["prefix_token_share"]["value"] < 100
    assert metrics["mfu"]["value"] == pytest.approx(
        metrics["mfu.prefill"]["value"] + metrics["mfu.decode"]["value"])


def test_the_programs_own_readings_reach_the_result(sound):
    """Admission times and the window's ledger counters reach their readers;
    the readers of the device trace stay silent on the CPU."""
    metrics = sound["metrics"]
    assert 0.0 <= metrics["queue_wait_p95_s"]["value"]
    assert 0.0 < metrics["prefill_p95_s"]["value"]
    assert metrics["host_syncs_per_step"]["value"] > 1
    assert "step_host_gap_ms" not in metrics and "decode_attention_ms" not in metrics


def test_fp8_control_fails_the_check(sound):
    control = sound["checks"]["control_gap"]
    assert control["value"] > control["limit"]
    assert sound["checks"]["logit_gap"]["value"] < control["limit"]


def test_an_altered_token_fails_the_check(tmp_path, monkeypatch):
    from repro.serve.engine import ServeEngine

    produce = ServeEngine._fused_decode_fn

    def altered(self, k):
        fn = produce(self, k)

        def wrong(*args):
            segments, pos, tok, toks, valid = fn(*args)
            return segments, pos, tok, (toks + 1) % TINY["vocab_size"], valid
        return wrong

    monkeypatch.setattr(ServeEngine, "_fused_decode_fn", altered)
    res = _run(tmp_path)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TINY["check"]["logit_gap_limit"]


def test_reference_matches_the_program_reference():
    """The benchmark's float32 reference and the program's own compute the
    same logits (two independent writings of the same equations)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import reference as program_ref

    reference = spec.arch("dense_gqa")
    cfg = {**TINY, "tie_embeddings": True}
    params = weights.make(reference, cfg, SEED)
    arch = run.build_model(cfg, reference).cfg
    toks = jnp.asarray(np.arange(1, 41) * 7 % 500 + 1, jnp.int32)
    want = np.asarray(program_ref.logits(arch, params, toks))
    targets = jnp.asarray(np.argsort(want, axis=-1)[:, -2].astype(np.int32))
    gaps = np.asarray(reference.served_gaps(cfg, params, toks, targets, 512))[:40]
    best = want.max(-1)
    second = np.take_along_axis(want, np.asarray(targets)[:, None], -1)[:, 0]
    np.testing.assert_allclose(gaps, best - second, rtol=1e-4, atol=1e-5)
    assert jax.devices()[0].platform == "cpu"


def _bench_with_arch(tmp_path, name: str, source: str):
    """A ``bench/`` beside a ``BENCHMARK.json`` whose one cell runs the tiny
    model under the architecture module ``archs/<name>.py`` (``source``)."""
    bench = tmp_path / "bench"
    for d in ("archs", "configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    (bench / "archs" / f"{name}.py").write_text(source)
    model = {k: v for k, v in TINY.items() if k not in spec.HARNESS_KEYS}
    (bench / "configs" / "tiny.json").write_text(json.dumps(
        {"arch": name, "model": model, "check": TINY["check"]}))
    (bench / "traffic" / "tiny_mix.json").write_text(json.dumps(MIX))
    (bench / "cells" / "tiny.cell.json").write_text(json.dumps(
        {k: SERVING[k] for k in ("batch_slots", "max_len", "check_tokens",
                                 "check_requests")}))
    bm = spec.benchmark(ROOT)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        **bm, "workloads": [{"name": "tiny.cell", "config": "tiny",
                             "traffic": "tiny_mix", "chips": 1, "why": "test"}]}))
    return spec.cell(tmp_path, "tiny.cell", bench=bench)


@pytest.mark.parametrize("name, unscaled, correct", [
    ("dense_copy", False, True),
    ("dense_unscaled", True, False),
])
def test_the_named_module_decides_correct(tmp_path, name, unscaled, correct):
    """A configuration that names a module new to ``bench/archs/`` runs end
    to end with no edit to the harness, and its reference is the one the
    check uses: a copy of ``dense_gqa`` passes, and a copy whose attention
    drops the 1/sqrt(head_dim) scale fails."""
    source = (Path(spec.BENCH) / "archs" / "dense_gqa.py").read_text()
    if unscaled:
        scale = " / jnp.sqrt(jnp.float32(hd))"
        assert source.count(scale) == 1
        source = source.replace(scale, "")
    cell = _bench_with_arch(tmp_path, name, source)
    assert cell.arch.__name__ == f"bench_arch_{name}"
    res = _run(tmp_path, cell=cell)
    assert res["correct"] is correct
    gap = res["checks"]["logit_gap"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert res["checks"]["unfinished"]["value"] == 0


MOE = {"num_experts": 4, "experts_per_token": 2, "d_ff_expert": 64,
       "num_shared_experts": 1}


@pytest.mark.parametrize("extra, named", [
    ({"logits_scaling": 16.0}, "logits_scaling"),
    ({"moe": {**MOE, "n_group": 1}}, "moe.n_group"),
], ids=["top-level", "in-a-group"])
def test_a_key_the_program_lacks_fails_before_weights(monkeypatch, extra, named):
    """A model key that ``ArchConfig`` has no field for, at the top or
    inside a group, stops set-up, named, before any weights are drawn: it
    never runs as another model."""
    def draw(*args, **kwargs):
        raise AssertionError("weights drawn")

    monkeypatch.setattr(weights, "make", draw)
    cell = _cell()
    cell.config = {**TINY, **extra}
    with pytest.raises(ValueError, match=named):
        run.Serving(cell, SEED)


def test_a_group_reaches_the_program_as_its_dataclass():
    """A ``moe`` group of a configuration's ``model`` is built into the
    program's ``MoEConfig``, its defaults filled in."""
    from repro.configs.base import MoEConfig

    model = run.build_model({**TINY, "moe": MOE}, spec.arch(TINY["arch"]))
    assert model.cfg.moe == MoEConfig(**MOE)
    assert model.cfg.moe.first_k_dense == 0 and model.cfg.mla is None



def test_warm_up_when_the_pre_compile_cannot_call_the_scatter(tmp_path, monkeypatch):
    """Should the program's eager pool scatter change its arguments, only
    the parallel pre-compile is lost: the warm-up's serving still covers
    every shape the window uses."""
    import threading

    from repro.serve import paged

    engine_scatter = paged.scatter_chunk

    def scatter(*args):
        if threading.current_thread() is not threading.main_thread():
            raise TypeError("scatter_chunk() takes other arguments")
        return engine_scatter(*args)

    monkeypatch.setattr(paged, "scatter_chunk", scatter)
    res = _run(tmp_path, trace=True)
    assert res["correct"] is True
    assert res["metrics"]["window_compiles"]["value"] == 0
