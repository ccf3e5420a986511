"""Configurations, architecture modules, traffic mixes, cells and
per-layer metrics are found by name: a new one is a new file, with no edit
to the harness."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]


def test_new_files_are_taken_as_data(tmp_path):
    bench = tmp_path / "bench"
    for d in ("archs", "configs", "traffic", "cells", "metrics"):
        (bench / d).mkdir(parents=True)
    model = {"num_layers": 2, "d_model": 64, "num_heads": 2, "num_kv_heads": 1,
             "head_dim": 32, "d_ff": 128, "vocab_size": 100, "rope_theta": 1e4,
             "norm_eps": 1e-5, "tie_embeddings": True, "dtype": "bfloat16"}
    (bench / "archs" / "new_arch.py").write_text(
        "FAMILY = 'new'\n\n\ndef shapes(cfg):\n    return cfg['d_model'] * 3\n")
    (bench / "configs" / "new-model.json").write_text(json.dumps(
        {"arch": "new_arch", "model": model, "check": {"logit_gap_limit": 0.1}}))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps({"loop": "open"}))
    (bench / "cells" / "new.cell.json").write_text(
        json.dumps({"batch_slots": 3, "max_len": 512}))
    (bench / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx.x * 2\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "new.cell", "config": "new-model",
                       "traffic": "new_mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "new.metric", "unit": "%"},
                      {"name": "elsewhere", "unit": "%", "workloads": ["other"]}],
    }))
    cell = spec.cell(tmp_path, "new.cell", bench=bench)
    assert cell.config["d_model"] == 64 and cell.config["name"] == "new-model"
    assert cell.config["check"] == {"logit_gap_limit": 0.1}
    assert cell.config["arch"] == "new_arch"
    assert cell.arch.FAMILY == "new" and cell.arch.shapes(cell.config) == 192
    assert spec.model(cell.config) == model
    assert cell.mix == {"loop": "open"}
    assert cell.serving["batch_slots"] == 3
    assert cell.serving["prefill_chunk"] == spec.ENGINE_DEFAULTS["prefill_chunk"]
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]

    class Ctx:
        x = 21

    assert spec.metric_reader("new.metric", bench=bench)(Ctx()) == 42


def test_every_named_file_exists():
    bm = spec.benchmark(ROOT)
    for w in bm["workloads"]:
        cell = spec.cell(ROOT, w["name"])
        assert cell.serving["max_len"] % 512 == 0      # the reference's blocks
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
    for m in bm["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for w in bm["workloads"]:
        cell = spec.cell(ROOT, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_a_configuration_names_its_architecture(tmp_path):
    """No configuration runs as dense GQA by default: one without ``arch``
    is refused."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "bare.json").write_text(json.dumps(
        {"model": {"d_model": 64}, "check": {"logit_gap_limit": 0.1}}))
    with pytest.raises(ValueError, match="architecture"):
        spec.config("bare", bench=tmp_path)
    for c in spec.benchmark(ROOT)["configs"]:
        cfg = spec.config(c["name"])
        assert (spec.BENCH / "archs" / f"{cfg['arch']}.py").is_file()
