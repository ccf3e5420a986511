"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, and its
one-chip phases pass at a tiny size (Pallas in interpret mode)."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_arch, reduced
from repro.core import dispatch

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses look it up there
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def _json_lines(text: str) -> list[str]:
    return [ln for ln in text.splitlines() if ln.startswith("{")]


def test_exits_nonzero_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert _json_lines(out.out) == []
    assert "needs a TPU" in out.err


def test_exits_nonzero_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    run = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert run.returncode != 0
    assert _json_lines(run.stdout) == []


def test_one_chip_phases_at_tiny_size(smoke):
    cfg = dataclasses.replace(
        reduced(get_arch("yi-6b"), layers=2, d_model=128, vocab=512),
        num_heads=4, num_kv_heads=1, head_dim=32,
    )
    plan = smoke.ServePlan(
        layers=2, slots=4, max_len=128, page_size=8, chunk=16,
        lengths=(12, 20, 32), prefix_len=32, tails=(5, 9), max_new=13,
        check_len=32, check_steps=3, kernel_page_sizes=(8,),
    )
    lines: list[str] = []
    model, params = smoke.init_model(cfg, 0)
    trace = dispatch.DispatchTrace()
    with dispatch.use(trace=trace):
        done = smoke.serve(model, params, plan, 0, lines.append)
        target = next(r for r in done if len(r.prompt) == plan.check_len)
        smoke.check_logits(model, params, plan, target, lines.append)
    assert len(done) == len(plan.lengths) + 2
    assert any("prefix hits 1" in ln for ln in lines), lines
    sources = {name for _, name in trace.events}
    assert "paged_decode_attention:xla:generic" in sources
    kernel_trace = dispatch.DispatchTrace()
    smoke.check_kernel(cfg, plan, 0, interpret=True, trace=kernel_trace,
                       log=lines.append)
    assert {n for _, n in kernel_trace.events} == {
        "paged_decode_attention:pallas:generic"
    }


def test_logit_check_catches_wrong_logits(smoke, monkeypatch):
    """Logits of other tokens are far outside the bounds."""
    from repro.models import reference

    cfg = dataclasses.replace(
        reduced(get_arch("yi-6b"), layers=2, d_model=128, vocab=512),
        num_heads=4, num_kv_heads=1, head_dim=32,
    )
    plan = smoke.ServePlan(layers=2, max_len=64, page_size=8, chunk=16,
                           check_len=32, check_steps=2)
    model, params = smoke.init_model(cfg, 1)
    request = type("Req", (), {"prompt": list(range(1, 33)),
                               "generated": [5, 6, 7]})
    smoke.check_logits(model, params, plan, request, lambda _: None)

    true_logits = reference.logits
    monkeypatch.setattr(reference, "logits",
                        lambda cfg, p, toks: true_logits(cfg, p, toks + 1))
    with pytest.raises(smoke.SmokeFailure, match="logits off the reference"):
        smoke.check_logits(model, params, plan, request, lambda _: None)
