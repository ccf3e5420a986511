"""Spans and counters inside ``ServeEngine`` and the HSA runtime.

A tiny engine (paged KV, chunked prefill, prefix sharing, every launch an
HSA packet) serves a few requests under ``jax.profiler`` on the CPU; the
``.xplane.pb`` it writes is read back with the benchmark's own trace
loader.  The spans nest under ``engine.step``, carry their request's uid,
and the ledger counts steps and host syncs.  Without a profiler the
ledger's categories and views read as they always did.
"""

import glob

import jax
import numpy as np
import pytest

from bench import devtrace
from repro.configs import ARCHS, reduced
from repro.core import ledger as L
from repro.core.hsa import Queue, Scheduler
from repro.core.reconfig import RegionManager
from repro.core.roles import RoleLibrary
from repro.models import build_model
from repro.models.params import init_params
from repro.serve.engine import ServeEngine

PREFIX = list(range(3, 19))                      # two full pages of 8
SUFFIXES = ([40, 41, 42, 43], [50, 51, 52, 53, 54])
REQUEST_SPANS = ("engine.chunk", "engine.prefix_copy", "engine.first_token",
                 "engine.prefill", "engine.resume")


@pytest.fixture(scope="module")
def engine_model():
    cfg = reduced(ARCHS["llama3.2-1b"], layers=2, d_model=64, vocab=128)
    model = build_model(cfg)
    return model, init_params(model.param_specs(), jax.random.key(5))


def _engine(model, params):
    led = L.OverheadLedger()
    sched = Scheduler(RegionManager(2, ledger=led), RoleLibrary(ledger=led),
                      ledger=led)
    q = sched.add_queue(Queue(None, 256, name="serve"))
    eng = ServeEngine(model, params, batch_slots=2, max_len=64,
                      decode_fusion=2, paged=True, page_size=8,
                      prefill_chunk=8, prefix=True, hsa_queue=q,
                      hsa_scheduler=sched)
    return eng, led


def _serve(eng):
    """A holder of ``PREFIX``, then a request that attaches it while the
    holder lives; the number of ``step()`` calls and the requests."""
    done, steps = [], 0
    holder = eng.submit(PREFIX + SUFFIXES[0], max_new_tokens=10)
    while not any(r.uid == holder for r in done) and steps < 200:
        done += eng.step()
        steps += 1
        if steps == 4:
            eng.submit(PREFIX + SUFFIXES[1], max_new_tokens=3)
    while len(done) < 2 and steps < 200:
        done += eng.step()
        steps += 1
    return steps, done


@pytest.fixture(scope="module")
def traced(engine_model, tmp_path_factory):
    eng, led = _engine(*engine_model)
    _serve(eng)                                  # compile outside the trace
    eng, led = _engine(*engine_model)
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        steps, done = _serve(eng)
    finally:
        jax.profiler.stop_trace()
    return trace_dir, eng, led, steps, done


def _events(trace_dir):
    """Host events named ``engine.*``/``hsa.*`` with their metadata."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events
                        if e.name.startswith(("engine.", "hsa."))]
    return out


def test_every_span_lies_inside_an_engine_step(traced):
    trace_dir, *_ = traced
    spans = devtrace.load(str(trace_dir), span_prefixes=("engine.", "hsa.")).spans
    steps = [(a, b) for name, a, b in spans if name == "engine.step"]
    inner = [s for s in spans if s[0] != "engine.step"]
    names = {name for name, _, _ in inner}
    assert {"engine.admit", "engine.chunk", "engine.prefix_copy",
            "engine.first_token", "engine.decode.prep", "engine.decode.launch",
            "engine.decode.commit", "hsa.submit", "hsa.grant", "hsa.dispatch",
            "hsa.exec", "hsa.wait"} <= names
    assert "engine.integrity" not in names           # integrity is off
    for name, a, b in inner:
        assert any(lo <= a and b <= hi for lo, hi in steps), name


def test_request_spans_carry_their_uid(traced):
    trace_dir, _, _, _, done = traced
    uids = {r.uid for r in done}
    seen: dict[int, set[str]] = {}
    for name, stats in _events(trace_dir):
        if name in REQUEST_SPANS:
            assert stats.get("uid") in uids, (name, stats)
            seen.setdefault(stats["uid"], set()).add(name)
    assert set(seen) == uids
    assert all({"engine.chunk", "engine.first_token"} <= s for s in seen.values())
    # the second request attached the holder's prefix through host memory
    assert "engine.prefix_copy" in seen[max(uids)]


def test_admission_lies_between_arrival_and_first_token(traced):
    *_, done = traced
    assert len(done) == 2
    for r in done:
        assert r.arrival_t <= r.admit_t <= r.first_token_t <= r.finish_t


def test_the_ledger_counts_every_step(traced):
    _, _, led, steps, _ = traced
    split = led.sync_split()
    assert split["engine_steps"] == steps
    assert split["host_syncs"] > steps
    assert split["host_syncs_per_step"] == split["host_syncs"] / steps


def test_a_decode_step_syncs_five_times(engine_model):
    """A step that only decodes waits for its one packet (the scheduler's
    exec) and copies positions, last tokens, tokens and validity to the
    host: five syncs."""
    eng, led = _engine(*engine_model)
    eng.submit(PREFIX, max_new_tokens=12)
    while not eng._active:
        eng.step()
    before = led.sync_split()["host_syncs"]
    eng.step()
    assert led.sync_split()["host_syncs"] - before == 5


def test_a_decode_launch_counts_live_and_table_pages(engine_model):
    """Each paged decode launch adds the pages mapped to the slots it
    advances, and slots x table width, to ``sync_split()``."""
    eng, led = _engine(*engine_model)
    eng.submit(PREFIX, max_new_tokens=12)
    while not eng._active:
        eng.step()
    (slot,) = eng._active
    before = led.sync_split()
    eng.step()
    after = led.sync_split()
    assert eng.table_pages == 8 and eng.slots == 2
    assert after["decode_kv_pages_table"] - before["decode_kv_pages_table"] == 16
    assert (after["decode_kv_pages_live"] - before["decode_kv_pages_live"]
            == eng._mapped[slot] == 3)   # 16 prompt rows + this launch's 2


def test_the_fused_decode_names_its_parts(engine_model):
    """The scopes are HLO metadata: the compiled fused decode carries them
    in its ``op_name``s."""
    eng, _ = _engine(*engine_model)
    eng.submit(PREFIX, max_new_tokens=4)
    eng.run_to_completion()
    slots = eng.slots
    args = (eng.params, eng._cache["segments"], np.asarray(eng._table),
            np.zeros(slots, np.int32), np.zeros(slots, np.int32),
            eng._slot_key, np.zeros(slots, np.int32), np.zeros(slots, bool),
            np.zeros(slots, np.int32))
    hlo = eng._fused_decode_fn(2).lower(*args).compile().as_text()
    for scope in ("attn.qkv", "attn.kv_write", "attn.core", "attn.out", "mlp",
                  "unembed", "sample"):
        assert f"/{scope}/" in hlo, scope


def test_without_a_profiler_the_ledger_reads_as_before(engine_model):
    """The spans add no category and change no view: every packet still
    records one submit, grant, dispatch, wait and exec leg, and the
    views keep their keys."""
    eng, led = _engine(*engine_model)
    empty = L.OverheadLedger()
    _, done = _serve(eng)
    assert set(led.summary()) == set(L.CATEGORIES)
    packets = led.stat(L.DISPATCH_SUBMIT).count
    assert packets > 0
    for cat in (L.DISPATCH_GRANT, L.DISPATCH, L.DISPATCH_WAIT, L.EXEC, L.WAIT):
        assert led.stat(cat).count == packets, cat
    assert led.stat(L.TTFT).count == led.stat(L.TPOT).count == len(done)
    for view in ("dispatch_split", "overcommit_split", "spill_split",
                 "availability_split", "integrity_split", "prefix_split",
                 "reconfig_split", "traffic_split", "memory_split"):
        assert set(getattr(led, view)()) == set(getattr(empty, view)()), view
    split = led.dispatch_split()
    assert split["submit_n"] == split["grant_n"] == split["wait_n"] == packets


def test_a_span_records_only_a_clean_exit():
    led = L.OverheadLedger()
    with led.span("hsa.exec", L.EXEC, queue="q") as s:
        pass
    assert led.stat(L.EXEC).count == 1 and led.stat(L.EXEC).total_s == s.seconds
    with pytest.raises(RuntimeError):
        with led.span("hsa.exec", L.EXEC, queue="q"):
            raise RuntimeError("launch failed")
    assert led.stat(L.EXEC).count == 1
    with L.span("engine.admit") as s:                # no ledger: timed only
        pass
    assert s.seconds >= 0.0
