"""Live-traffic serving: chunked prefill, engine-clock latency accounting,
and mid-flight submission.

The acceptance bars (ISSUE PR-6):

  - chunked prefill is a *scheduling* change, never a numerics change —
    token streams bitwise-identical to the whole-prompt engine across
    ``prefill_chunk`` x ``decode_fusion``, dense and paged, greedy and
    seeded temperature;
  - per-request timestamps ride the engine clock monotonically
    (``arrival_t <= first_token_t <= finish_t``);
  - the ledger's TTFT/TPOT quantiles match a hand-computed oracle on a
    deterministic virtual-clock trace;
  - ``submit()`` while ``run_to_completion`` is mid-flight lands at the
    next step boundary and is never misclassified as rejected — under a
    real feeder thread (WallClock) and deterministically (VirtualClock).
"""

import math
import threading

import jax
import pytest

from repro.configs import ARCHS, reduced
from repro.core.hsa.clock import VirtualClock
from repro.core.ledger import OverheadLedger
from repro.models import build_model
from repro.models.params import init_params
from repro.serve.engine import ServeEngine


@pytest.fixture(scope="module")
def engine_model():
    cfg = reduced(ARCHS["llama3.2-1b"], layers=2, d_model=64, vocab=128)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.key(11))
    return cfg, model, params


# one prompt long enough to span several chunks, plus shorts whose second
# wave admits at different steps under different chunk/fusion settings
PROMPTS = [list(range(3, 23)), [7, 8], [1, 2, 3, 4, 5, 6], [42]]


def _step_time(prefill_tokens: int, decode_tokens: int) -> float:
    return 1e-3 + 1e-4 * prefill_tokens + 5e-5 * decode_tokens


def _generate(model, params, *, chunk, fusion, paged=False, temperature=0.0,
              seed=0, max_new=6, prompts=PROMPTS):
    eng = ServeEngine(
        model, params, batch_slots=2, max_len=64, decode_fusion=fusion,
        temperature=temperature, seed=seed, paged=paged, page_size=16,
        prefill_chunk=chunk,
    )
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    done = sorted(eng.run_to_completion(), key=lambda r: r.uid)
    return [r.generated for r in done]


# ---------------------------------------------------------------------------
# bitwise identity: chunked == whole-prompt, every config
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("temperature,seed", [(0.0, 0), (0.7, 3)],
                         ids=["greedy", "temp"])
def test_chunked_streams_bitwise_identical(engine_model, paged, temperature,
                                           seed):
    _, model, params = engine_model
    base = _generate(model, params, chunk=None, fusion=1, paged=paged,
                     temperature=temperature, seed=seed)
    assert any(base), "baseline generated nothing"
    for chunk, fusion in ((4, 1), (4, 4), (16, 4)):
        got = _generate(model, params, chunk=chunk, fusion=fusion,
                        paged=paged, temperature=temperature, seed=seed)
        assert got == base, f"chunk={chunk} fusion={fusion} paged={paged}"


def test_chunked_actually_chunks(engine_model):
    """The identity test must not pass vacuously: a 20-token prompt under
    chunk=4 really streams through the chunk path (traced at least once)."""
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=64,
                      decode_fusion=1, prefill_chunk=4)
    eng.submit(PROMPTS[0], max_new_tokens=2)
    eng.run_to_completion()
    assert eng.chunk_traces >= 1


# ---------------------------------------------------------------------------
# engine clock: timestamps and ledger quantiles
# ---------------------------------------------------------------------------


def _replay(model, params, trace, *, chunk, ledger=None):
    """Feed ``[(arrival_s, prompt, max_new), ...]`` through a virtual-clock
    engine; the completed requests, uid-sorted."""
    clock = VirtualClock()
    eng = ServeEngine(
        model, params, batch_slots=2, max_len=64, decode_fusion=2,
        prefill_chunk=chunk, clock=clock, step_time_model=_step_time,
        ledger=ledger,
    )
    i, done = 0, []
    while True:
        while i < len(trace) and trace[i][0] <= clock.now():
            t_a, p, m = trace[i]
            eng.submit(p, max_new_tokens=m, arrival_t=t_a)
            i += 1
        if not (eng._active or eng._prefilling or eng._queue or eng._parked):
            if i >= len(trace):
                break
            clock.advance_to(trace[i][0])
            continue
        done += eng.step()
    return sorted(done, key=lambda r: r.uid)


TRACE = [
    (0.000, list(range(3, 23)), 5),
    (0.001, [7, 8], 4),
    (0.004, [1, 2, 3, 4, 5, 6], 3),
    (0.030, [42], 6),
    (0.031, [9, 9, 9], 1),       # single-token: TPOT divisor clamps at 1
    (0.090, [5, 4, 3, 2], 4),
]


def test_timestamps_monotone_per_request(engine_model):
    _, model, params = engine_model
    done = _replay(model, params, TRACE, chunk=4)
    assert len(done) == len(TRACE)
    for req, (t_a, _, m) in zip(done, TRACE):
        assert req.arrival_t == t_a
        assert req.first_token_t is not None and req.finish_t is not None
        assert req.arrival_t <= req.first_token_t <= req.finish_t
        assert len(req.generated) == m
        # a request whose remaining budget exceeds one fused launch (k=2 in
        # _replay) cannot finish in its first-token step: strictly later
        if m - 1 > 2:
            assert req.first_token_t < req.finish_t


def _oracle_quantile(samples, q):
    """The ledger's empirical quantile: sorted window, ceil-index."""
    s = sorted(samples)
    idx = min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))
    return s[idx]


def test_traffic_split_matches_hand_computed_oracle(engine_model):
    _, model, params = engine_model
    led = OverheadLedger()
    done = _replay(model, params, TRACE, chunk=4, ledger=led)

    ttft = [r.first_token_t - r.arrival_t for r in done]
    tpot = [(r.finish_t - r.first_token_t) / max(1, len(r.generated) - 1)
            for r in done]
    split = led.traffic_split()
    assert split["ttft_n"] == split["tpot_n"] == float(len(done))
    assert split["ttft_mean_s"] == pytest.approx(sum(ttft) / len(ttft))
    assert split["tpot_mean_s"] == pytest.approx(sum(tpot) / len(tpot))
    for q, name in ((0.5, "p50"), (0.99, "p99")):
        assert split[f"ttft_{name}_s"] == pytest.approx(
            _oracle_quantile(ttft, q)), name
        assert split[f"tpot_{name}_s"] == pytest.approx(
            _oracle_quantile(tpot, q)), name
    # virtual clock: every latency is a schedule property, so a second
    # replay reproduces the numbers bit-for-bit
    led2 = OverheadLedger()
    _replay(model, params, TRACE, chunk=4, ledger=led2)
    assert led2.traffic_split() == split


# ---------------------------------------------------------------------------
# mid-flight submission: feeder thread (WallClock) and deterministic variant
# ---------------------------------------------------------------------------


def test_midflight_submit_wallclock_feeder_thread(engine_model):
    """submit() from a feeder thread while run_to_completion is mid-flight:
    the late requests are admitted at a step boundary and finish — never
    lost, never misclassified as rejected.  "Mid-flight" is an event, not a
    sleep: the feeder submits once the engine has completed its first step,
    and that step returns to run_to_completion only after the feeder has
    submitted, so the submits land between steps whatever a step costs."""
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=64,
                      decode_fusion=2, prefill_chunk=4)
    first = [eng.submit(p, max_new_tokens=12) for p in PROMPTS[:2]]
    late: list[int] = []
    stepped, fed = threading.Event(), threading.Event()
    engine_step = eng.step

    def step():
        done = engine_step()
        if not stepped.is_set():
            stepped.set()
            assert fed.wait(timeout=60), "the feeder never submitted"
        return done

    eng.step = step

    def feeder():
        assert stepped.wait(timeout=600), "the engine never stepped"
        for p in PROMPTS[2:]:
            late.append(eng.submit(p, max_new_tokens=4))
        fed.set()

    th = threading.Thread(target=feeder)
    th.start()
    done = eng.run_to_completion()     # must also drain the feeder's requests
    th.join()
    got = sorted(r.uid for r in done)
    assert got == sorted(first + late)
    by_uid = {r.uid: r for r in done}
    assert all(len(by_uid[u].generated) == 12 for u in first)
    assert all(len(by_uid[u].generated) == 4 for u in late)


def test_concurrent_submit_uids_unique(engine_model):
    """The uid counter and queue are shared with feeder threads: concurrent
    submits must never mint duplicate uids or drop queue entries."""
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=64)
    uids: list[int] = []
    lock = threading.Lock()

    def feeder():
        mine = [eng.submit([1, 2, 3], max_new_tokens=1) for _ in range(8)]
        with lock:
            uids.extend(mine)

    threads = [threading.Thread(target=feeder) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(uids) == 32 and len(set(uids)) == 32
    assert len(eng._queue) == 32


def test_midflight_submit_virtualclock_deterministic(engine_model):
    """Deterministic variant: submit between step() calls on the virtual
    clock.  The late request is queued (not rejected), admitted at the very
    next step boundary, and stamps its backdated arrival."""
    _, model, params = engine_model
    eng = ServeEngine(
        model, params, batch_slots=2, max_len=64, decode_fusion=2,
        paged=True, page_size=16, prefill_chunk=4,
        clock=VirtualClock(), step_time_model=_step_time,
    )
    first = [eng.submit(PROMPTS[0], max_new_tokens=8),
             eng.submit(PROMPTS[1], max_new_tokens=8)]
    done = eng.step()                   # both admitted, mid-flight now
    t_mid = eng.clock.now()
    late = eng.submit(PROMPTS[2], max_new_tokens=3, arrival_t=t_mid)
    assert any(r.uid == late for r in eng._queue), "late submit not queued"
    for _ in range(200):
        done += eng.step()
        if {r.uid for r in done} == set(first) | {late}:
            break
    else:
        pytest.fail(f"late request never completed: {[r.uid for r in done]}")
    req = next(r for r in done if r.uid == late)
    assert req.arrival_t == t_mid
    assert t_mid <= req.first_token_t <= req.finish_t
    assert len(req.generated) == 3
