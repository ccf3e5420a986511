"""Paged KV cache: dense↔paged bitwise equivalence, allocator properties,
admission, and the concurrency win at equal memory.

The acceptance bar mirrors fused decode's: paging is a pure *memory
management* change — token streams must be bitwise-identical to the dense
engine for the same requests, across sampling modes and fusion depths, or
the paged engine is silently a different model.
"""

import numpy as np
import pytest

import jax

from repro.configs import ARCHS, reduced
from repro.core.hsa import FaultPlan, Queue, Scheduler, VirtualClock
from repro.core.ledger import OverheadLedger
from repro.core.policy import AdmissionPolicy, PreemptionPolicy, RetryPolicy
from repro.core.reconfig import RegionManager
from repro.core.roles import RoleLibrary
from repro.models import build_model
from repro.models.params import init_params
from repro.serve.engine import ServeEngine, ServeTruncated
from repro.serve.paged import (
    PageAllocator,
    PagePoolExhausted,
    TRASH_PAGE,
    pages_for,
)


@pytest.fixture(scope="module")
def engine_model():
    cfg = reduced(ARCHS["llama3.2-1b"], layers=2, d_model=64, vocab=128)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.key(11))
    return cfg, model, params


PROMPTS = [[3, 14, 15, 92], [7, 8], [1, 2, 3, 4, 5, 6], [42]]


def _generate(model, params, *, paged, fusion=1, temperature=0.0, slots=2,
              max_new=7, seed=0, prompts=PROMPTS, **kw):
    eng = ServeEngine(model, params, batch_slots=slots, max_len=32,
                      decode_fusion=fusion, temperature=temperature,
                      seed=seed, paged=paged,
                      page_size=8 if paged else 16, **kw)
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    done = sorted(eng.run_to_completion(), key=lambda r: r.uid)
    return [r.generated for r in done], eng


# ---------------------------------------------------------------------------
# dense <-> paged equivalence
# ---------------------------------------------------------------------------


# one page of shared system prompt, then a distinct user turn
SHARED_PROMPTS = [[9, 8, 7, 6, 5, 4, 3, 2] + s
                  for s in ([1], [2, 3], [4, 5, 6], [7])]


def _shared_spill_fault_kw():
    """Engine options for a paged run under stress: prefix sharing,
    overcommitted admission into a pool too small for both slots (page
    growth parks a victim, whose snapshot spills to the host arena), and
    one injected fault on the first fused decode launch, recovered by
    re-prefill replay through the HSA queue."""
    led = OverheadLedger()
    plan = FaultPlan()
    plan.force("exec", "decode_fused")
    sched = Scheduler(RegionManager(4, ledger=led), RoleLibrary(ledger=led),
                      ledger=led, clock=VirtualClock(), faults=plan)
    queue = sched.add_queue(Queue(None, 256, name="serve"))
    return dict(prefix=True, pool_pages=4, hsa_queue=queue,
                hsa_scheduler=sched, ledger=led, retry=RetryPolicy(),
                admission=AdmissionPolicy(growth_reserve=0.25),
                preemption=PreemptionPolicy(snapshot_threshold_tokens=0))


@pytest.mark.parametrize("fusion,stress", [
    pytest.param(1, False, id="1"),
    pytest.param(3, False, id="3"),
    pytest.param(4, False, id="4"),
    pytest.param(4, True, id="4-prefix-spill-fault"),
])
def test_paged_greedy_bitwise_identical(engine_model, fusion, stress):
    _, model, params = engine_model
    prompts = SHARED_PROMPTS if stress else PROMPTS
    kw = _shared_spill_fault_kw() if stress else {}
    dense, _ = _generate(model, params, paged=False, fusion=fusion,
                         prompts=prompts)
    paged, eng = _generate(model, params, paged=True, fusion=fusion,
                           prompts=prompts, **kw)
    assert paged == dense
    assert all(len(g) == 7 for g in paged)
    # every page back in the pool the moment serving drained
    eng.allocator.check_invariants()
    assert eng.allocator.free_pages == eng.allocator.total_pages
    if stress:                  # each stress actually happened
        assert eng.prefix_hits > 0
        assert eng.preemptions > 0 and eng.spills > 0
        avail = eng.ledger.availability_split()
        assert avail["faults"] == 1 and avail["recoveries"] >= 1
    # the fused decode takes the pool by donation and updates it in place:
    # the pool a launch was handed is gone once it returns
    eng.submit(PROMPTS[0], max_new_tokens=7)
    eng.step()                              # prefill (and a first launch)
    before = jax.tree.leaves(eng._cache["segments"])
    eng.step()                              # a decode launch
    assert all(leaf.is_deleted() for leaf in before)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(eng._cache["segments"]))


@pytest.mark.parametrize("fusion", [1, 3, 4])
def test_paged_temperature_bitwise_identical(engine_model, fusion):
    """Seeded temperature sampling must survive paging at any fusion depth —
    the draw depends only on (seed, uid, logits), and paged logits are
    bitwise-equal to dense."""
    _, model, params = engine_model
    dense, _ = _generate(model, params, paged=False, fusion=fusion,
                         temperature=0.7, seed=3)
    paged, _ = _generate(model, params, paged=True, fusion=fusion,
                         temperature=0.7, seed=3)
    assert paged == dense
    other, _ = _generate(model, params, paged=True, fusion=fusion,
                         temperature=0.7, seed=4)
    assert other != dense          # the seed knob is still live under paging


def test_paged_equal_memory_doubles_concurrency(engine_model):
    """At equal KV bytes (2 dense slots x 32 rows == 8 usable pages x 8
    rows) the paged engine sustains >= 2x the live requests — the tentpole
    claim, scaled down to test size — with identical streams."""
    _, model, params = engine_model
    reqs = [[3 + i, 14, 15] for i in range(8)]
    dense, deng = _generate(model, params, paged=False, slots=2, max_new=6,
                            prompts=reqs)
    paged, peng = _generate(model, params, paged=True, slots=8, max_new=6,
                            prompts=reqs, pool_pages=9)
    assert paged == dense
    ratio = (peng.concurrency_stats()["sustained"]
             / deng.concurrency_stats()["sustained"])
    assert ratio >= 2.0, peng.concurrency_stats()


def test_paged_rejects_recurrent_cache(engine_model):
    cfg = reduced(ARCHS["mamba2-780m"], layers=2, d_model=64, vocab=128)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.key(0))
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, batch_slots=2, max_len=32, paged=True,
                    page_size=8)


def test_paged_requires_page_aligned_max_len(engine_model):
    _, model, params = engine_model
    with pytest.raises(ValueError, match="multiple"):
        ServeEngine(model, params, batch_slots=2, max_len=30, paged=True,
                    page_size=8)


def test_paged_memory_split_accounting(engine_model):
    """The ledger's memory_split must show paged stranding < dense stranding
    on the same requests (pages strand at most a page tail; dense strands
    max_len - len)."""
    _, model, params = engine_model
    dled, pled = OverheadLedger(), OverheadLedger()
    _generate(model, params, paged=False, ledger=dled)
    _generate(model, params, paged=True, ledger=pled)
    dense, paged = dled.memory_split(), pled.memory_split()
    assert paged["peak_reserved_bytes"] > 0
    assert paged["peak_stranded_bytes"] < dense["peak_stranded_bytes"]


# ---------------------------------------------------------------------------
# allocator properties
# ---------------------------------------------------------------------------


def test_allocator_double_free_raises():
    alloc = PageAllocator(8)
    pages = alloc.allocate(owner=1, n=3)
    alloc.free(1, pages)
    with pytest.raises(ValueError, match="double free"):
        alloc.free(1, pages[:1])


def test_allocator_foreign_free_raises():
    alloc = PageAllocator(8)
    pages = alloc.allocate(owner=1, n=2)
    with pytest.raises(ValueError, match="belongs to"):
        alloc.free(2, pages)
    alloc.free(1, pages)


def test_allocator_never_hands_out_trash_page():
    alloc = PageAllocator(8)
    pages = alloc.allocate(owner=1, n=7)       # the whole usable pool
    assert TRASH_PAGE not in pages
    with pytest.raises(PagePoolExhausted):
        alloc.allocate(owner=2, n=1)
    with pytest.raises(ValueError, match="scratch"):
        alloc.free(1, [TRASH_PAGE])


def test_allocator_churn_invariants():
    """Random admit/grow/finish churn: no leak, no alias, allocation stats
    consistent."""
    rng = np.random.default_rng(7)
    alloc = PageAllocator(64)
    live: dict[int, list[int]] = {}
    uid = 0
    for _ in range(500):
        if live and rng.random() < 0.4:
            victim = int(rng.choice(list(live)))
            alloc.free(victim, live.pop(victim))
        elif alloc.free_pages > 4:
            uid += 1
            live[uid] = alloc.allocate(uid, int(rng.integers(1, 4)))
        elif live:                                # grow someone
            u = int(rng.choice(list(live)))
            if alloc.free_pages:
                live[u] += alloc.allocate(u, 1)
        alloc.check_invariants()
    for u, pages in list(live.items()):
        alloc.free(u, pages)
    alloc.check_invariants()
    assert alloc.free_pages == alloc.total_pages
    s = alloc.stats()
    assert s.allocs == s.frees


def test_no_leak_after_serve_truncated(engine_model):
    """Truncation parks requests with their pages (they are resumable);
    finishing the resume returns every page — nothing leaks across the
    error path."""
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=32, paged=True,
                      page_size=8)
    eng.submit([1, 2, 3], max_new_tokens=10)
    eng.submit([4, 5], max_new_tokens=10)
    with pytest.raises(ServeTruncated) as ei:
        eng.run_to_completion(max_steps=2)
    # in-flight requests legitimately hold pages at truncation
    held = eng.allocator.allocated_pages
    assert held > 0 and len(ei.value.pending) == 2
    done = eng.run_to_completion()
    assert len(done) == 2 and all(len(r.generated) == 10 for r in done)
    eng.allocator.check_invariants()
    assert eng.allocator.free_pages == eng.allocator.total_pages


def test_engine_churn_fragmentation_bounded(engine_model):
    """Random admit/finish churn through the real engine: at every step the
    stranded reservation is bounded by live_requests x O(page_size) rows —
    internal fragmentation only, never accumulated leaks."""
    _, model, params = engine_model
    rng = np.random.default_rng(3)
    led = OverheadLedger()
    eng = ServeEngine(model, params, batch_slots=4, max_len=32, paged=True,
                      page_size=8, decode_fusion=2, ledger=led)
    submitted = 0
    for step in range(40):
        if submitted < 12 and rng.random() < 0.5:
            n = int(rng.integers(1, 6))
            eng.submit([int(t) for t in rng.integers(1, 100, size=n)],
                       max_new_tokens=int(rng.integers(1, 8)))
            submitted += 1
        eng.step()
        eng.allocator.check_invariants()
        live = len(eng._active)
        split = led.memory_split()
        if eng._token_bytes:
            stranded_rows = split["stranded_bytes"] / eng._token_bytes
            # <= one page tail + one growth page per live request
            assert stranded_rows <= live * 2 * eng.page_size, (
                step, live, stranded_rows)
    eng.run_to_completion()
    eng.allocator.check_invariants()
    assert eng.allocator.free_pages == eng.allocator.total_pages


# ---------------------------------------------------------------------------
# admission policy
# ---------------------------------------------------------------------------


def test_admission_projected_pages():
    pol = AdmissionPolicy()
    assert pol.projected_pages(4, 8, 8) == pages_for(12, 8) == 2
    assert pol.projected_pages(8, 8, 8) == 2
    half = AdmissionPolicy(growth_reserve=0.5)
    assert half.projected_pages(4, 8, 8) == 1      # projects 4 + 4 tokens
    assert half.projected_pages(4, 0, 8) == 1      # at least one new token


def test_admission_accounts_projected_growth():
    pol = AdmissionPolicy()
    # 4 free pages, but live requests will still map 3 more: only 1 is real
    assert pol.admit(free_pages=4, projected_growth_pages=3, request_pages=1)
    assert not pol.admit(free_pages=4, projected_growth_pages=3,
                         request_pages=2)
    held = AdmissionPolicy(watermark_pages=2)
    assert not held.admit(free_pages=4, projected_growth_pages=1,
                          request_pages=2)


def test_admission_head_of_line_blocks_until_pages_free(engine_model):
    """A pool sized for ~1 live request serializes admission through the
    AdmissionPolicy (not the slot count), still completing everything."""
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=4, max_len=32, paged=True,
                      page_size=8, pool_pages=4)   # 3 usable pages
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=6)
    done = eng.run_to_completion()
    assert len(done) == 4 and all(len(r.generated) == 6 for r in done)
    assert eng.peak_concurrency < 4                # the pool was the limit
    assert eng.allocator.free_pages == eng.allocator.total_pages


def test_submit_rejects_never_fitting_request(engine_model):
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=32, paged=True,
                      page_size=8, pool_pages=3)   # 2 usable pages
    with pytest.raises(ValueError, match="block the queue forever"):
        eng.submit(list(range(20)), max_new_tokens=10)


def test_submit_rejection_is_worst_case_under_overcommit(engine_model):
    """Permanent rejection must test the growth_reserve-independent worst
    case: a request whose *projection* fits but whose full budget cannot
    would otherwise park forever instead of failing fast at submit."""
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=32, paged=True,
                      page_size=8, pool_pages=3,   # 2 usable pages
                      admission=AdmissionPolicy(growth_reserve=0.1))
    # projects pages_for(4 + 3) = 1 page, but worst case is 4 pages
    with pytest.raises(ValueError, match="block the queue forever"):
        eng.submit([1, 2, 3, 4], max_new_tokens=28)
    eng.submit([1, 2, 3, 4], max_new_tokens=8)     # worst case 2 pages: fits
    done = eng.run_to_completion()
    assert len(done) == 1 and len(done[0].generated) == 8


# ---------------------------------------------------------------------------
# preemption edge cases (PR 5)
# ---------------------------------------------------------------------------


def _dense_streams(model, params, reqs, **kw):
    eng = ServeEngine(model, params, batch_slots=len(reqs), max_len=32, **kw)
    for p, m in reqs:
        eng.submit(p, max_new_tokens=m)
    done = sorted(eng.run_to_completion(), key=lambda r: r.uid)
    return [r.generated for r in done]


@pytest.mark.parametrize("threshold", [0, 1000])   # snapshot / re-prefill
def test_preempt_during_prefill_phase(engine_model, threshold):
    """A victim parked right after its prefill — one sampled token, zero
    decode steps — must resume and finish bitwise-identically."""
    _, model, params = engine_model
    reqs = [([3, 14, 15, 92], 6), ([7, 8], 6)]
    eng = ServeEngine(model, params, batch_slots=2, max_len=32, paged=True,
                      page_size=8,
                      preemption=PreemptionPolicy(
                          snapshot_threshold_tokens=threshold))
    eng.submit(*reqs[0])
    req = eng._queue.pop(0)                 # admit by hand: prefill only,
    eng._prefill_slot(0, req)               # no decode launch yet
    eng._active[0] = req
    assert len(req.generated) == 1
    eng.preempt(req.uid)
    assert req.parked and eng.allocator.allocated_pages == 0
    eng.submit(*reqs[1])
    done = sorted(eng.run_to_completion(), key=lambda r: r.uid)
    assert [r.generated for r in done] == _dense_streams(model, params, reqs)


@pytest.mark.parametrize("threshold", [0, 1000])
def test_preempt_at_exact_page_boundary(engine_model, threshold):
    """Park when written rows exactly fill the mapped pages (pos a multiple
    of page_size): the snapshot must keep exactly pos/page_size pages and
    the resume's next write must map a fresh page."""
    _, model, params = engine_model
    prompt = list(range(1, 9))              # prefill pos = 8 = page_size
    eng = ServeEngine(model, params, batch_slots=1, max_len=32, paged=True,
                      page_size=8, decode_fusion=1,
                      preemption=PreemptionPolicy(
                          snapshot_threshold_tokens=threshold))
    eng.submit(prompt, max_new_tokens=9)    # runs through rows 8..16
    req = eng._queue.pop(0)
    eng._prefill_slot(0, req)
    eng._active[0] = req
    assert int(eng._pos[0]) == 8 and int(eng._mapped[0]) == 1
    eng.preempt(req.uid)
    entry = eng._parked[0]
    assert entry.pos == 8
    if entry.snapshot is not None:
        assert all(leaf.shape[1] == 1 for leaf in jax.tree.leaves(entry.snapshot))
    done = eng.run_to_completion()
    assert [r.generated for r in done] == _dense_streams(
        model, params, [(prompt, 9)])


def test_resume_while_pool_full_reparks_not_loops(engine_model):
    """A parked request whose pages are still claimed stays parked — the
    engine keeps decoding the survivor (progress, not a spin) and resumes
    the victim only when pages actually free up.

    Both requests need 3 pages worst-case of a 4-page pool; overcommitted
    admission (reserve 0.25) lets both in, so the first page-3 crossing
    organically parks the younger one, whose snapshot restore then stays
    unfundable (watermark held back) until the survivor finishes."""
    _, model, params = engine_model
    reqs = [([1, 2, 3], 16), ([4, 5], 16)]
    eng = ServeEngine(
        model, params, batch_slots=2, max_len=32, paged=True, page_size=8,
        pool_pages=5, decode_fusion=1,
        admission=AdmissionPolicy(growth_reserve=0.25, watermark_pages=1),
        preemption=PreemptionPolicy(snapshot_threshold_tokens=0),
    )
    for p, m in reqs:
        eng.submit(p, max_new_tokens=m)
    done, guard = [], 0
    while not eng.parked_requests:          # growth pressure parks uid 2
        done += eng.step()
        guard += 1
        assert guard < 30, "pool was never exhausted: test is vacuous"
    victim = eng.parked_requests[0].uid
    assert len(eng._active) == 1
    # forced resume while the survivor still holds the pool: clean no-op
    assert eng.resume(victim) is False
    assert [r.uid for r in eng.parked_requests] == [victim]
    parked_steps = 0
    while eng.parked_requests:              # survivor drains, victim waits
        done += eng.step()
        parked_steps += 1
        assert parked_steps < 60, "victim never resumed: livelock"
    assert parked_steps > 1, "victim resumed instantly: pool was never full"
    done = sorted(done + eng.run_to_completion(), key=lambda r: r.uid)
    assert eng.preemptions == 1 and eng.resumes == 1
    assert [r.generated for r in done] == _dense_streams(model, params, reqs)


def test_double_resume_and_bad_preempt_guards(engine_model):
    _, model, params = engine_model
    eng = ServeEngine(model, params, batch_slots=2, max_len=32, paged=True,
                      page_size=8)
    eng.submit([1, 2, 3], max_new_tokens=6)
    eng.step()
    uid = eng.preempt()
    with pytest.raises(ValueError, match="not active"):
        eng.preempt(uid)                    # parked, not active
    with pytest.raises(ValueError, match="not active"):
        eng.preempt(999)                    # unknown uid
    assert eng.resume(uid) is True
    with pytest.raises(ValueError, match="double resume"):
        eng.resume(uid)                     # second resume is a caller bug
    with pytest.raises(ValueError, match="no active request"):
        ServeEngine(model, params, batch_slots=1, max_len=32, paged=True,
                    page_size=8).preempt()
    done = eng.run_to_completion()
    assert len(done) == 1 and len(done[0].generated) == 6
