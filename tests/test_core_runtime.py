"""Unit + property tests for the paper's core: registry, dispatch, regions,
roles, ledger, planner, HSA runtime."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.kernels  # noqa: F401
from repro.core import dispatch, ledger as ledger_mod, policy
from repro.core.ledger import OverheadLedger
from repro.core.reconfig import RegionManager
from repro.core.registry import (
    FIXED_WEIGHT,
    GENERIC,
    GLOBAL_REGISTRY,
    KernelImpl,
    KernelRegistry,
)
from repro.core.roles import ONLINE, PRESYNTHESIZED, Role, RoleLibrary
from repro.core.hsa import (
    Agent,
    Executor,
    Queue,
    QueueFullError,
    Signal,
    hsa_init,
    hsa_shut_down,
    run_packet_sync,
)


# ---------------------------------------------------------------------------
# registry + dispatch
# ---------------------------------------------------------------------------


def test_registry_resolution_prefers_source_order():
    reg = KernelRegistry()
    reg.register(KernelImpl(op="f", device_kind="any", source="reference", fn=lambda x: x))
    reg.register(KernelImpl(op="f", device_kind="tpu", source="pallas", fn=lambda x: x + 1))
    assert reg.resolve("f", "tpu", ("pallas", "reference")).source == "pallas"
    assert reg.resolve("f", "tpu", ("xla", "reference")).source == "reference"
    with pytest.raises(KeyError):
        reg.resolve("f", "tpu", ("xla",))


def test_registry_priority_within_source():
    reg = KernelRegistry()
    reg.register(KernelImpl(op="f", device_kind="any", source="xla", fn=lambda: 1,
                            name="a", priority=0))
    reg.register(KernelImpl(op="f", device_kind="any", source="xla", fn=lambda: 2,
                            name="b", priority=5))
    assert reg.resolve("f", "any", ("xla",)).name == "b"


def test_registry_duplicate_rejected_unless_override():
    reg = KernelRegistry()
    impl = KernelImpl(op="f", device_kind="any", source="xla", fn=lambda: 1, name="a")
    reg.register(impl)
    with pytest.raises(ValueError):
        reg.register(impl)
    reg.register(impl, allow_override=True)


def test_transparent_dispatch_policy_switch():
    """The paper's headline: same call, different backend, same numerics."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(32, 8)), jnp.float32)
    with dispatch.use(prefer=("reference",)):
        a = dispatch.op("matmul", x, w)
    with dispatch.use(prefer=("xla", "reference")):
        b = dispatch.op("matmul", x, w)
    with dispatch.use(prefer=("pallas", "xla", "reference"), interpret=True):
        c = dispatch.op("matmul", x, w)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4)


def test_dispatch_trace_records_sequence():
    trace = dispatch.DispatchTrace()
    x = jnp.ones((8, 8))
    with dispatch.use(prefer=("xla", "reference"), trace=trace):
        dispatch.op("matmul", x, x)
        dispatch.op("rmsnorm", x, jnp.ones((8,)))
        dispatch.op("matmul", x, x)
    assert trace.op_counts() == {"matmul": 2, "rmsnorm": 1}


def test_dispatch_context_memoizes_resolution():
    """Hot trace loops resolve each (op, specialization) once per context."""
    calls = []

    class Counting(KernelRegistry):
        def resolve(self, *a, **kw):
            calls.append(1)
            return super().resolve(*a, **kw)

    creg = Counting()
    creg.register(KernelImpl(op="f", device_kind="any", source="xla", fn=lambda x: x))
    with dispatch.use(registry=creg, prefer=("xla",)) as ctx:
        a = ctx.resolve("f")
        b = ctx.resolve("f")
        c = ctx.resolve("f", specialization=None)
    assert a is b is c
    assert len(calls) == 1


def test_dispatch_memo_invalidated_by_late_registration():
    """A registration after the first resolve must not serve a stale impl."""
    reg = KernelRegistry()
    reg.register(KernelImpl(op="f", device_kind="any", source="xla",
                            fn=lambda x: x, name="old", priority=0))
    with dispatch.use(registry=reg, prefer=("xla",)) as ctx:
        assert ctx.resolve("f").name == "old"
        reg.register(KernelImpl(op="f", device_kind="any", source="xla",
                                fn=lambda x: x + 1, name="new", priority=9))
        assert ctx.resolve("f").name == "new"     # version bump busts the memo


def test_registry_version_monotone():
    reg = KernelRegistry()
    v0 = reg.version
    impl = KernelImpl(op="f", device_kind="any", source="xla", fn=lambda: 0)
    reg.register(impl)
    v1 = reg.version
    snap = reg.snapshot()
    reg.clear()
    v2 = reg.version
    reg.restore(snap)
    v3 = reg.version
    assert v0 < v1 < v2 < v3


def test_dispatch_inside_jit_is_trace_time():
    """Resolution happens at trace time: the jitted program is policy-baked."""
    calls = []
    reg = KernelRegistry()

    def noisy(x):
        calls.append(1)
        return x * 2

    reg.register(KernelImpl(op="dbl", device_kind="any", source="xla", fn=noisy))

    @jax.jit
    def f(x):
        with dispatch.use(registry=reg, prefer=("xla",)):
            return dispatch.op("dbl", x)

    f(jnp.ones(4))
    n_after_trace = len(calls)
    f(jnp.ones(4))  # cached: no re-dispatch
    assert len(calls) == n_after_trace == 1


# ---------------------------------------------------------------------------
# roles + regions (partial reconfiguration)
# ---------------------------------------------------------------------------


def _mk_role(lib, n=16, name_suffix="", source=PRESYNTHESIZED):
    impl = GLOBAL_REGISTRY.resolve("matmul", "any", ("xla",))
    a = jax.ShapeDtypeStruct((n, n), jnp.float32)
    return lib.add(Role(impl, (a, a), source=source, name=f"mm{n}{name_suffix}"))


def test_role_synthesize_then_load_then_unload():
    lib = RoleLibrary(ledger=OverheadLedger())
    r = _mk_role(lib, 16)
    assert not r.resident
    r.synthesize()
    assert r.synthesis_s is not None and not r.resident
    out = r(jnp.ones((16, 16)), jnp.ones((16, 16)))
    assert r.resident and r.load_count == 1
    np.testing.assert_allclose(np.asarray(out)[0, 0], 16.0)
    r.unload()
    assert not r.resident


def test_online_role_synthesizes_lazily():
    lib = RoleLibrary(ledger=OverheadLedger())
    r = _mk_role(lib, 8, source=ONLINE)
    assert r.synthesis_s is None
    r.load()
    assert r.synthesis_s is not None


def test_lru_eviction_order():
    led = OverheadLedger()
    lib = RoleLibrary(ledger=led)
    roles = [_mk_role(lib, n) for n in (8, 16, 32)]
    rm = RegionManager(2, ledger=led)
    rm.ensure_resident(roles[0])
    rm.ensure_resident(roles[1])
    assert rm.ensure_resident(roles[0]).hit          # refresh LRU position of 0
    res = rm.ensure_resident(roles[2])               # evicts 1 (least recent)
    assert not res.hit and res.evicted == roles[1].key
    assert rm.is_resident(roles[0].key) and not rm.is_resident(roles[1].key)
    assert not roles[1].resident                      # eviction unloaded it
    assert rm.stats.evictions == 1


def test_pinned_roles_survive_eviction():
    led = OverheadLedger()
    lib = RoleLibrary(ledger=led)
    roles = [_mk_role(lib, n) for n in (8, 16, 32)]
    rm = RegionManager(2, ledger=led)
    rm.pin(roles[0])
    rm.ensure_resident(roles[1])
    rm.ensure_resident(roles[2])                      # must evict 1, not pinned 0
    assert rm.is_resident(roles[0].key)
    with pytest.raises(RuntimeError):
        rm2 = RegionManager(1, ledger=led)
        rm2.pin(roles[0])
        rm2.ensure_resident(roles[1])


def test_reconfig_recorded_in_ledger_only_on_miss():
    led = OverheadLedger()
    lib = RoleLibrary(ledger=led)
    r = _mk_role(lib, 8)
    rm = RegionManager(2, ledger=led)
    rm.ensure_resident(r)
    rm.ensure_resident(r)
    rm.ensure_resident(r)
    assert led.stat(ledger_mod.RECONFIG).count == 1
    assert rm.stats.hits == 2 and rm.stats.misses == 1


@settings(max_examples=50, deadline=None)
@given(
    budget=st.integers(min_value=1, max_value=6),
    seq=st.lists(st.integers(min_value=0, max_value=7), min_size=1, max_size=60),
)
def test_property_lru_never_exceeds_budget_and_hits_iff_resident(budget, seq):
    """Invariant: residency <= budget; a lookup hits iff the key was resident."""
    from collections import OrderedDict

    cost = policy.CostModel(
        reconfig_s=1.0, dispatch_s=0.0,
        exec_generic_s={"op": 0.0}, exec_fixed_s={"op": 0.0},
    )
    roles = [(f"r{i}") for i in seq]
    spec_of = {r: GENERIC for r in roles}
    op_of = {r: "op" for r in roles}
    sim = policy.simulate_lru(roles, budget, cost, spec_of, op_of, repeats=1)

    # independent model
    resident: OrderedDict = OrderedDict()
    hits = misses = 0
    for r in roles:
        if r in resident:
            hits += 1
            resident.move_to_end(r)
        else:
            misses += 1
            if len(resident) >= budget:
                resident.popitem(last=False)
            resident[r] = None
        assert len(resident) <= budget
    assert sim.hits == hits and sim.misses == misses
    assert sim.total_s == pytest.approx(misses * 1.0)


# ---------------------------------------------------------------------------
# role planner (paper §IV trade-off)
# ---------------------------------------------------------------------------


def _cost(reconfig_ms=5.0):
    return policy.CostModel(
        reconfig_s=reconfig_ms * 1e-3,
        dispatch_s=10e-6,
        exec_generic_s={"fc": 100e-6},
        exec_fixed_s={"fc": 50e-6},
    )


def test_planner_prefers_generic_under_tight_budget():
    trace = [policy.Invocation("fc", i) for i in range(16)]
    plan = policy.plan_roles(trace, budget=2, cost=_cost())
    assert plan.assignment["fc"] == GENERIC
    assert plan.predicted.hit_rate == 1.0


def test_planner_prefers_fixed_weight_with_ample_regions():
    trace = [policy.Invocation("fc", i) for i in range(16)]
    plan = policy.plan_roles(trace, budget=32, cost=_cost())
    assert plan.assignment["fc"] == FIXED_WEIGHT


def test_planner_breakeven_moves_with_reconfig_cost():
    """Cheap reconfig -> specialization wins even when thrashing."""
    trace = [policy.Invocation("fc", i) for i in range(16)]
    plan_cheap = policy.plan_roles(trace, budget=2, cost=_cost(reconfig_ms=0.001))
    assert plan_cheap.assignment["fc"] == FIXED_WEIGHT


@settings(max_examples=25, deadline=None)
@given(
    budget=st.integers(min_value=1, max_value=40),
    n_layers=st.integers(min_value=1, max_value=24),
)
def test_property_planner_never_worse_than_all_generic(budget, n_layers):
    trace = [policy.Invocation("fc", i) for i in range(n_layers)]
    cost = _cost()
    plan = policy.plan_roles(trace, budget=budget, cost=cost)
    all_generic = policy.simulate_lru(
        policy.role_sequence(trace, {"fc": GENERIC}), budget, cost,
        {("fc", GENERIC): GENERIC}, {("fc", GENERIC): "fc"},
    )
    assert plan.predicted.total_s <= all_generic.total_s + 1e-12


# ---------------------------------------------------------------------------
# HSA runtime
# ---------------------------------------------------------------------------


def test_signal_semantics():
    s = Signal(2)
    assert s.load() == 2
    s.decrement()
    assert not s.wait_eq(0, timeout=0.01)
    s.decrement()
    assert s.wait_eq(0, timeout=0.1)


def test_queue_ring_and_overflow():
    agent = Agent.discover()[0]
    q = Queue(agent, size=2)
    led = OverheadLedger()
    lib = RoleLibrary(ledger=led)
    r = _mk_role(lib, 8)
    q.dispatch(r.key, jnp.ones((8, 8)), jnp.ones((8, 8)))
    q.dispatch(r.key, jnp.ones((8, 8)), jnp.ones((8, 8)))
    with pytest.raises(QueueFullError):
        q.dispatch(r.key, jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert q.pending() == 2


def test_hsa_end_to_end_dispatch_and_barrier():
    hsa_shut_down()
    sys_ = hsa_init(num_regions=2, ledger=OverheadLedger())
    try:
        lib = sys_.library
        r = _mk_role(lib, 16)
        lib.synthesize_all()
        agent = sys_.default_agent
        q, ex = sys_.queue_of(agent), sys_.executor_of(agent)
        x = jnp.ones((16, 16))
        p1 = q.dispatch(r.key, x, x, producer="tf")
        p2 = q.dispatch(r.key, x, x, producer="opencl")   # multi-producer
        bar = q.barrier([p1.completion, p2.completion])
        ex.drain(q)
        assert bar.completion.wait_eq(0, timeout=1.0)
        np.testing.assert_allclose(np.asarray(p2.out.value)[0, 0], 16.0)
        assert sys_.ledger.stat(ledger_mod.DISPATCH).count == 2
        assert sys_.ledger.stat(ledger_mod.RECONFIG).count == 1   # second was a hit
    finally:
        hsa_shut_down()


def test_hsa_background_executor():
    hsa_shut_down()
    sys_ = hsa_init(num_regions=2, ledger=OverheadLedger())
    try:
        lib = sys_.library
        r = _mk_role(lib, 8)
        agent = sys_.default_agent
        q, ex = sys_.queue_of(agent), sys_.executor_of(agent)
        ex.start(q)
        pkts = [q.dispatch(r.key, jnp.ones((8, 8)), jnp.ones((8, 8))) for _ in range(5)]
        for p in pkts:
            assert p.completion.wait_eq(0, timeout=5.0)
            np.testing.assert_allclose(np.asarray(p.out.value)[0, 0], 8.0)
    finally:
        hsa_shut_down()


def test_executor_surfaces_kernel_errors():
    hsa_shut_down()
    sys_ = hsa_init(num_regions=2, ledger=OverheadLedger())
    try:
        lib = sys_.library
        r = _mk_role(lib, 8)
        agent = sys_.default_agent
        q, ex = sys_.queue_of(agent), sys_.executor_of(agent)
        pkt = q.dispatch(r.key, jnp.ones((4, 4)), jnp.ones((4, 4)))  # wrong shape
        with pytest.raises(Exception):
            run_packet_sync(ex, q, pkt)
    finally:
        hsa_shut_down()


def test_agent_takes_tpu_peaks_from_its_device_kind():
    import types

    from repro import hw

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0)
    agent = Agent(v5e)
    hbm = next(r for r in agent.regions if r.name == "HBM")
    assert (hbm.size_bytes, hbm.bandwidth_bps) == (
        hw.TPU_V5E.hbm_bytes, hw.TPU_V5E.hbm_bw
    )
    other = types.SimpleNamespace(platform="tpu", device_kind="TPU v9", id=0)
    with pytest.raises(KeyError, match="TPU v9"):
        Agent(other)
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu", id=0)
    assert [r.name for r in Agent(cpu).regions] == ["RAM"]


def test_compile_cache_defers_to_the_environment(monkeypatch):
    from repro import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from pathlib import Path

    from repro import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
