"""Golden-path regression net for the transparency one-flag switch.

The paper's headline property is that retargeting a model is a *flag*, not a
code change.  These tests pin the exact flag -> preference-order mapping and
the op sequence a traced model produces, so runtime refactors (like the
async scheduler) cannot silently change what the flag dispatches to.
"""

import jax.numpy as jnp
import pytest

import repro.kernels  # noqa: F401
from repro.core import dispatch

# the contract: flag -> source preference order, verbatim
POLICY_GOLDEN = {
    "reference": ("reference",),
    "xla": ("xla", "reference"),
    "pallas": ("pallas", "xla", "reference"),
    "pallas-strict": ("pallas",),
}


def test_policy_from_flag_orders_are_stable():
    for flag, expected in POLICY_GOLDEN.items():
        assert dispatch.policy_from_flag(flag) == expected


def test_policy_from_flag_rejects_unknown():
    with pytest.raises(ValueError) as ei:
        dispatch.policy_from_flag("tensorflow")
    # error enumerates the valid flags
    for flag in POLICY_GOLDEN:
        assert flag in str(ei.value)


def test_policy_flag_set_is_closed():
    """Adding/removing a policy flag must update this golden set."""
    for flag in POLICY_GOLDEN:
        dispatch.policy_from_flag(flag)
    assert set(POLICY_GOLDEN) == {"reference", "xla", "pallas", "pallas-strict"}


def _traced_mlp_counts(prefer):
    """One transformer-ish block traced under a policy; returns op_counts."""
    trace = dispatch.DispatchTrace()
    x = jnp.ones((4, 32))
    w1 = jnp.ones((32, 64))
    w2 = jnp.ones((64, 32))
    g = jnp.ones((32,))
    with dispatch.use(prefer=prefer, trace=trace, interpret=True):
        h = dispatch.op("matmul", x, w1)
        h = dispatch.op("matmul", h, w2)
        h = dispatch.op("rmsnorm", h, g)
        h = dispatch.op("matmul", h, w2.T)
    return trace.op_counts()


GOLDEN_COUNTS = {"matmul": 3, "rmsnorm": 1}


def test_dispatch_trace_op_counts_stable_across_policies():
    """Same model, any policy: identical op multiset (transparency)."""
    for flag in POLICY_GOLDEN:
        counts = _traced_mlp_counts(dispatch.policy_from_flag(flag))
        assert counts == GOLDEN_COUNTS, flag


def test_dispatch_trace_records_impl_source_switch():
    """The trace records *which* impl served each op — and it follows the flag."""
    trace_ref = dispatch.DispatchTrace()
    trace_xla = dispatch.DispatchTrace()
    x = jnp.ones((8, 8))
    with dispatch.use(prefer=("reference",), trace=trace_ref):
        dispatch.op("matmul", x, x)
    with dispatch.use(prefer=("xla", "reference"), trace=trace_xla):
        dispatch.op("matmul", x, x)
    (op_r, impl_r), = trace_ref.events
    (op_x, impl_x), = trace_xla.events
    assert op_r == op_x == "matmul"
    assert impl_r != impl_x                       # different backends resolved


def test_op_counts_empty_trace():
    assert dispatch.DispatchTrace().op_counts() == {}


@pytest.mark.parametrize("platform,prefer,source", [
    ("cpu", None, "xla"),
    ("tpu", None, "pallas"),
    ("cpu", ("pallas", "xla", "reference"), "pallas"),
    ("tpu", ("xla", "reference"), "xla"),
    ("tpu", ("reference",), "reference"),
])
def test_paged_attention_source_follows_the_platform(platform, prefer, source):
    """Under the default preference the paged decode attention takes the
    Pallas kernel in a program lowered for a TPU and XLA elsewhere; an
    explicit preference wins on every platform, and no other op moves."""
    with dispatch.use(prefer=prefer) as ctx:
        got = ctx.resolve("paged_decode_attention", platform=platform)
        others = {op: ctx.resolve(op, platform=platform).source
                  for op in ("matmul", "rmsnorm", "decode_attention",
                             "flash_attention")}
    assert got.source == source
    assert set(others.values()) == {(prefer or ("xla",))[0]}


def test_the_default_backend_lowers_the_xla_paged_attention():
    """On the CPU the default dispatch lowers the XLA gather: no Pallas
    kernel in the program, and the trace records the XLA source."""
    import jax

    from repro.kernels import ops

    q = jnp.ones((2, 4, 16), jnp.bfloat16)
    pages = jnp.ones((5, 2, 8, 16), jnp.bfloat16)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.asarray([3, 16], jnp.int32)
    trace = dispatch.DispatchTrace()
    fn = jax.jit(lambda *a: dispatch.op("paged_decode_attention", *a))
    with dispatch.use(trace=trace):
        hlo = fn.lower(q, pages, pages, table, lengths).as_text()
        got = fn(q, pages, pages, table, lengths)
    assert jax.default_backend() == "cpu"
    assert "tpu_custom_call" not in hlo and "pallas" not in hlo.lower()
    assert [n for _, n in trace.events] == ["paged_decode_attention:xla:generic"]
    want = ops.xla_paged_decode_attention(q, pages, pages, table, lengths)
    assert jnp.array_equal(got, want)
