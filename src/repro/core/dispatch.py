"""Transparent op dispatch — the "no secondary toolchain" property.

Model code calls ``dispatch.op("matmul", x, w)`` instead of a concrete
implementation.  Under ``jax.jit`` this function runs at *trace time*, so the
resolved implementation is baked into the compiled program with zero runtime
indirection — the TPU-idiomatic translation of TensorFlow looking up a
registered HSA kernel in its executor.

The active :class:`DispatchContext` selects the device kind and source
preference.  Flipping ``prefer=("pallas", "xla", "reference")`` retargets an
entire model to hand-written Pallas roles without touching model code; that
one-flag switch is the paper's transparency claim.

With no explicit preference, an op named in :data:`PLATFORM_PREFER` is staged
per platform (``jax.lax.platform_dependent``): the program lowered for that
platform runs its source, every other platform the default order.  The choice
is made when the program is lowered, so a TPU program compiled on a CPU host
(a described topology) gets the TPU's source too.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable, Iterator, Sequence

import jax

from repro.core.registry import GLOBAL_REGISTRY, KernelImpl, KernelRegistry


#: source order of every op when the context names none
DEFAULT_PREFER: tuple[str, ...] = ("xla", "reference")

#: ops whose default source depends on the platform the program is lowered
#: for.  The paged decode attention's XLA source gathers every table page of
#: every slot; on a TPU the Pallas kernel reads each slot's live pages only.
PLATFORM_PREFER: dict[str, dict[str, tuple[str, ...]]] = {
    "paged_decode_attention": {"tpu": ("pallas", "xla", "reference")},
}


@dataclasses.dataclass(frozen=True)
class DispatchContext:
    device_kind: str = "tpu"
    prefer: tuple[str, ...] | None = None      # None: DEFAULT_PREFER / PLATFORM_PREFER
    registry: KernelRegistry = GLOBAL_REGISTRY
    interpret: bool = False          # forwarded to pallas impls (CPU validation)
    trace: "DispatchTrace | None" = None
    # resolution memo: device_kind/prefer/registry are frozen per context, so
    # (op, specialization) fully determines the resolved impl — hot trace
    # loops (one dispatch.op per layer per step) stop re-walking the
    # preference order.  Entries carry the registry version so a late
    # registration invalidates them.
    _resolve_cache: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False, hash=False
    )

    def order(self, op: str, platform: str | None = None) -> tuple[str, ...]:
        """Source preference for ``op`` in a program lowered for
        ``platform`` (default: JAX's default backend)."""
        if self.prefer is not None:
            return self.prefer
        by_platform = PLATFORM_PREFER.get(op, {})
        if by_platform and platform is None:
            platform = jax.default_backend()
        return by_platform.get(platform, DEFAULT_PREFER)

    def resolve(self, op: str, *, specialization: str | None = None,
                platform: str | None = None) -> KernelImpl:
        prefer = self.order(op, platform)
        key = (op, specialization, prefer)
        version = self.registry.version
        hit = self._resolve_cache.get(key)
        if hit is not None and hit[0] == version:
            return hit[1]
        impl = self.registry.resolve(
            op, self.device_kind, prefer, specialization=specialization
        )
        self._resolve_cache[key] = (version, impl)
        return impl


class DispatchTrace:
    """Records the sequence of resolved ops (role keys) during a trace.

    The role planner (:mod:`repro.core.policy`) consumes this to decide the
    generic-vs-fixed-weight split under a region budget.
    """

    def __init__(self) -> None:
        self.events: list[tuple[str, str]] = []   # (op, impl name)

    def record(self, op: str, impl: KernelImpl) -> None:
        self.events.append((op, impl.name))

    def op_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for op_name, _ in self.events:
            counts[op_name] = counts.get(op_name, 0) + 1
        return counts


_DEFAULT = DispatchContext()
_CTX: contextvars.ContextVar[DispatchContext] = contextvars.ContextVar(
    "repro_dispatch_ctx", default=_DEFAULT
)


def current() -> DispatchContext:
    return _CTX.get()


@contextlib.contextmanager
def use(
    *,
    device_kind: str | None = None,
    prefer: Sequence[str] | None = None,
    registry: KernelRegistry | None = None,
    interpret: bool | None = None,
    trace: DispatchTrace | None = None,
) -> Iterator[DispatchContext]:
    """Scoped dispatch policy, like the paper's device annotation in user code."""
    base = _CTX.get()
    ctx = DispatchContext(
        device_kind=device_kind if device_kind is not None else base.device_kind,
        prefer=tuple(prefer) if prefer is not None else base.prefer,
        registry=registry if registry is not None else base.registry,
        interpret=interpret if interpret is not None else base.interpret,
        trace=trace if trace is not None else base.trace,
    )
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def op(name: str, *args: Any, specialization: str | None = None, **kwargs: Any) -> Any:
    """Dispatch a logical op through the active context (trace-time resolved).

    An op of :data:`PLATFORM_PREFER` under the default preference is staged
    for each platform and picked when the program is lowered; the trace
    records the source of the default backend."""
    ctx = _CTX.get()
    impl = ctx.resolve(name, specialization=specialization)
    if ctx.trace is not None:
        ctx.trace.record(name, impl)

    def call(impl: KernelImpl) -> Callable[..., Any]:
        kw = dict(kwargs, interpret=True) if (
            impl.source == "pallas" and ctx.interpret) else kwargs
        return lambda *a: impl.fn(*a, **kw)

    if ctx.prefer is None and name in PLATFORM_PREFER:
        other = ctx.resolve(name, specialization=specialization, platform="")
        staged = {p: ctx.resolve(name, specialization=specialization, platform=p)
                  for p in PLATFORM_PREFER[name]}
        if any(i is not other for i in staged.values()):
            return jax.lax.platform_dependent(
                *args, default=call(other),
                **{p: call(i) for p, i in staged.items()})
    return call(impl)(*args)


def resolve(name: str, *, specialization: str | None = None) -> KernelImpl:
    return _CTX.get().resolve(name, specialization=specialization)


def policy_from_flag(policy: str) -> tuple[str, ...]:
    """Map a CLI ``--policy`` flag to a source-preference order."""
    orders = {
        "reference": ("reference",),
        "xla": ("xla", "reference"),
        "pallas": ("pallas", "xla", "reference"),
        "pallas-strict": ("pallas",),
    }
    if policy not in orders:
        raise ValueError(f"unknown policy {policy!r}; choose from {sorted(orders)}")
    return orders[policy]
