"""Roles: shape-specialized, presynthesized accelerator programs.

Paper mapping
-------------
An FPGA *role* is a presynthesized partial bitstream implementing one kernel,
registered with TensorFlow and loaded into a reconfigurable region on demand.
The TPU-native analogue implemented here:

  - *synthesis*   = trace + lower to StableHLO (``jit(fn).lower(*abstract)``).
    This is the expensive, offline, HLS-like step.  The lowered artifact is the
    "bitstream": device-agnostic, storable, registered in the role library.
  - *reconfiguration / load* = ``lowered.compile()`` — turning the stored
    artifact into a device-loaded executable.  On a real TPU fleet with a warm
    persistent compilation cache this is dominated by program upload; on this
    host it is the measured XLA-backend load.  Eviction (``unload``) drops the
    executable, freeing the region.
  - *dispatch*    = calling the loaded executable (async, HSA-queue mediated).

Two sources, as in the paper:
  - ``presynthesized`` roles lower at library-build time (``synthesize()``),
  - ``online`` roles lower lazily on first load ("runtime synthesis" — the
    flexible-but-costly OpenCL path the paper describes and then avoids for
    the mobile use case).

Roles are keyed by (op, abstract arg signature, specialization): like
bitstreams, they are shape- and dtype-specialized.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np

from repro.core import ledger as ledger_mod
from repro.core.ledger import GLOBAL_LEDGER, OverheadLedger
from repro.core.registry import GENERIC, KernelImpl

PRESYNTHESIZED = "presynthesized"
ONLINE = "online"


def _sig_of(aval: jax.ShapeDtypeStruct) -> tuple[tuple[int, ...], str]:
    return (tuple(aval.shape), np.dtype(aval.dtype).name)


@dataclasses.dataclass(frozen=True)
class RoleKey:
    op: str
    signature: tuple[tuple[tuple[int, ...], str], ...]
    specialization: str = GENERIC

    def __str__(self) -> str:
        shapes = ",".join("x".join(map(str, s)) + d for s, d in self.signature)
        return f"{self.op}[{shapes}]{'' if self.specialization == GENERIC else '#' + self.specialization}"


class Role:
    """One shape-specialized accelerator program."""

    def __init__(
        self,
        impl: KernelImpl,
        abstract_args: Sequence[jax.ShapeDtypeStruct],
        *,
        static_kwargs: Mapping[str, Any] | None = None,
        source: str = PRESYNTHESIZED,
        name: str | None = None,
    ) -> None:
        if source not in (PRESYNTHESIZED, ONLINE):
            raise ValueError(f"bad role source {source!r}")
        self.impl = impl
        self.abstract_args = tuple(abstract_args)
        self.static_kwargs = dict(static_kwargs or {})
        self.source = source
        self.key = RoleKey(
            op=impl.op,
            signature=tuple(_sig_of(a) for a in self.abstract_args),
            specialization=impl.specialization,
        )
        self.name = name or str(self.key)
        self._lowered: Any = None          # the "bitstream"
        self._executable: Any = None       # loaded into a region
        self.synthesis_s: float | None = None
        self.load_count = 0

    # -- lifecycle -----------------------------------------------------------

    def _jitted(self) -> Any:
        kw = self.static_kwargs

        def call(*args: Any) -> Any:
            return self.impl.fn(*args, **kw)

        return jax.jit(call)

    def synthesize(self) -> float:
        """Trace + lower (the offline 'HLS' step). Idempotent; returns seconds."""
        if self._lowered is None:
            t0 = time.perf_counter_ns()
            self._lowered = self._jitted().lower(*self.abstract_args)
            self.synthesis_s = (time.perf_counter_ns() - t0) * 1e-9
        return self.synthesis_s or 0.0

    def load(self) -> Any:
        """Compile/load the artifact into a 'region'. Returns the executable."""
        if self._executable is None:
            if self._lowered is None:
                # online synthesis at dispatch time (the flexible OpenCL path)
                self.synthesize()
            self._executable = self._lowered.compile()
            self.load_count += 1
        return self._executable

    def unload(self) -> None:
        """Eviction: free the region. The lowered artifact (bitstream) survives."""
        self._executable = None

    @property
    def resident(self) -> bool:
        return self._executable is not None

    # -- execution ------------------------------------------------------------

    def __call__(self, *args: Any) -> Any:
        exe = self.load()
        return exe(*args)

    # -- reporting (paper Table I analogue) ------------------------------------

    def footprint(self) -> dict[str, float]:
        arg_bytes = sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize for a in self.abstract_args
        )
        fp = self.impl.footprint
        out: dict[str, float] = {
            "arg_bytes": float(arg_bytes),
            "vmem_bytes": float(fp.vmem_bytes),
            "vmem_pct": 100.0 * fp.vmem_fraction(),
        }
        if self._executable is not None:
            try:
                ma = self._executable.memory_analysis()
                out["temp_bytes"] = float(ma.temp_size_in_bytes)
                out["code_bytes"] = float(ma.generated_code_size_in_bytes)
            except Exception:  # backend may not support it
                pass
        return out


class RoleLibrary:
    """All roles known to the runtime; the paper's registered-bitstream store."""

    def __init__(self, ledger: OverheadLedger = GLOBAL_LEDGER) -> None:
        self._roles: dict[RoleKey, Role] = {}
        self.ledger = ledger

    def add(self, role: Role) -> Role:
        if role.key in self._roles:
            return self._roles[role.key]
        self._roles[role.key] = role
        return role

    def make_role(
        self,
        impl: KernelImpl,
        abstract_args: Sequence[jax.ShapeDtypeStruct],
        **kw: Any,
    ) -> Role:
        return self.add(Role(impl, abstract_args, **kw))

    def get(self, key: RoleKey) -> Role:
        return self._roles[key]

    def __len__(self) -> int:
        return len(self._roles)

    def __iter__(self):
        return iter(self._roles.values())

    def synthesize_all(self) -> float:
        """Presynthesize every presynthesized-source role (device/kernel setup).

        Recorded under the ledger's SETUP category — the paper's one-time cost.
        """
        total = 0.0
        with self.ledger.span("hsa.synthesize", ledger_mod.SETUP,
                              what="synthesize_all", n=len(self._roles)):
            for role in self._roles.values():
                if role.source == PRESYNTHESIZED:
                    total += role.synthesize()
        return total
