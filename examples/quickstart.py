"""Quickstart: the paper's whole story in one script.

1. A tiny MLP written once against the transparent dispatch API.
2. The same model runs under three policies — pure-jnp reference, XLA,
   Pallas (interpreted on a CPU) — with identical numerics and zero model-code changes.
3. The HSA runtime path: presynthesized roles, bounded regions with LRU,
   and the Table II overhead ledger.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

import jax
import jax.numpy as jnp

import repro.kernels  # noqa: F401  (registers reference/xla/pallas kernels)
from repro.core import dispatch
from repro.core.hsa import hsa_init, hsa_shut_down, run_packet_sync
from repro.core.ledger import OverheadLedger
from repro.core.registry import GLOBAL_REGISTRY


def tiny_mlp(x, w1, w2):
    """User model code: no backend specifics, just logical ops."""
    h = dispatch.op("matmul", x, w1, activation="silu")
    h = dispatch.op("rmsnorm", h, jnp.ones(h.shape[-1], h.dtype))
    return dispatch.op("matmul", h, w2)


def main():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(128, 256)) * 0.05, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(256, 32)) * 0.05, jnp.float32)

    print("== 1. transparent backend switch (same code, same numbers) ==")
    outs = {}
    # Pallas kernels run compiled on a TPU; elsewhere only the interpreter
    # can run them
    interpret = jax.default_backend() == "cpu"
    for policy in ("reference", "xla", "pallas"):
        with dispatch.use(prefer=dispatch.policy_from_flag(policy),
                          interpret=interpret):
            outs[policy] = np.asarray(tiny_mlp(x, w1, w2))
        print(f"  policy={policy:10s} out[0,:3]={np.round(outs[policy][0,:3], 4)}")
    assert np.allclose(outs["reference"], outs["xla"], atol=1e-4)
    assert np.allclose(outs["reference"], outs["pallas"], atol=1e-3)
    print("  numerics agree across all three backends\n")

    print("== 2. HSA runtime: roles, regions, LRU, overhead ledger ==")
    ledger = OverheadLedger()
    sys_ = hsa_init(num_regions=2, ledger=ledger)
    try:
        impl = GLOBAL_REGISTRY.resolve("matmul", "any", ("xla",))
        a128 = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        a256 = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        w1s = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        w2s = jax.ShapeDtypeStruct((256, 32), jnp.float32)
        lib = sys_.library
        r1 = lib.make_role(impl, (a128, w1s), name="fc1")
        r2 = lib.make_role(impl, (a256, w2s), name="fc2")
        lib.synthesize_all()                      # presynthesis (device setup)

        agent = sys_.default_agent
        q, ex = sys_.queue_of(agent), sys_.executor_of(agent)
        for step in range(5):                     # both roles stay resident
            p1 = q.dispatch(r1.key, x, w1)
            h = run_packet_sync(ex, q, p1)
            p2 = q.dispatch(r2.key, jnp.asarray(h), w2)
            run_packet_sync(ex, q, p2)
        rm = sys_.regions_of(agent)
        print(f"  residency: {rm.stats} (regions={rm.num_regions})")
        print("  ledger (paper Table II layout):")
        for line in ledger.table().splitlines():
            print("   ", line)
    finally:
        hsa_shut_down()


if __name__ == "__main__":
    main()
