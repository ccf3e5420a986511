"""Compile the serving path's kernels for a described TPU v5e, at Yi-6B widths.

Nothing runs: each case lowers and compiles for a ``v5e:2x2`` topology that
is described, not attached, so the TPU compiler (Mosaic for the Pallas
kernels) refuses here what it would refuse on the chip — unaligned blocks,
over-budget VMEM, programs that do not fit HBM — at no chip time.  Interpret
mode cannot see any of that.

The topology is described inside a module fixture, never at import: only one
process may hold the TPU library at a time, and every test worker imports
this file.  All cases stay in this one file so one worker holds it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import dispatch
from repro.kernels import ops
from repro.kernels.conv2d import conv2d
from repro.kernels.decode_attention import (
    decode_attention,
    paged_decode_attention,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd import ssd
from repro.models import build_model
from repro.models.params import abstract_params

YI = get_arch("yi-6b")
HD, HQ, HKV = YI.head_dim, YI.num_heads, YI.num_kv_heads      # 128, 32, 4
D, F = YI.d_model, YI.d_ff                                     # 4096, 11008
B, T = 8, 4096                                                 # slots, max_len
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page_size", [16, 64])
def test_paged_decode_attention_compiles(one_chip, page_size):
    n_pages = B * (T // page_size) + 1
    args = (
        _spec((B, HQ, HD), BF16, one_chip),
        _spec((n_pages, HKV, page_size, HD), BF16, one_chip),
        _spec((n_pages, HKV, page_size, HD), BF16, one_chip),
        _spec((B, T // page_size), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
    )
    assert _has_kernel(_compile(paged_decode_attention, *args))


def test_decode_attention_compiles(one_chip):
    args = (
        _spec((B, HQ, HD), BF16, one_chip),
        _spec((B, HKV, T, HD), BF16, one_chip),
        _spec((B, HKV, T, HD), BF16, one_chip),
        _spec((B,), jnp.int32, one_chip),
    )
    assert _has_kernel(_compile(decode_attention, *args))


def test_flash_attention_compiles(one_chip):
    S = 2048                                  # one long prompt, causal
    args = (
        _spec((1, HQ, S, HD), BF16, one_chip),
        _spec((1, HKV, S, HD), BF16, one_chip),
        _spec((1, HKV, S, HD), BF16, one_chip),
    )
    assert _has_kernel(_compile(flash_attention, *args))


@pytest.mark.parametrize("m,k,n", [(512, D, F), (512, F, D), (B, D, HQ * HD)])
def test_matmul_compiles(one_chip, m, k, n):
    args = (_spec((m, k), BF16, one_chip), _spec((k, n), BF16, one_chip))
    assert _has_kernel(_compile(ops.pallas_matmul, *args))


@pytest.mark.parametrize("rows", [B, 512])
def test_rmsnorm_compiles(one_chip, rows):
    args = (_spec((rows, D), BF16, one_chip), _spec((D,), BF16, one_chip))
    assert _has_kernel(_compile(rmsnorm, *args))


@pytest.mark.parametrize("prefer", ["xla", "pallas"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_yi6b_decode_step_compiles(one_chip, prefer, paged):
    """Two Yi-6B layers at full width, one decode step for ``B`` slots."""
    import dataclasses

    cfg = dataclasses.replace(YI, num_layers=2)
    model = build_model(cfg)
    shard = lambda s: _spec(s.shape, s.dtype, one_chip)   # noqa: E731
    params = jax.tree.map(shard, abstract_params(model.param_specs()))
    page_size = 16
    if paged:
        # the serving layout: KV leaves are page pools, one block table
        n_pages = B * (T // page_size) + 1
        dense = model.cache_specs(1, page_size)["segments"]
        segments = jax.tree.map(
            lambda s: _spec((s.shape[0], n_pages, *s.shape[2:]), s.dtype,
                            one_chip),
            dense,
        )
        cache = {
            "pos": _spec((B,), jnp.int32, one_chip),
            "segments": segments,
            "block_table": _spec((B, T // page_size), jnp.int32, one_chip),
        }
    else:
        cache = jax.tree.map(shard, model.cache_specs(B, T))
        cache["pos"] = _spec((B,), jnp.int32, one_chip)
    tokens = _spec((B, 1), jnp.int32, one_chip)
    trace = dispatch.DispatchTrace()
    with dispatch.use(prefer=dispatch.policy_from_flag(prefer), trace=trace):
        compiled = _compile(model.decode_step, params, tokens, cache)
    sources = {name.split(":")[0] for _, name in trace.events}
    assert _has_kernel(compiled) == (prefer == "pallas"), sources
    assert compiled.memory_analysis() is not None


# Mosaic refuses these two kernels; they are off the serving path.  The PR
# that mends a kernel flips its case (strict: an unexpected pass fails).

@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Pallas TPU lowering requires that the last two dimensions of "
    "the block shape (1, 256, 1, 64) are divisible by 8 and 128",
)
def test_ssd_compiles(one_chip):
    Bs, S, H, P, G, N = 1, 1024, 64, 64, 1, 128   # Mamba-2-ish head shapes
    args = (
        _spec((Bs, S, H, P), BF16, one_chip),
        _spec((H,), jnp.float32, one_chip),
        _spec((Bs, S, G, N), BF16, one_chip),
        _spec((Bs, S, G, N), BF16, one_chip),
        _spec((Bs, S, H), jnp.float32, one_chip),
    )
    _compile(ssd, *args)


@pytest.mark.xfail(
    strict=True,
    reason="Mosaic failed to compile TPU kernel: Bad lhs/rhs type: "
    "'vector<15376x128xi32>' 'vector<128x128xi32>'",
)
def test_conv2d_compiles(one_chip):
    args = (
        _spec((1, 128, 128, 1), jnp.int16, one_chip),
        _spec((5, 5, 1, 1), jnp.int16, one_chip),
    )
    _compile(conv2d, *args)
