"""Pallas decode-attention kernel: one query token against a long KV cache.

The serving hot-spot: per decoded token the MXU does almost nothing and the
chip streams the KV cache from HBM once — so the kernel's job is to be
perfectly memory-shaped.  Design:

  - grid (B, Hkv, T/bk), KV-block axis innermost; the bf16 cache streams
    HBM→VMEM in ``bk``-sized tiles and is read exactly once.
  - GQA is blocked natively: one grid cell processes all ``group`` query
    heads of a kv head against the tile ([group, bk] logits fill MXU lanes).
  - online softmax (running max / denominator / accumulator in VMEM scratch),
    identical algebra to the flash kernel.
  - ``lengths`` [B] masks per-sequence valid cache (continuous batching:
    slots hold different positions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.registry import ResourceFootprint

NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref,
                *, scale: float, block_k: int, n_k: int) -> None:
    b = pl.program_id(0)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # [group, hd]
    k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)              # [bk, hd]
    length = len_ref[b]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [group, bk]
    kpos = ti * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ti == n_k - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,                   # [B, Hq, hd]
    k_cache: jax.Array,             # [B, Hkv, T, hd]
    v_cache: jax.Array,
    length,                         # scalar or [B] valid cache lengths
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, hd = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    bk = min(block_k, T)
    if T % bk:
        raise ValueError(f"T={T} not divisible by block_k={bk}")
    n_k = T // bk
    scale = scale if scale is not None else 1.0 / float(np.sqrt(hd))

    lengths = jnp.asarray(length)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    lengths = lengths.astype(jnp.int32)
    qg = q.reshape(B, Hkv, group, hd)

    kernel = functools.partial(_dec_kernel, scale=scale, block_k=bk, n_k=n_k)
    # lengths ride in SMEM as a scalar-prefetch operand, as in the paged
    # kernel: Mosaic refuses a (1,)-block of a rank-1 VMEM array
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # lengths
        grid=(B, Hkv, n_k),                       # KV innermost
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, t, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, t, ln: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, t, ln: (b, h, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, h, t, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(B, Hq, hd)


# ---------------------------------------------------------------------------
# paged (block-table) decode attention
# ---------------------------------------------------------------------------


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref,
                  *, scale: float, page_size: int, n_pages: int) -> None:
    """Same online softmax as :func:`_dec_kernel`, but the KV tile streamed at
    grid step ``i`` is pool page ``table_ref[b, i]`` (resolved by the
    scalar-prefetched block table in the BlockSpec index maps) instead of the
    ``i``-th contiguous slice of a dense cache — the cache never has to be
    contiguous in HBM, so the serving layer can allocate it page-at-a-time."""
    b = pl.program_id(0)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # [group, hd]
    k = k_ref[0, 0].astype(jnp.float32)              # [ps, hd]
    v = v_ref[0, 0].astype(jnp.float32)              # [ps, hd]
    length = len_ref[b]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [group, ps]
    kpos = ti * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ti == n_pages - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,                   # [B, Hq, hd]
    k_pages: jax.Array,             # [P, Hkv, ps, hd] global block pool
    v_pages: jax.Array,
    block_table: jax.Array,         # [B, NP] int32 page index -> pool page
    length,                         # scalar or [B] valid cache lengths
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a paged KV cache via a scalar-prefetched block
    table: grid (B, Hkv, NP), the page axis innermost, each KV tile DMA'd
    straight from its (non-contiguous) pool page.  Unlike the gather-based
    XLA formulation, no dense [B, Hkv, T, hd] copy is ever materialized in
    HBM — the gather happens on the HBM→VMEM stream."""
    B, Hq, hd = q.shape
    Hkv, ps = k_pages.shape[1], k_pages.shape[2]
    NP = block_table.shape[1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / float(np.sqrt(hd))

    lengths = jnp.asarray(length)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    lengths = lengths.astype(jnp.int32)
    table = block_table.astype(jnp.int32)
    qg = q.reshape(B, Hkv, group, hd)

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=ps, n_pages=NP
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # block table + lengths
        grid=(B, Hkv, NP),                        # page axis innermost
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, i, tab, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), lambda b, h, i, tab, ln: (tab[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), lambda b, h, i, tab, ln: (tab[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, h, i, tab, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        interpret=interpret,
    )(table, lengths, qg, k_pages, v_pages)
    return out.reshape(B, Hq, hd)


def paged_footprint(group: int = 8, page_size: int = 64, hd: int = 128,
                    itemsize: int = 2) -> ResourceFootprint:
    vmem = (
        group * hd * (itemsize + 4)       # q tile + accumulator
        + 2 * page_size * hd * itemsize   # k, v page tiles
        + group * page_size * 4           # logits tile
        + 2 * group * 4                   # m, l
    )
    return ResourceFootprint(vmem_bytes=vmem,
                             mxu_tiles=2 * max(1, page_size // 128))


def footprint(group: int = 8, block_k: int = 512, hd: int = 128,
              itemsize: int = 2) -> ResourceFootprint:
    vmem = (
        group * hd * (itemsize + 4)     # q tile + accumulator
        + 2 * block_k * hd * itemsize   # k, v tiles
        + group * block_k * 4           # logits tile
        + 2 * group * 4                 # m, l
    )
    return ResourceFootprint(vmem_bytes=vmem,
                             mxu_tiles=2 * max(1, block_k // 128))
