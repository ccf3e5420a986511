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

The paged kernel reads a block pool through a block table instead, and only
the pages below each slot's length: one grid step per slot, a loop over its
live blocks, whole pages copied HBM→VMEM by hand, double-buffered.
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

#: a block holds at least this many cache rows of every KV head, so each
#: head's logits tile [group, rows] fills the MXU's 128 lanes ...
MIN_BLOCK_ROWS = 128
#: ... and at least this many bytes of K (as many of V), so a block's page
#: copies outweigh the fixed cost of a loop step.  On a TPU v5e at Yi-6B's
#: pages (16 KiB), 64 calls over 12 slots of 1,400 rows took 6.6 ms with
#: 512-KiB blocks and 9.0 ms with 128-KiB ones
MIN_BLOCK_BYTES = 512 * 1024


def paged_block_pages(page_size: int, n_kv: int, hd: int, itemsize: int,
                      n_pages: int) -> int:
    """Pages per block of the paged kernel, from the page's shape: the
    fewest whole pages that give ``MIN_BLOCK_ROWS`` rows and
    ``MIN_BLOCK_BYTES`` of K, never more than the table holds."""
    page_bytes = n_kv * page_size * hd * itemsize
    want = max(pl.cdiv(MIN_BLOCK_ROWS, page_size),
               pl.cdiv(MIN_BLOCK_BYTES, page_bytes))
    return max(1, min(n_pages, want))


def _paged_kernel(table_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, half_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, page_size: int, block_pages: int,
                  n_pages: int, n_slots: int) -> None:
    """One grid step per slot; inside it, a loop over the slot's live blocks
    only.  A block is ``block_pages`` pages, each copied HBM→VMEM whole (all
    KV heads in one DMA) from pool page ``table[b, i]`` of layer ``layer``,
    and only if the page holds a position below the slot's length.  Copies
    are double-buffered across blocks and across slots: while block ``j``
    is computed, block ``j + 1`` (or the next slot's first block) lands in
    the other half of the buffers."""
    b = pl.program_id(0)
    n_kv, rows = k_buf.shape[1], k_buf.shape[2]
    group = q_ref.shape[2]

    def live_pages(s):
        return jnp.clip(pl.cdiv(len_ref[s], page_size), 1, n_pages)

    def page_copies(s, blk, half):
        first = blk * block_pages
        n_live = live_pages(s) - first
        layer = layer_ref[0]
        for i in range(block_pages):
            page = table_ref[s * n_pages + jnp.minimum(first + i, n_pages - 1)]
            dst = (half, slice(None), pl.ds(i * page_size, page_size))
            yield i < n_live, (
                pltpu.make_async_copy(k_hbm.at[layer, page], k_buf.at[dst],
                                      sems.at[0, half]),
                pltpu.make_async_copy(v_hbm.at[layer, page], v_buf.at[dst],
                                      sems.at[1, half]),
            )

    def start(s, blk, half):
        for live, copies in page_copies(s, blk, half):
            @pl.when(live)
            def _():
                for c in copies:
                    c.start()

    def wait(s, blk, half):
        for live, copies in page_copies(s, blk, half):
            @pl.when(live)
            def _():
                for c in copies:
                    c.wait()

    @pl.when(b == 0)
    def _first():
        # a page slot that a block leaves uncopied then holds zeros or a
        # page copied for an earlier block, never uninitialised memory: its
        # rows are masked out of the logits, and their V meets weight 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        half_ref[0] = 0
        start(0, 0, 0)

    length = len_ref[b]
    n_blocks = pl.cdiv(live_pages(b), block_pages)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(blk, carry):
        half = half_ref[0]
        other = 1 - half

        @pl.when(blk + 1 < n_blocks)
        def _next_block():
            start(b, blk + 1, other)

        @pl.when((blk + 1 == n_blocks) & (b + 1 < n_slots))
        def _next_slot():
            start(b + 1, 0, other)

        wait(b, blk, half)
        kpos = blk * rows + jax.lax.broadcasted_iota(jnp.int32, (group, rows), 1)
        valid = kpos < length
        for h in range(n_kv):
            k = k_buf[half, h]                                  # [rows, hd]
            v = v_buf[half, h]
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [group, rows]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        half_ref[0] = other
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    for h in range(n_kv):
        o_ref[0, h] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,                   # [B, Hq, hd]
    k_pages: jax.Array,             # [P, Hkv, ps, hd] global block pool
    v_pages: jax.Array,
    block_table: jax.Array,         # [B, NP] int32 page index -> pool page
    length,                         # scalar or [B] valid cache lengths, >= 1
    *,
    layer=None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a paged KV cache that reads only each slot's
    live pages: those holding a position below its ``length``.

    The pools stay in HBM (``pl.ANY``); the block table, the lengths and
    ``layer`` ride in SMEM as scalar prefetch.  The grid is one step per
    slot, and each step loops over ``ceil(pages / block_pages)`` blocks of
    that slot alone, so HBM reads and grid work scale with the live
    context, not with slots × table width: a slot of length 1 costs one
    page.  Each block is an online-softmax step in f32 over all of a KV
    head's query group at once.  With ``layer`` the pools are stacks
    [L, P, Hkv, ps, hd] and the layer is picked in the copies, so no layer
    is sliced out.  ``length`` must be at least 1 for every slot."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, Hq, hd = q.shape
    Hkv, ps = k_pages.shape[2], k_pages.shape[3]
    NP = block_table.shape[1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / float(np.sqrt(hd))
    block_pages = paged_block_pages(ps, Hkv, hd, k_pages.dtype.itemsize, NP)
    rows = block_pages * ps

    lengths = jnp.asarray(length)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    lengths = lengths.astype(jnp.int32)
    table = block_table.astype(jnp.int32).reshape(-1)
    layers = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    qg = q.reshape(B, Hkv, group, hd)

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=ps, block_pages=block_pages,
        n_pages=NP, n_slots=B,
    )
    slot_spec = pl.BlockSpec((1, Hkv, group, hd),
                             lambda b, tab, ln, ly: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # block table, lengths, layer
        grid=(B,),
        in_specs=[
            slot_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, rows, hd), k_pages.dtype),
            pltpu.VMEM((2, Hkv, rows, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # (K, V) x buffer half
            pltpu.SMEM((1,), jnp.int32),          # half the next block uses
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        # the copies of one step are started in the step before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(table, lengths, layers, qg, k_pages, v_pages)
    return out.reshape(B, Hq, hd)


def paged_footprint(group: int = 8, page_size: int = 16, hd: int = 128,
                    n_kv: int = 4, itemsize: int = 2,
                    n_pages: int = 256) -> ResourceFootprint:
    rows = page_size * paged_block_pages(page_size, n_kv, hd, itemsize, n_pages)
    vmem = (
        2 * 2 * n_kv * rows * hd * itemsize       # K and V, double-buffered
        + 2 * 2 * n_kv * group * hd * itemsize    # q and out slots, pipelined
        + n_kv * group * hd * 4                   # accumulator
        + 2 * n_kv * group * 4                    # m, l
        + group * rows * 4                        # one head's logits tile
    )
    return ResourceFootprint(vmem_bytes=vmem,
                             mxu_tiles=2 * n_kv * max(1, rows // 128))


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
