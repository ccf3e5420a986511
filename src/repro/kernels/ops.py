"""Registered kernel implementations — the role catalogue.

Importing this module populates ``GLOBAL_REGISTRY`` with three sources per op:

  - ``reference``: pure-jnp oracle (ref.py),
  - ``xla``: production XLA formulation (memory-efficient where it matters —
    chunked attention for 32k prefill, chunked SSD scan),
  - ``pallas``: the hand-written TPU kernel (the presynthesized role).

Model code never imports these directly; it calls ``dispatch.op(name, ...)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.registry import GLOBAL_REGISTRY as REG
from repro.kernels import conv2d as conv2d_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import matmul as matmul_k
from repro.kernels import ref
from repro.kernels import rmsnorm as rmsnorm_k
from repro.kernels import ssd as ssd_k

# --------------------------------------------------------------------------
# matmul
# --------------------------------------------------------------------------


def xla_matmul(x, w, *, out_dtype=None, activation=None):
    """Emits the input dtype directly for bf16 inputs: the TPU MXU
    accumulates in f32 internally either way, and an f32 dot output +
    convert doubles the tensor's HBM traffic at every fusion boundary."""
    target = out_dtype or x.dtype
    pet = jnp.float32 if target == jnp.float32 else x.dtype
    acc = jnp.dot(x, w, preferred_element_type=pet)
    if activation == "silu":
        acc = acc * jax.nn.sigmoid(acc.astype(jnp.float32)).astype(acc.dtype)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc)
    elif activation is not None:
        raise ValueError(activation)
    return acc.astype(target)


def _fit_block(dim: int, target: int) -> int:
    b = min(target, dim)
    while dim % b:
        b //= 2
        if b < 8:
            return dim  # single block
    return b


def pallas_matmul(x, w, *, out_dtype=None, activation=None, interpret: bool = False):
    """Reshapes batched x to 2-D and picks dividing block sizes."""
    *lead, K = x.shape
    M = int(np.prod(lead)) if lead else 1
    N = w.shape[-1]
    bm, bn, bk = _fit_block(M, 256), _fit_block(N, 256), _fit_block(K, 512)
    out = matmul_k.matmul(
        x.reshape(M, K), w, block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype, activation=activation, interpret=interpret,
    )
    return out.reshape(*lead, N)


REG.register(
    __import__("repro.core.registry", fromlist=["KernelImpl"]).KernelImpl(
        op="matmul", device_kind="any", source="reference", fn=ref.matmul,
    )
)
from repro.core.registry import KernelImpl  # noqa: E402

REG.register(KernelImpl(op="matmul", device_kind="any", source="xla", fn=xla_matmul))
REG.register(
    KernelImpl(
        op="matmul", device_kind="tpu", source="pallas", fn=pallas_matmul,
        footprint=matmul_k.footprint(),
    )
)

# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------

REG.register(KernelImpl(op="rmsnorm", device_kind="any", source="reference", fn=ref.rmsnorm))
REG.register(KernelImpl(op="rmsnorm", device_kind="any", source="xla", fn=ref.rmsnorm))
REG.register(
    KernelImpl(
        op="rmsnorm", device_kind="tpu", source="pallas", fn=rmsnorm_k.rmsnorm,
        footprint=rmsnorm_k.footprint(),
    )
)

# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


def xla_flash_attention(
    q, k, v, *, causal: bool = True, window: int | None = None,
    scale: float | None = None, block_q: int = 512,
):
    """Memory-efficient exact attention: lax.map over query chunks.

    Peak memory is O(block_q · T) per (batch, head) instead of O(S · T) — the
    property that lets 32k-token prefill fit HBM. Equivalent to ref for all
    mask settings (golden-tested).
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    group = Hq // Hkv
    scale_ = scale if scale is not None else 1.0 / float(np.sqrt(D))
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    n_blocks = S // bq
    kv_offset = T - S

    kg = jnp.repeat(k, group, axis=1)
    vg = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(T)[None, :]

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=2)        # [B,H,bq,D]
        # f32 softmax statistics; probs stored in the compute dtype for the
        # PV matmul — the all-f32 chain doubled attention HBM traffic
        logits = jnp.einsum("bhsd,bhtd->bhst", qb, kg,
                            preferred_element_type=jnp.float32) * scale_
        qpos = (i * bq + jnp.arange(bq) + kv_offset)[:, None]
        mask = jnp.ones((bq, T), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", probs, vg,
                          preferred_element_type=jnp.float32)

    from repro.roofline.unrolling import inner_loops_unrolled

    Dv = v.shape[-1]                    # MLA: d_v may differ from d_qk
    if n_blocks == 1:
        out = one_block(jnp.asarray(0))
    elif inner_loops_unrolled():        # cost-mode: straight-line for FLOP counting
        out = jnp.stack([one_block(jnp.asarray(i)) for i in range(n_blocks)])
        out = jnp.moveaxis(out, 0, 2).reshape(B, Hq, S, Dv)
    else:
        out = jax.lax.map(one_block, jnp.arange(n_blocks))              # [n,B,H,bq,Dv]
        out = jnp.moveaxis(out, 0, 2).reshape(B, Hq, S, Dv)
    return out.astype(q.dtype)


REG.register(
    KernelImpl(op="flash_attention", device_kind="any", source="reference",
               fn=ref.flash_attention)
)
REG.register(
    KernelImpl(op="flash_attention", device_kind="any", source="xla",
               fn=xla_flash_attention)
)
REG.register(
    KernelImpl(
        op="flash_attention", device_kind="tpu", source="pallas",
        fn=fa_k.flash_attention, footprint=fa_k.footprint(),
    )
)

# --------------------------------------------------------------------------
# decode attention (single-token query over a padded KV cache)
# --------------------------------------------------------------------------

def xla_decode_attention(q, k_cache, v_cache, length, *, scale=None):
    """Grouped-GQA decode attention: cache read once in its storage dtype
    (no head-repeat materialization, no f32 cache upcast)."""
    B, Hq, D = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale_ = scale if scale is not None else 1.0 / float(np.sqrt(D))
    qg = q.reshape(B, Hkv, group, D)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale_
    lengths = jnp.asarray(length)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    valid = jnp.arange(T)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(valid, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits - m)
    denom = jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return (out / denom).reshape(B, Hq, D).astype(q.dtype)


REG.register(
    KernelImpl(op="decode_attention", device_kind="any", source="reference",
               fn=ref.decode_attention)
)
REG.register(
    KernelImpl(op="decode_attention", device_kind="any", source="xla",
               fn=xla_decode_attention)
)

from repro.kernels import decode_attention as dec_k  # noqa: E402

REG.register(
    KernelImpl(
        op="decode_attention", device_kind="tpu", source="pallas",
        fn=dec_k.decode_attention, footprint=dec_k.footprint(),
    )
)

# --------------------------------------------------------------------------
# paged decode attention (block-table KV gather)
# --------------------------------------------------------------------------


def xla_paged_decode_attention(q, k_pages, v_pages, block_table, length, *,
                               layer=None, scale=None):
    """Gather-then-dense formulation: ``jnp.take`` reassembles the sequence's
    pages into the dense [B, Hkv, T, hd] layout, then the grouped-GQA dense
    decode attention runs unchanged.  Because the gather is arithmetic-free
    and the downstream math is *the same function*, the result is
    bitwise-identical to :func:`xla_decode_attention` over an equivalent
    dense cache — the property the paged serving engine's equivalence
    guarantee rests on, and why the CPU runs this source.  It gathers every
    table page of every slot whatever the length; a TPU runs the Pallas
    kernel instead (``core.dispatch.PLATFORM_PREFER``), which copies only
    each slot's live pages HBM→VMEM and never materializes the dense copy.
    With ``layer`` the pools are stacks [L, P, Hkv, ps, hd], gathered at
    ``(layer, block_table)`` so the layer's pool is never sliced out."""
    kg = ref.gather_kv_pages(k_pages, block_table, layer)
    vg = ref.gather_kv_pages(v_pages, block_table, layer)
    return xla_decode_attention(q, kg, vg, length, scale=scale)


REG.register(
    KernelImpl(op="paged_decode_attention", device_kind="any",
               source="reference", fn=ref.paged_decode_attention)
)
REG.register(
    KernelImpl(op="paged_decode_attention", device_kind="any", source="xla",
               fn=xla_paged_decode_attention)
)
REG.register(
    KernelImpl(
        op="paged_decode_attention", device_kind="tpu", source="pallas",
        fn=dec_k.paged_decode_attention, footprint=dec_k.paged_footprint(),
    )
)

# --------------------------------------------------------------------------
# conv2d
# --------------------------------------------------------------------------

REG.register(KernelImpl(op="conv2d", device_kind="any", source="reference", fn=ref.conv2d))
REG.register(KernelImpl(op="conv2d", device_kind="any", source="xla", fn=ref.conv2d))
REG.register(
    KernelImpl(
        op="conv2d", device_kind="tpu", source="pallas", fn=conv2d_k.conv2d,
        footprint=conv2d_k.footprint(),
    )
)

# --------------------------------------------------------------------------
# ssd (Mamba-2 state-space duality)
# --------------------------------------------------------------------------


def xla_ssd(x, a_log, b, c, dt, *, chunk: int = 256, initial_state=None,
            return_state: bool = False):
    """Chunked SSD in pure XLA: scan over chunk states, matmuls within chunks.

    Same decomposition as the Pallas kernel, vectorized over (B, H); the
    sequential dimension is S/chunk instead of S, preserving MXU-sized matmuls.
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    q = min(chunk, S)
    while S % q:
        q //= 2
    n_chunks = S // q

    xf = x.astype(jnp.float32).reshape(B, n_chunks, q, H, P)
    bf = jnp.repeat(b.astype(jnp.float32), rep, axis=2).reshape(B, n_chunks, q, H, N)
    cf = jnp.repeat(c.astype(jnp.float32), rep, axis=2).reshape(B, n_chunks, q, H, N)
    dtf = dt.astype(jnp.float32).reshape(B, n_chunks, q, H)
    a = a_log.astype(jnp.float32)

    cum = jnp.cumsum(dtf * a[None, None, None, :], axis=2)              # [B,n,q,H]
    dtx = xf * dtf[..., None]                                           # [B,n,q,H,P]

    # intra-chunk masked matmul. The exponent is clamped to <= 0: upper-
    # triangle (future) pairs would overflow exp and poison the backward pass
    # through the where-mask; valid (i >= j) pairs are always <= 0.
    g = jnp.einsum("bnqhm,bnkhm->bnhqk", cf, bf)                        # [B,n,H,q,q]
    delta = jnp.minimum(cum[:, :, :, None] - cum[:, :, None, :], 0.0)   # i - j
    decay = jnp.exp(delta).transpose(0, 1, 4, 2, 3)                     # [B,n,H,q,q]
    tri = jnp.tril(jnp.ones((q, q), bool))
    m = jnp.where(tri[None, None, None], g * decay, 0.0)
    y_intra = jnp.einsum("bnhqk,bnkhp->bnqhp", m, dtx)

    # per-chunk state contribution and carried scan over chunks
    w_end = jnp.exp(cum[:, :, -1:, :] - cum)                            # [B,n,q,H]
    s_chunk = jnp.einsum("bnqhp,bnqhs->bnhps", dtx * w_end[..., None], bf)
    chunk_decay = jnp.exp(cum[:, :, -1])                                # [B,n,H]

    h0 = (
        jnp.zeros((B, H, P, N), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def scan_fn(h, inp):
        s_c, dec = inp                                                  # [B,H,P,N],[B,H]
        h_prev = h
        h = h * dec[..., None, None] + s_c
        return h, h_prev

    hT, h_prevs = jax.lax.scan(
        scan_fn,
        h0,
        (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                               # [B,n,H,P,N]

    y_inter = jnp.exp(cum)[..., None] * jnp.einsum(
        "bnqhs,bnhps->bnqhp", cf, h_prevs
    )
    y = (y_intra + y_inter).reshape(B, S, H, P).astype(x.dtype)
    if return_state:
        return y, hT
    return y


def ssd_step(h, x_t, a_log, b_t, c_t, dt_t):
    """Single-token SSD update (decode path): h' = decay·h + dt·x⊗b; y = h'·c."""
    B, H, P = x_t.shape
    G, N = b_t.shape[1], b_t.shape[2]
    rep = H // G
    bf = jnp.repeat(b_t.astype(jnp.float32), rep, axis=1)
    cf = jnp.repeat(c_t.astype(jnp.float32), rep, axis=1)
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * a_log.astype(jnp.float32)[None, :])           # [B,H]
    h = h * decay[..., None, None] + (dtf[..., None] * x_t.astype(jnp.float32))[
        ..., None
    ] * bf[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", h, cf).astype(x_t.dtype)
    return h, y


REG.register(KernelImpl(op="ssd", device_kind="any", source="reference", fn=ref.ssd))
REG.register(KernelImpl(op="ssd", device_kind="any", source="xla", fn=xla_ssd))
REG.register(
    KernelImpl(
        op="ssd", device_kind="tpu", source="pallas", fn=ssd_k.ssd,
        footprint=ssd_k.footprint(),
    )
)
