"""Pure-jnp oracles for every Pallas kernel.

These are the "reference" source in the registry: always correct, never
hand-optimized.  Kernel tests sweep shapes/dtypes and assert_allclose the
Pallas implementations (interpret=True) against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    out_dtype: jnp.dtype | None = None,
    activation: str | None = None,
) -> jax.Array:
    """[M, K] @ [K, N] with f32 accumulation."""
    acc = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
    if activation == "silu":
        acc = acc * jax.nn.sigmoid(acc)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc)
    elif activation is not None:
        raise ValueError(f"unknown activation {activation!r}")
    return acc.astype(out_dtype or x.dtype)


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    """RMS norm over the last axis, f32 statistics."""
    xf = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf / rms) * weight.astype(jnp.float32)).astype(x.dtype)


def flash_attention(
    q: jax.Array,                   # [B, Hq, S, D]
    k: jax.Array,                   # [B, Hkv, T, D]
    v: jax.Array,                   # [B, Hkv, T, D]
    *,
    causal: bool = True,
    window: int | None = None,      # sliding window (inclusive of self)
    scale: float | None = None,
) -> jax.Array:
    """Exact attention oracle with GQA head grouping."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    kg = jnp.repeat(k, group, axis=1)
    vg = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum(
        "bhsd,bhtd->bhst", q.astype(jnp.float32), kg.astype(jnp.float32)
    ) * scale
    qpos = jnp.arange(S)[:, None] + (T - S)    # decode: q at the end of the kv axis
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def conv2d(
    x: jax.Array,                   # [B, H, W, Cin]
    w: jax.Array,                   # [kh, kw, Cin, F]
    *,
    accum_dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """VALID conv, stride 1. int16 weights accumulate in int32 (paper roles 3/4)."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        accum_dtype = jnp.int32
    out = jax.lax.conv_general_dilated(
        x.astype(accum_dtype),
        w.astype(accum_dtype),
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out


def ssd(
    x: jax.Array,                   # [B, S, H, P]   (heads, head dim)
    a_log: jax.Array,               # [H]            per-head decay log(a) < 0
    b: jax.Array,                   # [B, S, G, N]   input projection (groups, state)
    c: jax.Array,                   # [B, S, G, N]   output projection
    dt: jax.Array,                  # [B, S, H]      time deltas (positive)
    *,
    initial_state: jax.Array | None = None,   # [B, H, P, N]
    return_state: bool = False,
):
    """Mamba-2 SSD oracle: sequential state-space recurrence.

    h_t = exp(dt_t * a) * h_{t-1} + dt_t * x_t ⊗ b_t ;  y_t = h_t · c_t

    Heads are grouped over B/C (``G`` divides ``H``), as in Mamba-2.
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert H % G == 0
    rep = H // G
    xf = x.astype(jnp.float32)
    bf = jnp.repeat(b.astype(jnp.float32), rep, axis=2)    # [B,S,H,N]
    cf = jnp.repeat(c.astype(jnp.float32), rep, axis=2)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * a_log.astype(jnp.float32)[None, None, :])   # [B,S,H]

    h0 = (
        jnp.zeros((B, H, P, N), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(h, inputs):
        xt, bt, ct, dct, dtt = inputs           # [B,H,P],[B,H,N],[B,H,N],[B,H],[B,H]
        h = h * dct[..., None, None] + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(bf, 1, 0),
        jnp.moveaxis(cf, 1, 0),
        jnp.moveaxis(decay, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
    )
    hT, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)   # [B,S,H,P]
    if return_state:
        return y, hT.astype(jnp.float32)
    return y


def gather_kv_pages(pages: jax.Array, block_table: jax.Array,
                    layer: jax.Array | None = None) -> jax.Array:
    """Reassemble a per-sequence dense KV view from a paged pool.

    ``pages`` [P, Hkv, ps, D] is the global block pool; ``block_table``
    [B, NP] maps each sequence's page index to a pool page.  The result
    [B, Hkv, NP*ps, D] holds position ``t`` of sequence ``b`` at
    ``[b, :, t]`` — exactly the dense cache layout, so any dense decode
    attention runs unchanged (and bitwise-identically) on the gather.
    With ``layer``, ``pages`` is a stack of pools [L, P, Hkv, ps, D] and
    layer ``layer``'s pages are gathered straight from the stack.
    """
    B, NP = block_table.shape
    Hkv, ps, D = pages.shape[-3:]
    if layer is None:
        out = jnp.take(pages, block_table, axis=0)       # [B, NP, Hkv, ps, D]
    else:
        out = pages[layer, block_table]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, NP * ps, D)


def paged_decode_attention(
    q: jax.Array,                   # [B, Hq, D] single query token
    k_pages: jax.Array,             # [P, Hkv, ps, D] global block pool
    v_pages: jax.Array,             # [P, Hkv, ps, D]
    block_table: jax.Array,         # [B, NP] page index -> pool page
    length: jax.Array | int,        # valid cache length (scalar or [B])
    *,
    layer: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """One-token attention over a paged KV cache: gather, then dense oracle.

    Positions ``>= length`` (page tails, unmapped table entries pointing at
    the reserved scratch page) are masked before the softmax, so their
    contents never reach the output.  With ``layer`` the pools are stacks
    [L, P, Hkv, ps, D] and layer ``layer`` is read.
    """
    kg = gather_kv_pages(k_pages, block_table, layer)
    vg = gather_kv_pages(v_pages, block_table, layer)
    return decode_attention(q, kg, vg, length, scale=scale)


def decode_attention(
    q: jax.Array,                   # [B, Hq, D] single query token
    k_cache: jax.Array,             # [B, Hkv, T, D]
    v_cache: jax.Array,             # [B, Hkv, T, D]
    length: jax.Array | int,        # valid cache length (scalar or [B])
    *,
    scale: float | None = None,
) -> jax.Array:
    """One-token attention over a (possibly padded) KV cache."""
    B, Hq, D = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    kg = jnp.repeat(k_cache, group, axis=1)
    vg = jnp.repeat(v_cache, group, axis=1)
    logits = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32), kg.astype(jnp.float32))
    logits = logits * scale
    pos = jnp.arange(T)[None, :]
    lengths = jnp.asarray(length)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    valid = pos < lengths[:, None]
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,bhtd->bhd", probs, vg.astype(jnp.float32))
    return out.astype(q.dtype)
