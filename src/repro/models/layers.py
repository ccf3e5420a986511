"""Shared model building blocks.

Every compute hot-spot goes through ``dispatch.op`` — matmuls, norms,
attention, SSD — so the whole model zoo is transparently retargetable between
reference / XLA / Pallas kernels (the paper's property).  Functions are pure;
parameters are descriptor trees from :mod:`repro.models.params`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

import repro.kernels  # noqa: F401  (ensures registry population)
from repro.configs.base import ArchConfig
from repro.core import dispatch
from repro.dist.act import shard_act
from repro.models.params import ParamSpec

Params = Any
COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_table(positions: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for given positions: [..., dim/2]."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., S, H, D]; cos/sin: [S, D/2] (or broadcastable)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    # broadcast tables over head axis: [S, 1, D/2]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# elementary modules
# ---------------------------------------------------------------------------


def linear_spec(d_in: int, d_out: int, logical: tuple[str | None, str | None],
                scale: float | None = None) -> ParamSpec:
    return ParamSpec(
        shape=(d_in, d_out),
        logical=logical,
        scale=scale if scale is not None else 1.0 / np.sqrt(d_in),
    )


def norm_spec(d: int) -> ParamSpec:
    return ParamSpec(shape=(d,), logical=(None,), init="ones")


def apply_norm(p: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return dispatch.op("rmsnorm", x, p, eps=eps)


def embed_specs(cfg: ArchConfig) -> Params:
    p: dict[str, ParamSpec] = {
        "tok": ParamSpec(
            shape=(cfg.vocab_size, cfg.d_model), logical=("vocab", "embed"),
            scale=0.02,
        )
    }
    if not cfg.tie_embeddings:
        p["unembed"] = linear_spec(cfg.d_model, cfg.vocab_size, ("embed", "vocab"))
    return p


def embed_tokens(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0).astype(COMPUTE_DTYPE)


@jax.named_scope("unembed")
def unembed(p: Params, h: jax.Array) -> jax.Array:
    if "unembed" in p:
        out = dispatch.op("matmul", h, p["unembed"], out_dtype=jnp.float32)
    else:
        out = jnp.einsum(
            "...d,vd->...v", h.astype(jnp.float32), p["tok"].astype(jnp.float32)
        )
    return shard_act(out, "batch", *([None] * (out.ndim - 2)), "vocab")


# ---------------------------------------------------------------------------
# GQA attention
#
# The serving paths name their parts with ``jax.named_scope`` (metadata
# only: the HLO computes the same thing), so a device trace can split a
# step program by part: ``attn.qkv`` (projections and RoPE),
# ``attn.kv_write``, ``attn.core`` (the attention op), ``attn.out``, and
# ``mlp`` and ``unembed`` below.
# ---------------------------------------------------------------------------


def attention_specs(cfg: ArchConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": linear_spec(d, cfg.num_heads * hd, ("embed", "heads")),
        "wk": linear_spec(d, cfg.num_kv_heads * hd, ("embed", "kv_heads")),
        "wv": linear_spec(d, cfg.num_kv_heads * hd, ("embed", "kv_heads")),
        "wo": linear_spec(cfg.num_heads * hd, d, ("heads", "embed")),
    }


def _qkv(p: Params, x: jax.Array, cfg: ArchConfig):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = dispatch.op("matmul", x, p["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = dispatch.op("matmul", x, p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = dispatch.op("matmul", x, p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    q = shard_act(q, "batch", None, "heads", None)
    k = shard_act(k, "batch", None, "kv_heads", None)
    v = shard_act(v, "batch", None, "kv_heads", None)
    return q, k, v


def attention_full(
    p: Params,
    x: jax.Array,                      # [B, S, d]
    cfg: ArchConfig,
    *,
    positions: jax.Array,              # [S]
    window: int | None = None,
    causal: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence attention (train/prefill). Returns (y, k, v) post-rope."""
    with jax.named_scope("attn.qkv"):
        q, k, v = _qkv(p, x, cfg)
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    with jax.named_scope("attn.core"):
        out = dispatch.op(
            "flash_attention",
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=causal,
            window=window,
        ).swapaxes(1, 2)                # [B, S, H, hd]
    B, S = x.shape[:2]
    with jax.named_scope("attn.out"):
        y = dispatch.op("matmul", out.reshape(B, S, -1), p["wo"])
    return y, k.swapaxes(1, 2), v.swapaxes(1, 2)   # caches as [B, Hkv, S, hd]


def attention_prefill_chunk(
    p: Params,
    x: jax.Array,                      # [B, Sc, d] — prompt rows [start, start+Sc)
    cache_k: jax.Array,                # [B, Hkv, Tc, hd] staging cache
    cache_v: jax.Array,
    start: int,                        # static: chunk's absolute first position
    cfg: ArchConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One chunk of a split prefill against the partially-filled KV cache.

    Bitwise-identical to the corresponding rows of :func:`attention_full`
    over the whole prompt, with no new kernel: every per-row computation
    (qkv matmul, rope at absolute positions, rmsnorm) is row-local, and the
    ``flash_attention`` op already aligns a short query block to the *end*
    of its key sequence (``qpos = arange(Sc) + (T - Sc)``) — so feeding it
    the chunk's queries against the cache slice ``[:, :, :start+Sc]`` yields
    exactly the causal mask the full prefill applied to those rows.  Masked
    keys contribute an exact 0.0 after softmax, so the trailing
    already-cached rows change nothing bit for bit.

    ``start`` must be a static Python int: the cache slice bound is a trace
    constant, so each (Sc, start) pair is one jitted trace — bounded by
    ``max_len / chunk`` traces, the chunked analogue of prompt bucketing.
    """
    B, Sc, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn.qkv"):
        q, k, v = _qkv(p, x, cfg)
        positions = start + jnp.arange(Sc)
        cos, sin = rope_table(positions, hd, cfg.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    with jax.named_scope("attn.kv_write"):
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.swapaxes(1, 2).astype(cache_k.dtype), (0, 0, start, 0)
        )
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.swapaxes(1, 2).astype(cache_v.dtype), (0, 0, start, 0)
        )
    end = start + Sc
    with jax.named_scope("attn.core"):
        out = dispatch.op(
            "flash_attention",
            q.swapaxes(1, 2), cache_k[:, :, :end], cache_v[:, :, :end],
            causal=True,
        ).swapaxes(1, 2)                # [B, Sc, H, hd]
    with jax.named_scope("attn.out"):
        y = dispatch.op("matmul", out.reshape(B, Sc, -1), p["wo"])
    return y, cache_k, cache_v


def decode_positions(pos: jax.Array) -> jax.Array:
    """Rope positions for one decode step: pos scalar -> [1], [B] -> [B, 1]."""
    return pos[None] if pos.ndim == 0 else pos[:, None]


def write_kv(cache: jax.Array, new: jax.Array, slot: jax.Array) -> jax.Array:
    """Write one token's KV [B, H, hd] into cache [B, H, Tc, hd] at ``slot``.

    ``slot`` scalar (uniform batch) or [B] (continuous batching: per-sequence
    positions).
    """
    if slot.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, new[:, :, None, :].astype(cache.dtype), (0, 0, slot, 0)
        )
    B = cache.shape[0]
    return cache.at[jnp.arange(B), :, slot].set(new.astype(cache.dtype))


def _sp_decode_body(q, k_new, v_new, ck, cv, pos, *, scale: float):
    """Sequence-parallel decode attention (inside shard_map over "model").

    The KV cache time axis is sharded; the new token's KV lands on exactly one
    owner shard (zero-comm masked write), local partial attention runs over
    the local T-chunk, and softmax statistics reduce with [B, H]-sized
    pmax/psum — the whole layer costs KBs of ICI traffic instead of gathering
    a multi-GiB cache.
    """
    B, Hq, hd = q.shape
    Hkv = ck.shape[1]
    T_loc = ck.shape[2]
    group = Hq // Hkv
    my = jax.lax.axis_index("model")
    owner = pos // T_loc
    slot = pos % T_loc

    # owner-masked write (hypothesis log §Perf: a slice-granular masked write
    # was tried and REFUTED — it added ops without reducing counted traffic)
    upd_k = jax.lax.dynamic_update_slice(
        ck, k_new[:, :, None, :].astype(ck.dtype), (0, 0, slot, 0))
    upd_v = jax.lax.dynamic_update_slice(
        cv, v_new[:, :, None, :].astype(cv.dtype), (0, 0, slot, 0))
    ck = jnp.where(my == owner, upd_k, ck)
    cv = jnp.where(my == owner, upd_v, cv)

    # grouped GQA einsum: the bf16 cache is read once, never repeated or
    # upcast — the repeat+f32 formulation touched group× more bytes
    qg = q.reshape(B, Hkv, group, hd)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    base = my * T_loc
    valid = (base + jnp.arange(T_loc))[None, None, None, :] <= pos
    logits = jnp.where(valid, logits, -1e30)                 # [B, Hkv, g, T_loc]

    m_loc = jnp.max(logits, axis=-1)
    m = jax.lax.pmax(m_loc, "model")                         # [B, Hkv, g]
    probs = jnp.exp(logits - m[..., None])
    denom = jax.lax.psum(jnp.sum(probs, axis=-1), "model")
    o_part = jnp.einsum("bkgt,bktd->bkgd", probs.astype(cv.dtype), cv,
                        preferred_element_type=jnp.float32)
    o = jax.lax.psum(o_part, "model") / denom[..., None]
    return o.reshape(B, Hq, hd).astype(q.dtype), ck, cv


def _sp_decode_attention(q, k, v, cache_k, cache_v, pos, cfg, rules):
    """shard_map wrapper for sequence-parallel decode attention."""
    import functools

    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    B = q.shape[0]
    dpb = rules.batch_pspec(B, 0)[0]
    rep = P(dpb, None, None)
    cache_spec = P(dpb, None, "model", None)
    scale = 1.0 / float(np.sqrt(cfg.head_dim))

    body = functools.partial(_sp_decode_body, scale=scale)
    return shard_map(
        body,
        mesh=rules.mesh,
        in_specs=(rep, rep, rep, cache_spec, cache_spec, P()),
        out_specs=(rep, cache_spec, cache_spec),
        check_rep=False,
    )(q, k, v, cache_k, cache_v, pos)


def attention_decode(
    p: Params,
    x: jax.Array,                      # [B, 1, d]
    cache_k: jax.Array,                # [B, Hkv, Tc, hd]
    cache_v: jax.Array,
    pos: jax.Array,                    # scalar or [B]: tokens already cached
    cfg: ArchConfig,
    *,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode against a (ring-buffered, if windowed) KV cache."""
    from repro.dist import act

    Tc = cache_k.shape[2]
    q, k, v = _decode_qkv(p, x, pos, cfg)

    # sequence-parallel path: serving, kv heads don't divide TP, full cache
    rules = act.current()
    model_size = rules.mesh.shape.get("model", 1) if rules is not None else 1
    if (rules is not None and rules.serving and model_size > 1
            and cfg.num_kv_heads % model_size != 0
            and Tc % model_size == 0 and window is None and pos.ndim == 0):
        out, cache_k, cache_v = _sp_decode_attention(
            q, k, v, cache_k, cache_v, pos, cfg, rules
        )
        return _decode_out(p, out), cache_k, cache_v

    slot = pos % Tc                     # ring buffer when windowed; pos < Tc otherwise
    with jax.named_scope("attn.kv_write"):
        cache_k = write_kv(cache_k, k, slot)
        cache_v = write_kv(cache_v, v, slot)
    length = jnp.minimum(pos + 1, Tc)
    with jax.named_scope("attn.core"):
        out = dispatch.op("decode_attention", q, cache_k, cache_v, length)
    return _decode_out(p, out), cache_k, cache_v


@jax.named_scope("attn.qkv")
def _decode_qkv(p: Params, x: jax.Array, pos: jax.Array, cfg: ArchConfig):
    """One decode token's q [B, H, hd], k and v [B, Hkv, hd], post-RoPE."""
    q, k, v = _qkv(p, x, cfg)
    cos, sin = rope_table(decode_positions(pos), cfg.head_dim, cfg.rope_theta)
    return apply_rope(q, cos, sin)[:, 0], apply_rope(k, cos, sin)[:, 0], v[:, 0]


@jax.named_scope("attn.out")
def _decode_out(p: Params, out: jax.Array) -> jax.Array:
    """The output projection of one decode token's attention, [B, 1, d]."""
    y = dispatch.op("matmul", out.reshape(out.shape[0], 1, -1)[:, 0], p["wo"])
    return y[:, None, :]


def paged_write_kv(pool: jax.Array, new: jax.Array, layer: jax.Array,
                   page: jax.Array, offset: jax.Array) -> jax.Array:
    """Write one token's KV [B, H, hd] into layer ``layer`` of the stacked
    pool [L, P, H, ps, hd] at each sequence's ``(page[b], offset[b])``.

    One scatter into the whole stack, so a pool carried through a loop is
    updated in place.  Each (sequence, head) row is its own scatter index:
    a window of one ``hd`` row fits the pool's default layout, where a
    window of all heads' rows makes the TPU compiler relay the pool out
    (and copy it whole at the program's edges).  Live slots own disjoint
    pages, so batch writes never collide; masked (finished) slots are
    steered to the scratch page by their cleared block tables, where
    collisions are harmless.
    """
    heads = jnp.arange(new.shape[1])[None, :]
    return pool.at[layer, page[:, None], heads, offset[:, None]].set(
        new.astype(pool.dtype))


def attention_decode_paged(
    p: Params,
    x: jax.Array,                      # [B, 1, d]
    k_pool: jax.Array,                 # [L, P, Hkv, ps, hd] stacked block pools
    v_pool: jax.Array,
    block_table: jax.Array,            # [B, NP] page index -> pool page
    pos: jax.Array,                    # scalar or [B]: tokens already cached
    cfg: ArchConfig,
    layer: jax.Array,                  # scalar: this layer's index in the stack
    lengths: jax.Array | None = None,  # [B] rows attended; default pos + 1
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode against a paged KV cache.

    Identical q/k/v/rope math to :func:`attention_decode`; the only change
    is *where* KV lives: the new token is written into the pool page the
    block table maps its position to, and attention runs via the
    ``paged_decode_attention`` op.  Its XLA source (the one the CPU runs)
    gathers pages back into the dense layout and then executes the same
    dense decode-attention function, which is what makes paged serving
    bitwise-identical to dense, not merely allclose.  A TPU runs the
    length-bounded Pallas kernel instead, which reads only the pages below
    each sequence's bound (``core.dispatch.PLATFORM_PREFER``).  The pools
    hold every layer of a segment and are returned whole: the write touches
    one row per sequence and attention reads layer ``layer``'s pages
    through the block table, so neither copies the layer's pool.

    ``lengths`` bounds the rows each sequence attends to (at least 1).  The
    fused decode passes 1 for slots it does not advance, so they read one
    page, not their stale extent; their outputs are discarded.
    """
    B = x.shape[0]
    ps = k_pool.shape[3]
    q, k, v = _decode_qkv(p, x, pos, cfg)
    posb = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
    with jax.named_scope("attn.kv_write"):
        page = jnp.take_along_axis(
            block_table, (posb // ps)[:, None], axis=1
        )[:, 0]
        k_pool = paged_write_kv(k_pool, k, layer, page, posb % ps)
        v_pool = paged_write_kv(v_pool, v, layer, page, posb % ps)
    with jax.named_scope("attn.core"):
        out = dispatch.op(
            "paged_decode_attention", q, k_pool, v_pool, block_table,
            posb + 1 if lengths is None else lengths, layer=layer,
        )
    return _decode_out(p, out), k_pool, v_pool


def cross_attention_specs(cfg: ArchConfig) -> Params:
    return attention_specs(cfg)


def cross_attention(
    p: Params,
    x: jax.Array,                      # [B, S, d] decoder side
    mem_k: jax.Array,                  # [B, Hkv, T_enc, hd] precomputed
    mem_v: jax.Array,
    cfg: ArchConfig,
) -> jax.Array:
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = dispatch.op("matmul", x, p["wq"]).reshape(B, S, cfg.num_heads, hd)
    if S == 1:
        out = dispatch.op(
            "decode_attention", q[:, 0], mem_k, mem_v, mem_k.shape[2]
        )[:, None]
    else:
        out = dispatch.op(
            "flash_attention", q.swapaxes(1, 2), mem_k, mem_v, causal=False
        ).swapaxes(1, 2)
    return dispatch.op("matmul", out.reshape(B, S, -1), p["wo"])


def encode_memory(p: Params, memory: jax.Array, cfg: ArchConfig):
    """Precompute cross-attention K/V from encoder output ([B, T, d])."""
    B, T, _ = memory.shape
    hd = cfg.head_dim
    k = dispatch.op("matmul", memory, p["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = dispatch.op("matmul", memory, p["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    return k.swapaxes(1, 2), v.swapaxes(1, 2)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ArchConfig, d_ff: int | None = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": linear_spec(d, f, ("embed", "mlp")),
        "wu": linear_spec(d, f, ("embed", "mlp")),
        "wd": linear_spec(f, d, ("mlp", "embed")),
    }


@jax.named_scope("mlp")
def apply_mlp(p: Params, x: jax.Array) -> jax.Array:
    g = dispatch.op("matmul", x, p["wg"], activation="silu")
    u = dispatch.op("matmul", x, p["wu"])
    h = shard_act(g * u, "batch", *([None] * (x.ndim - 2)), "mlp")
    return dispatch.op("matmul", h, p["wd"])
