"""Plain float32 reference of a dense GQA decoder, and its fp8 control.

Written from the published layer equations (pre-norm RMSNorm blocks,
half-split rotary embeddings, causal grouped-query attention, SwiGLU MLP,
final RMSNorm, untied or tied unembedding), in ``jax.numpy`` with every
matmul at the highest precision.  It imports nothing of the program: it
reads the weight tree that ``bench.weights`` draws.  The benchmark keeps
its own copy so that the yardstick cannot move with the program; the
program's own reference (``repro.models.reference``) computes the same
function.

``mode="fp8"`` is the control: every matmul input (weights per tensor,
activations per row) is rounded to float8 e4m3 with a scale that maps its
largest magnitude to 448, the rest as in float32.  It stands for the step
below bfloat16 that a later change might take.

The layers run in a scan, one layer's float32 weights at a time, and the
attention and the unembedding run in blocks of rows, so a sequence of
``max_len`` tokens fits beside nothing else on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 512          # rows per attention / unembedding block
E4M3_MAX = 448.0


def _fp8(x, axis=None):
    """Round ``x`` to float8 e4m3 under a scale per ``axis`` slice (per
    tensor when ``axis`` is None) and return it in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, mode):
    if mode == "fp8":
        a, w = _fp8(a, axis=-1), _fp8(w)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, mode):
    """Causal GQA attention. q [S, Hkv, G, hd]; k, v [S, Hkv, hd]."""
    S, Hkv, G, hd = q.shape
    pos = jnp.arange(S)
    if mode == "fp8":
        k, v = _fp8(k, axis=-1), _fp8(v, axis=-1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK)
        if mode == "fp8":
            qb = _fp8(qb, axis=-1)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(jnp.float32(hd))
        rows = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if mode == "fp8":
            p = _fp8(p, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(block, jnp.arange(S // BLOCK))
    return out.reshape(S, Hkv * G * hd)


def _layer(cfg, mode, h, p):
    S = h.shape[0]
    H, Hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    pos = jnp.arange(S)
    a = p["attn"]
    x = _rms(h, p["ln1"], cfg["norm_eps"])
    q = _rope(_mm(x, a["wq"], mode).reshape(S, H, hd), pos, cfg["rope_theta"])
    k = _rope(_mm(x, a["wk"], mode).reshape(S, Hkv, hd), pos, cfg["rope_theta"])
    v = _mm(x, a["wv"], mode).reshape(S, Hkv, hd)
    o = _attention(q.reshape(S, Hkv, H // Hkv, hd), k, v, mode)
    h = h + _mm(o, a["wo"], mode)
    x = _rms(h, p["ln2"], cfg["norm_eps"])
    m = p["mlp"]
    g = jax.nn.silu(_mm(x, m["wg"], mode)) * _mm(x, m["wu"], mode)
    return h + _mm(g, m["wd"], mode)


def _hidden(cfg, mode, params, tokens):
    (stack,) = params["segments"]
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(lambda h, p: (_layer(cfg, mode, h, p["0"]), None),
                        h, stack)
    return _rms(h, params["ln_f"].astype(jnp.float32), cfg["norm_eps"])


def _logit_blocks(cfg, mode, params, h, fn):
    """``fn(logits_block, i)`` over blocks of rows of ``h``; stacked."""
    emb = params["embed"]
    w = (emb["unembed"] if "unembed" in emb else emb["tok"].T).astype(jnp.float32)

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * BLOCK, BLOCK)
        return fn(_mm(hb, w, mode), i)

    out = jax.lax.map(block, jnp.arange(h.shape[0] // BLOCK))
    return jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), out)


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(cfg_items, params, tokens, targets):
    """Per row: the reference's best logit minus its logit of ``targets``
    (the next token served at that row)."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, "f32", params, tokens)

        def fn(logits, i):
            t = jax.lax.dynamic_slice_in_dim(targets, i * BLOCK, BLOCK)
            got = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1) - got

        return _logit_blocks(cfg, "f32", params, h, fn)


@functools.partial(jax.jit, static_argnums=(0,))
def _control_tokens(cfg_items, params, tokens):
    """The token the fp8 control puts first at each row."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, "fp8", params, tokens)
        return _logit_blocks(cfg, "fp8", params, h,
                             lambda logits, i: jnp.argmax(logits, axis=-1))


REF_KEYS = ("num_heads", "num_kv_heads", "head_dim", "norm_eps", "rope_theta")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in REF_KEYS)


def padded(tokens, length: int):
    """``tokens`` zero-padded to ``length`` (a multiple of ``BLOCK``):
    causal rows before the padding do not see it."""
    return jnp.pad(jnp.asarray(tokens, jnp.int32), (0, length - len(tokens)))


def served_gaps(cfg: dict, params, sequence, targets, length: int):
    """Reference gaps of ``targets`` at every row of ``sequence`` (both
    padded to ``length``); a float32 array of ``length`` rows."""
    return _gaps(_items(cfg), params, padded(sequence, length),
                 padded(targets, length))


def control_gaps(cfg: dict, params, sequence, length: int):
    """Reference gaps of the tokens the fp8 control puts first."""
    toks = _control_tokens(_items(cfg), params, padded(sequence, length))
    return _gaps(_items(cfg), params, padded(sequence, length), toks)
