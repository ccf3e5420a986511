"""Plain float32 ``jax.numpy`` reference of the dense GQA decoder.

Independent of the code under test: no dispatch, no kernels, no caches.  It
reads the same parameter tree as :class:`repro.models.model.DecoderLM` and
computes the same function (pre-norm RMSNorm blocks, half-split rotary
embeddings, causal grouped-query attention, SwiGLU MLP), with every weight
upcast to float32 and every matmul at the highest precision.  Serving and
model tests compare logits against it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


def supports(cfg: ArchConfig) -> bool:
    """True for the configurations this reference implements."""
    return (cfg.family == "dense" and cfg.moe is None and cfg.mla is None
            and cfg.attn_window is None and not cfg.encoder_layers
            and cfg.frontend is None)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [S, H, hd], rotated in two halves as the model does."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * freqs        # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg: ArchConfig, h, p):
    S = h.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    pos = jnp.arange(S)
    x = _rmsnorm(h, p["ln1"], cfg.norm_eps)
    a = p["attn"]
    q = _rope((x @ a["wq"]).reshape(S, H, hd), pos, cfg.rope_theta)
    k = _rope((x @ a["wk"]).reshape(S, Hkv, hd), pos, cfg.rope_theta)
    v = (x @ a["wv"]).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    h = h + o.reshape(S, H * hd) @ a["wo"]
    x = _rmsnorm(h, p["ln2"], cfg.norm_eps)
    m = p["mlp"]
    return h + (jax.nn.silu(x @ m["wg"]) * (x @ m["wu"])) @ m["wd"]


def logits(cfg: ArchConfig, params, tokens: jax.Array) -> jax.Array:
    """Logits ``[S, V]`` (float32) of one sequence ``tokens`` ``[S]``.

    The layers run in a scan over the stacked parameters, so only one
    layer's float32 weights exist at a time.
    """
    if not supports(cfg):
        raise ValueError(f"no float32 reference for {cfg.name!r}")
    (stack,) = params["segments"]
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]
        h = emb["tok"][tokens].astype(jnp.float32)

        def body(h, p):
            return _layer(cfg, h, p["0"]), None

        h, _ = jax.lax.scan(body, h, stack)
        h = _rmsnorm(h, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
        if "unembed" in emb:
            return h @ emb["unembed"].astype(jnp.float32)
        return h @ emb["tok"].astype(jnp.float32).T
