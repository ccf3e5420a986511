"""Dense grouped-query-attention decoder, the architecture module
(``bench.spec``) of configurations with ``"arch": "dense_gqa"``: its
weights, float32 reference, fp8 control and work counts.

The reference is written from the published layer equations (pre-norm
RMSNorm blocks, half-split rotary embeddings, causal grouped-query
attention, SwiGLU MLP, final RMSNorm, untied or tied unembedding), in
``jax.numpy`` with every matmul at the highest precision.  It imports
nothing of the program: it reads the weight tree that ``bench.weights``
draws.  The benchmark keeps its own copy so that the yardstick cannot move
with the program; the program's own reference (``repro.models.reference``)
computes the same function.

``mode="fp8"`` is the control: every matmul input (weights per tensor,
activations per row) is rounded to float8 e4m3 with a scale that maps its
largest magnitude to 448, the rest as in float32.  It stands for the step
below bfloat16 that a later change might take.

The layers run in a scan, one layer's float32 weights at a time, and the
attention and the unembedding run in blocks of rows, so a sequence of
``max_len`` tokens fits beside nothing else on one chip.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

FAMILY = "dense"
BLOCK = 512          # rows per attention / unembedding block
E4M3_MAX = 448.0


def layout(cfg: dict) -> dict:
    """``{path: (shape, init, scale)}`` of every weight, keyed as the
    program's parameter tree nests them (``/``-joined)."""
    L, d, f, V = cfg["num_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    q, kv = cfg["num_heads"] * cfg["head_dim"], cfg["num_kv_heads"] * cfg["head_dim"]

    def lin(a, b):
        return ((L, a, b), "normal", 1.0 / np.sqrt(a))

    out = {
        "embed/tok": ((V, d), "normal", 0.02),
        "ln_f": ((d,), "ones", 0.0),
        "segments/0/0/ln1": ((L, d), "ones", 0.0),
        "segments/0/0/ln2": ((L, d), "ones", 0.0),
        "segments/0/0/attn/wq": lin(d, q),
        "segments/0/0/attn/wk": lin(d, kv),
        "segments/0/0/attn/wv": lin(d, kv),
        "segments/0/0/attn/wo": lin(q, d),
        "segments/0/0/mlp/wg": lin(d, f),
        "segments/0/0/mlp/wu": lin(d, f),
        "segments/0/0/mlp/wd": lin(f, d),
    }
    if not cfg["tie_embeddings"]:
        out["embed/unembed"] = ((d, V), "normal", 1.0 / np.sqrt(d))
    return out


def nest(flat: dict) -> dict:
    """The program's tree of the ``/``-joined ``flat`` leaves: one stacked
    segment of ``num_layers`` dense layers."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    tree["segments"] = [tree["segments"]["0"]]
    return tree


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
def _gaps(cfg_key, params, tokens, targets):
    """Per row: the reference's best logit minus its logit of ``targets``
    (the next token served at that row)."""
    cfg = json.loads(cfg_key)
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, "f32", params, tokens)

        def fn(logits, i):
            t = jax.lax.dynamic_slice_in_dim(targets, i * BLOCK, BLOCK)
            got = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1) - got

        return _logit_blocks(cfg, "f32", params, h, fn)


@functools.partial(jax.jit, static_argnums=(0,))
def _control_tokens(cfg_key, params, tokens):
    """The token the fp8 control puts first at each row."""
    cfg = json.loads(cfg_key)
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, "fp8", params, tokens)
        return _logit_blocks(cfg, "fp8", params, h,
                             lambda logits, i: jnp.argmax(logits, axis=-1))


def padded(tokens, length: int):
    """``tokens`` zero-padded to ``length`` (a multiple of ``BLOCK``):
    causal rows before the padding do not see it."""
    return jnp.pad(jnp.asarray(tokens, jnp.int32), (0, length - len(tokens)))


def served_gaps(cfg: dict, params, sequence, targets, length: int):
    """Reference gaps of ``targets`` at every row of ``sequence`` (both
    padded to ``length``); a float32 array of ``length`` rows."""
    return _gaps(spec.model_key(cfg), params, padded(sequence, length),
                 padded(targets, length))


def control_gaps(cfg: dict, params, sequence, length: int):
    """Reference gaps of the tokens the fp8 control puts first."""
    toks = _control_tokens(spec.model_key(cfg), params, padded(sequence, length))
    return _gaps(spec.model_key(cfg), params, padded(sequence, length), toks)


@dataclasses.dataclass(frozen=True)
class Shapes:
    """Work per token and layer (``bench.counts``): matmuls
    ``2 * (d*H*hd + 2*d*Hkv*hd + H*hd*d + 3*d*f)`` FLOPs, attention over
    ``c`` rows ``4 * H * hd * c``, one row's logits ``2 * d * V``."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    dtype_bytes: int = 2

    @classmethod
    def of(cls, cfg: dict) -> "Shapes":
        return cls(cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                   cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                   cfg["vocab_size"], cfg["tie_embeddings"],
                   2 if cfg["dtype"] in ("bfloat16", "float16") else 4)

    # -- parameters ----------------------------------------------------------

    @property
    def layer_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff + 2 * d

    @property
    def layer_bytes(self) -> int:
        """Weights of every layer."""
        return self.layers * self.layer_params * self.dtype_bytes

    @property
    def head_bytes(self) -> int:
        """The final norm and the unembedding (the embedding rows a step
        gathers are a rounding error)."""
        return (self.d_model * self.vocab + self.d_model) * self.dtype_bytes

    @property
    def weight_bytes(self) -> int:
        """Weights one decode step reads."""
        return self.layer_bytes + self.head_bytes

    @property
    def kv_bytes_per_row(self) -> int:
        """K and V of one context row, all layers."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    # -- FLOPs ----------------------------------------------------------------

    @property
    def matmul_flops(self) -> int:
        """Per token: every layer's projections and MLP."""
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.layers * 2 * (d * q + 2 * d * kv + q * d + 3 * d * self.d_ff)

    @property
    def head_flops(self) -> int:
        """One row's logits."""
        return 2 * self.d_model * self.vocab

    def attention_flops(self, context_rows: int) -> int:
        """Per token attending to ``context_rows`` rows, all layers."""
        return self.layers * 4 * self.heads * self.head_dim * context_rows


shapes = Shapes.of
