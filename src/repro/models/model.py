"""Model assembly: all 10 assigned architectures from shared blocks.

Layers are grouped into homogeneous **segments** scanned with ``lax.scan``
(stacked params → O(1) HLO size in depth; the only sane way to compile 80
dry-run cells).  Segment plans per family:

  dense/vlm:  [dense × L]
  moe (ds-v3): [dense × 3, moe × 58]
  moe (llama4):[(moe, dense) × 24]            (alternating unit)
  ssm:        [ssm × 48]
  hybrid:     [hybrid × 32]
  audio:      encoder [enc × 32] + decoder [dec × 32]

Three execution modes share the same layer code: ``full`` (training),
``prefill`` (full + emit KV/state caches), ``decode`` (one token, carry
caches).  MoE layers take a :class:`MoeMeshInfo` to run expert-parallel under
the active mesh (None = single-device smoke path).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import dispatch
from repro.dist.act import shard_act
from repro.models import layers, mla, moe, ssm
from repro.models.params import ParamSpec, stack_specs

Params = Any

AUX_KEYS = ("load_balance", "router_z", "dropped_frac")


def _zero_aux() -> dict[str, jax.Array]:
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


# ---------------------------------------------------------------------------
# segment planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    kinds: tuple[str, ...]           # layer kinds within one scanned unit
    count: int                       # scan length


def plan_segments(cfg: ArchConfig) -> list[Segment]:
    if cfg.family == "ssm":
        return [Segment(("ssm",), cfg.num_layers)]
    if cfg.family == "hybrid":
        return [Segment(("hybrid",), cfg.num_layers)]
    if cfg.moe is not None:
        k1 = cfg.moe.first_k_dense
        period = cfg.moe.layer_period
        segs = []
        if k1:
            segs.append(Segment(("dense",), k1))
        unit = ("moe",) + ("dense",) * (period - 1)
        segs.append(Segment(unit, (cfg.num_layers - k1) // period))
        return segs
    return [Segment(("dense",), cfg.num_layers)]


# ---------------------------------------------------------------------------
# per-kind layer specs
# ---------------------------------------------------------------------------


def _attn_specs(cfg: ArchConfig) -> Params:
    if cfg.mla is not None:
        return {"mla": mla.mla_specs(cfg)}
    return {"attn": layers.attention_specs(cfg)}


def layer_specs(cfg: ArchConfig, kind: str) -> Params:
    d = cfg.d_model
    if kind == "ssm":
        return {"ln1": layers.norm_spec(d), "mamba": ssm.ssm_specs(cfg)}
    if kind == "hybrid":
        return {
            "ln1": layers.norm_spec(d),
            "attn": layers.attention_specs(cfg),
            "mamba": ssm.ssm_specs(cfg),
            "attn_norm": layers.norm_spec(d),
            "ssm_norm": layers.norm_spec(d),
            "ln2": layers.norm_spec(d),
            "mlp": layers.mlp_specs(cfg),
        }
    if kind == "moe":
        return {
            "ln1": layers.norm_spec(d),
            **_attn_specs(cfg),
            "ln2": layers.norm_spec(d),
            "moe": moe.moe_specs(cfg),
        }
    if kind == "dense":
        return {
            "ln1": layers.norm_spec(d),
            **_attn_specs(cfg),
            "ln2": layers.norm_spec(d),
            "mlp": layers.mlp_specs(cfg),
        }
    if kind == "enc":
        return {
            "ln1": layers.norm_spec(d),
            "attn": layers.attention_specs(cfg),
            "ln2": layers.norm_spec(d),
            "mlp": layers.mlp_specs(cfg),
        }
    if kind == "dec":
        return {
            "ln1": layers.norm_spec(d),
            "attn": layers.attention_specs(cfg),
            "lnx": layers.norm_spec(d),
            "xattn": layers.cross_attention_specs(cfg),
            "ln2": layers.norm_spec(d),
            "mlp": layers.mlp_specs(cfg),
        }
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-kind layer application (full / prefill / decode)
# ---------------------------------------------------------------------------


def _apply_attn_full(cfg, p, h, positions, *, causal=True):
    if cfg.mla is not None:
        y, ckv, krope = mla.mla_full(p["mla"], h, cfg, positions=positions)
        return y, {"ckv": ckv, "krope": krope}
    y, k, v = layers.attention_full(
        p["attn"], h, cfg, positions=positions, window=cfg.attn_window,
        causal=causal,
    )
    return y, {"k": k, "v": v}


def apply_layer_full(cfg: ArchConfig, kind: str, p: Params, x: jax.Array,
                     positions: jax.Array, moe_info=None,
                     memory=None) -> tuple[jax.Array, dict]:
    aux = _zero_aux()
    if kind == "ssm":
        x = x + ssm.ssm_full(p["mamba"], layers.apply_norm(p["ln1"], x, cfg.norm_eps), cfg)
        return x, aux
    if kind == "hybrid":
        h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
        a, _ = _apply_attn_full(cfg, p, h, positions)
        s = ssm.ssm_full(p["mamba"], h, cfg)
        merged = 0.5 * (
            layers.apply_norm(p["attn_norm"], a, cfg.norm_eps)
            + layers.apply_norm(p["ssm_norm"], s, cfg.norm_eps)
        )
        x = x + merged
        x = x + layers.apply_mlp(p["mlp"], layers.apply_norm(p["ln2"], x, cfg.norm_eps))
        return x, aux
    # attention families
    h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
    causal = kind != "enc"
    a, _ = _apply_attn_full(cfg, p, h, positions, causal=causal)
    x = x + a
    if kind == "dec":
        hx = layers.apply_norm(p["lnx"], x, cfg.norm_eps)
        mem_k, mem_v = layers.encode_memory(p["xattn"], memory, cfg)
        x = x + layers.cross_attention(p["xattn"], hx, mem_k, mem_v, cfg)
    h2 = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        y, aux = moe.apply_moe(p["moe"], h2, cfg, mesh_info=moe_info)
        aux = {**_zero_aux(), **{k: jnp.asarray(v, jnp.float32) for k, v in aux.items()}}
    else:
        y = layers.apply_mlp(p["mlp"], h2)
    return x + y, aux


def apply_layer_prefill(cfg, kind, p, x, positions, moe_info=None, memory=None):
    """Like full, but also returns the layer cache."""
    aux = _zero_aux()
    cache: dict[str, jax.Array] = {}
    if kind == "ssm":
        h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
        y, state, tail = ssm.ssm_full(p["mamba"], h, cfg, return_state=True)
        return x + y, {"ssm_state": state, "conv_tail": tail}, aux
    if kind == "hybrid":
        h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
        a, kv = _apply_attn_full(cfg, p, h, positions)
        s, state, tail = ssm.ssm_full(p["mamba"], h, cfg, return_state=True)
        merged = 0.5 * (
            layers.apply_norm(p["attn_norm"], a, cfg.norm_eps)
            + layers.apply_norm(p["ssm_norm"], s, cfg.norm_eps)
        )
        x = x + merged
        x = x + layers.apply_mlp(p["mlp"], layers.apply_norm(p["ln2"], x, cfg.norm_eps))
        cache = {**_window_clip(cfg, kv), "ssm_state": state, "conv_tail": tail}
        return x, cache, aux
    h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
    causal = kind != "enc"
    a, kv = _apply_attn_full(cfg, p, h, positions, causal=causal)
    x = x + a
    cache = _window_clip(cfg, kv)
    if kind == "dec":
        hx = layers.apply_norm(p["lnx"], x, cfg.norm_eps)
        mem_k, mem_v = layers.encode_memory(p["xattn"], memory, cfg)
        x = x + layers.cross_attention(p["xattn"], hx, mem_k, mem_v, cfg)
        cache.update({"mem_k": mem_k, "mem_v": mem_v})
    h2 = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        y, aux_ = moe.apply_moe(p["moe"], h2, cfg, mesh_info=moe_info)
        aux = {**_zero_aux(), **{k: jnp.asarray(v, jnp.float32) for k, v in aux_.items()}}
    else:
        y = layers.apply_mlp(p["mlp"], h2)
    return x + y, cache, aux


def apply_layer_prefill_chunk(cfg, kind, p, x, cache, start, moe_info=None):
    """One prompt chunk through one layer against the partially-filled cache.

    Only plain dense GQA layers are chunk-safe: MoE capacity routing depends
    on the *other* tokens in the call (a chunk routes differently than the
    full prompt — not row-local, so not bitwise-reproducible), recurrent
    state folds sequentially, and windowed/ring caches clip by position.
    The engine gates chunking on the segment plan; this raise is the
    backstop for direct callers.
    """
    if kind != "dense" or cfg.mla is not None or cfg.attn_window is not None:
        raise ValueError(
            f"chunked prefill supports plain dense GQA layers only, not "
            f"{kind!r} (mla={cfg.mla is not None}, window={cfg.attn_window})"
        )
    h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
    a, ck, cv = layers.attention_prefill_chunk(
        p["attn"], h, cache["k"], cache["v"], start, cfg
    )
    x = x + a
    h2 = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
    y = layers.apply_mlp(p["mlp"], h2)
    return x + y, {"k": ck, "v": cv}


def _pad_cache_time(cfg: ArchConfig, caches, cache_len: int):
    """Pad prefill KV/latent caches along the time axis to ``cache_len``."""
    import jax.tree_util as jtu

    time_keys = {"k", "v", "ckv", "krope"}

    def fn(path, x):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key not in time_keys:
            return x
        target = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
        cur = x.shape[-2]
        if cur >= target:
            return x
        pad = [(0, 0)] * x.ndim
        pad[-2] = (0, target - cur)
        return jnp.pad(x, pad)

    return jtu.tree_map_with_path(fn, caches)


def _window_clip(cfg: ArchConfig, kv: dict) -> dict:
    """Ring-buffer clip of prefill KV to the attention window."""
    if cfg.attn_window is None or "k" not in kv:
        return kv
    w = cfg.attn_window
    S = kv["k"].shape[2]
    if S <= w:
        return kv
    # last `w` positions land at slots (S-w+i) % w — a roll of the tail
    tail_k, tail_v = kv["k"][:, :, -w:], kv["v"][:, :, -w:]
    shift = (S - w) % w
    return {
        "k": jnp.roll(tail_k, shift=shift, axis=2),
        "v": jnp.roll(tail_v, shift=shift, axis=2),
    }


def apply_layer_decode(cfg, kind, p, x, cache, pos, moe_info=None,
                       block_table=None, layer=None, lengths=None):
    """One-token step. Returns (x, new_cache).

    With ``block_table`` the k/v leaves are the segment's stacked page
    *pools* ([L, P, Hkv, page_size, hd]) shared by the whole batch, this
    layer's being ``[layer]``, and attention routes through the paged write
    + block-table kernel, attending to ``lengths`` rows per sequence
    (default ``pos + 1``).  Only plain position-indexed GQA caches support
    paging; recurrent / latent / windowed layouts raise.
    """
    if block_table is not None and (kind not in ("dense", "moe")
                                    or cfg.mla is not None
                                    or cfg.attn_window is not None):
        raise ValueError(
            f"paged decode supports plain GQA KV caches only, not {kind!r} "
            f"(mla={cfg.mla is not None}, window={cfg.attn_window})"
        )
    if kind == "ssm":
        h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
        y, state, tail = ssm.ssm_decode(
            p["mamba"], h, cache["ssm_state"], cache["conv_tail"], cfg
        )
        return x + y, {"ssm_state": state, "conv_tail": tail}
    if kind == "hybrid":
        h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
        a, ck, cv = layers.attention_decode(
            p["attn"], h, cache["k"], cache["v"], pos, cfg, window=cfg.attn_window
        )
        s, state, tail = ssm.ssm_decode(
            p["mamba"], h, cache["ssm_state"], cache["conv_tail"], cfg
        )
        merged = 0.5 * (
            layers.apply_norm(p["attn_norm"], a, cfg.norm_eps)
            + layers.apply_norm(p["ssm_norm"], s, cfg.norm_eps)
        )
        x = x + merged
        x = x + layers.apply_mlp(p["mlp"], layers.apply_norm(p["ln2"], x, cfg.norm_eps))
        return x, {"k": ck, "v": cv, "ssm_state": state, "conv_tail": tail}
    h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if cfg.mla is not None:
        a, ckv, krope = mla.mla_decode(
            p["mla"], h, cache["ckv"], cache["krope"], pos, cfg
        )
        new_cache.update({"ckv": ckv, "krope": krope})
    elif block_table is not None:
        a, pk, pv = layers.attention_decode_paged(
            p["attn"], h, cache["k"], cache["v"], block_table, pos, cfg, layer,
            lengths,
        )
        new_cache.update({"k": pk, "v": pv})
    else:
        a, ck, cv = layers.attention_decode(
            p["attn"], h, cache["k"], cache["v"], pos, cfg, window=cfg.attn_window
        )
        new_cache.update({"k": ck, "v": cv})
    x = x + a
    if kind == "dec":
        hx = layers.apply_norm(p["lnx"], x, cfg.norm_eps)
        x = x + layers.cross_attention(
            p["xattn"], hx, cache["mem_k"], cache["mem_v"], cfg
        )
    h2 = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        # decode routes only a handful of tokens: dropless is mandatory
        y, _ = moe.apply_moe(p["moe"], h2, cfg, mesh_info=moe_info, dropless=True)
    else:
        y = layers.apply_mlp(p["mlp"], h2)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# cache specs
# ---------------------------------------------------------------------------


def layer_cache_specs(cfg: ArchConfig, kind: str, batch: int, cache_len: int,
                      mem_len: int = 0) -> dict:
    out: dict[str, jax.ShapeDtypeStruct] = {}
    if kind in ("ssm", "hybrid"):
        out.update(ssm.init_ssm_cache_specs(cfg, batch))
    if kind == "ssm":
        return out
    eff = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
    if cfg.mla is not None:
        m = cfg.mla
        out["ckv"] = jax.ShapeDtypeStruct((batch, cache_len, m.kv_lora_rank),
                                          layers.COMPUTE_DTYPE)
        out["krope"] = jax.ShapeDtypeStruct((batch, cache_len, m.qk_rope_dim),
                                            layers.COMPUTE_DTYPE)
    else:
        kvshape = (batch, cfg.num_kv_heads, eff, cfg.head_dim)
        out["k"] = jax.ShapeDtypeStruct(kvshape, layers.COMPUTE_DTYPE)
        out["v"] = jax.ShapeDtypeStruct(kvshape, layers.COMPUTE_DTYPE)
    if kind == "dec":
        ms = (batch, cfg.num_kv_heads, mem_len, cfg.head_dim)
        out["mem_k"] = jax.ShapeDtypeStruct(ms, layers.COMPUTE_DTYPE)
        out["mem_v"] = jax.ShapeDtypeStruct(ms, layers.COMPUTE_DTYPE)
    return out


# ---------------------------------------------------------------------------
# the models
# ---------------------------------------------------------------------------


def _scan(body, carry, xs, *, remat: str, unroll: bool):
    """lax.scan, or a Python unroll (used by the roofline cost extrapolation:
    XLA's cost_analysis counts while-loop bodies once, so per-layer costs are
    recovered from small unrolled variants)."""
    if not unroll:
        return jax.lax.scan(_remat(body, remat), carry, xs)
    body_r = _remat(body, remat)         # match the scanned program's remat cost
    length = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body_r(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def chunked_ce(embed_params: Params, h: jax.Array, labels: jax.Array,
               *, chunk: int = 1024) -> jax.Array:
    """Next-token cross entropy without materializing [B, S, V] logits.

    The unembed matmul + log-softmax run per sequence-chunk inside a
    rematerialized scan: peak memory is O(B·chunk·V) instead of O(B·S·V) —
    the difference between 2 GB and 500 GB at 1M tokens × 128k vocab.
    """
    B, S, _ = h.shape
    h_in = h[:, :-1]
    tgt = labels[:, 1:]
    n = S - 1
    c = min(chunk, n)
    pad = (-n) % c                       # S-1 is odd: pad the tail chunk
    if pad:
        h_in = jnp.pad(h_in, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    n_chunks = (n + pad) // c

    def body(carry, i):
        hc = jax.lax.dynamic_slice_in_dim(h_in, i * c, c, axis=1)
        tc = jax.lax.dynamic_slice_in_dim(tgt, i * c, c, axis=1)
        logits = layers.unembed(embed_params, hc)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, tc[..., None], axis=-1)[..., 0]
        valid = (i * c + jnp.arange(c)) < n   # mask the padded tail
        return carry + jnp.sum(nll * valid[None, :]), None

    from repro.roofline.unrolling import inner_loops_unrolled

    if inner_loops_unrolled():          # cost-mode: count every chunk's FLOPs
        total = jnp.zeros((), jnp.float32)
        for i in range(n_chunks):
            total, _ = body(total, jnp.asarray(i))
    else:
        total, _ = jax.lax.scan(
            jax.checkpoint(body), jnp.zeros((), jnp.float32), jnp.arange(n_chunks)
        )
    return total / (B * n)


def _merge_aux(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] for k in a}


class DecoderLM:
    """Decoder-only LM: dense / vlm / moe / ssm / hybrid families."""

    def __init__(self, cfg: ArchConfig, *, plan: list[Segment] | None = None,
                 unroll: bool = False):
        self.cfg = cfg
        self.segments = plan if plan is not None else plan_segments(cfg)
        self.unroll = unroll

    # -- parameters --------------------------------------------------------

    def param_specs(self) -> Params:
        cfg = self.cfg
        segs = []
        for seg in self.segments:
            unit = {str(i): layer_specs(cfg, kind) for i, kind in enumerate(seg.kinds)}
            segs.append(stack_specs(unit, seg.count, logical="layers"))
        return {
            "embed": layers.embed_specs(cfg),
            "segments": segs,
            "ln_f": layers.norm_spec(cfg.d_model),
        }

    # -- embedding ------------------------------------------------------------

    def _embed_inputs(self, params: Params, batch: dict) -> jax.Array:
        h = layers.embed_tokens(params["embed"], batch["tokens"])
        if self.cfg.frontend == "vision_patches" and "patch_embeds" in batch:
            h = jax.lax.dynamic_update_slice(
                h, batch["patch_embeds"].astype(h.dtype), (0, 0, 0)
            )
        return shard_act(h, "batch", None, None)

    # -- full forward (training) ------------------------------------------------

    def backbone(self, params: Params, batch: dict, *, moe_info=None) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        h = self._embed_inputs(params, batch)
        S = h.shape[1]
        positions = jnp.arange(S)
        aux = _zero_aux()

        for seg, seg_params in zip(self.segments, params["segments"]):
            def body(carry, unit_params, _seg=seg):
                x, aux_c = carry
                dt0 = x.dtype
                for i, kind in enumerate(_seg.kinds):
                    x, aux_l = apply_layer_full(
                        cfg, kind, unit_params[str(i)], x, positions,
                        moe_info=moe_info,
                    )
                    x = shard_act(x.astype(dt0), "batch", None, None)
                    aux_c = _merge_aux(aux_c, aux_l)
                return (x, aux_c), None

            (h, aux), _ = _scan(body, (h, aux), seg_params,
                                remat=cfg.remat, unroll=self.unroll)

        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        return h, aux

    def forward(self, params: Params, batch: dict, *, moe_info=None):
        h, aux = self.backbone(params, batch, moe_info=moe_info)
        return layers.unembed(params["embed"], h), aux

    def loss(self, params: Params, batch: dict, *, moe_info=None) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        h, aux = self.backbone(params, batch, moe_info=moe_info)
        labels = batch.get("labels", batch["tokens"])
        loss = chunked_ce(params["embed"], h, labels)
        metrics = {"nll": loss, **aux}
        if cfg.moe is not None:
            loss = loss + cfg.moe.router_aux_weight * aux["load_balance"]
            loss = loss + cfg.moe.router_z_weight * aux["router_z"]
        metrics["loss"] = loss
        return loss, metrics

    # -- prefill ------------------------------------------------------------------

    def prefill(self, params: Params, batch: dict, *, moe_info=None,
                cache_len: int | None = None):
        """Returns (last-token logits, cache). ``cache_len`` pre-allocates the
        KV/latent caches to the serving max length (ring-window caches stay at
        window size)."""
        cfg = self.cfg
        h = self._embed_inputs(params, batch)
        S = h.shape[1]
        positions = jnp.arange(S)
        caches = []

        for seg, seg_params in zip(self.segments, params["segments"]):
            def body(carry, unit_params, _seg=seg):
                x = carry
                dt0 = x.dtype
                unit_cache = {}
                for i, kind in enumerate(_seg.kinds):
                    x, c, _ = apply_layer_prefill(
                        cfg, kind, unit_params[str(i)], x, positions,
                        moe_info=moe_info,
                    )
                    x = shard_act(x.astype(dt0), "batch", None, None)
                    unit_cache[str(i)] = c
                return x, unit_cache

            h, seg_cache = _scan(body, h, seg_params, remat="none",
                                 unroll=self.unroll)
            caches.append(seg_cache)

        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        logits = layers.unembed(params["embed"], h[:, -1:])
        if cache_len is not None:
            caches = _pad_cache_time(cfg, caches, cache_len)
        cache = {"pos": jnp.asarray(S, jnp.int32), "segments": caches}
        return logits[:, 0], cache

    # -- chunked prefill ------------------------------------------------------------

    def prefill_chunk(self, params: Params, tokens: jax.Array, cache: dict, *,
                      start: int, moe_info=None):
        """One ``[B, Sc]`` prompt chunk at absolute positions
        ``[start, start + Sc)`` -> (logits of the chunk's last row [B, V],
        updated cache).

        The cache is a full-capacity staging cache (leaves ``[L, B, Hkv,
        max_len, hd]``); rows ``[0, start)`` hold the previous chunks' KV,
        this call writes ``[start, start + Sc)``.  ``start`` must be a
        static Python int (each (Sc, start) pair is one jitted trace — see
        :func:`repro.models.layers.attention_prefill_chunk`).  Row-for-row
        bitwise-identical to :meth:`prefill` over the whole prompt, which
        is what lets the serving engine interleave prefill chunks with
        decode without perturbing a single token stream.
        """
        cfg = self.cfg
        h = self._embed_inputs(params, {"tokens": tokens})
        new_segs = []

        for seg, seg_params, seg_cache in zip(
            self.segments, params["segments"], cache["segments"]
        ):
            def body(carry, xs, _seg=seg):
                x = carry
                dt0 = x.dtype
                unit_params, unit_cache = xs
                new_unit = {}
                for i, kind in enumerate(_seg.kinds):
                    x, c = apply_layer_prefill_chunk(
                        cfg, kind, unit_params[str(i)], x, unit_cache[str(i)],
                        start, moe_info=moe_info,
                    )
                    x = shard_act(x.astype(dt0), "batch", None, None)
                    new_unit[str(i)] = c
                return x, new_unit

            h, new_seg = _scan(body, h, (seg_params, seg_cache), remat="none",
                               unroll=self.unroll)
            new_segs.append(new_seg)

        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        logits = layers.unembed(params["embed"], h[:, -1:])
        end = start + tokens.shape[1]
        return logits[:, 0], {"pos": jnp.asarray(end, jnp.int32),
                              "segments": new_segs}

    # -- decode ---------------------------------------------------------------------

    def decode_step(self, params: Params, tokens: jax.Array, cache: dict, *,
                    moe_info=None):
        """tokens [B, 1] -> (logits [B, V], new cache).

        A ``cache["block_table"]`` entry ([B, NP] int32) switches the
        attention layers to the paged KV path: k/v leaves of ``segments``
        are then global page pools, written and read through the table (see
        :mod:`repro.serve.paged`).  The table itself is engine-owned and not
        part of the returned cache, as is an optional ``cache["lengths"]``
        ([B] int32, at least 1): the rows each sequence attends to, by
        default ``pos + 1``.  The layer scan then carries each
        segment's stacked pools instead of scanning them: a token's K/V is
        written into the stack in place, where slicing out each layer's pool
        and restacking it would copy the whole pool every step.
        """
        cfg = self.cfg
        h = layers.embed_tokens(params["embed"], tokens)
        pos = cache["pos"]
        block_table = cache.get("block_table")
        lengths = cache.get("lengths")
        new_segs = []

        for seg, seg_params, seg_cache in zip(
            self.segments, params["segments"], cache["segments"]
        ):
            if block_table is not None:
                def body(carry, xs, _seg=seg):
                    x, pools = carry
                    dt0 = x.dtype
                    unit_params, layer = xs
                    pools = dict(pools)
                    for i, kind in enumerate(_seg.kinds):
                        x, pools[str(i)] = apply_layer_decode(
                            cfg, kind, unit_params[str(i)], x, pools[str(i)],
                            pos, moe_info=moe_info, block_table=block_table,
                            layer=layer, lengths=lengths,
                        )
                        x = shard_act(x.astype(dt0), "batch", None, None)
                    return (x, pools), None

                (h, new_seg), _ = _scan(
                    body, (h, seg_cache), (seg_params, jnp.arange(seg.count)),
                    remat="none", unroll=self.unroll,
                )
                new_segs.append(new_seg)
                continue

            def body(carry, xs, _seg=seg):
                x = carry
                dt0 = x.dtype
                unit_params, unit_cache = xs
                new_unit = {}
                for i, kind in enumerate(_seg.kinds):
                    x, c = apply_layer_decode(
                        cfg, kind, unit_params[str(i)], x, unit_cache[str(i)],
                        pos, moe_info=moe_info,
                    )
                    x = shard_act(x.astype(dt0), "batch", None, None)
                    new_unit[str(i)] = c
                return x, new_unit

            h, new_seg = _scan(body, h, (seg_params, seg_cache), remat="none",
                               unroll=self.unroll)
            new_segs.append(new_seg)

        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        logits = layers.unembed(params["embed"], h)
        return logits[:, 0], {"pos": pos + 1, "segments": new_segs}

    # -- cache specs -------------------------------------------------------------------

    def cache_specs(self, batch: int, cache_len: int) -> dict:
        cfg = self.cfg
        segs = []
        for seg in self.segments:
            unit = {
                str(i): layer_cache_specs(cfg, kind, batch, cache_len)
                for i, kind in enumerate(seg.kinds)
            }
            segs.append(
                jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((seg.count, *s.shape), s.dtype),
                    unit,
                )
            )
        return {"pos": jax.ShapeDtypeStruct((), jnp.int32), "segments": segs}


class EncDecLM:
    """Whisper-style encoder-decoder (conv frontend stubbed: frame embeddings in)."""

    def __init__(self, cfg: ArchConfig, *, plan=None, unroll: bool = False):
        self.cfg = cfg
        self.unroll = unroll

    def param_specs(self) -> Params:
        cfg = self.cfg
        return {
            "embed": layers.embed_specs(cfg),
            "enc_pos": ParamSpec((cfg.frontend_seq, cfg.d_model), (None, "embed"),
                                 scale=0.02),
            "encoder": stack_specs(layer_specs(cfg, "enc"), cfg.encoder_layers,
                                   logical="layers"),
            "ln_enc": layers.norm_spec(cfg.d_model),
            "decoder": stack_specs(layer_specs(cfg, "dec"), cfg.num_layers,
                                   logical="layers"),
            "ln_f": layers.norm_spec(cfg.d_model),
        }

    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = frames.astype(layers.COMPUTE_DTYPE) + params["enc_pos"].astype(layers.COMPUTE_DTYPE)
        positions = jnp.arange(h.shape[1])

        def body(x, lp):
            x, _ = apply_layer_full(cfg, "enc", lp, x, positions)
            return shard_act(x, "batch", None, None), None

        h, _ = _scan(body, h, params["encoder"], remat=cfg.remat,
                     unroll=self.unroll)
        return layers.apply_norm(params["ln_enc"], h, cfg.norm_eps)

    def _decode_full(self, params, tokens, memory, mode: str):
        cfg = self.cfg
        h = layers.embed_tokens(params["embed"], tokens)
        positions = jnp.arange(h.shape[1])

        if mode == "full":
            def body(x, lp):
                x, _ = apply_layer_full(cfg, "dec", lp, x, positions, memory=memory)
                return shard_act(x, "batch", None, None), None
            h, _ = _scan(body, h, params["decoder"], remat=cfg.remat,
                         unroll=self.unroll)
            return h, None

        def body(x, lp):
            x, c, _ = apply_layer_prefill(cfg, "dec", lp, x, positions, memory=memory)
            return shard_act(x, "batch", None, None), c
        h, cache = _scan(body, h, params["decoder"], remat="none",
                         unroll=self.unroll)
        return h, cache

    def loss(self, params: Params, batch: dict, *, moe_info=None):
        cfg = self.cfg
        memory = self.encode(params, batch["frames"])
        h, _ = self._decode_full(params, batch["tokens"], memory, "full")
        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        labels = batch.get("labels", batch["tokens"])
        loss = chunked_ce(params["embed"], h, labels)
        return loss, {"nll": loss, "loss": loss, **_zero_aux()}

    def prefill(self, params: Params, batch: dict, *, moe_info=None,
                cache_len: int | None = None):
        cfg = self.cfg
        memory = self.encode(params, batch["frames"])
        h, cache = self._decode_full(params, batch["tokens"], memory, "prefill")
        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        logits = layers.unembed(params["embed"], h[:, -1:])
        if cache_len is not None:
            # cross-attn memory caches are fixed-length; only self-attn pads
            def fn(path, x):
                import jax.tree_util as jtu  # noqa: F401
                key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
                if key in ("k", "v") and x.shape[-2] < cache_len:
                    pad = [(0, 0)] * x.ndim
                    pad[-2] = (0, cache_len - x.shape[-2])
                    return jnp.pad(x, pad)
                return x
            import jax.tree_util as jtu
            cache = jtu.tree_map_with_path(fn, cache)
        return logits[:, 0], {
            "pos": jnp.asarray(batch["tokens"].shape[1], jnp.int32),
            "segments": [cache],
        }

    def decode_step(self, params: Params, tokens: jax.Array, cache: dict, *,
                    moe_info=None):
        cfg = self.cfg
        h = layers.embed_tokens(params["embed"], tokens)
        pos = cache["pos"]

        def body(x, xs):
            lp, lc = xs
            x, c = apply_layer_decode(cfg, "dec", lp, x, lc, pos)
            return shard_act(x, "batch", None, None), c

        h, new_cache = _scan(body, h, (params["decoder"], cache["segments"][0]),
                             remat="none", unroll=self.unroll)
        h = layers.apply_norm(params["ln_f"], h, cfg.norm_eps)
        logits = layers.unembed(params["embed"], h)
        return logits[:, 0], {"pos": pos + 1, "segments": [new_cache]}

    def cache_specs(self, batch: int, cache_len: int) -> dict:
        cfg = self.cfg
        unit = layer_cache_specs(cfg, "dec", batch, cache_len,
                                 mem_len=cfg.frontend_seq)
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_layers, *s.shape), s.dtype), unit
        )
        return {"pos": jax.ShapeDtypeStruct((), jnp.int32), "segments": [stacked]}


def build_model(cfg: ArchConfig, *, plan: list[Segment] | None = None,
                unroll: bool = False):
    if cfg.family == "audio":
        return EncDecLM(cfg, plan=plan, unroll=unroll)
    return DecoderLM(cfg, plan=plan, unroll=unroll)
