"""Training step builder: loss → grads → optimizer under the production mesh.

Produces a jitted, donated, fully-sharded ``train_step(params, opt_state,
batch) -> (params, opt_state, metrics)``.  Sharding comes entirely from the
rules' in/out shardings; intermediates are GSPMD-propagated.  Gradient
accumulation (microbatching) runs as a ``lax.scan`` over batch slices with an
f32 accumulator.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.dist import act
from repro.dist.sharding import ShardingRules
from repro.models.model import DecoderLM, EncDecLM
from repro.models.moe import MoeMeshInfo
from repro.optim.adamw import OptConfig, opt_init, opt_state_specs, opt_update


def moe_mesh_info(cfg: ArchConfig, rules: ShardingRules, *,
                  for_decode: bool = False) -> MoeMeshInfo | None:
    if cfg.moe is None:
        return None
    mesh = rules.mesh
    axes = mesh.axis_names
    ep = rules.ep_axes()
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    psum_axes = None

    if for_decode and rules.serving:
        # Serving decode: tokens are tiny (B×1) — replicate them over the EP
        # axes and psum the combine.  Expert weights never move: either E
        # shards over every chip, or E over "model" with the FFN dim over
        # "data" (partial-f contributions also land in the psum).
        mode = "tp"
        ff = rules.logical_to_physical.get("expert_ff", ())
        if ff:                                     # f-sharded serving layout
            ep = ("model",)
            psum_axes = ("model",) + ff
            espec = {
                "wg": P("model", None, ff[0]),
                "wu": P("model", None, ff[0]),
                "wd": P("model", ff[0], None),
            }
        else:                                      # E sharded over data×model
            ep = tuple(a for a in ("data", "model") if a in axes)
            espec = {
                "wg": P(ep if len(ep) > 1 else ep[0], None, None),
                "wu": P(ep if len(ep) > 1 else ep[0], None, None),
                "wd": P(ep if len(ep) > 1 else ep[0], None, None),
            }
        token_spec = P(None, None, None)
    elif ep == ("model",) or len(ep) <= 1:
        mode = "tp"
        ep = ("model",) if "model" in axes else ep
        # [B, S, d]: B over dp, tokens replicated over the expert (model) axis
        token_spec = P(dp_entry, None, None)
        espec = {
            "wg": P(ep[0], None, None),
            "wu": P(ep[0], None, None),
            "wd": P(ep[0], None, None),
        }
    else:
        mode = "all"
        # [B, S, d]: B over dp, S over model — local flatten gives full-mesh
        # token sharding without a global reshape+reshard
        token_spec = P(dp_entry, "model", None)
        ep_sp: Any = ep if len(ep) > 1 else ep[0]
        espec = {
            "wg": P(ep_sp, None, None),
            "wu": P(ep_sp, None, None),
            "wd": P(ep_sp, None, None),
        }
    expert_specs = {"router": P(None, None), "experts": espec}
    return MoeMeshInfo(
        mesh=mesh, ep_axes=ep, mode=mode, token_spec=token_spec,
        expert_spec_tree=expert_specs, psum_axes=psum_axes,
    )


def auto_microbatches(global_batch: int, seq_len: int, rules: ShardingRules,
                      *, cfg: ArchConfig | None = None,
                      stack_budget_bytes: float = 4e9) -> int:
    """Gradient-accumulation depth.

    The backward pass saves one residual-stream tensor per layer
    (L × tokens_per_dev × d_model × 2 bytes under full remat); choose the
    microbatch count that keeps that stack under ``stack_budget_bytes``.
    """
    import numpy as np

    dp = rules.logical_to_physical["batch"]
    dp_size = int(np.prod([rules.mesh.shape[a] for a in dp])) if dp else 1
    if global_batch % dp_size:
        dp_size = 1
    b_loc = global_batch // dp_size
    if cfg is not None:
        layers_total = cfg.num_layers + cfg.encoder_layers
        per_token = layers_total * cfg.d_model * 2
        target = max(1024, int(stack_budget_bytes / per_token))
    else:
        target = 16384
    m = 1
    while b_loc % (m * 2) == 0 and (b_loc // m) * seq_len > target:
        m *= 2
    return m


def batch_shardings(cfg: ArchConfig, rules: ShardingRules, global_batch: int) -> dict:
    mesh = rules.mesh
    out = {"tokens": NamedSharding(mesh, rules.batch_pspec(global_batch, 1))}
    if cfg.frontend == "vision_patches":
        out["patch_embeds"] = NamedSharding(mesh, rules.batch_pspec(global_batch, 2))
    if cfg.frontend == "audio_frames":
        out["frames"] = NamedSharding(mesh, rules.batch_pspec(global_batch, 2))
    return out


def opt_shardings(opt_cfg: OptConfig, spec_tree, rules: ShardingRules):
    """NamedSharding tree of the optimizer state for ``spec_tree``'s params."""
    return jax.tree.map(
        lambda ps: NamedSharding(rules.mesh, ps),
        opt_state_specs(opt_cfg, spec_tree, rules.pspec),
        is_leaf=lambda x: isinstance(x, P),
    )


def make_train_step(
    model: DecoderLM | EncDecLM,
    opt_cfg: OptConfig,
    rules: ShardingRules,
    *,
    global_batch: int,
    microbatches: int = 1,
    donate: bool = True,
):
    """Returns (jitted step fn, param shardings, opt shardings, batch shardings)."""
    cfg = model.cfg
    spec_tree = model.param_specs()
    p_shard = rules.sharding_tree(spec_tree)
    o_shard = opt_shardings(opt_cfg, spec_tree, rules)
    b_shard = batch_shardings(cfg, rules, global_batch)
    minfo = moe_mesh_info(cfg, rules)

    def loss_fn(params, batch):
        with act.use_rules(rules):
            return model.loss(params, batch, moe_info=minfo)

    def whole_batch_grads(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return grads, metrics

    accum_dtype = jnp.dtype(opt_cfg.accum_dtype)

    def microbatched_grads(params, batch):
        def reshape(x):
            b = x.shape[0]
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])

        mb = jax.tree.map(reshape, batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)

        def body(acc, one):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, one
            )
            acc = jax.tree.map(
                lambda a, g: a + (g / microbatches).astype(accum_dtype), acc, grads
            )
            return acc, metrics

        grads, metrics = jax.lax.scan(body, g0, mb)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        return grads, metrics

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            grads, metrics = microbatched_grads(params, batch)
        else:
            grads, metrics = whole_batch_grads(params, batch)
        params, opt_state, om = opt_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {**metrics, **om}

    step = jax.jit(
        train_step,
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return step, p_shard, o_shard, b_shard


def init_train_state(model, opt_cfg: OptConfig, rules: ShardingRules, rng):
    """Materialize params + opt state with their production shardings.

    Only used at small scale (examples/tests); the dry-run never calls this.
    """
    from repro.models.params import init_params

    spec_tree = model.param_specs()
    params = init_params(spec_tree, rng)
    p_shard = rules.sharding_tree(spec_tree)
    params = jax.tree.map(jax.device_put, params, p_shard)
    # the step's in_shardings demand the state's own layout: zeros made
    # under the mesh would come out replicated
    opt_state = jax.device_put(
        opt_init(opt_cfg, params), opt_shardings(opt_cfg, spec_tree, rules)
    )
    return params, opt_state
