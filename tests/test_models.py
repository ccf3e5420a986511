"""Per-architecture smoke + consistency tests (reduced configs, CPU).

For each assigned arch: forward/train-step shape + NaN checks, and the
cache-correctness property: prefill + N decode steps == teacher-forced forward
(exact in f32; bf16 is used only in production configs).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as layers_mod
from repro.configs import ARCHS, reduced
from repro.models import build_model
from repro.models import reference
from repro.models.params import abstract_params, count_params, init_params

ALL_ARCHS = sorted(ARCHS)
REFERENCE_ARCHS = [n for n in ALL_ARCHS if reference.supports(ARCHS[n])]
RNG = np.random.default_rng(42)


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params
    )


def _smoke_cfg(name):
    cfg = reduced(ARCHS[name])
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    return cfg


def _batch(cfg, B, S):
    batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jnp.asarray(
            RNG.normal(size=(B, cfg.frontend_seq, cfg.d_model)), jnp.float32
        )
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.asarray(
            RNG.normal(size=(B, cfg.frontend_seq, cfg.d_model)), jnp.float32
        )
    return batch


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_smoke_forward_and_loss(name):
    cfg = _smoke_cfg(name)
    m = build_model(cfg)
    params = init_params(m.param_specs(), jax.random.key(0))
    batch = _batch(cfg, 2, 32)
    loss, metrics = m.loss(params, batch)
    assert np.isfinite(float(loss)), metrics
    assert float(loss) > 0


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_smoke_grads_finite(name):
    cfg = _smoke_cfg(name)
    m = build_model(cfg)
    params = _f32(init_params(m.param_specs(), jax.random.key(0)))
    batch = _batch(cfg, 2, 16)
    grads = jax.grad(lambda p: m.loss(p, batch)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in flat)
    # at least the embedding gradient must be nonzero
    assert float(jnp.abs(grads["embed"]["tok"]).sum()) > 0


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_prefill_decode_matches_forward(name):
    """The cache-correctness property across every family."""
    cfg = _smoke_cfg(name)
    m = build_model(cfg)
    params = _f32(init_params(m.param_specs(), jax.random.key(3)))
    B, S, EXTRA = 2, 16, 4
    full = _batch(cfg, B, S + EXTRA)
    pre = {k: (v[:, :S] if k == "tokens" else v) for k, v in full.items()}

    old = layers_mod.COMPUTE_DTYPE
    layers_mod.COMPUTE_DTYPE = jnp.float32
    try:
        if hasattr(m, "forward"):
            logits_full, _ = m.forward(params, full)
        else:
            memory = m.encode(params, full["frames"])
            h, _ = m._decode_full(params, full["tokens"], memory, "full")
            h = layers_mod.apply_norm(params["ln_f"], h, cfg.norm_eps)
            logits_full = layers_mod.unembed(params["embed"], h)

        lg, cache = m.prefill(params, pre, cache_len=S + EXTRA)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(logits_full[:, S - 1]), rtol=1e-4, atol=1e-4
        )
        for t in range(EXTRA):
            lg, cache = m.decode_step(params, full["tokens"][:, S + t: S + t + 1], cache)
            np.testing.assert_allclose(
                np.asarray(lg), np.asarray(logits_full[:, S + t]),
                rtol=1e-4, atol=1e-4,
                err_msg=f"decode step {t}",
            )
    finally:
        layers_mod.COMPUTE_DTYPE = old


def test_ring_buffer_window_attention_long_decode():
    """hymba: decoding past the window uses the ring buffer correctly."""
    cfg = _smoke_cfg("hymba-1.5b")          # window=32 in reduced form
    m = build_model(cfg)
    params = _f32(init_params(m.param_specs(), jax.random.key(5)))
    B, S, EXTRA = 1, 48, 3                  # S > window: ring engaged at prefill
    full = _batch(cfg, B, S + EXTRA)
    pre = {"tokens": full["tokens"][:, :S]}

    old = layers_mod.COMPUTE_DTYPE
    layers_mod.COMPUTE_DTYPE = jnp.float32
    try:
        logits_full, _ = m.forward(params, full)
        lg, cache = m.prefill(params, pre, cache_len=S + EXTRA)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(logits_full[:, S - 1]), rtol=1e-4, atol=1e-4
        )
        for t in range(EXTRA):
            lg, cache = m.decode_step(params, full["tokens"][:, S + t: S + t + 1], cache)
            np.testing.assert_allclose(
                np.asarray(lg), np.asarray(logits_full[:, S + t]),
                rtol=1e-4, atol=1e-4, err_msg=f"ring decode step {t}",
            )
    finally:
        layers_mod.COMPUTE_DTYPE = old


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_cache_specs_match_prefill_cache(name):
    cfg = _smoke_cfg(name)
    m = build_model(cfg)
    params = init_params(m.param_specs(), jax.random.key(0))
    B, S = 2, 16
    lg, cache = m.prefill(params, _batch(cfg, B, S), cache_len=S)
    specs = m.cache_specs(B, S)
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), cache)
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)), specs)
    assert got == want


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_full_config_abstract_params_match_published_size(name):
    """Full (production) configs: abstract param tree matches total_params()."""
    cfg = ARCHS[name]
    m = build_model(cfg)
    specs = m.param_specs()
    n = count_params(specs)
    expected = cfg.total_params()
    # layer norms / small vectors are excluded from the analytic count
    assert abs(n - expected) / expected < 0.01, (n, expected)
    # and nothing was materialized
    ap = abstract_params(specs)
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in jax.tree.leaves(ap))


@pytest.mark.parametrize("name", REFERENCE_ARCHS)
def test_float32_reference_matches_forward(name):
    """The plain jnp reference computes the model's function (exact in f32)."""
    cfg = _smoke_cfg(name)
    m = build_model(cfg)
    params = _f32(init_params(m.param_specs(), jax.random.key(11)))
    tokens = _batch(cfg, 1, 24)["tokens"]
    old = layers_mod.COMPUTE_DTYPE
    layers_mod.COMPUTE_DTYPE = jnp.float32
    try:
        want, _ = m.forward(params, {"tokens": tokens})
    finally:
        layers_mod.COMPUTE_DTYPE = old
    got = reference.logits(cfg, params, tokens[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


def test_float32_reference_refuses_other_families():
    with pytest.raises(ValueError, match="no float32 reference"):
        reference.logits(_smoke_cfg("mamba2-780m"), {}, jnp.zeros(4, jnp.int32))
