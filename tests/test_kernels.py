"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401  (registration)
from repro.kernels import ops, ref
from repro.kernels.conv2d import conv2d, conv2d_fixed_weight
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul, matmul_fixed_weight
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd import ssd

RNG = np.random.default_rng(1234)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (32, 64, 32, 16, 16, 32),
        (64, 128, 96, 32, 32, 64),
        (128, 256, 128, 128, 128, 128),
        (8, 512, 16, 8, 16, 256),
    ],
)
def test_matmul_sweep(dtype, m, k, n, bm, bn, bk):
    x, w = _rand((m, k), dtype), _rand((k, n), dtype)
    got = matmul(x, w, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    want = ref.matmul(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("activation", ["silu", "gelu"])
def test_matmul_fused_activation(activation):
    x, w = _rand((32, 64), jnp.float32), _rand((64, 32), jnp.float32)
    got = matmul(x, w, block_m=16, block_n=16, block_k=32,
                 activation=activation, interpret=True)
    want = ref.matmul(x, w, activation=activation)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_matmul_fixed_weight_role_matches_generic():
    x, w = _rand((32, 64), jnp.float32), _rand((64, 32), jnp.float32)
    fixed = matmul_fixed_weight(w, block_m=16, block_n=16, block_k=32)
    got = fixed(x, interpret=True)
    want = matmul(x, w, block_m=16, block_n=16, block_k=32, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_pallas_matmul_wrapper_batched():
    x, w = _rand((2, 3, 64), jnp.float32), _rand((64, 48), jnp.float32)
    got = ops.pallas_matmul(x, w, interpret=True)
    np.testing.assert_allclose(got, ref.matmul(x.reshape(6, 64), w).reshape(2, 3, 48),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (3, 5, 256), (64, 512)])
def test_rmsnorm_sweep(dtype, shape):
    x = _rand(shape, dtype)
    w = _rand(shape[-1:], dtype)
    got = rmsnorm(x, w, block_rows=16, interpret=True)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(dtype, hq, hkv, causal):
    B, S, D = 2, 64, 32
    q, k, v = (_rand((B, hq, S, D), dtype), _rand((B, hkv, S, D), dtype),
               _rand((B, hkv, S, D), dtype))
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [16, 48])
def test_flash_attention_sliding_window(window):
    B, H, S, D = 1, 2, 128, 32
    q, k, v = (_rand((B, H, S, D), jnp.float32), _rand((B, H, S, D), jnp.float32),
               _rand((B, H, S, D), jnp.float32))
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_query_at_kv_tail():
    """S < T: queries sit at the end of the KV axis (chunked prefill/decode)."""
    B, H, S, T, D = 1, 2, 32, 128, 32
    q = _rand((B, H, S, D), jnp.float32)
    k, v = _rand((B, H, T, D), jnp.float32), _rand((B, H, T, D), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_xla_flash_attention_matches_ref():
    B, H, S, D = 2, 2, 96, 16
    q, k, v = (_rand((B, H, S, D), jnp.float32), _rand((B, H, S, D), jnp.float32),
               _rand((B, H, S, D), jnp.float32))
    for kw in [dict(causal=True), dict(causal=False), dict(causal=True, window=32)]:
        got = ops.xla_flash_attention(q, k, v, block_q=32, **kw)
        want = ref.flash_attention(q, k, v, **kw)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# conv2d (paper roles 3/4)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kh,kw,cin,f,dtype",
    [
        (5, 5, 1, 1, jnp.int16),   # paper role 3
        (3, 3, 1, 2, jnp.int16),   # paper role 4
        (3, 3, 4, 8, jnp.float32),
    ],
)
def test_conv2d_sweep(kh, kw, cin, f, dtype):
    if dtype == jnp.int16:
        x = jnp.asarray(RNG.integers(-100, 100, size=(2, 20, 20, cin)), dtype)
        w = jnp.asarray(RNG.integers(-8, 8, size=(kh, kw, cin, f)), dtype)
    else:
        x, w = _rand((2, 20, 20, cin), dtype), _rand((kh, kw, cin, f), dtype)
    got = conv2d(x, w, interpret=True)
    want = ref.conv2d(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_conv2d_fixed_weight_role():
    x = jnp.asarray(RNG.integers(-50, 50, size=(1, 12, 12, 1)), jnp.int16)
    w = jnp.asarray(RNG.integers(-4, 4, size=(3, 3, 1, 2)), jnp.int16)
    fixed = conv2d_fixed_weight(w)
    np.testing.assert_array_equal(fixed(x, interpret=True), ref.conv2d(x, w))


# ---------------------------------------------------------------------------
# ssd (Mamba-2)
# ---------------------------------------------------------------------------


def _ssd_inputs(B=2, S=64, H=4, P=16, G=2, N=32, dtype=jnp.float32):
    x = _rand((B, S, H, P), dtype)
    a_log = jnp.asarray(-np.abs(RNG.normal(size=(H,))), jnp.float32)
    b = _rand((B, S, G, N), dtype)
    c = _rand((B, S, G, N), dtype)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.1, jnp.float32)
    return x, a_log, b, c, dt


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
def test_ssd_pallas_chunk_sweep(chunk):
    x, a_log, b, c, dt = _ssd_inputs()
    want, wstate = ref.ssd(x, a_log, b, c, dt, return_state=True)
    got, gstate = ssd(x, a_log, b, c, dt, chunk=chunk, return_state=True, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gstate, wstate, rtol=1e-4, atol=1e-4)


def test_ssd_xla_matches_ref_and_step():
    x, a_log, b, c, dt = _ssd_inputs()
    want, wstate = ref.ssd(x, a_log, b, c, dt, return_state=True)
    got, gstate = ops.xla_ssd(x, a_log, b, c, dt, chunk=16, return_state=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gstate, wstate, rtol=1e-4, atol=1e-4)

    # sequential single-token decode agrees with the parallel scan
    B, S, H, P = x.shape
    N = b.shape[-1]
    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(12):
        h, y = ops.ssd_step(h, x[:, t], a_log, b[:, t], c[:, t], dt[:, t])
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want[:, :12], rtol=1e-4, atol=1e-4)


def test_ssd_bf16_inputs():
    x, a_log, b, c, dt = _ssd_inputs(dtype=jnp.bfloat16)
    want = ref.ssd(x, a_log, b, c, dt)
    got = ssd(x, a_log, b, c, dt, chunk=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


def test_decode_attention_matches_full_attention():
    """Decoding one token == last row of full causal attention."""
    B, Hq, Hkv, T, D = 2, 4, 2, 64, 16
    q_full = _rand((B, Hq, T, D), jnp.float32)
    k = _rand((B, Hkv, T, D), jnp.float32)
    v = _rand((B, Hkv, T, D), jnp.float32)
    full = ref.flash_attention(q_full, k, v, causal=True)
    got = ref.decode_attention(q_full[:, :, -1], k, v, length=T)
    np.testing.assert_allclose(got, full[:, :, -1], rtol=2e-4, atol=2e-4)


def test_decode_attention_respects_length_mask():
    B, H, T, D = 1, 2, 32, 8
    q = _rand((B, H, D), jnp.float32)
    k = _rand((B, H, T, D), jnp.float32)
    v = _rand((B, H, T, D), jnp.float32)
    short = ref.decode_attention(q, k[:, :, :10], v[:, :, :10], length=10)
    padded = ref.decode_attention(q, k, v, length=10)
    np.testing.assert_allclose(short, padded, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Pallas decode attention (serving hot-spot kernel)
# ---------------------------------------------------------------------------

from repro.kernels.decode_attention import decode_attention as pallas_decode


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv,T,bk", [(8, 2, 64, 16), (4, 4, 128, 32),
                                         (8, 1, 64, 64)])
def test_pallas_decode_attention_sweep(dtype, hq, hkv, T, bk):
    B, D = 2, 32
    q = _rand((B, hq, D), dtype)
    k = _rand((B, hkv, T, D), dtype)
    v = _rand((B, hkv, T, D), dtype)
    got = pallas_decode(q, k, v, T, block_k=bk, interpret=True)
    want = ref.decode_attention(q, k, v, T)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_pallas_decode_attention_per_sequence_lengths():
    """Continuous batching: each slot masks its own cache length."""
    B, Hq, Hkv, T, D = 3, 4, 2, 64, 16
    q = _rand((B, Hq, D), jnp.float32)
    k = _rand((B, Hkv, T, D), jnp.float32)
    v = _rand((B, Hkv, T, D), jnp.float32)
    lengths = jnp.asarray([5, 64, 33])
    got = pallas_decode(q, k, v, lengths, block_k=16, interpret=True)
    want = ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_pallas_decode_attention_matches_grouped_xla():
    B, Hq, Hkv, T, D = 2, 8, 2, 96, 32
    q = _rand((B, Hq, D), jnp.float32)
    k = _rand((B, Hkv, T, D), jnp.float32)
    v = _rand((B, Hkv, T, D), jnp.float32)
    a = pallas_decode(q, k, v, 70, block_k=32, interpret=True)
    b = ops.xla_decode_attention(q, k, v, 70)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# paged decode attention (block-table KV gather)
# ---------------------------------------------------------------------------
from repro.kernels.decode_attention import (                      # noqa: E402
    paged_decode_attention as pallas_paged_decode,
)


def _paged_case(B, hkv, ps, n_pages, pool_pages, D, dtype, seed=0):
    """Random pool + disjoint per-sequence tables (page 0 left as scratch)."""
    rng = np.random.default_rng(seed)
    k_pages = _rand((pool_pages, hkv, ps, D), dtype)
    v_pages = _rand((pool_pages, hkv, ps, D), dtype)
    perm = rng.permutation(np.arange(1, pool_pages))[: B * n_pages]
    table = jnp.asarray(perm.reshape(B, n_pages), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, n_pages * ps + 1, size=B), jnp.int32)
    return k_pages, v_pages, table, lengths


def _case(hq, hkv, ps, n_pages, lengths="random", block_pages=None,
          tables="disjoint", layer=None, nan=False, id=None):
    return pytest.param(hq, hkv, ps, n_pages,
                        dict(lengths=lengths, block_pages=block_pages,
                             tables=tables, layer=layer, nan=nan),
                        id=id or f"{hq}-{hkv}-{ps}-{n_pages}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv,ps,n_pages,case", [
    _case(8, 2, 16, 4), _case(4, 4, 32, 2), _case(8, 1, 8, 8),
    # blocks of 2 pages (16 rows), so a slot spans several blocks
    _case(8, 2, 8, 8, "one", 2, id="bound-1"),
    _case(8, 2, 8, 8, "block-edge", 2, id="block-edge"),
    _case(8, 2, 8, 8, "block-edge+1", 2, id="block-edge-plus-1"),
    _case(8, 2, 8, 8, "full", 2, id="full-table"),
    _case(8, 2, 8, 8, "full", id="full-table-one-block"),
    _case(4, 1, 8, 8, "random", 3, "scrambled", id="scrambled-shared"),
    _case(8, 2, 8, 8, "random", 2, layer=2, id="layer-2-of-stack"),
    _case(8, 2, 8, 8, "random", 2, "scrambled", nan=True,
          id="nan-past-length"),
])
def test_pallas_paged_decode_attention_sweep(dtype, hq, hkv, ps, n_pages, case,
                                             monkeypatch):
    """The length-bounded kernel against the gather-then-dense oracle.

    ``nan``: every pool page past each slot's length, and every page the
    tables do not map, holds NaN, and the oracle reads clean copies.  A NaN
    that reached the P·V product would survive any masking of the logits,
    so equality shows those pages are never copied."""
    from repro.kernels import decode_attention as dec_k

    B, D, L = 3, 32, 3
    rng = np.random.default_rng(7)
    if case["block_pages"] is not None:
        monkeypatch.setattr(dec_k, "MIN_BLOCK_BYTES", 0)
        monkeypatch.setattr(dec_k, "MIN_BLOCK_ROWS", case["block_pages"] * ps)
    q = _rand((B, hq, D), dtype)
    k_pages, v_pages, table, lengths = _paged_case(
        B, hkv, ps, n_pages, B * n_pages + 3, D, dtype)
    P = k_pages.shape[0]
    rows = ps * dec_k.paged_block_pages(ps, hkv, D, jnp.dtype(dtype).itemsize,
                                        n_pages)
    lengths = {
        "random": lengths,
        "one": jnp.ones(B, jnp.int32),
        "block-edge": jnp.asarray([rows, 2 * rows, 3 * rows], jnp.int32),
        "block-edge+1": jnp.asarray([rows + 1, 2 * rows + 1, 1], jnp.int32),
        "full": jnp.full(B, n_pages * ps, jnp.int32),
    }[case["lengths"]]
    if case["tables"] == "scrambled":
        # any pool page, shared between slots (as prefix sharing maps them)
        table = jnp.asarray(rng.integers(1, P, (B, n_pages)), jnp.int32)
        table = table.at[1, :2].set(table[0, :2])
    clean = k_pages, v_pages
    if case["nan"]:
        live = -(-np.asarray(lengths) // ps)
        tab = np.array(table)
        keep = np.zeros(P, bool)
        for b in range(B):
            keep[tab[b, :live[b]]] = True
            tab[b, live[b]:] = 0                   # unmapped: the scratch page
        table = jnp.asarray(tab)
        poison = jnp.asarray(~keep)[:, None, None, None]
        k_pages, v_pages = (jnp.where(poison, jnp.nan, x).astype(dtype)
                            for x in clean)
    want = ref.paged_decode_attention(q, *clean, table, lengths)
    if case["layer"] is not None:
        stack = lambda x: jnp.stack(                # noqa: E731
            [jnp.roll(x, i + 1, axis=0) for i in range(L)]).at[case["layer"]].set(x)
        k_pages, v_pages = stack(k_pages), stack(v_pages)
        got = pallas_paged_decode(q, k_pages, v_pages, table, lengths,
                                  layer=jnp.int32(case["layer"]), interpret=True)
    else:
        got = pallas_paged_decode(q, k_pages, v_pages, table, lengths,
                                  interpret=True)
    assert (rows < n_pages * ps) == (case["block_pages"] is not None)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("source", ["reference", "xla", "pallas"])
def test_paged_decode_attention_reads_layer_of_stack(source):
    """With ``layer`` each source reads that layer of a stacked pool [L, P,
    Hkv, ps, hd] — bitwise what it reads from the layer's own pool."""
    B, Hq, Hkv, ps, n_pages, D, L = 2, 8, 2, 8, 4, 32, 3
    fn = {
        "reference": ref.paged_decode_attention,
        "xla": ops.xla_paged_decode_attention,
        "pallas": functools.partial(pallas_paged_decode, interpret=True),
    }[source]
    q = _rand((B, Hq, D), jnp.bfloat16)
    cases = [_paged_case(B, Hkv, ps, n_pages, B * n_pages + 1, D,
                         jnp.bfloat16, seed=0) for _ in range(L)]
    k_stack = jnp.stack([c[0] for c in cases])
    v_stack = jnp.stack([c[1] for c in cases])
    _, _, table, lengths = cases[0]
    for layer in range(L):
        got = jax.jit(lambda *a: fn(*a[:5], layer=a[5]))(
            q, k_stack, v_stack, table, lengths, jnp.int32(layer))
        want = fn(q, cases[layer][0], cases[layer][1], table, lengths)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_paged_gather_is_bitwise_dense():
    """The gather-based XLA source must be bitwise-equal to dense decode
    attention over the gathered cache — the paged serving engine's
    equivalence guarantee bottoms out in this property."""
    B, Hq, Hkv, ps, n_pages, D = 3, 4, 2, 16, 4, 16
    q = _rand((B, Hq, D), jnp.float32)
    k_pages, v_pages, table, lengths = _paged_case(
        B, Hkv, ps, n_pages, B * n_pages + 1, D, jnp.float32)
    paged = ops.xla_paged_decode_attention(q, k_pages, v_pages, table, lengths)
    dense = ops.xla_decode_attention(
        q, ref.gather_kv_pages(k_pages, table),
        ref.gather_kv_pages(v_pages, table), lengths)
    assert np.array_equal(np.asarray(paged), np.asarray(dense))


def test_paged_scrambled_table_matches_contiguous():
    """Page placement is transparent: scrambling WHERE pages live in the
    pool (fixing what they contain) cannot change the result."""
    B, Hkv, ps, n_pages, D = 2, 2, 8, 4, 16
    pool_pages = B * n_pages + 1
    q = _rand((B, 8, D), jnp.float32)
    k_pages, v_pages, table, lengths = _paged_case(
        B, Hkv, ps, n_pages, pool_pages, D, jnp.float32)
    base = ref.paged_decode_attention(q, k_pages, v_pages, table, lengths)

    # relocate every page under a permutation of the pool
    perm = np.random.default_rng(5).permutation(np.arange(1, pool_pages))
    relocate = np.zeros(pool_pages, np.int64)
    relocate[1:] = perm
    k2 = jnp.asarray(np.asarray(k_pages)[np.argsort(relocate)])
    v2 = jnp.asarray(np.asarray(v_pages)[np.argsort(relocate)])
    table2 = jnp.asarray(relocate[np.asarray(table)], jnp.int32)
    moved = ref.paged_decode_attention(q, k2, v2, table2, lengths)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(moved))
