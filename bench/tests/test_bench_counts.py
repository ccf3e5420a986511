"""``bench.counts`` over ``dense_gqa.shapes`` against hand arithmetic, for
the Yi-6B configuration and for a tied-embedding one with 8 KV heads at
Granite-3-8B's widths."""

from __future__ import annotations

import pytest

from bench import counts, spec, weights

DENSE = spec.arch("dense_gqa")

# per layer: wq + wk + wv + wo + MLP (+ two norms for the parameter count)
YI_LAYER = 4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096 + 3 * 4096 * 11008
GRANITE_LAYER = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12800
GRANITE = {"num_layers": 16, "d_model": 4096, "num_heads": 32, "num_kv_heads": 8,
           "head_dim": 128, "d_ff": 12800, "vocab_size": 49155, "rope_theta": 1e4,
           "norm_eps": 1e-5, "tie_embeddings": True, "dtype": "bfloat16"}


def _config(name):
    return GRANITE if name == "granite" else spec.config(name)


@pytest.mark.parametrize("name, layer, vocab, kv_heads, tied", [
    ("yi-6b", YI_LAYER, 64000, 4, False),
    ("granite", GRANITE_LAYER, 49155, 8, True),
])
def test_shapes_match_hand_arithmetic(name, layer, vocab, kv_heads, tied):
    cfg = _config(name)
    s = DENSE.shapes(cfg)
    assert s.matmul_flops == 16 * 2 * layer
    assert s.head_flops == 2 * 4096 * vocab
    assert s.layer_bytes == 16 * (layer + 2 * 4096) * 2
    assert s.head_bytes == (4096 * vocab + 4096) * 2
    assert s.kv_bytes_per_row == 2 * 16 * kv_heads * 128 * 2
    # what the benchmark draws and serves: the layers, the final norm, the
    # embedding and, untied, the unembedding
    stored = s.layer_bytes + s.head_bytes + (0 if tied else 4096 * vocab * 2)
    assert weights.nbytes(DENSE, cfg) == stored


def test_kv_and_weights_as_the_issue_states():
    s = DENSE.shapes(spec.config("yi-6b"))
    assert s.kv_bytes_per_row == 32 * 1024
    assert weights.nbytes(DENSE, spec.config("yi-6b")) == 6_585_327_616
    g = DENSE.shapes(GRANITE)
    assert g.kv_bytes_per_row == 64 * 1024


def test_prefill_of_one_chunk_by_hand():
    s = DENSE.shapes(spec.config("yi-6b"))
    flops, nbytes, chunks = counts.prefill(s, 0, 512, 512)
    attention = 16 * 4 * 32 * 128 * (512 * 513 // 2)      # rows attend to r+1
    assert flops == 512 * s.matmul_flops + s.head_flops + attention
    assert flops == 2_869_629_550_592
    assert chunks == 1
    assert nbytes == s.layer_bytes + s.head_bytes + 512 * s.kv_bytes_per_row


def test_prefill_after_a_shared_prefix_counts_only_new_rows():
    s = DENSE.shapes(GRANITE)
    flops, nbytes, chunks = counts.prefill(s, 1024, 1100, 512)
    ctx = sum(r + 1 for r in range(1024, 1100))
    assert flops == 76 * s.matmul_flops + s.head_flops + s.attention_flops(1) * ctx
    assert chunks == 1
    assert nbytes == s.layer_bytes + s.head_bytes + (1024 + 76) * s.kv_bytes_per_row
    # two chunks: the second reads the first's rows as well
    _, nbytes2, chunks2 = counts.prefill(s, 0, 700, 512)
    assert chunks2 == 2
    assert nbytes2 == (2 * s.layer_bytes + s.head_bytes
                       + (0 + 512 + 700) * s.kv_bytes_per_row)


def test_decode_by_hand():
    s = DENSE.shapes(GRANITE)
    flops, kv = counts.decode(s, 10, 3)         # two decode steps: rows 10, 11
    assert flops == 2 * (s.matmul_flops + s.head_flops) + s.attention_flops(1) * (11 + 12)
    assert kv == (11 + 12 + 2) * s.kv_bytes_per_row
    assert counts.decode(s, 10, 1) == (0, 0)


# every number of the shapes interface and of ``counts`` at these shapes, as
# the harness computed them before the counts moved into ``bench/archs``
PINNED = {
    "yi-6b": ([5536743424, 524296192, 6061039616, 32768, 5536481280, 524288000,
               262144, 1073741824],
              (8836284416000, 17292992512, 3), (1941498757120, 16175890432)),
    "granite": ([6375604224, 402685952, 6778290176, 65536, 6375342080, 402677760,
                 262144, 1073741824],
                (10094454005760, 19846430720, 3), (2155956674560, 32351780864)),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_are_pinned(name):
    s = DENSE.shapes(_config(name))
    got = [s.layer_bytes, s.head_bytes, s.weight_bytes, s.kv_bytes_per_row,
           s.matmul_flops, s.head_flops, s.attention_flops(1), s.attention_flops(4096)]
    assert (got, counts.prefill(s, 600, 2100, 512), counts.decode(s, 1500, 300)) \
        == PINNED[name]
