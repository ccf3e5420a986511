"""What ``dense_gqa`` computes is pinned: the weights drawn from a seed and
the reference's and the control's gaps on a fixed sequence read bit for
bit as the harness read them before it found its model by name."""

from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from bench import spec, weights
from bench.tests.test_bench_run import SEED, TINY

DENSE = spec.arch("dense_gqa")
TOKENS = np.arange(1, 41) * 7 % 500 + 1                 # 40 rows
TARGETS = (TOKENS * 3 + 11) % TINY["vocab_size"]
LENGTH = 512

# sha256 of the weights (each leaf's path, dtype, shape and bytes, in path
# order) and of the float32 gap arrays of ``LENGTH`` rows
PINNED = {
    False: {"weights": "715f143cfb1c3ed1763aec9b00f9b596ddd0c18acc45b64f41add797128bdbc9",
            "served": "357664ab15794d4c061fa63a10eb20ed2a6950cfd7d9df2123911a974fe00942",
            "control": "fd0e22ffad0c27f5d0523f0ea3e6366d0ea21fa5d4cfc00094dfed85566ff509"},
    True: {"weights": "145cb4721be69801996488cb781218a48d4e02f3af985f35dbdbadffa3793174",
           "served": "9821ff32886fbc4298b16d8e976a2648b9ba03e75cbf22d8a1d1d2f735d36216",
           "control": "714640c0227c1f9a973ff7938ddf2e06ed77a5717910bc578d521e69478ee6fc"},
}


def _weights_digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _cfg(tied: bool) -> dict:
    return {**TINY, "tie_embeddings": tied}


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_weights_are_pinned(tied):
    params = weights.make(DENSE, _cfg(tied), SEED)
    assert _weights_digest(params) == PINNED[tied]["weights"]


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("which", ["served", "control"])
def test_gaps_are_pinned(which, tied):
    cfg = _cfg(tied)
    params = weights.make(DENSE, cfg, SEED)
    if which == "served":
        gaps = DENSE.served_gaps(cfg, params, TOKENS, TARGETS, LENGTH)
    else:
        gaps = DENSE.control_gaps(cfg, params, TOKENS, LENGTH)
    gaps = np.asarray(gaps)
    assert gaps.dtype == np.float32 and gaps.shape == (LENGTH,)
    assert hashlib.sha256(gaps.tobytes()).hexdigest() == PINNED[tied][which]
