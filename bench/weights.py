"""Random weights for a dense GQA decoder, drawn from a seed on the device.

The benchmark makes the weights itself, in the parameter layout the
program's ``DecoderLM`` takes (one stacked segment of ``num_layers`` dense
layers), so that the float32 reference can draw the same weights again
without taking anything the program made.  One jitted call makes the whole
tree in the type it is served in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


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


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    tree["segments"] = [tree["segments"]["0"]]
    return tree


def _draw(cfg: dict, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    flat = {}
    items = sorted(layout(cfg).items())
    for k, (path, (shape, init, scale)) in zip(
            jax.random.split(key, len(items)), items):
        if init == "ones":
            flat[path] = jnp.ones(shape, dtype)
        else:
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * scale).astype(dtype)
    return _nest(flat)


@functools.lru_cache(maxsize=None)
def _compiled_draw(cfg_items: tuple):
    return jax.jit(functools.partial(_draw, dict(cfg_items)))


def make(cfg: dict, seed: int) -> dict:
    """The weights of ``cfg`` drawn from ``seed``, on the default device."""
    keys = ("num_layers", "d_model", "d_ff", "vocab_size", "num_heads",
            "num_kv_heads", "head_dim", "tie_embeddings", "dtype")
    return _compiled_draw(tuple((k, cfg[k]) for k in keys))(seed_key(seed))


def nbytes(cfg: dict) -> int:
    """Bytes of the weights as served."""
    size = jnp.dtype(cfg["dtype"]).itemsize
    return sum(int(np.prod(s)) * size for s, _, _ in layout(cfg).values())
