"""Random weights for the cell's model, drawn from a seed on the device.

The benchmark makes the weights itself, in the parameter layout the
program takes, so that the float32 reference can draw the same weights
again without taking anything the program made.  The architecture module
(``bench/archs/<a>.py``) says which weights there are (``layout``) and how
they nest into the program's tree (``nest``); each is drawn here from its
own key, one per path in sorted order.  One jitted call makes the whole
tree in the type it is served in.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def _draw(arch, cfg: dict, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    flat = {}
    items = sorted(arch.layout(cfg).items())
    for k, (path, (shape, init, scale)) in zip(
            jax.random.split(key, len(items)), items):
        if init == "ones":
            flat[path] = jnp.ones(shape, dtype)
        else:
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * scale).astype(dtype)
    return arch.nest(flat)


@functools.lru_cache(maxsize=None)
def _compiled_draw(arch, cfg_key: str):
    return jax.jit(functools.partial(_draw, arch, json.loads(cfg_key)))


def make(arch, cfg: dict, seed: int) -> dict:
    """The weights of ``cfg`` under architecture module ``arch``, drawn
    from ``seed``, on the default device."""
    return _compiled_draw(arch, spec.model_key(cfg))(seed_key(seed))


def nbytes(arch, cfg: dict) -> int:
    """Bytes of the weights as served."""
    size = jnp.dtype(cfg["dtype"]).itemsize
    return sum(int(np.prod(s)) * size for s, _, _ in arch.layout(cfg).values())
