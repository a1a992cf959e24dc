"""Seeded tensors: every weight and every input of a run comes from here.

A tensor is a pure function of (seed, name, layer): the program's
parameters and the reference's copy of them are drawn by the same call,
so the reference takes nothing the program made.  Values are uniform
with the requested standard deviation; uniform draws are bit-exact under
``vmap``, ``scan`` and sharded ``jit`` alike.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

# independent streams under one run seed
WEIGHTS, DATA = 1, 2


def root_key(seed: int, stream: int) -> jax.Array:
    """Key of one stream of ``seed``; seeds may exceed 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def name_key(key: jax.Array, name: str, layer) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(key, layer)


def tensor(key: jax.Array, name: str, layer, shape, std: float,
           dtype=jnp.bfloat16) -> jax.Array:
    """Uniform tensor of standard deviation ``std``, rounded to ``dtype``
    (the type it is served in)."""
    a = std * math.sqrt(3.0)
    x = jax.random.uniform(name_key(key, name, layer), tuple(shape),
                           jnp.float32, -a, a)
    return x.astype(dtype)


def stacked(key: jax.Array, name: str, n_layers: int, shape, std: float,
            dtype=jnp.bfloat16) -> jax.Array:
    """``[n_layers, *shape]``: layer ``l`` equals ``tensor(..., l, ...)``."""
    return jax.vmap(lambda l: tensor(key, name, l, shape, std, dtype))(
        jnp.arange(n_layers))
