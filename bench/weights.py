"""Random weights, made on the device from ``--seed``: the drawing that
every family shares.

The benchmark makes the weights itself, so that the plain reference can
make the same ones again without taking anything from the program.  Every
matrix is its own draw: ``normal(0, std)`` under a key folded from the
seed, the leaf's id and its layer, rounded to the served dtype.  A family
module (``bench/families/<family>.py``) owns its table of leaf ids and
builds its parameter tree, in one compiled call, from ``draw``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_words(seed: int):
    """A seed of up to 64 bits as two uint32 words (a key takes 32)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number of 64 bits")
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def _key(words, leaf_id, layer=0):
    lo, hi = words
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(jax.random.fold_in(key, leaf_id), layer)


def draw(words, leaf_id, layer, shape, std, dtype):
    """The leaf ``leaf_id`` of ``layer``: ``normal(0, std)`` in ``dtype``.
    A leaf id is part of every key, so a family never renumbers its ids."""
    z = jax.random.normal(_key(words, leaf_id, layer), shape, jnp.float32)
    return (z * std).astype(dtype)
