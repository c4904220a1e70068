"""Random weights of a configuration, made on the device from ``--seed``.

The benchmark makes the weights itself, so that the plain reference can
make the same ones again without taking anything from the program.  Every
matrix is its own draw: ``normal(0, initializer_range)`` under a key
folded from the seed, the leaf's name and its layer, rounded to the served
dtype (bfloat16).  RMSNorm scales are ones.  ``init`` makes the program's
whole parameter tree in one compiled call; ``layer`` makes one layer's
matrices, with the same keys, for the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# a stable number per leaf: part of every key, so never renumber
LEAF_IDS = {"embed": 0, "head": 1, "attn/wq": 2, "attn/wk": 3, "attn/wv": 4,
            "attn/wo": 5, "ffn/gate": 6, "ffn/up": 7, "ffn/down": 8}
PROJ = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
        "ffn/gate", "ffn/up", "ffn/down")


def sizes(cfg):
    """The sizes the weights need, from a configuration file's keys."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return {"d": d, "H": H, "KV": cfg["num_key_value_heads"], "hd": hd,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "std": cfg["initializer_range"]}


def proj_shapes(s):
    """(in, out) of each projection of one layer."""
    d, q, kv, ff = s["d"], s["H"] * s["hd"], s["KV"] * s["hd"], s["ff"]
    return {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d), "ffn/gate": (d, ff), "ffn/up": (d, ff),
            "ffn/down": (ff, d)}


def seed_words(seed: int):
    """A seed of up to 64 bits as two uint32 words (a key takes 32)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number of 64 bits")
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def _key(words, name, layer=0):
    lo, hi = words
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)


def _draw(words, name, layer, shape, std, dtype):
    z = jax.random.normal(_key(words, name, layer), shape, jnp.float32)
    return (z * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(words, frozen, dtype):
    s = dict(frozen)
    L, d, V, std = s["L"], s["d"], s["V"], s["std"]
    shapes = proj_shapes(s)

    def stacked(name):
        return jnp.stack([_draw(words, name, l, shapes[name], std, dtype)
                          for l in range(L)])

    ones = lambda: jnp.ones((L, d), dtype)  # noqa: E731
    return {
        "embed": {"table": _draw(words, "embed", 0, (V, d), std, dtype)},
        "head": {"table": _draw(words, "head", 0, (V, d), std, dtype)},
        "norm_f": {"scale": jnp.ones((d,), dtype)},
        "layers": {
            "ln1": {"scale": ones()},
            "attn": {n.split("/")[1]: {"w": stacked(n)}
                     for n in PROJ if n.startswith("attn/")},
            "ln2": {"scale": ones()},
            "ffn": {n.split("/")[1]: {"w": stacked(n)}
                    for n in PROJ if n.startswith("ffn/")},
        },
    }


def init(cfg, seed, dtype=jnp.bfloat16):
    """The program's parameter tree for ``cfg`` (a configuration file's
    dict), every leaf drawn on the device in one compiled call."""
    return _init(seed_words(seed), tuple(sorted(sizes(cfg).items())), dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _layer(words, layer, frozen, dtype):
    s = dict(frozen)
    return {n: _draw(words, n, layer, shp, s["std"], dtype)
            for n, shp in proj_shapes(s).items()}


def layer(cfg, seed, l, dtype=jnp.bfloat16):
    """Layer ``l``'s projections, {name: (in, out)}, as ``init`` draws
    them."""
    return _layer(seed_words(seed), l, tuple(sorted(sizes(cfg).items())),
                  dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _table(words, name, frozen, dtype):
    s = dict(frozen)
    return _draw(words, name, 0, (s["V"], s["d"]), s["std"], dtype)


def table(cfg, seed, name, dtype=jnp.bfloat16):
    """The ``embed`` or ``head`` table, (vocab, d), as ``init`` draws it."""
    return _table(seed_words(seed), name, tuple(sorted(sizes(cfg).items())),
                  dtype)
