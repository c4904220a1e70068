"""The Mistral family: a dense transformer with sliding-window GQA attention
and a SwiGLU FFN, as the program's ``dense`` family serves it.

Weights: every matrix is a ``bench.weights.draw`` of ``normal(0,
initializer_range)`` under its leaf id and layer (``LEAF_IDS``), rounded to
the served dtype (bfloat16); RMSNorm scales are ones.  ``init`` makes the
program's whole tree in one compiled call; ``layer`` and ``table`` make
one layer's projections and the embedding or head, with the same keys, for
the plain reference (``bench/configs/mistral.py``).

Work, from the shapes alone: what the model needs, whatever implements it.
The weights that survive pruning (from the masks the reference makes,
``block_keep``), the key/value entries a query may attend, the head where
the program needs logits.  Padding, pruned blocks, masked-out keys and
copies are not work.  Bytes are the least that has to cross HBM: each
weight once per program, each key/value entry read once, what is written
once, all in the served dtype.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

from bench.weights import draw, seed_words
from bench.work import ELT

# a stable number per leaf: part of every key, so never renumber
LEAF_IDS = {"embed": 0, "head": 1, "attn/wq": 2, "attn/wk": 3, "attn/wv": 4,
            "attn/wo": 5, "ffn/gate": 6, "ffn/up": 7, "ffn/down": 8}
PRUNED = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
          "ffn/gate", "ffn/up", "ffn/down")
_PRUNED_PATH = re.compile(r"(attn/w[qkvo]|ffn/(gate|up|down))/w$")


def is_pruned(path):
    """Whether a ``compile_model`` report path is one of ``PRUNED``."""
    return bool(_PRUNED_PATH.search(path))


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


def arch_config(cfg):
    """The program's ``ArchConfig`` for a configuration file.  It has no
    ``rms_norm_eps``: the program fixes 1e-6 (``models/layers.rmsnorm``),
    as the file's ``changed_from_source`` says."""
    from repro.configs.base import ArchConfig
    s = sizes(cfg)
    return ArchConfig(
        name=cfg["model_type"], family="dense", n_layers=s["L"],
        d_model=s["d"], n_heads=s["H"], n_kv_heads=s["KV"], d_ff=s["ff"],
        vocab=s["V"], head_dim=s["hd"], sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]))


def _frozen(cfg):
    return tuple(sorted(sizes(cfg).items()))


def _draw(words, name, layer, shape, std, dtype):
    return draw(words, LEAF_IDS[name], layer, shape, std, dtype)


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
                     for n in PRUNED if n.startswith("attn/")},
            "ln2": {"scale": ones()},
            "ffn": {n.split("/")[1]: {"w": stacked(n)}
                    for n in PRUNED if n.startswith("ffn/")},
        },
    }


def init(cfg, seed, dtype=jnp.bfloat16):
    """The program's parameter tree for ``cfg`` (a configuration file's
    dict), every leaf drawn on the device in one compiled call."""
    return _init(seed_words(seed), _frozen(cfg), dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _layer(words, layer, frozen, dtype):
    s = dict(frozen)
    return {n: _draw(words, n, layer, shp, s["std"], dtype)
            for n, shp in proj_shapes(s).items()}


def layer(cfg, seed, l, dtype=jnp.bfloat16):
    """Layer ``l``'s projections, {name: (in, out)}, as ``init`` draws
    them."""
    return _layer(seed_words(seed), l, _frozen(cfg), dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _table(words, name, frozen, dtype):
    s = dict(frozen)
    return _draw(words, name, 0, (s["V"], s["d"]), s["std"], dtype)


def table(cfg, seed, name, dtype=jnp.bfloat16):
    """The ``embed`` or ``head`` table, (vocab, d), as ``init`` draws it."""
    return _table(seed_words(seed), name, _frozen(cfg), dtype)


def live_weights(cfg, keep=None):
    """Weights of all layers' projections that survive pruning, and the
    live blocks per projection summed over layers ({name: blocks}, empty
    for a dense configuration)."""
    s = sizes(cfg)
    shapes = proj_shapes(s)
    if keep is None:
        return s["L"] * sum(k * n for k, n in shapes.values()), {}
    bk, bn = cfg["pruning"]["block"]
    blocks = {n: int(keep[n].sum()) for n in PRUNED}
    return sum(blocks.values()) * bk * bn, blocks


def _window_keys(P, window):
    """Keys attended, summed over the queries 0 .. P-1 of a prompt."""
    w = min(P, window)
    return w * (w + 1) // 2 + (P - w) * window


def prefill(cfg, P, keep=None):
    """(flops, bytes) of one B=1 prefill of a ``P``-token prompt that
    returns the last position's logits and the window's key/value cache."""
    s = sizes(cfg)
    live, _ = live_weights(cfg, keep)
    kv = s["KV"] * s["hd"]
    attn = 4 * s["H"] * s["hd"] * s["L"] * _window_keys(P, cfg["sliding_window"])
    flops = 2 * P * live + attn + 2 * s["d"] * s["V"]
    cached = min(P, cfg["sliding_window"])
    byts = ELT * (live + s["V"] * s["d"] + P * s["d"]
                  + 2 * s["L"] * cached * kv)
    return flops, byts


def decode_step(cfg, lengths, keep=None):
    """(flops, bytes) of one batched decode step over the active slots,
    whose caches hold ``lengths`` entries, the step's own included: each
    slot's new token attends the last ``min(length, sliding_window)``."""
    s = sizes(cfg)
    live, _ = live_weights(cfg, keep)
    kv = s["KV"] * s["hd"]
    n = len(lengths)
    keys = sum(min(int(t), cfg["sliding_window"]) for t in lengths)
    flops = (2 * n * (live + s["V"] * s["d"])
             + 4 * s["H"] * s["hd"] * s["L"] * keys)
    byts = ELT * (live + s["V"] * s["d"]
                  + 2 * s["L"] * keys * kv                 # read K and V
                  + n * (2 * s["L"] * kv                   # write the new
                         + s["d"]))                        # embed row
    return flops, byts


def packed_projections(cfg, keep, M):
    """(flops, bytes) of every packed projection of one forward at ``M``
    rows: the live blocks, each x row read and each y row written once."""
    s = sizes(cfg)
    bk, bn = cfg["pruning"]["block"]
    _, blocks = live_weights(cfg, keep)
    flops = 2 * M * sum(blocks.values()) * bk * bn
    io = s["L"] * sum(k + n for k, n in proj_shapes(s).values())
    return flops, ELT * (sum(blocks.values()) * bk * bn + M * io)
