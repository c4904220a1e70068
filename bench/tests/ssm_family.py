"""A stub family for the family contract's test: the program's ``ssm``
family (Mamba-2 mixers, no attention) at a smoke size, brought to the
harness as a family module alone (``bench/families/__init__.py``).

Its weights are ``bench.weights`` draws under its own leaf ids; its decode
work is fixed per slot (an SSM state), whatever the slot's length.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

from bench.weights import draw, seed_words
from bench.work import ELT

LEAF_IDS = {"embed": 0, "head": 1, "ssm/in_proj": 2, "ssm/conv": 3,
            "ssm/out_proj": 4}
PRUNED = ("ssm/in_proj", "ssm/out_proj")
_PRUNED_PATH = re.compile(r"ssm/(in_proj|out_proj)/w$")


def is_pruned(path):
    return bool(_PRUNED_PATH.search(path))


def sizes(cfg):
    d, e = cfg["hidden_size"], cfg["expand"]
    inner, n, hd = e * d, cfg["state_size"], cfg["head_dim"]
    return {"d": d, "inner": inner, "N": n, "H": inner // hd, "hd": hd,
            "conv": inner + 2 * n, "W": cfg["conv_kernel"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "std": cfg["initializer_range"]}


def proj_shapes(s):
    return {"ssm/in_proj": (s["d"], 2 * s["inner"] + 2 * s["N"] + s["H"]),
            "ssm/out_proj": (s["inner"], s["d"])}


def arch_config(cfg):
    from repro.configs.base import ArchConfig
    s = sizes(cfg)
    return ArchConfig(
        name=cfg["model_type"], family="ssm", n_layers=s["L"],
        d_model=s["d"], n_heads=0, n_kv_heads=0, d_ff=0, vocab=s["V"],
        ssm_state=s["N"], ssm_headdim=s["hd"], ssm_expand=cfg["expand"],
        remat="none")


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(words, frozen, dtype):
    s = dict(frozen)
    L, d, H, std = s["L"], s["d"], s["H"], s["std"]
    shapes = proj_shapes(s)

    def one(name, l, shape, scale):
        return draw(words, LEAF_IDS[name], l, shape, scale, dtype)

    def stacked(name, shape, scale):
        return jnp.stack([one(name, l, shape, scale) for l in range(L)])

    per_layer = lambda a: jnp.broadcast_to(a, (L,) + a.shape)  # noqa: E731
    return {
        "embed": {"table": one("embed", 0, (s["V"], d), std)},
        "head": {"table": one("head", 0, (s["V"], d), std)},
        "norm_f": {"scale": jnp.ones((d,), dtype)},
        "layers": {
            "ln1": {"scale": jnp.ones((L, d), dtype)},
            "ssm": {
                "in_proj": {"w": stacked("ssm/in_proj",
                                         shapes["ssm/in_proj"], std)},
                "conv": {"w": stacked("ssm/conv", (s["W"], s["conv"]),
                                      s["W"] ** -0.5)},
                "A_log": per_layer(jnp.log(jnp.linspace(1.0, 16.0, H))),
                "D": jnp.ones((L, H), jnp.float32),
                "dt_bias": jnp.zeros((L, H), jnp.float32),
                "norm": {"scale": jnp.ones((L, s["inner"]), dtype)},
                "out_proj": {"w": stacked("ssm/out_proj",
                                          shapes["ssm/out_proj"], std)},
            },
        },
    }


def init(cfg, seed, dtype=jnp.bfloat16):
    return _init(seed_words(seed), tuple(sorted(sizes(cfg).items())), dtype)


def _live(cfg, keep):
    s = sizes(cfg)
    if keep is None:
        return s["L"] * sum(k * n for k, n in proj_shapes(s).values())
    bk, bn = cfg["pruning"]["block"]
    return sum(int(keep[n].sum()) for n in PRUNED) * bk * bn


def prefill(cfg, P, keep=None):
    s = sizes(cfg)
    live = _live(cfg, keep)
    return (2 * P * live + 2 * s["d"] * s["V"],
            ELT * (live + s["V"] * s["d"] + P * s["d"]))


def decode_step(cfg, lengths, keep=None):
    """One step over ``len(lengths)`` slots, each reading and writing its
    fixed state (float32 ``h``, served-dtype conv tail), whatever its
    length."""
    s = sizes(cfg)
    live, n = _live(cfg, keep), len(lengths)
    state = s["L"] * (4 * s["H"] * s["hd"] * s["N"]
                      + ELT * (s["W"] - 1) * s["conv"])
    return (2 * n * (live + s["V"] * s["d"]),
            ELT * (live + s["V"] * s["d"]) + 2 * n * state)


def packed_projections(cfg, keep, M):
    s = sizes(cfg)
    io = s["L"] * sum(k + n for k, n in proj_shapes(s).values())
    live = _live(cfg, keep)
    return 2 * M * live, ELT * (live + M * io)
