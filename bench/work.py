"""The work a step requires: operations and bytes, from the shapes alone.

Counted is what the model needs, whatever implements it: the weights that
survive pruning (from the masks the reference makes, ``block_keep``), the
key/value entries a query may attend, the head where the program needs
logits.  Padding, pruned blocks, masked-out keys and copies are not work.
Bytes are the least that has to cross HBM: each weight once per program,
each key/value entry read once, what is written once.  All in the served
dtype (bfloat16, 2 bytes).
"""
from __future__ import annotations

from bench import weights as W

ELT = 2                   # bytes of a bfloat16


def live_weights(cfg, keep=None):
    """Weights of all layers' projections that survive pruning, and the
    live blocks per projection summed over layers ({name: blocks}, empty
    for a dense configuration)."""
    s = W.sizes(cfg)
    shapes = W.proj_shapes(s)
    if keep is None:
        return s["L"] * sum(k * n for k, n in shapes.values()), {}
    bk, bn = cfg["pruning"]["block"]
    blocks = {n: int(keep[n].sum()) for n in W.PROJ}
    return sum(blocks.values()) * bk * bn, blocks


def _window_keys(P, window):
    """Keys attended, summed over the queries 0 .. P-1 of a prompt."""
    w = min(P, window)
    return w * (w + 1) // 2 + (P - w) * window


def prefill(cfg, P, keep=None):
    """(flops, bytes) of one B=1 prefill of a ``P``-token prompt that
    returns the last position's logits and the window's key/value cache."""
    s = W.sizes(cfg)
    live, _ = live_weights(cfg, keep)
    kv = s["KV"] * s["hd"]
    attn = 4 * s["H"] * s["hd"] * s["L"] * _window_keys(P, cfg["sliding_window"])
    flops = 2 * P * live + attn + 2 * s["d"] * s["V"]
    cached = min(P, cfg["sliding_window"])
    byts = ELT * (live + s["V"] * s["d"] + P * s["d"]
                  + 2 * s["L"] * cached * kv)
    return flops, byts


def decode_step(cfg, n_active, keep=None):
    """(flops, bytes) of one batched decode step over ``n_active`` slots,
    each holding a full window of keys and values (the cells' prompts are
    at least one window long)."""
    s = W.sizes(cfg)
    live, _ = live_weights(cfg, keep)
    kv = s["KV"] * s["hd"]
    keys = cfg["sliding_window"]
    flops = n_active * (2 * (live + s["V"] * s["d"])
                        + 4 * s["H"] * s["hd"] * s["L"] * keys)
    byts = ELT * (live + s["V"] * s["d"]
                  + n_active * (2 * s["L"] * keys * kv      # read K and V
                                + 2 * s["L"] * kv           # write the new
                                + s["d"]))                  # embed row
    return flops, byts


def packed_projections(cfg, keep, M):
    """(flops, bytes) of every packed projection of one forward at ``M``
    rows: the live blocks, each x row read and each y row written once."""
    s = W.sizes(cfg)
    bk, bn = cfg["pruning"]["block"]
    _, blocks = live_weights(cfg, keep)
    flops = 2 * M * sum(blocks.values()) * bk * bn
    io = s["L"] * sum(k + n for k, n in W.proj_shapes(s).values())
    return flops, ELT * (sum(blocks.values()) * bk * bn + M * io)


def least_seconds(flops, byts, peaks):
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
