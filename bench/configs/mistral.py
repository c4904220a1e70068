"""Plain float32 reference of the Mistral-7B-v0.1 architecture.

Source: huggingface.co/mistralai/Mistral-7B-v0.1 (``config.json``, and the
``MistralForCausalLM`` modelling code it names).  Per layer:

    h = x + Wo . attn(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    y = h + Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotary
embeddings in the rotate-half form (theta ``rope_theta``), grouped-query
attention (query head ``h`` reads key/value head ``h // (H / KV)``),
scaled by ``head_dim ** -0.5``, causal, and a sliding window: a query at
position q attends keys q - (sliding_window - 1) ... q.  Then a final
RMSNorm and an untied head.  Departures, each stated in the configuration
file: the depth cut, and ``rms_norm_eps`` (see ``rms_norm_eps`` there).

Everything here is ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one
layer at a time, with no cache and no batching, and imports nothing of the
program.  The weights are made again from the seed by the family module
``bench.families.mistral`` (never taken from the program), and a pruned
configuration's masks are made again by the rule its file states
(``block_keeps``: the rule in exact arithmetic first, then the few masks
a threshold computed in float32 keeps instead, where it rounds onto a
block's sum).

``precision="float8"`` is the control of the comparison that decides
``correct``: the same forward with both operands of every projection and
of the head rounded to float8_e4m3fn (a scale per row of the activations
and per column of the weights), the step below the served bfloat16.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import mistral as F

HI = jax.lax.Precision.HIGHEST
CHUNK = 512          # query rows per attention block
F8_MAX = 448.0       # largest finite float8_e4m3fn
MAX_MASKS = 8        # masks of a seed the comparison tries at most


def _frozen(cfg):
    s = F.sizes(cfg)
    s.update(window=cfg["sliding_window"], eps=cfg["rms_norm_eps"],
             theta=float(cfg["rope_theta"]))
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _block_sq(w, bk, bn):
    """Each (bk, bn) block's sum of squares in float32, over all layers'
    (layers, K, N) stack at once: the rule's sums as a float32 deployment
    computes them, rounded alike."""
    L, K, N = w.shape
    sq = jnp.square(w.astype(jnp.float32))
    return sq.reshape(L, K // bk, bk, N // bn, bn).sum(axis=(-3, -1))


def rule_masks(gs, rate):
    """The masks the rule allows over blocks' sums of squares ``gs``: a
    block is kept when its sum exceeds the ``rate`` quantile, the linear
    interpolation ``a (1 - w) + b w`` between the two sums ``a``, ``b`` at
    the position ``jnp.quantile`` places in float32.  The first is the
    rule in exact arithmetic.  The others are what the threshold keeps
    where float32 arithmetic rounds it onto a block's sum, in any order
    of rounding: each product rounded or fused into the sum."""
    gs = np.asarray(gs, np.float32)
    flat = np.sort(gs.reshape(-1))
    pos = np.float32(rate) * np.float32(flat.size - 1)
    low = int(np.floor(pos))
    a, b = flat[low], flat[min(low + 1, flat.size - 1)]
    hw = pos - np.float32(low)
    lw = np.float32(1) - hw
    pa, pb = np.float64(a) * np.float64(lw), np.float64(b) * np.float64(hw)
    f32 = lambda x: np.float64(np.float32(x))  # noqa: E731
    thresholds = [pa + pb, f32(f32(pa) + f32(pb)), f32(pa + f32(pb)),
                  f32(f32(pa) + pb), f32(pa + pb)]
    masks = {}
    for t in thresholds:
        m = gs.astype(np.float64) > t
        masks.setdefault(int(m.sum()), m)
    return list(masks.values())


def block_keeps(cfg, seed):
    """The pruned configuration's masks by the rule its file states, or
    [None] for a dense one: a list of {projection: (layers, K/bk, N/bn)
    bool}.  For each projection, over all layers together, a (bk, bn)
    block is kept when its sum of squares exceeds the ``rate`` quantile
    (linear interpolation) of all of them (``rule_masks``), the sums taken
    in float32 (``_block_sq``).  The first is the rule in exact arithmetic
    over those sums; then, fewest departures first, those whose
    projections keep what a threshold computed in float32 keeps, at most
    ``MAX_MASKS`` in all."""
    pr = cfg.get("pruning")
    if not pr:
        return [None]
    bk, bn = pr["block"]
    layers = [F.layer(cfg, seed, l) for l in range(cfg["num_hidden_layers"])]
    alt = [rule_masks(_block_sq(jnp.stack([ws[n] for ws in layers]), bk, bn),
                      pr["rate"]) for n in F.PRUNED]
    del layers
    picks = sorted(itertools.product(*(range(len(a)) for a in alt)),
                   key=lambda ix: (sum(i > 0 for i in ix), ix))
    return [{n: a[i] for n, a, i in zip(F.PRUNED, alt, ix)}
            for ix in picks[:MAX_MASKS]]


def block_keep(cfg, seed):
    """The rule's masks in exact arithmetic (``block_keeps``' first), or
    None for a dense configuration."""
    return block_keeps(cfg, seed)[0]


def _rmsnorm(x, eps):
    """RMSNorm; every scale is 1 (``bench.families.mistral``)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f8(a, axis):
    """``a`` rounded to float8_e4m3fn with one scale per slice along
    ``axis``, returned in float32."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _lin(x, w, prec):
    if prec == "float8":
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _attend(q, k, v, window):
    """Causal sliding-window GQA over one sequence, in blocks of CHUNK
    queries: q (T, H, hd), k/v (T, KV, hd) -> (T, H * hd)."""
    T, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    kp = jnp.concatenate([jnp.zeros((window, KV, hd), k.dtype), k])
    vp = jnp.concatenate([jnp.zeros((window, KV, hd), v.dtype), v])

    def block(c):
        q0 = c * CHUNK
        qc = jax.lax.dynamic_slice_in_dim(q, q0, CHUNK).reshape(
            CHUNK, KV, G, hd)
        kc = jax.lax.dynamic_slice_in_dim(kp, q0, CHUNK + window)
        vc = jax.lax.dynamic_slice_in_dim(vp, q0, CHUNK + window)
        qpos = q0 + jnp.arange(CHUNK)
        kpos = q0 - window + jnp.arange(CHUNK + window)
        s = jnp.einsum("qkgd,skd->kgqs", qc, kc, precision=HI) * hd ** -0.5
        ok = ((kpos[None, :] <= qpos[:, None])
              & (kpos[None, :] > qpos[:, None] - window)
              & (kpos[None, :] >= 0))
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, vc, precision=HI)
        return o.reshape(CHUNK, H * hd)

    return jax.lax.map(block, jnp.arange(T // CHUNK)).reshape(T, H * hd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_fwd(x, ws, keep, frozen, prec):
    s = dict(frozen)
    T = x.shape[0]
    pos = jnp.arange(T)

    def mat(n):
        m = ws[n].astype(jnp.float32)
        if keep is not None:
            bk = m.shape[0] // keep[n].shape[0]
            bn = m.shape[1] // keep[n].shape[1]
            m = m * jnp.repeat(jnp.repeat(keep[n], bk, 0), bn, 1)
        return m

    h = _rmsnorm(x, s["eps"])
    q = _lin(h, mat("attn/wq"), prec).reshape(T, s["H"], s["hd"])
    k = _lin(h, mat("attn/wk"), prec).reshape(T, s["KV"], s["hd"])
    v = _lin(h, mat("attn/wv"), prec).reshape(T, s["KV"], s["hd"])
    q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
    x = x + _lin(_attend(q, k, v, s["window"]), mat("attn/wo"), prec)
    h = _rmsnorm(x, s["eps"])
    f = jax.nn.silu(_lin(h, mat("ffn/gate"), prec)) * _lin(
        h, mat("ffn/up"), prec)
    return x + _lin(f, mat("ffn/down"), prec)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(x, head, first, n_out, frozen, prec):
    s = dict(frozen)
    xs = jax.lax.dynamic_slice_in_dim(x, first, n_out)
    xs = _rmsnorm(xs, s["eps"])
    return _lin(xs, head.astype(jnp.float32).T, prec)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


def logits(cfg, seed, tokens, first, n_out, keep=None,
           precision="float32"):
    """Next-token logits, (n_out, vocab) float32, at positions ``first``
    ... ``first + n_out - 1`` of the sequence ``tokens``.  Positions past
    the end of ``tokens`` read padding and are to be ignored.  The length
    is padded to a whole number of attention blocks; padding follows the
    sequence, so causality keeps it out of every real position."""
    frozen = _frozen(cfg)
    T = max(len(tokens), first + n_out)
    Tp = -(-T // CHUNK) * CHUNK
    ids = np.zeros((Tp,), np.int32)
    ids[:len(tokens)] = tokens
    x = _embed(F.table(cfg, seed, "embed"), jnp.asarray(ids))
    for l in range(cfg["num_hidden_layers"]):
        kl = None if keep is None else {n: jnp.asarray(keep[n][l])
                                        for n in F.PRUNED}
        x = _layer_fwd(x, F.layer(cfg, seed, l), kl, frozen, precision)
    return np.asarray(_head(x, F.table(cfg, seed, "head"), first, n_out,
                            frozen, precision))
