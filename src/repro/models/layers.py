"""Shared layer primitives: RMSNorm, rotary embeddings, (maskable) linear,
embedding table, cross-entropy.  All ops are plain jnp so GSPMD partitions them
under pjit; sparsity enters either as a multiplicative mask (training path) or
through the BCS Pallas kernel (serving path, see repro.kernels).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packed import DegradedLayer
from repro.models import module as M


# -- RMSNorm ----------------------------------------------------------------

def rmsnorm_init(key, dim, dtype=jnp.bfloat16):
    del key
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


# -- Rotary -----------------------------------------------------------------

def rotary_freqs(head_dim, theta=10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rotary(x, positions, theta=10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rotary_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- Linear (dense, masked-sparse, or packed BCS-sparse) ---------------------

def linear_init(key, in_dim, out_dim, dtype=jnp.bfloat16, bias=False):
    p = {"w": M.dense_init(key, (in_dim, out_dim), dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def _apply_act(y, act):
    if act == "silu":
        return jax.nn.silu(y)
    if act == "relu":
        return jnp.maximum(y, 0.0)
    return y


def linear(params, x, mask=None, act="none", name=None):
    """y = act(x @ W + b) through whichever executor applies.

    If the layer carries a packed BCS layout (``params["packed"]``, a
    ``core.packed.PackedLayout`` installed by
    ``repro.serve.compile.compile_model``) the Pallas block-sparse kernel
    executes it — one launch per degree bin, bias + activation fused into
    the epilogue, outputs gathered back to original column order when the
    layout was row-reordered; any ``mask`` is ignored there (it was baked
    in at pack time).  Otherwise a dense einsum runs, with an optional
    pruning ``mask`` broadcastable to w (XLA fuses the multiply into the
    matmul operand).

    ``name`` (the projection's, e.g. "wq") names the packed kernel's
    launches ``bsr_matmul_<name>`` in a profile; the dense path ignores it.

    A ``core.packed.DegradedLayer`` sentinel (left by
    ``serve.compile.degrade_invalid_layers`` where a layout failed
    validation) routes to the dense einsum: the retained ``w`` carries the
    pruning zeros, so the fallback is masked-dense — slower, never wrong.
    """
    packed = params.get("packed")
    if isinstance(packed, DegradedLayer):
        packed = None                    # validated-corrupt: masked-dense
    if packed is not None:
        from repro.kernels import ops  # late import: kernels -> core only
        return ops.sparse_linear(x, packed=packed, bias=params.get("b"),
                                 act=act, name=name)
    w = params["w"]
    if mask is not None:
        w = w * mask.astype(w.dtype)
    y = jnp.einsum("...i,io->...o", x, w)
    if "b" in params:
        y = y + params["b"]
    return _apply_act(y, act)


# -- Embedding ---------------------------------------------------------------

def embedding_init(key, vocab, dim, dtype=jnp.bfloat16):
    return {"table": M.embed_init(key, (vocab, dim), dtype)}


def embed(params, tokens):
    return params["table"][tokens]


def unembed(params, x):
    """Logits against the (separate) output head table: (..., d) -> (..., vocab)."""
    return jnp.einsum("...d,vd->...v", x, params["table"])


# -- Loss ---------------------------------------------------------------------

def cross_entropy(logits, labels, mask=None):
    """Mean token cross-entropy.  logits (..., vocab) maybe vocab-sharded —
    written with plain reductions so GSPMD inserts the vocab all-reduce."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# -- SwiGLU FFN ---------------------------------------------------------------

def ffn_init(key, d_model, d_ff, dtype=jnp.bfloat16):
    ks = M.split_keys(key, ["gate", "up", "down"])
    return {
        "gate": linear_init(ks["gate"], d_model, d_ff, dtype),
        "up": linear_init(ks["up"], d_model, d_ff, dtype),
        "down": linear_init(ks["down"], d_ff, d_model, dtype),
    }


def ffn(params, x, masks=None):
    """SwiGLU: silu is requested as the gate projection's epilogue so the
    packed-BCS path fuses it into the kernel's final store.  Same math, but
    under bf16 the fused path applies silu to the fp32 accumulator BEFORE
    the output rounding (one rounding instead of two) — packed and dense
    outputs may differ by ~1 bf16 ulp; in fp32 they agree tightly."""
    m = masks or {}
    g = linear(params["gate"], x, m.get("gate"), act="silu", name="gate")
    u = linear(params["up"], x, m.get("up"), name="up")
    return linear(params["down"], g * u, m.get("down"), name="down")


# -- Depthwise causal conv1d (mamba/hymba mixers; NOT pruned per paper §5.2.4) --

def conv1d_init(key, channels, width, dtype=jnp.bfloat16):
    return {"w": M.dense_init(key, (width, channels), dtype, scale=width ** -0.5)}


def causal_conv1d(params, x):
    """x: (batch, seq, channels) depthwise causal conv."""
    w = params["w"]                              # (width, C)
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):  # width is tiny (4); unrolled taps fuse into one op
        out = out + xp[:, i:i + x.shape[1], :] * w[i]
    return out


def conv1d_step(params, state, x_t):
    """Single decode step. state: (batch, width-1, C); x_t: (batch, C)."""
    w = params["w"]
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)   # (b, width, C)
    out = jnp.einsum("bwc,wc->bc", window, w)
    return window[:, 1:, :], out
