"""GQA attention: flash-style KV-chunked online softmax (training/prefill),
direct cached-decode step, sliding window, and cross-attention.

Sharding modes (set per arch in configs, see DESIGN.md §5):
  - "heads": q heads sharded over the model axis (kv replicated when
    n_kv % tp != 0) — the default TP layout.
  - "seq":   query sequence sharded over the model axis (context parallel) —
    used when n_heads % tp != 0 (phi3: 40H, hymba: 25H).
Decode KV caches are sequence-sharded over the model axis universally.

Sparse serving: all four projections (wq/wk/wv/wo) dispatch through
``layers.linear``, so layers compiled by ``repro.serve.compile`` carry packed
BCS weights and execute on the Pallas block-sparse kernel transparently; the
training-time pruning masks are baked into the packed layout and dropped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import module as M
from repro.models import layers as L

NEG_INF = -1e30


def _proj(params, name, x, masks):
    """One attention projection.  ``layers.linear`` owns the dispatch:
    packed BCS layers route to the sparse kernel (and ignore the mask —
    it is baked into the layout), whose launches take the projection's
    ``name``; dense layers apply it."""
    return L.linear(params[name], x, masks.get(name), name=name)


def attn_init(key, d_model, n_heads, n_kv, head_dim, dtype=jnp.bfloat16,
              qkv_bias=False):
    ks = M.split_keys(key, ["wq", "wk", "wv", "wo"])
    return {
        "wq": L.linear_init(ks["wq"], d_model, n_heads * head_dim, dtype, bias=qkv_bias),
        "wk": L.linear_init(ks["wk"], d_model, n_kv * head_dim, dtype, bias=qkv_bias),
        "wv": L.linear_init(ks["wv"], d_model, n_kv * head_dim, dtype, bias=qkv_bias),
        "wo": L.linear_init(ks["wo"], n_heads * head_dim, d_model, dtype),
    }


def _grouped(q, n_kv):
    """(B,S,H,hd) -> (B,S,KV,G,hd) — decode path only (heads unsharded
    there; a head-sharded dim cannot be reshaped into (KV, G) under GSPMD
    without full rematerialization, so the training path stays in H-form)."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


def _expand_kv(k, n_heads):
    """(B,S,KV,hd) -> (B,S,H,hd) by repeating each KV head G times.  Keeps
    every attention tensor in H-form so the model-axis head sharding is
    preserved end to end (perf iteration 1, EXPERIMENTS.md §Perf)."""
    B, S, KV, hd = k.shape
    G = n_heads // KV
    if G == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, G, hd))
    return k.reshape(B, S, n_heads, hd)


def attend(q, k, v, q_pos, k_pos, causal=True, window=0, kv_chunk=1024):
    """Flash-style online-softmax attention, scanned over KV chunks.

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd) (KV already expanded);
    positions int32.  Returns (B, Sq, H, hd).  Memory is bounded by one
    (B, H, Sq, kv_chunk) score tile instead of the full Sq×Sk matrix.
    A single chunk (kv_chunk >= Sk) skips the scan entirely — cheaper for
    GSPMD (no carry resharding), used for the 4k training shapes."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5
    kv_chunk = min(kv_chunk, Sk)
    n_chunks = Sk // kv_chunk
    assert Sk % kv_chunk == 0, (Sk, kv_chunk)

    qf = q.astype(jnp.float32) * scale

    if n_chunks == 1:
        s = jnp.einsum("bqhe,bshe->bhqs", qf, k.astype(jnp.float32))
        mask = jnp.ones((Sq, Sk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqs,bshe->bqhe", p, v.astype(jnp.float32))
        return out.astype(q.dtype)

    kc = k.reshape(B, n_chunks, kv_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, kv_chunk)

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd), jnp.float32)

    def body(carry, chunk):
        m, l, acc = carry
        kj, vj, pj = chunk
        s = jnp.einsum("bqhe,bshe->bhqs", qf, kj.astype(jnp.float32))
        mask = jnp.ones((Sq, kv_chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= pj[None, :]
        if window > 0:
            mask &= pj[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqs,bshe->bhqe", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)      # (B,Sq,H,hd)


def attend_cached(q, k_cache, v_cache, q_pos, k_pos, window=0):
    """Single-token decode over an S-sharded KV cache — direct softmax; GSPMD
    emits the cross-shard max/sum all-reduces for the sharded Sk dim.

    q: (B, 1, KV, G, hd); caches: (B, Sk, KV, hd).  Positions come either
    batch-shared (``q_pos (Q,)``, ``k_pos (Sk,)`` — the single-sequence
    ``generate`` path) or per-slot ragged (``q_pos (B, Q)``, ``k_pos
    (B, Sk)`` — the continuous-batching engine, where every slot holds a
    different history length; never-written entries carry
    ``serve.kvcache.INVALID_POS`` so they fail the causal mask).  The
    masked-softmax math is identical elementwise, so a ragged batch stays
    bit-identical per slot to the shared-position B=1 decode."""
    hd = q.shape[-1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32) * hd ** -0.5,
                   k_cache.astype(jnp.float32))
    if k_pos.ndim == 1:
        mask = k_pos[None, :] <= q_pos[:, None]                  # (Q, Sk)
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
    else:
        mask = k_pos[:, None, :] <= q_pos[:, :, None]            # (B, Q, Sk)
        if window > 0:
            mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def mha(params, x, positions, n_heads, n_kv, head_dim, *, causal=True,
        window=0, rope_theta=10000.0, masks=None, dist=None, shard="heads",
        memory=None, kv_chunk=1024):
    """Full-sequence attention (training / prefill).  If ``memory`` is given,
    performs cross-attention against it (no causal mask, no rope on kv)."""
    m = masks or {}
    B, S, _ = x.shape
    q = _proj(params, "wq", x, m).reshape(B, S, n_heads, head_dim)
    src = memory if memory is not None else x
    Sk = src.shape[1]
    k = _proj(params, "wk", src, m).reshape(B, Sk, n_kv, head_dim)
    v = _proj(params, "wv", src, m).reshape(B, Sk, n_kv, head_dim)

    if memory is None:
        q = L.apply_rotary(q, positions, rope_theta)
        k = L.apply_rotary(k, positions, rope_theta)
        k_pos = positions
        causal_ = causal
    else:
        k_pos = jnp.arange(Sk, dtype=jnp.int32)
        causal_ = False

    if dist is not None:
        # gather/shard K,V in compact KV-form BEFORE head expansion
        k = dist.shard_attn_kv(k, shard, n_kv)
        v = dist.shard_attn_kv(v, shard, n_kv)
    kf = _expand_kv(k, n_heads)
    vf = _expand_kv(v, n_heads)
    if dist is not None:
        q = dist.shard_attn_q(q, shard)
        if dist.mode != "fsdp" and shard == "heads":
            kf = dist.shard_attn_q(kf, shard)  # H-form TP head sharding
            vf = dist.shard_attn_q(vf, shard)

    out = attend(q, kf, vf, positions, k_pos,
                 causal=causal_, window=window, kv_chunk=kv_chunk)
    out = out.reshape(B, S, n_heads * head_dim)
    return _proj(params, "wo", out, m), (k, v)


def mha_decode(params, x, cache, pos, n_heads, n_kv, head_dim, *,
               window=0, rope_theta=10000.0, masks=None, dist=None):
    """One-token decode.  cache = dict(k=(B,S,KV,hd), v=..., ) already holding
    ``S`` tokens; the new token attends over the cache plus itself written in.
    Returns (out, cache) — cache is rolled (drop-oldest) to stay fixed-shape.
    """
    m = masks or {}
    B, _, _ = x.shape
    q = _proj(params, "wq", x, m).reshape(B, 1, n_heads, head_dim)
    k = _proj(params, "wk", x, m).reshape(B, 1, n_kv, head_dim)
    v = _proj(params, "wv", x, m).reshape(B, 1, n_kv, head_dim)
    q = L.apply_rotary(q, pos, rope_theta)
    k = L.apply_rotary(k, pos, rope_theta)

    S = cache["k"].shape[1]
    # Fixed-shape ring update: overwrite slot pos % S (positions track validity).
    slot = (pos[0, 0] % S).astype(jnp.int32)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    k_pos = jax.lax.dynamic_update_slice(cache["pos"], pos[0], (slot,))
    if dist is not None:
        k_cache = dist.shard_cache(k_cache)
        v_cache = dist.shard_cache(v_cache)

    out = attend_cached(_grouped(q, n_kv), k_cache, v_cache, pos[:, 0:1][0],
                        k_pos, window=window)
    out = out.reshape(B, 1, n_heads * head_dim)
    y = _proj(params, "wo", out, m)
    return y, {"k": k_cache, "v": v_cache, "pos": k_pos}


def mha_decode_ragged(params, x, cache, pos, cap, n_heads, n_kv, head_dim, *,
                      window=0, rope_theta=10000.0, masks=None, dist=None):
    """One-token decode across RAGGED slot histories (continuous batching).

    Unlike ``mha_decode`` — which assumes every batch row sits at the same
    position — each slot ``b`` carries its own position ``pos[b]`` and its
    own ring capacity ``cap[b]`` (the request's effective prefill length,
    see ``serve.kvcache``).  cache: ``k/v (B, S, KV, hd)`` slot arrays and
    a per-entry position map ``pos (B, S)``; the new token writes ring
    index ``pos[b] % cap[b]`` of row ``b`` — the same fixed-shape
    drop-oldest rule ``mha_decode`` applies, so each slot's outputs are
    bit-identical to a B=1 ``mha_decode`` sequence over the same request.
    Entries beyond a slot's capacity keep ``INVALID_POS`` and never pass
    the causal mask.  Returns (out, cache).
    """
    m = masks or {}
    B, _, _ = x.shape
    q = _proj(params, "wq", x, m).reshape(B, 1, n_heads, head_dim)
    k = _proj(params, "wk", x, m).reshape(B, 1, n_kv, head_dim)
    v = _proj(params, "wv", x, m).reshape(B, 1, n_kv, head_dim)
    q = L.apply_rotary(q, pos, rope_theta)
    k = L.apply_rotary(k, pos, rope_theta)

    slots = (pos[:, 0] % jnp.maximum(cap, 1)).astype(jnp.int32)     # (B,)
    upd = jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s, 0, 0)))
    k_cache = upd(cache["k"], k.astype(cache["k"].dtype), slots)
    v_cache = upd(cache["v"], v.astype(cache["v"].dtype), slots)
    k_pos = jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s,)))(
        cache["pos"], pos[:, :1], slots)
    if dist is not None:
        k_cache = dist.shard_cache(k_cache)
        v_cache = dist.shard_cache(v_cache)

    out = attend_cached(_grouped(q, n_kv), k_cache, v_cache, pos, k_pos,
                        window=window)
    out = out.reshape(B, 1, n_heads * head_dim)
    y = _proj(params, "wo", out, m)
    return y, {"k": k_cache, "v": v_cache, "pos": k_pos}
