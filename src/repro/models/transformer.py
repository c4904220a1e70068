"""Model assembly for all assigned architecture families.

Layer stacks are `lax.scan`-ed over vmapped-stacked per-layer params to keep
the HLO size O(1) in depth — essential for the 512-device dry-run compiles.
Heterogeneous stacks (vlm cross-attn every k layers) scan over homogeneous
*groups*.  Decode paths thread per-layer caches through the same scans.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.packed import layer_views
from repro.models import module as M
from repro.models import layers as L
from repro.models import attention as A
from repro.models import ssm as S
from repro.models.moe import moe, moe_init


# ---------------------------------------------------------------------------
# Per-layer init/apply by family
# ---------------------------------------------------------------------------

def maybe_scan(body, carry, xs, unroll=False):
    """lax.scan, or an unrolled Python loop when ``unroll`` (the dry-run's
    cost-probe mode: XLA cost analysis counts while-loop bodies once).
    The scan hands packed layouts to ``body`` as views of their layer
    stacks (``core.packed.layer_views``): the kernels read each layer's
    weights in place, where a sliced layout would be a copy per layer."""
    if not unroll:
        xs, view = layer_views(xs)
        return jax.lax.scan(lambda c, x: body(c, view(x)), carry, xs)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _layer_init(key, cfg: ArchConfig, kind: str):
    ks = M.split_keys(key, ["a", "b", "c", "d", "e", "f"])
    hd = cfg.hd
    if kind == "dense":
        return {"ln1": L.rmsnorm_init(None, cfg.d_model),
                "attn": A.attn_init(ks["a"], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, hd),
                "ln2": L.rmsnorm_init(None, cfg.d_model),
                "ffn": L.ffn_init(ks["b"], cfg.d_model, cfg.d_ff)}
    if kind == "moe":
        return {"ln1": L.rmsnorm_init(None, cfg.d_model),
                "attn": A.attn_init(ks["a"], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, hd),
                "ln2": L.rmsnorm_init(None, cfg.d_model),
                "moe": moe_init(ks["b"], cfg.d_model, cfg.d_ff, cfg.n_experts)}
    if kind == "ssm":
        return {"ln1": L.rmsnorm_init(None, cfg.d_model),
                "ssm": S.ssm_init(ks["a"], cfg.d_model, cfg.ssm_state,
                                  headdim=cfg.ssm_headdim,
                                  expand=cfg.ssm_expand)}
    if kind == "hybrid":  # hymba: parallel attn + ssm heads, then FFN
        return {"ln1": L.rmsnorm_init(None, cfg.d_model),
                "attn": A.attn_init(ks["a"], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, hd),
                "ssm": S.ssm_init(ks["b"], cfg.d_model, cfg.ssm_state,
                                  headdim=cfg.ssm_headdim,
                                  expand=cfg.ssm_expand),
                "ln2": L.rmsnorm_init(None, cfg.d_model),
                "ffn": L.ffn_init(ks["c"], cfg.d_model, cfg.d_ff)}
    if kind == "cross":  # vlm cross-attn layer (own ffn, llama-vision style)
        return {"ln1": L.rmsnorm_init(None, cfg.d_model),
                "xattn": A.attn_init(ks["a"], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, hd),
                "gate": jnp.zeros((1,), jnp.float32),
                "ln2": L.rmsnorm_init(None, cfg.d_model),
                "ffn": L.ffn_init(ks["b"], cfg.d_model, cfg.d_ff)}
    if kind == "xdec":  # enc-dec decoder layer: self + cross + ffn
        return {"ln1": L.rmsnorm_init(None, cfg.d_model),
                "attn": A.attn_init(ks["a"], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, hd),
                "lnx": L.rmsnorm_init(None, cfg.d_model),
                "xattn": A.attn_init(ks["b"], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, hd),
                "ln2": L.rmsnorm_init(None, cfg.d_model),
                "ffn": L.ffn_init(ks["c"], cfg.d_model, cfg.d_ff)}
    raise ValueError(kind)


def _stack_init(key, cfg, kind, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _layer_init(k, cfg, kind))(keys)


def _layer_fwd(p, x, positions, cfg: ArchConfig, kind, *, dist=None,
               memory=None, collect_cache=False):
    """Returns (x, aux, cache_kv) for one layer."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if kind in ("dense", "moe", "hybrid", "xdec"):
        h = L.rmsnorm(p["ln1"], x)
        att, kv = A.mha(p["attn"], h, positions, cfg.n_heads, cfg.n_kv_heads,
                        cfg.hd, window=cfg.sliding_window,
                        rope_theta=cfg.rope_theta, dist=dist,
                        shard=cfg.attn_shard, kv_chunk=cfg.kv_chunk)
        if kind == "hybrid":
            sm, _ = S.ssm(p["ssm"], h, dist=dist)
            att = (att + sm) * 0.5
        x = x + att
        if collect_cache:
            cache = {"k": kv[0], "v": kv[1]}
        if kind == "xdec":
            h = L.rmsnorm(p["lnx"], x)
            xa, xkv = A.mha(p["xattn"], h, positions, cfg.n_heads,
                            cfg.n_kv_heads, cfg.hd, dist=dist,
                            shard=cfg.attn_shard, memory=memory)
            x = x + xa
            if collect_cache:
                cache.update({"xk": xkv[0], "xv": xkv[1]})
        h = L.rmsnorm(p["ln2"], x)
        if kind == "moe":
            f, aux = moe(p["moe"], h, top_k=cfg.top_k, group=cfg.moe_group,
                         dist=dist)
        else:
            f = L.ffn(p["ffn"], h)
        x = x + f
    elif kind == "ssm":
        h = L.rmsnorm(p["ln1"], x)
        sm, _ = S.ssm(p["ssm"], h, dist=dist)
        x = x + sm
    elif kind == "cross":
        h = L.rmsnorm(p["ln1"], x)
        xa, _ = A.mha(p["xattn"], h, positions, cfg.n_heads, cfg.n_kv_heads,
                      cfg.hd, dist=dist, shard=cfg.attn_shard, memory=memory)
        x = x + jnp.tanh(p["gate"]).astype(x.dtype) * xa
        h = L.rmsnorm(p["ln2"], x)
        x = x + L.ffn(p["ffn"], h)
    else:
        raise ValueError(kind)
    if dist is not None:
        x = dist.shard_residual(x)
    return x, aux, cache


def _scan_stack(stacked, x, positions, cfg, kind, *, dist=None, memory=None,
                remat=True):
    def body(carry, lp):
        h, aux = carry
        h, a, _ = _layer_fwd(lp, h, positions, cfg, kind, dist=dist,
                             memory=memory)
        return (h, aux + a), None

    if remat and cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = maybe_scan(body, (x, jnp.zeros((), jnp.float32)), stacked,
                             cfg.unroll_layers)
    return x, aux


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ArchConfig):
    ks = M.split_keys(key, ["embed", "head", "stack", "enc", "cross"])
    params = {
        "embed": L.embedding_init(ks["embed"], cfg.vocab, cfg.d_model),
        "head": L.embedding_init(ks["head"], cfg.vocab, cfg.d_model),
        "norm_f": L.rmsnorm_init(None, cfg.d_model),
    }
    fam = cfg.family
    if fam in ("dense", "moe", "ssm", "hybrid"):
        params["layers"] = _stack_init(ks["stack"], cfg, fam, cfg.n_layers)
    elif fam == "encdec":
        params["enc"] = _stack_init(ks["enc"], cfg, "dense", cfg.n_enc_layers)
        params["dec"] = _stack_init(ks["stack"], cfg, "xdec", cfg.n_layers)
        params["norm_e"] = L.rmsnorm_init(None, cfg.d_model)
    elif fam == "vlm":
        k = cfg.cross_attn_interval
        n_groups = cfg.n_layers // k
        keys = jax.random.split(ks["stack"], n_groups)

        def group_init(gk):
            g1, g2 = jax.random.split(gk)
            return {"selfs": _stack_init(g1, cfg, "dense", k - 1),
                    "cross": _layer_init(g2, cfg, "cross")}
        params["groups"] = jax.vmap(group_init)(keys)
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg: ArchConfig, tokens, *, frontend=None, dist=None,
            positions=None):
    """tokens (B,S) -> logits (B,S,vocab).  ``frontend`` is the precomputed
    audio-frame / image-patch embedding stand-in (B, T, d_model) for
    encdec/vlm archs (modality frontends are stubs per the assignment)."""
    B, Sq = tokens.shape
    if positions is None:
        positions = jnp.arange(Sq, dtype=jnp.int32)
    x = L.embed(params["embed"], tokens)
    if dist is not None:
        x = dist.shard_activations(x)
    fam = cfg.family
    if fam in ("dense", "moe", "ssm", "hybrid"):
        x, aux = _scan_stack(params["layers"], x, positions, cfg, fam,
                             dist=dist)
    elif fam == "encdec":
        enc_pos = jnp.arange(frontend.shape[1], dtype=jnp.int32)
        # bidirectional encoder over the frontend embeddings
        def enc_body(carry, lp):
            h, = carry
            hn = L.rmsnorm(lp["ln1"], h)
            att, _ = A.mha(lp["attn"], hn, enc_pos, cfg.n_heads,
                           cfg.n_kv_heads, cfg.hd, causal=False, dist=dist,
                           shard=cfg.attn_shard)
            h = h + att
            h = h + L.ffn(lp["ffn"], L.rmsnorm(lp["ln2"], h))
            if dist is not None:
                h = dist.shard_residual(h)
            return (h,), None
        if cfg.remat == "full":
            enc_body = jax.checkpoint(enc_body, prevent_cse=False)
        (memory,), _ = maybe_scan(enc_body, (frontend.astype(x.dtype),),
                                  params["enc"], cfg.unroll_layers)
        memory = L.rmsnorm(params["norm_e"], memory)
        x, aux = _scan_stack(params["dec"], x, positions, cfg, "xdec",
                             dist=dist, memory=memory)
    elif fam == "vlm":
        memory = frontend.astype(x.dtype)

        def group_body(carry, gp):
            h, aux = carry
            h, a1 = _scan_stack(gp["selfs"], h, positions, cfg, "dense",
                                dist=dist, remat=False)
            h, a2, _ = _layer_fwd(gp["cross"], h, positions, cfg, "cross",
                                  dist=dist, memory=memory)
            return (h, aux + a1 + a2), None
        if cfg.remat == "full":
            group_body = jax.checkpoint(group_body, prevent_cse=False)
        (x, aux), _ = maybe_scan(
            group_body, (x, jnp.zeros((), jnp.float32)), params["groups"],
            cfg.unroll_layers)
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["norm_f"], x)
    logits = L.unembed(params["head"], x)
    if dist is not None:
        logits = dist.shard_logits(logits)
    return logits, aux


# ---------------------------------------------------------------------------
# Decode (single new token over a seq_len cache)
# ---------------------------------------------------------------------------

def init_cache(params, cfg: ArchConfig, batch, seq, dtype=jnp.bfloat16):
    """Fixed-shape per-layer caches, stacked on the layer dim for scanning."""
    hd = cfg.hd
    fam = cfg.family

    eff = min(seq, cfg.sliding_window) if cfg.sliding_window else seq

    def kv(n):
        return {"k": jnp.zeros((n, batch, eff, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((n, batch, eff, cfg.n_kv_heads, hd), dtype),
                "pos": jnp.broadcast_to(
                    jnp.arange(eff, dtype=jnp.int32), (n, eff))}

    def ssm_state(n):
        one = S.ssm_state_init(
            jax.tree_util.tree_map(lambda a: a[0], params_layers_ssm), batch,
            cfg.d_model, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    if fam == "dense":
        return {"kv": kv(cfg.n_layers)}
    if fam == "moe":
        return {"kv": kv(cfg.n_layers)}
    if fam == "ssm":
        params_layers_ssm = params["layers"]["ssm"]
        return {"ssm": ssm_state(cfg.n_layers)}
    if fam == "hybrid":
        params_layers_ssm = params["layers"]["ssm"]
        return {"kv": kv(cfg.n_layers), "ssm": ssm_state(cfg.n_layers)}
    if fam == "encdec":
        enc_len = cfg.n_frontend_tokens
        return {"kv": kv(cfg.n_layers),
                "xk": jnp.zeros((cfg.n_layers, batch, enc_len,
                                 cfg.n_kv_heads, hd), dtype),
                "xv": jnp.zeros((cfg.n_layers, batch, enc_len,
                                 cfg.n_kv_heads, hd), dtype)}
    if fam == "vlm":
        k = cfg.cross_attn_interval
        n_groups = cfg.n_layers // k
        img = cfg.n_frontend_tokens
        return {"kv_self": kv(n_groups * (k - 1)),
                "xk": jnp.zeros((n_groups, batch, img, cfg.n_kv_heads, hd),
                                dtype),
                "xv": jnp.zeros((n_groups, batch, img, cfg.n_kv_heads, hd),
                                dtype)}
    raise ValueError(fam)


def decode_step(params, cfg: ArchConfig, token, cache, pos, *, dist=None):
    """token (B,1) int32; pos (B,1) int32 current position; returns
    (logits (B,1,V), new cache)."""
    fam = cfg.family
    x = L.embed(params["embed"], token)
    if dist is not None:
        x = dist.shard_activations(x)

    def attn_dec(lp, h, c, window=0):
        hn = L.rmsnorm(lp["ln1"], h)
        att, c = A.mha_decode(lp["attn"], hn, c, pos, cfg.n_heads,
                              cfg.n_kv_heads, cfg.hd, window=window,
                              rope_theta=cfg.rope_theta, dist=dist)
        return att, c

    if fam in ("dense", "moe"):
        def body(h, xs):
            lp, c = xs
            att, c = attn_dec(lp, h, c, cfg.sliding_window)
            h = h + att
            hn = L.rmsnorm(lp["ln2"], h)
            if fam == "moe":
                f, _ = moe(lp["moe"], hn, top_k=cfg.top_k,
                           group=cfg.moe_group, dist=dist)
            else:
                f = L.ffn(lp["ffn"], hn)
            return h + f, c
        x, kv = maybe_scan(body, x, (params["layers"], cache["kv"]),
                           cfg.unroll_layers)
        cache = {"kv": kv}
    elif fam == "ssm":
        def body(h, xs):
            lp, st = xs
            out, st = S.ssm_decode(lp["ssm"], L.rmsnorm(lp["ln1"], h), st,
                                   dist=dist)
            return h + out, st
        x, st = maybe_scan(body, x, (params["layers"], cache["ssm"]),
                           cfg.unroll_layers)
        cache = {"ssm": st}
    elif fam == "hybrid":
        def body(h, xs):
            lp, c, st = xs
            hn = L.rmsnorm(lp["ln1"], h)
            att, c = A.mha_decode(lp["attn"], hn, c, pos, cfg.n_heads,
                                  cfg.n_kv_heads, cfg.hd,
                                  window=cfg.sliding_window,
                                  rope_theta=cfg.rope_theta, dist=dist)
            sm, st = S.ssm_decode(lp["ssm"], hn, st, dist=dist)
            h = h + (att + sm) * 0.5
            h = h + L.ffn(lp["ffn"], L.rmsnorm(lp["ln2"], h))
            return h, (c, st)
        x, (kv, st) = maybe_scan(
            body, x, (params["layers"], cache["kv"], cache["ssm"]),
            cfg.unroll_layers)
        cache = {"kv": kv, "ssm": st}
    elif fam == "encdec":
        def body(h, xs):
            lp, c, xk, xv = xs
            att, c = attn_dec(lp, h, c)
            h = h + att
            hn = L.rmsnorm(lp["lnx"], h)
            B = hn.shape[0]
            q = L.linear(lp["xattn"]["wq"], hn).reshape(
                B, 1, cfg.n_heads, cfg.hd)
            enc_pos = jnp.arange(xk.shape[1], dtype=jnp.int32)
            o = A.attend_cached(A._grouped(q, cfg.n_kv_heads), xk, xv,
                                jnp.full((1,), 1 << 30, jnp.int32), enc_pos)
            h = h + L.linear(lp["xattn"]["wo"],
                             o.reshape(B, 1, cfg.n_heads * cfg.hd))
            h = h + L.ffn(lp["ffn"], L.rmsnorm(lp["ln2"], h))
            return h, c
        x, kv = maybe_scan(
            body, x, (params["dec"], cache["kv"], cache["xk"],
                      cache["xv"]), cfg.unroll_layers)
        cache = dict(cache, kv=kv)
    elif fam == "vlm":
        k = cfg.cross_attn_interval
        n_groups = cfg.n_layers // k
        selfs = params["groups"]["selfs"]   # already (n_groups, k-1, ...)
        kv_self = jax.tree_util.tree_map(
            lambda a: a.reshape(n_groups, k - 1, *a.shape[1:]),
            cache["kv_self"])

        def group_body(h, xs):
            gp_selfs, gp_cross, c_self, xk, xv = xs

            def self_body(hh, ys):
                lp, c = ys
                att, c = attn_dec(lp, hh, c)
                hh = hh + att
                hh = hh + L.ffn(lp["ffn"], L.rmsnorm(lp["ln2"], hh))
                return hh, c
            h, c_self = maybe_scan(self_body, h, (gp_selfs, c_self),
                                   cfg.unroll_layers)
            hn = L.rmsnorm(gp_cross["ln1"], h)
            B = hn.shape[0]
            q = L.linear(gp_cross["xattn"]["wq"], hn).reshape(
                B, 1, cfg.n_heads, cfg.hd)
            img_pos = jnp.arange(xk.shape[1], dtype=jnp.int32)
            o = A.attend_cached(A._grouped(q, cfg.n_kv_heads), xk, xv,
                                jnp.full((1,), 1 << 30, jnp.int32), img_pos)
            h = h + jnp.tanh(gp_cross["gate"]).astype(h.dtype) * L.linear(
                gp_cross["xattn"]["wo"],
                o.reshape(B, 1, cfg.n_heads * cfg.hd))
            h = h + L.ffn(gp_cross["ffn"], L.rmsnorm(gp_cross["ln2"], h))
            return h, c_self
        x, kv_self = maybe_scan(
            group_body, x,
            (selfs, params["groups"]["cross"], kv_self, cache["xk"],
             cache["xv"]), cfg.unroll_layers)
        cache = dict(cache, kv_self=jax.tree_util.tree_map(
            lambda a: a.reshape(n_groups * (k - 1), *a.shape[2:]), kv_self))
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["norm_f"], x)
    logits = L.unembed(params["head"], x)
    if dist is not None:
        logits = dist.shard_logits(logits)
    return logits, cache


def decode_step_ragged(params, cfg: ArchConfig, token, cache, pos, cap, *,
                       dist=None):
    """Continuous-batching decode step: ONE forward over every cache slot.

    token (B, 1) int32 per-slot current tokens; pos (B, 1) int32 per-slot
    positions; cap (B,) int32 per-slot ring capacities (free slots run as
    pos=0/cap=1 padding work whose outputs the engine discards).  cache is
    the ``serve.kvcache`` slot cache.  Returns (logits (B, 1, V), cache).

    Per slot the math is bit-identical to ``decode_step`` at B=1: the
    ragged attention masks by per-entry positions, every other op is
    row-wise, and MoE dispatches with ``group=1`` so batch occupancy can
    never change a token's expert-capacity outcome (at B=1 the group
    clamp makes ``group`` irrelevant, so this matches ``generate``
    exactly).  One compiled executable serves the engine's whole lifetime
    — admission/eviction only rewrite cache rows, never shapes.
    """
    fam = cfg.family
    x = L.embed(params["embed"], token)
    if dist is not None:
        x = dist.shard_activations(x)

    def attn_dec(lp, hn, c):
        return A.mha_decode_ragged(lp["attn"], hn, c, pos, cap, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.hd,
                                   window=cfg.sliding_window,
                                   rope_theta=cfg.rope_theta, dist=dist)

    if fam in ("dense", "moe"):
        def body(h, xs):
            lp, c = xs
            att, c = attn_dec(lp, L.rmsnorm(lp["ln1"], h), c)
            h = h + att
            hn = L.rmsnorm(lp["ln2"], h)
            if fam == "moe":
                f, _ = moe(lp["moe"], hn, top_k=cfg.top_k, group=1,
                           dist=dist)
            else:
                f = L.ffn(lp["ffn"], hn)
            return h + f, c
        x, kv = maybe_scan(body, x, (params["layers"], cache["kv"]),
                           cfg.unroll_layers)
        cache = {"kv": kv}
    elif fam == "ssm":
        def body(h, xs):
            lp, st = xs
            out, st = S.ssm_decode(lp["ssm"], L.rmsnorm(lp["ln1"], h), st,
                                   dist=dist)
            return h + out, st
        x, st = maybe_scan(body, x, (params["layers"], cache["ssm"]),
                           cfg.unroll_layers)
        cache = {"ssm": st}
    elif fam == "hybrid":
        def body(h, xs):
            lp, c, st = xs
            hn = L.rmsnorm(lp["ln1"], h)
            att, c = attn_dec(lp, hn, c)
            sm, st = S.ssm_decode(lp["ssm"], hn, st, dist=dist)
            h = h + (att + sm) * 0.5
            h = h + L.ffn(lp["ffn"], L.rmsnorm(lp["ln2"], h))
            return h, (c, st)
        x, (kv, st) = maybe_scan(
            body, x, (params["layers"], cache["kv"], cache["ssm"]),
            cfg.unroll_layers)
        cache = {"kv": kv, "ssm": st}
    else:
        raise NotImplementedError(
            f"family {fam!r} is not served by the continuous-batching "
            "engine (dense/moe/ssm/hybrid only)")

    x = L.rmsnorm(params["norm_f"], x)
    logits = L.unembed(params["head"], x)
    if dist is not None:
        logits = dist.shard_logits(logits)
    return logits, cache


# ---------------------------------------------------------------------------
# Fused decode loop (scan over decode_step — no per-token Python round-trip)
# ---------------------------------------------------------------------------

def decode_loop(params, cfg: ArchConfig, tok, cache, start_pos, n_new, *,
                temperature=0.0, key=None, dist=None):
    """Generate ``n_new`` tokens with ONE compiled program: a ``lax.scan``
    whose body is ``decode_step`` + sampling.  The per-token Python loop
    (dispatch + device sync every token) disappears; the whole decode is a
    single XLA while-loop on device.

    tok (B, 1) int32 first token to emit; start_pos (B, 1) int32 its
    position.  Returns (tokens (B, n_new), final cache) — tok itself is the
    first output token, matching the eager loop in
    ``serve.engine.generate_python``.
    """
    if key is None:
        key = jax.random.PRNGKey(0)

    def body(carry, i):
        tok, cache = carry
        pos = start_pos + i
        logits, cache = decode_step(params, cfg, tok, cache, pos, dist=dist)
        if temperature > 0:
            sub = jax.random.fold_in(key, i)
            nxt = jax.random.categorical(
                sub, logits[:, -1, :] / temperature)[:, None]
        else:
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        return (nxt.astype(jnp.int32), cache), tok

    (_, cache), toks = jax.lax.scan(
        body, (tok, cache), jnp.arange(n_new, dtype=jnp.int32))
    return jnp.swapaxes(toks[..., 0], 0, 1), cache      # (B, n_new)
