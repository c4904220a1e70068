"""Serving: prefill (forward pass that also emits the per-layer caches),
the single-sequence fused decode loop (``generate`` — the bit-identity
oracle), and the continuous-batching ``ServingEngine`` that decodes many
live requests through ONE batched step per token so every packed kernel
launch amortizes the streamed weights over the whole batch.
``decode_step`` itself lives in models/transformer (it is what the
decode_* dry-run shapes lower)."""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import bsr_matmul as BM
from repro.models import layers as L
from repro.models import attention as A
from repro.models import ssm as S
from repro.models import transformer as T
from repro.serve import kvcache as KV
from repro.serve import trace as TR
from repro.serve.scheduler import (REASON_DEADLINE_EXPIRED, REASON_OVER_BUDGET,
                                   REASON_QUARANTINED, Request, Scheduler)

tmap = jax.tree_util.tree_map


def _window_kv(k, v, S_len, window):
    if window and window < S_len:
        k = k[:, S_len - window:]
        v = v[:, S_len - window:]
        pos = jnp.arange(S_len - window, S_len, dtype=jnp.int32)
    else:
        pos = jnp.arange(S_len, dtype=jnp.int32)
    return k, v, pos


def prefill(params, cfg: ArchConfig, tokens, frontend=None, dist=None):
    """tokens (B,S) -> (last-token logits (B,1,V), cache matching init_cache)."""
    B, Sq = tokens.shape
    positions = jnp.arange(Sq, dtype=jnp.int32)
    x = L.embed(params["embed"], tokens)
    if dist is not None:
        x = dist.shard_activations(x)
    fam = cfg.family
    W = cfg.sliding_window

    if fam in ("dense", "moe", "hybrid"):
        def body(carry, lp):
            h, = carry
            h, _, c = T._layer_fwd(lp, h, positions, cfg, fam, dist=dist,
                                   collect_cache=True)
            k, v, pos = _window_kv(c["k"], c["v"], Sq, W)
            out_c = {"k": k, "v": v, "pos": pos}
            if fam == "hybrid":
                hn = L.rmsnorm(lp["ln1"], h)  # recompute state cheaply
                _, st = S.ssm(lp["ssm"], hn, dist=dist)
                out_c = (out_c, st)
            return (h,), out_c
        (x,), caches = T.maybe_scan(body, (x,), params["layers"],
                                    cfg.unroll_layers)
        if fam == "hybrid":
            cache = {"kv": caches[0], "ssm": caches[1]}
        else:
            cache = {"kv": caches}
    elif fam == "ssm":
        def body(carry, lp):
            h, = carry
            hn = L.rmsnorm(lp["ln1"], h)
            out, st = S.ssm(lp["ssm"], hn, dist=dist)
            return (h + out,), st
        (x,), st = T.maybe_scan(body, (x,), params["layers"],
                                cfg.unroll_layers)
        cache = {"ssm": st}
    elif fam == "encdec":
        # encode once; decoder prefill caches self-KV and cross-KV
        enc_pos = jnp.arange(frontend.shape[1], dtype=jnp.int32)

        def enc_body(carry, lp):
            h, = carry
            att, _ = A.mha(lp["attn"], L.rmsnorm(lp["ln1"], h), enc_pos,
                           cfg.n_heads, cfg.n_kv_heads, cfg.hd, causal=False,
                           dist=dist, shard=cfg.attn_shard)
            h = h + att
            h = h + L.ffn(lp["ffn"], L.rmsnorm(lp["ln2"], h))
            return (h,), None
        (memory,), _ = T.maybe_scan(enc_body, (frontend.astype(x.dtype),),
                                    params["enc"], cfg.unroll_layers)
        memory = L.rmsnorm(params["norm_e"], memory)

        def body(carry, lp):
            h, = carry
            h, _, c = T._layer_fwd(lp, h, positions, cfg, "xdec", dist=dist,
                                   memory=memory, collect_cache=True)
            pos = jnp.arange(Sq, dtype=jnp.int32)
            return (h,), ({"k": c["k"], "v": c["v"], "pos": pos},
                          c["xk"], c["xv"])
        (x,), (kv, xk, xv) = T.maybe_scan(body, (x,), params["dec"],
                                          cfg.unroll_layers)
        cache = {"kv": kv, "xk": xk, "xv": xv}
    elif fam == "vlm":
        memory = frontend.astype(x.dtype)
        k = cfg.cross_attn_interval

        def group_body(carry, gp):
            h, = carry

            def self_body(hc, lp):
                hh, = hc
                hh, _, c = T._layer_fwd(lp, hh, positions, cfg, "dense",
                                        dist=dist, collect_cache=True)
                return (hh,), {"k": c["k"], "v": c["v"],
                               "pos": jnp.arange(Sq, dtype=jnp.int32)}
            (h,), kv_self = T.maybe_scan(self_body, (h,), gp["selfs"],
                                         cfg.unroll_layers)
            hn = L.rmsnorm(gp["cross"]["ln1"], h)
            xa, xkv = A.mha(gp["cross"]["xattn"], hn, positions, cfg.n_heads,
                            cfg.n_kv_heads, cfg.hd, dist=dist,
                            shard=cfg.attn_shard, memory=memory)
            h = h + jnp.tanh(gp["cross"]["gate"]).astype(h.dtype) * xa
            h = h + L.ffn(gp["cross"]["ffn"], L.rmsnorm(gp["cross"]["ln2"], h))
            return (h,), (kv_self, xkv[0], xkv[1])
        (x,), (kv_self, xk, xv) = T.maybe_scan(group_body, (x,),
                                               params["groups"],
                                               cfg.unroll_layers)
        n_groups = cfg.n_layers // k
        kv_self = tmap(lambda a: a.reshape((n_groups * (k - 1),) + a.shape[2:]),
                       kv_self)
        cache = {"kv_self": kv_self, "xk": xk, "xv": xv}
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["norm_f"], x[:, -1:, :])
    logits = L.unembed(params["head"], x)
    return logits, cache


# jitted closures are cached per call signature: a fresh jax.jit(lambda ...)
# every generate() would re-trace + re-compile the whole model per request.
# cfg is a frozen (hashable) dataclass; dist objects are keyed by identity.
# LRU-bounded — each entry pins a full compiled executable, so an unbounded
# dict would grow with every distinct (cfg, n_new, temperature) seen.
_JIT_CACHE: OrderedDict = OrderedDict()
_JIT_CACHE_MAX = 32


def _cached_jit(key, make, dist=None):
    """``jax.jit(make())``, cached under ``key``.  With a ``dist`` the
    function traces under its mesh, so the Pallas kernels place their
    launches per device (``kernels.bsr_matmul.traced_on``)."""
    if key in _JIT_CACHE:
        _JIT_CACHE.move_to_end(key)
    else:
        fn = make()
        if dist is not None:
            fn = _under_mesh(fn, dist)
        _JIT_CACHE[key] = jax.jit(fn)
        while len(_JIT_CACHE) > _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
    return _JIT_CACHE[key]


def _under_mesh(fn, dist):
    def traced(*args):
        with BM.traced_on(dist.mesh, dist.model_axis):
            return fn(*args)
    return traced


def _jit_prefill(cfg, dist):
    return _cached_jit(
        ("prefill", cfg, id(dist)),
        lambda: lambda p, t, f: prefill(p, cfg, t, frontend=f, dist=dist),
        dist)


def _jit_decode_loop(cfg, n_new, temperature, dist):
    return _cached_jit(
        ("loop", cfg, n_new, temperature, id(dist)),
        lambda: lambda p, t, c, s, k: T.decode_loop(
            p, cfg, t, c, s, n_new, temperature=temperature, key=k,
            dist=dist), dist)


def _jit_decode_step(cfg, dist):
    return _cached_jit(
        ("step", cfg, id(dist)),
        lambda: lambda p, tok, c, pos: T.decode_step(p, cfg, tok, c, pos,
                                                     dist=dist), dist)


def generate(params, cfg: ArchConfig, tokens, n_new, frontend=None,
             dist=None, temperature=0.0, key=None):
    """Fused generation: jitted prefill, then ONE compiled scan over
    ``decode_step`` (``models.transformer.decode_loop``) — decoding never
    round-trips through Python per token.  Works with dense, masked, and
    ``compile_model``-packed params alike."""
    B, Sq = tokens.shape
    logits, cache = _jit_prefill(cfg, dist)(params, tokens, frontend)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    start = jnp.full((B, 1), Sq, jnp.int32)
    loop = _jit_decode_loop(cfg, n_new, temperature, dist)
    toks, _ = loop(params, tok, cache, start,
                   key if key is not None else jax.random.PRNGKey(0))
    return toks


def _jit_serving_step(cfg, dist):
    """The engine's batched decode executable: ragged decode step + greedy
    argmax + per-slot finite check fused into one program.  Cached per
    (cfg, dist); the slot-array shapes are fixed for an engine's lifetime,
    so admission/eviction never retraces (locked by a trace-count
    regression test).

    The finite flag (``ok``, one bool per slot) is the numerical
    quarantine probe: it reduces THIS slot's logits only, inside the same
    launch — no extra dispatch, no retrace — so the harvest loop can evict
    a poisoned slot before its garbage argmax ever becomes a token."""
    def make():
        def step(p, tok, cache, pos, cap):
            logits, cache = T.decode_step_ragged(p, cfg, tok, cache, pos,
                                                 cap, dist=dist)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            ok = jnp.all(jnp.isfinite(logits[:, -1, :].astype(jnp.float32)),
                         axis=-1)
            return nxt, ok, cache
        return step
    return _cached_jit(("serving_step", cfg, id(dist)), make, dist)


class ServingEngine:
    """Continuous-batching serving engine: scheduler + slot KV cache +
    one batched decode launch per step.

    Requests are admitted into free slots mid-flight (each admission is a
    B=1 jitted prefill plus a slot-row write), every step runs ALL active
    slots through one ``decode_step_ragged`` — so each degree-bin
    ``bsr_matmul_packed`` launch does an M=B GEMM over the same packed
    weights instead of B separate M=1 GEMVs — and finished requests are
    evicted the step their stop condition fires, freeing the slot for the
    queue.  Decoding is greedy (temperature 0): a batch of N requests is
    token-for-token identical to N independent ``generate`` calls (the
    oracle test in tests/test_serving.py).

    Fault tolerance: ``validate=True`` (default) runs
    ``serve.compile.degrade_invalid_layers`` over the exec params at
    construction — any packed layout failing ``core.validate`` is retired
    to the masked-dense ``DegradedLayer`` path (slower, never wrong) and
    counted in ``stats["degraded_layers"]``.  Every step, queue TTLs and
    running deadlines are swept BEFORE admission, and the batched decode's
    fused per-slot finite probe quarantines any slot whose logits went
    non-finite — the slot is evicted (status ``"quarantined"``) without
    emitting the garbage token, and the surviving slots' tokens are
    bit-identical to a run where the poisoned request was never admitted
    (slots share weights, never activations — locked by the chaos suite).

    Counters in ``stats``: engine steps, admitted/finished/evicted/
    rejected requests, quarantined slots, expired deadlines, degraded
    layers, emitted tokens, and the running occupancy sum
    (``mean_occupancy()`` = mean fraction of busy slots per step).

    Host spans (``serve.trace.span``, on the profiler's clock while a
    trace runs; a request's spans share its ``rid``):

    * ``serve.submit`` (``rid``, ``prompt_len``): the whole of ``submit``;
    * ``serve.step`` (``index`` = ``stats["steps"]`` at entry, ``active``
      = slots holding a request at entry): the whole of ``step``;
    * ``serve.admit`` (``rid``, ``slot``, ``prompt_len``), inside
      ``serve.step``, one per admitted request: from the prompt's transfer
      through the B=1 prefill and the first token's readback to the
      dispatch of the slot write;
    * ``serve.harvest`` (``active``), inside ``serve.step``: from the
      moment the step's tokens and finite flags are on the host to the end
      of the token append and release loop.
    """

    FAMILIES = ("dense", "moe", "ssm", "hybrid")

    def __init__(self, params, cfg: ArchConfig, *, n_slots=8, seq_cap=256,
                 dist=None, max_queue=None, validate=True, report=None):
        if cfg.family not in self.FAMILIES:
            raise NotImplementedError(
                f"family {cfg.family!r} is not served (supported: "
                f"{self.FAMILIES})")
        if cfg.sliding_window:
            # a slot never needs more ring than the attention window
            seq_cap = min(seq_cap, cfg.sliding_window)
        self.report = report
        degraded = []
        if validate:
            from repro.serve import compile as SC  # late: compile is heavy
            params, self.report, degraded = SC.degrade_invalid_layers(
                params, report=report)
        self.params, self.cfg, self.dist = params, cfg, dist
        self.n_slots, self.seq_cap = n_slots, seq_cap
        dtype = params["embed"]["table"].dtype
        self.cache = KV.init_slots(params, cfg, n_slots, seq_cap,
                                   dtype=dtype)
        self.sched = Scheduler(n_slots, max_queue=max_queue)
        # per-slot decode operands; free slots idle as pos=0/cap=1 padding
        self.tok = np.zeros((n_slots, 1), np.int32)
        self.pos = np.zeros((n_slots, 1), np.int32)
        self.cap = np.ones((n_slots,), np.int32)
        self._step_fn = _jit_serving_step(cfg, dist)
        self._rid = 0
        self.requests: dict = {}
        self.stats = {"steps": 0, "occupancy_sum": 0.0, "tokens": 0,
                      "admitted": 0, "finished": 0, "evicted": 0,
                      "rejected": 0, "quarantined": 0, "expired": 0,
                      "degraded_layers": len(degraded)}

    # -- request intake -----------------------------------------------------

    def submit(self, prompt, max_new_tokens, *, arrival=0,
               stop_token=None, deadline_steps=None, queue_ttl=None,
               retries=0, backoff=1) -> int:
        """Queue one request; returns its id (``requests[rid].tokens`` holds
        the output).  Prompts whose effective (window-clipped) length
        exceeds the slot capacity are rejected up front — the one budget a
        slot cannot ring-buffer away.

        ``deadline_steps`` / ``queue_ttl`` bound slot occupancy and queue
        wait (see ``serve.scheduler.Request``); ``retries`` / ``backoff``
        bound the resubmission policy when the scheduler's ``max_queue``
        is full.
        """
        with TR.span("serve.submit", rid=self._rid, prompt_len=len(prompt)):
            req = Request(self._rid, tuple(int(t) for t in prompt),
                          int(max_new_tokens), arrival=arrival,
                          stop_token=stop_token,
                          deadline_steps=deadline_steps, queue_ttl=queue_ttl,
                          retries=retries, backoff=backoff)
            self._rid += 1
            self.requests[req.rid] = req
            if (not req.prompt or req.max_new_tokens < 1
                    or KV.slot_capacity(self.cfg, len(req.prompt))
                    > self.seq_cap):
                self.sched.reject(req, REASON_OVER_BUDGET)
                self.stats["rejected"] += 1
            else:
                if self.sched.submit(req, self.stats["steps"]) == "rejected":
                    self.stats["rejected"] += 1
            return req.rid

    # -- engine loop --------------------------------------------------------

    def _admit(self):
        while (pair := self.sched.admit(self.stats["steps"])) is not None:
            slot, req = pair
            with TR.span("serve.admit", rid=req.rid, slot=slot,
                         prompt_len=len(req.prompt)):
                toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
                logits, rc = _jit_prefill(self.cfg, self.dist)(
                    self.params, toks, None)
                t0 = int(jnp.argmax(logits[:, -1, :], axis=-1)[0])
                req.tokens.append(t0)
                self.stats["admitted"] += 1
                self.stats["tokens"] += 1
                if req.done():      # budget of 1 (or instant stop token)
                    self._release(slot, req, "finished")
                    continue
                self.cache = KV.write_prefill(self.cache, slot, rc)
            self.cap[slot] = KV.slot_capacity(self.cfg, len(req.prompt))
            self.pos[slot] = len(req.prompt)
            self.tok[slot] = t0

    def _release(self, slot, req, status, reason=None):
        self.sched.release(req, status, reason)
        self.cache = KV.clear_slot(self.cache, slot)
        self.tok[slot], self.pos[slot], self.cap[slot] = 0, 0, 1
        if status == "finished":
            self.stats["finished"] += 1
        elif status == "quarantined":
            self.stats["quarantined"] += 1
        else:
            self.stats["evicted"] += 1

    def _sweep_faults(self):
        """Top-of-step fault pass, all BEFORE admission so freed slots
        refill the same step (bounded recovery): expire overdue queue
        TTLs, re-submit due retry backoffs, evict running requests past
        their ``deadline_steps`` budget."""
        now = self.stats["steps"]
        self.stats["expired"] += len(self.sched.expire(now))
        self.stats["rejected"] += len(self.sched.poll_retries(now))
        for slot, req in self.sched.active():
            if (req.deadline_steps is not None
                    and req.admitted_at is not None
                    and now - req.admitted_at >= req.deadline_steps):
                self._release(slot, req, "evicted",
                              reason=REASON_DEADLINE_EXPIRED)
                self.stats["expired"] += 1

    def step(self) -> int:
        """One engine step: sweep deadlines/TTLs/retries, admit from the
        queue into free slots, decode every active slot in one batched
        launch, harvest tokens, evict finished requests, and quarantine
        any slot whose logits came back non-finite (its garbage argmax is
        never appended; neighbors are untouched).  Returns the number of
        active slots stepped (0 = an idle tick while the open-loop queue
        waits to arrive)."""
        with TR.span("serve.step", index=self.stats["steps"],
                     active=len(self.sched.active())):
            self._sweep_faults()
            self._admit()
            active = self.sched.active()
            self.stats["steps"] += 1
            self.stats["occupancy_sum"] += len(active) / self.n_slots
            if not active:
                return 0
            nxt, ok, self.cache = self._step_fn(
                self.params, jnp.asarray(self.tok), self.cache,
                jnp.asarray(self.pos), jnp.asarray(self.cap))
            nxt, ok = np.asarray(nxt), np.asarray(ok)
            with TR.span("serve.harvest", active=len(active)):
                self._harvest(active, nxt, ok)
            return len(active)

    def _harvest(self, active, nxt, ok):
        """Append each stepped slot's token, or quarantine the slot when
        its logits came back non-finite; release finished requests."""
        for slot, req in active:
            if not bool(ok[slot]):
                self._release(slot, req, "quarantined",
                              reason=REASON_QUARANTINED)
                continue
            t = int(nxt[slot])
            req.tokens.append(t)
            self.stats["tokens"] += 1
            self.pos[slot] += 1
            self.tok[slot] = t
            if req.done():
                self._release(slot, req, "finished")

    def run(self, max_steps=100_000):
        """Drive ``step`` until queue and slots drain; returns ``stats``.
        ``max_steps`` bounds runaway workloads — anything still live when
        it trips is evicted (status ``"evicted"``), never silently lost."""
        while self.sched.has_work() and self.stats["steps"] < max_steps:
            self.step()
        for slot, req in self.sched.active():
            self._release(slot, req, "evicted")
        return self.stats

    def mean_occupancy(self) -> float:
        """Mean fraction of busy slots per engine step so far."""
        steps = self.stats["steps"]
        return self.stats["occupancy_sum"] / steps if steps else 0.0


def generate_python(params, cfg: ArchConfig, tokens, n_new, frontend=None,
                    dist=None, temperature=0.0, key=None):
    """Reference eager loop over jitted decode_step (one dispatch + one
    device sync per token).  Kept as the parity oracle for the fused scan
    loop and for step-by-step debugging."""
    B, Sq = tokens.shape
    logits, cache = _jit_prefill(cfg, dist)(params, tokens, frontend)
    step_fn = _jit_decode_step(cfg, dist)
    out = []
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    if key is None:
        key = jax.random.PRNGKey(0)
    for i in range(n_new):
        out.append(tok)
        pos = jnp.full((B, 1), Sq + i, jnp.int32)
        logits, cache = step_fn(params, tok, cache, pos)
        if temperature > 0:
            sub = jax.random.fold_in(key, i)
            tok = jax.random.categorical(
                sub, logits[:, -1, :] / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)
