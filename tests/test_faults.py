"""Fault-matrix chaos suite (ISSUE 10 acceptance): every injector in
``repro.testing.faults`` is driven against the serving stack and must
yield (a) forward progress — the engine drains, nothing hangs, (b)
bit-identical tokens for every SURVIVING request versus an oracle run
that never admitted the faulty one, and (c) no silently wrong token —
a faulted request's emitted prefix still matches its healthy oracle,
because every fault is caught BEFORE its first garbage token.

All injections are seeded (`numpy.random.RandomState`), so the suite —
and the replay-determinism test at the bottom — sees the same faults,
events, and recoveries on every run.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import reweighted as RW
from repro.core import validate as V
from repro.core.packed import DegradedLayer
from repro.launch.serve import sparse_spec
from repro.models import transformer as T
from repro.serve import artifacts as ART
from repro.serve import engine as E
from repro.serve.compile import (CompileSpec, compile_model, compiled_summary,
                                 degrade_invalid_layers)
from repro.serve.engine import ServingEngine, generate
from repro.serve.scheduler import (REASON_DEADLINE_EXPIRED,
                                   REASON_OVER_BUDGET, REASON_QUARANTINED,
                                   Request, Scheduler)
from repro.testing import faults as F
from repro.train.trainer import apply_masks

import jax


def _lm(arch):
    cfg = configs.get(arch, smoke=True)
    return T.init_lm(jax.random.PRNGKey(0), cfg), cfg


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab, size=n).tolist() for n in lens]


def _oracle(params, cfg, prompt, n_new):
    toks = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new)
    return np.asarray(toks)[0].tolist()


@pytest.fixture(scope="module")
def dense_lm():
    return _lm("yi-9b")


@pytest.fixture(scope="module")
def packed_lm():
    """Masked + compiled smoke model (keep_dense=True so every packed
    layer carries the masked-dense fallback the degrade path needs)."""
    params, cfg = _lm("yi-9b")
    masks = RW.magnitude_block_masks(params, sparse_spec(cfg), None, rate=0.6)
    params = apply_masks(params, masks)
    exec_params, report = compile_model(params, masks, sparse_spec(cfg),
                                        spec=CompileSpec(keep_dense=True))
    return cfg, params, masks, exec_params, report


def _counting(fn, counter):
    def wrapped(*a, **kw):
        counter.append(1)
        return fn(*a, **kw)
    return wrapped


# -- nan_slot: numerical quarantine ----------------------------------------

def test_nan_slot_quarantines_victim_only(dense_lm):
    """Poisoning one slot's cache quarantines THAT request (before any
    garbage token) and leaves every survivor bit-identical to a run that
    never admitted the victim."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [8, 12, 5])
    n_new = 6

    eng = ServingEngine(params, cfg, n_slots=3, seq_cap=32)
    rids = [eng.submit(p, n_new) for p in prompts]
    eng.step()                                   # admit all three
    victim = rids[1]
    vslot = eng.requests[victim].slot
    healthy_prefix = list(eng.requests[victim].tokens)
    rec = F.nan_slot(eng, vslot)
    assert rec.kind == "nan_slot"
    eng.run()

    assert eng.requests[victim].status == "quarantined"
    assert eng.stats["quarantined"] == 1
    assert eng.stats["finished"] == 2
    # the typed audit event names the slot and the reason
    assert ("quarantined", victim, vslot,
            REASON_QUARANTINED) in eng.sched.events
    # no silent wrong token: the victim kept only its pre-fault tokens,
    # which match its healthy oracle prefix
    vtok = eng.requests[victim].tokens
    assert vtok == healthy_prefix
    assert vtok == _oracle(params, cfg, prompts[1], n_new)[:len(vtok)]

    # never-admitted oracle: same engine, victim never submitted
    ref = ServingEngine(params, cfg, n_slots=3, seq_cap=32)
    ref_rids = [ref.submit(p, n_new) for i, p in enumerate(prompts)
                if i != 1]
    ref.run()
    survivors = [eng.requests[r].tokens for i, r in enumerate(rids)
                 if i != 1]
    assert survivors == [ref.requests[r].tokens for r in ref_rids]
    # and both equal the single-sequence generate oracle
    for toks, p in zip(survivors, [prompts[0], prompts[2]]):
        assert toks == _oracle(params, cfg, p, n_new)


def test_quarantined_slot_readmits_next_step(dense_lm):
    """Recovery is bounded: the slot a quarantine frees is refilled from
    the queue on the very next engine step."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [6, 9, 7], seed=2)
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32)
    rids = [eng.submit(p, 8) for p in prompts]
    eng.step()                                   # admit first two
    F.nan_slot(eng, eng.requests[rids[1]].slot)
    eng.step()                                   # probe fires -> evict
    assert eng.requests[rids[1]].status == "quarantined"
    q_step = eng.stats["steps"]
    eng.step()                                   # freed slot refills
    assert eng.requests[rids[2]].status == "running"
    assert eng.stats["steps"] - q_step == 1
    eng.run()
    assert eng.stats["finished"] == 2


def test_quarantine_probe_never_retraces(dense_lm, monkeypatch):
    """The fused finite probe rides the one batched decode executable:
    poisoning, quarantining, and re-admitting never retrace."""
    params, cfg = dense_lm
    traces = []
    monkeypatch.setattr(T, "decode_step_ragged",
                        _counting(T.decode_step_ragged, traces))
    E._JIT_CACHE.clear()
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32)
    rids = [eng.submit(p, 5) for p in _prompts(cfg, [8, 5, 12], seed=3)]
    eng.step()
    F.nan_slot(eng, eng.requests[rids[0]].slot)
    eng.run()
    assert eng.stats["quarantined"] == 1
    assert eng.stats["finished"] == 2
    assert len(traces) == 1


# -- corrupt_leaf: validate + degraded-mode fallback -----------------------

def test_bitflip_is_detected_by_validate(packed_lm):
    """The seeded bit-flip saturates exponent bits, so the new
    ``non_finite`` check is GUARANTEED to see it (a silent mantissa flip
    would be undetectable — the injector never produces one)."""
    _, _, _, exec_params, _ = packed_lm
    bad, rec = F.bitflip_packed_leaf(exec_params, seed=0)
    assert rec.kind == "corrupt_leaf"
    layers = dict(F._packed_layers(bad))
    with pytest.raises(V.LayoutError) as ei:
        V.validate_layout(layers[rec.target]["packed"], path=rec.target)
    assert ei.value.code in ("non_finite", "index_range")
    with pytest.raises(V.LayoutError):
        V.validate_tree(bad)
    # the input tree is skeleton-copied: the healthy original still passes
    assert V.validate_tree(exec_params) > 0


def test_bitflip_degrades_layer_to_masked_dense(packed_lm):
    """A corrupt packed layout degrades to the masked-dense path for THAT
    layer only: the engine serves tokens bit-identical to dense execution
    of the degraded tree, counts the layer, and annotates the report."""
    cfg, _, _, exec_params, report = packed_lm
    bad, rec = F.bitflip_packed_leaf(exec_params, seed=3)
    prompts = _prompts(cfg, [8, 5], seed=4)

    eng = ServingEngine(bad, cfg, n_slots=2, seq_cap=32, report=report)
    assert eng.stats["degraded_layers"] == 1
    # the marker replaced the layout at the faulted path
    degraded_node = eng.params
    for part in rec.target.split("/"):
        degraded_node = degraded_node[part]
    assert isinstance(degraded_node["packed"], DegradedLayer)
    assert degraded_node["packed"].code in ("non_finite", "index_range")
    # report row re-emitted with the degraded flag + structured reason
    rows = [r for r in eng.report if getattr(r, "degraded", None)]
    assert len(rows) == 1 and rows[0].path == f"{rec.target}/w"
    assert "masked-dense" in rows[0].reason
    assert "[DEGRADED" in compiled_summary(eng.report)

    rids = [eng.submit(p, 5) for p in prompts]
    eng.run()
    assert eng.stats["finished"] == 2
    for rid, p in zip(rids, prompts):
        assert eng.requests[rid].tokens == _oracle(eng.params, cfg, p, 5)


def test_corrupt_layout_without_dense_fallback_raises(packed_lm):
    """keep_dense=False leaves no masked-dense fallback: a corrupt layout
    must RAISE (fail loud), never degrade silently into wrong math."""
    _, _, _, exec_params, _ = packed_lm
    bad, rec = F.bitflip_packed_leaf(exec_params, seed=0)
    node = dict(F._packed_layers(bad))[rec.target]
    stripped = F._skeleton_swap(
        bad, node, {k: v for k, v in node.items() if k != "w"})
    with pytest.raises(V.LayoutError):
        degrade_invalid_layers(stripped)


def test_degraded_layer_marker_is_static_pytree():
    """DegradedLayer carries no array leaves — it is jit-static aux data,
    so swapping a layout for a marker changes the cache key (one retrace)
    instead of poisoning a compiled executable."""
    m = DegradedLayer(path="layers/attn/wq", code="non_finite", detail="x")
    leaves, treedef = jax.tree_util.tree_flatten(m)
    assert leaves == []
    assert jax.tree_util.tree_unflatten(treedef, leaves) == m
    assert hash(m) == hash(DegradedLayer(path="layers/attn/wq",
                                         code="non_finite", detail="x"))


# -- expired_deadline: deadlines, TTLs, bounded retry ----------------------

def test_running_deadline_evicts_with_typed_event(dense_lm):
    """A request past its ``deadline_steps`` budget is evicted at the
    top-of-step sweep with a typed event; its emitted prefix is still
    oracle-exact (bounded lateness, never wrong tokens)."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [8, 6], seed=5)
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32)
    doomed = eng.submit(prompts[0], 20, deadline_steps=2)
    other = eng.submit(prompts[1], 4)
    eng.run()
    dreq = eng.requests[doomed]
    assert dreq.status == "evicted"
    assert eng.stats["expired"] == 1
    assert any(e[0] == "evicted" and e[1] == doomed
               and e[-1] == REASON_DEADLINE_EXPIRED
               for e in eng.sched.events)
    # prefill token + 2 decode steps before the sweep fired
    assert len(dreq.tokens) == 3
    assert dreq.tokens == _oracle(params, cfg, prompts[0], 20)[:3]
    # the neighbor is untouched
    assert eng.requests[other].tokens == _oracle(params, cfg, prompts[1], 4)


def test_queue_ttl_expires_waiting_request(dense_lm):
    """A queued request whose TTL lapses is swept (typed ``expire`` event)
    before it can ever race into a slot; slot holders are unaffected."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [7, 9], seed=6)
    eng = ServingEngine(params, cfg, n_slots=1, seq_cap=32)
    hog = eng.submit(prompts[0], 8)
    brief = eng.submit(prompts[1], 8, queue_ttl=2)
    eng.run()
    assert eng.requests[brief].status == "expired"
    assert eng.requests[brief].tokens == []
    assert eng.stats["expired"] == 1
    assert any(e[0] == "expire" and e[1] == brief
               and e[2] == REASON_DEADLINE_EXPIRED
               for e in eng.sched.events)
    assert eng.requests[hog].tokens == _oracle(params, cfg, prompts[0], 8)


def test_expire_deadline_injector_evicts_running(dense_lm):
    """The chaos injector zeroes a RUNNING request's budget: next sweep
    evicts it and the freed slot keeps the engine making progress."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [8, 6, 5], seed=7)
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32)
    rids = [eng.submit(p, 6) for p in prompts]
    eng.step()
    rec = F.expire_deadline(eng, rids[0])
    assert rec.kind == "expired_deadline"
    eng.run()
    assert eng.requests[rids[0]].status == "evicted"
    assert eng.stats["finished"] == 2
    for rid, p in zip(rids[1:], prompts[1:]):
        assert eng.requests[rid].tokens == _oracle(params, cfg, p, 6)


def test_retry_backoff_is_bounded_and_audited():
    """Scheduler unit: a queue-full submission defers with exponential
    backoff (deterministic due steps), retries at most ``retries`` times,
    then rejects with the typed ``over_budget`` reason."""
    def scenario():
        sched = Scheduler(1, max_queue=1)
        r1 = Request(0, (1,), 4)
        r2 = Request(1, (2,), 4)
        r3 = Request(2, (3,), 4, retries=2, backoff=1)
        sched.submit(r1, 0)
        sched.admit(0)
        sched.submit(r2, 0)                  # queue now full
        assert sched.submit(r3, 0) == "deferred"
        assert sched.poll_retries(1) == []   # due at 1: defers again (due 3)
        assert r3.status == "deferred" and r3.attempts == 2
        rejected = sched.poll_retries(3)     # budget exhausted
        assert rejected == [r3] and r3.status == "rejected"
        return sched.events

    ev = scenario()
    assert ("defer", 2, 1, 1) in ev
    assert ("defer", 2, 2, 3) in ev
    assert ("reject", 2, REASON_OVER_BUDGET) in ev
    assert ev == scenario()                  # byte-identical replay


def test_retry_eventually_admits_when_queue_drains(dense_lm):
    """A deferred submission re-enters once its backoff elapses and the
    queue has space — the retry path ends in tokens, not starvation."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [6, 8], seed=8)
    eng = ServingEngine(params, cfg, n_slots=1, seq_cap=32, max_queue=1)
    first = eng.submit(prompts[0], 4)
    retry = eng.submit(prompts[1], 4, retries=3, backoff=1)
    assert eng.requests[retry].status == "deferred"
    eng.run()
    assert eng.requests[first].status == "finished"
    assert eng.requests[retry].status == "finished"
    assert eng.requests[retry].tokens == _oracle(params, cfg, prompts[1], 4)
    kinds = [e[0] for e in eng.sched.events if e[1] == retry]
    assert "defer" in kinds and "retry" in kinds


# -- crashed_publish: artifact-store fault tolerance -----------------------

def test_crashed_publish_staging_husk_is_ignored(tmp_path, packed_lm):
    """A writer killed mid-stage leaves a ``.tmp_*`` husk; the store's
    atomic-rename protocol means the published artifact stays warm."""
    cfg, params, masks, _, _ = packed_lm
    spec = CompileSpec(keep_dense=True)
    compile_model(params, masks, sparse_spec(cfg), spec=spec,
                  artifact_dir=tmp_path)        # cold pack + publish
    key = ART.model_digest(params, masks, sparse_spec(cfg), spec=spec)
    rec = F.crash_publish(tmp_path, key, stage="staging")
    assert rec.kind == "crashed_publish"
    warm = ART.load_grafted(tmp_path, key, params, keep_dense=True)
    assert warm is not None                      # husk never consulted


def test_crashed_publish_torn_artifact_repacks(tmp_path, packed_lm):
    """A torn final dir (no manifest) is treated as absent: load returns
    None and compile_model silently repays the fresh pack — tokens stay
    oracle-exact."""
    cfg, params, masks, exec_params, _ = packed_lm
    spec = CompileSpec(keep_dense=True)
    key = ART.model_digest(params, masks, sparse_spec(cfg), spec=spec)
    F.crash_publish(tmp_path, key, stage="torn")
    assert ART.load_grafted(tmp_path, key, params, keep_dense=True) is None
    repacked, report = compile_model(params, masks, sparse_spec(cfg), spec=spec,
                                     artifact_dir=tmp_path)
    assert any(r.packed for r in report)
    prompts = _prompts(cfg, [8, 5], seed=9)
    eng = ServingEngine(repacked, cfg, n_slots=2, seq_cap=32)
    rids = [eng.submit(p, 4) for p in prompts]
    eng.run()
    for rid, p in zip(rids, prompts):
        assert eng.requests[rid].tokens == _oracle(exec_params, cfg, p, 4)


# -- the full matrix, replayed ---------------------------------------------

def _chaos_run(params, cfg, prompts):
    """One deterministic multi-fault scenario: TTL expiry + deadline
    eviction + retry exhaustion + a mid-flight NaN slot, all at fixed
    steps.  Returns (events, token streams, stats)."""
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32, max_queue=2)
    rids = [
        eng.submit(prompts[0], 6),
        eng.submit(prompts[1], 6, deadline_steps=3),
        eng.submit(prompts[2], 6, queue_ttl=1),
        eng.submit(prompts[3], 6, retries=1, backoff=1),
        eng.submit(prompts[4], 6),
    ]
    eng.step()
    F.nan_slot(eng, eng.requests[rids[0]].slot)
    eng.run()
    toks = {r: list(eng.requests[r].tokens) for r in rids}
    status = {r: eng.requests[r].status for r in rids}
    return list(eng.sched.events), toks, status, dict(eng.stats)


def test_chaos_matrix_replays_identically(dense_lm):
    """The whole fault matrix in one run, twice: identical audit trails,
    token streams, terminal statuses, and counters — chaos is replayable,
    every request reaches a typed terminal state, and the engine drains."""
    params, cfg = dense_lm
    prompts = _prompts(cfg, [8, 6, 5, 7, 9], seed=10)
    a = _chaos_run(params, cfg, prompts)
    b = _chaos_run(params, cfg, prompts)
    assert a == b
    events, toks, status, stats = a
    terminal = {"finished", "quarantined", "evicted", "expired", "rejected"}
    assert set(status.values()) <= terminal
    assert status[0] == "quarantined"
    assert stats["quarantined"] == 1
    assert stats["finished"] >= 1
    # accounting closes: every admitted request left through a counted door
    assert (stats["finished"] + stats["quarantined"]
            + stats["evicted"] == stats["admitted"])
