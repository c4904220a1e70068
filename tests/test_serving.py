"""Continuous-batching serving engine tests: ragged-batch decode is
bit-identical to N independent ``generate`` calls (the oracle) across all
served families and the packed-kernel path, slot reuse leaks no stale KV,
the scheduler replays deterministically, and neither ``generate`` nor the
engine's batched step ever retraces after the first call."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import reweighted as RW
from repro.models import transformer as T
from repro.serve import engine as E
from repro.serve import kvcache as KV
from repro.serve.compile import CompileSpec, compile_model
from repro.serve.engine import ServingEngine, generate
from repro.serve.scheduler import Request, Scheduler
from repro.train.trainer import apply_masks

SMOKE = {"dense": "yi-9b", "moe": "mixtral-8x7b", "ssm": "mamba2-1.3b",
         "hybrid": "hymba-1.5b"}


def _lm(arch, **over):
    cfg = configs.get(arch, smoke=True)
    if over:
        cfg = cfg.replace(**over)
    return T.init_lm(jax.random.PRNGKey(0), cfg), cfg


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab, size=n).tolist() for n in lens]


def _oracle(params, cfg, prompt, n_new):
    toks = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new)
    return np.asarray(toks)[0].tolist()


def _assert_engine_matches_oracle(params, cfg, prompts, n_new, n_slots):
    eng = ServingEngine(params, cfg, n_slots=n_slots, seq_cap=32)
    rids = [eng.submit(p, n_new) for p in prompts]
    eng.run()
    for rid, p in zip(rids, prompts):
        req = eng.requests[rid]
        assert req.status == "finished"
        assert req.tokens == _oracle(params, cfg, p, n_new), (
            f"rid={rid} prompt_len={len(p)} diverged from generate")
    return eng


# -- ragged-batch bit-identity oracle, all served families -------------------

@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_engine_bit_identical_to_generate(family):
    """A batch of mixed-length requests sharing slots decodes exactly the
    tokens N independent single-sequence ``generate`` calls produce."""
    params, cfg = _lm(SMOKE[family])
    prompts = _prompts(cfg, [8, 12, 5])
    eng = _assert_engine_matches_oracle(params, cfg, prompts, 6, n_slots=2)
    # 3 requests through 2 slots: the third reused an evicted slot
    assert eng.stats["finished"] == 3
    assert eng.stats["tokens"] == sum(len(eng.requests[r].tokens)
                                      for r in eng.requests)


def test_compile_model_refuses_unlowerable_blocks_on_tpu(monkeypatch):
    """On a TPU backend, the smoke widths' (16, 16) blocks cannot lower, so
    ``compile_model`` raises before packing anything — the layout is never
    packed and then interpreted.  Published widths get (128, 128)."""
    from repro.launch.serve import sparse_spec
    params, cfg = _lm(SMOKE["dense"])
    spec = sparse_spec(cfg)
    masks = RW.magnitude_block_masks(params, spec, None, rate=0.6)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="cannot be served on TPU"):
        compile_model(apply_masks(params, masks), masks, spec,
                      spec=CompileSpec(keep_dense=False))
    assert {c.block for _, c in sparse_spec(configs.get("yi-9b"))} == \
        {(128, 128)}


def test_engine_packed_kernel_path():
    """The oracle holds on compile_model-packed params — the batched
    launch hits the real Pallas BCS kernels, not a dense fallback."""
    params, cfg = _lm(SMOKE["dense"])
    from repro.launch.serve import sparse_spec
    masks = RW.magnitude_block_masks(params, sparse_spec(cfg), None, rate=0.6)
    params = apply_masks(params, masks)
    params, _ = compile_model(params, masks, sparse_spec(cfg),
                              spec=CompileSpec(keep_dense=False))
    _assert_engine_matches_oracle(params, cfg, _prompts(cfg, [9, 6]), 5,
                                  n_slots=2)


def test_engine_sliding_window_parity():
    """Per-slot ring capacities reproduce ``generate``'s drop-oldest
    window semantics when prompts straddle the window length."""
    params, cfg = _lm(SMOKE["dense"], sliding_window=8)
    # one prompt longer than the window (ring wraps), one shorter
    _assert_engine_matches_oracle(params, cfg, _prompts(cfg, [12, 5]), 6,
                                  n_slots=2)


# -- slot hygiene ------------------------------------------------------------

def test_slot_reuse_leaks_no_stale_kv():
    """Back-to-back occupants of the SAME slot each match their oracle:
    the second request decodes as if the first never existed."""
    params, cfg = _lm(SMOKE["dense"])
    p1, p2 = _prompts(cfg, [11, 7], seed=3)
    eng = ServingEngine(params, cfg, n_slots=1, seq_cap=32)
    r1 = eng.submit(p1, 6)
    r2 = eng.submit(p2, 6)
    eng.run()
    assert eng.requests[r1].tokens == _oracle(params, cfg, p1, 6)
    assert eng.requests[r2].tokens == _oracle(params, cfg, p2, 6)
    # both really went through slot 0, serially
    admits = [e for e in eng.sched.events if e[0] == "admit"]
    assert [e[2] for e in admits] == [0, 0]


def test_cleared_slot_positions_invalidated():
    """Eviction leaves the slot row with every position INVALID — the
    dead history is structurally unreachable even before the next
    admission's zero-fill."""
    params, cfg = _lm(SMOKE["dense"])
    eng = ServingEngine(params, cfg, n_slots=1, seq_cap=16)
    eng.submit(_prompts(cfg, [6])[0], 3)
    eng.run()
    pos = np.asarray(eng.cache["kv"]["pos"])
    assert (pos == KV.INVALID_POS).all(), "evicted slot kept live positions"


def test_stop_token_ends_request_early():
    params, cfg = _lm(SMOKE["dense"])
    prompt = _prompts(cfg, [8])[0]
    ref = _oracle(params, cfg, prompt, 8)
    stop = ref[3]
    eng = ServingEngine(params, cfg, n_slots=1, seq_cap=32)
    rid = eng.submit(prompt, 8, stop_token=stop)
    eng.run()
    # truncated at the FIRST emission of the stop token
    cut = ref.index(stop) + 1
    assert eng.requests[rid].tokens == ref[:cut]
    assert len(eng.requests[rid].tokens) < 8


# -- scheduler ---------------------------------------------------------------

def test_scheduler_replays_deterministically():
    """Same submissions -> byte-identical event audit trails."""
    def run_once():
        sched = Scheduler(2)
        reqs = [Request(i, (1,), 3, arrival=i // 2) for i in range(5)]
        for r in reqs:
            sched.submit(r)
        now = 0
        while sched.has_work():
            while sched.admit(now) is not None:
                pass
            for _, r in sched.active():
                r.tokens.append(0)
                if r.done():
                    sched.release(r)
            now += 1
        return sched.events
    assert run_once() == run_once()


def test_scheduler_admits_lowest_slot_and_gates_on_arrival():
    sched = Scheduler(3)
    early = Request(0, (1,), 2, arrival=0)
    late = Request(1, (1,), 2, arrival=5)
    sched.submit(early)
    sched.submit(late)
    slot, req = sched.admit(now=0)
    assert (slot, req.rid) == (0, 0)
    # head-of-line: rid 1 hasn't arrived, so nothing admits at now=0
    assert sched.admit(now=0) is None
    assert sched.admit(now=5) == (1, late)
    sched.release(early)
    assert sched.active() == [(1, late)]


def test_over_budget_prompt_rejected_not_queued():
    params, cfg = _lm(SMOKE["dense"])
    eng = ServingEngine(params, cfg, n_slots=1, seq_cap=8)
    rid = eng.submit(list(range(1, 20)), 4)     # prompt 19 > seq_cap 8
    assert eng.requests[rid].status == "rejected"
    assert eng.stats["rejected"] == 1
    assert not eng.sched.has_work()
    ok = eng.submit(_prompts(cfg, [4])[0], 3)
    eng.run()
    assert eng.requests[ok].status == "finished"
    assert eng.stats["evicted"] == 0


def test_occupancy_and_counter_accounting():
    params, cfg = _lm(SMOKE["dense"])
    eng = ServingEngine(params, cfg, n_slots=4, seq_cap=32)
    for p in _prompts(cfg, [6, 6]):
        eng.submit(p, 4)
    eng.run()
    assert eng.stats["admitted"] == eng.stats["finished"] == 2
    assert eng.stats["evicted"] == 0
    assert 0.0 < eng.mean_occupancy() <= 0.5    # 2 busy of 4 slots


# -- retrace regression ------------------------------------------------------

def _counting(fn, counter):
    def wrapped(*a, **kw):
        counter.append(1)
        return fn(*a, **kw)
    return wrapped


def test_generate_traces_once_across_requests(monkeypatch):
    """Two same-shape generate calls share one compiled decode loop: the
    per-request retrace would otherwise dominate small-request serving."""
    params, cfg = _lm(SMOKE["dense"])
    traces = []
    monkeypatch.setattr(T, "decode_loop", _counting(T.decode_loop, traces))
    E._JIT_CACHE.clear()
    toks = jnp.asarray(_prompts(cfg, [8, 8], seed=1), jnp.int32)
    generate(params, cfg, toks[:1], 4)
    generate(params, cfg, toks[1:], 4)
    assert len(traces) == 1


def test_engine_step_traces_once_across_admissions(monkeypatch):
    """Admission, eviction, and slot reuse never retrace the batched
    decode step — its shapes are pinned by (n_slots, seq_cap)."""
    params, cfg = _lm(SMOKE["dense"])
    traces = []
    monkeypatch.setattr(T, "decode_step_ragged",
                        _counting(T.decode_step_ragged, traces))
    E._JIT_CACHE.clear()
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32)
    for i, p in enumerate(_prompts(cfg, [8, 5, 12])):
        eng.submit(p, 4, arrival=i)             # staggered arrivals
    eng.run()
    assert eng.stats["finished"] == 3
    assert len(traces) == 1
