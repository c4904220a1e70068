"""The serving engine's host spans (``repro.serve.trace.span``) and the
names of its packed-kernel launches, on the CPU.

A recorder stands in for ``span`` and keeps each span's name, arguments
and enclosing span; the engine under it must serve the same tokens as
without it.  The launch names are read from the traced programs: every
``pallas_call`` of the prefill and the serving step is named after its
projection (``bsr_matmul_<proj>``), which is the name the compiled
program, and so a profile, gives the launch.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import reweighted as RW
from repro.launch.serve import sparse_spec
from repro.models import transformer as T
from repro.serve import engine as E
from repro.serve import kvcache as KV
from repro.serve import trace as TR
from repro.serve.compile import CompileSpec, compile_model
from repro.train.trainer import apply_masks

SPANS = {"serve.submit", "serve.step", "serve.admit", "serve.harvest"}
PROJECTIONS = ("wq", "wk", "wv", "wo", "gate", "up", "down")
LENS, NEW = (9, 5, 12), (4, 6, 3)       # 3 requests through 2 slots


class Recorder:
    """Records every span opened through it, with its enclosing span."""

    def __init__(self):
        self.spans, self._open = [], []

    @contextlib.contextmanager
    def __call__(self, name, **args):
        parent = self._open[-1]["name"] if self._open else None
        rec = {"name": name, "args": args, "parent": parent}
        self.spans.append(rec)
        self._open.append(rec)
        try:
            yield
        finally:
            self._open.pop()

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]


def _serve(params, cfg, prompts):
    eng = E.ServingEngine(params, cfg, n_slots=2, seq_cap=32)
    rids = [eng.submit(p, n) for p, n in zip(prompts, NEW)]
    eng.run()
    return eng, rids


@pytest.fixture(scope="module")
def served():
    """(engine, rids, prompts, recorder, tokens of a run without it)."""
    cfg = configs.get("yi-9b", smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab, size=n).tolist() for n in LENS]
    plain, plain_rids = _serve(params, cfg, prompts)
    rec = Recorder()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(TR, "span", rec)
        eng, rids = _serve(params, cfg, prompts)
    plain_tokens = [plain.requests[r].tokens for r in plain_rids]
    return eng, rids, prompts, rec, plain_tokens


def test_engine_records_the_four_spans(served):
    eng, rids, _, rec, _ = served
    assert {s["name"] for s in rec.spans} == SPANS
    assert len(rec.named("serve.submit")) == len(rids)
    assert len(rec.named("serve.admit")) == eng.stats["admitted"] == 3
    assert len(rec.named("serve.step")) == eng.stats["steps"]


def test_admit_and_harvest_nest_in_step(served):
    _, _, _, rec, _ = served
    for s in rec.spans:
        inside = "serve.step" if s["name"] in ("serve.admit",
                                               "serve.harvest") else None
        assert s["parent"] == inside, s


def test_step_indices_are_consecutive(served):
    eng, _, _, rec, _ = served
    idx = [s["args"]["index"] for s in rec.named("serve.step")]
    assert idx == list(range(eng.stats["steps"]))
    assert all(0 <= s["args"]["active"] <= eng.n_slots
               for s in rec.named("serve.step"))
    # every slot a step decoded is harvested once, with its token
    assert sum(s["args"]["active"] for s in rec.named("serve.harvest")) == \
        eng.stats["tokens"] - eng.stats["admitted"]


def test_span_arguments_match_the_requests(served):
    eng, rids, prompts, rec, _ = served
    sub = rec.named("serve.submit")
    assert [s["args"] for s in sub] == [
        {"rid": r, "prompt_len": len(p)} for r, p in zip(rids, prompts)]
    slots = {e[1]: e[2] for e in eng.sched.events if e[0] == "admit"}
    adm = rec.named("serve.admit")
    assert sorted(s["args"]["rid"] for s in adm) == sorted(rids)
    for s in adm:
        rid = s["args"]["rid"]
        assert s["args"] == {"rid": rid, "slot": slots[rid],
                             "prompt_len": len(prompts[rid])}


def test_request_spans_share_rid(served):
    _, rids, _, rec, _ = served
    for rid in rids:
        assert [s["name"] for s in rec.spans
                if s["args"].get("rid") == rid] == ["serve.submit",
                                                    "serve.admit"]


def test_recorded_run_serves_the_same_tokens(served):
    eng, rids, _, _, plain_tokens = served
    assert [eng.requests[r].tokens for r in rids] == plain_tokens
    assert all(eng.requests[r].status == "finished" for r in rids)


# -- launch names ------------------------------------------------------------

def _pallas_names(jaxpr, out):
    """Names of every ``pallas_call`` in ``jaxpr`` and the jaxprs nested
    in its equations (jit, scan, cond bodies)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_names(sub, out)
    return out


@pytest.fixture(scope="module")
def launch_names():
    """{program: [launch name]} for the packed smoke model's admission
    prefill and serving step, as the engine traces them."""
    cfg = configs.get("yi-9b", smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    masks = RW.magnitude_block_masks(params, sparse_spec(cfg), None, rate=0.6)
    params, _ = compile_model(apply_masks(params, masks), masks,
                              sparse_spec(cfg),
                              spec=CompileSpec(keep_dense=False))
    n = 2
    cache = KV.init_slots(params, cfg, n, 32)
    col = jnp.zeros((n, 1), jnp.int32)
    progs = {
        "prefill": jax.make_jaxpr(E._jit_prefill(cfg, None))(
            params, jnp.ones((1, 8), jnp.int32), None),
        "step": jax.make_jaxpr(E._jit_serving_step(cfg, None))(
            params, col, cache, col, jnp.ones((n,), jnp.int32)),
    }
    return {k: _pallas_names(j.jaxpr, []) for k, j in progs.items()}


@pytest.mark.parametrize("program", ["prefill", "step"])
@pytest.mark.parametrize("proj", PROJECTIONS)
def test_projection_names_its_launches(launch_names, program, proj):
    names = launch_names[program]
    assert f"bsr_matmul_{proj}" in names
    assert set(names) <= {f"bsr_matmul_{p}" for p in PROJECTIONS}
