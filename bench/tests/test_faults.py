"""The comparison that decides ``correct``, driven through a whole run of
a tiny cell on the CPU (``bench.run.run``, past the look for a chip):
sound, it passes; with the timed path broken underneath, or with the
float8 control in the program's place, it fails.

Faults a serving cell can have, each planted where the engine's serving
step produces it: a token altered, and a step that returns its state (the
slot cache) unchanged.  The other two kinds of fault (half a batch left
out of a mean, an exchange between chips left out) have nothing to act
on in a one-chip serving cell, whose step takes no mean over its batch.

At this size, sound runs read gaps of 0 to 2.5e-3 logits (eight seeds)
and the float8 control 1.9e-2 to 4.8e-2 (five seeds); a stale cache read
2.1e-2 to 1.4e-1 and altered tokens 1.5 to 2.0 (three seeds each; CPU,
float32 reference against the bfloat16 program).  The limit here is
8e-3.
"""
import jax
import numpy as np
import pytest

from bench import check, system
from bench import run as R
from bench.tests import tiny

LIMIT = 8e-3


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return tiny.cell(tmp_path_factory.mktemp("root"), LIMIT)


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    """Tests keep JAX's persistent cache off, and start each run from
    fresh jitted programs."""
    from repro.serve import engine as E
    monkeypatch.setattr(R, "enable_cache", lambda: "off")
    monkeypatch.setattr(E, "_JIT_CACHE", type(E._JIT_CACHE)())


def _planted(kind):
    from repro.serve import engine as E
    real = E._jit_serving_step

    def make(cfg, dist):
        f = real(cfg, dist)

        def step(p, tok, cache, pos, cap):
            nxt, ok, new = f(p, tok, cache, pos, cap)
            if kind == "token":
                nxt = (nxt + 1) % cfg.vocab
            elif kind == "state":
                new = cache
            return nxt, ok, new
        return step
    return make


def test_sound_run_is_correct(cell):
    res = R.run(cell, 2**33 + 5, 2.0, 0, jax.devices())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("kind", ["token", "state"])
def test_planted_fault_is_not_correct(cell, kind, monkeypatch):
    from repro.serve import engine as E
    monkeypatch.setattr(E, "_jit_serving_step", _planted(kind))
    res = R.run(cell, 11, 2.0, 0, jax.devices())
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_gap_max"]["value"] > LIMIT


def test_float8_control_is_not_correct(cell):
    """The reference computed in float8 put in the program's place, judged
    by the run's own comparison and limits: not correct, where the
    program's own tokens are."""
    seed = 21
    eng, counts = system.build(cell, seed, {})
    record, _, _ = R.serve(cell, eng, seed, 4.0, 0)
    del eng
    checks, ok, _, ctl = R.compare(cell, seed, record, counts,
                                   controls=("float8",))
    assert ok, checks
    f8_checks, f8_ok = ctl["float8"]
    assert not f8_ok, f8_checks
    assert f8_checks["served_gap_max"]["value"] > LIMIT


def test_compare_tries_every_rule_mask(cell, monkeypatch):
    """Where the first of the reference's masks does not explain the
    served tokens (here it keeps every block the program pruned), the
    others are tried: the run is judged by the one that does, and the
    masks returned are those.  With only the wrong masks it is not
    correct."""
    from bench.configs import mistral
    seed = 21
    eng, counts = system.build(cell, seed, {})
    record, _, _ = R.serve(cell, eng, seed, 4.0, 0)
    del eng
    right = mistral.block_keeps(cell.config, seed)[0]
    wrong = {n: np.ones_like(m) for n, m in right.items()}
    monkeypatch.setattr(mistral, "block_keeps",
                        lambda cfg, s: [wrong, right, wrong])
    checks, ok, keep, _ = R.compare(cell, seed, record, counts)
    assert ok, checks
    assert keep is right
    monkeypatch.setattr(mistral, "block_keeps", lambda cfg, s: [wrong])
    checks, ok, keep, _ = R.compare(cell, seed, record, counts)
    assert not ok and checks["served_gap_max"]["value"] > LIMIT
    assert keep is wrong


def test_verdict_reads_every_limit():
    checks, ok = check.verdict({"a": 0.1, "b": 0}, {"a": 0.2, "b": 0})
    assert ok and checks == {"a": {"value": 0.1, "limit": 0.2},
                             "b": {"value": 0, "limit": 0}}
    assert not check.verdict({"a": 0.3}, {"a": 0.2})[1]
    assert not check.verdict({}, {"a": 0.2})[1]       # nothing read fails


def test_gaps_by_hand():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, 0.25]])
    np.testing.assert_allclose(check.gaps(ref, [2, 0]), [1.0, 0.0])
