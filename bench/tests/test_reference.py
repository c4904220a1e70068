"""The plain reference against the program, in float32 on the CPU at a
smoke size: equal on prefill, equal through the decode ring where the
window divides the prompt, and unequal where it does not (ROADMAP R1:
the ring keeps position p at index p % P, its slot only when W divides P).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import mistral
from bench.families import mistral as F

CFG = {"model_type": "mistral", "hidden_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 256, "vocab_size": 256, "num_hidden_layers": 2,
       "sliding_window": 64, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
       "initializer_range": 0.02}
SEED = 2**32 + 77
STEPS = 12


def _program_logits(cfg, prompt, forced):
    """The engine's own functions, float32: the admission prefill, the
    slot write, then one ragged decode step per forced token (slot 1 of
    two, as the engine serves it).  (1 + len(forced), vocab)."""
    from repro.models import transformer as T
    from repro.serve import engine as E
    from repro.serve import kvcache as KV
    acfg = F.arch_config(cfg)
    # the served bfloat16 weights, computed in float32 as the reference is
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    F.init(cfg, SEED))
    logits, rc = E.prefill(params, acfg, jnp.asarray([prompt], jnp.int32))
    out = [np.asarray(logits[0, -1])]
    cache = KV.init_slots(params, acfg, 2, cfg["sliding_window"],
                          dtype=jnp.float32)
    cache = KV.write_prefill(cache, 1, rc)
    cap = jnp.asarray([1, KV.slot_capacity(acfg, len(prompt))], jnp.int32)
    step = jax.jit(lambda p, t, c, pos: T.decode_step_ragged(
        p, acfg, t, c, pos, cap))
    for i, tok in enumerate(forced):
        pos = jnp.asarray([[0], [len(prompt) + i]], jnp.int32)
        lg, cache = step(params, jnp.asarray([[0], [tok]], jnp.int32),
                         cache, pos)
        out.append(np.asarray(lg[1, -1]))
    return np.stack(out)


def _rel(a, b):
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _case(cfg, P):
    rng = np.random.default_rng(P)
    prompt = rng.integers(1, cfg["vocab_size"], P)
    forced = rng.integers(1, cfg["vocab_size"], STEPS)
    got = _program_logits(cfg, prompt, forced)
    seq = np.concatenate([prompt, forced])
    ref = mistral.logits(cfg, SEED, seq, P - 1, STEPS + 1)
    return got, ref[:STEPS + 1]


@pytest.mark.parametrize("P", [64, 128])
def test_equal_where_the_window_divides_the_prompt(P):
    got, ref = _case(CFG, P)
    assert _rel(got[0], ref[0]) < 1e-4          # the prefill
    assert _rel(got, ref) < 1e-4                # every decode step


def test_unequal_where_it_does_not():
    """sliding_window 64, a 96-token prompt: the program's ring drops keys
    the window still holds, so its decode parts from the reference."""
    got, ref = _case(CFG, 96)
    assert _rel(got[0], ref[0]) < 1e-4
    assert _rel(got[1:], ref[1:]) > 1e-2


def test_block_keep_follows_the_program_masks():
    """The reference's masks, made by the rule the configuration file
    states, are the ones ``launch.serve.prune`` makes."""
    from repro.launch.serve import prune
    # 128-wide blocks: what the program's spec serves at such widths
    cfg = dict(CFG, hidden_size=256, intermediate_size=512,
               pruning={"block": [128, 128], "rate": 0.6})
    keep = mistral.block_keep(cfg, SEED)
    _, masks, _ = prune(F.init(cfg, SEED), F.arch_config(cfg), 0.6)
    for name in F.PRUNED:
        group, leaf = name.split("/")
        m = np.asarray(masks["layers"][group][leaf]["w"])[:, ::128, ::128]
        np.testing.assert_array_equal(m.astype(bool), keep[name])


def test_rule_masks_admit_the_block_a_rounded_threshold_lands_on():
    """At rate 0.78 over 11 blocks the interpolated quantile of two sums
    one float apart rounds onto the upper one, so a float32 threshold
    drops a block the rule in exact arithmetic keeps: the exact mask
    (3 blocks) comes first, the rounded one (2) is admitted beside it."""
    a = np.float32(6.573404312133789)
    b = np.nextafter(a, np.float32(np.inf))
    gs = np.array([1, 2, 3, 4, 5, 6, 5.5, a, b, 7, 8], np.float32)
    rounded = gs > np.asarray(jnp.quantile(jnp.asarray(gs), 0.78))
    assert int(rounded.sum()) == 2
    masks = mistral.rule_masks(gs, 0.78)
    np.testing.assert_array_equal(masks[0], gs >= b)
    assert any(np.array_equal(m, rounded) for m in masks[1:])
    assert {int(m.sum()) for m in masks} <= {1, 2, 3, 4}


def test_rule_masks_are_one_where_no_sum_lies_near_the_threshold():
    """Sums a unit apart, a threshold between two of them: only the exact
    rule's mask, the same as a float32 threshold keeps."""
    distinct = np.random.default_rng(3).permutation(101).astype(np.float32)
    for rate in (0.255, 0.505, 0.6049, 0.783):
        masks = mistral.rule_masks(distinct.reshape(1, 101), rate)
        assert len(masks) == 1
        np.testing.assert_array_equal(
            masks[0], distinct.reshape(1, 101) > jnp.quantile(distinct, rate))


def test_block_keeps_put_the_exact_rule_first():
    cfg = dict(CFG, hidden_size=256, intermediate_size=512,
               pruning={"block": [128, 128], "rate": 0.6})
    keeps = mistral.block_keeps(cfg, SEED)
    assert 1 <= len(keeps) <= mistral.MAX_MASKS
    for name, m in mistral.block_keep(cfg, SEED).items():
        np.testing.assert_array_equal(keeps[0][name], m)
    assert mistral.block_keeps(CFG, SEED) == [None]
