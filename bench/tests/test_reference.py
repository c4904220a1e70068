"""The plain reference against the program, in float32 on the CPU at a
smoke size: equal on prefill, equal through the decode ring where the
window divides the prompt, and unequal where it does not (ROADMAP R1:
the ring keeps position p at index p % P, its slot only when W divides P).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import system, weights as W
from bench.configs import mistral

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
    acfg = system.arch_config(cfg)
    # the served bfloat16 weights, computed in float32 as the reference is
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    W.init(cfg, SEED))
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
    _, masks, _ = prune(W.init(cfg, SEED), system.arch_config(cfg), 0.6)
    for name in W.PROJ:
        group, leaf = name.split("/")
        m = np.asarray(masks["layers"][group][leaf]["w"])[:, ::128, ::128]
        np.testing.assert_array_equal(m.astype(bool), keep[name])
