"""The required-work counts against counts made by hand."""
import numpy as np
import pytest

from bench import work

# hidden 4, 2 heads of 2, 1 key/value head, ffn 8, vocab 10, 1 layer,
# window 3: the projections hold 16 + 8 + 8 + 16 + 32 + 32 + 32 = 144
CFG = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 8, "vocab_size": 10, "num_hidden_layers": 1,
       "initializer_range": 0.02, "sliding_window": 3,
       "pruning": {"block": [2, 2], "rate": 0.5}}


def test_prefill_by_hand():
    # keys attended by queries 0..4 under a window of 3: 1+2+3+3+3 = 12
    # flops: 2*5*144 projections + 4*2 heads*2 hd*12 keys + 2*4*10 head
    # bytes: 2 * (144 weights + 40 head + 5*4 embed rows + 2*3*2 cache)
    assert work.prefill(CFG, 5) == (1440 + 192 + 80, 2 * 216)


def test_decode_step_by_hand():
    # 2 slots, each attending a full window of 3 keys:
    # flops: 2 * (2*(144 + 40) + 4*2*2*1*3)
    # bytes: 2 * (144 + 40 + 2 * (2*3*2 read + 2*2 written + 4 embed))
    assert work.decode_step(CFG, 2) == (832, 448)


def test_live_weights_follow_the_masks():
    keep = {n: np.zeros((1, *s), bool) for n, s in {
        "attn/wq": (2, 2), "attn/wk": (2, 1), "attn/wv": (2, 1),
        "attn/wo": (2, 2), "ffn/gate": (2, 4), "ffn/up": (2, 4),
        "ffn/down": (4, 2)}.items()}
    keep["attn/wq"][0, 0, 1] = keep["ffn/down"][0, 3, 0] = True
    live, blocks = work.live_weights(CFG, keep)
    assert live == 8 and sum(blocks.values()) == 2
    # M rows: 2*M*8 flops; bytes 2*(8 weights + M*64 x and y elements),
    # 64 = sum of in+out over the seven projections
    assert work.packed_projections(CFG, keep, 3) == (48, 2 * (8 + 3 * 64))
    full = {n: np.ones_like(k) for n, k in keep.items()}
    assert work.live_weights(CFG, full)[0] == work.live_weights(CFG)[0] \
        == 144


def test_least_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(200, 10, peaks) == (2.0, "compute")
    assert work.least_seconds(100, 30, peaks) == pytest.approx((3.0, "memory"))
