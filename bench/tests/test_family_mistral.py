"""The Mistral family module: its work counts against counts made by hand
and against readings pinned before the harness reached the model through
family modules, and its weights against a digest pinned then."""
import hashlib

import jax
import numpy as np
import pytest

from bench import spec
from bench.families import mistral as F
from bench.tests import tiny

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
    assert F.prefill(CFG, 5) == (1440 + 192 + 80, 2 * 216)


@pytest.mark.parametrize("lengths", [[3, 3], [4, 9]])
def test_decode_step_by_hand(lengths):
    # 2 slots, each attending a full window of 3 keys:
    # flops: 2 * (2*(144 + 40) + 4*2*2*1*3)
    # bytes: 2 * (144 + 40 + 2 * (2*3*2 read + 2*2 written + 4 embed))
    assert F.decode_step(CFG, lengths) == (832, 448)


def test_decode_step_follows_each_slot_length():
    # slots of 1 and 2 entries, under the window: 3 keys in all
    # flops: 2*2*(144 + 40) + 4*2*2*1*3; bytes: 2 * (144 + 40 + 2*1*3*2
    # read + 2 * (2*2 written + 4 embed))
    assert F.decode_step(CFG, [1, 2]) == (736 + 48, 2 * (184 + 12 + 16))


def test_live_weights_follow_the_masks():
    keep = {n: np.zeros((1, *s), bool) for n, s in {
        "attn/wq": (2, 2), "attn/wk": (2, 1), "attn/wv": (2, 1),
        "attn/wo": (2, 2), "ffn/gate": (2, 4), "ffn/up": (2, 4),
        "ffn/down": (4, 2)}.items()}
    keep["attn/wq"][0, 0, 1] = keep["ffn/down"][0, 3, 0] = True
    live, blocks = F.live_weights(CFG, keep)
    assert live == 8 and sum(blocks.values()) == 2
    # M rows: 2*M*8 flops; bytes 2*(8 weights + M*64 x and y elements),
    # 64 = sum of in+out over the seven projections
    assert F.packed_projections(CFG, keep, 3) == (48, 2 * (8 + 3 * 64))
    full = {n: np.ones_like(k) for n, k in keep.items()}
    assert F.live_weights(CFG, full)[0] == F.live_weights(CFG)[0] == 144


# Read from ``bench.work`` as it was before the work counts moved into the
# family module, for the doc4k configuration, with the live blocks per
# projection that ``block_keep`` keeps at rate 0.6 over 6 layers: of a
# projection's n blocks, the n - 1 - floor(0.6 (n - 1)) above the
# interpolated quantile, at any seed whose block norms are distinct.  The
# counts depend on the masks only through these sums.
BLOCKS = {"attn/wq": 2458, "attn/wk": 614, "attn/wv": 614, "attn/wo": 2458,
          "ffn/gate": 8602, "ffn/up": 8602, "ffn/down": 8602}
PREFILL = {4096: (5113353601024, 1443299328),
           8192: (11050877452288, 1476853760)}
DECODE_16_FULL_WINDOWS = (27387756544, 2920218624)
PACKED = {16: (16751001600, 1062666240), 4096: (4288256409600, 5073469440),
          8192: (8576512819200, 9100001280)}


@pytest.fixture(scope="module")
def doc4k():
    cfg = spec.load("mistral7b-bsr60.doc4k").config
    s = F.sizes(cfg)
    bk, bn = cfg["pruning"]["block"]
    keep = {}
    for n, (K, N) in F.proj_shapes(s).items():
        m = np.zeros(s["L"] * (K // bk) * (N // bn), bool)
        m[:BLOCKS[n]] = True
        keep[n] = m.reshape(s["L"], K // bk, N // bn)
    return cfg, keep


def test_pinned_prefill(doc4k):
    cfg, keep = doc4k
    assert {P: F.prefill(cfg, P, keep) for P in PREFILL} == PREFILL


@pytest.mark.parametrize("lengths", [[4096] * 16, [4097, 8192, 8200] * 5
                                     + [5000]])
def test_pinned_decode_step(doc4k, lengths):
    """Every doc4k slot holds at least one window, so the step's work is
    the one pinned for 16 full windows, whatever the lengths."""
    cfg, keep = doc4k
    assert F.decode_step(cfg, lengths, keep) == DECODE_16_FULL_WINDOWS


def test_pinned_packed_projections(doc4k):
    cfg, keep = doc4k
    assert {M: F.packed_projections(cfg, keep, M) for M in PACKED} == PACKED


def _digest(tree):
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    (7, "f3440db23501a39b24a28752feb054b10fa78cb7e260c9d22093744adda912f8"),
    (2**32 + 77,
     "551900d19719e0cb9dd6675db35b6e50609df6d4fbdc8d4ecdf5b890f1f677eb")])
def test_init_is_pinned(seed, digest):
    """The tiny configuration's whole tree, bit for bit, as the parent
    tree's ``bench.weights.init`` drew it: the leaf ids, and so the keys,
    are unchanged."""
    assert _digest(F.init(tiny.CONFIG, seed)) == digest

