"""The comparison that decides ``correct``.

What is compared is what the timed path served: the greedy tokens of
requests the window's engine finished.  A sample of them, drawn from the
seed and holding the longest, is run through the configuration's plain
reference (prompt plus served tokens, teacher-forced), and each served
token's logit is held against the reference's best at its position.  The
widest such gap over the sample (``served_gap_max``, in logits) has the
limit the cell file states, set from readings of sound runs and of the
control (PERF.md).  A sound program serves the reference's argmax except
where two logits lie closer than its bfloat16 rounding; a wrong one, or
one computed a precision lower, serves tokens the reference ranks well
below its best.

The sample covers what each cell's ``why`` names: the first token comes
from the admission prefill, every later one from a decode step through
the slot's 4096-entry ring, which has wrapped from the first decode step
on; 8192-token prompts are in it; and in a pruned cell every projection
runs packed.  A pruned cell's masks are made again by the reference from the
rule its file states; where the float32 rounding of the rule's threshold
decides a block, each mask it allows is tried and the least widest gap
counts (``run.compare``).  Exact counts are compared beside it, with the limit 0:
finished requests that served another number of tokens than asked, and
projections the engine served otherwise than the configuration states.
"""
from __future__ import annotations

import numpy as np

from bench import traffic

MIN_SAMPLE_TOKENS = 256
MAX_SAMPLE = 6


def sample(entries, seed):
    """Finished requests to compare: the one with the longest prompt plus
    output, the longest of each other prompt length, then others drawn
    from the seed until the sample serves ``MIN_SAMPLE_TOKENS`` tokens."""
    done = [e for e in entries if e.status == "finished"]
    if not done:
        return []
    size = lambda e: e.due.prompt_len + len(e.tokens)  # noqa: E731
    picked = [max(done, key=size)]
    for p in sorted({e.due.prompt_len for e in done}):
        best = max((e for e in done if e.due.prompt_len == p), key=size)
        if best not in picked:
            picked.append(best)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    for i in rng.permutation(len(done)):
        if (len(picked) >= MAX_SAMPLE
                or sum(len(e.tokens) for e in picked) >= MIN_SAMPLE_TOKENS):
            break
        if done[i] not in picked:
            picked.append(done[i])
    return picked


def cases(entries, seed, vocab):
    """The sample as (prompt ids, served tokens) pairs."""
    return [(traffic.prompt_ids(seed, e.due, vocab),
             np.asarray(e.tokens, np.int32)) for e in sample(entries, seed)]


def gaps(ref_logits, tokens):
    """Per position: how far the reference's logit of ``tokens`` lies
    below its best."""
    ref = np.asarray(ref_logits[:len(tokens)], np.float64)
    return ref.max(axis=1) - ref[np.arange(len(tokens)), tokens]


def served_gaps(reference, cfg, seed, cases, n_out, keep, controls=()):
    """For each (prompt, served tokens) case: the gaps of the served tokens
    and, for each precision in ``controls``, of the tokens that precision
    puts first at the same positions."""
    out = []
    for prompt, served in cases:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        ref = reference.logits(cfg, seed, seq, len(prompt) - 1, n_out, keep)
        row = {"served": gaps(ref, served)}
        for prec in controls:
            low = reference.logits(cfg, seed, seq, len(prompt) - 1, n_out,
                                   keep, precision=prec)
            row[prec] = gaps(ref, low[:len(served)].argmax(axis=1))
        out.append(row)
    return out


def verdict(values, limits):
    """{name: {"value", "limit"}} and whether every value is within its
    limit.  A value that could not be read fails."""
    checks = {k: {"value": values.get(k), "limit": limits[k]}
              for k in limits}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return checks, ok
