"""The comparison that decides ``correct``.

After the window closes and the program's state is freed, a sample of the
requests that were served tokens — drawn from the seed, always with the
one that was served the most — is run through the float32 reference
(``reference.py``) over its prompt followed by the tokens the timed path
served. At each served token the reference's best logit minus its logit
for the served token is that token's gap. The number compared is
``mismatch_share``: the share of served tokens whose gap is above 0, that
is, tokens that are not the reference's first choice. Serving is greedy,
so a sound program serves the reference's first choice except where
bfloat16 rounding flips a near tie (of the best logits, or of the
router's top-k, after which the sequence goes on from another token).
The widest and the mean gap are read beside it.

The control reads the same numbers for the reference computed with
float8 operands: at each of the same positions, the gap of the token the
float8 forward puts first.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np


def sample(served: Sequence, count: int, rng: np.random.Generator
           ) -> List:
    """``count`` requests, the one served the most tokens among them.
    A request still decoding when the window closed is compared on the
    tokens it was served: each is an answer as final as a finished
    request's."""
    if not served:
        return []
    order = sorted(served, key=lambda r: (-len(r.tokens), r.index))
    rest = list(order[1:])
    picked = [order[0]]
    if rest and count > 1:
        take = rng.choice(len(rest), min(count - 1, len(rest)),
                          replace=False)
        picked += [rest[i] for i in sorted(take)]
    return picked


def served_gaps(ref_logits: np.ndarray, prompt_len: int,
                served: Sequence[int]) -> np.ndarray:
    """Gap of each served token below the reference's best logit at its
    position (the row of the token before it)."""
    rows = ref_logits[prompt_len - 1: prompt_len - 1 + len(served)]
    best = rows.max(-1)
    return best - rows[np.arange(len(served)), np.asarray(served)]


def compare(params, model_cfg, reqs: Sequence, length: int,
            control: bool = False) -> Dict[str, float]:
    """Runs the reference over each request. Returns, over the sample's
    served tokens, the widest gap (``logit_gap``), the mean gap
    (``mean_gap``) and the share of tokens that are not the reference's
    first choice (``mismatch_share``), with the counts; with ``control``
    the same three readings of the float8 forward's first choices
    (``..._control``)."""
    from . import reference
    gaps, ctl = [], []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        ref = np.asarray(reference.logits(params, model_cfg, seq, length))
        gaps.append(served_gaps(ref, len(r.prompt), r.tokens))
        if control:
            low = np.asarray(reference.logits(params, model_cfg, seq, length,
                                              fp8=True))
            picks = low[len(r.prompt) - 1:].argmax(-1)
            ctl.append(served_gaps(ref, len(r.prompt), picks))
    out = {"requests_compared": len(reqs),
           "tokens_compared": int(sum(len(g) for g in gaps))}
    for key, per in (("", gaps), ("_control", ctl)):
        if per:
            g = np.concatenate(per)
            out["logit_gap" + key] = float(g.max())
            out["mean_gap" + key] = float(g.mean())
            out["mismatch_share" + key] = float((g > 0).mean())
    if not gaps:
        out.update(logit_gap=math.inf, mean_gap=math.inf,
                   mismatch_share=math.inf)
    return out
