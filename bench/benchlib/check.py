"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it served, drawn
from the run's seed and always holding the longest, is run through the
configuration's plain reference, teacher-forced on the tokens the program
served.  Besides the longest it holds at least two requests drawn from
the seed, so different seeds compare different requests and decode rows.  At each served token the gap is the reference's best logit minus
its logit for the served token: zero where the program served the
reference's argmax, small where rounding turned a near-tie, large where
the served path computed something else.  The number compared is the
widest gap over the sample.

The control (``quant="fp8"``) is the reference computed one precision step
below the configuration's bf16; its number is the gap, in the float32
reference, of the token that the lower precision puts first.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

MIN_TOKENS = 300       # served tokens compared per run, at least
MIN_REQUESTS = 3       # requests compared per run, at least


def sample(served: Dict[int, List[int]], prompt_len: Dict[int, int],
           seed: int, min_tokens: int = MIN_TOKENS,
           min_requests: int = MIN_REQUESTS) -> List[int]:
    """The request with the longest sequence, then others in an order
    drawn from ``seed``, until ``min_tokens`` served tokens and
    ``min_requests`` requests are in."""
    rids = sorted(r for r in served if served[r])
    if not rids:
        return []
    longest = max(rids, key=lambda r: (prompt_len[r] + len(served[r]), r))
    rest = [r for r in rids if r != longest]
    order = np.random.default_rng(int(seed) % 2**64 + 1).permutation(
        len(rest))
    out, n = [longest], len(served[longest])
    for i in order:
        if n >= min_tokens and len(out) >= min_requests:
            break
        out.append(rest[i])
        n += len(served[rest[i]])
    return out


def _teacher_forced(prompt: np.ndarray, tokens: List[int]):
    """The sequence the reference reads and the rows whose next-token
    logits predict each served token."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return seq.astype(np.int32), rows


def gaps(ref, weights, conf_model: dict, prompts: Dict[int, np.ndarray],
         served: Dict[int, List[int]], rids: List[int],
         control: bool = False) -> dict:
    """Widest gap over the sampled requests' served tokens (the program),
    and with ``control`` the widest gap of the fp8 reference's first
    choice at the same positions."""
    out = {"tokens": 0, "gap": 0.0, "argmax_served": 0}
    if control:
        out["control_gap"] = 0.0
    for rid in rids:
        toks = served[rid]
        seq, rows = _teacher_forced(prompts[rid], toks)
        lg = ref.logits(weights, conf_model, seq, rows)
        best = lg.max(axis=1)
        got = lg[np.arange(len(toks)), np.asarray(toks)]
        g = best - got
        out["tokens"] += len(toks)
        out["gap"] = max(out["gap"], float(g.max()))
        out["argmax_served"] += int((g == 0).sum())
        if control:
            lq = ref.logits(weights, conf_model, seq, rows, quant="fp8")
            pick = lq.argmax(axis=1)
            cg = best - lg[np.arange(len(toks)), pick]
            out["control_gap"] = max(out["control_gap"], float(cg.max()))
    return out
