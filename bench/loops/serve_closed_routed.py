"""Closed-loop chat serving of a model that routes tokens to experts:
``serve_closed``'s loop, unchanged, judged also by numbers that a few
flipped routes cannot move.

Where two router scores nearly tie, a bfloat16 program can take another
expert than the float32 reference, and that position's logits move by
up to a logit or more.  The widest gap (``logit_gap``) then reads the
worst of a handful of such flips, which a sound program and the fp8
control both reach.  The mean gap over the served tokens
(``logit_gap_mean``) and the share of them that are not the reference's
argmax (``off_argmax_share``) move with every position's rounding, and
with a few flips hardly at all.  Each is read for the controls too, from
the same reference logits as ``logit_gap``; which of them decide
``correct`` is for the cell's limits file to say.
"""

from __future__ import annotations

import numpy as np

from bench import correct
from bench.loops import serve_closed


class KeptReference:
    """The run's reference, keeping each call's positions and logits."""

    def __init__(self, ref):
        self.ref = ref
        self.calls: dict = {}          # quant (None: float32) -> arrays

    def logits_at(self, seed, c, tokens, rows, cols, quant=None):
        out = self.ref.logits_at(seed, c, tokens, rows, cols, quant=quant)
        self.calls[quant] = (np.asarray(tokens), np.asarray(rows),
                             np.asarray(cols), np.asarray(out))
        return out


def spread_checks(ref: np.ndarray, chosen: np.ndarray) -> dict:
    """Mean logit gap and share off the reference's argmax (%)."""
    gaps = correct.logit_gaps(ref, chosen)
    return {"logit_gap_mean": float(gaps.mean()),
            "off_argmax_share": 100.0 * float((gaps > 0).mean())}


def run(run, *, t_start: float, keep_trace=None) -> None:
    kept = run.ref = KeptReference(run.ref)
    try:
        serve_closed.run(run, t_start=t_start, keep_trace=keep_trace)
    finally:
        run.ref = kept.ref
    if None not in kept.calls:         # nothing finished in the window
        run.checks.update(logit_gap_mean=None, off_argmax_share=None)
        return
    tokens, rows, cols, ref = kept.calls.pop(None)
    out = spread_checks(ref, tokens[rows, cols + 1])
    for quant, (*_, low) in kept.calls.items():
        out.update({f"{k}.{quant}": v for k, v in
                    spread_checks(ref, low.argmax(-1)).items()})
    run.log(f"served tokens' mean gap {out['logit_gap_mean']:.6f}, "
            f"{out['off_argmax_share']:.2f}% off the reference's argmax")
    run.checks.update(out)
