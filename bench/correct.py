"""What decides ``correct``: the timed path's output against the plain
reference, after the window, each number beside its limit in
``bench/limits/<cell>.json``.

Serving: a sample, drawn from the seed, of the requests finished in the
window, the longest among them.  The reference runs once over each
prompt with its served tokens; the number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position (``logit_gap``).  Greedy decoding serves the argmax,
so a sound program reads the rounding of its bfloat16 path.

Training: the first steps, driven in set-up through the timed step,
against the reference following the same batches: the worst step's
cross-entropy gap (``loss_gap``), the gap of first-gradient norms of
the worst leaf and of the median leaf (``grad_gap_worst_leaf``,
``grad_gap_median_leaf``), and the worst leaf's gap of the norms of the
parameters' change over the steps (``change_gap_worst_leaf``).  A
leaf's gap is measured against the larger of its reference norm and the
median leaf's.  Leaves whose reference gradient is under a thousandth
of the median leaf's move by round-off alone and sit out of the change.
Which of these numbers decide ``correct`` is for the cell's limits file
to say.
"""

from __future__ import annotations

import numpy as np

QBLOCK = 256


def serve_sample(cl, rids, seed: int, tokens: int) -> list:
    """(prompt, served) pairs: the longest finished request, then others
    in an order drawn from the seed, until ``tokens`` served tokens."""
    rids = sorted(rids)
    if not rids:
        return []
    size = {r: len(cl.prompt[r]) + len(cl.tokens[r]) for r in rids}
    longest = max(rids, key=lambda r: (size[r], r))
    rest = [r for r in rids if r != longest]
    order = np.random.default_rng([seed, 0xC4]).permutation(len(rest))
    out, n = [], 0
    for r in [longest] + [rest[i] for i in order]:
        out.append((cl.prompt[r], cl.tokens[r]))
        n += len(cl.tokens[r])
        if n >= tokens:
            break
    return out


def pack_sequences(sample):
    """Token matrix [N, T] (T a multiple of QBLOCK) and, per served
    token, its row, the position that predicts it, and the token."""
    total = max(len(p) + len(s) for p, s in sample)
    t = -(-total // QBLOCK) * QBLOCK
    toks = np.zeros((len(sample), t), np.int32)
    rows, cols, tgt = [], [], []
    for i, (p, s) in enumerate(sample):
        seq = np.concatenate([p, s])
        toks[i, :len(seq)] = seq
        n = len(s)
        rows += [i] * n
        cols += list(range(len(p) - 1, len(p) - 1 + n))
        tgt += list(s)
    return toks, np.asarray(rows), np.asarray(cols), np.asarray(tgt)


def logit_gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far the reference logit of each chosen token lies below the
    reference's best at that position."""
    return ref.max(-1) - ref[np.arange(len(chosen)), chosen]


def serve_checks(run, sample, controls=()) -> dict:
    """``logit_gap`` of the served tokens; for each precision of
    ``controls`` also the gap of the tokens it would have chosen."""
    import jax.numpy as jnp
    if not sample:
        run.log("no request finished in the window")
        return {"logit_gap": None}
    toks, rows, cols, tgt = pack_sequences(sample)
    ref = np.asarray(run.ref.logits_at(run.seed, run.config,
                                       jnp.asarray(toks), jnp.asarray(rows),
                                       jnp.asarray(cols)))
    gaps = logit_gaps(ref, tgt)
    out = {"logit_gap": float(gaps.max())}
    run.log(f"compared {len(tgt)} served tokens of {len(sample)} requests "
            f"(longest {toks.shape[1]} positions): widest gap "
            f"{gaps.max():.5f}, mean {gaps.mean():.6f}, "
            f"{int((gaps > 0).sum())} not the reference's argmax")
    for quant in controls:
        low = np.asarray(run.ref.logits_at(run.seed, run.config,
                                           jnp.asarray(toks),
                                           jnp.asarray(rows),
                                           jnp.asarray(cols), quant=quant))
        out[f"logit_gap.{quant}"] = float(
            logit_gaps(ref, low.argmax(-1)).max())
    return out


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf |prog - ref| / max(ref, median ref) over leaf norms."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in ref]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in names}


def moving_leaves(ref_grad: dict) -> set:
    med = float(np.median(list(ref_grad.values())))
    return {n for n, g in ref_grad.items() if g >= 1e-3 * med}


def train_checks(prog: dict, ref: dict, log) -> dict:
    """``prog``/``ref``: {"nll": [...], "grad": {leaf: norm},
    "delta": {leaf: norm}}."""
    loss = max(abs(a - b) for a, b in zip(prog["nll"], ref["nll"]))
    g = leaf_gaps(prog["grad"], ref["grad"])
    d = leaf_gaps(prog["delta"], ref["delta"], moving_leaves(ref["grad"]))
    wg, wd = max(g, key=g.get), max(d, key=d.get)
    log(f"nll program {prog['nll']} reference {ref['nll']}")
    log(f"grad norms program {prog['grad']} reference {ref['grad']}")
    log(f"change norms program {prog['delta']} reference {ref['delta']}")
    log(f"worst grad leaf {wg} {g[wg]:.3e}; worst change leaf {wd} "
        f"{d[wd]:.3e}; left out of the change: "
        f"{sorted(set(ref['delta']) - set(d))}")
    return {"loss_gap": loss, "grad_gap_worst_leaf": g[wg],
            "grad_gap_median_leaf": float(np.median(list(g.values()))),
            "change_gap_worst_leaf": d[wd]}


def half_batch(tokens: np.ndarray):
    """The fault of a step that leaves half of its batch out and takes
    the mean over the rest: the first half of the rows, or of the one
    row's positions."""
    import jax.numpy as jnp
    b, t = tokens.shape
    kept = tokens[:b // 2] if b > 1 else tokens[:, :(t - 1) // 2 + 1]
    return jnp.asarray(kept)
