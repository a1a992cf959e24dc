"""Model operations of the tokens trained in the traced window (forward
and backward, no recomputation) over the window, as a share of the
chips' bf16 peak."""

from bench.counts import dense


def read(run):
    s = run.stats
    if not s.get("tokens"):
        return None
    flops = s["tokens"] * dense.train_flops_per_token(
        run.config, run.traffic["seq_len"])
    return 100.0 * flops / s["window_s"] / (
        run.chips * run.peak["bf16_flops_per_s"])
