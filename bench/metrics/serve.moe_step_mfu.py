"""Model operations of the ticks in the traced window of a latent-
attention MoE cell (every active slot's token at its own context length,
the held experts' expected share of the routed ones) over the window, as
a share of the chips' bf16 peak."""

from bench.counts import mla_moe


def read(run):
    s = run.stats
    if not s.get("positions"):
        return None
    flops = mla_moe.decode_flops(run.config, s["positions"])
    return 100.0 * flops / s["window_s"] / (
        run.chips * run.peak["bf16_flops_per_s"])
