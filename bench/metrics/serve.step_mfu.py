"""Model operations of the ticks in the traced window (every active
slot's token at its own context length) over the window, as a share of
the chips' bf16 peak."""

from bench.counts import dense


def read(run):
    s = run.stats
    if not s.get("positions"):
        return None
    flops = dense.decode_flops(run.config, s["positions"])
    return 100.0 * flops / s["window_s"] / (
        run.chips * run.peak["bf16_flops_per_s"])
