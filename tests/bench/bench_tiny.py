"""A benchmark root at test size: the repository's own harness, readers,
peaks and data files, with tiny configurations and traffic added as new
files and a ``BENCHMARK.json`` naming only tiny cells."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (REPO, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

TP_CHAT, CHAT, TRAIN = "tiny.tp4.chat", "tiny.chat", "tiny.train"

# Limits at test size, set from CPU readings at these sizes (the chip
# cells' limits are in bench/limits/).  Over seeds 7-22 the program
# read a widest logit gap of at most 0.038 over 150 served tokens, the
# fp8 control at least 0.15.  Over seeds 7-18 the program read a
# gradient-norm gap of at most 7.8e-4 on the median leaf (fp8: at least
# 3.2e-3) and 1.9e-3 on the worst leaf (fp8: at least 1.1e-2; half the
# batch: 0.27), and a change-norm gap on the worst leaf of at most 0.15
# (bf16 rounding of changes of a few ulps at these widths).  The loss
# gap (program up to 7.2e-3, fp8 from 2.1e-2, half the batch from
# 3.2e-2) has no upper reading and is logged only.
LIMITS = {
    CHAT: {"logit_gap": {"limit": 0.08}},
    TP_CHAT: {"logit_gap": {"limit": 0.08}},
    TRAIN: {"loss_gap": {"limit": None, "compared": False},
            "grad_gap_worst_leaf": {"limit": 5e-3},
            "grad_gap_median_leaf": {"limit": 3e-3},
            "change_gap_worst_leaf": {"limit": 0.3}},
}


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: Path, *, extra_metrics: dict | None = None) -> Path:
    """``extra_metrics``: {name: (BENCHMARK.json entry, reader source)}
    added as new files."""
    root = tmp / "root"
    for d in ("metrics", "traffic", "configs"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d)
    shutil.copy(REPO / "bench" / "peaks.json", root / "bench")
    os.symlink(REPO / "src", root / "src")
    small = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, head_dim=16, vocab_size=256)
    _write(root / "bench/configs/tiny-qk.json", dict(
        _read(REPO / "bench/configs/qwen3-8b.json"), name="tiny-qk",
        num_key_value_heads=4, **small))
    _write(root / "bench/configs/tiny-tied.json", dict(
        _read(REPO / "bench/configs/granite-3-8b.json"), name="tiny-tied",
        num_key_value_heads=2, init={"embed_std": 0.125}, **small))
    _write(root / "bench/traffic/chat_tiny.json", dict(
        _read(REPO / "bench/traffic/chat_lmsys_closed.json"), clients=4,
        slots=4,
        max_seq=64, prompt={"median": 8, "sigma": 0.7, "min": 2, "max": 24},
        output={"median": 6, "sigma": 0.7, "min": 2, "max": 16},
        strata=10, warmup_ticks=16, check={"tokens": 150}))
    _write(root / "bench/traffic/train_tiny.json", dict(
        _read(REPO / "bench/traffic/train_4k_b1.json"), seq_len=32,
        rows_per_chip=2, pool_batches=4, use_kernels=False))
    for cell, lim in LIMITS.items():
        _write(root / "bench/limits" / f"{cell}.json", lim)

    spec = _read(REPO / "BENCHMARK.json")
    spec["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test size"}
        for n in ("tiny-qk", "tiny-tied")]
    spec["workloads"] = [
        {"name": TP_CHAT, "config": "tiny-qk", "traffic": "chat_tiny",
         "chips": 4, "why": "test"},
        {"name": CHAT, "config": "tiny-tied", "traffic": "chat_tiny",
         "chips": 1, "why": "test"},
        {"name": TRAIN, "config": "tiny-tied", "traffic": "train_tiny",
         "chips": 1, "why": "test"}]
    # the serving metrics of the one-chip chat cell apply to both tiny
    # serving cells
    tiny = {"granite-3-8b.chat": [CHAT, TP_CHAT],
            "granite-3-8b.train": [TRAIN]}
    for sec in ("end_to_end", "per_layer"):
        for m in spec[sec]:
            if "workloads" in m:
                m["workloads"] = [t for w in m["workloads"]
                                  for t in tiny.get(w, [])]
    for name, (entry, source) in (extra_metrics or {}).items():
        spec["per_layer"].append(entry)
        (root / "bench/metrics" / f"{name}.py").write_text(source)
    _write(root / "BENCHMARK.json", spec)
    return root


def run_cell(root: Path, cell: str, *, seed: int = 3_000_000_019,
             trace: int = 0, seconds: float = 0.5) -> dict:
    """One in-process run on the CPU; returns the result line."""
    from bench import run as harness
    cell_, devs, peak, log = harness.prepare(cell, root, False, False)
    run, result = harness.execute(cell_, seed, seconds, bool(trace), devs,
                                  peak, log)
    del run
    return result


def control(root: Path, cell: str, seeds=(7, 8)) -> dict:
    from bench import control as ctl
    return ctl.main(["--workload", cell, "--seconds", "1", "--seeds",
                     *map(str, seeds)], root=root,
                    require_accelerator=False, cache=False)
