"""The harness: its refusals, its discovery of cells, metrics and
configurations by name, and whole runs at test size on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny
from bench_tiny import CHAT, TP_CHAT, TRAIN


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-8b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_an_accelerator():
    p = _cli(bench_tiny.REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(bench_tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.REPO / "bench", tmp_path / "bench")
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    echo = {"name": "tiny.echo", "unit": "1", "better": "higher",
            "source": "program_counter", "layer": "test layer",
            "moves": "decode_tokens_per_s", "workloads": [CHAT]}
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"),
                                extra_metrics={"tiny.echo": (echo, (
                                    "def read(run):\n"
                                    "    return float(run.stats['ticks'])\n"))})


def _check_line(result, cell_metrics):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == set(cell_metrics)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)


def test_serve_cell_runs_and_is_correct(root):
    r = bench_tiny.run_cell(root, CHAT)
    _check_line(r, ["decode_tokens_per_s", "tbt_p95_ms", "ttft_p95_ms",
                    "setup_s"])
    assert r["correct"], r["checks"]
    assert r["metrics"]["decode_tokens_per_s"]["unit"] == "tokens/s"


def test_tensor_parallel_serve_cell_runs_and_is_correct(root):
    r = bench_tiny.run_cell(root, TP_CHAT)
    assert r["correct"], r["checks"]


def test_train_cell_runs_and_is_correct(root):
    r = bench_tiny.run_cell(root, TRAIN)
    _check_line(r, ["train_tokens_per_s", "setup_s"])
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == {"grad_gap_worst_leaf",
                                "grad_gap_median_leaf",
                                "change_gap_worst_leaf"}


def test_metric_config_and_traffic_added_as_files_are_found(root):
    """tiny-tied, chat_tiny and the tiny.echo reader exist only as new
    files and BENCHMARK.json entries; a traced run reads them."""
    r = bench_tiny.run_cell(root, CHAT, trace=1)
    assert r["metrics"]["tiny.echo"]["value"] > 0
    assert r["metrics"]["serve.host_ms_per_tick"]["unit"] == "ms"
    # no device planes on the CPU: the device readers stay silent
    assert "serve.device_idle_share" not in r["metrics"]
    assert r["device"]["window_s"] > 0
