"""The training cell on four chips (``granite-3-8b.dp4.train`` at test
size): the harness's train loop over a four-device ``data`` mesh, one
set of rows a device, correct against the reference following all the
rows, and the controls judged not correct by the same limits."""

import pytest

import bench_tiny

CELL = "tiny.dp4.train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    spec = bench_tiny._read(root / "BENCHMARK.json")
    spec["workloads"].append({"name": CELL, "config": "tiny-tied",
                              "traffic": "train_tiny", "chips": 4,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if bench_tiny.TRAIN in m.get("workloads", ()):
            m["workloads"].append(CELL)
    bench_tiny._write(root / "BENCHMARK.json", spec)
    bench_tiny._write(root / "bench/limits" / f"{CELL}.json",
                      bench_tiny.LIMITS[bench_tiny.TRAIN])
    return root


def test_four_chip_train_cell_is_correct(root):
    r = bench_tiny.run_cell(root, CELL)
    assert r["device"]["count"] >= 4
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_four_chip_controls_are_not_correct(root):
    summary = bench_tiny.control(root, CELL, seeds=(7,))
    assert summary["verdicts"]["program"] == [True]
    assert summary["verdicts"]["fp8"] == [False]
    assert summary["verdicts"]["half_batch"] == [False]


def test_every_cell_is_its_own_pair_of_config_and_traffic():
    """The four-chip cell runs ``train_4k_b1``'s parameters under a name
    of its own, as the one-chip cell already pairs that name with the
    same configuration."""
    spec = bench_tiny._read(bench_tiny.REPO / "BENCHMARK.json")
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    cells = {w["name"]: w for w in spec["workloads"]}
    traffic = bench_tiny.REPO / "bench" / "traffic"
    one, four = (bench_tiny._read(traffic / f"{cells[c]['traffic']}.json")
                 for c in ("granite-3-8b.train", "granite-3-8b.dp4.train"))
    one.pop("why"), four.pop("why")
    assert one == four
