"""The control, the plain reference one precision below the
configuration's bfloat16 put in the program's place, comes out not
correct at test size, while the program's own readings hold."""

import pytest

import bench_tiny
from bench_tiny import CHAT, LIMITS, TRAIN


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_serve_control_fails_where_the_program_holds(root):
    s = bench_tiny.control(root, CHAT)
    lim = LIMITS[CHAT]["logit_gap"]["limit"]
    assert s["logit_gap"] <= lim
    assert s["logit_gap.fp8"] > lim
    # the harness judges the control as it judges the program
    assert all(s["verdicts"]["program"])
    assert not any(s["verdicts"]["fp8"])


def test_train_control_and_half_batch_fail_where_the_program_holds(root):
    s = bench_tiny.control(root, TRAIN)
    lim = {k: v["limit"] for k, v in LIMITS[TRAIN].items()
           if v.get("compared", True)}
    assert all(s[k] <= lim[k] for k in lim)
    for bad in ("fp8", "half_batch"):
        assert any(s[f"{k}.{bad}"] > lim[k] for k in lim), (bad, s)
        assert not any(s["verdicts"][bad]), (bad, s)
    assert all(s["verdicts"]["program"])


def test_control_verdicts_hold_each_reading_against_the_programs_limit():
    from bench import control
    limits = {"gap": {"limit": 0.1}, "loss": {"limit": None,
                                              "compared": False}}
    v = control.verdicts({"gap": 0.05, "loss": 9.0, "gap.fp8": 0.3,
                          "loss.fp8": 0.0, "gap.fault": 0.1}, limits)
    assert v["program"]["correct"] and v["fault"]["correct"]
    assert not v["fp8"]["correct"]
    assert v["fp8"]["checks"] == {"gap": {"value": 0.3, "limit": 0.1}}
    # a reading with no limit, or a limit with no reading, never holds
    assert not control.verdicts({"gap": 0.05}, {"gap": {"limit": None}})[
        "program"]["correct"]
    assert not control.verdicts({"x": 0.0}, limits)["program"]["correct"]
