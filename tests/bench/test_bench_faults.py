"""A run with the timed path broken underneath comes out not correct,
once for each fault the cell can have."""

import pytest

import bench_tiny
from bench_tiny import CHAT, TP_CHAT, TRAIN


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _wrap_decode(monkeypatch, change):
    from repro.models import decode as D
    orig = D.decode_step

    def broken(params, cfg, token, cache, index, **kw):
        lg, new = orig(params, cfg, token, cache, index, **kw)
        return change(lg, cache, new)
    monkeypatch.setattr(D, "decode_step", broken)


def token_altered(monkeypatch):
    # every tick's token becomes id 0
    _wrap_decode(monkeypatch, lambda lg, old, new: (
        lg.at[:, 0].set(lg.max(-1) + 1.0), new))


def cache_unchanged(monkeypatch):
    _wrap_decode(monkeypatch, lambda lg, old, new: (lg, old))


def exchange_left_out(monkeypatch):
    from repro.serve import collectives
    monkeypatch.setattr(collectives.CompiledTPHook, "_all_reduce",
                        lambda self, x: x)


def state_unchanged(monkeypatch):
    from repro.train import optimizer
    orig = optimizer.adamw

    def adamw(*a, **kw):
        opt = orig(*a, **kw)
        return optimizer.Optimizer(opt.init, lambda g, s, p, step: (p, s),
                                   "adamw")
    monkeypatch.setattr(optimizer, "adamw", adamw)


def half_batch(monkeypatch):
    from repro.train import step
    orig = step._accumulate_grads

    def half(model, params, batch, microbatches, mesh):
        tok = batch["tokens"]
        return orig(model, params, {"tokens": tok[:tok.shape[0] // 2]},
                    microbatches, mesh)
    monkeypatch.setattr(step, "_accumulate_grads", half)


@pytest.mark.parametrize("cell,fault", [
    (CHAT, token_altered), (CHAT, cache_unchanged),
    (TP_CHAT, exchange_left_out), (TP_CHAT, token_altered),
    (TRAIN, state_unchanged), (TRAIN, half_batch)],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_not_correct(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = bench_tiny.run_cell(root, cell)
    assert r["correct"] is False, r["checks"]
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in r["checks"].values())
