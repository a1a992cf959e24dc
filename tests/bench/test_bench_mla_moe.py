"""The latent-attention MoE architecture of the benchmark (DeepSeek-V2,
one chip's expert share): its layout against the program, the program
against the plain reference through the latent cache, the shares of a
layer against the uncut layer, its operation counts and reader by hand,
and a tiny serving cell run end to end through the harness."""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench.arch import mla_moe as A
from bench.counts import mla_moe as counts
from bench.loops import serve_closed_routed as routed
from bench.ref import mla_moe as R

CELL = "tiny.mla_moe.chat"
CHIP_CELL = "deepseek-v2.ep8.chat"
PUBLISHED = bench_tiny.REPO / "bench/configs/deepseek-v2.ep8.json"


def tiny(**kw) -> dict:
    """The published configuration at test widths: 16 experts in 4
    groups, top 3 of the best 2 groups, 4 held from expert 4 (group 1),
    one dense and two MoE layers, YaRN as published.  Softmax weights
    over 16 experts are about 10 times those over 160, so the routed
    scale is the published 16 times 16/160: a choice weighs what it
    weighs in the published model."""
    with open(PUBLISHED) as f:
        c = json.load(f)
    c.update(name="tiny-mla-moe", hidden_size=64, intermediate_size=96,
             num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=8,
             moe_intermediate_size=32, n_routed_experts=4, first_expert=4,
             n_group=4, topk_group=2, num_experts_per_tok=3,
             vocab_size=256, routed_scaling_factor=1.6,
             published={"num_hidden_layers": 60, "n_routed_experts": 16})
    c.update(kw)
    return c


def program(c, max_seq=64):
    from repro.models import Model
    return Model(A.model_config(c, max_seq=max_seq))


def test_layout_matches_the_program_at_test_and_published_size():
    c = tiny()
    A.check_layout(c, program(c))
    with open(PUBLISHED) as f:
        full = json.load(f)
    m = program(full, 1536)
    A.check_layout(full, m)          # shapes only (eval_shape)
    shapes = m.param_shapes()
    moe = shapes["layers"]["pos0_moe_self"]["moe"]
    assert moe["router"].shape == (6, 5120, 160)
    assert moe["experts"]["wi_gate"].shape == (6, 20, 5120, 1536)
    assert moe["shared"]["wi_up"].shape == (6, 5120, 3072)
    assert m.cfg.moe.held_range == (0, 20)
    assert m.cfg.rope_scaling.factor == 40


def test_experts_are_seeded_by_their_global_index():
    """Expert 6 is the same tensor in every share that holds it."""
    key = jax.random.key(0)
    a = A.held_experts(tiny(first_expert=4), key, 2)["e_up"]
    b = A.held_experts(tiny(first_expert=6, n_routed_experts=2), key,
                       2)["e_up"]
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))


@pytest.mark.parametrize("seed", [0, 3])
def test_program_through_the_latent_cache_matches_the_reference(seed):
    """Prefill and then decode through the latent cache against the
    reference's full forward pass (per-head keys and values decompressed,
    not absorbed), comparing logits.  In float32 parameters and cache
    the two agree to float32 rounding (5e-5 of the logits' scale at
    these sizes), while the fp8 control misses by over 0.5 of it: the
    bf16 program itself can take another expert where two scores nearly
    tie, which moves a logit by up to the scale, so the bf16 path is
    judged by the served tokens' logit gap (the tiny cell below)."""
    c = tiny()
    m = program(c)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          A.program_params(c, m, seed))
    b, p, t = 3, 7, 32
    toks = np.random.default_rng(seed).integers(0, 256, (b, t))
    toks = jnp.asarray(toks, jnp.int32)
    cache = m.init_cache(b, 64, jnp.float32)
    lg, cache = jax.jit(m.prefill)(params, toks[:, :p], cache)
    out = [lg]
    step = jax.jit(m.decode_step)
    for i in range(p, t - 1):
        lg, cache = step(params, toks[:, i], cache, jnp.int32(i))
        out.append(lg)
    prog = np.stack([np.asarray(o) for o in out], 1)
    padded = jnp.zeros((b, 256), jnp.int32).at[:, :t].set(toks)
    rows = np.repeat(np.arange(b), t - p)
    cols = np.tile(np.arange(p - 1, t - 1), b)
    ref = np.asarray(R.logits_at(seed, c, padded, rows, cols)).reshape(
        prog.shape)
    scale = np.abs(ref).max()
    assert np.abs(prog - ref).max() < 1e-3 * scale
    fp8 = np.asarray(R.logits_at(seed, c, padded, rows, cols,
                                 quant="fp8")).reshape(prog.shape)
    assert np.abs(fp8 - ref).max() > 0.1 * scale


def test_shares_add_up_to_the_uncut_layer():
    """The four shares of 4 experts each, run through the program's MoE
    layer, with the shared experts counted once, add up to the
    reference's layer holding all 16."""
    from repro.models import moe as MOE
    whole = tiny(first_expert=0, n_routed_experts=16)
    key = jax.random.key(9)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 5, 64))
    w = R.seeded(key, A.moe_specs(whole), 1)
    with jax.default_matmul_precision("highest"):
        want = R.moe(w, key, 1, x, whole, None)
    cfg = program(whole).cfg.moe
    parts = []
    for first in range(0, 16, 4):
        share = tiny(first_expert=first)
        p = {"router": w["router"],
             "shared": {"wi_gate": w["s_gate"], "wi_up": w["s_up"],
                        "wo": w["s_down"]},
             "experts": {k: v.astype(jnp.float32) for k, v in zip(
                 ("wi_gate", "wi_up", "wo"),
                 (A.held_experts(share, key, 1)[n]
                  for n in ("e_gate", "e_up", "e_down")))}}
        scfg = dataclasses.replace(cfg, first_expert=first, experts_held=4,
                                   capacity_factor=16.0)
        parts.append(MOE.moe_ffn(p, x, scfg, "swiglu")[0])
    shared = R.swiglu(x, w["s_gate"], w["s_up"], w["s_down"], None)
    got = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# operation counts and the reader
# ---------------------------------------------------------------------------

# d=8, 2 heads; q_lora 4, kv_lora 2, nope 3, rope 2, v 3; dense ffn 10,
# expert 5, 1 shared; 8 experts, 2 held, top 3; 3 layers (1 dense),
# vocab 11
SMALL = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
         "kv_lora_rank": 2, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2,
         "v_head_dim": 3, "intermediate_size": 10,
         "moe_intermediate_size": 5, "n_shared_experts": 1,
         "n_routed_experts": 2, "num_experts_per_tok": 3,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "vocab_size": 11, "published": {"n_routed_experts": 8}}


def test_counts_by_hand():
    # q down 8x4, q up 4x(2x5), latent 8x(2+2), k absorbed 2x(2x3),
    # v absorbed 2x(2x3), out (2x3)x8
    attn = 32 + 40 + 32 + 12 + 12 + 48
    assert counts.attn_params(SMALL) == attn == 176
    # dense ffn 3x8x10; per MoE layer: router 8x8, shared 3x8x5, routed
    # 3 choices x 2/8 held x 3x8x5
    moe = 64 + 120 + 3 * 2 / 8 * 120
    assert counts.matmul_params(SMALL) == 3 * attn + 240 + 2 * moe + 88
    # one query against 4 keys: 2 x 3 layers x 2 heads x (2x2 + 2) x 4
    assert counts.attention_flops(SMALL, 4) == 2 * 3 * 2 * 6 * 4
    want = 2 * 2 * counts.matmul_params(SMALL) \
        + counts.attention_flops(SMALL, 1) + counts.attention_flops(SMALL, 4)
    assert counts.decode_flops(SMALL, [0, 3]) == want


def test_moe_step_mfu_on_a_hand_built_run():
    read = bench_tiny_reader("serve.moe_step_mfu")
    run = types.SimpleNamespace(
        config=SMALL, chips=1, stats={"positions": [0, 3], "window_s": 0.5},
        peak={"bf16_flops_per_s": 1e6})
    flops = counts.decode_flops(SMALL, [0, 3])
    assert read(run) == pytest.approx(100 * flops / 0.5 / 1e6)
    run.stats = {"positions": [], "window_s": 0.5}
    assert read(run) is None


def bench_tiny_reader(name):
    import importlib.util
    path = bench_tiny.REPO / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# a tiny serving cell through the harness
# ---------------------------------------------------------------------------

# CPU readings at this size over 150 served tokens (seeds 7-18),
# program's largest / fp8's smallest: widest gap 0.0486 / 0.232, mean
# gap 4.21e-4 / 0.0103, share off the reference's argmax 3.16% / 9.38%.
# The chip cell's rule: a number is compared where fp8 reads 3 times the
# program or more, at the geometric mean of the two readings.
LIMITS = {"logit_gap": {"limit": None, "compared": False},
          "logit_gap_mean": {"limit": 0.0021},
          "off_argmax_share": {"limit": None, "compared": False}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def make_root(tmp):
    """``bench_tiny``'s root with a tiny MLA+MoE chat cell added, in the
    workloads of the metrics that list the chip's cell."""
    root = bench_tiny.make_root(tmp)
    bench_tiny._write(root / "bench/configs/tiny-mla-moe.json", tiny())
    bench_tiny._write(root / "bench/traffic/chat_tiny_routed.json", dict(
        bench_tiny._read(root / "bench/traffic/chat_tiny.json"),
        kind="serve_closed_routed"))
    bench_tiny._write(root / "bench/limits" / f"{CELL}.json", LIMITS)
    spec = bench_tiny._read(root / "BENCHMARK.json")
    spec["configs"].append({"name": "tiny-mla-moe", "source": "test",
                            "file": "bench/configs/tiny-mla-moe.json",
                            "reduced": [], "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny-mla-moe",
                              "traffic": "chat_tiny_routed", "chips": 1,
                              "why": "test"})
    chip = bench_tiny._read(bench_tiny.REPO / "BENCHMARK.json")
    listed = {m["name"] for m in chip["end_to_end"] + chip["per_layer"]
              if CHIP_CELL in m.get("workloads", ())}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in listed:
            m["workloads"].append(CELL)
    bench_tiny._write(root / "BENCHMARK.json", spec)
    return root


def test_tiny_cell_is_correct_end_to_end(root):
    r = bench_tiny.run_cell(root, CELL)
    assert set(r["metrics"]) == {"tbt_p95_ms", "setup_s"}
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == {"logit_gap_mean"}
    assert r["attempted"] > 0 and r["failed"] == 0


def test_spread_checks_by_hand():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.2, 0.1], [2.0, 2.0, 1.0]])
    # gaps 1, 0, and 0 for a token tied with the best
    out = routed.spread_checks(ref, np.array([2, 0, 1]))
    assert out["logit_gap_mean"] == pytest.approx(1 / 3)
    assert out["off_argmax_share"] == pytest.approx(100 / 3)


def test_kept_reference_passes_logits_through_and_keeps_them():
    seen = []

    class Fake:
        @staticmethod
        def logits_at(seed, c, tokens, rows, cols, quant=None):
            seen.append(quant)
            return np.full((len(rows), 3), float(len(seen)))

    kept = routed.KeptReference(Fake)
    toks, rows, cols = np.zeros((1, 4), np.int32), np.arange(2), np.arange(2)
    assert kept.logits_at(0, {}, toks, rows, cols)[0, 0] == 1.0
    assert kept.logits_at(0, {}, toks, rows, cols, quant="fp8")[0, 0] == 2.0
    assert seen == [None, "fp8"] and set(kept.calls) == {None, "fp8"}
    np.testing.assert_array_equal(kept.calls["fp8"][3], np.full((2, 3), 2.0))


def test_tiny_cell_traced_reads_its_metrics(root):
    r = bench_tiny.run_cell(root, CELL, trace=1)
    assert r["metrics"]["serve.moe_step_mfu"]["value"] > 0
    assert r["metrics"]["serve.moe_step_mfu"]["unit"] == "%"
    assert set(r["metrics"]) == {"serve.moe_step_mfu"}


def test_fp8_control_is_not_correct(root):
    summary = bench_tiny.control(root, CELL, seeds=(7, 8))
    assert summary["verdicts"]["program"] == [True, True]
    assert summary["verdicts"]["fp8"] == [False, False]
