"""Traffic and weights are a pure function of ``--seed``."""

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bench_tiny
from bench import gen
from bench import weights as W
from bench.arch import dense_gqa as A

BIG = 3_000_000_019          # a seed over 31 bits


def _traffic(name):
    with open(bench_tiny.REPO / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


CHAT = "chat_lmsys_closed"


def _sizes(t, seed, n):
    return [(len(p), o) for p, o in
            itertools.islice(gen.chat_requests(t, seed, 1000), n)]


def test_chat_sizes_deterministic_per_seed():
    """Every seed serves the same sizes in the same order."""
    t = _traffic(CHAT)
    assert _sizes(t, BIG, 300) == _sizes(t, BIG, 300)
    assert _sizes(t, BIG, 300) == _sizes(t, BIG + 1, 300) == _sizes(t, 0, 300)
    assert _sizes(t, 0, 300) == list(itertools.islice(gen.chat_sizes(t), 300))
    other = dict(t, order_seed=t["order_seed"] + 1)
    assert _sizes(other, BIG, 300) != _sizes(t, BIG, 300)


def test_chat_sizes_same_set_per_block_for_every_seed():
    t = _traffic(CHAT)
    k = t["strata"]
    sizes = list(itertools.islice(gen.chat_sizes(t), 3 * k))
    blocks = [sizes[i * k:(i + 1) * k] for i in range(3)]
    for block in blocks:
        assert sorted(p for p, _ in block) == sorted(p for p, _ in blocks[0])
        assert sorted(o for _, o in block) == sorted(o for _, o in blocks[0])
        assert all(t["prompt"]["min"] <= p <= t["prompt"]["max"]
                   and t["output"]["min"] <= o <= t["output"]["max"]
                   for p, o in block)
    assert blocks[0] != blocks[1]
    # the strata midpoints follow the lognormal: the median stratum
    p = sorted(p for p, _ in blocks[0])
    assert abs(p[k // 2] - t["prompt"]["median"]) / t["prompt"]["median"] < 0.1
    # the longest request fits the engine's cache
    assert t["prompt"]["max"] + t["output"]["max"] < t["max_seq"]


def test_chat_lengths_have_the_published_means():
    """LMSYS-Chat-1M, Table 1: 69.5 tokens per prompt and 214.5 per
    response on average; the strata's means are fitted to them."""
    t = _traffic(CHAT)
    block = list(itertools.islice(gen.chat_sizes(t), t["strata"]))
    assert abs(np.mean([p for p, _ in block]) - 69.5) < 0.5
    assert abs(np.mean([o for _, o in block]) - 214.5) < 0.5


def test_chat_request_tokens_deterministic():
    t = _traffic(CHAT)
    a = list(itertools.islice(gen.chat_requests(t, BIG, 1000), 5))
    b = list(itertools.islice(gen.chat_requests(t, BIG, 1000), 5))
    c = list(itertools.islice(gen.chat_requests(t, BIG + 1, 1000), 5))
    for (pa, oa), (pb, ob), (pc, oc) in zip(a, b, c):
        assert oa == ob == oc and np.array_equal(pa, pb)
        assert len(pc) == len(pa) and not np.array_equal(pa, pc)
        assert pa.dtype == np.int32 and pa.min() >= 0 and pa.max() < 1000


def test_bigram_batches_deterministic_and_follow_the_table():
    t = dict(_traffic("train_4k_b1"), seq_len=16)
    key = W.root_key(BIG, W.DATA)
    a = gen.bigram_batches(key, t, 50, 3, 2)
    b = gen.bigram_batches(key, t, 50, 3, 2)
    c = gen.bigram_batches(W.root_key(BIG + 1, W.DATA), t, 50, 3, 2)
    assert len(a) == 2 and a[0].shape == (3, 17)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))
    succ, _ = gen.bigram_table(50, t["data"]["branching"],
                               t["data"]["table_seed"])
    succ, tok = np.asarray(succ), np.asarray(a[0])
    for r in range(3):
        for i in range(16):
            assert tok[r, i + 1] in succ[tok[r, i]]


def test_tensor_same_under_vmap_and_per_layer():
    key = W.root_key(BIG, W.WEIGHTS)
    st = W.stacked(key, "wq", 3, (8, 6), 0.25)
    for l in range(3):
        assert np.array_equal(np.asarray(st[l]),
                              np.asarray(W.tensor(key, "wq", l, (8, 6), 0.25)))
    x = np.asarray(st, np.float32)
    assert st.dtype == jnp.bfloat16 and abs(x.std() - 0.25) < 0.05


def test_seed_over_32_bits_is_its_own_stream():
    a = W.tensor(W.root_key(BIG, W.WEIGHTS), "x", 0, (16,), 1.0)
    b = W.tensor(W.root_key(BIG % 2**32 + 2**32, W.WEIGHTS), "x", 0,
                 (16,), 1.0)
    c = W.tensor(W.root_key(BIG, W.DATA), "x", 0, (16,), 1.0)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        W.root_key(-1, W.WEIGHTS)


@pytest.mark.parametrize("name", ["qwen3-8b", "granite-3-8b",
                                  "granite-3-8b.serve"])
def test_seeded_params_have_the_program_layout(name):
    from repro.models import Model
    with open(bench_tiny.REPO / "bench" / "configs" / f"{name}.json") as f:
        c = json.load(f)
    c = dict(c, hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=128)
    model = Model(A.model_config(c, max_seq=64))
    A.check_layout(c, model)
    params = A.program_params(c, model, BIG)
    flat = A.from_program(c, params)
    assert set(flat) == {n for n, _, _ in A.global_specs(c)
                         + A.layer_specs(c)}
    again = A.canonical(c, W.root_key(BIG, W.WEIGHTS))
    for n in flat:
        assert np.array_equal(np.asarray(flat[n]), np.asarray(again[n]))


def test_sharded_generation_equals_unsharded():
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), ("tp",))
    key = W.root_key(BIG, W.WEIGHTS)
    make = lambda k: W.stacked(k, "w_up", 2, (16, 32), 0.1)  # noqa: E731
    sharded = jax.jit(make, out_shardings=NamedSharding(
        mesh, P(None, None, "tp")))(key)
    assert np.array_equal(np.asarray(sharded), np.asarray(make(key)))
