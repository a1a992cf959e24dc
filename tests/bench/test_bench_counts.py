"""The benchmark's operation and byte counts against hand-computed
values at a small size."""

import numpy as np

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench.counts import dense

# d=8, f=16, 2 layers, 2 query heads and 1 kv head of 4, vocab 10
C = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
     "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
     "vocab_size": 10}


def test_matmul_params_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert per_layer == 576
    assert dense.matmul_params(C) == 2 * 576 + 8 * 10


def test_attention_and_decode_flops_by_hand():
    # one query against 5 keys: q.k and p.v, 2 heads of 4, 2 layers
    assert dense.attention_flops(C, 5) == 2 * 2 * (2 * 4 * 5) * 2
    # tokens at positions 0 and 4 attend over 1 and 5 keys
    want = 2 * 2 * 1232 + dense.attention_flops(C, 1) \
        + dense.attention_flops(C, 5)
    assert dense.decode_flops(C, [0, 4]) == want


def test_train_flops_per_token_by_hand():
    # forward and backward: 3 x (2 x params + attention over (T+1)/2)
    seq = 7
    fwd = 2 * 1232 + 4 * 2 * 2 * 4 * (seq + 1) / 2
    assert dense.train_flops_per_token(C, seq) == 3 * fwd

