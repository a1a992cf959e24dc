"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles.

Every Pallas kernel executes in interpret mode (CPU container; TPU is the
deploy target) and must match its oracle to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [(7,), (128,), (1000,), (64, 64), (3, 129), (2048,), (17, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fused_combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("op", ["add", "max", "min"])
def test_fused_combine_sweep(rng, shape, dtype, op):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    y = jnp.asarray(rng.standard_normal(shape), dtype)
    got = getattr(ops, f"combine_{op}")(x, y)
    want = getattr(ref, f"combine_{op}")(x, y)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("alpha", [1.0, -0.5, 0.125])
def test_fused_combine_mac(rng, alpha):
    x = jnp.asarray(rng.standard_normal((513,)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((513,)), jnp.float32)
    got = ops.combine_mac(x, y, alpha)
    want = ref.combine_mac(x, y, alpha)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# quant_combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nblocks", [1, 3, 64, 65, 200])
def test_quant_combine_sweep(rng, nblocks):
    qa = jnp.asarray(rng.integers(-127, 128, (nblocks, 256)), jnp.int8)
    qb = jnp.asarray(rng.integers(-127, 128, (nblocks, 256)), jnp.int8)
    sa = jnp.asarray(rng.random(nblocks) + 0.01, jnp.float32)
    sb = jnp.asarray(rng.random(nblocks) + 0.01, jnp.float32)
    gq, gs = ops.quant_combine(qa, sa, qb, sb)
    wq, ws = ref.quant_combine(qa, sa, qb, sb)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(gq), np.asarray(wq))


# ---------------------------------------------------------------------------
# topk_accumulate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size,k", [(100, 5), (2048, 32), (5000, 100),
                                    (65536, 512)])
def test_topk_accumulate_sweep(rng, size, k):
    dense = jnp.asarray(rng.standard_normal(size), jnp.float32)
    idx = jnp.asarray(rng.integers(0, size, k), jnp.int32)
    vals = jnp.asarray(rng.standard_normal(k), jnp.float32)
    got = ops.topk_accumulate(dense, idx, vals)
    want = ref.topk_accumulate(dense, idx, vals)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_topk_accumulate_duplicate_indices(rng):
    dense = jnp.zeros((512,), jnp.float32)
    idx = jnp.asarray([3, 3, 3, 100, 100], jnp.int32)
    vals = jnp.ones((5,), jnp.float32)
    got = np.asarray(ops.topk_accumulate(dense, idx, vals))
    assert got[3] == 3.0 and got[100] == 2.0


# ---------------------------------------------------------------------------
# prefix_sum / rglru_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(10,), (256,), (1000,), (300, 8),
                                   (1024, 16), (300, 1030)])
def test_prefix_sum_sweep(rng, shape):
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = ops.prefix_sum(x)
    want = ref.prefix_sum(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# (300, 1100): several feature blocks, the last one ragged
@pytest.mark.parametrize("t,d", [(8, 4), (64, 16), (300, 8), (1024, 4),
                                 (300, 1100)])
def test_rglru_scan_sweep(rng, t, d):
    a = jnp.asarray(rng.random((t, d)) * 0.98, jnp.float32)  # decay in (0,1)
    b = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    got = ops.rglru_scan(a, b)
    want = ref.rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(1, 8), st.integers(0, 2 ** 31 - 1))
def test_rglru_scan_property(t, d, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.random((t, d)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    got = ops.rglru_scan(a, b)
    want = ref.rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# rwkv6_recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,t,k,v", [(1, 16, 8, 8), (2, 64, 16, 16),
                                     (4, 100, 32, 32), (2, 130, 64, 64)])
def test_rwkv6_recurrence_sweep(rng, h, t, k, v):
    r = jnp.asarray(rng.standard_normal((h, t, k)) * 0.5, jnp.float32)
    kk = jnp.asarray(rng.standard_normal((h, t, k)) * 0.5, jnp.float32)
    vv = jnp.asarray(rng.standard_normal((h, t, v)) * 0.5, jnp.float32)
    w = jnp.asarray(0.5 + 0.5 * rng.random((h, t, k)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, k)) * 0.1, jnp.float32)
    go, gs = ops.rwkv6_recurrence(r, kk, vv, w, u)
    for head in range(h):
        wo, ws = ref.rwkv6_recurrence(r[head], kk[head], vv[head], w[head],
                                      u[head])
        np.testing.assert_allclose(np.asarray(go[head]), np.asarray(wo),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gs[head]), np.asarray(ws),
                                   rtol=1e-4, atol=1e-4)


def test_rwkv6_state_carries_across_chunks(rng):
    """t > CHUNK_T forces the VMEM carry path."""
    h, t, k, v = 1, 200, 8, 8
    r = jnp.asarray(rng.standard_normal((h, t, k)) * 0.3, jnp.float32)
    kk = jnp.asarray(rng.standard_normal((h, t, k)) * 0.3, jnp.float32)
    vv = jnp.asarray(rng.standard_normal((h, t, v)) * 0.3, jnp.float32)
    w = jnp.asarray(0.9 * jnp.ones((h, t, k)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, k)) * 0.1, jnp.float32)
    go, _ = ops.rwkv6_recurrence(r, kk, vv, w, u)
    wo, _ = ref.rwkv6_recurrence(r[0], kk[0], vv[0], w[0], u[0])
    np.testing.assert_allclose(np.asarray(go[0]), np.asarray(wo),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# switchops registry binding
# ---------------------------------------------------------------------------

def test_switchops_kernel_binding(rng):
    from repro.core import switchops
    switchops.load_kernels()
    x = jnp.asarray(rng.standard_normal(300), jnp.float32)
    y = jnp.asarray(rng.standard_normal(300), jnp.float32)
    got_k = switchops.get("add")(x, y, use_kernel=True)
    got_r = switchops.get("add")(x, y, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_r),
                               rtol=1e-6)
