"""YaRN rope scaling against the published formulas, written out here
from DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` and its attention's
softmax scale, at the published settings."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import YarnScaling
from repro.models import layers as L
from repro.models import mla as MLA

YARN = configs.get("deepseek-v2-236b").rope_scaling


def published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    inv_freq_mask = 1.0 - np.clip(linear, 0, 1)
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (factor * base ** exps)
    return (freq_inter * (1 - inv_freq_mask)
            + freq_extra * inv_freq_mask), (low, high)


def test_published_settings():
    assert YARN == YarnScaling(factor=40,
                               original_max_position_embeddings=4096,
                               beta_fast=32, beta_slow=1, mscale=0.707,
                               mscale_all_dim=0.707)


def test_frequencies_match_the_published_formula():
    want, (low, high) = published_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    got = np.asarray(L.rope_frequencies(64, 10000.0, YARN))
    np.testing.assert_allclose(got, want, rtol=2e-7)
    plain = np.asarray(L.rope_frequencies(64, 10000.0))
    # dims 0-10 keep their frequency, 23-31 turn 40 times slower
    np.testing.assert_array_equal(got[:11], plain[:11])
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all((got[11:23] < plain[11:23])
                  & (got[11:23] > plain[11:23] / 40))


def test_mscale():
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert L.yarn_mscale(40, 0.707) == m
    assert abs(m - 1.2608) < 1e-4 and abs(m * m - 1.5896) < 1e-4
    assert L.yarn_mscale(1, 0.707) == 1.0
    # the cos/sin factor mscale/mscale_all_dim is 1 at the published
    # settings: the rotation keeps norms
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    r = L.apply_rope(x, jnp.arange(5)[None] * 997, 10000.0, YARN)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_rope_rotates_by_the_yarn_frequencies():
    x = jax.random.normal(jax.random.key(1), (1, 3, 1, 64))
    pos = jnp.array([[0, 5, 3000]])
    got = np.asarray(L.apply_rope(x, pos, 10000.0, YARN))
    inv, _ = published_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    ang = np.asarray(pos, np.float32)[0, :, None, None] * inv
    x1, x2 = np.split(np.asarray(x), 2, -1)
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_latent_attention_scale_takes_mscale_squared():
    """Doubling the keys' latent part doubles every score; the softmax
    scale times mscale^2 is what turns scores into weights."""
    cfg = configs.get_smoke("deepseek-v2-236b").mla
    key = jax.random.key(2)
    p = MLA.init_mla(key, 32, 2, cfg, jnp.float32)
    q_nope = jnp.zeros((1, 1, 2, cfg.nope_head_dim))
    q_rope = jax.random.normal(key, (1, 1, 2, cfg.rope_head_dim))
    c_kv = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, cfg.kv_lora))
    k_rope = jax.random.normal(jax.random.fold_in(key, 2),
                               (1, 4, cfg.rope_head_dim))
    out = {}
    for name, rs in (("plain", None), ("yarn", YARN)):
        out[name] = MLA._attend(p, q_nope, q_rope, c_kv, k_rope, 2, cfg,
                                causal=False, q_offset=3, rope_scaling=rs)
    # the same attention with the query's rope part scaled by mscale^2
    m2 = L.yarn_mscale(40, 0.707) ** 2
    scaled = MLA._attend(p, q_nope, q_rope * m2, c_kv, k_rope, 2, cfg,
                         causal=False, q_offset=3)
    np.testing.assert_allclose(np.asarray(out["yarn"]), np.asarray(scaled),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(out["yarn"]), np.asarray(out["plain"]))


def test_rope_scaling_needs_latent_attention():
    """Only latent attention reads ``rope_scaling``: a config that would
    silently rope without it is refused."""
    dense = configs.get_smoke("qwen3-8b")
    with pytest.raises(ValueError, match="latent attention"):
        dataclasses.replace(dense, rope_scaling=YARN)
    ds = configs.get_smoke("deepseek-v2-236b")
    assert dataclasses.replace(ds, rope_scaling=YARN).rope_scaling == YARN
