"""Multi-head Latent Attention (DeepSeek-V2).

KV is compressed to a low-rank latent ``c_kv`` [B, T, kv_lora] plus a shared
rope key [B, T, rope_dim]; per-head K/V are never decompressed (the
up-projections are absorbed into the query and the output).  The decode
cache stores only (c_kv, k_rope): 512+64 values a token a layer for
DeepSeek-V2 against 2·128·128 for its 128 heads as plain MHA, 57× less.

Heads here use separate "nope" (content) and "rope" (position) sub-keys,
matching the published architecture.  The rope part may carry YaRN
scaling (``ModelConfig.rope_scaling``), which also raises the softmax
scale by ``mscale(factor, mscale_all_dim)**2``.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import layer_slab, write_token
from repro.models.config import MLAConfig

PyTree = Any


def init_mla(key, d_model: int, n_heads: int, cfg: MLAConfig,
             dtype=jnp.bfloat16) -> PyTree:
    ks = jax.random.split(key, 8)
    qdim = cfg.nope_head_dim + cfg.rope_head_dim
    p = {
        "w_dkv": L.dense_init(ks[0], d_model, cfg.kv_lora + cfg.rope_head_dim,
                              dtype),
        "kv_norm": L.init_rmsnorm(cfg.kv_lora),
        "w_uk": L.dense_init(ks[1], cfg.kv_lora,
                             n_heads * cfg.nope_head_dim, dtype),
        "w_uv": L.dense_init(ks[2], cfg.kv_lora,
                             n_heads * cfg.v_head_dim, dtype),
        "wo": L.dense_init(ks[3], n_heads * cfg.v_head_dim, d_model, dtype),
    }
    if cfg.q_lora:
        p["w_dq"] = L.dense_init(ks[4], d_model, cfg.q_lora, dtype)
        p["q_norm"] = L.init_rmsnorm(cfg.q_lora)
        p["w_uq"] = L.dense_init(ks[5], cfg.q_lora, n_heads * qdim, dtype)
    else:
        p["wq"] = L.dense_init(ks[4], d_model, n_heads * qdim, dtype)
    return p


def _queries(p, x, n_heads, cfg, positions, rope_theta, rope_scaling):
    b, t, _ = x.shape
    qdim = cfg.nope_head_dim + cfg.rope_head_dim
    if "w_dq" in p:
        q = L.rmsnorm(p["q_norm"], x @ p["w_dq"]) @ p["w_uq"]
    else:
        q = x @ p["wq"]
    q = q.reshape(b, t, n_heads, qdim)
    q_nope = q[..., :cfg.nope_head_dim]
    q_rope = L.apply_rope(q[..., cfg.nope_head_dim:], positions, rope_theta,
                          rope_scaling)
    return q_nope, q_rope


def _latents(p, x, cfg, positions, rope_theta, rope_scaling):
    b, t, _ = x.shape
    dkv = x @ p["w_dkv"]
    c_kv = L.rmsnorm(p["kv_norm"], dkv[..., :cfg.kv_lora])
    k_rope = L.apply_rope(dkv[..., cfg.kv_lora:][:, :, None, :],
                          positions, rope_theta, rope_scaling)[:, :, 0, :]
    return c_kv, k_rope


def _attend(p, q_nope, q_rope, c_kv, k_rope, n_heads, cfg, *,
            causal, q_offset, kv_len=None, chunk=1024, unroll=False,
            rope_scaling=None):
    """Latent-space attention via the absorbed-projection trick.

    score = q_nope·(W_uk c) + q_rope·k_rope = (W_uk^T q_nope ⊕ q_rope)·(c ⊕
    k_rope) — i.e. an MQA flash attention with a single shared "key"
    (c_kv ⊕ k_rope) and "value" c_kv.  Per-head K/V are never materialized;
    the context is lifted through W_uv after the softmax.  Reuses the
    KV-chunked online-softmax kernel, so 32k prefill stays O(Tq·chunk).
    """
    b, tq, h, _ = q_nope.shape
    scale = 1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)
    if rope_scaling is not None:
        scale *= L.yarn_mscale(rope_scaling.factor,
                               rope_scaling.mscale_all_dim) ** 2
    w_uk = p["w_uk"].reshape(cfg.kv_lora, n_heads, cfg.nope_head_dim)
    q_lat = jnp.einsum("bqhd,khd->bqhk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    q_eff = jnp.concatenate([q_lat,
                             q_rope.astype(jnp.float32)], axis=-1)
    k_eff = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
    v_eff = c_kv[:, :, None, :]
    from repro.models.attention import flash_attention
    ctx_lat = flash_attention(
        q_eff, k_eff.astype(jnp.float32), v_eff.astype(jnp.float32),
        causal=causal, q_offset=q_offset, kv_len=kv_len, chunk=chunk,
        softmax_scale=scale, unroll=unroll)                     # [B, Tq, H, kv_lora]
    w_uv = p["w_uv"].reshape(cfg.kv_lora, n_heads, cfg.v_head_dim)
    out = jnp.einsum("bqhk,khv->bqhv", ctx_lat.astype(jnp.float32),
                     w_uv.astype(jnp.float32))
    return out.reshape(b, tq, n_heads * cfg.v_head_dim)


def mla_attention(p: PyTree, x: jax.Array, *, n_heads: int, cfg: MLAConfig,
                  rope_theta: float = 10000.0, rope_scaling=None,
                  q_offset: int = 0, chunk: int = 1024,
                  unroll: bool = False) -> jax.Array:
    b, t, _ = x.shape
    pos = (q_offset + jnp.arange(t))[None]
    q_nope, q_rope = _queries(p, x, n_heads, cfg, pos, rope_theta,
                              rope_scaling)
    c_kv, k_rope = _latents(p, x, cfg, pos, rope_theta, rope_scaling)
    out = _attend(p, q_nope, q_rope, c_kv, k_rope, n_heads, cfg,
                  causal=True, q_offset=q_offset, chunk=chunk, unroll=unroll,
                  rope_scaling=rope_scaling)
    return out.astype(x.dtype) @ p["wo"]


def init_mla_cache(batch: int, seq: int, cfg: MLAConfig,
                   dtype=jnp.bfloat16) -> PyTree:
    return {"c_kv": jnp.zeros((batch, seq, cfg.kv_lora), dtype),
            "k_rope": jnp.zeros((batch, seq, cfg.rope_head_dim), dtype)}


def mla_decode(p: PyTree, x: jax.Array, cache: PyTree, index: jax.Array, *,
               n_heads: int, cfg: MLAConfig, rope_theta: float = 10000.0,
               rope_scaling=None, unroll: bool = False,
               layer: Optional[jax.Array] = None
               ) -> tuple[jax.Array, PyTree]:
    """``index``: scalar or per-row [B] vector (continuous batching);
    ``cache`` stacked [P, ...] with ``layer``, as in ``gqa_decode``."""
    b = x.shape[0]
    idx = jnp.asarray(index)
    vec = idx.ndim > 0
    pos = (idx[:, None] if vec else jnp.full((b, 1), idx)).astype(jnp.int32)
    q_nope, q_rope = _queries(p, x, n_heads, cfg, pos, rope_theta,
                              rope_scaling)
    c_new, kr_new = _latents(p, x, cfg, pos, rope_theta, rope_scaling)
    with jax.named_scope("decode.kv_cache"):
        cache = {"c_kv": write_token(cache["c_kv"], c_new[:, 0], idx, layer),
                 "k_rope": write_token(cache["k_rope"], kr_new[:, 0], idx,
                                       layer)}
    c_kv = layer_slab(cache["c_kv"], layer)
    k_rope = layer_slab(cache["k_rope"], layer)
    out = _attend(p, q_nope, q_rope, c_kv, k_rope, n_heads, cfg,
                  causal=False, q_offset=idx, kv_len=idx + 1, unroll=unroll,
                  rope_scaling=rope_scaling)
    return out.astype(x.dtype) @ p["wo"], cache
