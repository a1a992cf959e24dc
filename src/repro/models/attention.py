"""Attention: GQA flash-attention (KV-chunked, online softmax) + decode.

One implementation serves every attention in the zoo:
  * full causal (dense LMs, training/prefill)
  * sliding-window causal (recurrentgemma local attention)
  * non-causal (whisper encoder)
  * cross attention (whisper decoder, llama-vision image layers)
  * single-token decode against a KV cache

The KV-chunked online-softmax formulation (lax.scan over KV blocks with
running max / denominator) bounds live memory to O(Tq · chunk) — mandatory
for the 32k-prefill cells — and is the standard XLA-level flash pattern on
TPU.  f32 softmax statistics throughout.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any
NEG_INF = -1e30


def _gqa_expand(q: jax.Array, n_kv: int) -> jax.Array:
    """[B, T, Hq, d] -> [B, T, Hkv, G, d]."""
    b, t, hq, d = q.shape
    return q.reshape(b, t, n_kv, hq // n_kv, d)


def flash_attention(
    q: jax.Array,            # [B, Tq, Hq, d]
    k: jax.Array,            # [B, Tk, Hkv, d]
    v: jax.Array,            # [B, Tk, Hkv, dv]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int | jax.Array = 0,
    kv_len: Optional[jax.Array] = None,   # valid KV prefix (decode masking)
    chunk: int = 1024,
    softmax_scale: Optional[float] = None,
    unroll: bool = False,
) -> jax.Array:
    b, tq, hq, d = q.shape
    _, tk, hkv, dv = v.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    qf = _gqa_expand(q.astype(jnp.float32) * scale, hkv)   # [B,Tq,Hkv,G,d]
    g = qf.shape[3]

    chunk = min(chunk, tk)
    pad = (-tk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkc = (tk + pad) // chunk
    ks = k.reshape(b, nkc, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nkc, chunk, hkv, dv).transpose(1, 0, 2, 3, 4)

    # q_offset / kv_len may be scalars or per-batch [B] vectors
    # (continuous batching: every slot sits at its own position).
    q_off = jnp.asarray(q_offset)
    per_batch = q_off.ndim > 0 or (kv_len is not None
                                   and jnp.asarray(kv_len).ndim > 0)
    q_pos = (q_off[..., None] + jnp.arange(tq))             # [Tq] or [B,Tq]
    if per_batch:
        q_pos = jnp.broadcast_to(q_pos.reshape(-1, tq), (b, tq))

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, ci = xs                                     # [B,C,Hkv,d], idx
        s = jnp.einsum("bqhgd,bchd->bqhgc", qf,
                       kc.astype(jnp.float32))              # [B,Tq,Hkv,G,C]
        k_pos = ci * chunk + jnp.arange(chunk)              # [C]
        valid = k_pos < tk                                  # [C]
        if kv_len is not None:
            kl = jnp.asarray(kv_len)
            if kl.ndim > 0:
                valid = valid[None, :] & (k_pos[None, :] < kl[:, None])
            else:
                valid = valid & (k_pos < kl)
        if per_batch:
            mask = jnp.broadcast_to(
                valid if valid.ndim == 2 else valid[None, :],
                (b, chunk))[:, None, :]                     # [B,1,C]
            mask = jnp.broadcast_to(mask, (b, tq, chunk))
            if causal:
                mask = mask & (k_pos[None, None, :] <= q_pos[:, :, None])
            if window is not None:
                mask = mask & (k_pos[None, None, :]
                               > q_pos[:, :, None] - window)
            s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        else:
            mask = jnp.broadcast_to(valid[None, :], (tq, chunk))
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            if window is not None:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqhgc,bchv->bqhgv", p, vc.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, tq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, tq, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, tq, hkv, g, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (ks, vs, jnp.arange(nkc)),
        unroll=nkc if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, tq, hq, dv).astype(v.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (params + apply)
# ---------------------------------------------------------------------------

def init_gqa(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
             qk_norm: bool = False, dtype=jnp.bfloat16) -> PyTree:
    from repro.models import layers as L
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d_model, n_heads * d_head, dtype),
        "wk": L.dense_init(ks[1], d_model, n_kv * d_head, dtype),
        "wv": L.dense_init(ks[2], d_model, n_kv * d_head, dtype),
        "wo": L.dense_init(ks[3], n_heads * d_head, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = L.init_rmsnorm(d_head)
        p["k_norm"] = L.init_rmsnorm(d_head)
    return p


def _project_qkv(p, x, xc, n_heads, n_kv, d_head, qk_norm, rope_theta,
                 q_positions, k_positions, use_rope=True):
    from repro.models import layers as L
    b, t, _ = x.shape
    tc = xc.shape[1]
    q = (x @ p["wq"]).reshape(b, t, n_heads, d_head)
    k = (xc @ p["wk"]).reshape(b, tc, n_kv, d_head)
    v = (xc @ p["wv"]).reshape(b, tc, n_kv, d_head)
    if qk_norm:
        q = L.rmsnorm(p["q_norm"], q)
        k = L.rmsnorm(p["k_norm"], k)
    if use_rope:
        q = L.apply_rope(q, q_positions, rope_theta)
        k = L.apply_rope(k, k_positions, rope_theta)
    from repro.sharding.act import shard_act
    q = shard_act(q, "dp", None, "tp", None)
    k = shard_act(k, "dp", None, "tp", None)
    v = shard_act(v, "dp", None, "tp", None)
    return q, k, v


def gqa_attention(
    p: PyTree, x: jax.Array, *, n_heads: int, n_kv: int, d_head: int,
    causal: bool = True, window: Optional[int] = None, qk_norm: bool = False,
    rope_theta: float = 10000.0, q_offset: int = 0, chunk: int = 1024,
    context: Optional[jax.Array] = None, use_rope: bool = True,
    unroll: bool = False,
) -> jax.Array:
    """Self (context=None) or cross attention over full sequences."""
    xc = x if context is None else context
    b, t, _ = x.shape
    q_pos = q_offset + jnp.arange(t)
    k_pos = jnp.arange(xc.shape[1])
    q, k, v = _project_qkv(p, x, xc, n_heads, n_kv, d_head, qk_norm,
                           rope_theta, q_pos[None], k_pos[None],
                           use_rope=use_rope and context is None)
    out = flash_attention(q, k, v, causal=causal and context is None,
                          window=window, q_offset=q_offset, chunk=chunk,
                          unroll=unroll)
    return out.reshape(b, t, n_heads * d_head) @ p["wo"]


def write_token(buf: jax.Array, new: jax.Array, index: jax.Array,
                layer: Optional[jax.Array] = None) -> jax.Array:
    """``buf`` with one token's rows ``new`` [B, ...] written at position
    ``index`` of each row: a ``dynamic_update_slice`` for a scalar index,
    a per-row scatter for an int32 [B] vector.  ``buf`` is one layer's
    [B, S, ...], or with ``layer`` the stacked [P, B, S, ...], written at
    that layer only, so a carried stack is updated in place."""
    new = new.astype(buf.dtype)
    idx = jnp.asarray(index, jnp.int32)
    lead = () if layer is None else (jnp.asarray(layer, jnp.int32),)
    if idx.ndim > 0:
        return buf.at[lead + (jnp.arange(new.shape[0]), idx)].set(new)
    zero = jnp.int32(0)
    start = lead + (zero, idx) + (zero,) * (new.ndim - 1)
    upd = new.reshape((1,) * len(lead) + (new.shape[0], 1) + new.shape[1:])
    return jax.lax.dynamic_update_slice(buf, upd, start)


def layer_slab(buf: jax.Array, layer: Optional[jax.Array]) -> jax.Array:
    """One layer's cache out of a stacked [P, ...] ``buf`` (``buf`` itself
    when ``layer`` is None)."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


# Decode attention reads one layer's K and V slab in row groups of at most
# this many bytes each.  A piece this size is staged whole in the TPU's
# on-chip memory (v5e: 128 MiB of VMEM) by the slice that cuts it out of
# the stacked cache; a larger slab is copied out to HBM first and read
# from there again, a whole extra pass over it.
READ_BYTES = 64 << 20


def _read_groups(rows: int, row_bytes: int) -> int:
    """The fewest row groups, dividing ``rows``, of at most READ_BYTES."""
    need = -(-rows * row_bytes // READ_BYTES)
    return next(g for g in range(max(need, 1), rows + 1) if rows % g == 0)


def cached_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     index: jax.Array, layer: Optional[jax.Array], *,
                     window: Optional[int] = None, unroll: bool = False
                     ) -> jax.Array:
    """:func:`flash_attention` of one token per row ``q`` [B, 1, Hq, d]
    over one layer of the cache ``k``/``v`` ([B, S, Hkv, d], or stacked
    [P, B, S, Hkv, d] with ``layer``) holding positions ``<= index``.
    Rows are independent, so the rows are read in groups of at most
    READ_BYTES of K (and of V), each cut straight out of the stack."""
    if layer is None:
        k, v, layer = k[None], v[None], 0
    b, seq = q.shape[0], k.shape[2]
    idx = jnp.asarray(index)
    n = _read_groups(b, math.prod(k.shape[2:]) * k.dtype.itemsize)
    rows = b // n

    def group(g):
        r0 = g * rows
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, rows, 0)
        kg, vg = (jax.lax.dynamic_slice(a, (layer, r0, 0, 0, 0),
                                        (1, rows) + a.shape[2:])[0]
                  for a in (k, v))
        ig = cut(idx) if idx.ndim > 0 else idx
        return flash_attention(cut(q), kg, vg, causal=False, window=window,
                               q_offset=ig, kv_len=ig + 1,
                               chunk=min(4096, seq), unroll=unroll)

    out = jax.lax.map(group, jnp.arange(n, dtype=jnp.int32))
    return out.reshape((b,) + out.shape[2:])


def gqa_decode(
    p: PyTree, x: jax.Array, cache: PyTree, index: jax.Array, *,
    n_heads: int, n_kv: int, d_head: int, window: Optional[int] = None,
    qk_norm: bool = False, rope_theta: float = 10000.0,
    use_rope: bool = True, unroll: bool = False,
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, PyTree]:
    """One-token decode.  x: [B, 1, D]; cache: {k,v: [B, S, Hkv, d]}, or
    with ``layer`` the stacked {k,v: [P, B, S, Hkv, d]} (see
    :func:`write_token`); returns the cache with the token written.

    ``index`` is a scalar (lockstep batch) or an int32 [B] vector
    (continuous batching: per-slot positions; cache writes are per-row
    scatters and masking is per-row).
    """
    b = x.shape[0]
    idx = jnp.asarray(index)
    vec = idx.ndim > 0
    pos = (idx[:, None] if vec else jnp.full((b, 1), idx)).astype(jnp.int32)
    q, k_new, v_new = _project_qkv(
        p, x, x, n_heads, n_kv, d_head, qk_norm, rope_theta, pos, pos,
        use_rope=use_rope)
    with jax.named_scope("decode.kv_cache"):
        cache = {"k": write_token(cache["k"], k_new[:, 0], idx, layer),
                 "v": write_token(cache["v"], v_new[:, 0], idx, layer)}
    out = cached_attention(q, cache["k"], cache["v"], idx, layer,
                           window=window, unroll=unroll)
    y = out.reshape(b, 1, n_heads * d_head) @ p["wo"]
    return y, cache


def init_gqa_cache(batch: int, seq: int, n_kv: int, d_head: int,
                   dtype=jnp.bfloat16) -> PyTree:
    return {"k": jnp.zeros((batch, seq, n_kv, d_head), dtype),
            "v": jnp.zeros((batch, seq, n_kv, d_head), dtype)}


# ---------------------------------------------------------------------------
# sliding-window decode with a ring-buffer cache — O(window) state, the
# reason the hybrid arch is long_500k-eligible.
# ---------------------------------------------------------------------------

def init_window_cache(batch: int, window: int, n_kv: int, d_head: int,
                      dtype=jnp.bfloat16) -> PyTree:
    return {"k": jnp.zeros((batch, window, n_kv, d_head), dtype),
            "v": jnp.zeros((batch, window, n_kv, d_head), dtype),
            "pos": jnp.full((batch, window), -1, jnp.int32)}


def window_decode(
    p: PyTree, x: jax.Array, cache: PyTree, index: jax.Array, *,
    n_heads: int, n_kv: int, d_head: int, window: int,
    qk_norm: bool = False, rope_theta: float = 10000.0,
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, PyTree]:
    """One-token decode against a ring buffer of the last ``window`` KVs
    (stacked [P, ...] with ``layer``, as in :func:`gqa_decode`).

    ``index``: scalar or per-row [B] vector (continuous batching)."""
    b = x.shape[0]
    idx = jnp.asarray(index)
    idx_b = jnp.broadcast_to(idx, (b,)).astype(jnp.int32)   # [B]
    pos = idx_b[:, None]
    q, k_new, v_new = _project_qkv(
        p, x, x, n_heads, n_kv, d_head, qk_norm, rope_theta, pos, pos)
    slot = idx_b % window
    with jax.named_scope("decode.kv_cache"):
        cache = {"k": write_token(cache["k"], k_new[:, 0], slot, layer),
                 "v": write_token(cache["v"], v_new[:, 0], slot, layer),
                 "pos": write_token(cache["pos"], idx_b, slot, layer)}
    k, v, slot_pos = (layer_slab(cache[n], layer) for n in ("k", "v", "pos"))

    scale = 1.0 / math.sqrt(d_head)
    qe = _gqa_expand(q.astype(jnp.float32) * scale, n_kv)  # [B,1,Hkv,G,d]
    s = jnp.einsum("bqhgd,bwhd->bqhgw", qe, k.astype(jnp.float32))
    valid = ((slot_pos >= 0) & (slot_pos <= idx_b[:, None])
             & (slot_pos > idx_b[:, None] - window))        # [B, W]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgw,bwhv->bqhgv", a, v.astype(jnp.float32))
    y = out.reshape(b, 1, n_heads * d_head).astype(x.dtype) @ p["wo"]
    return y, cache
