"""Serving paths: cache init, prefill, and single-token decode.

Caches mirror the stacked-layer structure: one stacked cache pytree per
period position (carried through the layer scan, which scans the params,
and updated in place), plus unstacked caches for remainder layers.  Cache
kinds per block:

  self/dense_self/moe_self(GQA) — {k, v}: [B, S, Hkv, dh]
  moe_self(MLA)                 — {c_kv, k_rope}: [B, S, ·] (57× smaller)
  window                        — ring buffer [B, W, Hkv, dh] + slot pos
  lru                           — {h: [B, W], conv: [B, cw-1, W]}
  rwkv                          — {s: [B, H, K, V], x_tok, x_ch: [B, D]}

decode_step cost is O(1) in generated length for lru/rwkv (the long_500k
story) and O(S) attention reads for KV-cache kinds.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import parallel as TP
from repro.models import rglru as RG
from repro.models import rwkv6 as RW
from repro.models.config import ModelConfig
from repro.models.transformer import (_norm, _period_of, apply_block, logits)

PyTree = Any


def _block_cache(cfg: ModelConfig, kind: str, batch: int, seq: int,
                 dtype=jnp.bfloat16) -> PyTree:
    if kind in ("self", "dense_self", "enc_self", "moe_self"):
        if kind in ("dense_self", "moe_self") and cfg.mla is not None:
            return MLA.init_mla_cache(batch, seq, cfg.mla, dtype)
        return A.init_gqa_cache(batch, seq, cfg.n_kv_heads, cfg.head_dim,
                                dtype)
    if kind == "window":
        return A.init_window_cache(batch, min(cfg.hybrid.window, seq),
                                   cfg.n_kv_heads, cfg.head_dim, dtype)
    if kind == "lru":
        return RG.init_rglru_cache(batch, cfg.hybrid, cfg.d_model, dtype)
    if kind == "rwkv":
        return RW.init_rwkv6_cache(batch, cfg.d_model, dtype)
    if kind == "dec_self_cross":
        return A.init_gqa_cache(batch, seq, cfg.n_kv_heads, cfg.head_dim,
                                dtype)
    if kind == "cross":
        return {}  # context is static; nothing cached
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, seq: int,
               dtype=jnp.bfloat16) -> PyTree:
    period, n_periods, rem = _period_of(cfg)

    def stack(kind):
        one = _block_cache(cfg, kind, batch, seq, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy(), one)

    cache = {"layers": {f"pos{j}_{kind}": stack(kind)
                        for j, kind in enumerate(period)},
             "rem": {f"rem{j}_{kind}": _block_cache(cfg, kind, batch, seq,
                                                    dtype)
                     for j, kind in enumerate(rem)}}
    return cache


# ---------------------------------------------------------------------------
# single-block decode
# ---------------------------------------------------------------------------

def block_decode(p: PyTree, x: jax.Array, cache: PyTree, index: jax.Array,
                 cfg: ModelConfig, kind: str, *,
                 context: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, PyTree]:
    """One block's decode of one token.  ``cache`` is the block's own
    cache, or with ``layer`` its period position's stacked [P, ...]
    cache, returned with this layer's entries updated in place: a KV
    kind writes the token's rows at ``[layer, row, index]``, a state kind
    (lru, rwkv) its whole small state at ``layer``."""
    akw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta)
    tp = TP.current()
    if kind in ("lru", "rwkv") and layer is not None:
        own = jax.tree.map(lambda a: A.layer_slab(a, layer), cache)
        x, own = block_decode(p, x, own, index, cfg, kind, context=context)
        return x, jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
            cache, own)
    if kind in ("self", "dense_self", "moe_self"):
        with jax.named_scope("decode.attn"):
            xin = _norm(p["ln1"], x, cfg)
            if kind in ("dense_self", "moe_self") and cfg.mla is not None:
                h, cache = MLA.mla_decode(p["attn"], xin, cache, index,
                                          n_heads=cfg.n_heads, cfg=cfg.mla,
                                          rope_theta=cfg.rope_theta,
                                          rope_scaling=cfg.rope_scaling,
                                          layer=layer)
            else:
                h, cache = A.gqa_decode(p["attn"], xin, cache, index,
                                        layer=layer, **akw)
        if tp is not None:
            h = tp.attn_reduce(h)
        x = x + h
        if kind == "moe_self":
            with jax.named_scope("decode.mlp"):
                y, _ = MOE.moe_ffn(p["moe"], _norm(p["ln2"], x, cfg),
                                   cfg.moe, cfg.activation,
                                   scope="decode.moe")
            x = x + y
        else:
            with jax.named_scope("decode.mlp"):
                f = L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
            if tp is not None:
                f = tp.ffn_reduce(f)
            x = x + f
    elif kind == "window":
        h, cache = A.window_decode(p["attn"], _norm(p["ln1"], x, cfg), cache,
                                   index, window=cfg.hybrid.window,
                                   layer=layer, **akw)
        x = x + h
        x = x + L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
    elif kind == "lru":
        h, cache = RG.rglru_decode(p["mixer"], _norm(p["ln1"], x, cfg),
                                   cache, cfg=cfg.hybrid)
        x = x + h
        x = x + L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
    elif kind == "rwkv":
        x, cache = RW.rwkv6_decode(
            p["tok"], p["ch"], x, cache,
            lambda z: _norm(p["ln1"], z, cfg),
            lambda z: _norm(p["ln2"], z, cfg))
    elif kind == "dec_self_cross":
        h, cache = A.gqa_decode(p["attn"], _norm(p["ln1"], x, cfg), cache,
                                index, use_rope=False, layer=layer, **akw)
        x = x + h
        h = A.gqa_attention(p["xattn"], _norm(p["ln_x"], x, cfg),
                            context=context, causal=False, use_rope=False,
                            chunk=cfg.attn_chunk, **akw)
        x = x + h
        x = x + L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
    elif kind == "cross":
        h = A.gqa_attention(p["attn"], _norm(p["ln1"], x, cfg),
                            context=context, causal=False,
                            chunk=cfg.attn_chunk, **akw)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        f = L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
        x = x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * f
    else:
        raise ValueError(kind)
    return x, cache


# ---------------------------------------------------------------------------
# decode step over the whole stack
# ---------------------------------------------------------------------------

def decode_step(params: PyTree, cfg: ModelConfig, token: jax.Array,
                cache: PyTree, index: jax.Array, *,
                context: Optional[jax.Array] = None
                ) -> tuple[jax.Array, PyTree]:
    """token: [B] int32; ``index`` scalar or per-row [B] vector.
    Returns (logits [B, V], new_cache)."""
    x = L.embed_lookup(params["embed"], token[:, None])
    if cfg.family == "encdec":
        idx = jnp.asarray(index)
        if idx.ndim > 0:
            pos = jnp.take(params["dec_pos"], idx, axis=0)[:, None, :]
        else:
            pos = jax.lax.dynamic_slice_in_dim(
                params["dec_pos"], idx, 1, 0)[None]
        x = x + pos.astype(x.dtype)
    period, _, rem = _period_of(cfg)
    prefix_rem = cfg.family == "moe" and bool(rem)

    def run_rem(x, cache_rem):
        new = {}
        for name in sorted(cache_rem):
            kind = name.split("_", 1)[1]
            blk = params["rem"][name]
            x, c = block_decode(blk, x, cache_rem[name], index, cfg, kind,
                                context=context)
            new[name] = c
        return x, new

    new_cache = {"layers": None, "rem": cache["rem"]}
    if prefix_rem:
        x, new_cache["rem"] = run_rem(x, cache["rem"])

    def period_body(carry, pp):
        x, cc, layer = carry
        cc = dict(cc)
        for j, kind in enumerate(period):
            name = f"pos{j}_{kind}"
            x, cc[name] = block_decode(pp[name], x, cc[name], index, cfg,
                                       kind, context=context, layer=layer)
        return (x, cc, layer + 1), None

    n_per = jax.tree.leaves(params["layers"])[0].shape[0]
    # the stacked cache rides the layer scan's carry, so each layer's
    # writes update the (donated) stack in place; a stack passed as
    # xs/ys would be sliced, restacked and copied whole every tick
    with jax.named_scope("decode.kv_cache"):
        (x, new_cache["layers"], _), _ = jax.lax.scan(
            period_body, (x, cache["layers"], jnp.int32(0)),
            params["layers"], unroll=n_per if cfg.analysis_unroll else 1)

    if not prefix_rem:
        x, new_cache["rem"] = run_rem(x, cache["rem"])

    with jax.named_scope("decode.head"):
        x = _norm(params["final_norm"], x, cfg)
        lg = logits(params, cfg, x)[:, 0, :]
    return lg, new_cache


# ---------------------------------------------------------------------------
# prefill: forward pass that also fills the caches
# ---------------------------------------------------------------------------

def prefill(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
            cache: PyTree, *, context: Optional[jax.Array] = None
            ) -> tuple[jax.Array, PyTree]:
    """Fill caches with a whole prompt [B, T]; returns (last_logits, cache).

    Implemented as T sequential decode steps under lax.fori_loop for state
    kinds (exact for every cache kind).  For pure-GQA stacks a fast batched
    path projects K/V for the whole prompt in one forward pass.
    """
    period, _, rem = _period_of(cfg)
    kinds = set(period) | {n.split("_", 1)[1] for n in cache["rem"]}
    if kinds <= {"self", "dense_self"} and cfg.mla is None:
        return _prefill_gqa_fast(params, cfg, tokens, cache, context=context)

    b, t = tokens.shape

    def body(i, carry):
        lg, cache = carry
        lg, cache = decode_step(params, cfg, tokens[:, i], cache, i,
                                context=context)
        return lg, cache

    lg0 = jnp.zeros((b, cfg.vocab), jnp.float32)
    lg, cache = jax.lax.fori_loop(0, t, body, (lg0, cache))
    return lg, cache


def _prefill_gqa_fast(params, cfg, tokens, cache, *, context=None):
    """Batched prefill for homogeneous GQA stacks: one forward pass emits
    every layer's K/V (collected as scan ys) plus the last-token logits."""
    from repro.models.transformer import forward
    b, t = tokens.shape
    x = L.embed_lookup(params["embed"], tokens)
    period, _, rem = _period_of(cfg)

    # Single pass per layer: reuse apply_block for the hidden stream and
    # project K/V once more for the cache (cheap relative to attention).
    def body(x, pp):
        new_kv = {}
        for j, kind in enumerate(period):
            name = f"pos{j}_{kind}"
            p = pp[name]
            xin = _norm(p["ln1"], x, cfg)
            pos = jnp.arange(t)[None]
            _, k, v = A._project_qkv(p["attn"], xin, xin, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim,
                                     cfg.qk_norm, cfg.rope_theta, pos, pos)
            x, _ = apply_block(p, x, cfg, kind, context=context)
            new_kv[name] = {"k": k, "v": v}
        return x, new_kv

    x, kv = jax.lax.scan(body, x, params["layers"])
    x = _norm(params["final_norm"], x, cfg)
    lg = logits(params, cfg, x[:, -1:, :])[:, 0, :]

    seq = jax.tree.leaves(cache["layers"])[0].shape[2]

    def place(full, new):  # full: [P, B, S, H, d]; new: [P, B, T, H, d]
        return jax.lax.dynamic_update_slice_in_dim(
            full, new.astype(full.dtype), 0, axis=2)

    new_cache = {"layers": {}, "rem": cache["rem"]}
    for name, c in cache["layers"].items():
        new_cache["layers"][name] = {
            "k": place(c["k"], kv[name]["k"]),
            "v": place(c["v"], kv[name]["v"])}
    return lg, new_cache
