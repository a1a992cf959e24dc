"""Model assembly: blocks per family + scan-over-layers + decode paths.

Scan-over-layers keeps HLO size O(1) in depth (the 60-layer 236B dry-run
compiles in seconds at 512 devices) and is wrapped in jax.checkpoint per the
config remat policy.  Heterogeneous stacks (hybrid RG-LRU patterns, VLM
cross-attention interleave) scan over *periods* — a period is the repeating
unit, each position in it with its own stacked params — plus an unscanned
remainder.

Families:
  dense   — [attn, ffn] × L
  moe     — [attn, moe-ffn] × L (optional leading dense layers; MLA option)
  hybrid  — pattern ("lru","lru","attn") × periods (+ remainder), local attn
  ssm     — [rwkv6 token mix, channel mix] × L
  encdec  — encoder [attn,ffn] × Le ; decoder [self, cross, ffn] × L
  vlm     — period [cross, self×(k-1)] × (L/k)
"""

from __future__ import annotations

import functools
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
from repro.sharding.act import shard_act

PyTree = Any


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # full


# ---------------------------------------------------------------------------
# block init / apply (single layer)
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str) -> PyTree:
    """kind ∈ {self, window, cross, lru, moe_self, rwkv, enc_self}."""
    dt = L._dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"ln1": L.init_norm(d, cfg.norm), "ln2": L.init_norm(d, cfg.norm)}
    if kind in ("self", "window", "enc_self"):
        p["attn"] = A.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.qk_norm, dt)
        p["ffn"] = L.init_ffn(ks[1], d, cfg.d_ff, cfg.activation, dt)
    elif kind == "cross":
        p["attn"] = A.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.qk_norm, dt)
        p["ffn"] = L.init_ffn(ks[1], d, cfg.d_ff, cfg.activation, dt)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_ffn"] = jnp.zeros((), jnp.float32)
    elif kind == "lru":
        p["mixer"] = RG.init_rglru(ks[0], d, cfg.hybrid, dt)
        p["ffn"] = L.init_ffn(ks[1], d, cfg.d_ff, cfg.activation, dt)
    elif kind == "moe_self":
        if cfg.mla is not None:
            p["attn"] = MLA.init_mla(ks[0], d, cfg.n_heads, cfg.mla, dt)
        else:
            p["attn"] = A.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim, cfg.qk_norm, dt)
        p["moe"] = MOE.init_moe(ks[1], d, cfg.moe, cfg.activation, dt)
    elif kind == "dense_self":  # leading dense layers of a MoE stack
        if cfg.mla is not None:
            p["attn"] = MLA.init_mla(ks[0], d, cfg.n_heads, cfg.mla, dt)
        else:
            p["attn"] = A.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim, cfg.qk_norm, dt)
        p["ffn"] = L.init_ffn(ks[1], d, cfg.moe.d_ff_dense or cfg.d_ff,
                              cfg.activation, dt)
    elif kind == "rwkv":
        p = {"ln1": L.init_norm(d, cfg.norm), "ln2": L.init_norm(d, cfg.norm),
             "tok": RW.init_rwkv6(ks[0], d, dt),
             "ch": RW.init_channel_mix(ks[1], d, cfg.d_ff, dt)}
    elif kind == "dec_self_cross":
        p["attn"] = A.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.qk_norm, dt)
        p["ln_x"] = L.init_norm(d, cfg.norm)
        p["xattn"] = A.init_gqa(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                cfg.head_dim, False, dt)
        p["ffn"] = L.init_ffn(ks[2], d, cfg.d_ff, cfg.activation, dt)
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _norm(p, x, cfg):
    return L.apply_norm(p, x, eps=cfg.norm_eps)


def apply_block(p: PyTree, x: jax.Array, cfg: ModelConfig, kind: str, *,
                context: Optional[jax.Array] = None,
                q_offset: int = 0) -> tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    akw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
               chunk=cfg.attn_chunk, q_offset=q_offset,
               unroll=cfg.analysis_unroll)
    tp = TP.current()
    if kind in ("self", "enc_self", "window"):
        h = A.gqa_attention(p["attn"], _norm(p["ln1"], x, cfg),
                            causal=(kind != "enc_self"),
                            window=cfg.hybrid.window if kind == "window"
                            else None,
                            use_rope=cfg.family not in ("encdec",), **akw)
        if tp is not None:
            h = tp.attn_reduce(h)
        x = x + h
        f = L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
        if tp is not None:
            f = tp.ffn_reduce(f)
        x = x + f
    elif kind == "cross":
        h = A.gqa_attention(p["attn"], _norm(p["ln1"], x, cfg),
                            context=context, causal=False, **akw)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        f = L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
        x = x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * f
    elif kind == "lru":
        x = x + RG.rglru_block(p["mixer"], _norm(p["ln1"], x, cfg),
                               cfg=cfg.hybrid)
        x = x + L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
    elif kind in ("moe_self", "dense_self"):
        if cfg.mla is not None:
            h = MLA.mla_attention(p["attn"], _norm(p["ln1"], x, cfg),
                                  n_heads=cfg.n_heads, cfg=cfg.mla,
                                  rope_theta=cfg.rope_theta,
                                  rope_scaling=cfg.rope_scaling,
                                  q_offset=q_offset, chunk=cfg.attn_chunk,
                                  unroll=cfg.analysis_unroll)
        else:
            h = A.gqa_attention(p["attn"], _norm(p["ln1"], x, cfg),
                                causal=True, **akw)
        if tp is not None:
            h = tp.attn_reduce(h)
        x = x + h
        if kind == "moe_self":
            y, aux = MOE.moe_ffn(p["moe"], _norm(p["ln2"], x, cfg), cfg.moe,
                                 cfg.activation)
            x = x + y
        else:
            f = L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
            if tp is not None:
                f = tp.ffn_reduce(f)
            x = x + f
    elif kind == "rwkv":
        x = x + RW.rwkv6_token_mix(p["tok"], _norm(p["ln1"], x, cfg),
                                   chunk=cfg.wkv_chunk,
                                   unroll=cfg.analysis_unroll)
        x = x + RW.rwkv6_channel_mix(p["ch"], _norm(p["ln2"], x, cfg))
    elif kind == "dec_self_cross":
        h = A.gqa_attention(p["attn"], _norm(p["ln1"], x, cfg), causal=True,
                            use_rope=False, **akw)
        x = x + h
        h = A.gqa_attention(p["xattn"], _norm(p["ln_x"], x, cfg),
                            context=context, causal=False, use_rope=False,
                            **akw)
        x = x + h
        x = x + L.ffn(p["ffn"], _norm(p["ln2"], x, cfg), cfg.activation)
    else:
        raise ValueError(kind)
    x = shard_act(x, "dp", None, None)
    return x, aux


# ---------------------------------------------------------------------------
# layer-stack schedules (which kind at which depth)
# ---------------------------------------------------------------------------

def layer_schedule(cfg: ModelConfig) -> list[str]:
    if cfg.family == "dense":
        return ["self"] * cfg.n_layers
    if cfg.family == "moe":
        lead = cfg.moe.first_dense_layers
        return ["dense_self"] * lead + ["moe_self"] * (cfg.n_layers - lead)
    if cfg.family == "hybrid":
        pat = list(cfg.hybrid.pattern)
        return [("window" if pat[i % len(pat)] == "attn" else "lru")
                for i in range(cfg.n_layers)]
    if cfg.family == "ssm":
        return ["rwkv"] * cfg.n_layers
    if cfg.family == "encdec":
        return ["dec_self_cross"] * cfg.n_layers
    if cfg.family == "vlm":
        k = cfg.vlm.cross_every
        return [("cross" if i % k == 0 else "self")
                for i in range(cfg.n_layers)]
    raise ValueError(cfg.family)


def _period_of(cfg: ModelConfig) -> tuple[list[str], int, list[str]]:
    """(period_kinds, n_periods, remainder_kinds)."""
    sched = layer_schedule(cfg)
    if cfg.family == "hybrid":
        period = [("window" if p == "attn" else p)
                  for p in cfg.hybrid.pattern]
    elif cfg.family == "vlm":
        k = cfg.vlm.cross_every
        period = ["cross"] + ["self"] * (k - 1)
    elif cfg.family == "moe" and cfg.moe.first_dense_layers:
        # leading dense layers are the remainder-prefix; period is moe
        n = cfg.n_layers - cfg.moe.first_dense_layers
        return ["moe_self"], n, sched[:cfg.moe.first_dense_layers]
    else:
        return [sched[0]], cfg.n_layers, []
    n_periods = cfg.n_layers // len(period)
    rem = sched[n_periods * len(period):]
    return period, n_periods, rem


# ---------------------------------------------------------------------------
# stack init
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig) -> PyTree:
    dt = L._dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    period, n_periods, rem = _period_of(cfg)
    p: dict = {
        "embed": L.embed_init(keys[0], cfg.vocab, cfg.d_model, dt),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(keys[1], cfg.d_model, cfg.vocab, dt)

    def stacked_init(k, kind, n):
        return jax.vmap(lambda kk: init_block(kk, cfg, kind))(
            jax.random.split(k, n))

    p["layers"] = {f"pos{j}_{kind}": stacked_init(jax.random.fold_in(
        keys[2], j), kind, n_periods) for j, kind in enumerate(period)}
    p["rem"] = {f"rem{j}_{kind}": init_block(
        jax.random.fold_in(keys[3], j), cfg, kind)
        for j, kind in enumerate(rem)}

    if cfg.family == "encdec":
        e = cfg.encdec
        p["enc"] = {
            "pos": (0.02 * jax.random.normal(
                keys[4], (e.encoder_seq, cfg.d_model), jnp.float32)).astype(dt),
            "layers": {"pos0_enc_self": stacked_init(
                keys[5], "enc_self", e.n_encoder_layers)},
            "final_norm": L.init_norm(cfg.d_model, cfg.norm),
        }
        p["dec_pos"] = (0.02 * jax.random.normal(
            keys[6], (cfg.max_seq, cfg.d_model), jnp.float32)).astype(dt)
    return p


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _scan_stack(p_layers: PyTree, x: jax.Array, cfg: ModelConfig,
                period: list[str], *, context=None, q_offset=0
                ) -> tuple[jax.Array, jax.Array]:
    def period_body(x, period_params):
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(period):
            blk = period_params[f"pos{j}_{kind}"]
            x, a = apply_block(blk, x, cfg, kind, context=context,
                               q_offset=q_offset)
            aux = aux + a
        return x, aux

    body = _remat(lambda x, pp: period_body(x, pp), cfg.remat)
    if cfg.scan_layers:
        n = jax.tree.leaves(p_layers)[0].shape[0]
        x, auxs = jax.lax.scan(lambda c, pp: body(c, pp), x, p_layers,
                               unroll=n if cfg.analysis_unroll else 1)
        return x, auxs.sum()
    # unrolled (analysis probes / tiny models) — keep the remat policy so
    # recompute FLOPs are counted identically to the scanned program
    n = jax.tree.leaves(p_layers)[0].shape[0]
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(n):
        sl = jax.tree.map(lambda a: a[i], p_layers)
        x, aux = body(x, sl)
        aux_total = aux_total + aux
    return x, aux_total


def encode(params: PyTree, cfg: ModelConfig,
           enc_embeds: jax.Array) -> jax.Array:
    """Whisper-style encoder over stub frame embeddings [B, Te, D]."""
    e = params["enc"]
    x = enc_embeds + e["pos"][None, :enc_embeds.shape[1], :].astype(
        enc_embeds.dtype)
    x, _ = _scan_stack(e["layers"], x, cfg, ["enc_self"])
    return _norm(e["final_norm"], x, cfg)


def forward(params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
            context: Optional[jax.Array] = None,
            q_offset: int = 0) -> tuple[jax.Array, jax.Array]:
    """tokens [B, T] -> (hidden [B, T, D], aux_loss).

    ``context``: encoder memory (encdec) or image embeddings (vlm).
    """
    x = L.embed_lookup(params["embed"], tokens)
    if cfg.family == "encdec":
        x = x + params["dec_pos"][None, q_offset:q_offset + tokens.shape[1],
                                  :].astype(x.dtype)
    period, _, rem = _period_of(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    # remainder-prefix (moe leading dense layers) runs first
    prefix_rem = cfg.family == "moe" and bool(rem)
    if prefix_rem:
        for name, blk in params["rem"].items():
            kind = name.split("_", 1)[1]
            x, aux = apply_block(blk, x, cfg, kind, context=context,
                                 q_offset=q_offset)
            aux_total += aux
    x, aux = _scan_stack(params["layers"], x, cfg, period, context=context,
                         q_offset=q_offset)
    aux_total += aux
    if not prefix_rem:
        for name, blk in params["rem"].items():
            kind = name.split("_", 1)[1]
            x, aux = apply_block(blk, x, cfg, kind, context=context,
                                 q_offset=q_offset)
            aux_total += aux
    x = _norm(params["final_norm"], x, cfg)
    return x, aux_total


def logits(params: PyTree, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return L.logits_head(hidden, w)
