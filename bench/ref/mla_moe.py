"""Plain reference of DeepSeek-V2's decoder, as one chip's expert share,
in float32.

Written from the published description (arXiv:2405.04434 and the
model's ``modeling_deepseek.py``): pre-norm RMSNorm blocks; multi-head
latent attention as published, not absorbed: the query through its
low-rank path, the key-value latent and one shared rope key, each
head's key and value decompressed from the latent, YaRN rope on the rope
dims and the softmax scale raised by mscale(factor, mscale_all_dim)^2;
one leading dense SwiGLU layer; then MoE layers whose router scores
every expert by softmax, keeps the best ``topk_group`` of ``n_group``
groups (a group scores its best expert), takes the top
``num_experts_per_tok`` inside them and, unnormalised, scales them by
``routed_scaling_factor``; only the held experts' part of the routed sum
is added, with the shared experts; an untied head.  Straightforward
``jax.numpy``: no kernels, no cache, no batching tricks.  Weights are
drawn from the seed by canonical name (``bench.arch.mla_moe``), the bf16
values the program serves, upcast to float32, one layer at a time
inside the layer scan.  Matmuls run at ``highest`` precision.  Imports
nothing of the program.

``quant`` is the control, the reference one precision below the
configuration's bfloat16: every projection's operands (the router's and
the latent decompressions' too) rounded to int8 or fp8, as in
``bench/ref/dense_gqa.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.arch.mla_moe import (dense_specs, expert_specs, expert_tensor,
                                experts, global_specs, layer_tensor,
                                moe_specs)
from bench.ref.dense_gqa import hashable, mm, rms

F32 = jnp.float32
NEG = -1e30


# ---------------------------------------------------------------------------
# YaRN rope (DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict) -> jax.Array:
    """[dim/2] inverse wavelengths of the YaRN rotary embedding."""
    exps = jnp.arange(0, dim, 2, dtype=F32) / dim
    extra = 1.0 / (base ** exps)
    inter = 1.0 / (rs["factor"] * base ** exps)

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    extra_mask = 1.0 - ramp
    return inter * (1 - extra_mask) + extra * extra_mask


def rope(x, pos, c: dict):
    """x [B, T, h, dh]; pos [T]: rotate-half YaRN rope."""
    rs = c["rope_scaling"]
    dh = x.shape[-1]
    inv = yarn_inv_freq(dh, float(c["rope_theta"]), rs)
    amp = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"])
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos = (jnp.cos(ang) * amp)[None, :, None, :]
    sin = (jnp.sin(ang) * amp)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

def attention(q, k, v, scale: float, qblock: int):
    """Causal softmax attention, q/k [B, T, H, dq], v [B, T, H, dv];
    T % qblock == 0.  Query blocks bound the score matrix."""
    b, t, h, _ = q.shape
    qblock = min(qblock, t)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, k) * scale
        qpos = i * qblock + jnp.arange(qblock)
        s = jnp.where(jnp.arange(t)[None, :] <= qpos[:, None], s, NEG)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(t // qblock))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, v.shape[-1])


def mla(w: dict, x, c: dict, quant, qblock: int):
    """Latent attention of ``x`` [B, T, d] (normed), per-head K and V
    decompressed from the latent."""
    b, t, _ = x.shape
    H, eps = c["num_attention_heads"], c["rms_norm_eps"]
    nope, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    kl = c["kv_lora_rank"]
    pos = jnp.arange(t)
    q = mm(rms(mm(x, w["w_dq"], quant), w["q_norm"], eps), w["w_uq"],
           quant).reshape(b, t, H, nope + dr)
    kv = mm(x, w["w_dkv"], quant)
    latent = rms(kv[..., :kl], w["kv_norm"], eps)
    q_pe = rope(q[..., nope:], pos, c)
    k_pe = rope(kv[..., None, kl:], pos, c)
    k_nope = mm(latent, w["w_uk"], quant).reshape(b, t, H, nope)
    v = mm(latent, w["w_uv"], quant).reshape(b, t, H, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, t, H, dr))], -1)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    rs = c["rope_scaling"]
    scale = yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2 \
        / math.sqrt(nope + dr)
    a = attention(q, k, v, scale, qblock).reshape(b, t, H * dv)
    return mm(a, w["wo"], quant)


def swiglu(x, gate, up, down, quant):
    return mm(jax.nn.silu(mm(x, gate, quant)) * mm(x, up, quant), down,
              quant)


def routing(x, router, c: dict, quant):
    """(weights [B, T, k], experts [B, T, k]) of the published router."""
    s = jax.nn.softmax(mm(x, router, quant), -1)
    if c["topk_method"] == "group_limited_greedy":
        n = c["n_group"]
        g = s.reshape(s.shape[:-1] + (n, -1))
        _, best = jax.lax.top_k(g.max(-1), c["topk_group"])
        kept = jax.nn.one_hot(best, n, dtype=F32).sum(-2) > 0
        s = jnp.where(kept[..., None], g, 0.0).reshape(s.shape)
    vals, idx = jax.lax.top_k(s, c["num_experts_per_tok"])
    if c["num_experts_per_tok"] > 1 and c["norm_topk_prob"]:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
    else:
        vals = vals * c["routed_scaling_factor"]
    return vals, idx


def moe(w: dict, key, layer, x, c: dict, quant):
    """The held experts' part of the routed sum, plus the shared
    experts, for ``x`` [B, T, d] (normed)."""
    _, first, held = experts(c)
    vals, idx = routing(x, w["router"], c, quant)
    mine = first + jnp.arange(held)
    weight = jnp.sum(jnp.where(idx[..., None] == mine, vals[..., None], 0.0),
                     axis=-2)                                # [B, T, held]

    def expert(acc, i):
        e = {n: expert_tensor(c, key, n, layer, first + i, s, std).astype(F32)
             for n, s, std in expert_specs(c)}
        y = swiglu(x, e["e_gate"], e["e_up"], e["e_down"], quant)
        return acc + jnp.take(weight, i, axis=-1)[..., None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    return routed + swiglu(x, w["s_gate"], w["s_up"], w["s_down"], quant)


def seeded(key, specs, l) -> dict:
    return {n: layer_tensor(key, n, l, s, std).astype(F32)
            for n, s, std in specs}


# ---------------------------------------------------------------------------
# serving: logits at the served positions
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("c", "quant", "qblock"))
def _hidden(key, tokens, *, c, quant, qblock):
    eps = c["rms_norm_eps"]
    gl = seeded(key, global_specs(c), 0)
    x = gl["embed"][tokens]
    w = seeded(key, dense_specs(c), 0)
    x = x + mla(w, rms(x, w["ln1"], eps), c, quant, qblock)
    h = rms(x, w["ln2"], eps)
    x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)

    def body(x, l):
        w = seeded(key, moe_specs(c), l)
        x = x + mla(w, rms(x, w["ln1"], eps), c, quant, qblock)
        return x + moe(w, key, l, rms(x, w["ln2"], eps), c, quant), None

    x, _ = jax.lax.scan(body, x, jnp.arange(c["first_k_dense_replace"],
                                            c["num_hidden_layers"]))
    return rms(x, gl["final_norm"], eps)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _logits_at(key, hidden, rows, cols, *, c, quant):
    head = seeded(key, [s for s in global_specs(c) if s[0] == "lm_head"], 0)
    return mm(hidden[rows, cols], head["lm_head"], quant)


def logits_at(seed: int, c: dict, tokens, rows, cols, quant=None,
              qblock: int = 256):
    """Reference logits [M, V] at (row, position) pairs of ``tokens``
    [N, T] (T a multiple of ``qblock``); each predicts the token after
    that position."""
    if c["first_k_dense_replace"] != 1:
        raise ValueError("one leading dense layer expected")
    key = W.root_key(seed, W.WEIGHTS)
    c = hashable(c)
    with jax.default_matmul_precision("highest"):
        h = _hidden(key, tokens, c=c, quant=quant, qblock=qblock)
        return _logits_at(key, h, rows, cols, c=c, quant=quant)
