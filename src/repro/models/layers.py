"""Base layers (pure-functional): norms, dense, embedding, RoPE, FFN, conv.

Params are plain nested dicts of jax.Arrays; ``init_*`` builds them,
``apply``-style functions consume them.  Sharding is attached later by
path-pattern rules (repro/sharding/rules.py) so layer code stays
mesh-agnostic.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((d,), dtype)}


def _rmsnorm_fwd_impl(scale, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)
    return y, (xf, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm(scale, x, eps):
    return _rmsnorm_fwd_impl(scale, x, eps)[0]


def _rmsnorm_fwd(scale, x, eps):
    y, res = _rmsnorm_fwd_impl(scale, x, eps)
    # zero-size sentinel carries the primal dtype (dtypes aren't jax types)
    return y, (scale, jnp.zeros((0,), x.dtype)) + res


def _rmsnorm_bwd(eps, res, g):
    """Backward in f32 internally, but the cotangent LEAVES in the primal
    dtype: without this, the f32 upcast promotes the whole residual-stream
    cotangent chain to f32 and every TP all-reduce on the backward path
    doubles its wire bytes (measured: the dominant collective in the dense
    train cells)."""
    scale, xdt_sentinel, xf, inv = res
    xdt = xdt_sentinel.dtype
    gf = g.astype(jnp.float32)
    sf = scale.astype(jnp.float32)
    xhat = xf * inv
    dscale = jnp.sum(gf * xhat, axis=tuple(range(gf.ndim - 1)))
    gx = gf * sf
    d = xf.shape[-1]
    dx = inv * (gx - xhat * jnp.mean(gx * xhat, axis=-1, keepdims=True))
    return dscale.astype(scale.dtype), dx.astype(xdt)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(p: PyTree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return _rmsnorm(p["scale"], x, eps)


def init_layernorm(d: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: PyTree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def init_norm(d: int, kind: str = "rms") -> PyTree:
    return init_layernorm(d) if kind == "layer" else init_rmsnorm(d)


def apply_norm(p: PyTree, x: jax.Array, kind: str = "rms",
               eps: float = 1e-6) -> jax.Array:
    return layernorm(p, x, eps) if "bias" in p else rmsnorm(p, x, eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor 0.1 * mscale * ln(factor) + 1 (1 for a
    context that is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(d_head: int, theta: float = 10000.0,
                     scaling=None) -> jax.Array:
    """[d_head/2] inverse wavelengths; with a ``YarnScaling`` those of
    DeepSeek-V2's YaRN rotary embedding: the original frequencies up to
    the dim that turns ``beta_fast`` times over the original context,
    ``factor`` times slower from the one that turns ``beta_slow`` times,
    and a linear ramp between."""
    exps = jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head
    freq = 1.0 / (theta ** exps)
    if scaling is None:
        return freq
    s = scaling

    def dim_of(turns):
        return d_head * math.log(s.original_max_position_embeddings
                                 / (turns * 2 * math.pi)) / (
                                     2 * math.log(theta))

    low = max(math.floor(dim_of(s.beta_fast)), 0)
    high = min(math.ceil(dim_of(s.beta_slow)), d_head - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d_head // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    slow = 1.0 / (s.factor * theta ** exps)
    return slow * ramp + freq * (1 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0, scaling=None) -> jax.Array:
    """x: [..., T, H, d_head]; positions: [..., T] (absolute); rotate
    half.  ``scaling``: a ``YarnScaling`` or None."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta, scaling)            # [d/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,T,1,d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        amp = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------

def init_ffn(key, d: int, f: int, activation: str, dtype=jnp.bfloat16) -> PyTree:
    ks = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return {"wi_gate": dense_init(ks[0], d, f, dtype),
                "wi_up": dense_init(ks[1], d, f, dtype),
                "wo": dense_init(ks[2], f, d, dtype)}
    return {"wi": dense_init(ks[0], d, f, dtype),
            "wo": dense_init(ks[1], f, d, dtype)}


def ffn(p: PyTree, x: jax.Array, activation: str) -> jax.Array:
    from repro.sharding.act import shard_act
    if activation == "swiglu":
        h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    elif activation == "geglu":
        h = jax.nn.gelu(x @ p["wi_gate"], approximate=True) * (x @ p["wi_up"])
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["wi"]))
    elif activation == "gelu":
        h = jax.nn.gelu(x @ p["wi"], approximate=True)
    else:
        raise ValueError(f"unknown activation {activation!r}")
    if h.ndim == 3:
        h = shard_act(h, "dp", None, "tp")
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# causal temporal conv (RG-LRU branch / audio-style frontends)
# ---------------------------------------------------------------------------

def init_conv1d(key, width: int, channels: int, dtype=jnp.bfloat16) -> PyTree:
    k = jax.random.normal(key, (width, channels), jnp.float32) / math.sqrt(width)
    return {"kernel": k.astype(dtype), "bias": jnp.zeros((channels,), dtype)}


def causal_conv1d(p: PyTree, x: jax.Array) -> jax.Array:
    """Depthwise causal conv over time.  x: [B, T, C]."""
    width = p["kernel"].shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + pad[:, i:i + x.shape[1], :] * p["kernel"][i]
    return out + p["bias"]


def conv1d_decode(p: PyTree, window: jax.Array, x_t: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """Single-step conv with a rolling window cache.

    window: [B, width-1, C] (the last width-1 inputs); x_t: [B, C].
    Returns (y_t, new_window).
    """
    width = p["kernel"].shape[0]
    full = jnp.concatenate([window, x_t[:, None, :]], axis=1)  # [B, width, C]
    y = jnp.einsum("bwc,wc->bc", full, p["kernel"]) + p["bias"]
    return y, full[:, 1:, :]


# ---------------------------------------------------------------------------
# embedding / logits
# ---------------------------------------------------------------------------

def embed_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


def logits_head(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: [..., D] @ w: [D, V] in f32 for stable softmax/CE."""
    return (x.astype(jnp.float32) @ w.astype(jnp.float32))
