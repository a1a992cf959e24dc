"""Plain reference of the dense GQA decoder, in float32.

Written from the published description (pre-norm RMSNorm blocks, GQA
attention with rotate-half RoPE, optional per-head q/k RMSNorm before
RoPE, SwiGLU MLP, optional tied embedding) in straightforward
``jax.numpy``: no kernels, no cache, no batching tricks.  Weights are
drawn from the seed by canonical name (``bench.weights``), the bf16
values the program serves, upcast to float32.  Matmuls run at
``highest`` precision.  Imports nothing of the program.

``quant`` computes the reference one precision below the bfloat16 the
configuration states, the control: every projection's operands are
rounded to ``"int8"`` (symmetric) or ``"fp8"`` (e4m3), scaled per
output channel for weights and per token for activations, and scaled
back.  Gradients pass straight through the rounding.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.arch.dense_gqa import dims, global_specs, layer_specs

F32 = jnp.float32
NEG = -1e30


_RANGE = {"int8": 127.0, "fp8": 448.0}


def _round(x: jax.Array, axis: int, quant: str) -> jax.Array:
    """Round ``x`` to ``quant`` with one scale per slice along ``axis``;
    the gradient passes straight through."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _RANGE[quant]
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        q = jnp.clip(jnp.round(x / s), -127, 127)
    else:
        q = (x / s).astype(jnp.float8_e4m3fn).astype(F32)
    return x + jax.lax.stop_gradient(q * s - x)


def mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    if quant is not None:
        x, w = _round(x, -1, quant), _round(w, 0, quant)
    return x @ w


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x [B, T, h, dh]; pos [T]: rotate-half RoPE."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, qblock: int):
    """Causal softmax attention, query head h reading kv head
    h // (H/K).  q [B, T, H, dh], k/v [B, T, K, dh]; T % qblock == 0.
    Query blocks bound the score matrix; each is recomputed in the
    backward pass."""
    b, t, h, dh = q.shape
    g = h // k.shape[2]
    qblock = min(qblock, t)
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    nb = t // qblock

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, k) / math.sqrt(dh)
        qpos = i * qblock + jnp.arange(qblock)
        s = jnp.where(jnp.arange(t)[None, :] <= qpos[:, None], s, NEG)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(nb))          # [nb, B, qb, H, dh]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, dh)


def layer(w: dict, x, c: dict, quant=None, qblock: int = 256):
    g = dims(c)
    b, t, _ = x.shape
    eps = c["rms_norm_eps"]
    pos = jnp.arange(t)
    hdn = rms(x, w["ln1"], eps)
    q = mm(hdn, w["wq"], quant).reshape(b, t, g["H"], g["dh"])
    k = mm(hdn, w["wk"], quant).reshape(b, t, g["K"], g["dh"])
    v = mm(hdn, w["wv"], quant).reshape(b, t, g["K"], g["dh"])
    if c["qk_norm"]:
        q, k = rms(q, w["q_norm"], eps), rms(k, w["k_norm"], eps)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    a = attention(q, k, v, qblock).reshape(b, t, g["H"] * g["dh"])
    x = x + mm(a, w["wo"], quant)
    hdn = rms(x, w["ln2"], eps)
    f = jax.nn.silu(mm(hdn, w["w_gate"], quant)) * mm(hdn, w["w_up"], quant)
    return x + mm(f, w["w_down"], quant)


def seeded_layer(key, c: dict, l) -> dict:
    """Layer ``l``'s weights from the weights key, float32."""
    return {n: (jnp.ones(s, F32) if std == 0
                else W.tensor(key, n, l, s, std).astype(F32))
            for n, s, std in layer_specs(c)}


def seeded_globals(key, c: dict) -> dict:
    return {n: (jnp.ones(s, F32) if std == 0
                else W.tensor(key, n, 0, s, std).astype(F32))
            for n, s, std in global_specs(c)}


def head(gl: dict, c: dict) -> jax.Array:
    """[d, V] output projection."""
    return gl["embed"].T if c["tie_word_embeddings"] else gl["lm_head"]


# ---------------------------------------------------------------------------
# serving: logits at the served positions
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("c", "quant", "qblock"))
def _hidden(key, tokens, *, c, quant, qblock):
    gl = seeded_globals(key, c)
    x = gl["embed"][tokens]

    def body(x, l):
        return layer(seeded_layer(key, c, l), x, c, quant, qblock), None

    x, _ = jax.lax.scan(body, x, jnp.arange(c["num_hidden_layers"]))
    return rms(x, gl["final_norm"], c["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _logits_at(key, hidden, rows, cols, *, c, quant):
    gl = seeded_globals(key, c)
    return mm(hidden[rows, cols], head(gl, c), quant)


class _Hashable(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


def hashable(c: dict) -> _Hashable:
    return c if isinstance(c, _Hashable) else _Hashable(c)


def logits_at(seed: int, c: dict, tokens, rows, cols, quant=None,
              qblock: int = 256):
    """Reference logits [M, V] at (row, position) pairs of ``tokens``
    [N, T] (T a multiple of ``qblock``); each predicts the token after
    that position."""
    key = W.root_key(seed, W.WEIGHTS)
    c = hashable(c)
    with jax.default_matmul_precision("highest"):
        h = _hidden(key, tokens, c=c, quant=quant, qblock=qblock)
        return _logits_at(key, h, rows, cols, c=c, quant=quant)


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW over the first steps
# ---------------------------------------------------------------------------

def init_params(key, c: dict) -> dict:
    """Canonical float32 parameters, layer tensors stacked [L, ...]."""
    p = seeded_globals(key, c)
    L = c["num_hidden_layers"]
    for n, s, std in layer_specs(c):
        p[n] = jnp.ones((L,) + s, F32) if std == 0 else jax.vmap(
            lambda l: W.tensor(key, n, l, s, std))(jnp.arange(L)).astype(F32)
    return p


def loss(p: dict, tokens, c: dict, z_loss: float, quant=None,
         qblock: int = 256, tblock: int = 512):
    """Mean next-token loss (cross-entropy + z_loss * logsumexp^2) and
    mean cross-entropy of ``tokens`` [B, T+1]."""
    names = [n for n, _, _ in layer_specs(c)]
    x = p["embed"][tokens[:, :-1]]

    @jax.checkpoint
    def body(x, lw):
        return layer(dict(zip(names, lw)), x, c, quant, qblock), None

    x, _ = jax.lax.scan(body, x, [p[n] for n in names])
    x = rms(x, p["final_norm"], c["rms_norm_eps"])
    b, t, d = x.shape
    tgt = tokens[:, 1:]
    w = head(p, c)

    @jax.checkpoint
    def chunk(args):
        xc, yc = args
        lg = mm(xc, w, quant)
        lse = jax.nn.logsumexp(lg, -1)
        nll = lse - jnp.take_along_axis(lg, yc[..., None], -1)[..., 0]
        return jnp.sum(nll), jnp.sum(z_loss * lse * lse)

    tblock = min(tblock, t)
    nc = t // tblock
    xs = x.reshape(b, nc, tblock, d).transpose(1, 0, 2, 3)
    ys = tgt.reshape(b, nc, tblock).transpose(1, 0, 2)
    nll, zl = jax.lax.map(chunk, (xs, ys))
    n = b * t
    return (jnp.sum(nll) + jnp.sum(zl)) / n, jnp.sum(nll) / n


def adamw(p, g, m, v, step, hp: dict):
    """One AdamW step (bias-corrected, decoupled weight decay)."""
    t = step + 1.0
    b1, b2 = hp["b1"], hp["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        return p - hp["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
                               + hp["weight_decay"] * p)
    return jax.tree.map(upd, p, m, v), m, v


@functools.partial(jax.jit, static_argnames=("c", "z_loss", "quant"))
def _grad(p, tokens, *, c, z_loss, quant):
    """Mean cross-entropy and gradient of the mean loss over the rows of
    ``tokens``, one row at a time."""
    def one(tok):
        (_, nll), g = jax.value_and_grad(loss, has_aux=True)(
            p, tok[None], c, z_loss, quant)
        return g, nll

    n = tokens.shape[0]
    if n == 1:
        return one(tokens[0])

    def row(acc, tok):
        return jax.tree.map(jnp.add, acc, one(tok)), None

    zero = (jax.tree.map(jnp.zeros_like, p), jnp.zeros((), F32))
    (g, nll), _ = jax.lax.scan(row, zero, tokens)
    return jax.tree.map(lambda x: x / n, g), nll / n


def norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))))
            for k, v in tree.items()}


def train_readings(seed: int, c: dict, batches, hp: dict, z_loss: float,
                   quant=None) -> dict:
    """Follow the first ``len(batches)`` steps: per-step mean
    cross-entropy, the first step's gradient norm per canonical leaf,
    and the norm of each leaf's change over all the steps."""
    c = hashable(c)
    key = W.root_key(seed, W.WEIGHTS)
    init = jax.jit(init_params, static_argnums=1)
    # the update donates parameters and moments: at most one copy of
    # each is alive
    step = jax.jit(functools.partial(adamw, hp=hp), donate_argnums=(0, 2, 3))
    with jax.default_matmul_precision("highest"):
        p = init(key, c)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        nlls, g1 = [], None
        for i, tok in enumerate(batches):
            g, nll = _grad(p, tok, c=c, z_loss=z_loss, quant=quant)
            nlls.append(float(nll))
            if g1 is None:
                g1 = norms(g)
            p, m, v = step(p, g, m, v, jnp.float32(i))
            del g
        del m, v
        delta = norms(jax.tree.map(jnp.subtract, p, init(key, c)))
    return {"nll": nlls, "grad": g1, "delta": delta}
