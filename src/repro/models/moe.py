"""Mixture-of-Experts FFN — grouped scatter/gather dispatch (EP-shardable).

Dispatch is the production TPU formulation (MaxText-style "dropped token"
MoE): tokens are split into groups aligned with the data shards; within a
group, routing/capacity bookkeeping is local and tokens are *scattered*
into per-expert capacity slots (O(N·k·D) data movement — NOT the GShard
one-hot dispatch einsum, whose O(N·E·C·D) FLOPs rival the expert compute
itself at E=160).  The group→expert reshard of the slot tensor is where
GSPMD inserts the all-to-all — the exact communication pattern ACiS Type 4
fuses (core/fused.fused_allreduce_alltoall).

Routing (:func:`route`): softmax → top-k, greedy or group-limited
(DeepSeek-V2) → renormalised (Qwen-MoE style) or not → scaled, plus the
standard load-balancing auxiliary loss.  Fixed per-group capacity keeps
shapes static (TPU requirement); overflow tokens drop (combine weight 0)
exactly as in GShard.  Single-token decode uses capacity = group size (no
drops).

Expert share: a layer may hold only ``MoEConfig.held_range`` of the
experts, as one chip of expert parallelism does.  It routes over all of
them and dispatches only the choices that land on its own; the others
add nothing, and the shared experts run whole.
"""

from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import parallel as TP
from repro.models.config import MoEConfig

PyTree = Any

# Target tokens per dispatch group.  Must be small enough that the group
# count covers the data axis (G % dp == 0) for every assigned cell —
# otherwise the [G, slots, D] dispatch tensor replicates across data
# shards (observed: 39 GB/device on deepseek-v2 before this was sized).
GROUP_TOKENS = 4096


def init_moe(key, d_model: int, cfg: MoEConfig, activation: str,
             dtype=jnp.bfloat16) -> PyTree:
    ks = jax.random.split(key, 8)
    e, f = cfg.held_range[1], cfg.d_ff_expert

    def stack(k, d_in, d_out):
        keys = jax.random.split(k, e)
        return jnp.stack([L.dense_init(kk, d_in, d_out, dtype) for kk in keys])

    p = {"router": L.dense_init(ks[0], d_model, cfg.n_experts, jnp.float32,
                                scale=0.02)}
    if activation in ("swiglu", "geglu"):
        p["experts"] = {"wi_gate": stack(ks[1], d_model, f),
                        "wi_up": stack(ks[2], d_model, f),
                        "wo": stack(ks[3], f, d_model)}
    else:
        p["experts"] = {"wi": stack(ks[1], d_model, f),
                        "wo": stack(ks[3], f, d_model)}
    if cfg.n_shared:
        p["shared"] = L.init_ffn(ks[4], d_model,
                                 cfg.d_ff_shared or cfg.n_shared * f,
                                 activation, dtype)
    return p


def _expert_ffn(experts: PyTree, xe: jax.Array, activation: str) -> jax.Array:
    """xe: [E, S, D] -> [E, S, D] through per-expert FFN weights."""
    if activation in ("swiglu", "geglu"):
        gate = jnp.einsum("esd,edf->esf", xe, experts["wi_gate"])
        up = jnp.einsum("esd,edf->esf", xe, experts["wi_up"])
        act = jax.nn.silu if activation == "swiglu" else \
            (lambda a: jax.nn.gelu(a, approximate=True))
        h = act(gate) * up
    else:
        h = jnp.einsum("esd,edf->esf", xe, experts["wi"])
        h = jnp.square(jax.nn.relu(h)) if activation == "relu2" else \
            jax.nn.gelu(h, approximate=True)
    return jnp.einsum("esf,efd->esd", h, experts["wo"])


def _n_groups(n_tok: int) -> int:
    if n_tok <= GROUP_TOKENS:
        return 1
    g = n_tok // GROUP_TOKENS
    while n_tok % g:
        g -= 1
    return max(g, 1)


def route(logits: jax.Array, cfg: MoEConfig
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router logits [..., E] -> (scores [..., E], weights [..., k],
    experts [..., k]).  ``group_limited_greedy`` keeps the ``topk_group``
    of ``n_group`` groups whose best score is highest and takes the top
    k inside them; ties go to the lower index."""
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    if cfg.topk_method == "group_limited_greedy":
        grouped = probs.reshape(probs.shape[:-1] + (cfg.n_group, -1))
        _, best = jax.lax.top_k(grouped.max(-1), cfg.topk_group)
        kept = jnp.any(best[..., :, None] == jnp.arange(cfg.n_group), -2)
        scores = jnp.where(kept[..., None], grouped, 0.0).reshape(
            probs.shape)
    gate_vals, gate_idx = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gate_vals = gate_vals * cfg.routed_scaling_factor
    return probs, gate_vals, gate_idx


def moe_ffn(p: PyTree, x: jax.Array, cfg: MoEConfig, activation: str, *,
            scope: Optional[str] = None) -> tuple[jax.Array, jax.Array]:
    """x: [B, T, D].  Returns (y, aux_loss).  ``scope``: a name prefix
    for the routing, routed-expert and shared-expert regions
    (``<scope>.route``, ``.experts``, ``.shared``)."""
    def region(name):
        return jax.named_scope(f"{scope}.{name}") if scope else \
            contextlib.nullcontext()

    b, t, d = x.shape
    n_tok = b * t
    e, k = cfg.n_experts, cfg.top_k
    first, held = cfg.held_range
    g = _n_groups(n_tok)
    ng = n_tok // g
    if t == 1:                                   # decode: never drop
        cap = ng
    else:
        cap = max(1, int(ng * k * cfg.capacity_factor / e))
    xt = x.reshape(g, ng, d)

    with region("route"):
        # float32 routing: the default precision would round both
        # operands to bfloat16 on a TPU, and a near-tie would then take
        # another expert than the float32 scores do
        logits = jnp.dot(xt.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)  # [G, Ng, E]
        probs, gate_vals, gate_idx = route(logits, cfg)        # [G, Ng, k]
        local = gate_idx - first             # index among the held experts
        mine = (local >= 0) & (local < held)

    with region("experts"):
        # position-in-expert within the group, k-major priority (GShard
        # order); a choice off the held experts has no row in the one-hot
        onehot = jax.nn.one_hot(local, held, dtype=jnp.float32)  # [G,Ng,k,E]
        flat = onehot.transpose(0, 2, 1, 3).reshape(g, k * ng, held)
        pos_flat = jnp.cumsum(flat, axis=1) - flat
        pos = pos_flat.reshape(g, k, ng, held).transpose(0, 2, 1, 3)
        pos_in_e = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
        keep = mine & (pos_in_e < cap)

        # scatter tokens into capacity slots: xe [G, E*cap(+dump), D].
        # Slot buffers live in the activation dtype (bf16): each slot
        # receives at most ONE token (positions are unique), so the
        # "accumulation" is really placement — no precision is lost, and
        # the buffers are the dominant MoE activation (f32 here cost 2×
        # memory: 23 GB/device on the 236B prefill cell before this).
        n_slots = held * cap
        slot = jnp.where(keep, local * cap + pos_in_e, n_slots)  # [G,Ng,k]
        xe = jnp.zeros((g, n_slots + 1, d), xt.dtype)
        for j in range(k):           # k small: one scatter per choice
            xe = jax.vmap(lambda buf, s, v: buf.at[s].add(v))(
                xe, slot[:, :, j], xt)
        xe = xe[:, :n_slots, :]

        from repro.sharding.act import shard_act
        xe = shard_act(xe.reshape(g, held, cap, d), "dp", None, None, None)
        # group-major -> expert-major: THE all-to-all (GSPMD inserts it
        # here).  Slot dim stays DATA-sharded: when E doesn't divide the
        # model axis (qwen2: 60 experts on 16) "tp" drops and a replicated
        # slot tensor would force activation-sized all-reduces in the
        # expert FFN (observed: 83 s/step collective time on qwen2-moe
        # before this).
        xem = xe.transpose(1, 0, 2, 3).reshape(held, g * cap, d)
        xem = shard_act(xem, "tp", "dp", None)
        tp = TP.current()
        if tp is not None:                   # rank-local TP (serving path)
            xem = tp.moe_dispatch(xem)       # [E, S, D] -> [E/tp, S, D]
        yem = _expert_ffn(p["experts"], xem, activation)
        yem = shard_act(yem, "tp", "dp", None)
        # expert-major -> group-major: the second all-to-all (bf16 on the
        # wire)
        shared_y = None
        if tp is not None:
            # the shared-expert partial (sliced FFN needing an all-reduce)
            # rides the combine all-to-all: FuseHops merges the independent
            # same-axis pair into one Type-4 allreduce+alltoall stage
            part = None
            if "shared" in p:
                with region("shared"):
                    part = L.ffn(p["shared"], xt, activation)
            yem, shared_y = tp.moe_combine(yem, part)
        ye = yem.reshape(held, g, cap, d).transpose(1, 0, 2, 3)
        ye = ye.reshape(g, n_slots, d)
        ye = jnp.concatenate([ye, jnp.zeros((g, 1, d), ye.dtype)], 1)

        y = jnp.zeros((g, ng, d), jnp.float32)
        for j in range(k):                   # gather + weighted combine
            yj = jnp.take_along_axis(ye, slot[:, :, j][..., None], axis=1)
            wj = (gate_vals[:, :, j] * keep[:, :, j].astype(jnp.float32))
            y = y + yj.astype(jnp.float32) * wj[..., None]
    if shared_y is None and "shared" in p:
        with region("shared"):
            shared_y = L.ffn(p["shared"], xt, activation)
    if shared_y is not None:
        y = y + shared_y.astype(jnp.float32)

    # load-balance aux: E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))
    ce = jax.nn.one_hot(gate_idx[:, :, 0], e, dtype=jnp.float32).mean(
        axis=(0, 1))
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight
    return y.reshape(b, t, d).astype(x.dtype), aux
