"""MoE routing and expert shares.

Group-limited routing against a plain NumPy version (ties included);
a layer holding a range of the experts against one holding all; the
tally of choices on the held experts; and configurations without the
new keys held, bit for bit, to the formulation that preceded them (kept
below as the reference).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import MoEConfig, Model
from repro.models import moe as MOE
from repro.sharding.act import shard_act

GROUPED = MoEConfig(n_experts=16, top_k=3, n_shared=1, d_ff_expert=8,
                    d_ff_shared=8, topk_method="group_limited_greedy",
                    n_group=4, topk_group=2, norm_topk_prob=False,
                    routed_scaling_factor=16.0)


def numpy_route(logits: np.ndarray, cfg: MoEConfig):
    """The published router, one token at a time: softmax; groups
    ranked by their best score, ties to the lower group; the top k of the
    kept groups' experts, ties to the lower expert."""
    e = logits - logits.max(-1, keepdims=True)
    probs = np.exp(e) / np.exp(e).sum(-1, keepdims=True)
    vals = np.zeros(probs.shape[:-1] + (cfg.top_k,), np.float32)
    idx = np.zeros(probs.shape[:-1] + (cfg.top_k,), np.int64)
    for pos in np.ndindex(probs.shape[:-1]):
        s = probs[pos].copy()
        if cfg.topk_method == "group_limited_greedy":
            per = s.reshape(cfg.n_group, -1)
            order = np.argsort(-per.max(-1), kind="stable")
            dropped = order[cfg.topk_group:]
            per[dropped] = 0.0
            s = per.reshape(-1)
        top = np.argsort(-s, kind="stable")[:cfg.top_k]
        w = s[top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        vals[pos], idx[pos] = w * cfg.routed_scaling_factor, top
    return probs, vals, idx


def test_group_limited_routing_matches_numpy():
    logits = jax.random.normal(jax.random.key(3), (5, 7, 16), jnp.float32)
    probs, vals, idx = MOE.route(logits, GROUPED)
    p_np, v_np, i_np = numpy_route(np.asarray(logits, np.float64), GROUPED)
    np.testing.assert_array_equal(np.asarray(idx), i_np)
    np.testing.assert_allclose(np.asarray(vals), v_np, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(probs), p_np, rtol=1e-5)
    # every choice lies in one of the token's topk_group groups
    groups = np.asarray(idx) // (16 // 4)
    assert all(len(set(g)) <= 2 for g in groups.reshape(-1, 3))


def test_ties_go_to_the_lower_index():
    """Ties inside a group, for the last choice, and between groups."""
    lg = np.full((3, 16), -4.0, np.float32)
    # token 0: experts 1 and 2 (group 0, kept with group 3) tie for the
    # third choice; expert 5 would beat both but group 1 is dropped
    lg[0, [0, 1, 2, 12]] = [3.0, 1.0, 1.0, 2.0]
    lg[0, 5] = 1.0 - 1e-3
    # token 1: groups 1 and 3 tie on their best score; group 0 is first
    lg[1, [1, 4, 14]] = [2.0, 1.5, 1.5]
    # token 2: everything equal
    lg[2] = 0.0
    _, vals, idx = MOE.route(jnp.asarray(lg), GROUPED)
    _, v_np, i_np = numpy_route(lg.astype(np.float64), GROUPED)
    np.testing.assert_array_equal(np.asarray(idx), i_np)
    np.testing.assert_allclose(np.asarray(vals), v_np, rtol=1e-5)
    assert list(np.asarray(idx[0])) == [0, 12, 1]
    assert set(np.asarray(idx[1])) == {1, 4, 0}   # group 3 loses the tie
    assert list(np.asarray(idx[2])) == [0, 1, 2]


def test_greedy_renormalised_default():
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=4)
    logits = jax.random.normal(jax.random.key(0), (4, 8))
    _, vals, idx = MOE.route(logits, cfg)
    _, v_np, i_np = numpy_route(np.asarray(logits, np.float64), cfg)
    np.testing.assert_array_equal(np.asarray(idx), i_np)
    np.testing.assert_allclose(np.asarray(vals), v_np, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 1.0, rtol=1e-6)


def test_config_checks():
    with pytest.raises(ValueError, match="topk_method"):
        MoEConfig(n_experts=8, topk_method="noaux_tc")
    with pytest.raises(ValueError, match="n_group"):
        MoEConfig(n_experts=8, n_group=3)
    with pytest.raises(ValueError, match="outside"):
        MoEConfig(n_experts=8, first_expert=4, experts_held=8)
    assert MoEConfig(n_experts=8).held_range == (0, 8)
    assert GROUPED.held_range == (0, 16)


def _layer(cfg, key, d=12):
    """A MoE layer's float32 parameters with every expert; and x."""
    p = MOE.init_moe(key, d, cfg, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 5, d))
    return p, x


def _share(p, first, held):
    return dict(p, experts=jax.tree.map(lambda a: a[first:first + held],
                                        p["experts"]))


@pytest.mark.parametrize("first", [0, 4, 12])
def test_held_range_adds_its_experts_part(first):
    """A share's output, less the shared experts, is the whole layer's
    routed sum over the choices that land on its experts."""
    cfg = dataclasses.replace(GROUPED, capacity_factor=16.0)
    p, x = _layer(cfg, jax.random.key(5))
    share = dataclasses.replace(cfg, first_expert=first, experts_held=4)
    y, _ = MOE.moe_ffn(_share(p, first, 4), x, share, "swiglu")
    shared = MOE.L.ffn(p["shared"], x, "swiglu")
    _, vals, idx = MOE.route(x @ p["router"], cfg)
    want = jnp.zeros_like(x)
    for e in range(first, first + 4):
        w = jnp.sum(jnp.where(idx == e, vals, 0.0), -1)[..., None]
        ex = jax.tree.map(lambda a: a[e:e + 1], p["experts"])
        ye = MOE._expert_ffn(ex, x.reshape(1, -1, x.shape[-1]), "swiglu")
        want = want + w * ye.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y - shared), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# configurations without the new keys: the preceding formulation, bit for
# bit
# ---------------------------------------------------------------------------

def parent_moe_ffn(p, x, cfg, activation):
    """The MoE FFN as it was before routing options and expert shares:
    softmax, top-k, renormalised; every expert held."""
    b, t, d = x.shape
    n_tok = b * t
    e, k = cfg.n_experts, cfg.top_k
    g = MOE._n_groups(n_tok)
    ng = n_tok // g
    cap = ng if t == 1 else max(1, int(ng * k * cfg.capacity_factor / e))
    xt = x.reshape(g, ng, d)
    logits = (xt.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    flat = onehot.transpose(0, 2, 1, 3).reshape(g, k * ng, e)
    pos_flat = jnp.cumsum(flat, axis=1) - flat
    pos = pos_flat.reshape(g, k, ng, e).transpose(0, 2, 1, 3)
    pos_in_e = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
    keep = pos_in_e < cap
    n_slots = e * cap
    slot = jnp.where(keep, gate_idx * cap + pos_in_e, n_slots)
    xe = jnp.zeros((g, n_slots + 1, d), x.dtype)
    for j in range(k):
        xe = jax.vmap(lambda buf, s, v: buf.at[s].add(v))(
            xe, slot[:, :, j], xt)
    xe = xe[:, :n_slots, :]
    xe = shard_act(xe.reshape(g, e, cap, d), "dp", None, None, None)
    xem = xe.transpose(1, 0, 2, 3).reshape(e, g * cap, d)
    yem = MOE._expert_ffn(p["experts"], xem, activation)
    shared_y = MOE.L.ffn(p["shared"], xt, activation) if "shared" in p \
        else None
    ye = yem.reshape(e, g, cap, d).transpose(1, 0, 2, 3)
    ye = ye.reshape(g, n_slots, d)
    ye = jnp.concatenate([ye, jnp.zeros((g, 1, d), ye.dtype)], axis=1)
    y = jnp.zeros((g, ng, d), jnp.float32)
    for j in range(k):
        yj = jnp.take_along_axis(ye, slot[:, :, j][..., None], axis=1)
        wj = (gate_vals[:, :, j] * keep[:, :, j].astype(jnp.float32))
        y = y + yj.astype(jnp.float32) * wj[..., None]
    if shared_y is not None:
        y = y + shared_y.astype(jnp.float32)
    me = probs.mean(axis=(0, 1))
    ce = onehot[:, :, 0, :].mean(axis=(0, 1))
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight
    return y.reshape(b, t, d).astype(x.dtype), aux


@pytest.mark.parametrize("t", [1, 6])
def test_qwen2_moe_layer_is_bit_identical(t):
    cfg = configs.get_smoke("qwen2-moe-a2.7b")
    model = Model(cfg)
    params = model.init(jax.random.key(1))
    p = jax.tree.map(lambda a: a[0], params["layers"])["pos0_moe_self"]["moe"]
    x = jax.random.normal(jax.random.key(2), (4, t, cfg.d_model),
                          jnp.bfloat16)
    y, aux = jax.jit(lambda p, x: MOE.moe_ffn(p, x, cfg.moe,
                                              cfg.activation))(p, x)
    y0, aux0 = jax.jit(lambda p, x: parent_moe_ffn(p, x, cfg.moe,
                                                   cfg.activation))(p, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(aux), np.asarray(aux0))


def test_rope_without_scaling_is_bit_identical():
    from repro.models import layers as L
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 64), jnp.bfloat16)
    pos = jnp.arange(9)[None] + 1000
    for theta in (10000.0, 1e6):
        def parent_rope(x, pos):
            inv = 1.0 / (theta ** (jnp.arange(0, 64, 2, dtype=jnp.float32)
                                   / 64))
            ang = pos[..., :, None, None].astype(jnp.float32) * inv
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
            return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                   axis=-1).astype(x.dtype), inv

        want, inv = jax.jit(parent_rope)(x, pos)
        got = jax.jit(lambda x, p: L.apply_rope(x, p, theta))(x, pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(L.rope_frequencies, static_argnums=(0, 1))(
                64, theta)), np.asarray(inv))
