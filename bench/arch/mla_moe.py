"""Latent-attention MoE decoder (DeepSeek-V2): the configuration file's
keys, the seeded weights by canonical name, and how they map onto the
program's parameter tree.

The configuration file uses the published ``config.json`` key names.  It
holds one chip's expert-parallel share of each MoE layer:
``n_routed_experts`` experts from ``first_expert`` on, of the
``published`` count the router scores.  An expert's weights are drawn by
its global index, so a share holds the same tensors whichever range it
is given.  Layer 0 (``first_k_dense_replace`` of them) is dense; every
layer has latent attention.  Canonical weight names are shared with the
plain reference in ``bench/ref/mla_moe.py``; only this module knows the
program's layout, and ``program_params`` checks it against the program's
own shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import weights as W

# program tree of the dense leading layer and of the stacked MoE layers
DENSE, MOE = ("rem", "rem0_dense_self"), ("layers", "pos0_moe_self")


def experts(c: dict) -> tuple[int, int, int]:
    """(experts the router scores, first held, number held)."""
    total = c.get("published", {}).get("n_routed_experts",
                                       c["n_routed_experts"])
    return total, c.get("first_expert", 0), c["n_routed_experts"]


def attn_specs(c: dict) -> list[tuple[str, tuple, float]]:
    """(name, shape, std) of one layer's norms and latent attention; std
    0 marks a norm scale (ones)."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return [("ln1", (d,), 0.0), ("ln2", (d,), 0.0),
            ("w_dq", (d, ql), 1 / math.sqrt(d)),
            ("q_norm", (ql,), 0.0),
            ("w_uq", (ql, H * (nope + rope)), 1 / math.sqrt(ql)),
            ("w_dkv", (d, kl + rope), 1 / math.sqrt(d)),
            ("kv_norm", (kl,), 0.0),
            ("w_uk", (kl, H * nope), 1 / math.sqrt(kl)),
            ("w_uv", (kl, H * v), 1 / math.sqrt(kl)),
            ("wo", (H * v, d), 1 / math.sqrt(H * v))]


def dense_specs(c: dict) -> list[tuple[str, tuple, float]]:
    d, f = c["hidden_size"], c["intermediate_size"]
    return attn_specs(c) + [("w_gate", (d, f), 1 / math.sqrt(d)),
                            ("w_up", (d, f), 1 / math.sqrt(d)),
                            ("w_down", (f, d), 1 / math.sqrt(f))]


def moe_specs(c: dict) -> list[tuple[str, tuple, float]]:
    """A MoE layer's tensors but its routed experts (:func:`expert_specs`);
    the router is float32."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    fs = c["n_shared_experts"] * f
    return attn_specs(c) + [
        ("router", (d, experts(c)[0]), 1 / math.sqrt(d)),
        ("s_gate", (d, fs), 1 / math.sqrt(d)),
        ("s_up", (d, fs), 1 / math.sqrt(d)),
        ("s_down", (fs, d), 1 / math.sqrt(fs))]


def expert_specs(c: dict) -> list[tuple[str, tuple, float]]:
    """One routed expert's tensors."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return [("e_gate", (d, f), 1 / math.sqrt(d)),
            ("e_up", (d, f), 1 / math.sqrt(d)),
            ("e_down", (f, d), 1 / math.sqrt(f))]


def global_specs(c: dict) -> list[tuple[str, tuple, float]]:
    d, V = c["hidden_size"], c["vocab_size"]
    return [("embed", (V, d), c["init"]["embed_std"]),
            ("final_norm", (d,), 0.0),
            ("lm_head", (d, V), 1 / math.sqrt(d))]


def layer_tensor(key, name: str, layer, shape, std: float):
    """A layer's tensor as served: norms f32 ones, the router f32, the
    rest bf16."""
    if std == 0:
        return jnp.ones(shape, jnp.float32)
    return W.tensor(key, name, layer, shape, std,
                    jnp.float32 if name == "router" else jnp.bfloat16)


def expert_tensor(c: dict, key, name: str, layer, expert, shape,
                  std: float):
    """Routed expert ``expert`` (global index) of ``layer``, bf16."""
    return W.tensor(key, name, layer * experts(c)[0] + expert, shape, std)


def held_experts(c: dict, key, layer) -> dict:
    """{name: [held, ...]} of ``layer``'s held experts."""
    _, first, held = experts(c)
    ids = first + jnp.arange(held)
    return {n: jax.vmap(lambda e: expert_tensor(c, key, n, layer, e, s,
                                                std))(ids)
            for n, s, std in expert_specs(c)}


# canonical name -> path inside a block of the program's tree
_BLOCK_PATH = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    "w_dq": ("attn", "w_dq"), "q_norm": ("attn", "q_norm", "scale"),
    "w_uq": ("attn", "w_uq"), "w_dkv": ("attn", "w_dkv"),
    "kv_norm": ("attn", "kv_norm", "scale"), "w_uk": ("attn", "w_uk"),
    "w_uv": ("attn", "w_uv"), "wo": ("attn", "wo"),
    "w_gate": ("ffn", "wi_gate"), "w_up": ("ffn", "wi_up"),
    "w_down": ("ffn", "wo"),
    "router": ("moe", "router"),
    "s_gate": ("moe", "shared", "wi_gate"), "s_up": ("moe", "shared", "wi_up"),
    "s_down": ("moe", "shared", "wo"),
    "e_gate": ("moe", "experts", "wi_gate"),
    "e_up": ("moe", "experts", "wi_up"), "e_down": ("moe", "experts", "wo"),
}
_GLOBAL_PATH = {"embed": ("embed",), "final_norm": ("final_norm", "scale"),
                "lm_head": ("lm_head",)}


def program_paths(c: dict) -> dict[str, tuple]:
    """Canonical name -> path of that leaf in the program's tree; the
    dense layer's names carry the prefix ``dense.``, the MoE layers'
    (stacked [N, ...]) ``moe.``."""
    out = {n: _GLOBAL_PATH[n] for n, _, _ in global_specs(c)}
    out.update({f"dense.{n}": DENSE + _BLOCK_PATH[n]
                for n, _, _ in dense_specs(c)})
    out.update({f"moe.{n}": MOE + _BLOCK_PATH[n]
                for n, _, _ in moe_specs(c) + expert_specs(c)})
    return out


def canonical(c: dict, key: jax.Array) -> dict[str, jax.Array]:
    """Every weight by canonical name (:func:`program_paths`) from the
    weights ``key`` of a seed; layer ``l`` of the stack [N, ...] is
    absolute layer ``first_k_dense_replace + l``."""
    n_dense = c["first_k_dense_replace"]
    if n_dense != 1:
        raise ValueError(f"{c['name']}: one leading dense layer, not "
                         f"{n_dense}")
    moe_layers = jnp.arange(n_dense, c["num_hidden_layers"])
    out = {n: layer_tensor(key, n, 0, s, std)
           for n, s, std in global_specs(c)}
    out.update({f"dense.{n}": layer_tensor(key, n, 0, s, std)
                for n, s, std in dense_specs(c)})
    for n, s, std in moe_specs(c):
        out[f"moe.{n}"] = jax.vmap(
            lambda l: layer_tensor(key, n, l, s, std))(moe_layers)
    stack = jax.vmap(lambda l: held_experts(c, key, l))(moe_layers)
    out.update({f"moe.{n}": v for n, v in stack.items()})
    return out


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def to_program(c: dict, flat: dict) -> dict:
    """Canonical name -> leaf, arranged as the program's tree."""
    tree: dict = {}
    for name, path in program_paths(c).items():
        _set(tree, path, flat[name])
    return tree


def from_program(c: dict, tree) -> dict:
    """The program's tree -> canonical name -> leaf."""
    out = {}
    for name, path in program_paths(c).items():
        node = tree
        for k in path:
            node = node[k]
        out[name] = node
    return out


def model_config(c: dict, *, max_seq: int, remat: str = "full"):
    """The program's ``ModelConfig`` for this file."""
    from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                     YarnScaling)
    rs = c["rope_scaling"]
    if (c["hidden_act"] != "silu" or c["attention_bias"]
            or c["tie_word_embeddings"] or c["moe_layer_freq"] != 1
            or c["scoring_func"] != "softmax" or rs["type"] != "yarn"):
        raise ValueError(f"{c['name']}: not a DeepSeek-V2 decoder (SwiGLU, "
                         "no attention bias, untied, MoE in every layer "
                         "after the dense ones, softmax router, YaRN "
                         "rope)")
    total, first, held = experts(c)
    f = c["moe_intermediate_size"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=f, vocab=c["vocab_size"],
        activation="swiglu", tie_embeddings=False,
        rope_theta=float(c["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        norm_eps=c["rms_norm_eps"], max_seq=max_seq, remat=remat,
        moe=MoEConfig(
            n_experts=total, top_k=c["num_experts_per_tok"],
            n_shared=c["n_shared_experts"], d_ff_expert=f,
            d_ff_shared=c["n_shared_experts"] * f,
            first_dense_layers=c["first_k_dense_replace"],
            d_ff_dense=c["intermediate_size"],
            topk_method=c["topk_method"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            norm_topk_prob=c["norm_topk_prob"],
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            first_expert=first, experts_held=held),
        mla=MLAConfig(kv_lora=c["kv_lora_rank"], q_lora=c["q_lora_rank"],
                      rope_head_dim=c["qk_rope_head_dim"],
                      nope_head_dim=c["qk_nope_head_dim"],
                      v_head_dim=c["v_head_dim"]))


def check_layout(c: dict, model) -> None:
    """Raise unless the seeded tree has the program's structure,
    shapes and dtypes."""
    want = model.param_shapes()
    got = jax.eval_shape(lambda k: to_program(c, canonical(c, k)),
                         W.root_key(0, W.WEIGHTS))
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{jax.tree.structure(got)}\nvs\n"
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"leaf {a.shape}/{a.dtype} vs program "
                             f"{b.shape}/{b.dtype}")


def program_params(c: dict, model, seed: int, shardings=None):
    """The program's parameters from ``seed``, made on the device in one
    jitted call (sharded by ``shardings`` when given)."""
    check_layout(c, model)
    make = jax.jit(lambda k: to_program(c, canonical(c, k)),
                   out_shardings=shardings)
    return make(W.root_key(seed, W.WEIGHTS))
