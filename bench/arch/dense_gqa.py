"""Dense GQA decoder: the configuration file's keys, the seeded weights
by canonical name, and how they map onto the program's parameter tree.

The configuration file uses the published ``config.json`` key names.
Canonical weight names (``wq``, ``w_down``, ...) are shared with the
plain reference in ``bench/ref/dense_gqa.py``; only this module knows the
program's layout, and ``program_params`` checks it against the program's
own shapes, so a change of layout fails loudly instead of silently.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import weights as W


def dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "f": c["intermediate_size"],
            "L": c["num_hidden_layers"], "H": c["num_attention_heads"],
            "K": c["num_key_value_heads"], "dh": c["head_dim"],
            "V": c["vocab_size"]}


def layer_specs(c: dict) -> list[tuple[str, tuple, float]]:
    """(name, shape, std) of one layer's tensors; std 0 marks a norm
    scale (ones)."""
    g = dims(c)
    d, f, H, K, dh = g["d"], g["f"], g["H"], g["K"], g["dh"]
    out = [("ln1", (d,), 0.0), ("ln2", (d,), 0.0),
           ("wq", (d, H * dh), 1 / math.sqrt(d)),
           ("wk", (d, K * dh), 1 / math.sqrt(d)),
           ("wv", (d, K * dh), 1 / math.sqrt(d)),
           ("wo", (H * dh, d), 1 / math.sqrt(H * dh)),
           ("w_gate", (d, f), 1 / math.sqrt(d)),
           ("w_up", (d, f), 1 / math.sqrt(d)),
           ("w_down", (f, d), 1 / math.sqrt(f))]
    if c["qk_norm"]:
        out += [("q_norm", (dh,), 0.0), ("k_norm", (dh,), 0.0)]
    return out


def global_specs(c: dict) -> list[tuple[str, tuple, float]]:
    g = dims(c)
    out = [("embed", (g["V"], g["d"]), c["init"]["embed_std"]),
           ("final_norm", (g["d"],), 0.0)]
    if not c["tie_word_embeddings"]:
        out.append(("lm_head", (g["d"], g["V"]), 1 / math.sqrt(g["d"])))
    return out


# canonical name -> path in the program's parameter tree
_LAYER_PATH = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    "wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"),
    "wo": ("attn", "wo"), "q_norm": ("attn", "q_norm", "scale"),
    "k_norm": ("attn", "k_norm", "scale"),
    "w_gate": ("ffn", "wi_gate"), "w_up": ("ffn", "wi_up"),
    "w_down": ("ffn", "wo"),
}
_GLOBAL_PATH = {"embed": ("embed",), "final_norm": ("final_norm", "scale"),
                "lm_head": ("lm_head",)}


def program_paths(c: dict) -> dict[str, tuple]:
    """Canonical name -> path of that (layer-stacked) leaf in the
    program's parameter tree."""
    out = {n: _GLOBAL_PATH[n] for n, _, _ in global_specs(c)}
    out.update({n: ("layers", "pos0_self") + _LAYER_PATH[n]
                for n, _, _ in layer_specs(c)})
    return out


def canonical(c: dict, key: jax.Array) -> dict[str, jax.Array]:
    """Every weight by canonical name from the weights ``key`` of a seed
    (``weights.root_key(seed, weights.WEIGHTS)``), layer tensors stacked
    ``[L, ...]``: norms f32 ones, the rest bf16 as served."""
    L = c["num_hidden_layers"]
    out = {}
    for name, shape, std in global_specs(c):
        out[name] = jnp.ones(shape, jnp.float32) if std == 0 else \
            W.tensor(key, name, 0, shape, std)
    for name, shape, std in layer_specs(c):
        out[name] = jnp.ones((L,) + shape, jnp.float32) if std == 0 else \
            W.stacked(key, name, L, shape, std)
    return out


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def to_program(c: dict, flat: dict) -> dict:
    """Canonical name -> leaf, arranged as the program's tree."""
    tree: dict = {"layers": {"pos0_self": {}}, "rem": {}}
    for name, path in program_paths(c).items():
        _set(tree, path, flat[name])
    return tree


def from_program(c: dict, tree) -> dict:
    """The program's tree (or one shaped like it, as its optimizer
    state) -> canonical name -> leaf."""
    out = {}
    for name, path in program_paths(c).items():
        node = tree
        for k in path:
            node = node[k]
        out[name] = node
    return out


def model_config(c: dict, *, max_seq: int, remat: str = "full"):
    """The program's ``ModelConfig`` for this file."""
    from repro.models.config import ModelConfig
    if c["hidden_act"] != "silu" or c.get("attention_bias"):
        raise ValueError(f"{c['name']}: not a SwiGLU decoder without "
                         "attention bias")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        activation="swiglu", qk_norm=c["qk_norm"],
        tie_embeddings=c["tie_word_embeddings"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], max_seq=max_seq, remat=remat)


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
