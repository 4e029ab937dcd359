"""GPT-2 on the program's side: its model configuration, and the mapping
between the reference's leaves and the program's two parameter layouts
(the RAD op-graph's per-op tree and the pipeline's stacked blocks)."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2 import (LAYER_LEAVES, init_layer_leaf,
                                      init_leaf, layer_name, read_leaves,
                                      sample)

# reference leaf -> path inside one block of the program
_BLOCK = {"ln1.scale": ("ln1", "scale"), "ln1.bias": ("ln1", "bias"),
          "wq": ("attn", "wq", "w"), "wk": ("attn", "wk", "w"),
          "wv": ("attn", "wv", "w"), "wo": ("attn", "wo", "w"),
          "ln2.scale": ("ln2", "scale"), "ln2.bias": ("ln2", "bias"),
          "up": ("mlp", "up", "w"), "down": ("mlp", "down", "w")}
_RAD = {"wte": ("embed", "tok", "table"), "wpe": ("embed", "pos", "table"),
        "lnf.scale": ("head", "ln", "scale"),
        "lnf.bias": ("head", "ln", "bias"), "head": ("head", "w", "w")}
_STACKED = {"wte": ("embed", "table"), "wpe": ("pos_embed", "table"),
            "lnf.scale": ("final_norm", "scale"),
            "lnf.bias": ("final_norm", "bias"), "head": ("head", "w")}


def program_cfg(conf: dict):
    """The program's ``ModelCfg`` for the configuration as it is run."""
    from repro.configs.base import ModelCfg

    if conf["tie_word_embeddings"] or conf["linear_bias"]:
        raise ValueError("the program's GPT-2 has an untied head and no "
                         "projection biases")
    d = conf["n_embd"]
    return ModelCfg(
        name=conf["name"], family="dense", n_layers=conf["n_layer"],
        d_model=d, n_heads=conf["n_head"], n_kv_heads=conf["n_head"],
        head_dim=d // conf["n_head"], d_ff=conf.get("n_inner") or 4 * d,
        vocab=conf["vocab_size"], vocab_pad_to=conf["vocab_pad_to"],
        norm="layernorm", act="gelu", rope_fraction=0.0,
        max_seq=conf["n_positions"], dtype=jnp.dtype(conf["dtype"]),
        param_dtype=jnp.float32)


def _put(tree: dict, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def rad_params(conf: dict, leaves: Dict[str, jax.Array]) -> dict:
    """Reference leaves -> the op-graph's {op: params} tree."""
    out: dict = {}
    for name, path in _RAD.items():
        _put(out, path, leaves[name])
    for i in range(conf["n_layer"]):
        for leaf in LAYER_LEAVES:
            _put(out, (f"block_{i}",) + _BLOCK[leaf],
                 leaves[layer_name(i, leaf)])
    return out


def rad_leaves(conf: dict, tree: dict) -> Dict[str, jax.Array]:
    """The op-graph's tree (params, or a state shaped like them) ->
    reference leaves."""
    out = {name: _get(tree, path) for name, path in _RAD.items()}
    for i in range(conf["n_layer"]):
        for leaf in LAYER_LEAVES:
            out[layer_name(i, leaf)] = _get(tree, (f"block_{i}",)
                                            + _BLOCK[leaf])
    return out


def stacked_init(conf: dict, key: jax.Array) -> dict:
    """The reference's weights of ``key`` in ``causal_lm``'s tree, blocks
    stacked on axis 0 (each layer drawn by ``vmap``, so a stack sharded by
    layer is drawn where it lives)."""
    out: dict = {}
    for name, path in _STACKED.items():
        _put(out, path, init_leaf(conf, key, name))
    layers = jnp.arange(conf["n_layer"])
    for leaf in LAYER_LEAVES:
        _put(out, ("blocks",) + _BLOCK[leaf], jax.vmap(
            lambda i, leaf=leaf: init_layer_leaf(conf, key, i, leaf))(layers))
    return out


def stacked_reads(conf: dict, tree: dict) -> Dict[str, tuple]:
    """{reference leaf: (norm, sample)} of a stacked tree (traceable)."""
    out = read_leaves({name: _get(tree, path)
                       for name, path in _STACKED.items()})
    for leaf in LAYER_LEAVES:
        a = _get(tree, ("blocks",) + _BLOCK[leaf]).astype(jnp.float32)
        norms = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))
        samples = jax.vmap(sample)(a)
        for i in range(conf["n_layer"]):
            out[layer_name(i, leaf)] = (norms[i], samples[i])
    return out
