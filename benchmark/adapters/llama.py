"""The program's side of a decoder configuration: how the published sizes
become ``models/llama.py``'s Config, and how the benchmark's flat, named
weights sit in its parameter tree. The one file of the benchmark that knows
the program's model module."""

import jax.numpy as jnp

from mpi_operator_tpu.models import llama

# benchmark leaf name -> path in llama's parameter tree
_PATHS = {
    "embed": ("embed", "w"), "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    "attn_norm": ("layers", "attn_norm", "scale"),
    "mlp_norm": ("layers", "mlp_norm", "scale"),
    **{k: ("layers", k, "w") for k in
       ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")},
}


def config(conf, control=False):
    """llama.Config at the configuration's sizes. ``control`` switches on
    the program's own lower-precision path (int8 feed-forward products)."""
    m, a = conf, conf["assumed"]
    return llama.Config(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        compute_dtype=jnp.dtype(a["compute_dtype"]),
        remat_layers=bool(a["remat_layers"]),
        matmul_precision="int8" if control else "bf16",
    )


def loss_fn(cfg, mesh):
    return lambda params, batch: llama.loss_fn(cfg, params, batch, mesh=mesh)


def logical_axes(cfg):
    return llama.logical_axes(cfg)


def to_tree(flat):
    tree = {}
    for name, path in _PATHS.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def to_flat(tree):
    flat = {}
    for name, path in _PATHS.items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return flat
