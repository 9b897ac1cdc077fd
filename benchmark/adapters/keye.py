"""The program's side of the Keye-VL-2.0 configuration: how its published
keys become ``models/llama.py``'s Config (full attention over a learned
selection of keys in every layer, per-head q/k norms, M-RoPE read as 1-D
for text, softmax-routed SwiGLU experts of which this chip holds its
share), how the benchmark's flat, named weights sit in its parameter tree,
and what the cell's control is (every matrix of every product rounded to
fp8). The one file of the benchmark that knows the program's model module
for this model."""

import dataclasses

import jax
import jax.numpy as jnp

from adapters.mellum import _fp8
from mpi_operator_tpu.models import llama

_ATTENTION = ("wq", "wk", "wv", "wo")
_INDEXER = {"index_wq": ("wq_index", "w"), "index_wk": ("wk_index", "w"),
            "index_w": ("w_index", "w"),
            "index_k_norm": ("k_index_norm", "scale"),
            "index_k_bias": ("k_index_norm", "bias")}
# benchmark leaf name -> path in llama's parameter tree
_PATHS = {
    "embed": ("embed", "w"), "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    **{k: ("layers", k, "scale") for k in
       ("attn_norm", "mlp_norm", "q_norm", "k_norm")},
    **{k: ("layers", k, "w") for k in
       _ATTENTION + ("router", "w_gate", "w_up", "w_down")},
    **{k: ("layers", *path) for k, path in _INDEXER.items()},
}
# every matrix of a bf16 product of the step (the router's product is
# float32, the embedding a lookup)
_MATRICES = _ATTENTION + ("index_wq", "index_wk", "index_w", "w_gate",
                          "w_up", "w_down", "lm_head")


def config(conf, control=False):
    """llama.Config at the configuration's sizes. ``control`` says fp8
    (e4m3: three mantissa bits for bf16's seven), which :func:`loss_fn`
    reads as the control: every matrix of every product rounded to fp8.
    What the program does not compute is refused here."""
    m, a = conf, conf["assumed"]
    held, sa, rope = (m["stands_for"]["experts_held"], m["sa_config"],
                      m["rope_scaling"])
    dh = m["head_dim"]
    if (m["attention_bias"] or m["tie_word_embeddings"]
            or m["use_sliding_window"] or m["sliding_window"] is not None
            or m["decoder_sparse_step"] != 1 or m["mlp_only_layers"]
            or not m["norm_topk_prob"] or m["hidden_act"] != "silu"
            or rope["rope_type"] != "default"
            or sum(rope["mrope_section"]) != dh // 2
            or sa["indexer_num_kv_heads"] != 1
            or m["num_experts"] != m["num_local_experts"]):
        raise ValueError(
            "no bias, an untied head, no window, every layer routed "
            "(softmax, top-k renormalised, silu experts), plain M-RoPE over "
            "half a head's pairs, one indexer key head: " + m["name"])
    return llama.Config(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=dh,
        d_ff=m["intermediate_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        compute_dtype=jnp.dtype(a["compute_dtype"]),
        remat_layers=bool(a["remat_layers"]),
        matmul_precision="fp8" if control else "bf16",
        layer_kinds=("full",), qk_norm=True,
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        n_experts=held["of"], n_experts_held=held["count"],
        first_expert=held["first"],
        experts_per_token=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"], router_score="softmax",
    )


def _control(params, dtype=jnp.bfloat16):
    """``params`` with every matrix of :data:`_MATRICES` rounded to fp8 a
    column, in place in the program's own tree and handed on in the compute
    ``dtype`` the program would cast it to next (a float32 copy of every
    stack beside the step's temporaries is memory the chip does not
    have)."""
    tree = jax.tree.map(lambda a: a, params)  # the dicts are this call's
    for name in _MATRICES:
        *path, last = _PATHS[name]
        node = tree
        for key in path:
            node = node[key]
        node[last] = _fp8(node[last]).astype(dtype)
    return tree


def loss_fn(cfg, mesh):
    """The step's loss. Under the control (``config(conf, control=True)``)
    every matrix of every product, the indexer's included, is rounded to
    fp8 before the program reads it, and the program computes in bf16
    (Laguna's control, for its reason: the program's own fp8 path does not
    fit the chip beside this step)."""
    if cfg.matmul_precision == "bf16":
        return lambda params, batch: llama.loss_fn(cfg, params, batch,
                                                   mesh=mesh)
    plain = dataclasses.replace(cfg, matmul_precision="bf16")
    return lambda params, batch: llama.loss_fn(
        plain, _control(params, cfg.compute_dtype), batch, mesh=mesh)


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

