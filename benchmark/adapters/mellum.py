"""The program's side of the Mellum 2 configuration: how its published keys
become ``models/llama.py``'s Config (a period of window and full layers,
RoPE by kind, the routed feed-forward's share), and how the benchmark's
flat, named weights sit in its parameter tree; and what the cell's control
is (fp8 in every product of the step). The one file of the benchmark that
knows the program's model module for this model."""

import jax
import jax.numpy as jnp
from jax import lax

from mpi_operator_tpu.models import llama

KINDS = {"sliding_attention": "window", "full_attention": "full"}

# benchmark leaf name -> path in llama's parameter tree
_PATHS = {
    "embed": ("embed", "w"), "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    "attn_norm": ("layers", "attn_norm", "scale"),
    "mlp_norm": ("layers", "mlp_norm", "scale"),
    **{k: ("layers", k, "w") for k in
       ("wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down")},
}


def _yarn(rope):
    if rope["rope_type"] == "default":
        return None
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    return llama.Yarn(
        factor=float(rope["factor"]),
        original_len=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        attention_factor=float(rope["attention_factor"]))


def config(conf, control=False):
    """llama.Config at the configuration's sizes. ``control`` switches on
    the program's own lower-precision path for the expert products, fp8
    (e4m3: three mantissa bits for bf16's seven), and :func:`loss_fn` then
    rounds every other matrix of a bf16 product to fp8 as well. Not int8,
    the dense configuration's control: a row's absmax over 127 steps keeps
    more of the large values than bf16 does, and here it moved no compared
    number out of the sound runs' range, in the experts alone or in every
    matrix (six runs: PERF.md section 2, PR 28)."""
    m, a = conf, conf["assumed"]
    held, rope = m["stands_for"]["experts_held"], m["rope_parameters"]
    thetas = {float(r["rope_theta"]) for r in rope.values()}
    if (len(thetas) != 1 or rope["sliding_attention"]["rope_type"] != "default"
            or any(t != "sparse" for t in m["mlp_layer_types"])):
        raise ValueError("one theta, plain RoPE on the window layers and "
                         "sparse layers only: " + m["name"])
    period = tuple(KINDS[t] for t in m["layer_types"])
    return llama.Config(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], rope_theta=thetas.pop(),
        norm_eps=float(m["rms_norm_eps"]),
        compute_dtype=jnp.dtype(a["compute_dtype"]),
        remat_layers=bool(a["remat_layers"]),
        matmul_precision="fp8" if control else "bf16",
        layer_kinds=period, window=int(m["sliding_window"]),
        yarn_full=_yarn(rope["full_attention"]),
        n_experts=held["of"], n_experts_held=held["count"],
        first_expert=held["first"],
        experts_per_token=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"],
    )


# the matrices of the step's bf16 products outside the experts (the router's
# product is float32 and the embedding is a lookup: both stay)
_MATRICES = ("wq", "wk", "wv", "wo")


def _fp8(w):
    """``w`` rounded as ``kernels.quant_matmul`` rounds a weight for fp8: a
    column of each matrix scaled to e4m3's range (absmax over the
    contraction axis to 448) and cut to three mantissa bits, by arithmetic
    (a convert to fp8 and back is dropped by the compiler). Straight
    through: the step computes with the rounded matrix and the gradient
    goes to the one it was rounded from."""
    scale = jnp.maximum(
        jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12) / 448.0
    rounded = lax.reduce_precision(
        w / scale, exponent_bits=5, mantissa_bits=3) * scale
    return w + lax.stop_gradient(rounded - w)


def _control(params):
    layers = dict(params["layers"])
    for k in _MATRICES:
        layers[k] = jax.tree.map(_fp8, layers[k])
    return dict(params, layers=layers,
                lm_head=jax.tree.map(_fp8, params["lm_head"]))


def loss_fn(cfg, mesh):
    """The step's loss. Under the control (``config(conf, control=True)``:
    fp8 expert products, both operands, in the program) the attention's
    four projections and the head are rounded to fp8 before the program
    reads them, so that precision is lowered in every product of the step
    and not in a quarter-weight partial sum alone."""
    control = cfg.matmul_precision != "bf16"
    return lambda params, batch: llama.loss_fn(
        cfg, _control(params) if control else params, batch, mesh=mesh)


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
