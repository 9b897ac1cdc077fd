"""The program's side of the DeepSeek-V3-style configuration: how its
published keys become ``models/llama.py``'s Config (``latent`` pairs: a
stack of leading dense ones, then the routed ones with their gated shared
expert), and how the benchmark's flat, named weights sit in its parameter
tree; and what the cell's control is (fp8 in every product of the step).
The one file of the benchmark that knows the program's model module for
this model.

The rotation. The published form turns interleaved pairs (columns 2i and
2i + 1 of a head's rotated part, ``rope_interleave``); the program turns
half-split pairs (columns i and i + R / 2: ``llama._rotate``, the one
rotation it has). A score is a sum over the pairs, so the two agree where
the rotated columns of ``W_q`` (the last R of each head's) and of ``W_kva``
(its last R) are put in the program's order: program column j is published
column 2j, program column R / 2 + j published column 2j + 1.
:func:`to_tree` permutes them so, :func:`to_flat` back."""

import jax
import jax.numpy as jnp
import numpy as np

from adapters.mellum import _fp8  # the rounding the fp8 control makes
from mpi_operator_tpu.models import llama
from reference.deepseek_v3 import score_bias  # the buffer's one draw

_ATTENTION = ("wq", "wkv_a", "wkv_b", "wo")
_LAYER = {
    "attn_norm": ("attn_norm", "scale"), "kv_a_norm": ("kv_a_norm", "scale"),
    "mlp_norm": ("mlp_norm", "scale"),
    **{k: (k, "w") for k in _ATTENTION + ("w_gate", "w_up", "w_down")}}
# benchmark leaf name -> path in llama's parameter tree
_PATHS = {
    "embed": ("embed", "w"), "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    **{"d_" + k: ("lead", *path) for k, path in _LAYER.items()},
    **{k: ("layers", *path) for k, path in _LAYER.items()},
    **{k: ("layers", k, "w") for k in
       ("router", "shared_gate", "shared_up", "shared_down")},
}


def config(conf, control=False):
    """llama.Config at the configuration's sizes. ``control`` switches on
    the program's own lower-precision path for the dense and the routed
    expert products, fp8 (e4m3: three mantissa bits for bf16's seven), and
    :func:`loss_fn` then rounds every other matrix of a bf16 product to fp8
    as well (the routed configurations' control: PERF.md section 2). What
    the program does not compute is refused here."""
    m, a = conf, conf["assumed"]
    held = m["stands_for"]["experts_held"]
    if m["q_lora_rank"] is not None or m["rope_scaling"] is not None:
        raise ValueError("a compressed query and a scaled RoPE are not "
                         "computed: " + m["name"])
    if (m["n_group"], m["topk_group"]) != (1, 1):
        raise ValueError("a group limit on the router's choice "
                         f"(n_group {m['n_group']}, topk_group "
                         f"{m['topk_group']}) is not computed")
    if (m["scoring_func"], m["topk_method"], m["norm_topk_prob"],
            m["hidden_act"], m["moe_layer_freq"], m["rope_interleave"]) != (
                "sigmoid", "noaux_tc", True, "silu", 1, True):
        raise ValueError("sigmoid scores chosen with the correction bias, "
                         "renormalised weights, silu, every layer behind "
                         "the dense ones routed, interleaved rotation: "
                         + m["name"])
    if m["attention_bias"] or m["tie_word_embeddings"]:
        raise ValueError("no bias, an untied head: " + m["name"])
    if (m["qk_head_dim"] != m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
            or m["num_key_value_heads"] != m["num_attention_heads"]):
        raise ValueError("a key is its unrotated and rotated parts, a head "
                         "of its own for every query head: " + m["name"])
    if not 0 < m["first_k_dense_replace"] < m["num_hidden_layers"]:
        raise ValueError("leading dense layers, then routed ones: "
                         + m["name"])
    return llama.Config(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        d_ff=m["intermediate_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        compute_dtype=jnp.dtype(a["compute_dtype"]),
        remat_layers=bool(a["remat_layers"]),
        matmul_precision="fp8" if control else "bf16",
        layer_kinds=("latent",), n_dense_layers=m["first_k_dense_replace"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_experts=held["of"], n_experts_held=held["count"],
        first_expert=held["first"],
        experts_per_token=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"],
        router_score="sigmoid",
        router_scale=float(m["routed_scaling_factor"]),
        experts_gated=True,
        d_shared=m["n_shared_experts"] * m["moe_intermediate_size"],
    )


# the matrices of the step's bf16 products outside the dense and routed
# feed-forwards (the router's product is float32, the embedding a lookup)
_MATRICES = (*_ATTENTION, *("d_" + k for k in _ATTENTION),
             "shared_gate", "shared_up", "shared_down", "lm_head")


def _control(params, dtype=jnp.bfloat16):
    """``params`` with every matrix of :data:`_MATRICES` rounded to fp8 a
    column, in place in the program's own tree (the rounding is a column's
    own, so the rotated columns' order does not matter) and handed on in
    the compute ``dtype`` the program would cast it to next: a float32 copy of
    every stack, alive through the whole step, is 0.9 GB this chip does not
    have beside the step's temporaries."""
    tree = jax.tree.map(lambda a: a, params)  # the dicts are this call's
    for name in _MATRICES:
        *path, last = _PATHS[name]
        node = tree
        for key in path:
            node = node[key]
        node[last] = _fp8(node[last]).astype(dtype)
    return tree


def loss_fn(cfg, mesh):
    """The step's loss. Under the control (``config(conf, control=True)``:
    fp8 dense and routed expert products, both operands, in the program)
    every other matrix of a bf16 product is rounded to fp8 before the
    program reads it, so that precision is lowered in every product of the
    step and not in an eighth-weight partial sum alone."""
    control = cfg.matmul_precision != "bf16"
    return lambda params, batch: llama.loss_fn(
        cfg, _control(params, cfg.compute_dtype) if control else params, batch,
        mesh=mesh)


def logical_axes(cfg):
    return llama.logical_axes(cfg)


def _order(width, block, rot, back):
    """Column indices over ``width`` = blocks of ``block`` whose last
    ``rot`` go from interleaved pairs to half-split ones (or back)."""
    pairs = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2)])
    if back:
        pairs = np.argsort(pairs)
    one = np.concatenate([np.arange(block - rot), block - rot + pairs])
    return (np.arange(0, width, block)[:, None] + one[None, :]).reshape(-1)


def _rotated_order(flat, back=False):
    """``flat`` with the rotated columns of ``wq`` and ``wkv_a`` (both
    stacks') in the program's order, or ``back`` in the published one. The
    sizes follow from the leaves' shapes, since neither :func:`to_tree` nor
    :func:`to_flat` is handed the configuration: R = wkv_a's columns less
    the latent's norm's; heads x R = wq's columns less the unrotated ones,
    which are wkv_b's columns less wo's rows. What is no array (a
    sharding) stays as it came."""
    if not isinstance(flat["wq"], jax.Array):
        return flat
    r = flat["kv_a_norm"].shape[-1]
    rot = flat["wkv_a"].shape[-1] - r
    q = flat["wq"].shape[-1]
    heads = (q - (flat["wkv_b"].shape[-1] - flat["wo"].shape[-2])) // rot
    order = {"wq": _order(q, q // heads, rot, back),
             "wkv_a": _order(r + rot, r + rot, rot, back)}
    return {k: (jnp.take(w, order[k.removeprefix("d_")], axis=-1)
                if k.removeprefix("d_") in order else w)
            for k, w in flat.items()}


def to_tree(flat):
    flat = _rotated_order(flat)
    tree = {}
    for name, path in _PATHS.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    # the correction bias: a buffer in the program's tree, no flat leaf
    layers, _, experts = flat["router"].shape
    tree["layers"]["router"]["bias"] = jnp.asarray(score_bias(layers, experts))
    return tree


def to_flat(tree):
    flat = {}
    for name, path in _PATHS.items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return _rotated_order(flat, back=True)
