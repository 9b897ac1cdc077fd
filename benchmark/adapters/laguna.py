"""The program's side of the Laguna configuration: how its published keys
become ``models/llama.py``'s Config (a leading dense full pair, then
periods of window and full pairs whose query heads, RoPE theta and rotated
share differ by kind, a sigmoid gate on every head's output, sigmoid-routed
SwiGLU experts with a shared expert), and how the benchmark's flat, named
weights sit in its parameter tree (a stack a kind: the kinds' query heads
differ); and what the cell's control is (every matrix of every product
rounded to fp8). The one file of the benchmark that knows the program's
model module for this model."""

import dataclasses

import jax
import jax.numpy as jnp

from adapters.mellum import KINDS, _fp8, _yarn
from mpi_operator_tpu.models import llama
from reference.laguna import stacks

_ATTENTION = ("wq", "wk", "wv", "wg", "wo")
_PAIR = {"attn_norm": ("attn_norm", "scale"),
         "mlp_norm": ("mlp_norm", "scale"),
         **{k: (k, "w") for k in _ATTENTION + ("w_gate", "w_up", "w_down")}}
_ROUTED = {**_PAIR, **{k: (k, "w") for k in
                       ("router", "shared_gate", "shared_up", "shared_down")}}
# benchmark leaf name -> path in llama's parameter tree
_PATHS = {
    "embed": ("embed", "w"), "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    **{"d_" + k: ("lead", *path) for k, path in _PAIR.items()},
    **{f"{kind}_{k}": ("layers", kind, *path)
       for kind in ("full", "window") for k, path in _ROUTED.items()},
}


def config(conf, control=False):
    """llama.Config at the configuration's sizes. ``control`` says fp8
    (e4m3: three mantissa bits for bf16's seven), which :func:`loss_fn`
    reads as the control: every matrix of every product rounded to fp8.
    What the program does not compute is refused here."""
    m, a = conf, conf["assumed"]
    held = m["stands_for"]["experts_held"]
    by_stack = stacks(m)  # refuses a stack of unlike layers
    types, ffns = m["layer_types"], m["mlp_layer_types"]
    lead = ffns.count("dense")
    if (ffns != ["dense"] * lead + ["sparse"] * (len(ffns) - lead)
            or not 0 < lead < len(ffns)):
        raise ValueError("leading dense layers, then routed ones: "
                         + m["name"])
    rest = [KINDS[t] for t in types[lead:]]
    period = next(p for p in range(1, len(rest) + 1)
                  if rest == rest[:p] * (len(rest) // p))
    if (m["attention_bias"] or m["tie_word_embeddings"]
            or m["gating"] is not True
            or m["moe_apply_router_weight_on_input"]
            or any(k in m for k in ("topk_method", "norm_topk_prob",
                                    "moe_router_logit_softcapping"))):
        raise ValueError("no bias, an untied head, a gate on every head, "
                         "the router's weights on the experts' results, "
                         "sigmoid scores renormalised with no correction "
                         "bias: " + m["name"])
    heads = {}
    for _n, t, h in by_stack.values():
        if heads.setdefault(KINDS[t], h) != h:
            raise ValueError(f"{t} layers of {heads[KINDS[t]]} and {h} "
                             "query heads: " + m["name"])
    if heads["full"] == heads["window"]:
        raise ValueError("the tree is a stack a kind: query heads by kind")
    rope = {KINDS[t]: r for t, r in m["rope_parameters"].items()
            if t in KINDS}
    if rope["window"]["rope_type"] != "default":
        raise ValueError("plain RoPE on the window layers: " + m["name"])
    dh = m["head_dim"]
    by_kind = tuple(
        (kind, llama.KindAttention(
            heads=heads[kind], theta=float(r["rope_theta"]),
            rotated=int(round(dh * float(r["partial_rotary_factor"])))))
        for kind, r in sorted(rope.items()))
    return llama.Config(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=dh,
        d_ff=m["intermediate_size"],
        rope_theta=float(rope["window"]["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        compute_dtype=jnp.dtype(a["compute_dtype"]),
        remat_layers=bool(a["remat_layers"]),
        matmul_precision="fp8" if control else "bf16",
        layer_kinds=tuple(rest[:period]), window=int(m["sliding_window"]),
        n_dense_layers=lead, lead_kind=KINDS[types[0]],
        attention_by_kind=by_kind, head_gate=True,
        yarn_full=_yarn(rope["full"]),
        n_experts=held["of"], n_experts_held=held["count"],
        first_expert=held["first"],
        experts_per_token=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"],
        router_score="sigmoid", router_bias=False,
        router_scale=float(m["moe_routed_scaling_factor"]),
        experts_gated=True, d_shared=m["shared_expert_intermediate_size"],
    )


# every matrix of a product of the step (the router's product is float32,
# the embedding a lookup)
_MATRICES = (*(f"{stack}_{k}" for stack in ("d", "full", "window")
               for k in _ATTENTION + ("w_gate", "w_up", "w_down")),
             *(f"{kind}_{k}" for kind in ("full", "window")
               for k in ("shared_gate", "shared_up", "shared_down")),
             "lm_head")


def _control(params, dtype=jnp.bfloat16):
    """``params`` with every matrix of :data:`_MATRICES` rounded to fp8 a
    column, in place in the program's own tree and handed on in the compute
    ``dtype`` the program would cast it to next (a float32 copy of every
    stack beside the step's temporaries is memory the chip does not have)."""
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
    every matrix of every product is rounded to fp8 before the program
    reads it, and the program computes in bf16: its own fp8 path, which
    rounds both operands of the routed products, does not fit the chip
    beside this step (PERF.md section 2), so the products' other operand
    stays bf16."""
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
