"""The program's side of the Nemotron-H configuration: how its published
keys become ``models/llama.py``'s Config (a period of single-mixer layers:
Mamba-2, the routed layer with its shared expert, attention without
positions), and how the benchmark's flat, named weights sit in its
parameter tree (each kind's layers on a stack of their own); and what the
cell's control is (fp8 in every product of the step). The one file of the
benchmark that knows the program's model module for this model."""

import jax.numpy as jnp

from adapters.mellum import _fp8  # the rounding the fp8 control makes
from mpi_operator_tpu.models import llama

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}

# benchmark leaf name -> path in llama's parameter tree
_PATHS = {
    "embed": ("embed", "w"), "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    "m_norm": ("layers", "mamba", "norm", "scale"),
    "m_in_proj": ("layers", "mamba", "in_proj", "w"),
    "m_conv_w": ("layers", "mamba", "conv", "w"),
    "m_conv_b": ("layers", "mamba", "conv", "b"),
    "m_dt_bias": ("layers", "mamba", "dt_bias"),
    "m_A_log": ("layers", "mamba", "A_log"),
    "m_D": ("layers", "mamba", "D"),
    "m_gate_norm": ("layers", "mamba", "gate_norm", "scale"),
    "m_out_proj": ("layers", "mamba", "out_proj", "w"),
    "a_norm": ("layers", "attention", "attn_norm", "scale"),
    **{k: ("layers", "attention", k, "w") for k in ("wq", "wk", "wv", "wo")},
    "e_norm": ("layers", "experts", "mlp_norm", "scale"),
    "router": ("layers", "experts", "router", "w"),
    "router_bias": ("layers", "experts", "router", "bias"),
    **{k: ("layers", "experts", k, "w")
       for k in ("w_up", "w_down", "shared_up", "shared_down")},
}


def config(conf, control=False):
    """llama.Config at the configuration's sizes. ``control`` switches on
    the program's own lower-precision path for the routed experts'
    products, fp8 (e4m3: three mantissa bits for bf16's seven), and
    :func:`loss_fn` then rounds every other matrix of a bf16 product to fp8
    as well (the routed configuration's control carried over: PERF.md
    section 2). What the program does not compute is refused here."""
    m, a = conf, conf["assumed"]
    held = m["stands_for"]["experts_held"]
    pattern = m["hybrid_override_pattern"]
    if set(pattern) - set(KINDS):
        raise ValueError(f"layer letters {sorted(set(pattern) - set(KINDS))} "
                         f"in {pattern!r}: M, E and * are computed")
    if (m["n_group"], m["topk_group"]) != (1, 1):
        raise ValueError("a group limit on the router's choice "
                         f"(n_group {m['n_group']}, topk_group "
                         f"{m['topk_group']}) is not computed")
    if (m["mlp_hidden_act"], m["mamba_hidden_act"], m["n_shared_experts"],
            m["norm_topk_prob"], m["use_conv_bias"]) != (
                "relu2", "silu", 1, True, True):
        raise ValueError("relu2 experts, one shared expert, renormalised "
                         "weights, silu and a bias on the convolution: "
                         + m["name"])
    if any(m[k] for k in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                          "use_bias", "residual_in_fp32",
                          "tie_word_embeddings")):
        raise ValueError("no bias but the convolution's, the residual in "
                         "the compute dtype, an untied head: " + m["name"])
    if a["position_embedding"]["kind"] != "none":
        raise ValueError("the attention layers of this model rotate nothing")
    if len(pattern) != m["num_hidden_layers"]:
        raise ValueError(f"{m['num_hidden_layers']} layers, pattern "
                         f"{pattern!r}")
    return llama.Config(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        norm_eps=float(m["layer_norm_epsilon"]),
        compute_dtype=jnp.dtype(a["compute_dtype"]),
        remat_layers=bool(a["remat_layers"]),
        matmul_precision="fp8" if control else "bf16",
        layer_kinds=tuple(KINDS[letter] for letter in pattern),
        n_experts=held["of"], n_experts_held=held["count"],
        first_expert=held["first"],
        experts_per_token=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"],
        router_score="sigmoid",
        router_scale=float(m["routed_scaling_factor"]),
        experts_gated=False,
        d_shared=m["moe_shared_expert_intermediate_size"],
        ssm_heads=m["mamba_num_heads"], ssm_head_dim=m["mamba_head_dim"],
        ssm_groups=m["n_groups"], ssm_state=m["ssm_state_size"],
        conv_kernel=m["conv_kernel"], ssm_chunk=m["chunk_size"],
    )


# the matrices of the step's bf16 products outside the routed experts (the
# router's product and the scan's decays and states are float32, the
# embedding is a lookup, the convolution's taps are no matrix: all stay)
_MATRICES = ("m_in_proj", "m_out_proj", "wq", "wk", "wv", "wo",
             "shared_up", "shared_down", "lm_head")


def _control(params):
    flat = to_flat(params)
    return to_tree({k: _fp8(v) if k in _MATRICES else v
                    for k, v in flat.items()})


def loss_fn(cfg, mesh):
    """The step's loss. Under the control (``config(conf, control=True)``:
    fp8 routed expert products, both operands, in the program) every other
    matrix of a bf16 product is rounded to fp8 before the program reads it,
    so that precision is lowered in every product of the step and not in a
    sixteenth-weight partial sum alone."""
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
