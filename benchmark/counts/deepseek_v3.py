"""What the DeepSeek-V3-style decoder's mathematics needs, from its
configuration's sizes: parameters held, the training step's matmul FLOPs a
token, the operations and bytes of its latent attention and of its routed
experts' products. Counts of the algorithm, whatever implements it: a
causal mask counted as causal, scores over the ``qk_head_dim`` numbers of a
key and weighted values over the ``v_head_dim`` of a value (lanes a kernel
pads are no work), nothing recomputed, the routed experts at the share of
the assignments that falls to those held."""

BYTES = 2  # bf16 operands


def layers(c):
    """(leading dense layers, routed layers) held."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def attention_matrix_params(c):
    """W_q, W_kva, W_kvb and W_o of one layer."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    return (d * h * c["qk_head_dim"] + d * (r + c["qk_rope_head_dim"])
            + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def attention_params(c):
    """The four matrices and the latent's norm."""
    return attention_matrix_params(c) + c["kv_lora_rank"]


def dense_params(c):
    """A leading layer's SwiGLU."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c):
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c):
    """The shared expert, n_shared_experts routed widths wide."""
    return c["n_shared_experts"] * expert_params(c)


def router_params(c):
    return c["hidden_size"] * c["published"]["n_routed_experts"]


def param_count(c):
    """Parameters this chip holds: every layer's attention and two norms
    whole, the leading layers' feed-forward, of each routed layer the
    router (with its correction bias, a buffer), the shared expert and its
    own routed experts, its slice of the embedding and of the head."""
    d, v = c["hidden_size"], c["vocab_size"]
    nd, nr = layers(c)
    pair = attention_params(c) + 2 * d
    routed = (router_params(c) + c["published"]["n_routed_experts"]
              + shared_params(c) + c["n_routed_experts"] * expert_params(c))
    return (v * d + nd * (pair + dense_params(c)) + nr * (pair + routed)
            + d + d * v)


def held_assignments_per_token(c):
    """Expected assignments a token gives the experts held here: its
    num_experts_per_tok choices fall evenly over the published experts."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["published"]["n_routed_experts"])


def keys_per_query(seq_len):
    """Mean number of keys a query meets under a causal mask: i + 1."""
    return (seq_len + 1) / 2


def attention_forward_flops_per_token(c, seq_len):
    """Scores (qk_head_dim wide) and weighted values (v_head_dim wide), one
    layer, one token."""
    return (2 * c["num_attention_heads"]
            * (c["qk_head_dim"] + c["v_head_dim"]) * keys_per_query(seq_len))


def train_flops_per_token(c, seq_len):
    """Forward plus backward (twice the forward) matmul FLOPs a token on
    this chip: the routed experts at the expected share of held
    assignments, the shared expert's and the dense layers' products whole."""
    nd, nr = layers(c)
    attention = (2 * attention_matrix_params(c)
                 + attention_forward_flops_per_token(c, seq_len))
    fwd = ((nd + nr) * attention + nd * 2 * dense_params(c)
           + nr * 2 * (router_params(c) + shared_params(c)
                       + held_assignments_per_token(c) * expert_params(c))
           + 2 * c["hidden_size"] * c["vocab_size"])
    return 3.0 * fwd


def attention_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's attention, every layer, on a chip
    holding ``batch`` rows: forward two products (scores over qk_head_dim,
    weighted values over v_head_dim), backward four (dq and dk over
    qk_head_dim, dp and dv over v_head_dim; the scores a kernel makes again
    are not counted). Bytes: q and k (qk_head_dim a head) and v and o
    (v_head_dim) read or written once forward; q, k, v, o, do read and dq,
    dk, dv written backward. Every head has keys and values of its own."""
    h = c["num_attention_heads"]
    qk, v = h * c["qk_head_dim"], h * c["v_head_dim"]
    nd, nr = layers(c)
    tokens = batch * seq_len
    flops = 3.0 * tokens * attention_forward_flops_per_token(c, seq_len)
    nbytes = float(tokens * ((2 * qk + 2 * v) + (2 * qk + 3 * v)
                             + (2 * qk + v)) * BYTES)
    return (nd + nr) * flops, (nd + nr) * nbytes


def expert_step_work(c, assignments):
    """(flops, bytes) of the three routed expert products, forward and
    backward, of ONE layer in which ``assignments`` rows fell to the experts
    held here (the step's own ``moe.assignments_held``). Forward 3 products
    of rows x d x f; backward each product's two transposes. Bytes: forward
    the rows read (d), gate and up written (2f), their product read (f),
    the result written (d), every held expert's three matrices read;
    backward 3d + 4f a row and the matrices read and their gradients
    written."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    rows = float(assignments)
    flops = 3.0 * rows * 2 * expert_params(c)
    weights = c["n_routed_experts"] * expert_params(c)
    nbytes = ((2 * d + 3 * f) * rows + weights
              + (3 * d + 4 * f) * rows + 2 * weights) * BYTES
    return flops, nbytes
