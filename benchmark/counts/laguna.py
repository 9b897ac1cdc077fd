"""What the Laguna decoder's mathematics needs, from its configuration's
sizes: parameters held, the training step's matmul FLOPs a token, and the
operations and bytes of its attention, with each layer's query heads as
that layer has them and a window counted as a window. Counts of the
algorithm, whatever implements it: nothing recomputed, the routed experts
at the share of the assignments that falls to those held."""

from counts.mellum import keys_per_query

BYTES = 2  # bf16 operands


def _layers(c):
    """(layer type, query heads, dense) of every layer held."""
    return [(c["layer_types"][i], c["num_attention_heads_per_layer"][i],
             c["mlp_layer_types"][i] == "dense")
            for i in range(c["num_hidden_layers"])]


def attention_matrix_params(c, heads):
    """W_q, W_k, W_v, W_o and the gate's W_g of a layer of ``heads``."""
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv = heads * dh, c["num_key_value_heads"] * dh
    return d * (q + 2 * kv) + q * d + d * heads


def dense_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c):
    return 3 * c["hidden_size"] * c["shared_expert_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["published"]["num_experts"]


def param_count(c):
    """Parameters this chip holds: every layer's attention, gate and two
    norms whole, the dense layers' feed-forward, of each routed layer the
    router, the shared expert and its own routed experts, its slice of the
    embedding and of the head."""
    d, v = c["hidden_size"], c["vocab_size"]
    routed = (router_params(c) + shared_params(c)
              + c["num_experts"] * expert_params(c))
    layers = sum(attention_matrix_params(c, heads) + 2 * d
                 + (dense_params(c) if dense else routed)
                 for _type, heads, dense in _layers(c))
    return v * d + layers + d + d * v


def held_assignments_per_token(c):
    """Expected assignments a token gives the experts held here: its
    num_experts_per_tok choices fall evenly over the published experts."""
    return (c["num_experts_per_tok"] * c["num_experts"]
            / c["published"]["num_experts"])


def attention_forward_flops_per_token(c, layer_type, heads, seq_len):
    """Scores and weighted values, one layer of ``heads``, one token."""
    return (2 * 2 * heads * c["head_dim"]
            * keys_per_query(c, layer_type, seq_len))


def train_flops_per_token(c, seq_len):
    """Forward plus backward (twice the forward) matmul FLOPs a token on
    this chip: the routed experts at the expected share of held
    assignments, the shared expert's and the dense layers' products whole."""
    routed = 2 * (router_params(c) + shared_params(c)
                  + held_assignments_per_token(c) * expert_params(c))
    fwd = sum(2 * attention_matrix_params(c, heads)
              + attention_forward_flops_per_token(c, t, heads, seq_len)
              + (2 * dense_params(c) if dense else routed)
              for t, heads, dense in _layers(c))
    return 3.0 * (fwd + 2 * c["hidden_size"] * c["vocab_size"])


def attention_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's attention, every layer with its own
    query heads and mask, on a chip holding ``batch`` rows: forward two
    products, backward four (the scores a kernel makes again are not
    counted); q, k, v, o read or written once forward, q, k, v, o, do read
    and dq, dk, dv written backward (a band moves no fewer bytes than a
    causal mask)."""
    tokens = batch * seq_len
    kv = c["num_key_value_heads"] * c["head_dim"]
    flops = nbytes = 0.0
    for layer_type, heads, _dense in _layers(c):
        q = heads * c["head_dim"]
        flops += 3.0 * tokens * attention_forward_flops_per_token(
            c, layer_type, heads, seq_len)
        nbytes += float((tokens * (2 * q + 2 * kv) + tokens * (3 * q + 2 * kv)
                         + tokens * (q + 2 * kv)) * BYTES)
    return flops, nbytes
