"""What the Mellum 2 decoder's mathematics needs, from its configuration's
sizes: parameters held, the training step's matmul FLOPs a token, the
attention's products and bytes by layer kind, and the expert products' for
a given number of rows. Counts of the algorithm, whatever implements it: a
window counted as a window, nothing recomputed, the experts at the share
of the assignments that falls to those held."""

BYTES = 2  # bf16 operands


def _sizes(c):
    hd = c["head_dim"]
    return (c["hidden_size"], c["num_hidden_layers"],
            c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd,
            c["moe_intermediate_size"], c["vocab_size"])


def expert_params(c):
    d, _n, _q, _kv, ff, _v = _sizes(c)
    return 3 * d * ff


def dense_params_per_layer(c):
    """A layer's matrices outside its experts: q, k, v, o and the router
    over every published expert."""
    d, _n, q, kv, _ff, _v = _sizes(c)
    return d * (q + 2 * kv) + q * d + d * c["published"]["num_experts"]


def param_count(c):
    """Parameters this chip holds: its experts of every layer, its slice
    of the embedding and of the head."""
    d, n, _q, _kv, _ff, v = _sizes(c)
    per_layer = (dense_params_per_layer(c) + 2 * d
                 + c["num_experts"] * expert_params(c))
    return v * d + n * per_layer + d + d * v


def held_assignments_per_token(c):
    """Expected assignments a token gives the experts held here: its
    num_experts_per_tok choices fall evenly over the published experts."""
    return (c["num_experts_per_tok"] * c["num_experts"]
            / c["published"]["num_experts"])


def keys_per_query(c, layer_type, seq_len):
    """Mean number of keys a query meets: i + 1 under a causal mask,
    min(i + 1, window) under a band."""
    if layer_type != "sliding_attention":
        return (seq_len + 1) / 2
    w = min(c["sliding_window"], seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def attention_forward_flops_per_token(c, layer_type, seq_len):
    """Scores and weighted values, one layer, one token."""
    _d, _n, q, _kv, _ff, _v = _sizes(c)
    return 2 * 2 * q * keys_per_query(c, layer_type, seq_len)


def train_flops_per_token(c, seq_len):
    """Forward plus backward (twice the forward) matmul FLOPs a token on
    this chip: the experts at the expected share of held assignments."""
    d, _n, _q, _kv, _ff, v = _sizes(c)
    per_layer = 2 * (dense_params_per_layer(c)
                     + held_assignments_per_token(c) * expert_params(c))
    fwd = (sum(per_layer + attention_forward_flops_per_token(c, t, seq_len)
               for t in c["layer_types"])
           + 2 * d * v)
    return 3.0 * fwd


def attention_step_work_by_kind(c, batch, seq_len):
    """{layer type: (flops, bytes)} the attention of one training step
    needs on a chip holding ``batch`` rows, all layers of that type
    together: forward two products, backward four; q, k, v, o read or
    written once forward, q, k, v, o, do read and dq, dk, dv written
    backward (a band moves no fewer bytes than a causal mask)."""
    _d, _n, q, kv, _ff, _v = _sizes(c)
    tokens = batch * seq_len
    nbytes = float(
        (tokens * (2 * q + 2 * kv) + tokens * (3 * q + 2 * kv)
         + tokens * (q + 2 * kv)) * BYTES)
    out = {}
    for layer_type in c["layer_types"]:
        flops = 3.0 * tokens * attention_forward_flops_per_token(
            c, layer_type, seq_len)
        f, b = out.get(layer_type, (0.0, 0.0))
        out[layer_type] = (f + flops, b + nbytes)
    return out


def attention_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's attention, every layer."""
    work = attention_step_work_by_kind(c, batch, seq_len).values()
    return sum(f for f, _ in work), sum(b for _, b in work)


def expert_step_work(c, assignments):
    """(flops, bytes) of the three expert products, forward and backward,
    of ONE layer in which ``assignments`` rows fell to the experts held
    here (the step's own ``moe.assignments_held``). Forward 3 products of
    rows x d x f; backward each product's two transposes. Bytes: forward
    the rows read (d), gate and up written (2f), their product read (f),
    the result written (d), every held expert's three matrices read;
    backward 3d + 4f a row and the matrices read and their gradients
    written."""
    d, _n, _q, _kv, ff, _v = _sizes(c)
    rows = float(assignments)
    flops = 3.0 * rows * 2 * expert_params(c)
    weights = c["num_experts"] * expert_params(c)
    nbytes = ((2 * d + 3 * ff) * rows + weights
              + (3 * d + 4 * ff) * rows + 2 * weights) * BYTES
    return flops, nbytes
