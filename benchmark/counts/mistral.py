"""What the Mistral decoder's mathematics needs, from its published sizes:
parameters, the training step's matmul FLOPs a token, and the attention's
own products and bytes. Counts of the algorithm, whatever implements it:
causal attention as causal, nothing recomputed."""


def _sizes(c):
    hd = c["head_dim"]
    return (c["hidden_size"], c["num_hidden_layers"],
            c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd,
            c["intermediate_size"], c["vocab_size"])


def matmul_params_per_layer(c):
    d, _n, q, kv, ff, _v = _sizes(c)
    return d * (q + 2 * kv) + q * d + 3 * d * ff


def param_count(c):
    d, n, _q, _kv, _ff, v = _sizes(c)
    per_layer = matmul_params_per_layer(c) + 2 * d
    return v * d + n * per_layer + d + d * v


def attention_forward_flops_per_token(c, seq_len):
    """Scores and weighted values, one layer, one token: a query at
    position i meets i + 1 keys, (T + 1) / 2 on average."""
    _d, _n, q, _kv, _ff, _v = _sizes(c)
    return 2 * 2 * q * (seq_len + 1) / 2


def train_flops_per_token(c, seq_len):
    """Forward plus backward (twice the forward) matmul FLOPs a token."""
    d, n, _q, _kv, _ff, v = _sizes(c)
    fwd = (n * (2 * matmul_params_per_layer(c)
                + attention_forward_flops_per_token(c, seq_len))
           + 2 * d * v)
    return 3.0 * fwd


def attention_step_work(c, batch, seq_len, bytes_per_value=2):
    """(flops, bytes) the attention of one training step needs on one chip
    holding ``batch`` rows: forward two products (scores, values), backward
    four (dV, dP, dQ, dK); bytes are q, k, v, o read or written once forward
    and q, k, v, o, do read and dq, dk, dv written backward."""
    _d, n, q, kv, _ff, _v = _sizes(c)
    tokens = batch * seq_len
    flops = 3.0 * n * tokens * attention_forward_flops_per_token(c, seq_len)
    fwd_bytes = tokens * (2 * q + 2 * kv)
    bwd_bytes = tokens * (3 * q + 2 * kv) + tokens * (q + 2 * kv)
    return flops, float(n * (fwd_bytes + bwd_bytes) * bytes_per_value)
