"""What Keye-VL-2.0's language model needs, from its configuration's sizes:
parameters held, the training step's matmul FLOPs a token, the operations
and bytes of its attention over the selected keys, and those of the
selection. Counts of the algorithm, whatever implements it: the attention
at the pairs it keeps (each query's ``topk`` keys, every causal key where
it has no more), the indexer's scores at every causal pair, nothing
recomputed, the routed experts at the share of the assignments that falls
to those held."""

BYTES = 2  # bf16 operands


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def selected_pairs(c, seq_len):
    """Sum over queries t of min(t + 1, topk): the pairs attention keeps
    (ties at a threshold aside)."""
    k = min(c["sa_config"]["topk"], seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def attention_matrix_params(c):
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    return d * (q + 2 * kv) + q * d


def indexer_matrix_params(c):
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return c["hidden_size"] * (hi * di + di + hi)


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["published"]["num_experts"]


def param_count(c):
    """Parameters this chip holds: every layer's attention, its two norms
    and q/k norms, the indexer (its key norm's scale and bias), the router
    and its own routed experts; its slice of the embedding and of the
    head."""
    d, v = c["hidden_size"], c["vocab_size"]
    di = c["sa_config"]["indexer_head_dim"]
    layer = (attention_matrix_params(c) + 2 * d + 2 * c["head_dim"]
             + indexer_matrix_params(c) + 2 * di + router_params(c)
             + c["num_experts"] * expert_params(c))
    return v * d + c["num_hidden_layers"] * layer + d + d * v


def held_assignments_per_token(c):
    return (c["num_experts_per_tok"] * c["num_experts"]
            / c["published"]["num_experts"])


def attention_forward_flops_per_token(c, seq_len):
    """Scores and weighted values over the selected pairs, every head, a
    token's mean."""
    return (2 * 2 * c["num_attention_heads"] * c["head_dim"]
            * selected_pairs(c, seq_len) / seq_len)


def index_forward_flops_per_token(c, seq_len):
    """The indexer's scores (a product a head) over every causal pair, a
    token's mean."""
    sa = c["sa_config"]
    return (2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
            * causal_pairs(seq_len) / seq_len)


def train_flops_per_token(c, seq_len):
    """Forward plus backward (twice the forward) matmul FLOPs a token on
    this chip: every layer's projections, attention at the selected pairs,
    the indexer's projections and scores (its loss's backward is the
    scores' twice over), the router and the held experts at the expected
    share of assignments; the head."""
    layer = (2 * attention_matrix_params(c) + 2 * indexer_matrix_params(c)
             + attention_forward_flops_per_token(c, seq_len)
             + index_forward_flops_per_token(c, seq_len)
             + 2 * (router_params(c)
                    + held_assignments_per_token(c) * expert_params(c)))
    return 3.0 * (c["num_hidden_layers"] * layer
                  + 2 * c["hidden_size"] * c["vocab_size"])


def attention_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's attention over the selected pairs, every
    layer, on a chip holding ``batch`` rows: forward two products, backward
    four (the scores a kernel makes again are not counted); q, k, v, o read
    or written once forward, q, k, v, o, do read and dq, dk, dv written
    backward. The selection's bits are the selection's work
    (:func:`select_step_work`), not attention's."""
    tokens = batch * seq_len
    dh = c["head_dim"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    flops = 3.0 * tokens * attention_forward_flops_per_token(c, seq_len)
    nbytes = float((tokens * (2 * q + 2 * kv) + tokens * (3 * q + 2 * kv)
                    + tokens * (q + 2 * kv)) * BYTES)
    n = c["num_hidden_layers"]
    return n * flops, n * nbytes


def select_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's selections, every layer once (the bits
    are kept for the backward pass): the indexer's scores at every causal
    pair, a product a head; its queries, key and weights read (bf16, the
    weights float32), the bits written (a bit a pair of the square tiles'
    causal half and diagonal: T^2 / 8 bytes as the kernel writes them),
    each query's log-sum-exp and count written (float32)."""
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    flops = batch * 2 * hi * di * causal_pairs(seq_len)
    nbytes = batch * (seq_len * (hi * di + di) * BYTES + seq_len * hi * 4
                      + seq_len * seq_len // 8 + 2 * seq_len * 4)
    n = c["num_hidden_layers"]
    return n * float(flops), n * float(nbytes)


def index_kl_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's indexer losses, every layer once (the
    gradients are kept for the backward pass): at each selected pair the
    main attention's scores, every head (the loss's target needs its
    probabilities; the attention's kernels hand none over), and the
    indexer's scores and the two products of their gradient (queries and
    key); q, k, the log-sum-exps, the indexer's queries, key and weights
    and the bits read, their gradients written (float32)."""
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h, dh = c["num_attention_heads"], c["head_dim"]
    kv = c["num_key_value_heads"] * dh
    flops = batch * selected_pairs(c, seq_len) * (
        2 * h * dh + 3 * 2 * hi * di)
    tokens = batch * seq_len
    nbytes = (tokens * (h * dh + kv + hi * di + di) * BYTES
              + tokens * (h + hi + 1) * 4 + batch * seq_len * seq_len // 8
              + tokens * (hi * di + di + hi) * 4)
    n = c["num_hidden_layers"]
    return n * float(flops), n * float(nbytes)
