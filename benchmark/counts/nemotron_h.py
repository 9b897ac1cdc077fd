"""What the Nemotron-H decoder's mathematics needs, from its configuration's
sizes: parameters held, the training step's matmul FLOPs a token, and the
operations and bytes of its attention, of its state-space scans and of its
routed experts' products. Counts of the algorithm, whatever implements it:
a causal mask counted as causal (in the attention and inside a chunk of the
scan alike), nothing recomputed, the routed experts at the share of the
assignments that falls to those held."""

BYTES = 2  # bf16 operands
F32 = 4


def kinds(c):
    """(Mamba, expert, attention) layers in the pattern held."""
    pattern = c["hybrid_override_pattern"]
    return tuple(pattern.count(letter) for letter in "ME*")


def mamba_widths(c):
    """(inner H P, convolved H P + 2 G N, projected 2 H P + 2 G N + H)."""
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return inner, conv, inner + conv + c["mamba_num_heads"]


def mamba_matrix_params(c):
    """A Mamba layer's two projections."""
    inner, _conv, proj = mamba_widths(c)
    return c["hidden_size"] * (proj + inner)


def mamba_params(c):
    """The projections, the convolution's taps and bias, dt_bias, A_log and
    D a head, the gated norm's weight and the layer's norm."""
    inner, conv, _proj = mamba_widths(c)
    return (mamba_matrix_params(c) + conv * (c["conv_kernel"] + 1)
            + 3 * c["mamba_num_heads"] + inner + c["hidden_size"])


def attention_matrix_params(c):
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def expert_params(c):
    """One routed expert: up and down (no gate)."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c):
    return 2 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["published"]["n_routed_experts"]


def param_count(c):
    """Parameters this chip holds: every Mamba and attention layer whole,
    of each expert layer the router (with its correction bias), the shared
    expert and its own routed experts, its slice of the embedding and of
    the head."""
    d, v = c["hidden_size"], c["vocab_size"]
    nm, ne, na = kinds(c)
    expert_layer = (router_params(c) + c["published"]["n_routed_experts"]
                    + shared_params(c)
                    + c["n_routed_experts"] * expert_params(c) + d)
    return (v * d + nm * mamba_params(c)
            + na * (attention_matrix_params(c) + d) + ne * expert_layer
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
    """Scores and weighted values, one layer, one token."""
    q = c["num_attention_heads"] * c["head_dim"]
    return 2 * 2 * q * keys_per_query(seq_len)


def scan_forward_flops_per_token(c):
    """The chunked scan's four products, one layer, one token, at the
    published chunk Q: inside a chunk a position meets the (Q + 1) / 2
    positions up to its own, as a causal query meets its keys (C . B a
    group, N wide; the weighted x a head, P wide); the chunk's closing
    state and the entering state's part of the output are P x N a head
    each. The pass of states between chunks is a [chunks, chunks] product a
    chunk, not a token: left out."""
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    inside = keys_per_query(c["chunk_size"]) * 2 * (g * n + h * p)
    return inside + 2 * 2 * h * p * n


def train_flops_per_token(c, seq_len):
    """Forward plus backward (twice the forward) matmul FLOPs a token on
    this chip: the routed experts at the expected share of held
    assignments, the scan's products and the attention's as above."""
    nm, ne, na = kinds(c)
    fwd = (nm * (2 * mamba_matrix_params(c) + scan_forward_flops_per_token(c))
           + na * (2 * attention_matrix_params(c)
                   + attention_forward_flops_per_token(c, seq_len))
           + ne * 2 * (router_params(c) + shared_params(c)
                       + held_assignments_per_token(c) * expert_params(c))
           + 2 * c["hidden_size"] * c["vocab_size"])
    return 3.0 * fwd


def attention_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's attention, every attention layer, on a
    chip holding ``batch`` rows: forward two products, backward four; q, k,
    v, o read or written once forward, q, k, v, o, do read and dq, dk, dv
    written backward."""
    hd = c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    _nm, _ne, na = kinds(c)
    tokens = batch * seq_len
    flops = 3.0 * tokens * attention_forward_flops_per_token(c, seq_len)
    nbytes = float(
        (tokens * (2 * q + 2 * kv) + tokens * (3 * q + 2 * kv)
         + tokens * (q + 2 * kv)) * BYTES)
    return na * flops, na * nbytes


def ssd_step_work(c, batch, seq_len):
    """(flops, bytes) of one step's state-space scans, every Mamba layer,
    forward and backward, on a chip holding ``batch`` rows, whatever
    implements them. FLOPs: three times the forward's (each product has two
    transposes). Bytes: forward x [H P], B and C [G N each] in bf16 and dt
    [H] in float32 read, y [H P] written; backward those and dy read, dx,
    dB, dC and d dt written. The states stay on the chip: a chunk's are
    made and used in place."""
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    nm, _ne, _na = kinds(c)
    tokens = batch * seq_len
    flops = 3.0 * tokens * scan_forward_flops_per_token(c)
    operands = (h * p + 2 * g * n) * BYTES + h * F32  # x, B, C, dt
    result = h * p * BYTES
    nbytes = float(tokens * ((operands + result)
                             + (operands + result) + operands))
    return nm * flops, nm * nbytes


def expert_step_work(c, assignments):
    """(flops, bytes) of the two routed expert products, forward and
    backward, of ONE layer in which ``assignments`` rows fell to the experts
    held here (the step's own ``moe.assignments_held``). Forward 2 products
    of rows x d x f; backward each product's two transposes. Bytes: forward
    the rows read (d), up written (f), its relu ** 2 read (f), the result
    written (d), every held expert's two matrices read; backward 3d + 3f a
    row and the matrices read and their gradients written."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    rows = float(assignments)
    flops = 3.0 * rows * 2 * expert_params(c)
    weights = c["n_routed_experts"] * expert_params(c)
    nbytes = ((2 * d + 2 * f) * rows + weights
              + (3 * d + 3 * f) * rows + 2 * weights) * BYTES
    return flops, nbytes
