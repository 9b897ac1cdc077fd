"""Plain reference of the Nemotron-H decoder (NVIDIA-Nemotron-3-Nano, "Nemotron-H",
arXiv:2504.03624; the state-space layer is Mamba-2, arXiv:2405.21060), from
the keys of its public ``config.json`` (``model_type: nemotron_h``): float32
``jax.numpy`` at ``highest`` matmul precision, no kernels, no chunked scan,
no sort, no grouped product, no capacity, no sharding rules. It imports
nothing of the program.

  x = embed[tokens]
  per layer l, by its letter in ``hybrid_override_pattern``:
      x = x + mixer_l(rmsnorm(x))          one mixer a layer, nothing else
  logits = rmsnorm(x) . head, loss = mean next-token cross-entropy over the
  first T-1 positions, over the held slice of the vocabulary. No bias
  anywhere but the convolution's; rmsnorm(x) = w x / sqrt(mean(x^2) + eps)
  with eps = ``layer_norm_epsilon``.

  ``M``, Mamba-2: H = mamba_num_heads heads of P = mamba_head_dim, G =
  n_groups groups, state N = ssm_state_size, kernel K = conv_kernel.
      [z | xBC | dt] = u . W_in      widths H P | H P + 2 G N | H
      xBC = silu(conv(xBC) + b), a causal depthwise convolution (each
            channel alone, K taps, nought before the sequence's start)
      x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC); head h reads
            group h // (H / G)
      dt = softplus(dt + dt_bias), A = -exp(A_log)      (a number a head)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S_0 = 0   [P, N] a head
      y_t = S_t . C_t + D x_t
      g = y silu(z), RMS-normalised over each of the G groups of channels
          alone (eps as above), times a weight; out = g . W_out
  The scan is **the literal recurrence** over positions (``lax.scan`` over
  t with the state [B, H, P, N] carried): a different algorithm from the
  program's chunked form for the same function.

  ``*``, attention: q = Wq u (num_attention_heads of head_dim), k, v = Wk u,
  Wv u (num_key_value_heads), causal softmax attention scaled by
  head_dim**-0.5, query head h reads key/value head h // group, out = Wo .
  attn. **No rotary or other positional embedding** (``assumed`` in the
  configuration's file says where that comes from).

  ``E``, expert layer: s = sigmoid(u . Wr) over all published experts;
  chosen = the num_experts_per_tok largest of s + e_score_correction_bias
  (a buffer: it enters the choice alone); w = s[chosen] / (sum s[chosen] +
  1e-20) * routed_scaling_factor (norm_topk_prob); out = sum_k w_k
  Wdown_e(relu(Wup_e u)^2) over those of the chosen that this chip holds
  (``stands_for.experts_held``) + Wdown_s(relu(Wup_s u)^2), the shared
  expert of every token. What the absent experts would add is left out,
  here and in the program alike. n_group = topk_group = 1: no group limit.

Not in the config and so not computed: an auxiliary balance loss, an update
of the correction bias (``assumed`` in the configuration's file says the
same). Published and unused: ``expand`` (2 x hidden is not H P),
``rope_theta``, ``partial_rotary_factor``, ``intermediate_size``.

Departures, all of layout and none of mathematics: weights are kept (in,
out) and each kind's layers stacked on a leading axis of their own; the
layers are a Python loop; the recurrence runs in blocks of positions, each
block recomputed in the backward pass (so that 8,192 steps' states need not
be kept), a Mamba layer one row of the batch at a time; the product S . C is a
multiply and a sum in float32, no matrix unit; attention runs in blocks of
queries; the experts are a plain loop over those held (one rolled
``lax.scan``), each a dense product over every token weighted by what the
router gave it (nought for most), in blocks of positions; every block and
every layer is recomputed in the backward pass, so that full width fits one
chip beside float32 AdamW state.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
MAMBA_KEYS = ("m_norm", "m_in_proj", "m_conv_w", "m_conv_b", "m_dt_bias",
              "m_A_log", "m_D", "m_gate_norm", "m_out_proj")
ATTENTION_KEYS = ("a_norm", "wq", "wk", "wv", "wo")
EXPERT_KEYS = ("e_norm", "router", "router_bias", "w_up", "w_down",
               "shared_up", "shared_down")
LAYER_KEYS = {"mamba": MAMBA_KEYS, "attention": ATTENTION_KEYS,
              "experts": EXPERT_KEYS}


def mamba_widths(c):
    """(inner H P, convolved H P + 2 G N, projected 2 H P + 2 G N + H)."""
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return inner, conv, inner + conv + c["mamba_num_heads"]


def param_shapes(c):
    """name -> (shape, std of the normal draw; None draws ones). The stds
    of the leaves whose published starting values have a mean
    (``assumed.draw`` in the configuration's file says why) come from
    there, and so does the reading of ``rescale_prenorm_residual``."""
    d, v = c["hidden_size"], c["vocab_size"]
    pattern = c["hybrid_override_pattern"]
    nm, ne, na = (pattern.count(letter) for letter in "ME*")
    inner, conv, proj = mamba_widths(c)
    h, k = c["mamba_num_heads"], c["conv_kernel"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    held, published = c["n_routed_experts"], c["published"]["n_routed_experts"]
    f, fs = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    draw = c["assumed"]["draw"]
    # rescale_prenorm_residual: every projection that writes to the
    # residual stream starts 1 / sqrt(published depth) smaller
    res = (c["published"]["num_hidden_layers"] ** -0.5
           if c["rescale_prenorm_residual"] else 1.0)
    return {
        "embed": ((v, d), 1.0),
        "m_norm": ((nm, d), None),
        "m_in_proj": ((nm, d, proj), d ** -0.5),
        "m_conv_w": ((nm, conv, k), k ** -0.5),
        "m_conv_b": ((nm, conv), draw["conv_bias_std"]),
        "m_dt_bias": ((nm, h), draw["dt_bias_std"]),
        "m_A_log": ((nm, h), draw["A_log_std"]),
        "m_D": ((nm, h), None),
        "m_gate_norm": ((nm, inner), None),
        "m_out_proj": ((nm, inner, d), res * inner ** -0.5),
        "a_norm": ((na, d), None),
        "wq": ((na, d, q), d ** -0.5),
        "wk": ((na, d, kv), d ** -0.5),
        "wv": ((na, d, kv), d ** -0.5),
        "wo": ((na, q, d), res * q ** -0.5),
        "e_norm": ((ne, d), None),
        "router": ((ne, d, published), d ** -0.5),
        "router_bias": ((ne, published), draw["score_bias_std"]),
        "w_up": ((ne, held, d, f), d ** -0.5),
        "w_down": ((ne, held, f, d), res * f ** -0.5),
        "shared_up": ((ne, d, fs), d ** -0.5),
        "shared_down": ((ne, fs, d), res * fs ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d ** -0.5),
    }


def _mm(x, w, cast):
    return jnp.matmul(cast(x), cast(w), precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _over_positions(fn, x, chunk):
    """fn over [B, chunk, ...] slices of x's position axis, recomputed in
    the backward pass; results stacked on a leading axis."""
    b, t = x[0].shape[:2]
    chunk = min(chunk, t)
    parts = tuple(
        jnp.moveaxis(a.reshape(b, t // chunk, chunk, *a.shape[2:]), 1, 0)
        for a in x)
    return lax.map(jax.checkpoint(lambda args: fn(*args)), parts)


# -- the state-space layer ----------------------------------------------------

def causal_conv(x, w, bias):
    """x [B, T, C], w [C, K], bias [C]: each channel's own K taps over the
    positions up to its own, nought before the start (left-padded by
    K - 1), as a grouped convolution with a group a channel."""
    k = w.shape[1]
    y = lax.conv_general_dilated(
        x, jnp.transpose(w)[:, None, :], window_strides=(1,),
        padding=[(k - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1], precision=HIGHEST)
    return y + bias


def recurrence(x, dt, a, b, c, block=128):
    """The state-space scan as written: x [B, T, H, P], dt [B, T, H], a [H],
    b and c [B, T, G, N] (head h reads group h // (H / G)) -> y [B, T, H,
    P]. One position a step, the state [B, H, P, N] carried; ``block``
    positions are recomputed together in the backward pass."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    block = min(block, t)
    while t % block:
        block -= 1

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(v, h // g, axis=1) for v in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def positions(s, ats):
        return lax.scan(step, s, ats)

    # [B, T, ...] -> [blocks, block, B, ...]
    blocks = tuple(
        jnp.moveaxis(v, 1, 0).reshape(t // block, block, *v.shape[:1],
                                      *v.shape[2:])
        for v in (x, dt, b, c))
    _, y = lax.scan(positions, jnp.zeros((bsz, h, p, n), jnp.float32), blocks)
    return jnp.moveaxis(y.reshape(t, bsz, h, p), 0, 1)


def quadratic(x, dt, a, b, c, q_block=128):
    """The same function in its dual form, a block of queries at a time:
    ``y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j`` with ``cum``
    the running sum of ``dt A``. Kept as the fallback should the recurrence
    be too slow on the chip, and tied to it by a test; ``loss`` does not
    call it."""
    bsz, t, h, p = x.shape
    b, c = (jnp.repeat(v, h // v.shape[2], axis=2) for v in (b, c))
    q_block = min(q_block, t)
    while t % q_block:
        q_block -= 1
    cum = jnp.cumsum(dt * a, axis=1)  # [B, T, H]
    xdt = x * dt[..., None]
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        i, c_i, cum_i = args
        q_pos = i * q_block + jnp.arange(q_block)
        seen = key_pos[None, :] <= q_pos[:, None]  # [Q, T]
        span = cum_i[:, :, None, :] - cum[:, None, :, :]  # [B, Q, T, H]
        decay = jnp.exp(jnp.where(seen[None, :, :, None], span, -jnp.inf))
        scores = jnp.einsum("bqhn,bthn->bqth", c_i, b, precision=HIGHEST)
        return jnp.einsum("bqth,bthp->bqhp", scores * decay, xdt,
                          precision=HIGHEST)

    nb = t // q_block
    split = lambda v: jnp.moveaxis(
        v.reshape(bsz, nb, q_block, *v.shape[2:]), 1, 0)
    y = lax.map(block, (jnp.arange(nb), split(c), split(cum)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, h, p)


def mamba(c, u, w, cast):
    bsz, t, _ = u.shape
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    inner, conv, _ = mamba_widths(c)
    zxbcdt = _mm(u, w["m_in_proj"], cast).astype(jnp.float32)
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w["m_conv_w"], w["m_conv_b"]))
    x, b, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(bsz, t, h, p)
    dt = jax.nn.softplus(dt + w["m_dt_bias"])
    a = -jnp.exp(w["m_A_log"])
    y = recurrence(x, dt, a, b.reshape(bsz, t, g, n),
                   cm.reshape(bsz, t, g, n))
    y = y + w["m_D"][:, None] * x
    gated = (y.reshape(bsz, t, inner) * jax.nn.silu(z)).reshape(
        bsz, t, g, inner // g)
    gated = gated * lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True)
        + c["layer_norm_epsilon"])
    gated = gated.reshape(bsz, t, inner) * w["m_gate_norm"]
    return _mm(gated, w["m_out_proj"], cast).astype(jnp.float32)


# -- attention ------------------------------------------------------------------

def attention(q, k, v, q_block, cast):
    """Grouped-query attention, causal. q [B,T,KV,G,Dh], k, v [B,T,KV,Dh];
    one block of ``q_block`` queries at a time against every key."""
    b, t, kv, g, dh = q.shape
    q_block = min(q_block, t)
    while t % q_block:
        q_block -= 1
    nb = t // q_block
    qs = jnp.moveaxis(q.reshape(b, nb, q_block, kv, g, dh), 1, 0)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("bqkgd,btkd->bkgqt", cast(qi), cast(k),
                       precision=HIGHEST).astype(jnp.float32) * dh ** -0.5
        q_pos = i * q_block + jnp.arange(q_block)
        seen = key_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", cast(p), cast(v),
                          precision=HIGHEST).astype(jnp.float32)

    out = lax.map(block, (jnp.arange(nb), qs))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, kv * g * dh)


def attend(c, u, w, q_block, cast):
    b, t, _ = u.shape
    hd, kv = c["head_dim"], c["num_key_value_heads"]
    g = c["num_attention_heads"] // kv
    q = _mm(u, w["wq"], cast).astype(jnp.float32).reshape(b, t, kv, g, hd)
    k = _mm(u, w["wk"], cast).astype(jnp.float32).reshape(b, t, kv, hd)
    v = _mm(u, w["wv"], cast).astype(jnp.float32).reshape(b, t, kv, hd)
    return _mm(attention(q, k, v, q_block, cast), w["wo"],
               cast).astype(jnp.float32)


# -- the expert layer -----------------------------------------------------------

def route(c, u, router, bias):
    """(weights [..., k], experts [..., k]) of every token."""
    if c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("a group limit on the choice is not computed")
    s = jax.nn.sigmoid(jnp.matmul(u, router, precision=HIGHEST))
    _, chosen = lax.top_k(s + bias, c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * c["routed_scaling_factor"], chosen


def shared_expert(u, w, cast):
    return _mm(relu2(_mm(u, w["shared_up"], cast).astype(jnp.float32)),
               w["shared_down"], cast).astype(jnp.float32)


def routed_experts(c, u, w, cast, first=None, count=None):
    """The held experts' part of the routed result of u [..., D]: experts
    ``first`` .. ``first + count`` of the published ones (the
    configuration's share unless given), whose matrices are ``w``'s."""
    held = c["stands_for"]["experts_held"]
    first = held["first"] if first is None else first
    count = w["w_up"].shape[0] if count is None else count
    top_w, top_e = route(c, u, w["router"], w["router_bias"])

    def one(out, expert):
        e, w_up, w_down = expert
        # what the router gave expert e of each token: nought for most
        weight = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        h = relu2(_mm(u, w_up, cast).astype(jnp.float32))
        return out + weight[..., None] * _mm(
            h, w_down, cast).astype(jnp.float32), None

    # one expert after another: a loop, rolled so that it compiles once
    out, _ = lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(count), w["w_up"][:count], w["w_down"][:count]))
    return out


def experts(c, u, w, chunk, cast):
    b, t, d = u.shape
    out = _over_positions(
        lambda uc: routed_experts(c, uc, w, cast) + shared_expert(uc, w, cast),
        (u,), chunk)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


# -- the decoder ------------------------------------------------------------------

def layer(c, x, w, kind, chunk, q_block, cast):
    eps = c["layer_norm_epsilon"]
    if kind == "mamba":
        # a row of the batch at a time, recomputed in the backward pass:
        # the layer's float32 intermediates are 40 KB a token
        row = lambda r: mamba(c, rmsnorm(r[None], w["m_norm"], eps), w,
                              cast)[0]
        return x + lax.map(jax.checkpoint(row), x)
    if kind == "attention":
        return x + attend(c, rmsnorm(x, w["a_norm"], eps), w, q_block, cast)
    return x + experts(c, rmsnorm(x, w["e_norm"], eps), w, chunk, cast)


def loss(c, params, batch, *, chips=1, chunk_tokens=2048, q_block=128,
         compute_dtype=jnp.float32):
    """Mean next-token cross-entropy of ``batch["tokens"]`` [B, T] (int32).
    ``chunk_tokens`` bounds the tokens a chip's block of the expert layer
    or of the loss holds; ``compute_dtype`` below float32 is the control:
    matmul inputs rounded to it (the router's and the scan stay float32, as
    the program's do), everything else as here."""
    tokens, chunk_tokens = batch["tokens"], chunk_tokens * chips
    if compute_dtype == jnp.float32:
        cast = lambda a: a
    else:
        cast = lambda a: a.astype(compute_dtype)
    b, t = tokens.shape
    chunk = max(1, chunk_tokens // b)
    while t % chunk:
        chunk -= 1
    x = params["embed"][tokens]
    taken = dict.fromkeys(LAYER_KEYS, 0)
    for letter in c["hybrid_override_pattern"]:
        kind = KINDS[letter]
        i, taken[kind] = taken[kind], taken[kind] + 1
        w = {k: params[k][i] for k in LAYER_KEYS[kind]}
        x = jax.checkpoint(
            lambda x, w, kind=kind: layer(
                c, x, w, kind, chunk, q_block, cast))(x, w)
    x = rmsnorm(x, params["final_norm"], c["layer_norm_epsilon"])
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))

    def nll(xc, yc, mc):
        logits = _mm(xc, params["lm_head"], cast).astype(jnp.float32)
        ll = (jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
              - jax.nn.logsumexp(logits, axis=-1))
        return -jnp.sum(jnp.where(mc, ll, 0.0))

    return jnp.sum(_over_positions(nll, (x, targets, counted), chunk)) \
        / (b * (t - 1))
