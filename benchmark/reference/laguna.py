"""Plain reference of the Laguna decoder (``model_type: laguna``), from the
keys of its public ``config.json``: float32 ``jax.numpy`` at ``highest``
matmul precision, no kernels, no sort, no grouped product, no capacity, no
sharding rules. It imports nothing of the program.

  x = embed[tokens]
  per layer l:  h = x + attn_l(rmsnorm(x));  x = h + ffn_l(rmsnorm(h))
  logits = rmsnorm(x) . head, loss = mean next-token cross-entropy over the
  first T-1 positions, over the held slice of the vocabulary. No bias;
  rmsnorm(x) = w x / sqrt(mean(x^2) + eps), eps = ``rms_norm_eps``.

  attn_l(n), H = num_attention_heads_per_layer[l] query heads over
  num_key_value_heads, D = head_dim:
      q = n W_q -> [T, H, D], k = n W_k, v = n W_v -> [T, KV, D]
      RoPE of ``layer_types[l]`` (``rope_parameters[type]``): the first
          R = partial_rotary_factor x D dims of each head of q and k turn as
          halves (columns i and i + R / 2) by pos * f_i, f_i =
          theta**(-2i / R); the other D - R pass as they are. ``yarn``:
          with dim(r) = R ln(original / (2 pi r)) / (2 ln theta), low =
          floor(dim(beta_fast)), high = ceil(dim(beta_slow)) (clipped to
          the rotated dims), ramp_i = clip((i - low) / (high - low), 0, 1),
          the frequency is f_i ((1 - ramp_i) + ramp_i / factor), and cos
          and sin of the rotated part are multiplied by attention_factor
      scores q k^T / sqrt(D); causal, and ``sliding_attention`` lets query
          i see key j iff i - sliding_window < j <= i; softmax in float32;
          query head h reads key/value head h // (H / KV)
      o_h = softmax . v, then the per-head gate (``gating``):
          o_h <- sigmoid(n W_g)_h o_h, W_g [hidden, H]
      out = concat_h(o_h) W_o

  ffn_l, ``mlp_layer_types[l]`` dense: SwiGLU ``intermediate_size`` wide,
      (silu(n W_g) * n W_u) W_d.
  sparse: s = sigmoid(n W_r) over all published experts; the
      num_experts_per_tok largest (no correction bias); w = s[chosen] /
      sum s[chosen] * moe_routed_scaling_factor; out = sum_k w_k
      SwiGLU_e(n) over those of the chosen that this chip holds
      (``stands_for.experts_held``), ``moe_intermediate_size`` wide, + the
      shared expert, the same form ``shared_expert_intermediate_size`` wide,
      weight 1. What the absent experts would add is left out, here and in
      the program alike.

Not in the config and so not computed: a norm on q and k, an auxiliary
balance or z loss, a multi-token head (``assumed`` in the configuration's
file says the same, and why the gate, the rotation and the router are read
as above).

The plain pieces this model shares with the others (the normed product,
blocked attention with a band, the blocks over positions, SwiGLU) are the
Mellum 2 and DeepSeek-V3 references' own.

Departures, all of layout and none of mathematics: weights are kept (in,
out) and stacked on a leading axis by what a layer is, a dense layer's
under ``d_*`` names, a routed layer's under its attention's (``full_*``,
``window_*``: their query heads differ); each run of consecutive layers of
one stack is one rolled loop (``lax.scan``, so that a stack compiles once);
attention runs in blocks of queries with the band a mask on positions; the
experts are a plain loop over those held (one rolled ``lax.scan``), each a
dense product over every token weighted by what the router gave it (nought
for most), in blocks of positions; every block and every layer is
recomputed in the backward pass, so that full width fits one chip beside
float32 AdamW state.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from reference.deepseek_v3 import swiglu
from reference.mellum import _mm, _over_positions, attention, rmsnorm

HIGHEST = lax.Precision.HIGHEST
KINDS = {"full_attention": "full", "sliding_attention": "window"}
ATTENTION_KEYS = ("attn_norm", "wq", "wk", "wv", "wg", "wo", "mlp_norm")
DENSE_KEYS = ATTENTION_KEYS + ("w_gate", "w_up", "w_down")
ROUTED_KEYS = ATTENTION_KEYS + (
    "router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
    "shared_down")


def stack_of(c, layer):
    """The stack a layer's weights lie on: ``d`` for a dense layer, else
    its attention's kind."""
    if c["mlp_layer_types"][layer] == "dense":
        return "d"
    return KINDS[c["layer_types"][layer]]


def stacks(c):
    """{stack: (layers on it, its layer type, its query heads)}; every
    layer of a stack has the one type and head count."""
    out = {}
    for layer in range(c["num_hidden_layers"]):
        key = (c["layer_types"][layer],
               c["num_attention_heads_per_layer"][layer])
        n, *seen = out.get(stack_of(c, layer), (0, *key))
        if tuple(seen) != key:
            raise ValueError(f"layer {layer} is {key}, its stack {seen}")
        out[stack_of(c, layer)] = (n + 1, *key)
    return out


def param_shapes(c):
    """name -> (shape, std of the normal draw; None draws ones).
    Projections std fan_in**-0.5, the embedding 1; each leaf carries its
    stack's name before it (``d_``, ``full_``, ``window_``)."""
    d, dh, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    v, ff = c["vocab_size"], c["intermediate_size"]
    f, fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    held, published = c["num_experts"], c["published"]["num_experts"]
    shapes = {"embed": ((v, d), 1.0)}
    for stack, (n, _type, heads) in stacks(c).items():
        q = heads * dh
        leaves = {
            "attn_norm": ((n, d), None),
            "wq": ((n, d, q), d ** -0.5),
            "wk": ((n, d, kv * dh), d ** -0.5),
            "wv": ((n, d, kv * dh), d ** -0.5),
            "wg": ((n, d, heads), d ** -0.5),
            "wo": ((n, q, d), q ** -0.5),
            "mlp_norm": ((n, d), None),
        }
        if stack == "d":
            leaves.update({"w_gate": ((n, d, ff), d ** -0.5),
                           "w_up": ((n, d, ff), d ** -0.5),
                           "w_down": ((n, ff, d), ff ** -0.5)})
        else:
            leaves.update({"router": ((n, d, published), d ** -0.5),
                           "w_gate": ((n, held, d, f), d ** -0.5),
                           "w_up": ((n, held, d, f), d ** -0.5),
                           "w_down": ((n, held, f, d), f ** -0.5),
                           "shared_gate": ((n, d, fs), d ** -0.5),
                           "shared_up": ((n, d, fs), d ** -0.5),
                           "shared_down": ((n, fs, d), fs ** -0.5)})
        shapes.update({f"{stack}_{k}": s for k, s in leaves.items()})
    shapes["final_norm"] = ((d,), None)
    shapes["lm_head"] = ((d, v), d ** -0.5)
    return shapes


def rope_frequencies(c, layer_type):
    """(the R / 2 frequencies of the rotated dims, the factor on their cos
    and sin) of a layer type, from ``rope_parameters``."""
    r = c["rope_parameters"][layer_type]
    rot = round(c["head_dim"] * r["partial_rotary_factor"])
    theta, half = float(r["rope_theta"]), rot // 2
    k = jnp.arange(half, dtype=jnp.float32)
    f = theta ** (-k / half)
    if r["rope_type"] == "default":
        return f, 1.0
    if r["rope_type"] != "yarn":
        raise ValueError(f"rope_type {r['rope_type']!r}")
    dim = lambda turns: (rot * math.log(
        r["original_max_position_embeddings"] / (2 * math.pi * turns))
        / (2 * math.log(theta)))
    low = max(math.floor(dim(r["beta_fast"])), 0)
    high = min(math.ceil(dim(r["beta_slow"])), rot - 1)
    ramp = jnp.clip((k - low) / (high - low), 0.0, 1.0)
    return f * ((1.0 - ramp) + ramp / r["factor"]), r["attention_factor"]


def rope(x, freqs, factor):
    """x [B, T, heads, D]: the first 2 x len(freqs) dims turned as halves,
    positions 0..T-1, cos and sin times ``factor``; the rest as they are."""
    half = freqs.shape[0]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * half:]], -1)


def gated_attention(c, n, w, layer_type, heads, q_block, cast):
    b, t, _ = n.shape
    dh, kv = c["head_dim"], c["num_key_value_heads"]
    freqs, factor = rope_frequencies(c, layer_type)
    window = (c["sliding_window"] if layer_type == "sliding_attention"
              else None)
    q = rope(_mm(n, w["wq"], cast).astype(jnp.float32)
             .reshape(b, t, heads, dh), freqs, factor)
    k = rope(_mm(n, w["wk"], cast).astype(jnp.float32)
             .reshape(b, t, kv, dh), freqs, factor)
    v = _mm(n, w["wv"], cast).astype(jnp.float32).reshape(b, t, kv, dh)
    o = attention(q.reshape(b, t, kv, heads // kv, dh), k, v, window,
                  q_block, cast).reshape(b, t, heads, dh)  # head h = kv, g
    gate = jax.nn.sigmoid(_mm(n, w["wg"], cast).astype(jnp.float32))
    o = o * gate[..., None]
    return _mm(o.reshape(b, t, heads * dh), w["wo"], cast).astype(
        jnp.float32)


def route(c, u, router):
    """(weights [..., k], experts [..., k]) of every token."""
    s = jax.nn.sigmoid(jnp.matmul(u, router, precision=HIGHEST))
    w, chosen = lax.top_k(s, c["num_experts_per_tok"])
    return w / jnp.sum(w, axis=-1, keepdims=True) * c[
        "moe_routed_scaling_factor"], chosen


def routed_experts(c, u, w, cast, first=None, count=None):
    """The held experts' part of the routed result of u [..., D]: experts
    ``first`` .. ``first + count`` of the published ones (the
    configuration's share unless given), whose matrices are ``w``'s."""
    held = c["stands_for"]["experts_held"]
    first = held["first"] if first is None else first
    count = w["w_up"].shape[0] if count is None else count
    top_w, top_e = route(c, u, w["router"])

    def one(out, expert):
        e, w_gate, w_up, w_down = expert
        # what the router gave expert e of each token: nought for most
        weight = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        return out + weight[..., None] * swiglu(
            u, w_gate, w_up, w_down, cast), None

    # one expert after another: a loop, rolled so that it compiles once
    out, _ = lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(count), w["w_gate"][:count], w["w_up"][:count],
        w["w_down"][:count]))
    return out


def layer(c, x, w, stack, layer_type, heads, chunk, q_block, cast):
    b, t, d = x.shape
    eps = c["rms_norm_eps"]
    h = x + gated_attention(c, rmsnorm(x, w["attn_norm"], eps), w,
                            layer_type, heads, q_block, cast)

    def ffn(hc):
        u = rmsnorm(hc, w["mlp_norm"], eps)
        if stack == "d":
            return hc + swiglu(u, w["w_gate"], w["w_up"], w["w_down"], cast)
        return (hc + routed_experts(c, u, w, cast)
                + swiglu(u, w["shared_gate"], w["shared_up"],
                         w["shared_down"], cast))

    out = _over_positions(ffn, (h,), chunk)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def loss(c, params, batch, *, chips=1, chunk_tokens=2048, q_block=128,
         compute_dtype=jnp.float32):
    """Mean next-token cross-entropy of ``batch["tokens"]`` [B, T] (int32).
    ``chunk_tokens`` bounds the tokens a chip's block of the feed-forward
    or of the loss holds; ``compute_dtype`` below float32 is the control:
    matmul inputs rounded to it (the router's stay float32, as the
    program's do), everything else as here."""
    tokens, chunk_tokens = batch["tokens"], chunk_tokens * chips
    if compute_dtype == jnp.float32:
        cast = lambda a: a
    else:
        cast = lambda a: a.astype(compute_dtype)
    b, t = tokens.shape
    chunk = max(1, chunk_tokens // b)
    while t % chunk:
        chunk -= 1
    by_stack = stacks(c)
    x = params["embed"][tokens]
    taken = dict.fromkeys(by_stack, 0)
    layers = [stack_of(c, i) for i in range(c["num_hidden_layers"])]
    i = 0
    while i < len(layers):  # each run of one stack's layers: one loop
        stack, n = layers[i], 1
        while i + n < len(layers) and layers[i + n] == stack:
            n += 1
        _, layer_type, heads = by_stack[stack]
        keys = DENSE_KEYS if stack == "d" else ROUTED_KEYS
        first = taken[stack]
        w = {k: params[f"{stack}_{k}"][first:first + n] for k in keys}

        @jax.checkpoint
        def one(x, w, stack=stack, layer_type=layer_type, heads=heads):
            return layer(c, x, w, stack, layer_type, heads, chunk, q_block,
                         cast), None

        x, _ = lax.scan(one, x, w)
        taken[stack] += n
        i += n
    x = rmsnorm(x, params["final_norm"], c["rms_norm_eps"])
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
