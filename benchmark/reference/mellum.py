"""Plain reference of the Mellum 2 decoder, from the keys of its public
``config.json`` (``model_type: mellum``): float32 ``jax.numpy`` at
``highest`` matmul precision, no kernels, no sort, no grouped product, no
capacity, no sharding rules. It imports nothing of the program.

  x = embed[tokens]
  per layer l:  h = x + Wo . attn_l(rope_l(Wq . n1), rope_l(Wk . n1), Wv . n1)
                x = h + moe(n2)
  with n1 = rmsnorm(x), n2 = rmsnorm(h), no biases; grouped-query attention
  (query head h reads key/value head h // group), scores scaled by
  head_dim**-0.5; logits = rmsnorm(x) . head, loss = mean next-token
  cross-entropy over the first T-1 positions, over the held slice of the
  vocabulary.

  ``layer_types[l]`` says the attention of layer l. ``sliding_attention``:
  query i sees key j iff i - sliding_window < j <= i (sliding_window keys
  with its own), rotate-half RoPE at the plain theta. ``full_attention``:
  causal, YaRN RoPE: with f_k = theta**(-2k / head_dim),
  dim(r) = head_dim * ln(original / (2 pi r)) / (2 ln theta),
  low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)) (clipped to the
  head), ramp_k = clip((k - low) / (high - low), 0, 1), the frequency is
  f_k * ((1 - ramp_k) + ramp_k / factor), and cos and sin are multiplied by
  attention_factor.

  moe(n): p = softmax(n . Wr) in float32 over all published experts; the
  num_experts_per_tok largest; their weights divided by their sum
  (norm_topk_prob); sum_k w_k * Wdown_e(silu(Wgate_e . n) * Wup_e . n) over
  those of them that this chip holds (``stands_for.experts_held``). What the
  absent experts would add is left out, here and in the program alike. No
  shared expert; every layer is sparse (mlp_layer_types).

Not in the config and so not computed: a norm on q and k, an auxiliary
balance or z loss, a multi-token head (``assumed`` in the configuration's
file says the same).

Departures, all of layout and none of mathematics: weights are kept (in,
out) and stacked over layers on a leading axis; the layers are a Python
loop (they are of unlike kinds); attention runs in blocks of queries with
the band as a mask on positions, the experts as a plain loop over those
held (one rolled ``lax.scan``), each a dense product over every token weighted by what the router
gave it (nought for most), in blocks of positions; every block is
recomputed in the backward pass, so that full width fits one chip beside
float32 AdamW state.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router",
              "w_gate", "w_up", "w_down")


def _sizes(c):
    hd = c["head_dim"]
    return (c["hidden_size"], c["num_hidden_layers"],
            c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd,
            c["moe_intermediate_size"], c["vocab_size"])


def param_shapes(c):
    """name -> (shape, std of the normal draw; None draws ones)."""
    d, n, q, kv, ff, v = _sizes(c)
    held, published = c["num_experts"], c["published"]["num_experts"]
    return {
        "embed": ((v, d), 1.0),
        "attn_norm": ((n, d), None),
        "wq": ((n, d, q), d ** -0.5),
        "wk": ((n, d, kv), d ** -0.5),
        "wv": ((n, d, kv), d ** -0.5),
        "wo": ((n, q, d), q ** -0.5),
        "mlp_norm": ((n, d), None),
        "router": ((n, d, published), d ** -0.5),
        "w_gate": ((n, held, d, ff), d ** -0.5),
        "w_up": ((n, held, d, ff), d ** -0.5),
        "w_down": ((n, held, ff, d), ff ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d ** -0.5),
    }


def _mm(x, w, cast):
    return jnp.matmul(cast(x), cast(w), precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_frequencies(c, layer_type):
    """(the head_dim / 2 frequencies, the factor on cos and sin) of a
    layer type, from ``rope_parameters``."""
    r, hd = c["rope_parameters"][layer_type], c["head_dim"]
    theta, half = float(r["rope_theta"]), hd // 2
    k = jnp.arange(half, dtype=jnp.float32)
    f = theta ** (-k / half)
    if r["rope_type"] == "default":
        return f, 1.0
    if r["rope_type"] != "yarn":
        raise ValueError(f"rope_type {r['rope_type']!r}")
    dim = lambda turns: (hd * math.log(
        r["original_max_position_embeddings"] / (2 * math.pi * turns))
        / (2 * math.log(theta)))
    low = max(math.floor(dim(r["beta_fast"])), 0)
    high = min(math.ceil(dim(r["beta_slow"])), hd - 1)
    ramp = jnp.clip((k - low) / (high - low), 0.0, 1.0)
    return f * ((1.0 - ramp) + ramp / r["factor"]), r["attention_factor"]


def rope(x, freqs, factor):
    """x [B, T, heads, Dh]; rotate-half form, positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, q_block, cast):
    """Grouped-query attention, causal, with ``window`` a band (None:
    every earlier key). q [B,T,KV,G,Dh], k, v [B,T,KV,Dh]; one block of
    ``q_block`` queries at a time against every key, the band a mask on
    positions."""
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
        if window is not None:
            seen = seen & (key_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", cast(p), cast(v),
                          precision=HIGHEST).astype(jnp.float32)

    out = lax.map(block, (jnp.arange(nb), qs))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, kv * g * dh)


def _over_positions(fn, x, chunk):
    """fn over [B, chunk, ...] slices of x's position axis, recomputed in
    the backward pass; results stacked on a leading axis."""
    b, t = x[0].shape[:2]
    chunk = min(chunk, t)
    parts = tuple(
        jnp.moveaxis(a.reshape(b, t // chunk, chunk, *a.shape[2:]), 1, 0)
        for a in x)
    return lax.map(jax.checkpoint(lambda args: fn(*args)), parts)


def moe(c, n2, w, cast, first=None, count=None):
    """The held experts' part of the routed feed-forward of n2 [..., D]:
    experts ``first`` .. ``first + count`` of the published ones (the
    configuration's share unless given), whose matrices are ``w``'s."""
    held = c["stands_for"]["experts_held"]
    first = held["first"] if first is None else first
    count = w["w_gate"].shape[0] if count is None else count
    p = jax.nn.softmax(
        jnp.matmul(n2, w["router"], precision=HIGHEST), axis=-1)
    top_p, top_e = lax.top_k(p, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    def one(out, expert):
        e, w_gate, w_up, w_down = expert
        # what the router gave expert e of each token: nought for most
        weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
        gate = jax.nn.silu(_mm(n2, w_gate, cast).astype(jnp.float32))
        up = _mm(n2, w_up, cast).astype(jnp.float32)
        return out + weight[..., None] * _mm(
            gate * up, w_down, cast).astype(jnp.float32), None

    # one expert after another: a loop, rolled so that it compiles once
    out, _ = lax.scan(one, jnp.zeros_like(n2), (
        jnp.arange(count), w["w_gate"][:count], w["w_up"][:count],
        w["w_down"][:count]))
    return out


def layer(c, x, w, layer_type, chunk, q_block, cast):
    b, t, d = x.shape
    hd, kv = c["head_dim"], c["num_key_value_heads"]
    g = c["num_attention_heads"] // kv
    eps = c["rms_norm_eps"]
    freqs, factor = rope_frequencies(c, layer_type)
    window = (c["sliding_window"] if layer_type == "sliding_attention"
              else None)
    n1 = rmsnorm(x, w["attn_norm"], eps)
    q = rope(_mm(n1, w["wq"], cast).astype(jnp.float32)
             .reshape(b, t, kv * g, hd), freqs, factor)
    k = rope(_mm(n1, w["wk"], cast).astype(jnp.float32)
             .reshape(b, t, kv, hd), freqs, factor)
    v = _mm(n1, w["wv"], cast).astype(jnp.float32).reshape(b, t, kv, hd)
    a = attention(q.reshape(b, t, kv, g, hd), k, v, window, q_block, cast)
    h = x + _mm(a, w["wo"], cast).astype(jnp.float32)

    def ffn(hc):
        return hc + moe(c, rmsnorm(hc, w["mlp_norm"], eps), w, cast)

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
    x = params["embed"][tokens]
    for i, layer_type in enumerate(c["layer_types"]):
        w = {k: params[k][i] for k in LAYER_KEYS}
        x = jax.checkpoint(
            lambda x, w, kind=layer_type: layer(
                c, x, w, kind, chunk, q_block, cast))(x, w)
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
