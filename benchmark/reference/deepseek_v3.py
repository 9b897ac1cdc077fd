"""Plain reference of the DeepSeek-V3-style decoder (``model_type:
deepseek_v3``; the family's description is DeepSeek-V2's and -V3's,
arXiv:2405.04434 and arXiv:2412.19437), from the keys of its public
``config.json``: float32 ``jax.numpy`` at ``highest`` matmul precision, no
kernels, no sort, no grouped product, no capacity, no sharding rules. It
imports nothing of the program.

  x = embed[tokens]
  per layer l:  h = x + attn(rmsnorm(x));  x = h + ffn_l(rmsnorm(h))
  logits = rmsnorm(x) . head, loss = mean next-token cross-entropy over the
  first T-1 positions, over the held slice of the vocabulary. No bias;
  rmsnorm(x) = w x / sqrt(mean(x^2) + eps), eps = ``rms_norm_eps``.

  attn(n), multi-head latent attention, H = num_attention_heads:
      q = n W_q -> [T, H, qk_head_dim], split q_nope (qk_nope_head_dim) |
          q_rope (qk_rope_head_dim); no compressed query (q_lora_rank null)
      [c | k_rope] = n W_kva -> kv_lora_rank | qk_rope_head_dim: ONE k_rope
          for all heads; c = rmsnorm(c; kv_a_layernorm)
      [k_nope | v] = c W_kvb -> [T, H, qk_nope_head_dim | v_head_dim]
      RoPE (rope_theta, no scaling) on q_rope and k_rope over INTERLEAVED
          pairs (rope_interleave): pair i is columns 2i, 2i + 1, angle
          pos * theta^(-2i / qk_rope_head_dim)
      k = [k_nope | k_rope broadcast to the H heads]
      scores q k^T * qk_head_dim^(-1/2), causal, softmax in float32,
      o = softmax . v -> [T, H, v_head_dim], out = o W_o

  ffn_l, l < first_k_dense_replace: dense SwiGLU ``intermediate_size`` wide,
      (silu(n W_g) * n W_u) W_d.
  ffn_l, the other layers: s = sigmoid(n W_r) over all published experts;
      chosen = the num_experts_per_tok largest of s +
      e_score_correction_bias (topk_method noaux_tc; a buffer that enters
      the choice alone; n_group = topk_group = 1: no group limit);
      w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
      (norm_topk_prob); out = sum_k w_k (silu(n W_g[e]) * n W_u[e]) W_d[e]
      over those of the chosen that this chip holds
      (``stands_for.experts_held``) + the shared expert, the same form
      n_shared_experts * moe_intermediate_size wide, weight 1. What the
      absent experts would add is left out, here and in the program alike.

Not in the config and so not computed: an auxiliary balance or z loss, the
rule by which e_score_correction_bias follows the experts' load, a
multi-token head (``assumed`` in the configuration's file says the same).
Published and unused: ``head_dim`` (64: the rotated part's size under
another name), ``max_position_embeddings``.

The correction bias is no leaf of ``param_shapes``: its gradient is
identically nought, and a number that reads 0 on every side cannot be given
a limit. :func:`score_bias` draws it, one fixed draw a (layers, experts)
shape whatever the run's seed, as a checkpoint's buffer is one; the
program's adapter calls the same function.

Departures, all of layout and none of mathematics (none beyond the cut):
weights are kept (in, out) and stacked over layers on a leading axis, the
leading dense layers' under ``d_*`` names on a stack of their own; each
stack's layers are one rolled loop (``lax.scan``: the five routed layers
compile once, which is what keeps the reference's first run on a chip at a
minute and not five); attention runs in blocks of queries; the experts
are a plain loop over those held (one rolled ``lax.scan``), each a dense
product over every token weighted by what the router gave it (nought for
most), in blocks of positions; every block and every layer is recomputed in
the backward pass, so that full width fits one chip beside float32 AdamW
state.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ATTENTION_KEYS = ("attn_norm", "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")
DENSE_KEYS = ATTENTION_KEYS + ("mlp_norm", "w_gate", "w_up", "w_down")
ROUTED_KEYS = ATTENTION_KEYS + (
    "mlp_norm", "router", "w_gate", "w_up", "w_down", "shared_gate",
    "shared_up", "shared_down")
# the correction bias's draw: the spacing of neighbouring scores around the
# sixth largest of 128 (the configuration's ``assumed.draw`` says why)
SCORE_BIAS_STD = 0.01
SCORE_BIAS_SEED = 2601


def _sizes(c):
    heads = c["num_attention_heads"]
    return (c["hidden_size"], heads * c["qk_head_dim"],
            c["kv_lora_rank"], c["qk_rope_head_dim"],
            heads * (c["qk_nope_head_dim"] + c["v_head_dim"]),
            heads * c["v_head_dim"])


def param_shapes(c):
    """name -> (shape, std of the normal draw; None draws ones).
    Projections std fan_in**-0.5, the embedding 1; a leading dense layer's
    leaves carry ``d_`` before the name."""
    d, q, r, rot, up, o = _sizes(c)
    v, ff = c["vocab_size"], c["intermediate_size"]
    f = c["moe_intermediate_size"]
    fs = c["n_shared_experts"] * f
    nd = c["first_k_dense_replace"]
    n = c["num_hidden_layers"] - nd
    held, published = c["n_routed_experts"], c["published"]["n_routed_experts"]

    def attention(count):
        return {
            "attn_norm": ((count, d), None),
            "wq": ((count, d, q), d ** -0.5),
            "wkv_a": ((count, d, r + rot), d ** -0.5),
            "kv_a_norm": ((count, r), None),
            "wkv_b": ((count, r, up), r ** -0.5),
            "wo": ((count, o, d), o ** -0.5),
            "mlp_norm": ((count, d), None),
        }

    dense = {**attention(nd),
             "w_gate": ((nd, d, ff), d ** -0.5),
             "w_up": ((nd, d, ff), d ** -0.5),
             "w_down": ((nd, ff, d), ff ** -0.5)}
    return {
        "embed": ((v, d), 1.0),
        **{"d_" + k: s for k, s in dense.items()},
        **attention(n),
        "router": ((n, d, published), d ** -0.5),
        "w_gate": ((n, held, d, f), d ** -0.5),
        "w_up": ((n, held, d, f), d ** -0.5),
        "w_down": ((n, held, f, d), f ** -0.5),
        "shared_gate": ((n, d, fs), d ** -0.5),
        "shared_up": ((n, d, fs), d ** -0.5),
        "shared_down": ((n, fs, d), fs ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d ** -0.5),
    }


def score_bias(layers, experts):
    """e_score_correction_bias of every routed layer, [layers, experts]
    float32: one fixed normal draw a shape (numpy's generator, so the same
    bits wherever it runs), no leaf and no function of the run's seed."""
    rng = np.random.default_rng([SCORE_BIAS_SEED, layers, experts])
    return (rng.standard_normal((layers, experts)) * SCORE_BIAS_STD).astype(
        np.float32)


def _mm(x, w, cast):
    return jnp.matmul(cast(x), cast(w), precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, T, ..., R], positions along axis 1: interleaved pairs, pair i
    columns 2i and 2i + 1 turned by ``pos * theta**(-2i / R)``."""
    t, rot = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape(1, t, *([1] * (x.ndim - 3)), rot // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, scale, q_block, cast):
    """Causal softmax attention, every head its own keys and values.
    q, k [B, T, H, Dqk], v [B, T, H, Dv]; one block of ``q_block`` queries
    at a time against every key."""
    b, t, h, dqk = q.shape
    q_block = min(q_block, t)
    while t % q_block:
        q_block -= 1
    nb = t // q_block
    qs = jnp.moveaxis(q.reshape(b, nb, q_block, h, dqk), 1, 0)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("bqhd,bthd->bhqt", cast(qi), cast(k),
                       precision=HIGHEST).astype(jnp.float32) * scale
        q_pos = i * q_block + jnp.arange(q_block)
        seen = key_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthd->bqhd", cast(p), cast(v),
                          precision=HIGHEST).astype(jnp.float32)

    out = lax.map(block, (jnp.arange(nb), qs))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h * v.shape[-1])


def latent_attention(c, n, w, q_block, cast):
    b, t, _ = n.shape
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rot, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    theta = float(c["rope_theta"])
    q = _mm(n, w["wq"], cast).astype(jnp.float32).reshape(b, t, h, nope + rot)
    down = _mm(n, w["wkv_a"], cast).astype(jnp.float32)
    latent = rmsnorm(down[..., :r], w["kv_a_norm"], c["rms_norm_eps"])
    k_rope = rope(down[..., r:], theta)  # [B, T, rot]: one for all heads
    up = _mm(latent, w["wkv_b"], cast).astype(jnp.float32).reshape(
        b, t, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (b, t, h, rot))], axis=-1)
    o = attention(q, k, up[..., nope:], (nope + rot) ** -0.5, q_block, cast)
    return _mm(o, w["wo"], cast).astype(jnp.float32)


def swiglu(u, w_gate, w_up, w_down, cast):
    gate = jax.nn.silu(_mm(u, w_gate, cast).astype(jnp.float32))
    up = _mm(u, w_up, cast).astype(jnp.float32)
    return _mm(gate * up, w_down, cast).astype(jnp.float32)


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


def routed_experts(c, u, w, bias, cast, first=None, count=None):
    """The held experts' part of the routed result of u [..., D]: experts
    ``first`` .. ``first + count`` of the published ones (the
    configuration's share unless given), whose matrices are ``w``'s."""
    held = c["stands_for"]["experts_held"]
    first = held["first"] if first is None else first
    count = w["w_up"].shape[0] if count is None else count
    top_w, top_e = route(c, u, w["router"], bias)

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


def _over_positions(fn, x, chunk):
    """fn over [B, chunk, ...] slices of x's position axis, recomputed in
    the backward pass; results stacked on a leading axis."""
    b, t = x[0].shape[:2]
    chunk = min(chunk, t)
    parts = tuple(
        jnp.moveaxis(a.reshape(b, t // chunk, chunk, *a.shape[2:]), 1, 0)
        for a in x)
    return lax.map(jax.checkpoint(lambda args: fn(*args)), parts)


def layer(c, x, w, bias, chunk, q_block, cast):
    """One pair; ``bias`` None says a leading dense layer."""
    b, t, d = x.shape
    eps = c["rms_norm_eps"]
    h = x + latent_attention(c, rmsnorm(x, w["attn_norm"], eps), w, q_block,
                             cast)

    def ffn(hc):
        u = rmsnorm(hc, w["mlp_norm"], eps)
        if bias is None:
            return hc + swiglu(u, w["w_gate"], w["w_up"], w["w_down"], cast)
        return (hc + routed_experts(c, u, w, bias, cast)
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
    nd = c["first_k_dense_replace"]
    biases = score_bias(c["num_hidden_layers"] - nd,
                        c["published"]["n_routed_experts"])
    x = params["embed"][tokens]

    @jax.checkpoint
    def pair(x, w_and_bias):
        w, bias = w_and_bias
        return layer(c, x, w, bias, chunk, q_block, cast), None

    # the leading dense layers, then the routed ones: each stack one loop
    x, _ = lax.scan(pair, x, (
        {k: params["d_" + k] for k in DENSE_KEYS}, None), length=nd)
    x, _ = lax.scan(pair, x, (
        {k: params[k] for k in ROUTED_KEYS}, jnp.asarray(biases)))
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
