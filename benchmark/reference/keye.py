"""Plain reference of Keye-VL-2.0's language model (``model_type:
KeyeVL2``), text only, from the keys of its public ``config.json``: float32
``jax.numpy`` at ``highest`` matmul precision, no kernels, no bits, no
grouped product, no sharding rules. It imports nothing of the program.

  x = embed[tokens]
  per layer:  h = x + attn(rmsnorm(x));  x = h + moe(rmsnorm(h))
  logits = rmsnorm(x) . head; loss = mean next-token cross-entropy over the
  first T-1 positions, over the held slice of the vocabulary, plus each
  layer's indexer loss. No bias; rmsnorm(x) = w x / sqrt(mean(x^2) + eps),
  eps = ``rms_norm_eps``.

  attn(n), H = num_attention_heads over KV = num_key_value_heads, D =
  head_dim:
      q = n W_q -> [T, H, D], k = n W_k, v = n W_v -> [T, KV, D]
      q <- rmsnorm(q, q_norm), k <- rmsnorm(k, k_norm), over each head's D
      M-RoPE: the D / 2 frequencies f_i = theta**(-2i / D) split by
          ``mrope_section`` among the time, height and width position ids
          (text: all three are the token's position), each half-pair (i,
          i + D / 2) turned by its section's id * f_i
      the indexer (``sa_config``), from m = n with its gradient stopped:
          qI = m W_qI -> [T, Hi, Di], kI = layernorm(m W_kI) -> [T, Di]
          (scale and bias, eps), both turned as q and k are, at the same
          theta, over their own Di / 2 frequencies;
          w = m W_w * Hi**-0.5 * Di**-0.5 -> [T, Hi]
          I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
      the selection: S_t = {s <= t : I[t, s] >= tau_t}, tau_t the topk-th
          largest of I[t, s <= t] (``lax.top_k``), -inf where t + 1 <= topk
      o_h = softmax over S_t of (q_h . k_s / sqrt(D)) . v_s, query head h
          reading key/value head h // (H / KV); out = concat_h(o_h) W_o
      the indexer's loss: p_t = mean over h of that softmax (stopped),
          L = mean over t of KL(p_t || softmax over S_t of I_t)

  moe(n2): p = softmax(n2 W_r) over all published experts; the
      num_experts_per_tok largest, divided by their sum (``norm_topk_prob``);
      out = sum_k p_k SwiGLU_e(n2) over those of the chosen that this chip
      holds (``stands_for.experts_held``), ``moe_intermediate_size`` wide,
      (silu(n2 W_g) * n2 W_u) W_d. What the absent experts would add is left
      out, here and in the program alike.

Departures, all of layout and none of mathematics: weights are kept (in,
out) and stacked on a leading axis over layers, one rolled loop
(``lax.scan``); attention, the indexer, the selection and its loss run in
blocks of queries against every key, a block's selection a mask on
positions; the experts are the Mellum 2 reference's plain loop over those
held, in blocks of positions; every block and every layer is recomputed in
the backward pass, so that the 16,384-token row fits one chip beside
float32 AdamW state.
"""

import jax
import jax.numpy as jnp
from jax import lax

from reference.mellum import _mm, _over_positions, moe, rmsnorm

HIGHEST = lax.Precision.HIGHEST
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "index_wq", "index_wk", "index_k_norm", "index_k_bias",
              "index_w", "mlp_norm", "router", "w_gate", "w_up", "w_down")


def param_shapes(c):
    """name -> (shape, std of the normal draw; None draws ones, 0 zeros)."""
    d, n, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    dh, f = c["head_dim"], c["moe_intermediate_size"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    held, published = c["num_experts"], c["published"]["num_experts"]
    return {
        "embed": ((v, d), 1.0),
        "attn_norm": ((n, d), None),
        "wq": ((n, d, q), d ** -0.5),
        "wk": ((n, d, kv), d ** -0.5),
        "wv": ((n, d, kv), d ** -0.5),
        "wo": ((n, q, d), q ** -0.5),
        "q_norm": ((n, dh), None),
        "k_norm": ((n, dh), None),
        "index_wq": ((n, d, hi * di), d ** -0.5),
        "index_wk": ((n, d, di), d ** -0.5),
        "index_k_norm": ((n, di), None),
        "index_k_bias": ((n, di), 0.0),
        "index_w": ((n, d, hi), d ** -0.5),
        "mlp_norm": ((n, d), None),
        "router": ((n, d, published), d ** -0.5),
        "w_gate": ((n, held, d, f), d ** -0.5),
        "w_up": ((n, held, d, f), d ** -0.5),
        "w_down": ((n, held, f, d), f ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d ** -0.5),
    }


def mrope(c, x):
    """x [B, T, heads, W]: M-RoPE over W / 2 frequency pairs, text ids (the
    token's position in each of the three sections)."""
    b, t, _, width = x.shape
    half = width // 2
    f = float(c["rope_theta"]) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    sections = c["rope_scaling"]["mrope_section"]
    scale = half / sum(sections)  # the indexer's head: the same split
    pos = jnp.arange(t, dtype=jnp.float32)
    ids = [pos, pos, pos]  # time, height, width: one id a token for text
    which = jnp.repeat(jnp.arange(3), jnp.array(
        [round(s * scale) for s in sections]), total_repeat_length=half)
    ang = jnp.stack(ids)[which].T * f[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layernorm(x, w, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * w + bias


def selected_attention(c, q, k, v, qi, ki, w, q_block, cast):
    """(out [B, T, H * D], the indexer's KL summed over queries). q
    [B,T,KV,G,D], k, v [B,T,KV,D]; qI [B,T,Hi,Di], kI [B,T,Di], w
    [B,T,Hi]; a block of ``q_block`` queries at a time against every key."""
    b, t, kvh, g, dh = q.shape
    topk = c["sa_config"]["topk"]
    q_block = min(q_block, t)
    while t % q_block:
        q_block -= 1
    nb = t // q_block
    split = lambda a: jnp.moveaxis(
        a.reshape(b, nb, q_block, *a.shape[2:]), 1, 0)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        i, qb, qib, wb = args
        q_pos = i * q_block + jnp.arange(q_block)
        causal = key_pos[None, :] <= q_pos[:, None]
        relu = jax.nn.relu(jnp.einsum("bqjd,btd->bqjt", cast(qib), cast(ki),
                                      precision=HIGHEST).astype(jnp.float32))
        score = jnp.einsum("bqj,bqjt->bqt", wb, relu, precision=HIGHEST)
        score = jnp.where(causal, score, -jnp.inf)
        kth = min(topk, t)
        tau = lax.top_k(score, kth)[0][..., kth - 1:kth]
        tau = jnp.where((q_pos < topk)[:, None], -jnp.inf, tau)
        chosen = causal & (score >= tau)  # [B, Q, T]
        s = jnp.einsum("bqkgd,btkd->bkgqt", cast(qb), cast(k),
                       precision=HIGHEST).astype(jnp.float32) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf),
                           axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", cast(p), cast(v),
                       precision=HIGHEST).astype(jnp.float32)
        target = lax.stop_gradient(jnp.mean(p, axis=(1, 2)))  # [B, Q, T]
        log_q = jax.nn.log_softmax(jnp.where(chosen, score, -jnp.inf),
                                   axis=-1)
        kl = jnp.where(chosen & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(chosen, log_q, 0.0)), 0.0)
        return o, jnp.sum(kl)

    o, kl = lax.map(block, (jnp.arange(nb), split(q), split(qi), split(w)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, kvh * g * dh), jnp.sum(kl)


def layer(c, x, w, chunk, q_block, cast):
    b, t, d = x.shape
    dh, kvh = c["head_dim"], c["num_key_value_heads"]
    g = c["num_attention_heads"] // kvh
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps = c["rms_norm_eps"]
    n1 = rmsnorm(x, w["attn_norm"], eps)
    q = _mm(n1, w["wq"], cast).astype(jnp.float32).reshape(b, t, kvh * g, dh)
    k = _mm(n1, w["wk"], cast).astype(jnp.float32).reshape(b, t, kvh, dh)
    v = _mm(n1, w["wv"], cast).astype(jnp.float32).reshape(b, t, kvh, dh)
    q = mrope(c, rmsnorm(q, w["q_norm"], eps))
    k = mrope(c, rmsnorm(k, w["k_norm"], eps))
    m = lax.stop_gradient(n1)
    qi = mrope(c, _mm(m, w["index_wq"], cast).astype(jnp.float32)
               .reshape(b, t, hi, di))
    ki = mrope(c, layernorm(
        _mm(m, w["index_wk"], cast).astype(jnp.float32), w["index_k_norm"],
        w["index_k_bias"], eps)[:, :, None])[:, :, 0]
    iw = _mm(m, w["index_w"], cast).astype(jnp.float32) * (
        hi ** -0.5 * di ** -0.5)
    a, kl = selected_attention(c, q.reshape(b, t, kvh, g, dh), k, v, qi, ki,
                               iw, q_block, cast)
    h = x + _mm(a, w["wo"], cast).astype(jnp.float32)

    def ffn(hc):
        return hc + moe(c, rmsnorm(hc, w["mlp_norm"], eps), w, cast)

    out = _over_positions(ffn, (h,), chunk)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d), kl / (b * t)


def loss(c, params, batch, *, chips=1, chunk_tokens=2048, q_block=128,
         compute_dtype=jnp.float32):
    """Mean next-token cross-entropy of ``batch["tokens"]`` [B, T] (int32)
    plus every layer's indexer loss. ``chunk_tokens`` bounds the tokens a
    chip's block of the feed-forward or of the loss holds;
    ``compute_dtype`` below float32 is the control: matmul inputs rounded to
    it (the router's stay float32, as the program's do), everything else
    as here."""
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

    @jax.checkpoint
    def one(x, w):
        return layer(c, x, w, chunk, q_block, cast)

    x, kls = lax.scan(one, x, {k: params[k] for k in LAYER_KEYS})
    x = rmsnorm(x, params["final_norm"], c["rms_norm_eps"])
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))

    def nll(xc, yc, mc):
        logits = _mm(xc, params["lm_head"], cast).astype(jnp.float32)
        ll = (jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
              - jax.nn.logsumexp(logits, axis=-1))
        return -jnp.sum(jnp.where(mc, ll, 0.0))

    ce = jnp.sum(_over_positions(nll, (x, targets, counted), chunk)) \
        / (b * (t - 1))
    return ce + jnp.sum(kls)
