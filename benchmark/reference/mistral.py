"""Plain reference of the Mistral decoder (Mistral 7B, arXiv:2310.06825, and
the public ``modeling_mistral`` description): float32 ``jax.numpy`` at
``highest`` matmul precision, no kernels, no cache, no sharding rules. It
imports nothing of the program.

  x = embed[tokens]
  per layer:  h = x + Wo . attention(rope(Wq . n1), rope(Wk . n1), Wv . n1)
              x = h + Wdown . (silu(Wgate . n2) * (Wup . n2))
  with n1 = rmsnorm(x), n2 = rmsnorm(h); grouped-query causal attention
  (query head h reads key/value head h // group), rotate-half RoPE, no
  sliding window (v0.3 and Codestral state none); logits = rmsnorm(x) . head,
  loss = mean next-token cross-entropy over the first T-1 positions.

Departures, all of layout and none of mathematics: weights are kept
(in, out) and stacked over layers on a leading axis, which one ``lax.scan``
walks; attention runs in blocks of queries and the feed-forward and the loss in blocks of positions,
each recomputed in the backward pass, so that full width fits one chip
beside float32 AdamW state.

Sizes are read from the published ``config.json`` keys.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
              "w_gate", "w_up", "w_down")


def param_shapes(c):
    """name -> (shape, std of the normal draw; None draws ones)."""
    d, n, hd = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    ff, v = c["intermediate_size"], c["vocab_size"]
    return {
        "embed": ((v, d), 1.0),
        "attn_norm": ((n, d), None),
        "wq": ((n, d, q), d ** -0.5),
        "wk": ((n, d, kv), d ** -0.5),
        "wv": ((n, d, kv), d ** -0.5),
        "wo": ((n, q, d), q ** -0.5),
        "mlp_norm": ((n, d), None),
        "w_gate": ((n, d, ff), d ** -0.5),
        "w_up": ((n, d, ff), d ** -0.5),
        "w_down": ((n, ff, d), ff ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d ** -0.5),
    }


def _mm(x, w, cast):
    return jnp.matmul(cast(x), cast(w), precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, T, heads, Dh]; rotate-half form, positions 0..T-1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_block, cast):
    """Causal grouped-query attention. q [B,T,KV,G,Dh], k, v [B,T,KV,Dh];
    one block of ``q_block`` queries at a time against every key."""
    b, t, kv, g, dh = q.shape
    q_block = min(q_block, t)
    nb = t // q_block
    qs = jnp.moveaxis(q.reshape(b, nb, q_block, kv, g, dh), 1, 0)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("bqkgd,btkd->bkgqt", cast(qi), cast(k),
                       precision=HIGHEST).astype(jnp.float32) * dh ** -0.5
        q_pos = i * q_block + jnp.arange(q_block)
        s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
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


def layer(c, x, w, chunk, q_block, cast):
    b, t, d = x.shape
    hd, kv = c["head_dim"], c["num_key_value_heads"]
    g = c["num_attention_heads"] // kv
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    n1 = rmsnorm(x, w["attn_norm"], eps)
    q = rope(_mm(n1, w["wq"], cast).astype(jnp.float32)
             .reshape(b, t, kv * g, hd), theta)
    k = rope(_mm(n1, w["wk"], cast).astype(jnp.float32)
             .reshape(b, t, kv, hd), theta)
    v = _mm(n1, w["wv"], cast).astype(jnp.float32).reshape(b, t, kv, hd)
    a = attention(q.reshape(b, t, kv, g, hd), k, v, q_block, cast)
    h = x + _mm(a, w["wo"], cast).astype(jnp.float32)

    def ffn(hc):
        n2 = rmsnorm(hc, w["mlp_norm"], eps)
        gate = jax.nn.silu(_mm(n2, w["w_gate"], cast).astype(jnp.float32))
        up = _mm(n2, w["w_up"], cast).astype(jnp.float32)
        return hc + _mm(gate * up, w["w_down"], cast).astype(jnp.float32)

    out = _over_positions(ffn, (h,), chunk)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def loss(c, params, batch, *, chips=1, chunk_tokens=2048, q_block=128,
         compute_dtype=jnp.float32):
    """Mean next-token cross-entropy of ``batch["tokens"]`` [B, T] (int32).
    ``chunk_tokens`` bounds the tokens a chip's block of the feed-forward
    or of the loss holds; ``compute_dtype`` below float32 is the control:
    matmul inputs rounded to it, everything else as here."""
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
    x, _ = lax.scan(
        jax.checkpoint(
            lambda x, w: (layer(c, x, w, chunk, q_block, cast), None)),
        x, {k: params[k] for k in LAYER_KEYS})
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
