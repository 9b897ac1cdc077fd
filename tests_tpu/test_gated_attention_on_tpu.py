"""The Laguna layer's attention on the chip at published widths: query
heads by kind (48 on a full layer, 64 on a window layer, over 8 KV heads of
128), the per-head sigmoid gate on attention's output, and half-width YaRN
rotation on the full layer against whole-head plain rotation on the window
layer of 512 keys. One dense pair through ``models/llama.py`` (the flash
kernels, bf16) against ``benchmark/reference/laguna.py``'s layer (float32 at
``highest``) on the same weights and tokens: the forward's features and the
gradients of the attention's matrices, and a program without the gate or
with the whole head rotated failing the same comparison.

    python -m pytest tests_tpu/test_gated_attention_on_tpu.py -q   # on a TPU
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from mpi_operator_tpu.models import llama

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))  # the plain references

from reference import laguna as reference  # noqa: E402
CONF = os.path.join(ROOT, "benchmark", "configs", "laguna-xs.2-l5-e32.json")
KINDS = {"full": ("full_attention", 48), "window": ("sliding_attention", 64)}


def _config(conf, kind):
    """The program's one dense pair of ``kind`` at the configuration's
    widths, its RoPE as the adapter states it."""
    full = conf["rope_parameters"]["full_attention"]
    return llama.Config(
        vocab=512, d_model=conf["hidden_size"], n_layers=1,
        n_heads=KINDS[kind][1], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        rope_theta=10_000.0, norm_eps=conf["rms_norm_eps"],
        layer_kinds=(kind,), window=conf["sliding_window"]
        if kind == "window" else None,
        attention_by_kind=(("full", llama.KindAttention(
            theta=float(full["rope_theta"]), rotated=int(
                conf["head_dim"] * full["partial_rotary_factor"]))),),
        head_gate=True,
        yarn_full=llama.Yarn(
            factor=float(full["factor"]),
            original_len=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=full["attention_factor"]))


def _weights(conf, kind, key):
    """The reference's dense-layer leaves (``d_*``) for one layer."""
    layer_type, heads = KINDS[kind]
    one = dict(conf, num_hidden_layers=1, layer_types=[layer_type],
               mlp_layer_types=["dense"], num_attention_heads_per_layer=[heads],
               vocab_size=512)
    shapes = reference.param_shapes(one)
    keys = jax.random.split(key, len(shapes))
    return {name: (jnp.ones(shape, jnp.float32) if std is None else
                   jax.random.normal(k, shape, jnp.float32) * std)
            for k, (name, (shape, std)) in zip(keys, sorted(shapes.items()))}


def _tree(w):
    pair = {k: {"w": w["d_" + k]} for k in
            ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")}
    pair.update(attn_norm={"scale": w["d_attn_norm"]},
                mlp_norm={"scale": w["d_mlp_norm"]})
    return {"embed": {"w": w["embed"]}, "layers": pair,
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": {"w": w["lm_head"]}}


def _features_against_reference(kind, t, cfg_change=None):
    """(relative RMS gap of the features, of each attention matrix's
    gradient): program (bf16, kernels where the chip has them) against the
    reference (float32, highest) for one pair of ``kind`` on t tokens."""
    with open(CONF) as f:
        conf = json.load(f)
    cfg = _config(conf, kind)
    if cfg_change:
        cfg = dataclasses.replace(cfg, **cfg_change)
    w = _weights(conf, kind, jax.random.PRNGKey(41))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, t), 0, 512)
    cot = jax.random.normal(jax.random.PRNGKey(6), (1, t, conf["hidden_size"]))
    layer_type, heads = KINDS[kind]

    def program(w):
        tree = _tree(w)
        if not cfg.head_gate:
            del tree["layers"]["wg"]
        out = llama.apply(cfg, tree, tokens, return_features=True)
        return out.astype(jnp.float32)

    def ref(w):
        with jax.default_matmul_precision("highest"):
            x = w["embed"][tokens]
            lw = {k: w["d_" + k][0] for k in reference.DENSE_KEYS}
            x = reference.layer(conf, x, lw, "d", layer_type, heads, t,
                                128, lambda a: a)
            return reference.rmsnorm(x, w["final_norm"], conf["rms_norm_eps"])

    def with_grads(fn):
        out, vjp = jax.vjp(fn, w)
        return out, vjp(cot)[0]

    got, g_got = jax.jit(lambda w: with_grads(program))(w)
    want, g_want = jax.jit(lambda w: with_grads(ref))(w)
    rel = lambda a, b: float(jnp.sqrt(jnp.mean((a - b) ** 2))
                             / jnp.sqrt(jnp.mean(b ** 2)))
    return rel(got, want), {
        k: rel(g_got["d_" + k], g_want["d_" + k])
        for k in ("wq", "wk", "wv", "wg", "wo")}


@pytest.mark.parametrize("kind", ["full", "window"])
def test_gated_heads_by_kind_match_the_reference(kind):
    """At 8192 tokens: bf16 operands and a bf16 residual against float32,
    one layer: the features' gap is a few thousandths (2e-2 is some five
    times that), a gradient's under 3e-2 (products summed over 8192
    positions, rounded to bf16 before and after)."""
    feats, grads = _features_against_reference(kind, 8192)
    assert feats < 2e-2, feats
    assert all(g < 3e-2 for g in grads.values()), grads


@pytest.mark.parametrize("kind,change", [
    ("full", dict(head_gate=False)),
    ("window", dict(head_gate=False)),
    # the full layer with its whole head turned: the other 64 dims move
    ("full", dict(attention_by_kind=(
        ("full", llama.KindAttention(theta=500_000.0)),))),
])
def test_a_program_without_the_gate_or_the_half_rotation_fails_it(kind,
                                                                  change):
    """Twice the limit above at least (at 1024 tokens on the CPU the sound
    program read 4.7e-3, these 0.08 to 0.16)."""
    feats, _ = _features_against_reference(kind, 8192, change)
    assert feats > 4e-2, feats
