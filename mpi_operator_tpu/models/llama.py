"""Llama-family decoder: the one model the training jobs of this repo
run, at whatever widths a ``Config`` states (``Config()`` is Llama-3-8B;
the benchmark's cells state Mistral-7B's and Mellum2's).

The reference has no LLM workload — its examples top out at CNN scale
(SURVEY.md §2.6). TPU-native design:

- scan-over-layers: all layer params stacked on a leading axis and the
  decoder body is one ``lax.scan`` — O(1) HLO size regardless of depth,
  which is what keeps 32-layer compile times sane on TPU;
- bf16 compute, f32 params/optimizer;
- GQA (grouped-query attention) with RoPE; K/V heads expanded to Q heads
  only at the attention call;
- long context via parallel/ring_attention.py when the mesh has a
  ``sequence`` axis — RoPE and norms operate on global [B,T,D] arrays (XLA
  global-view), only the attention inner loop is manually ring-scheduled;
- logical-axis pytree drives DP/FSDP/TP/SP resharding with zero model edits;
- one decoder for every family it runs: the scan walks *periods* of layers,
  and the kinds inside a period (``Config.layer_kinds``) are unrolled in the
  scan's body, so a model of one kind compiles to the plain scan over
  layers. A layer is a *pair* or a *single mixer*. A pair (``full``,
  ``window``, ``latent``) is attention then a feed-forward, each behind its
  own norm and residual: full causal attention or a sliding window of keys,
  each with its own RoPE, or multi-head latent attention (keys and values
  expanded from one normalised low-rank latent a token, a rotated part of
  the key that all heads share, keys and queries wider than values; its own
  parameter tree); the feed-forward dense (SwiGLU) or routed
  (``Config.n_experts`` > 0: parallel/moe.py's share of the experts).
  ``Config.n_dense_layers`` pairs in front of a routed model's periods keep
  the dense feed-forward, ``d_ff`` wide: a stack of their own
  (``params["lead"]``), scanned before the periods, so the program is two
  small scans whatever the depth. A single mixer (``mamba``,
  ``experts``, ``attention``) is ``x + mixer(rmsnorm(x))`` and nothing else:
  a Mamba-2 state-space layer (models/mamba2.py), a routed layer alone, or
  causal attention with no positional embedding (position comes through the
  state-space layers). Pairs share one parameter tree, stacked over layers;
  single mixers have unlike trees, so theirs stack by kind
  (``params["layers"][kind]``) and a period's body takes the next layer of
  each kind as it walks the pattern. What a model is follows from its
  configuration's shape; there is no switch.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi_operator_tpu.models import mamba2
from mpi_operator_tpu.parallel import moe
from mpi_operator_tpu.parallel.ring_attention import (
    dense_attention,
    ring_attention,
)
from mpi_operator_tpu.parallel.sharding import (
    with_logical_constraint,
    with_logical_constraint_fwd,
)
from mpi_operator_tpu.runtime.topology import AXIS_SEQ

Params = Dict[str, Any]


PAIR_KINDS = ("full", "window", "latent")  # attention, then a feed-forward
MIXER_KINDS = ("mamba", "experts", "attention")  # one mixer a layer
LAYER_KINDS = PAIR_KINDS + MIXER_KINDS


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN scaling of a RoPE table (arXiv:2309.00071, as the public
    ``rope_type: yarn`` configurations state it): frequencies whose
    wavelength exceeds the original context are divided by ``factor``, those
    well inside it are kept, with a linear ramp between the dimensions at
    which ``beta_fast`` and ``beta_slow`` rotations fit the original
    context; cos and sin are multiplied by ``attention_factor``. Here the
    factor's square is folded into the scores' scale instead, in float32:
    the same scores, and no rounding of the factor into a bf16 table (1.277
    is no bf16 number: the table's cos and sin would come out 0.3 % low at
    every position of the slow dimensions, all to one side)."""

    factor: float
    original_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class Config:
    vocab: int = 128_256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 14_336
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    # "auto": flash_attention whenever the sequence isn't ring-sharded — the
    # compiled Pallas kernel on TPU, the memory-bounded chunked XLA lowering
    # on other backends (never the dense [T,T] matrix, which OOMs at
    # production sequence lengths). "dense" forces the quadratic oracle
    # (tests/small cases only); "flash" forces the kernel path. A sharded
    # sequence axis always takes the ring — the only exact option there.
    attention_impl: str = "auto"
    # FFN matmul precision (ISSUE 16): "bf16" is the exact baseline; "int8"
    # / "fp8" route w_gate/w_up/w_down (~2/3 of model FLOPs) through
    # kernels.quant_matmul — dynamically quantized forward on the MXU's
    # narrow-dtype tier, full-precision straight-through backward.
    # Attention and the lm_head stay bf16: they are numerically the
    # touchiest matmuls and a minority of the FLOPs.
    matmul_precision: str = "bf16"
    # checkpoint each scan layer: backward stores only the 12-layer stack of
    # [B,T,D] layer inputs instead of every intra-layer intermediate — the
    # remat that actually bounds peak HBM for deep stacks (a whole-loss
    # jax.checkpoint would not: its backward recomputation re-materializes
    # all layer intermediates at once)
    remat_layers: bool = False
    # one period of the layer pattern. Pairs, by their attention: "full"
    # (causal), "window" (query i sees the ``window`` keys up to its own)
    # or "latent" (causal, keys and values from a latent: below). Or single
    # mixers: "mamba", "experts" (the routed layer alone),
    # "attention" (causal, no positional embedding). One or the other, not
    # both in one pattern. The stack is ``n_dense_layers`` leading pairs,
    # then (n_layers - n_dense_layers) / len(layer_kinds) periods.
    layer_kinds: Tuple[str, ...] = ("full",)
    window: Optional[int] = None
    # leading pairs of a routed model whose feed-forward is the dense one
    # (``d_ff`` wide), with the attention of the (one) pair kind
    n_dense_layers: int = 0
    # latent attention: a head's query and key are ``qk_nope_dim`` numbers
    # without position and ``qk_rope_dim`` rotated ones (the key's rotated
    # part is one for all heads), its value ``v_head_dim``; keys without
    # position and values are expanded from a normalised latent of
    # ``kv_lora_rank`` a token. ``head_dim`` and ``n_kv_heads`` are unused
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # RoPE by kind: window layers rotate at the plain ``rope_theta``, full
    # layers too unless there is a Yarn for them
    yarn_full: Optional[Yarn] = None
    # routed feed-forward (n_experts > 0; ``d_ff`` is then the leading
    # dense layers' width, unused without them): the
    # router scores ``n_experts`` published experts and keeps the
    # ``experts_per_token`` largest; this program holds ``n_experts_held``
    # of them (0: all), ``first_expert`` on, each ``d_expert`` wide
    n_experts: int = 0
    n_experts_held: int = 0
    first_expert: int = 0
    experts_per_token: int = 0
    d_expert: int = 0
    # the routed layer's form (parallel/moe.py): the score function
    # ("softmax", or "sigmoid" with a correction bias in the choice), a
    # factor on the weights, gated SwiGLU experts or ungated relu ** 2 ones,
    # and the width of a shared expert (0: none; of the experts' form)
    router_score: str = "softmax"
    router_scale: float = 1.0
    experts_gated: bool = True
    d_shared: int = 0
    # the state-space layers (models/mamba2.py): heads and their size,
    # groups that share B and C, the state's size, the convolution's
    # kernel and the scan's chunk (a sequence is whole chunks)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    conv_kernel: int = 4
    ssm_chunk: int = 128

    def __post_init__(self):
        if self.attention_impl not in ("auto", "dense", "flash"):
            raise ValueError(
                f"attention_impl={self.attention_impl!r}; "
                "expected auto|dense|flash"
            )
        if self.matmul_precision not in ("bf16", "int8", "fp8"):
            raise ValueError(
                f"matmul_precision={self.matmul_precision!r}; "
                "expected bf16|int8|fp8"
            )
        kinds = self.layer_kinds
        if not kinds or any(k not in LAYER_KINDS for k in kinds):
            raise ValueError(f"layer_kinds={kinds!r}; each of {LAYER_KINDS}")
        if len({k in MIXER_KINDS for k in kinds}) != 1:
            raise ValueError(
                f"layer_kinds={kinds!r}: pairs {PAIR_KINDS} or single "
                f"mixers {MIXER_KINDS}, not both in one pattern")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score={self.router_score!r}; "
                             "expected softmax|sigmoid")
        if "experts" in kinds and not self.n_experts:
            raise ValueError("an 'experts' layer needs n_experts")
        if "mamba" in kinds and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0
                and self.ssm_state > 0 and self.ssm_chunk > 0
                and self.conv_kernel > 0 and self.ssm_groups > 0
                and self.ssm_heads % self.ssm_groups == 0):
            raise ValueError(
                f"state-space layers: {self.ssm_heads} heads of "
                f"{self.ssm_head_dim} in {self.ssm_groups} groups, state "
                f"{self.ssm_state}, kernel {self.conv_kernel}, chunk "
                f"{self.ssm_chunk}")
        if "latent" in kinds and not (
                self.kv_lora_rank > 0 and self.qk_nope_dim > 0
                and self.v_head_dim > 0 and self.qk_rope_dim > 0
                and self.qk_rope_dim % 2 == 0):
            raise ValueError(
                f"latent attention: a latent of {self.kv_lora_rank}, keys "
                f"of {self.qk_nope_dim} + {self.qk_rope_dim} (an even "
                f"number rotated), values of {self.v_head_dim}")
        if self.n_dense_layers and not (
                self.routed and len(set(kinds)) == 1
                and kinds[0] in PAIR_KINDS):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers}: leading dense "
                "pairs stand in front of a routed model's periods of one "
                f"pair kind (layer_kinds={kinds!r}, n_experts="
                f"{self.n_experts})")
        periods, rest = divmod(self.n_layers - self.n_dense_layers,
                               len(kinds))
        if periods < 1 or rest or self.n_dense_layers < 0:
            raise ValueError(
                f"n_layers={self.n_layers} is no whole number of periods "
                f"of {len(kinds)} layers behind {self.n_dense_layers} "
                "leading dense ones")
        if ("window" in kinds) != (self.window is not None):
            raise ValueError(
                "window layers need a window, and a window needs them: "
                f"layer_kinds={kinds!r}, window={self.window!r}")
        if self.n_experts:
            held = self.n_experts_held or self.n_experts
            if not (0 < self.experts_per_token <= self.n_experts
                    and self.d_expert > 0 and self.first_expert >= 0
                    and self.first_expert + held <= self.n_experts):
                raise ValueError(
                    f"routed feed-forward: {self.experts_per_token} of "
                    f"{self.n_experts} experts a token, {held} held from "
                    f"{self.first_expert} on, {self.d_expert} wide")

    @property
    def routed(self) -> bool:
        return self.n_experts > 0

    @property
    def single_mixers(self) -> bool:
        return self.layer_kinds[0] in MIXER_KINDS

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def latent(self) -> bool:
        return "latent" in self.layer_kinds

    @property
    def qk_head_dim(self) -> int:
        """What a query meets a key over."""
        return (self.qk_nope_dim + self.qk_rope_dim if self.latent
                else self.head_dim)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def llama3_8b() -> Config:
    return Config()


def bench_single_chip() -> Config:
    """Llama-3-architecture decoder (~0.79B params) sized so AdamW training
    fits one 16 GiB v5e chip: every matmul dim a multiple of 128 (MXU tiles),
    GQA 4:1, d_ff = 3.5x like the 8B config. What ``chip_smoke.py`` and
    the example worker's ``LLAMA_CONFIG=bench`` run."""
    return Config(
        vocab=32_768, d_model=2048, n_layers=12, n_heads=16, n_kv_heads=4,
        head_dim=128, d_ff=7168, remat_layers=True,
    )


def tiny(vocab: int = 256) -> Config:
    """Test-scale config with the same architecture (GQA ratio included)."""
    return Config(
        vocab=vocab, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, rope_theta=10_000.0,
    )


def tiny_routed(vocab: int = 256) -> Config:
    """Test-scale config of the routed, mixed-attention shape: a period of
    three window layers and one full one (YaRN on the full one), 8 experts
    of which 2 a token and the first 4 held, q_dim != d_model."""
    return Config(
        vocab=vocab, d_model=48, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, rope_theta=10_000.0, norm_eps=1e-6,
        layer_kinds=("window", "window", "window", "full"), window=8,
        yarn_full=Yarn(factor=4.0, original_len=16, attention_factor=1.1),
        n_experts=8, n_experts_held=4, experts_per_token=2, d_expert=32,
    )


def tiny_hybrid(vocab: int = 256) -> Config:
    """Test-scale config of the single-mixer shape: a period holding all
    three kinds (state-space, routed with a shared expert, attention without
    positions), sigmoid routing over 8 experts of which 2 a token and the
    first 4 held, 4 state-space heads in 2 groups, chunks of 8."""
    return Config(
        vocab=vocab, d_model=48, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=16, norm_eps=1e-5,
        layer_kinds=("mamba", "experts", "mamba", "attention", "experts"),
        n_experts=8, n_experts_held=4, experts_per_token=2, d_expert=24,
        router_score="sigmoid", router_scale=2.5, experts_gated=False,
        d_shared=40, ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
        ssm_chunk=8,
    )


def tiny_latent(vocab: int = 256) -> Config:
    """Test-scale config of the latent-attention shape: one leading dense
    pair, then two routed ones; keys and queries 24 wide (16 + 8 rotated),
    values 16, a latent of 32; sigmoid routing over 8 gated experts of
    which 2 a token and the first 4 held, beside a gated shared expert."""
    return Config(
        vocab=vocab, d_model=48, n_layers=3, n_heads=4, d_ff=96,
        rope_theta=10_000.0, norm_eps=1e-6, layer_kinds=("latent",),
        n_dense_layers=1, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, n_experts=8, n_experts_held=4, experts_per_token=2,
        d_expert=24, router_score="sigmoid", router_scale=2.5, d_shared=48,
    )


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _moe_form(c: Config) -> Dict[str, bool]:
    """What of the routed layer's optional parts this configuration has."""
    return {"gated": c.experts_gated,
            "score_bias": c.router_score == "sigmoid"}


def _init_attention(c: Config, keys, lead=()) -> Params:
    d = c.d_model
    return {
        "attn_norm": {"scale": jnp.ones((*lead, d), jnp.float32)},
        "wq": {"w": _normal(keys[0], (*lead, d, c.q_dim), d**-0.5)},
        "wk": {"w": _normal(keys[1], (*lead, d, c.kv_dim), d**-0.5)},
        "wv": {"w": _normal(keys[2], (*lead, d, c.kv_dim), d**-0.5)},
        "wo": {"w": _normal(keys[3], (*lead, c.q_dim, d), c.q_dim**-0.5)},
    }


def _init_latent(c: Config, keys, lead=()) -> Params:
    """Latent attention's tree: the query's projection, the projection down
    to the latent and the shared rotated key, the latent's norm, the
    expansion to every head's key without position and value, the output's
    projection. Projections std fan_in**-0.5, as the others."""
    d, h, r = c.d_model, c.n_heads, c.kv_lora_rank
    up = h * (c.qk_nope_dim + c.v_head_dim)
    return {
        "attn_norm": {"scale": jnp.ones((*lead, d), jnp.float32)},
        "wq": {"w": _normal(keys[0], (*lead, d, h * c.qk_head_dim), d**-0.5)},
        "wkv_a": {"w": _normal(keys[1], (*lead, d, r + c.qk_rope_dim),
                               d**-0.5)},
        "kv_a_norm": {"scale": jnp.ones((*lead, r), jnp.float32)},
        "wkv_b": {"w": _normal(keys[2], (*lead, r, up), r**-0.5)},
        "wo": {"w": _normal(keys[3], (*lead, h * c.v_head_dim, d),
                            (h * c.v_head_dim)**-0.5)},
    }


def _init_pairs(c: Config, lk, n: int, routed: bool) -> Params:
    """``n`` pairs stacked on axis 0 (``lax.scan`` walks the leading axis):
    the attention's leaves, the feed-forward's norm, and every layer's
    router and held experts where ``routed``, else the dense SwiGLU."""
    d = c.d_model
    if routed:
        feed_forward = jax.vmap(lambda k: _init_routed(c, k))(
            jax.random.split(lk[4], n))
    else:
        feed_forward = {
            "w_gate": {"w": _normal(lk[4], (n, d, c.d_ff), d**-0.5)},
            "w_up": {"w": _normal(lk[5], (n, d, c.d_ff), d**-0.5)},
            "w_down": {"w": _normal(lk[6], (n, c.d_ff, d), c.d_ff**-0.5)},
        }
    attention = _init_latent if c.latent else _init_attention
    return {
        **attention(c, lk, lead=(n,)),
        "mlp_norm": {"scale": jnp.ones((n, d), jnp.float32)},
        **feed_forward,
    }


def _init_routed(c: Config, key) -> Params:
    return moe.init(
        key, d_model=c.d_model, d_expert=c.d_expert, n_experts=c.n_experts,
        n_held=c.experts_held, d_shared=c.d_shared, **_moe_form(c))


def _init_mixer(c: Config, kind: str, key) -> Params:
    """One single-mixer layer's tree: the mixer's weights and its norm."""
    if kind == "mamba":
        return mamba2.init(key, c)
    if kind == "attention":
        return _init_attention(c, jax.random.split(key, 4))
    return {"mlp_norm": {"scale": jnp.ones((c.d_model,), jnp.float32)},
            **_init_routed(c, key)}


def _per_kind(c: Config) -> Dict[str, int]:
    """How many layers of each kind the whole stack has."""
    periods = c.n_layers // len(c.layer_kinds)
    return {kind: c.layer_kinds.count(kind) * periods
            for kind in sorted(set(c.layer_kinds))}


def init(config: Config, key) -> Params:
    c = config
    ke, kl, kh = jax.random.split(key, 3)
    lk = jax.random.split(kl, 7)
    d = c.d_model
    s_d = d**-0.5
    if c.single_mixers:
        # unlike trees: each kind's layers stacked on an axis of their own
        layers = {
            kind: jax.vmap(lambda k, kind=kind: _init_mixer(c, kind, k))(
                jax.random.split(lk[i], count))
            for i, (kind, count) in enumerate(_per_kind(c).items())}
    else:
        layers = _init_pairs(c, lk, c.n_layers - c.n_dense_layers, c.routed)
    params = {
        "embed": {"w": _normal(ke, (c.vocab, d), 1.0)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "lm_head": {"w": _normal(kh, (d, c.vocab), s_d)},
    }
    if c.n_dense_layers:  # a stack of their own, in front of the periods
        params["lead"] = _init_pairs(
            c, jax.random.split(jax.random.fold_in(kl, 7), 7),
            c.n_dense_layers, routed=False)
    return params


_ATTENTION_AXES = {
    "attn_norm": {"scale": ("stats",)},
    "wq": {"w": ("embed", "heads")},
    "wk": {"w": ("embed", "kv_heads")},
    "wv": {"w": ("embed", "kv_heads")},
    "wo": {"w": ("heads", "embed")},
}


# latent attention: the latent's own axis is spread like the model's
_LATENT_AXES = {
    "attn_norm": {"scale": ("stats",)},
    "wq": {"w": ("embed", "heads")},
    "wkv_a": {"w": ("embed", None)},
    "kv_a_norm": {"scale": ("stats",)},
    "wkv_b": {"w": ("latent", "heads")},
    "wo": {"w": ("heads", "embed")},
}
_DENSE_AXES = {
    "mlp_norm": {"scale": ("stats",)},
    "w_gate": {"w": ("embed", "mlp")},
    "w_up": {"w": ("embed", "mlp")},
    "w_down": {"w": ("mlp", "embed")},
}


def logical_axes(config: Config) -> Params:
    c = config
    routed_axes = lambda: {
        "mlp_norm": {"scale": ("stats",)},
        **moe.logical_axes(shared=c.d_shared > 0, **_moe_form(c))}
    attention_axes = _LATENT_AXES if c.latent else _ATTENTION_AXES
    if c.single_mixers:
        by_kind = {"mamba": mamba2.logical_axes,
                   "attention": lambda: _ATTENTION_AXES,
                   "experts": routed_axes}
        layers = {kind: by_kind[kind]() for kind in _per_kind(c)}
    else:
        layers = {**attention_axes,
                  **(routed_axes() if c.routed else _DENSE_AXES)}
    # the leading stack axis is always replicated (None)
    stacked = lambda tree: jax.tree.map(
        lambda axes: (None, *axes), tree,
        is_leaf=lambda x: isinstance(x, tuple))
    axes = {
        "embed": {"w": ("vocab", "embed")},
        "layers": stacked(layers),
        "final_norm": {"scale": ("stats",)},
        "lm_head": {"w": ("embed", "vocab")},
    }
    if c.n_dense_layers:
        axes["lead"] = stacked({**attention_axes, **_DENSE_AXES})
    return axes


def _rmsnorm32(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale


def _rmsnorm(x, scale, eps):
    return _rmsnorm32(x, scale, eps).astype(x.dtype)


def yarn_ramp(dh: int, theta: float, yarn: Yarn):
    """(low, high): the dimensions between which YaRN's ramp runs.
    ``dim(r) = dh * ln(original_len / (2 pi r)) / (2 ln theta)`` is the
    dimension whose frequency turns ``r`` times within the original
    context; low = floor(dim(beta_fast)), high = ceil(dim(beta_slow))."""
    dim = lambda r: (dh * math.log(yarn.original_len / (2 * math.pi * r))
                     / (2 * math.log(theta)))
    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), dh - 1)
    return low, high


def rope_frequencies(dh: int, theta: float, yarn: Optional[Yarn] = None):
    """The dh/2 rotation frequencies, YaRN-scaled where there is one."""
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn is None:
        return freqs
    low, high = yarn_ramp(dh, theta, yarn)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0)
    return freqs * ((1.0 - ramp) + ramp / yarn.factor)


def _rope_tables(t, dh, theta, dtype, yarn=None):
    """cos/sin rotation tables [T, Dh/2] for global positions 0..T-1
    (arrays are global-view; sequence sharding is XLA's problem, not
    RoPE's). Shared by both layout variants so the math can never drift."""
    freqs = rope_frequencies(dh, theta, yarn)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def _rotate(x, cos, sin):
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _rope(x, theta, yarn=None):
    """RoPE, model layout: x [B,T,H,Dh], positions along axis 1."""
    cos, sin = _rope_tables(x.shape[1], x.shape[-1], theta, x.dtype, yarn)
    return _rotate(x, cos[None, :, None, :], sin[None, :, None, :])


def _rope_bhtd(x, theta, yarn=None):
    """RoPE, kernel heads-major layout: x [B,H,T,Dh], positions along
    axis 2 (same tables, different broadcast)."""
    cos, sin = _rope_tables(x.shape[2], x.shape[-1], theta, x.dtype, yarn)
    return _rotate(x, cos[None, None], sin[None, None])


def apply(
    config: Config,
    params: Params,
    tokens,
    *,
    mesh=None,
    rules=None,
    return_features=False,
) -> jnp.ndarray:
    """tokens [B,T] int32 → logits [B,T,vocab] f32 (or the final-norm
    features [B,T,d_model] with ``return_features`` — the long-context
    loss applies the lm_head blockwise instead).

    With a mesh that has a ``sequence`` axis, attention runs as ring
    attention over ICI; otherwise dense causal attention. All other ops are
    global-view and sharded by constraint propagation."""
    return _forward(config, params, tokens, mesh=mesh, rules=rules,
                    return_features=return_features)[0]


def _forward(config, params, tokens, *, mesh, rules, return_features):
    """:func:`apply`, and beside its result the router's counters of each
    layer ({name: one value a layer}), empty for a dense model."""
    c = config
    dt = c.compute_dtype

    def constrain(x, axes):
        if mesh is None:
            return x
        return with_logical_constraint(x, axes, rules=rules, mesh=mesh)

    def constrain_fwd(x, axes):
        # forward-only at activation boundaries: the cotangent arrives
        # sharded by the weight layout (d_model over fsdp); forcing the
        # batch-sharded primal spec onto it makes the partitioner fall back
        # to replicate-then-repartition (involuntary full remat)
        if mesh is None:
            return x
        return with_logical_constraint_fwd(x, axes, rules=rules, mesh=mesh)

    # gather from a table laid out for lookup: vocab stays tensor-sharded
    # (XLA's TP-embedding gather + psum), the embed dim is gathered over
    # fsdp VOLUNTARILY here — otherwise the partitioner reshards the gather
    # output [.,.,fsdp] → [batch-sharded] by full rematerialization
    # the named scopes (embed, attention, mlp, head_loss) are metadata in
    # each operation's `op_name`: a device trace reads time by scope
    with jax.named_scope("embed"):
        emb = constrain(params["embed"]["w"].astype(dt), ["vocab", None])
        x = emb[tokens]
        x = constrain_fwd(x, ["batch", "seq", "embed"])

    seq_sharded = (
        mesh is not None
        and AXIS_SEQ in mesh.axis_names
        and mesh.shape[AXIS_SEQ] > 1
    )
    if seq_sharded and c.window is not None:
        raise ValueError(
            "a window needs the whole sequence on one chip: the ring "
            "(mesh axis 'sequence') has no band")
    if seq_sharded and c.latent:
        raise ValueError(
            "latent attention needs the whole sequence on one chip: the "
            "ring (mesh axis 'sequence') carries keys and values of one "
            "head size")

    def latent_attention(h, lp):
        """Multi-head latent attention: ``[c | k_rot] = y W_kva``, keys
        without position and values expanded from ``rmsnorm(c)``, the
        key's rotated part one for all heads, RoPE over the rotated
        parts alone (half-split pairs, as :func:`_rotate`), scores over
        the concatenated ``qk_head_dim`` scaled by its root."""
        y = _rmsnorm(h, lp["attn_norm"]["scale"], c.norm_eps)
        b, t, _ = y.shape
        nope, rot, r = c.qk_nope_dim, c.qk_rope_dim, c.kv_lora_rank
        # heads-major end to end, as the other kinds: the transposes fold
        # into the products
        with jax.named_scope("latent_q"):
            q = jnp.einsum("btd,dhx->bhtx", y, lp["wq"]["w"].astype(dt)
                           .reshape(-1, c.n_heads, nope + rot))
        with jax.named_scope("latent_kv_down"):
            down = y @ lp["wkv_a"]["w"].astype(dt)  # [B, T, r + rot]
            latent = _rmsnorm(down[..., :r], lp["kv_a_norm"]["scale"],
                              c.norm_eps)
        with jax.named_scope("latent_kv_up"):
            # two products, so that the values are an array of their own
            # and not a slice the kernel would need copied out
            wkv3 = lp["wkv_b"]["w"].astype(dt).reshape(
                r, c.n_heads, nope + c.v_head_dim)
            k_nope = jnp.einsum("btr,rhx->bhtx", latent, wkv3[..., :nope])
            v = jnp.einsum("btr,rhx->bhtx", latent, wkv3[..., nope:])
        with jax.named_scope("latent_rope"):
            cos, sin = _rope_tables(t, rot, c.rope_theta, dt)
            q = jnp.concatenate(
                [q[..., :nope], _rotate(q[..., nope:], cos, sin)], axis=-1)
            k_rot = _rotate(down[..., r:], cos, sin)  # [B, T, rot]
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rot[:, None], (b, c.n_heads, t, rot))], axis=-1)
        scale = c.qk_head_dim**-0.5
        if c.attention_impl != "dense":
            from mpi_operator_tpu.kernels import flash_attention

            attn = flash_attention(q, k, v, causal=True, scale=scale,
                                   mesh=mesh, layout="bhtd")
        else:
            attn = dense_attention(
                *(a.transpose(0, 2, 1, 3) for a in (q, k, v)), causal=True,
                scale=scale).transpose(0, 2, 1, 3)
        with jax.named_scope("latent_out"):
            h = h + jnp.einsum(
                "bhtx,hxd->btd", attn, lp["wo"]["w"].astype(dt).reshape(
                    c.n_heads, c.v_head_dim, -1))
        return constrain_fwd(h, ["batch", "seq", "embed"])

    def attention(h, lp, kind):
        if kind == "latent":
            return latent_attention(h, lp)
        y = _rmsnorm(h, lp["attn_norm"]["scale"], c.norm_eps)
        b, t, _ = y.shape
        # K/V stay at n_kv_heads: every attention path is GQA-aware, so the
        # ring never carries expanded K/V
        window = c.window if kind == "window" else None
        yarn = c.yarn_full if kind == "full" else None
        # the single mixer "attention" has no positional embedding
        rotates = kind != "attention"
        # YaRN's factor on cos and sin of q and k alike is its square on
        # their product
        scale = c.head_dim**-0.5 * (yarn.attention_factor**2 if yarn else 1)
        use_flash = not seq_sharded and c.attention_impl != "dense"
        if use_flash:
            from mpi_operator_tpu.kernels import flash_attention

            # heads-major end to end: project straight into the kernel's
            # [B,H,T,Dh] layout via einsum (the transpose folds into the
            # matmul) and fold the attention output into wo the same way —
            # no standalone [B,T,H,D]↔[B,H,T,D] copies around the kernel.
            # auto/flash: the kernel on TPU, chunked XLA elsewhere; mesh
            # passed through (the pallas call is not SPMD-partitionable).
            wq3 = lp["wq"]["w"].astype(dt).reshape(-1, c.n_heads, c.head_dim)
            wk3 = lp["wk"]["w"].astype(dt).reshape(-1, c.n_kv_heads, c.head_dim)
            wv3 = lp["wv"]["w"].astype(dt).reshape(-1, c.n_kv_heads, c.head_dim)
            rope = (lambda a: _rope_bhtd(a, c.rope_theta, yarn)) if rotates \
                else (lambda a: a)
            q = rope(jnp.einsum("btd,dhx->bhtx", y, wq3))
            k = rope(jnp.einsum("btd,dhx->bhtx", y, wk3))
            v = jnp.einsum("btd,dhx->bhtx", y, wv3)
            attn = flash_attention(
                q, k, v, causal=True, scale=scale, mesh=mesh,
                layout="bhtd", window=window,
            )
            wo3 = lp["wo"]["w"].astype(dt).reshape(c.n_heads, c.head_dim, -1)
            h = h + jnp.einsum("bhtx,hxd->btd", attn, wo3)
        else:
            q = (y @ lp["wq"]["w"].astype(dt)).reshape(b, t, c.n_heads, c.head_dim)
            k = (y @ lp["wk"]["w"].astype(dt)).reshape(b, t, c.n_kv_heads, c.head_dim)
            v = (y @ lp["wv"]["w"].astype(dt)).reshape(b, t, c.n_kv_heads, c.head_dim)
            if rotates:
                q = _rope(q, c.rope_theta, yarn)
                k = _rope(k, c.rope_theta, yarn)
            if seq_sharded:
                # ring attention: the only exact option over a sharded sequence
                attn = ring_attention(q, k, v, mesh, causal=True, scale=scale)
            else:
                attn = dense_attention(
                    q, k, v, causal=True, scale=scale, window=window,
                )
            attn = attn.reshape(b, t, c.q_dim)
            h = h + attn @ lp["wo"]["w"].astype(dt)
        return constrain_fwd(h, ["batch", "seq", "embed"])

    def mlp(h, lp):
        y = _rmsnorm(h, lp["mlp_norm"]["scale"], c.norm_eps)
        if c.matmul_precision == "bf16":
            gate = jax.nn.silu(y @ lp["w_gate"]["w"].astype(dt))
            up = y @ lp["w_up"]["w"].astype(dt)
            h = h + (gate * up) @ lp["w_down"]["w"].astype(dt)
        else:
            # quantized FFN (config-gated): forward contraction on the
            # int8/fp8 MXU tier, backward full-precision (custom_vjp in
            # kernels.quant_matmul — the straight-through estimator)
            from mpi_operator_tpu.kernels.quant_matmul import quant_matmul

            mp = c.matmul_precision
            gate = jax.nn.silu(
                quant_matmul(y, lp["w_gate"]["w"].astype(dt), precision=mp)
            )
            up = quant_matmul(y, lp["w_up"]["w"].astype(dt), precision=mp)
            h = h + quant_matmul(
                gate * up, lp["w_down"]["w"].astype(dt), precision=mp
            )
        return constrain_fwd(h, ["batch", "seq", "embed"])

    def routed(h, lp):
        # the router reads the norm's float32 result, the experts its
        # rounding to the compute dtype: top-k is a discontinuous choice
        y32 = _rmsnorm32(h, lp["mlp_norm"]["scale"], c.norm_eps)
        out, counters = moe.apply(
            {k: v for k, v in lp.items() if not k.endswith("_norm")},
            y32.astype(h.dtype), router_in=y32,
            experts_per_token=c.experts_per_token,
            first_expert=c.first_expert, router_scale=c.router_scale,
            compute_dtype=dt,
            matmul_precision=c.matmul_precision, mesh=mesh)
        return constrain_fwd(h + out, ["batch", "seq", "embed"]), counters

    def remat(layer):
        if not c.remat_layers:
            return layer
        # save the flash kernel's (o, lse) residuals across the remat
        # boundary: recomputing them in the backward costs a full kernel
        # pass (~4% of the llama step on v5e) for ~70MB/layer of HBM.
        # Likewise the scan kernel's y and entering states (kernels/ssd.py;
        # a Mamba layer's only: no other program holds these names): 24 KB
        # a token a layer at Nemotron's widths (16 the float32 states, 8
        # y), 403 MB a layer at 2 x 8192 tokens a chip, nine times the
        # layer's carry, for 1.5 ms a layer of replay: +1.0 % tokens/s in
        # nemotron3nano.steady-8k's four layers (PERF.md section 6, PR 34).
        # It grows with depth and tokens a chip as the carry does: the
        # source's 23 Mamba layers would keep 9.3 GB at those tokens on a
        # 16 GB chip. A job that deep takes these two names out
        return jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse", "ssd_y", "ssd_states"
            ),
        )

    def attend(carry, lp, kind):
        # the single mixer is causal over every key: "full" in the trace
        name = "full" if kind == "attention" else kind
        with jax.named_scope("attention"), \
                jax.named_scope(f"attention_{name}"):
            return attention(carry, lp, kind)

    def layer_of(kind):
        """A pair: attention of ``kind``, then the feed-forward."""
        def layer(carry, lp):
            h = attend(carry, lp, kind)
            with jax.named_scope("mlp"):
                if c.routed:
                    with jax.named_scope("moe"):
                        return routed(h, lp)
                return mlp(h, lp), {}

        return remat(layer)

    def lead_layer(carry, lp):
        """A leading pair of a routed model: the dense feed-forward."""
        with jax.named_scope("lead"):
            h = attend(carry, lp, c.layer_kinds[0])
            with jax.named_scope("mlp"):
                return mlp(h, lp), None

    def mixer_of(kind):
        """A single mixer: ``x + mixer(rmsnorm(x))`` and nothing else."""
        def layer(carry, lp):
            if kind == "attention":
                return attend(carry, lp, kind), {}
            if kind == "experts":
                with jax.named_scope("mlp"), jax.named_scope("moe"):
                    return routed(carry, lp)
            with jax.named_scope("mamba"):
                out, counters = mamba2.apply(
                    c, lp, _rmsnorm(carry, lp["norm"]["scale"], c.norm_eps),
                    mesh=mesh)
                return constrain_fwd(
                    carry + out, ["batch", "seq", "embed"]), counters

        return remat(layer)

    if c.n_dense_layers:  # a scan of their own, before the periods'
        x, _ = lax.scan(remat(lead_layer), x, params["lead"])

    # the scan walks periods; the kinds inside one are unrolled in its
    # body. A model of one kind is the plain scan over its layers.
    kinds = c.layer_kinds
    if c.single_mixers:
        # each kind's layers are a stack of their own: the body takes the
        # next layer of its kind as it walks the pattern
        layers = {kind: mixer_of(kind) for kind in set(kinds)}

        def period(carry, pp):
            taken, outs = dict.fromkeys(pp, 0), {}
            for kind in kinds:
                i, taken[kind] = taken[kind], taken[kind] + 1
                carry, out = layers[kind](
                    carry, jax.tree.map(lambda a: a[i], pp[kind]))
                for name, value in out.items():
                    outs.setdefault(name, []).append(value)
            return carry, {n: jnp.stack(v) for n, v in outs.items()}

        x, counters = lax.scan(period, x, {
            kind: jax.tree.map(
                lambda a: a.reshape(-1, kinds.count(kind), *a.shape[1:]),
                params["layers"][kind])
            for kind in set(kinds)})
    elif len(kinds) == 1:
        x, counters = lax.scan(layer_of(kinds[0]), x, params["layers"])
    else:
        layers = {kind: layer_of(kind) for kind in set(kinds)}

        def period(carry, pp):
            outs = []
            for i, kind in enumerate(kinds):
                carry, out = layers[kind](
                    carry, jax.tree.map(lambda a: a[i], pp))
                outs.append(out)
            return carry, jax.tree.map(lambda *a: jnp.stack(a), *outs)

        x, counters = lax.scan(period, x, jax.tree.map(
            lambda a: a.reshape(-1, len(kinds), *a.shape[1:]),
            params["layers"]))
    with jax.named_scope("head_loss"):
        x = _rmsnorm(x, params["final_norm"]["scale"], c.norm_eps)
        if return_features:
            return x, counters
        logits = x @ params["lm_head"]["w"].astype(dt)
        return logits.astype(jnp.float32), counters


def _step_counters(counters):
    """One scalar a counter for the step, from each layer's: how many
    assignments fell to held experts (mean a layer), the fullest held
    expert over the mean (worst layer), assignments without a row (sum),
    rows the passes in row order visited (mean a layer), the share of the
    scan's chunks that hand state on (mean a layer)."""
    fold = {moe.ASSIGNMENTS_HELD: jnp.mean, moe.LOAD_MAX_OVER_MEAN: jnp.max,
            moe.ASSIGNMENTS_DROPPED: jnp.sum, moe.ROWS_WORKED: jnp.mean,
            mamba2.CARRY_SHARE: jnp.mean}
    return {name: fold[name](values) for name, values in counters.items()}


def loss_fn(
    config: Config,
    params: Params,
    batch,
    *,
    mesh=None,
    rules=None,
    ce_chunk: int = 2048,
):
    """Next-token cross-entropy. batch = {"tokens": [B,T]}; position t
    predicts token t+1; the final position is dropped. A model with routed
    or state-space layers returns ``(loss, counters)``: their named scalars
    of the step (parallel/moe.py, models/mamba2.py), which ``Trainer`` puts
    into the step's metrics.

    Above ``ce_chunk`` positions the loss is computed blockwise over the
    sequence (checkpointed lax.map): the [B,T,vocab] f32 logits plus their
    log-softmax are each >2 GB at 16k×32k-vocab — materializing them is
    what OOMs long-context training, not the attention. Chunking keeps CE
    memory at O(B·chunk·vocab) with exact results."""
    tokens = batch["tokens"]
    t = tokens.shape[1]
    chunked = t - 1 > ce_chunk
    out, counters = _forward(
        config, params, tokens, mesh=mesh, rules=rules,
        return_features=chunked,
    )  # logits, or above ce_chunk the features [B, T, D] in compute dtype
    with jax.named_scope("head_loss"):
        if chunked:
            loss = _chunked_nll(out, params["lm_head"]["w"], tokens, ce_chunk)
        else:
            targets = tokens[:, 1:]
            lp = jax.nn.log_softmax(out[:, :-1])
            ll = jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
            loss = -jnp.mean(ll)
    return (loss, _step_counters(counters)) if counters else loss


def _chunked_nll(feats, head, tokens, ce_chunk):
    """Mean next-token NLL from final-norm features [B,T,D], the lm_head
    applied blockwise over the sequence (see :func:`loss_fn`)."""
    # shift targets by roll instead of slicing feats[:-1]/tokens[1:]:
    # keeping T intact aligns chunk boundaries with the (typically
    # power-of-two) sequence length so no repad is needed; the final
    # position is masked out below
    b, t_full = tokens.shape
    y = jnp.roll(tokens, -1, axis=1)
    n = t_full - 1  # real prediction positions
    n_chunks = -(-t_full // ce_chunk)
    pad = n_chunks * ce_chunk - t_full
    x = feats
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
        y = jnp.pad(y, [(0, 0), (0, pad)])
    xr = x.reshape(b, n_chunks, ce_chunk, -1).transpose(1, 0, 2, 3)
    yr = y.reshape(b, n_chunks, ce_chunk).transpose(1, 0, 2)
    valid = (
        jnp.arange(n_chunks * ce_chunk).reshape(n_chunks, 1, ce_chunk) < n
    )

    @jax.checkpoint
    def chunk_nll(xc, yc, vc):
        logits = (xc @ head.astype(xc.dtype)).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(lp, yc[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(vc, ll, 0.0))

    totals = lax.map(lambda args: chunk_nll(*args), (xr, yr, valid))
    return -jnp.sum(totals) / (b * n)


def param_count(config: Config) -> int:
    """The parameters held: of a routed model's experts, this share's."""
    c = config
    d = c.d_model
    if c.latent:
        r, h = c.kv_lora_rank, c.n_heads
        attention = (d * h * c.qk_head_dim + d * (r + c.qk_rope_dim) + r
                     + r * h * (c.qk_nope_dim + c.v_head_dim)
                     + h * c.v_head_dim * d + d)
    else:
        attention = d * (c.q_dim + 2 * c.kv_dim) + c.q_dim * d + d
    matrices = 3 if c.experts_gated else 2  # of an expert, routed or shared
    routed = (d * c.n_experts
              + (c.n_experts if c.router_score == "sigmoid" else 0)
              + matrices * d * (c.experts_held * c.d_expert + c.d_shared)
              + d)
    if c.single_mixers:
        inner, conv, proj = mamba2.widths(c)
        mamba = (d + d * proj + conv * (c.conv_kernel + 1) + 3 * c.ssm_heads
                 + inner + inner * d)
        per_kind = {"mamba": mamba, "attention": attention, "experts": routed}
        layers = sum(per_kind[kind] * count
                     for kind, count in _per_kind(c).items())
    else:
        dense = 3 * d * c.d_ff + d
        layers = (c.n_dense_layers * (attention + dense)
                  + (c.n_layers - c.n_dense_layers)
                  * (attention + (routed if c.routed else dense)))
    return c.vocab * d + layers + d + d * c.vocab
