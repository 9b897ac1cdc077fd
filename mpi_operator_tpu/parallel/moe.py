"""Mixture-of-Experts FFN with expert parallelism over the ``expert`` axis.

Absent from the reference (SURVEY.md §2.5) — supplied here as the EP
capability. TPU-native switch-routing design:

- top-1 (switch) router with capacity factor and jitter-free softmax
  probabilities; dropped tokens pass through the residual (standard switch
  semantics);
- experts sharded over the ``expert`` mesh axis; the scatter into per-expert
  capacity buffers is the dispatch, and XLA derives the token movement (the
  all-to-all-shaped reshard, ≙ MPI_Alltoall) from the buffer's expert-axis
  sharding;
- everything static-shaped (capacity buffers) so XLA compiles one program —
  no data-dependent shapes.

Batch/token dims stay sharded over (data, fsdp) as usual; the all_to_all
reshards tokens expert-major only inside this layer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from mpi_operator_tpu.runtime.topology import AXIS_EXPERT

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 256
    n_experts: int = 8
    capacity_factor: float = 1.25
    compute_dtype: Any = jnp.bfloat16


def init(config: MoEConfig, key) -> Params:
    kr, k1, k2 = jax.random.split(key, 3)
    s_d = config.d_model**-0.5
    s_f = config.d_ff**-0.5
    e = config.n_experts
    return {
        "router": {"w": jax.random.normal(kr, (config.d_model, e), jnp.float32) * s_d},
        "w_in": {
            "w": jax.random.normal(k1, (e, config.d_model, config.d_ff), jnp.float32) * s_d
        },
        "w_out": {
            "w": jax.random.normal(k2, (e, config.d_ff, config.d_model), jnp.float32) * s_f
        },
    }


def logical_axes(config: MoEConfig) -> Params:
    return {
        "router": {"w": ("embed", None)},
        "w_in": {"w": ("expert", "embed", "mlp")},
        "w_out": {"w": ("expert", "mlp", "embed")},
    }


def _route(logits, n_experts, capacity):
    """Top-1 routing with capacity. Returns (expert_idx, slot_idx, keep_mask,
    gate) per token; slot via a cumulative count per expert."""
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [T, E]
    position = jnp.cumsum(onehot, axis=0) * onehot  # 1-based slot per token
    slot = jnp.max(position, axis=-1) - 1  # [T]
    keep = slot < capacity
    return expert_idx, slot, keep, gate, probs


def aux_load_balance_loss(probs, expert_idx, n_experts):
    """Switch-transformer load-balancing loss: E * Σ_e f_e · P_e."""
    me = jnp.mean(jax.nn.one_hot(expert_idx, n_experts, dtype=probs.dtype), axis=0)
    pe = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(me * pe)


def apply(config: MoEConfig, params: Params, x, *, mesh: Mesh = None):
    """x [B, T, D] → (y [B, T, D], aux_loss scalar).

    With a mesh carrying an ``expert`` axis the expert FFNs run sharded and
    tokens move via all_to_all; otherwise all experts run locally (same
    math, zero collectives) — one code path for tests and deployment."""
    b, t, d = x.shape
    e = config.n_experts
    tokens = x.reshape(b * t, d)
    n_tok = b * t
    capacity = int(config.capacity_factor * n_tok / e)
    capacity = max(capacity, 1)

    logits = tokens.astype(jnp.float32) @ params["router"]["w"]
    expert_idx, slot, keep, gate, probs = _route(logits, e, capacity)
    aux = aux_load_balance_loss(probs, expert_idx, e)

    # scatter tokens into [E, C, D] capacity buffers (dropped → zeros)
    buf = jnp.zeros((e, capacity, d), tokens.dtype)
    safe_slot = jnp.where(keep, slot, 0)
    buf = buf.at[expert_idx, safe_slot].add(
        jnp.where(keep[:, None], tokens, 0.0)
    )

    dt = config.compute_dtype

    def expert_ffn(w_in, w_out, xb):
        h = jax.nn.gelu(xb.astype(dt) @ w_in.astype(dt))
        return (h @ w_out.astype(dt)).astype(xb.dtype)

    if mesh is not None and AXIS_EXPERT in mesh.axis_names and mesh.shape[AXIS_EXPERT] > 1:

        def sharded(buf_local, w_in_local, w_out_local):
            # buf arrives sharded on dim 0: each device holds its experts'
            # capacity buffers (XLA inserted the dispatch reshard). Run them.
            def one(xb, wi, wo):
                return expert_ffn(wi, wo, xb)

            return jax.vmap(one)(buf_local, w_in_local, w_out_local)

        out_buf = jax.shard_map(
            sharded,
            mesh=mesh,
            in_specs=(P(AXIS_EXPERT), P(AXIS_EXPERT), P(AXIS_EXPERT)),
            out_specs=P(AXIS_EXPERT),
        )(buf, params["w_in"]["w"], params["w_out"]["w"])
    else:
        out_buf = jax.vmap(lambda xb, wi, wo: expert_ffn(wi, wo, xb))(
            buf, params["w_in"]["w"], params["w_out"]["w"]
        )

    # gather back: token i reads its (expert, slot) result, scaled by gate
    gathered = out_buf[expert_idx, safe_slot]
    y = jnp.where(keep[:, None], gathered * gate[:, None].astype(gathered.dtype), 0.0)
    return y.reshape(b, t, d), aux
