"""SPMD pipeline parallelism over the ``pipe`` mesh axis.

Absent from the reference (its ranks are workload-agnostic MPI processes;
SURVEY.md §2.5 row TP/PP/SP/EP: "No") — here it's a first-class schedule.
TPU-native shape: instead of a per-stage program + point-to-point sends (the
GPU idiom), ONE program runs on every device under shard_map; the layer
stack is sharded over ``pipe`` (each device owns n_layers/S consecutive
layers) and microbatch activations rotate stage-to-stage with neighbour
``ppermute`` hops — a GPipe schedule with S+M-1 ticks, collectives riding
ICI.

The schedule works on any per-stage function; models/llama.py plugs its
scanned layer body in directly.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_operator_tpu.parallel import collectives as c
from mpi_operator_tpu.runtime.topology import AXIS_PIPE


def pipeline_spmd(
    stage_fn: Callable,
    stage_params,
    microbatches,
    *,
    axis_name: str = AXIS_PIPE,
):
    """Run under shard_map. Executes the GPipe schedule:

    - ``stage_fn(stage_params, x) -> y``: this device's slice of the model
      (its layers), applied to one microbatch of activations.
    - ``microbatches``: [M, ...] stacked microbatch inputs (every stage
      receives the same array; only stage 0 consumes it).

    Returns [M, ...] outputs as produced by the LAST stage (other stages
    return zeros — callers psum or slice; keeping it zero elsewhere makes
    the loss reduction a plain psum over the pipe axis).

    Schedule: T = M + S - 1 ticks. At tick t, stage s processes microbatch
    t - s (when in range). Activations hop s→s+1 between ticks via a single
    ICI ppermute.
    """
    n_stages = c.axis_size_static(axis_name)
    stage = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    x_shape = microbatches.shape[1:]

    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        inflight, outputs = carry
        # stage 0 injects microbatch t (if any); others take the hopped-in
        # activation from the previous tick
        mb_idx = jnp.clip(t, 0, m - 1)
        fresh = microbatches[mb_idx]
        x = jnp.where(stage == 0, fresh, inflight)
        y = stage_fn(stage_params, x)
        # last stage banks its result for microbatch t - (S-1); masked write
        # (not lax.cond) keeps both paths the same varying type
        out_idx = t - (n_stages - 1)
        is_last = stage == n_stages - 1
        valid = jnp.logical_and(is_last, out_idx >= 0)
        safe_idx = jnp.clip(out_idx, 0, m - 1)
        banked = jnp.where(valid, y, outputs[safe_idx])
        outputs = outputs.at[safe_idx].set(banked)
        # hop activations to the next stage (last→0 wraps but stage 0
        # ignores what it receives, so the wrap is harmless)
        inflight = lax.ppermute(y, axis_name, fwd)
        return (inflight, outputs), None

    # carries must be device-varying over the pipe axis AND inherit the
    # microbatches' own varying axes (e.g. data sharding) from tick 0 —
    # scan type-checks carry vma under shard_map
    inflight0 = lax.pcast(microbatches[0] * 0, (axis_name,), to="varying")
    outputs0 = lax.pcast(microbatches * 0, (axis_name,), to="varying")
    (_, outputs), _ = lax.scan(
        tick, (inflight0, outputs0), jnp.arange(m + n_stages - 1)
    )
    # zero everywhere except the last stage
    return jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs))


def run_pipeline(
    stage_fn: Callable,
    stacked_params,
    batch,
    mesh: Mesh,
    *,
    n_microbatches: int,
    axis_name: str = AXIS_PIPE,
    batch_axes=("data", "fsdp"),
):
    """Global-view wrapper: shards ``stacked_params`` (leading dim = stages)
    over the pipe axis and ``batch`` (leading dim = global batch) into
    microbatches, runs the schedule, returns [B, ...] outputs (from the
    final stage, broadcast to all stages via psum of the zero-padded
    outputs)."""
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        # no pipelining in this mesh: apply all stages sequentially
        def all_stages(x):
            def body(h, p):
                return stage_fn(p, h), None

            h, _ = lax.scan(body, x, stacked_params)
            return h

        return all_stages(batch)

    b = batch.shape[0]
    mb = b // n_microbatches
    micro = batch.reshape((n_microbatches, mb) + batch.shape[1:])

    def shard_body(params, micro_in):
        # this device's param slice keeps a leading local-layers dim; a
        # local scan turns the per-layer stage_fn into this stage's body
        def local_stage(p_local, x):
            def body(h, p):
                return stage_fn(p, h), None

            h, _ = lax.scan(body, x, p_local)
            return h

        outs = pipeline_spmd(
            local_stage, params, micro_in, axis_name=axis_name
        )
        # every stage holds zeros except the last → psum broadcasts the
        # result to all stages (cheap: one pass over the output bytes)
        outs = lax.psum(outs, axis_name)
        return outs

    param_spec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    # microbatch dim 1 (the per-microbatch batch dim) shards over the data
    # axes so a data×pipe mesh does DP beside PP instead of replicating
    b_part = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    micro_spec = P(None, b_part, *(None,) * (micro.ndim - 2))
    out = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(param_spec, micro_spec),
        out_specs=micro_spec,
    )(stacked_params, micro)
    return out.reshape((b,) + batch.shape[1:])
