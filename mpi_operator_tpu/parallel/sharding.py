"""Logical-axis sharding rules.

Models in this framework never name mesh axes directly: they annotate arrays
with *logical* axes ("batch", "embed", "heads", …) and a rule table maps
those to the mesh axes of runtime/topology.py. Deployment then re-shards the
same model from pure-DP (the reference's only strategy, SURVEY.md §2.5) to
FSDP/TP/SP/EP mixes by swapping the rule table — no model edits. This is the
capability the reference cannot express (its ranks are placement-flat MPI
processes); here it's the default.

A rule maps a logical axis to: a mesh axis name, a tuple of mesh axis names
(the array axis is sharded over their product), or None (replicated).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from mpi_operator_tpu.runtime.topology import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
)

Rule = Union[str, Tuple[str, ...], None]
Rules = Dict[str, Rule]

# The standard table. "batch" shards over both DP-ish axes (data carries the
# plain-DP component, fsdp the ZeRO component); parameter logical axes shard
# over fsdp (ZeRO-3 gather) and/or tensor (megatron split); "seq" is the
# ring-attention axis.
DEFAULT_RULES: Rules = {
    "batch": (AXIS_DATA, AXIS_FSDP),
    "seq": AXIS_SEQ,
    "embed": AXIS_FSDP,
    "latent": AXIS_FSDP,
    "mlp": AXIS_TENSOR,
    "heads": AXIS_TENSOR,
    "kv_heads": AXIS_TENSOR,
    "qkv": None,
    "head_dim": None,
    "vocab": AXIS_TENSOR,
    "expert": AXIS_EXPERT,
    "conv_kernel": None,
    "conv_in": None,
    "conv_out": AXIS_FSDP,
    "stats": None,
}


def logical_spec(
    logical_axes: Sequence[Optional[str]], rules: Optional[Rules] = None
) -> PartitionSpec:
    """(logical axis per array dim) → PartitionSpec via the rule table.

    A mesh axis may appear at most once in a PartitionSpec; when two logical
    axes map to the same mesh axis the later one degrades to replicated
    (matching flax's logical-axis semantics)."""
    rules = DEFAULT_RULES if rules is None else rules
    used: set = set()
    parts = []
    for ax in logical_axes:
        rule = rules.get(ax) if ax is not None else None
        if rule is None:
            parts.append(None)
            continue
        mesh_axes = (rule,) if isinstance(rule, str) else tuple(rule)
        fresh = tuple(m for m in mesh_axes if m not in used)
        if not fresh:
            parts.append(None)
            continue
        used.update(fresh)
        parts.append(fresh[0] if len(fresh) == 1 else fresh)
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def mesh_filtered_spec(spec: PartitionSpec, mesh: Mesh) -> PartitionSpec:
    """Drop mesh axes the given mesh doesn't have (so one rule table serves
    meshes of any dimensionality — a pure-DP mesh simply ignores tensor/seq
    rules)."""
    parts = []
    for p in spec:
        if p is None:
            parts.append(None)
        elif isinstance(p, str):
            parts.append(p if p in mesh.axis_names else None)
        else:
            kept = tuple(m for m in p if m in mesh.axis_names)
            parts.append(kept[0] if len(kept) == 1 else (kept or None))
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Rules] = None,
) -> NamedSharding:
    return NamedSharding(mesh, mesh_filtered_spec(logical_spec(logical_axes, rules), mesh))


def with_logical_constraint(
    x,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Rules] = None,
    mesh: Optional[Mesh] = None,
):
    """``with_sharding_constraint`` by logical axes — the in-jit annotation
    that steers XLA's sharding propagation at activation boundaries (the knob
    deciding which collectives get inserted and where resharding happens).

    ``mesh`` is the trace-time mesh (pass it explicitly from the trainer; it
    is static). Without one, falls back to the ambient abstract mesh if set,
    else no-op — so model code runs unchanged on a single device."""
    import jax

    if mesh is None:
        am = jax.sharding.get_abstract_mesh()
        if am is None or not am.axis_names:
            return x
        mesh = am
    spec = mesh_filtered_spec(logical_spec(logical_axes, rules), mesh)
    if isinstance(mesh, Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def with_logical_constraint_fwd(
    x,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Rules] = None,
    mesh: Optional[Mesh] = None,
):
    """Forward-only logical constraint: the primal is annotated, the
    cotangent passes through UNconstrained.

    ``with_sharding_constraint`` transposes to the same constraint on the
    cotangent — but activation gradients often arrive sharded by the
    *weight* layout (e.g. d_model sharded over fsdp out of a ZeRO matmul
    backward) while the primal constraint shards the *batch* dim over
    fsdp. Forcing that transition makes the SPMD partitioner fall back to
    "replicate then repartition" ([SPMD] Involuntary full
    rematerialization). Leaving the backward free lets XLA keep the
    natural cotangent sharding and pick the cheap collective itself."""
    import jax

    @jax.custom_vjp
    def _constrained(y):
        return with_logical_constraint(y, logical_axes, rules=rules, mesh=mesh)

    def _fwd(y):
        return _constrained(y), None

    def _bwd(_, g):
        return (g,)

    _constrained.defvjp(_fwd, _bwd)
    return _constrained(x)
