"""Slice topology → jax.sharding.Mesh construction.

The reference has no notion of interconnect topology: MPI ranks are flat and
Horovod's ring is formed at runtime over whatever TCP routes exist (SURVEY.md
§2.5). On TPU the device mesh IS the performance model — collectives along a
mesh axis ride ICI only if that axis maps onto physically adjacent chips —
so mesh construction is a first-class runtime primitive here.

Axis vocabulary (fixed, so every layer — models, trainer, bench — speaks the
same names):

- ``data``      batch sharding (pure DP; gradient psum ≙ Horovod allreduce)
- ``fsdp``      batch + parameter sharding (ZeRO-3-style, rides ICI)
- ``tensor``    megatron-style tensor parallelism (activations all-reduce)
- ``sequence``  context/sequence parallelism (ring attention via ppermute)
- ``expert``    MoE expert parallelism (all_to_all dispatch)
- ``pipe``      pipeline stages (microbatched, ppermute between stages)

A mesh never needs all six: :class:`MeshPlan` names only the axes with size>1
and :func:`build_mesh` lays them out best-ICI-first. Across slices (DCN), the
plan's ``dcn`` sizes produce a hybrid mesh where only the outermost
(gradient-reduction) axes cross the slow network — the scaling-book recipe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_SEQ = "sequence"
AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"
AXIS_TENSOR = "tensor"

# Canonical ordering, outermost (cheapest to put on DCN, reduced least often)
# to innermost (hottest collectives, must sit on shortest ICI paths). This is
# the order build_mesh lays axes onto the physical device array.
MESH_AXES: Tuple[str, ...] = (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_EXPERT,
    AXIS_SEQ,
    AXIS_TENSOR,
)


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Logical mesh layout: axis name → size. ``dcn`` gives the per-axis
    slice-count for multi-slice (DCN-spanning) meshes; only leading axes may
    cross DCN."""

    axes: Dict[str, int] = dataclasses.field(default_factory=dict)
    dcn: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for name in list(self.axes) + list(self.dcn):
            if name not in MESH_AXES:
                raise ValueError(
                    f"unknown mesh axis {name!r}; the vocabulary is {MESH_AXES}"
                )

    @property
    def ici_size(self) -> int:
        return math.prod(self.axes.values()) if self.axes else 1

    @property
    def dcn_size(self) -> int:
        return math.prod(self.dcn.values()) if self.dcn else 1

    @property
    def total_devices(self) -> int:
        return self.ici_size * self.dcn_size

    def ordered(self) -> Tuple[Tuple[str, int], ...]:
        """All axes in canonical order with combined (dcn*ici) sizes."""
        out = []
        for name in MESH_AXES:
            size = self.axes.get(name, 1) * self.dcn.get(name, 1)
            if size > 1 or name in self.axes or name in self.dcn:
                out.append((name, size))
        if not out:
            out.append((AXIS_DATA, 1))
        return tuple(out)

    @staticmethod
    def data_parallel(n: int) -> "MeshPlan":
        return MeshPlan(axes={AXIS_DATA: n})

    @staticmethod
    def parse(spec: str, dcn: str = "") -> "MeshPlan":
        """Parse a parallelism spec from a job manifest / env var —
        ``"fsdp=4,tensor=2"`` (ICI axes) plus an optional DCN spec like
        ``"data=2"`` (slice counts on leading axes). This is how a TPUJob
        chooses non-DP parallelism without code: the worker passes the
        parsed plan to mesh_from_context (e.g. examples/llama_worker.py's
        LLAMA_MESH)."""

        def parse_axes(s: str) -> Dict[str, int]:
            out: Dict[str, int] = {}
            for part in (p.strip() for p in s.split(",") if p.strip()):
                name, _, size = part.partition("=")
                name = name.strip()
                if name in out:
                    raise ValueError(f"duplicate mesh axis {name!r} in {s!r}")
                try:
                    out[name] = int(size)
                except ValueError:
                    raise ValueError(
                        f"bad mesh spec entry {part!r}; expected axis=N"
                    ) from None
                if out[name] < 1:
                    raise ValueError(f"bad mesh axis size in {part!r}")
            return out

        return MeshPlan(axes=parse_axes(spec), dcn=parse_axes(dcn))


def _cpu_or_flat_mesh(shape: Sequence[int], devices) -> np.ndarray:
    return np.asarray(devices).reshape(tuple(shape))


def _hybrid_flat_mesh(
    ici_shape: Sequence[int], dcn_shape: Sequence[int], devices
) -> np.ndarray:
    """Hybrid mesh layout for backends without physical topology (CPU/tests).

    Same device-placement contract as mesh_utils.create_hybrid_device_mesh:
    devices arrive slice-major (slice i owns the i-th contiguous block of
    ici_size devices), and each logical axis of combined size dcn*ici is
    laid out [dcn, ici] with the DCN factor outermost — so a collective
    along an axis with dcn==1 never leaves its slice, and gradient
    reductions along the leading (dcn>1) axes are the only DCN traffic."""
    n = len(ici_shape)
    arr = np.asarray(devices).reshape(tuple(dcn_shape) + tuple(ici_shape))
    perm = [a for i in range(n) for a in (i, n + i)]
    arr = arr.transpose(perm)
    return arr.reshape(tuple(d * i for d, i in zip(dcn_shape, ici_shape)))


def build_mesh(plan: MeshPlan, devices: Optional[Sequence] = None):
    """Materialize the plan as a ``jax.sharding.Mesh``.

    On TPU backends this delegates to ``mesh_utils.create_device_mesh`` (and
    ``create_hybrid_device_mesh`` when the plan spans DCN), which permutes
    devices so that the innermost logical axes land on physical ICI rings.
    On CPU/emulated backends (tests, the driver's virtual 8-device mesh) the
    device list is reshaped row-major — there is no physical topology to
    optimize.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = tuple(n for n, _ in plan.ordered())
    sizes = tuple(s for _, s in plan.ordered())
    total = math.prod(sizes)
    if total != len(devices):
        raise ValueError(
            f"mesh plan wants {total} devices ({dict(plan.ordered())}) but "
            f"{len(devices)} are visible — gang placement and plan disagree"
        )

    platform = getattr(devices[0], "platform", "cpu")
    if plan.dcn_size > 1:
        ici_shape = [plan.axes.get(n, 1) for n in names]
        dcn_shape = [plan.dcn.get(n, 1) for n in names]
        if platform == "tpu":
            from jax.experimental import mesh_utils

            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices
            )
        else:
            # emulated slices: same layout contract, no topology to optimize
            dev_array = _hybrid_flat_mesh(ici_shape, dcn_shape, devices)
    elif platform == "tpu":
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    else:
        dev_array = _cpu_or_flat_mesh(sizes, devices)
    return Mesh(dev_array, names)


def mesh_from_context(
    ctx,
    plan: Optional[MeshPlan] = None,
):
    """Build the job-wide mesh for a bootstrapped host.

    With no explicit plan, defaults to pure data parallelism over every chip
    in the slice — the moral equivalent of the reference's Horovod ring over
    all ranks (examples/horovod/tensorflow_mnist.py, SURVEY.md §2.5). For a
    multi-slice gang (ctx.num_slices > 1) the default is data parallelism
    with the slice count on the DCN factor of the data axis, so gradient
    reductions are the only cross-slice traffic.

    Fails fast when the gang the controller declared (num_hosts ×
    chips_per_host) disagrees with what XLA sees after rendezvous — the
    TPU-side analogue of mpirun's "not enough slots" error; without it a
    worker with mangled env would silently train on a local-only mesh.
    """
    import jax

    # not at module level: `python -m ...runtime.stepstats` imports this
    # package first, and would then run a second copy of that module
    from mpi_operator_tpu.runtime import stepstats

    with stepstats.setup_span("mesh"):
        if ctx is not None and ctx.chips_per_host:
            expected = ctx.num_hosts * ctx.chips_per_host
            if expected != jax.device_count():
                raise RuntimeError(
                    f"gang declares {ctx.num_hosts} hosts × "
                    f"{ctx.chips_per_host} chips = {expected} devices but "
                    f"XLA sees {jax.device_count()} — rendezvous and "
                    "placement disagree"
                )
        ns = getattr(ctx, "num_slices", 1) if ctx is not None else 1
        if plan is None:
            n = jax.device_count()
            if ns > 1 and n % ns == 0:
                plan = MeshPlan(
                    axes={AXIS_DATA: n // ns}, dcn={AXIS_DATA: ns})
            else:
                plan = MeshPlan.data_parallel(n)
        elif ns > 1 and plan.dcn_size != ns:
            # an explicit plan on a multi-slice gang MUST name the DCN
            # factor: silently flattening the slices would let inner mesh
            # axes span the slice boundary and put per-layer collectives on
            # DCN instead of ICI — the invariant this module exists to uphold
            raise ValueError(
                f"gang spans {ns} slices but the mesh plan's DCN factor is "
                f"{plan.dcn_size}; declare it "
                f"(e.g. LLAMA_MESH_DCN='data={ns}')"
            )
        return build_mesh(plan)
