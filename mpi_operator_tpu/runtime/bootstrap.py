"""SPMD boot + coordinator rendezvous (≙ mpirun/orted/SSH wireup).

The reference's bootstrap is rank-spawn: the launcher's ``mpirun`` reads a
hostfile and ssh-es into each worker to start ``orted``
(/root/reference/v2/pkg/controller/mpi_job_controller.go:176-200, SURVEY.md
§3.3). On TPU the bootstrap is inverted (SURVEY.md §7 "hard parts"): every
host boots the same program; rendezvous is a coordinator handshake
(``jax.distributed``), after which ``jax.devices()`` spans the whole slice and
XLA collectives ride ICI.

The handshake inputs come from the ``TPUJOB_*`` env the controller injects
into every worker pod (controller/controller.py ENV_*), which is this
framework's replacement for ``OMPI_MCA_orte_default_hostfile`` /
``I_MPI_HYDRA_HOST_FILE``.
"""

from __future__ import annotations

import dataclasses
import importlib
import logging
import os
import threading
import time
from typing import Any, Dict, Mapping, Optional, Tuple

log = logging.getLogger(__name__)

# Env names are deliberately duplicated from controller/controller.py: worker
# images ship only the runtime package, so bootstrap cannot import the
# controller. tests/test_runtime.py asserts both copies stay identical.
ENV_JOB_NAME = "TPUJOB_NAME"
ENV_NAMESPACE = "TPUJOB_NAMESPACE"
ENV_COORDINATOR = "TPUJOB_COORDINATOR_ADDRESS"
ENV_NUM_HOSTS = "TPUJOB_NUM_HOSTS"
ENV_HOST_ID = "TPUJOB_HOST_ID"
ENV_CHIPS_PER_HOST = "TPUJOB_CHIPS_PER_HOST"
ENV_ACCELERATOR = "TPUJOB_ACCELERATOR"
ENV_TOPOLOGY = "TPUJOB_TOPOLOGY"
ENV_HOST_MESH = "TPUJOB_HOST_MESH"
ENV_HOST_COORD = "TPUJOB_HOST_COORD"
ENV_SLICE_ID = "TPUJOB_SLICE_ID"
ENV_NUM_SLICES = "TPUJOB_NUM_SLICES"
# node-local mount point of the cluster's SHARED checkpoint volume, stamped
# by the node agent (--ckpt-dir). A restarted gang can land on different
# nodes, so checkpoints must never live on a node-local path the next
# incarnation cannot see; workloads derive a per-job dir from this via
# default_checkpoint_dir() instead of hardcoding node paths in manifests.
ENV_CKPT_DIR = "TPUJOB_CKPT_DIR"


def _parse_shape(s: str) -> Tuple[int, ...]:
    return tuple(int(p) for p in s.split("x")) if s else ()


@dataclasses.dataclass(frozen=True)
class RuntimeContext:
    """One host's view of the gang — everything the reference smeared across
    hostfile + env + pod identity, in one immutable record."""

    job_name: str = "local"
    namespace: str = "default"
    coordinator_address: str = ""
    num_hosts: int = 1
    host_id: int = 0
    chips_per_host: int = 0  # 0 = undeclared; local_chips() discovers
    accelerator: str = ""  # "" = undeclared: jax picks the platform
    topology: Tuple[int, ...] = ()
    host_mesh: Tuple[int, ...] = ()
    host_coord: Tuple[int, ...] = ()
    slice_id: int = 0
    num_slices: int = 1

    @property
    def is_distributed(self) -> bool:
        return self.num_hosts > 1

    def local_chips(self) -> int:
        """Declared chips per host, or (when the controller didn't declare —
        local dev runs) whatever XLA actually attached to this host."""
        if self.chips_per_host:
            return self.chips_per_host
        import jax

        return jax.local_device_count()

    @property
    def is_coordinator(self) -> bool:
        """Host 0 absorbs the reference's launcher role (SURVEY.md §7 phase 3:
        the Launcher/Worker split collapses; host 0's exit status is the
        job's)."""
        return self.host_id == 0


def context_from_env(environ: Optional[Mapping[str, str]] = None) -> RuntimeContext:
    """Build the host's RuntimeContext from controller-injected env.

    Absent env falls back to a single-host local context, so the same training
    script runs unmodified on a dev machine (the reference has no analogue —
    an MPIJob image cannot run outside ``mpirun``)."""
    env = os.environ if environ is None else environ
    return RuntimeContext(
        job_name=env.get(ENV_JOB_NAME, "local"),
        namespace=env.get(ENV_NAMESPACE, "default"),
        coordinator_address=env.get(ENV_COORDINATOR, ""),
        num_hosts=int(env.get(ENV_NUM_HOSTS, "1")),
        host_id=int(env.get(ENV_HOST_ID, "0")),
        chips_per_host=int(env.get(ENV_CHIPS_PER_HOST, "0") or 0),
        accelerator=env.get(ENV_ACCELERATOR, ""),
        topology=_parse_shape(env.get(ENV_TOPOLOGY, "")),
        host_mesh=_parse_shape(env.get(ENV_HOST_MESH, "")),
        host_coord=_parse_shape(env.get(ENV_HOST_COORD, "")),
        slice_id=int(env.get(ENV_SLICE_ID, "0") or 0),
        num_slices=int(env.get(ENV_NUM_SLICES, "1") or 1),
    )


def default_checkpoint_dir(
    ctx: RuntimeContext,
    environ: Optional[Mapping[str, str]] = None,
) -> Optional[str]:
    """Per-job checkpoint directory on the shared checkpoint volume the
    node agent advertised (``TPUJOB_CKPT_DIR``), or None when no volume is
    configured. ``<base>/<namespace>/<job>``: namespaced so two tenants'
    jobs of the same name never collide, job-derived so a restarted gang
    RE-PLACED ONTO DIFFERENT NODES resumes from the same path — the
    property the reference inherits from PVCs mounted at a fixed path in
    every worker pod (mpi_job_controller.go:817-877 just runs the template;
    kubernetes mounts the same claim everywhere)."""
    env = os.environ if environ is None else environ
    base = env.get(ENV_CKPT_DIR, "")
    if not base:
        return None
    return os.path.join(base, ctx.namespace, ctx.job_name)


class _BackgroundImport:
    """``import <name>`` on a daemon thread, joined at the first use.

    Orbax's import takes seconds (12.6 s on the v5e's machine, PERF.md §5)
    and needs neither the chip nor the state, so it runs beside the
    rendezvous and the TPU attach instead of after them. The thread keeps
    its own wall seconds and whatever the import raised.
    """

    def __init__(self, name: str):
        self.name = name
        self._module: Any = None
        self._error: Optional[BaseException] = None
        self._started = time.perf_counter()
        self._ended: Optional[float] = None  # set when the import ends
        self._thread = threading.Thread(
            target=self._run, name=f"import-{name}", daemon=True)
        self._thread.start()

    @property
    def seconds(self) -> float:
        """Wall seconds the import has run: up to now while it runs, its
        whole once it has ended. On the chip's machine it outlasts set-up
        (26-29 s beside the attach and the first steps, PERF.md §5), and
        a blob flushed meanwhile says how far it has come, like every
        other count in it."""
        ended = self._ended
        return (time.perf_counter() if ended is None else ended) \
            - self._started

    def _run(self) -> None:
        try:
            self._module = importlib.import_module(self.name)
        except BaseException as e:
            # kept for the caller: module() raises it at the first use
            self._error = e
            log.warning("background import of %s failed; it is raised at "
                        "the first use", self.name, exc_info=True)
        finally:
            self._ended = time.perf_counter()

    def module(self) -> Any:
        """The imported module, once the thread is done; what the import
        raised is raised here, on the caller's thread, unchanged."""
        self._thread.join()
        if self._error is not None:
            raise self._error
        return self._module


_initialized_ctx: Optional[RuntimeContext] = None
_orbax_import: Optional[_BackgroundImport] = None


def orbax_checkpoint() -> Any:
    """The ``orbax.checkpoint`` module: joins the import that
    :func:`initialize` started in the background; where none was started
    (a library caller with no ``initialize`` before it) imports it here."""
    if _orbax_import is None:
        return importlib.import_module("orbax.checkpoint")
    return _orbax_import.module()


def setup_overlapped_seconds() -> Dict[str, float]:
    """Wall seconds of what set-up ran on another thread, for the stats
    blob's ``setup_overlapped`` (machinery/objects.SETUP_OVERLAPPED):
    the background import's own, so far while it still runs."""
    if _orbax_import is None:
        return {}
    return {"ckpt_import": _orbax_import.seconds}


def initialize(
    ctx: Optional[RuntimeContext] = None,
    *,
    environ: Optional[Mapping[str, str]] = None,
) -> RuntimeContext:
    """Rendezvous with the gang. Idempotent; returns the active context.

    Single-host contexts skip the distributed handshake entirely (≙ running
    ``mpirun -n 1`` without any hostfile). Multi-host contexts call
    ``jax.distributed.initialize`` — the coordinator (host 0) binds the port
    the controller advertised via the headless service DNS name; every other
    host dials it. This is the TPU-native replacement for the v2 SSH wireup
    (SURVEY.md §3.3) and the v1 kubectl-exec path (§3.4).

    The platform is chosen here and nowhere else, from the accelerator the
    manifest declared: ``cpu`` pins jax to the CPU; a TPU family raises
    unless jax came up on a TPU; undeclared (a script run outside the
    operator) pins and checks nothing.
    """
    global _initialized_ctx, _orbax_import
    if _initialized_ctx is not None:
        return _initialized_ctx
    from mpi_operator_tpu.runtime import compile_cache, stepstats

    # set-up's first span closes here: process start, `import jax`, the
    # program's imports (runtime/stepstats.py)
    stepstats.mark_pre_bootstrap()
    if ctx is None:
        ctx = context_from_env(environ)
    import jax

    # Everything before the rendezvous must leave jax's backends
    # uninitialized: jax.distributed.initialize refuses to run once one
    # exists. That holds for the import started here too (a test pins
    # it): it runs beside the rendezvous and the attach, and
    # ops/checkpoint.py joins it at the first use of a checkpoint.
    if _orbax_import is None:
        _orbax_import = _BackgroundImport("orbax.checkpoint")
    if ctx.accelerator == "cpu":
        jax.config.update("jax_platforms", "cpu")
    # point jax at the persistent compile cache BEFORE anything compiles —
    # a relaunched gang then reads its executables off disk instead of
    # repaying the warmup
    with stepstats.setup_span("cache_config"):
        compile_cache.configure_from_env(environ)
    if ctx.is_distributed:
        if not ctx.coordinator_address:
            raise RuntimeError(
                f"{ENV_NUM_HOSTS}={ctx.num_hosts} but {ENV_COORDINATOR} is "
                "unset — the controller always injects both; refusing to guess"
            )
        log.info(
            "rendezvous: job=%s host %d/%d coordinator=%s",
            ctx.job_name,
            ctx.host_id,
            ctx.num_hosts,
            ctx.coordinator_address,
        )
        with stepstats.setup_span("rendezvous"):
            jax.distributed.initialize(
                coordinator_address=ctx.coordinator_address,
                num_processes=ctx.num_hosts,
                process_id=ctx.host_id,
            )
    if ctx.accelerator not in ("", "cpu"):
        # every accelerator but the "cpu" test family names TPU hardware
        # (api/types.py HOST_BLOCK). No fallback: a job declared for a chip
        # must not train on whatever platform $JAX_PLATFORMS happened to
        # allow. After the rendezvous on purpose: default_backend()
        # initializes the backend, which is the TPU attach.
        with stepstats.setup_span("attach"):
            backend = jax.default_backend()
        if backend != "tpu":
            raise RuntimeError(
                f"{ENV_ACCELERATOR}={ctx.accelerator} but jax's backend is "
                f"{backend!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r}) — refusing to "
                "run a TPU job off the chip"
            )
    _initialized_ctx = ctx
    return ctx


def active_context() -> Optional[RuntimeContext]:
    return _initialized_ctx


def _reset_for_tests() -> None:
    global _initialized_ctx, _orbax_import
    from mpi_operator_tpu.runtime import compile_cache

    _initialized_ctx = None
    _orbax_import = None
    compile_cache._reset_for_tests()
