"""Elastic training loop: membership changes → checkpoint → re-mesh → resume.

≙ the reference's elastic-Horovod capability (SURVEY.md §3.5: controller
publishes discover_hosts.sh, horovodrun re-forms the ring in place, in-memory
state recovery) — redesigned for XLA's reality (SURVEY.md §7 "hard parts"):
a compiled program is fixed to its mesh, so membership changes cannot re-form
in place. The TPU-native protocol is restart-based:

  1. every worker trains under a jit step compiled for the current gang;
  2. a membership source (the controller-projected config file, or any
     callable) reports the *desired* world size;
  3. on change, every worker force-checkpoints and exits with
     EXIT_RESTART (EX_TEMPFAIL) — a retryable code under
     restart_policy: ExitCode;
  4. the controller re-runs the gang at the new size; workers restore from
     the checkpoint (reshard-on-load, ops/checkpoint.py) and continue at the
     saved step.

State survives via orbax instead of Horovod's in-memory rings because TPU
preemption would lose in-memory state anyway — the checkpoint path must
exist, so it IS the elasticity path.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
from typing import Any, Callable, Dict, Iterator, Optional

from mpi_operator_tpu.ops.checkpoint import CheckpointManager
from mpi_operator_tpu.ops.profiling import ProfileRequestWatcher, StepProfiler
from mpi_operator_tpu.ops.trainer import Trainer, TrainState
from mpi_operator_tpu.runtime.stepstats import StepStatsRecorder, setup_span

# EX_TEMPFAIL: the "re-run me" exit code workers use on membership change.
# Job specs pair it with restart_policy: ExitCode (the controller treats the
# exit as retryable and relaunches the gang, ≙ setRestartPolicy :1394-1400).
EXIT_RESTART = 75

ENV_CONFIG_DIR = "TPUJOB_CONFIG_DIR"
HOSTFILE_NAME = "hostfile"


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    checkpoint_dir: str = ""
    save_interval_steps: int = 100
    membership_check_every: int = 10


@dataclasses.dataclass
class ElasticResult:
    outcome: str  # "done" | "restart"
    state: Any
    last_step: int
    metrics: Optional[Dict[str, float]] = None
    start_step: int = 0  # step this incarnation resumed from (0 = fresh)

    @property
    def steps_run(self) -> int:
        """Steps executed by THIS process (excludes restored progress) —
        the denominator-matching count for throughput reporting."""
        return self.last_step - self.start_step

    @property
    def exit_code(self) -> int:
        return 0 if self.outcome == "done" else EXIT_RESTART


# preemption signal: eviction (scheduler preemption, `ctl drain`, node
# shutdown) reaches the worker as SIGTERM with a kill grace behind it
# (executor/local.py eviction_grace — ≙ terminationGracePeriodSeconds).
# The handler only sets a flag: checkpointing from inside a signal handler
# would re-enter orbax/XLA mid-step. The step loop folds the flag into its
# gang-synchronized membership check so every host force-checkpoints at
# the SAME step — a lone host checkpointing on its own signal timing would
# diverge the SPMD control flow and hang the gang's collectives.
_PREEMPTED = threading.Event()


def install_preemption_handler() -> None:
    """Route SIGTERM into the elastic loop's checkpoint-and-exit path.
    Main-thread only (signal module contract); a no-op elsewhere so
    library callers embedded in servers don't crash."""
    try:
        signal.signal(signal.SIGTERM, lambda sig, frame: _PREEMPTED.set())
    except ValueError:
        pass  # not the main thread: the host process owns signal routing


def preemption_requested() -> bool:
    return _PREEMPTED.is_set()


def declared_world_size() -> int:
    """Desired gang size per the controller: hostfile lines in the projected
    config dir (≙ discover_hosts.sh consumers; the executor/kubelet syncs
    the file when the controller rescales)."""
    cfg_dir = os.environ.get(ENV_CONFIG_DIR, "")
    path = os.path.join(cfg_dir, HOSTFILE_NAME)
    if not cfg_dir or not os.path.exists(path):
        return int(os.environ.get("TPUJOB_NUM_HOSTS", "1"))
    with open(path) as f:
        return sum(1 for line in f if line.strip())


def _final_checkpoint(mgr: CheckpointManager, stats: StepStatsRecorder,
                      step: int, state: Any) -> None:
    """THE sanctioned blocking-wait seam (oplint CKP001): the only places
    the step loop may block on a checkpoint COMMIT are the SIGTERM
    force-checkpoint (the eviction grace window is about to expire — an
    uncommitted save is a lost step) and the terminal exit (the process
    is about to vanish). Periodic saves stay async: their commit overlaps
    the next steps and the `ckpt` bucket charges only the blocking
    device→host snapshot slice, which is what keeps the goodput pager
    silent through steady-state saves."""
    with stats.phase("ckpt"):
        if mgr.latest_step() != step:
            mgr.save(step, state, force=True)
        mgr.wait()


def run_elastic(
    trainer: Trainer,
    batches: Iterator[Any],
    *,
    total_steps: int,
    config: ElasticConfig,
    init_state: Callable[[], TrainState],
    membership: Callable[[], int] = declared_world_size,
    current_world: Optional[int] = None,
) -> ElasticResult:
    """Train to total_steps or until membership changes.

    ``init_state`` builds a fresh TrainState with ``trainer`` (run only
    when no checkpoint exists; otherwise it is merely traced for its
    shapes, and the latest checkpoint is restored INTO the current mesh
    layout). Returns "restart" (caller exits EXIT_RESTART) or "done".
    """
    import jax

    if current_world is None:
        current_world = jax.process_count()

    def agreed_gang_state() -> "tuple[int, bool]":
        """(desired world size, preemption requested) as ONE gang-uniform
        decision. Each host polls its own projected hostfile, and
        projection timing skews across hosts — if hosts acted on their
        *local* read they could diverge on which step to exit at,
        desynchronizing the collectives (the step loop is SPMD: every
        control-flow decision must be gang-uniform). Same argument for
        SIGTERM: eviction delivers it to each host on its own schedule, so
        the checkpoint-and-exit decision is an allgather-OR (any host
        signaled → the whole gang exits at this step), not a local check.
        Membership stays host 0's view (the old broadcast semantics);
        single-process is a passthrough."""
        if jax.process_count() == 1:
            return membership(), _PREEMPTED.is_set()
        import numpy as np
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(
            np.array([membership(), int(_PREEMPTED.is_set())],
                     dtype=np.int32)
        )
        return int(gathered[0][0]), bool(gathered[:, 1].any())

    # clear-then-install: a fresh incarnation cannot still be preempted by
    # a signal delivered to a PREVIOUS run in this process (the flag would
    # otherwise force-restart every later run at its first sync point). A
    # SIGTERM before the install kills the process outright (default
    # disposition), so nothing meaningful can race the clear.
    _PREEMPTED.clear()
    install_preemption_handler()
    # set-up spans (runtime/stepstats.py): host seconds of each part of
    # what a start or a restart pays before its first step. `ckpt_open`
    # on a fresh start is a makedirs and a listdir: an empty directory
    # has no step, and orbax waits for the first save. On a resume it
    # holds the wait for what is left of orbax's background import
    # (bootstrap.initialize) and orbax's own look at the directory.
    with setup_span("ckpt_open"):
        mgr = CheckpointManager(
            config.checkpoint_dir,
            save_interval_steps=config.save_interval_steps,
        )
        resume = mgr.latest_step() is not None
    if resume:
        # restore INTO an abstract template (shapes, dtypes, this mesh's
        # shardings): a materialized one would hold a second full state in
        # device memory beside the restored one — two ~9.5 GB states do
        # not fit a 16 GB chip
        with setup_span("restore"):
            state = mgr.restore(trainer.abstract_state(init_state))
    else:
        with setup_span("init_state"):
            state = init_state()

    # Track the step host-side: int(state.step) forces a device sync on a
    # jit output, which would serialize dispatch of step N+1 behind compute
    # of step N every iteration. One sync at restore, then a local counter.
    step = start_step = int(state.step)
    metrics = None
    # the workload telemetry plane (ISSUE 15): every wall-second of every
    # step classifies into an attributed bucket — input wait, compute (the
    # first one lands in `compile`), membership sync, checkpoint save —
    # flushed to $TPUJOB_STEPSTATS_FILE for the executor to mirror into
    # pod.status.train_stats, with this process's set-up spans. Each phase
    # is also a `tpujob.<bucket>` annotation in a profiler trace.
    stats = StepStatsRecorder.from_env()
    # no-op unless TPUJOB_PROFILE_DIR is set; acks through the recorder
    profiler = StepProfiler(stats=stats)
    # operator-triggered profiling: `ctl profile` stamps the annotation,
    # the controller projects it into the same config dir the membership
    # check polls; captures land under the job's artifact dir
    prof_watch = ProfileRequestWatcher(
        stats,
        out_root=(os.path.join(config.checkpoint_dir, "profiles")
                  if config.checkpoint_dir else None),
    )
    try:
        while step < total_steps:
            with stats.phase("input"):
                batch = next(batches)
            with stats.phase("compute"):
                state, metrics = trainer.train_step(state, batch)
            step += 1
            # the loss's named scalars (a router's counters) ride the blob:
            # the newest finished step's, read at a flush with no sync
            stats.set_counters(metrics)
            profiler.observe(step)
            prof_watch.observe(step)
            stats.step_done(step)
            if step % config.save_interval_steps == 0:
                # async save: returns after the blocking device→host
                # snapshot; the disk commit overlaps the next steps, so
                # this phase charges only the blocking slice (the old
                # synchronous save stalled the whole gang here for the
                # full serialize+fsync — the periodic `ckpt` spike the
                # goodput pager used to see)
                with stats.phase("ckpt"):
                    mgr.save(step, state)
            if step % config.membership_check_every == 0:
                with stats.phase("sync"):
                    want, preempted = agreed_gang_state()
                prof_watch.poll(step)
                if preempted or want != current_world:
                    # force-checkpoint BEFORE exiting: for preemption this
                    # runs inside the executor's eviction grace window, so
                    # the next incarnation resumes from this step instead
                    # of the last periodic save
                    _final_checkpoint(mgr, stats, step, state)
                    return ElasticResult(
                        "restart",
                        state,
                        step,
                        {k: float(v) for k, v in (metrics or {}).items()},
                        start_step=start_step,
                    )
        _final_checkpoint(mgr, stats, step, state)
    finally:
        prof_watch.close()
        profiler.close()
        stats.close()
        mgr.close()
    return ElasticResult(
        "done",
        state,
        step,
        {k: float(v) for k, v in (metrics or {}).items()},
        start_step=start_step,
    )
