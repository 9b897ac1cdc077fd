"""Checkpoint / resume (orbax-backed).

The reference has NO checkpointing — SURVEY.md §5.4: job-level "resume" is
only launcher-pod retry, and elastic Horovod recovers from in-memory state.
On TPU, preemption is routine and XLA can't re-form a ring in place
(membership change ⇒ recompile), so durable checkpoints are the recovery
primitive (SURVEY.md §7 phase 7): scale events save → re-mesh → restore.

Restore is *reshard-on-load*: the target shardings come from the new mesh,
so a checkpoint written on 16 hosts restores cleanly onto 8 or 32 — this is
exactly the elastic-resume path the controller's scale-up/down drives.

Orbax is off the start's critical path. Its import takes seconds and
needs neither the chip nor the state: ``bootstrap.initialize`` starts it
on a background thread before the TPU attaches, and this module gets it
through ``bootstrap.orbax_checkpoint()``, which joins that thread (or
imports then and there where no ``initialize`` ran). The join is made at
the first use of orbax and no earlier: on a resume that is the start's
``latest_step()``, on a fresh directory the first save."""

from __future__ import annotations

import os
import resource
from typing import Any, Optional

import jax

from mpi_operator_tpu.runtime.bootstrap import orbax_checkpoint

# Saves are cut into bounded OCDBT data files: orbax's default lets one
# file grow to 2 GiB, and a multi-hundred-MB single file is a poor unit on
# any shared volume — and impossible to write at all on a machine with a
# smaller RLIMIT_FSIZE (EFBIG killed the first full-width save on one).
# orbax chunks every array down to the target and OCDBT closes a data file
# once it passes it, so a file stays under twice this.
DATA_FILE_TARGET_BYTES = 16 << 20


def _data_file_target() -> int:
    """DATA_FILE_TARGET_BYTES, lowered to a quarter of a finite
    RLIMIT_FSIZE so even the 2x overshoot stays well inside the limit."""
    soft, _hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft == resource.RLIM_INFINITY:
        return DATA_FILE_TARGET_BYTES
    return max(1, min(DATA_FILE_TARGET_BYTES, soft // 4))


class CheckpointManager:
    """Thin wrapper over orbax's CheckpointManager pinned to this
    framework's TrainState layout and elastic-resume semantics.

    Orbax's manager is built at the first call that needs it
    (``latest_step()`` on a directory that holds anything, ``save``,
    ``restore``, ``wait``), not here: the constructor only makes the
    directory, so that one that cannot be written fails the start and not
    the first save. On several hosts orbax's manager makes a barrier when
    it is built; every host sees the same shared directory, so all build
    it at the same call."""

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 3,
        save_interval_steps: int = 1000,
        async_save: bool = True,
    ):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._options = dict(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            # async commit (ISSUE 16): save() returns once the device
            # arrays are snapshotted host-side; serialization to disk
            # overlaps the NEXT steps on orbax's background thread.
            # The step loop then charges only that blocking snapshot
            # slice to its `ckpt` bucket — the commit costs goodput
            # nothing. Durability is unchanged WHERE IT MATTERS: the
            # sanctioned seams (SIGTERM force-checkpoint, terminal
            # exit, pre-restore) call wait() to fence the commit.
            enable_async_checkpointing=async_save,
        )
        self._manager: Any = None

    @property
    def manager(self) -> Any:
        """Orbax's manager, built on first use (joins orbax's import)."""
        if self._manager is None:
            ocp = orbax_checkpoint()
            self._manager = ocp.CheckpointManager(
                self.directory,
                options=ocp.CheckpointManagerOptions(
                    create=True, **self._options),
            )
        return self._manager

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        """Save if the step hits the interval (or force). Multi-host safe:
        every process must call this (orbax coordinates the barrier).
        With ``async_save`` (the default) this returns after the blocking
        device→host snapshot; the disk commit overlaps later steps and is
        fenced by :meth:`wait`."""
        ocp = orbax_checkpoint()
        saved = self.manager.save(
            step,
            args=ocp.args.PyTreeSave(
                state, ocdbt_target_data_file_size=_data_file_target()
            ),
            force=force,
        )
        return bool(saved)

    def latest_step(self) -> Optional[int]:
        """The newest committed step, or None. A directory with no entry
        at all holds no step, and says so without orbax: that is a fresh
        start, which then needs orbax at its first save and no earlier.
        Anything in it (a step, a leftover temporary step, a stray file)
        is orbax's to judge: which entries count as a committed step is
        its rule, and none of it is copied here."""
        if self._manager is None and not os.listdir(self.directory):
            return None
        return self.manager.latest_step()

    def restore(self, state_template: Any, *, step: Optional[int] = None) -> Any:
        """Restore into the layout of ``state_template``: a TrainState of
        ``jax.ShapeDtypeStruct`` leaves (or concrete arrays, of which only
        shape, dtype and sharding are read) whose shardings describe the
        *current* mesh — resharding across gang sizes happens here. Pass
        the abstract form at scale: a materialized template costs a second
        copy of the state in device memory beside the restored one."""
        # pre-restore fence (a sanctioned wait seam, oplint CKP001): an
        # in-flight async commit of the step being restored must finish
        # before its files are read back
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.directory}")
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if hasattr(x, "sharding")
            else x,
            state_template,
        )
        ocp = orbax_checkpoint()
        return self.manager.restore(
            step,
            args=ocp.args.PyTreeRestore(
                abstract,
                restore_args=ocp.checkpoint_utils.construct_restore_args(
                    abstract
                ),
            ),
        )

    def wait(self) -> None:
        """Block until any async save has committed."""
        self.manager.wait_until_finished()

    def close(self) -> None:
        if self._manager is not None:
            self._manager.close()
