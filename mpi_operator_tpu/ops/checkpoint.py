"""Checkpoint / resume (orbax-backed).

The reference has NO checkpointing — SURVEY.md §5.4: job-level "resume" is
only launcher-pod retry, and elastic Horovod recovers from in-memory state.
On TPU, preemption is routine and XLA can't re-form a ring in place
(membership change ⇒ recompile), so durable checkpoints are the recovery
primitive (SURVEY.md §7 phase 7): scale events save → re-mesh → restore.

Restore is *reshard-on-load*: the target shardings come from the new mesh,
so a checkpoint written on 16 hosts restores cleanly onto 8 or 32 — this is
exactly the elastic-resume path the controller's scale-up/down drives."""

from __future__ import annotations

import os
import resource
from typing import Any, Optional

import jax

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
    framework's TrainState layout and elastic-resume semantics."""

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 3,
        save_interval_steps: int = 1000,
        async_save: bool = True,
    ):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        self.manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                create=True,
                # async commit (ISSUE 16): save() returns once the device
                # arrays are snapshotted host-side; serialization to disk
                # overlaps the NEXT steps on orbax's background thread.
                # The step loop then charges only that blocking snapshot
                # slice to its `ckpt` bucket — the commit costs goodput
                # nothing. Durability is unchanged WHERE IT MATTERS: the
                # sanctioned seams (SIGTERM force-checkpoint, terminal
                # exit, pre-restore) call wait() to fence the commit.
                enable_async_checkpointing=async_save,
            ),
        )

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        """Save if the step hits the interval (or force). Multi-host safe:
        every process must call this (orbax coordinates the barrier).
        With ``async_save`` (the default) this returns after the blocking
        device→host snapshot; the disk commit overlaps later steps and is
        fenced by :meth:`wait`."""
        saved = self.manager.save(
            step,
            args=self._ocp.args.PyTreeSave(
                state, ocdbt_target_data_file_size=_data_file_target()
            ),
            force=force,
        )
        return bool(saved)

    def latest_step(self) -> Optional[int]:
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
        return self.manager.restore(
            step,
            args=self._ocp.args.PyTreeRestore(
                abstract,
                restore_args=self._ocp.checkpoint_utils.construct_restore_args(
                    abstract
                ),
            ),
        )

    def wait(self) -> None:
        """Block until any async save has committed."""
        self.manager.wait_until_finished()

    def close(self) -> None:
        self.manager.close()
