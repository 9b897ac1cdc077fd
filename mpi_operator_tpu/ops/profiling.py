"""XLA profiler hooks for training loops.

≙ SURVEY.md §5.1's TPU-build obligation: the reference punts workload
profiling to the roadmap (Horovod Timeline, /root/reference/ROADMAP.md:14);
here every worker can capture an XLA trace of a step window with zero code
changes — the controller passes container env through, so setting

    TPUJOB_PROFILE_DIR=/tmp/trace        (per-host subdir appended)
    TPUJOB_PROFILE_START=10              (first step to trace, default 10)
    TPUJOB_PROFILE_STEPS=5               (how many steps, default 5)

on a job's worker template makes each host write an xplane trace readable
with xprof/tensorboard (see PERF.md for the analysis recipe).

Since the workload telemetry plane (ISSUE 15) there is also the
OPERATOR-TRIGGERED path: ``ctl profile <job> --steps N`` stamps the
``tpujob.dev/profile-request`` annotation, the controller projects it
into the job ConfigMap's ``profile`` key (the same projected-file channel
the elastic membership check already polls), and each worker's
:class:`ProfileRequestWatcher` captures a ``jax.profiler`` trace for N
steps into the job's artifact dir, acking progress through its
train_stats ``profile`` entry (``ctl profile --status/--fetch`` read the
acks back) — attaching a profiler to a live gang without restarting it.
Capture is host-local tracing with no effect on SPMD control flow, so
each host may start on its own request-file timing.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
from typing import Any, Dict, Optional

log = logging.getLogger("tpujob.profiling")

ENV_DIR = "TPUJOB_PROFILE_DIR"
ENV_START = "TPUJOB_PROFILE_START"
ENV_STEPS = "TPUJOB_PROFILE_STEPS"

# the ConfigMap key the controller projects the profile-request
# annotation into (a file under $TPUJOB_CONFIG_DIR, like the hostfile)
PROFILE_REQUEST_FILE = "profile"


# the id under which the env-driven capture acks in the train_stats
# ``profile`` entry (an operator's request carries its own id)
ENV_PROFILE_ID = "env"


class StepProfiler:
    """Drive from a training loop: call observe(step) once per step; the
    trace starts/stops itself around the configured window. No-op (and
    import-free) when TPUJOB_PROFILE_DIR is unset. With a recorder
    (``stats``), the capture acks through its ``profile`` entry like an
    operator's request does — id ``env``, ``capturing`` then ``done``,
    ``dir`` this host's trace directory — so whoever reads the blob learns
    where the trace is."""

    def __init__(self, directory: Optional[str] = None, *, stats=None):
        self.stats = stats  # StepStatsRecorder, optional
        self.directory = directory if directory is not None else os.environ.get(ENV_DIR, "")
        self.start_step = int(os.environ.get(ENV_START, "10") or "10")
        self.num_steps = max(1, int(os.environ.get(ENV_STEPS, "5") or "5"))
        self._active = False
        self._done = False

    @property
    def enabled(self) -> bool:
        return bool(self.directory)

    def _trace_dir(self) -> str:
        import jax

        return os.path.join(self.directory, f"host{jax.process_index()}")

    def observe(self, step: int) -> None:
        if not self.enabled or self._done:
            return
        import jax

        if not self._active and self.start_step <= step < self.start_step + self.num_steps:
            jax.profiler.start_trace(self._trace_dir())
            self._active = True
            self._ack("capturing")
        elif self._active and step >= self.start_step + self.num_steps:
            self._stop()

    def _ack(self, state: str) -> None:
        if self.stats is not None:
            self.stats.set_profile(ENV_PROFILE_ID, state, self._trace_dir())

    def _stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        self._ack("done")

    def close(self) -> None:
        if self._active:
            self._stop()


class ProfileRequestWatcher:
    """The operator-triggered profiling hook: polls the controller-
    projected request file at the membership-check cadence, captures a
    ``jax.profiler`` trace for the requested step window, and acks
    progress through the step-stats recorder (→ pod status → `ctl
    profile --status`).

    Drive from a training loop::

        watcher = ProfileRequestWatcher(stats, out_root=...)
        ...
        watcher.observe(step)           # every step (no-op unless active)
        if step % check_every == 0:
            watcher.poll(step)          # re-read the projected request

    ``start_trace``/``stop_trace`` are injectable so tests never need a
    live jax; the defaults import jax lazily on first capture.
    """

    def __init__(self, stats=None, *, config_dir: Optional[str] = None,
                 out_root: Optional[str] = None,
                 host_index: Optional[int] = None,
                 start_trace=None, stop_trace=None):
        self.stats = stats  # StepStatsRecorder (acks ride its blob); opt
        self.config_dir = (
            config_dir if config_dir is not None
            else os.environ.get("TPUJOB_CONFIG_DIR", "")
        )
        self.out_root = out_root or os.path.join(
            tempfile.gettempdir(), "tpujob-profiles",
            os.environ.get("TPUJOB_NAMESPACE", "default")
            + "-" + os.environ.get("TPUJOB_NAME", "job"),
        )
        self._host_index = host_index
        self._start = start_trace or self._jax_start
        self._stop = stop_trace or self._jax_stop
        self._handled: Optional[str] = None  # last request id acted on
        self._active: Optional[Dict[str, Any]] = None  # {id, until, dir}

    # -- jax backends (lazy: the watcher must import clean without jax) ------

    def _host(self) -> int:
        if self._host_index is not None:
            return self._host_index
        import jax

        return jax.process_index()

    def _jax_start(self, directory: str) -> None:
        import jax

        jax.profiler.start_trace(directory)

    def _jax_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    # -- the request channel -------------------------------------------------

    def _read_request(self) -> Optional[Dict[str, Any]]:
        if not self.config_dir:
            return None
        path = os.path.join(self.config_dir, PROFILE_REQUEST_FILE)
        try:
            with open(path, encoding="utf-8") as f:
                raw = f.read().strip()
        except OSError:
            return None
        if not raw:
            return None
        try:
            req = json.loads(raw)
        except ValueError:
            log.warning("malformed profile request ignored: %.128s", raw)
            return None
        if not isinstance(req, dict) or not req.get("id"):
            return None
        return req

    def poll(self, step: int) -> None:
        """Check the projected request file (membership-check cadence —
        one stat+read per check, never per step)."""
        if self._active is not None:
            return
        req = self._read_request()
        if req is None or str(req["id"]) == self._handled:
            # compare NORMALIZED: a hand-stamped numeric id must not read
            # as forever-new and restart the capture on every poll
            return
        self._handled = str(req["id"])
        try:
            steps = max(1, int(req.get("steps", 5)))
        except (TypeError, ValueError):
            steps = 5
        try:
            host = self._host()
        except Exception as e:
            # the lazy jax import / process_index() can itself fail (no
            # profiler build, half-initialized jax.distributed) — the
            # module contract says a broken backend must not kill the
            # training loop, and since the annotation is never cleared a
            # propagated exception here would crash-loop every relaunch
            log.warning("profile capture failed: host index "
                        "unavailable: %s", e)
            if self.stats is not None:
                self.stats.set_profile(
                    self._handled, "failed",
                    os.path.join(self.out_root, self._handled))
            return
        directory = os.path.join(self.out_root, self._handled,
                                 f"host{host}")
        try:
            already = os.path.isdir(directory) and os.listdir(directory)
        except OSError:
            already = False
        if already:
            # the annotation is never cleared and _handled is
            # per-process: a RELAUNCHED worker (preemption, rescale,
            # migration — routine for elastic gangs) re-reads the old
            # request with fresh state. The artifact dir lives on the
            # SHARED checkpoint volume, so a non-empty host dir IS the
            # durable 'this id already captured here' marker — ack done,
            # never overwrite a fetched trace or re-pay the overhead.
            log.info("profile %s: already captured (%s); skipping",
                     self._handled, directory)
            if self.stats is not None:
                self.stats.set_profile(self._handled, "done", directory)
            return
        try:
            os.makedirs(directory, exist_ok=True)
            self._start(directory)
        except Exception as e:
            # a broken profiler backend must not kill the training loop;
            # the failure is the ack the requester sees
            log.warning("profile capture failed to start: %s", e)
            if self.stats is not None:
                self.stats.set_profile(self._handled, "failed", directory)
            return
        self._active = {"id": self._handled, "until": step + steps,
                        "dir": directory}
        log.info("profile %s: capturing %d steps into %s",
                 self._handled, steps, directory)
        if self.stats is not None:
            self.stats.set_profile(self._handled, "capturing", directory)

    def observe(self, step: int) -> None:
        """Per-step hook: stops the capture once the window elapsed."""
        act = self._active
        if act is None or step < act["until"]:
            return
        self._finish("done")

    def _finish(self, state: str) -> None:
        act, self._active = self._active, None
        if act is None:
            return
        try:
            self._stop()
        except Exception as e:
            log.warning("profile trace stop failed: %s", e)
            state = "failed"
        # the requester polls pod status for exactly this transition
        if self.stats is not None:
            self.stats.set_profile(act["id"], state, act["dir"])
        log.info("profile %s: %s (%s)", act["id"], state, act["dir"])

    def close(self) -> None:
        """End-of-run cleanup: an in-flight capture stops and acks (a
        gang restarting mid-capture leaves a truncated-but-valid trace,
        not a wedged profiler)."""
        if self._active is not None:
            self._finish("done")
