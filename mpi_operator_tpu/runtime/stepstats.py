"""Per-step goodput telemetry: stall-attributed wall-second buckets for
the training loop (the workload telemetry plane, ISSUE 15).

The reference operator treats the training process as an opaque
``mpirun`` (PAPER.md §1 layer 1): it can say a job is Running, never WHY
it is slow. This module is the worker-side half of the eyes: a
:class:`StepStatsRecorder` the step loop (ops/elastic.py) threads through
its phases so every wall-second of every step classifies into exactly one
attributed bucket of :data:`~mpi_operator_tpu.machinery.objects.TRAIN_BUCKETS`:

- ``compile``  — the first compute dispatch (trace + XLA compile or the
  cache read, and the enqueue);
- ``input``    — waiting on ``next(batches)`` (the input pipeline);
- ``compute``  — the call of the jitted step: ENQUEUE time under JAX's
  async dispatch, plus whatever the call waits for (the previous step's
  donated buffers). A host-clock share, not a device time: the step's
  device time comes from a profiler trace (PERF.md §3);
- ``sync``     — the gang-uniform membership/preemption allgather;
- ``ckpt``     — checkpoint saves (periodic and forced).

Set-up, before the first step, is a second fixed key set
(:data:`~mpi_operator_tpu.machinery.objects.SETUP_SPANS`): host seconds
from the process's start to the first batch, recorded by the module-level
:func:`setup_span` (the recorder does not exist yet when most of them
run) and carried in the blob's ``setup`` field. What set-up runs on
another thread while those spans pass (the background ``import
orbax.checkpoint``, runtime/bootstrap.py) keeps its own wall seconds,
which the blob carries in ``setup_overlapped``
(:data:`~mpi_operator_tpu.machinery.objects.SETUP_OVERLAPPED`): beside
``setup``, never inside it, so the spans stay additive.

Every phase and set-up span is also a ``jax.profiler.TraceAnnotation``
(``tpujob.<name>``) where jax is loaded, so a trace taken by
``StepProfiler`` or ``ctl profile`` shows what the step loop was doing on
the device's own timeline. The executor and the controller import this
module without jax and get no annotation.

The recorder accumulates cumulative per-incarnation totals plus a rolling
step-time window, and flushes a BOUNDED blob (``bounded_train_stats``,
oplint OBS004) to the file named by ``$TPUJOB_STEPSTATS_FILE`` via atomic
replace. The EXECUTOR owns that env (it points into its log dir) and
polls the file, mirroring the blob into ``pod.status.train_stats``
through the same ``patch_pod_status``/StatusBatcher path ``serve_stats``
rides — workers never need store credentials, exactly like the kubelet
reading cAdvisor. The controller-side goodput aggregator
(controller/goodput.py) rolls the per-pod blobs up into per-job goodput,
dominant-stall attribution and straggler detection.

Overhead: two ``perf_counter`` calls, one annotation (half a microsecond
with the profiler off, on this sandbox's CPU) and one dict add per phase,
three phases a step. What that costs a step on the chip is what the
benchmark's cells measure with the profiler off and on (PERF.md §6).

``python -m mpi_operator_tpu.runtime.stepstats --smoke`` is the <30s
verify-gate check: one hollow gang with a seeded input-stall timeline
must roll up to dominant bucket ``input``, and a seeded straggler worker
must fire the skew Event naming its exact pod and node.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import sys
import time
from typing import Any, Dict, Optional

from mpi_operator_tpu.machinery.objects import (
    SETUP_SPANS,
    TRAIN_BUCKETS,
    TRAIN_COUNTERS,
    bounded_train_stats,
)

log = logging.getLogger("tpujob.stepstats")

# the executor→worker contract: where the worker flushes its stats blob
# (the executor sets it into the pod env at launch, pointing into its own
# log dir, and polls the file to mirror pod.status.train_stats)
ENV_STATS_FILE = "TPUJOB_STEPSTATS_FILE"
ENV_STATS_INTERVAL = "TPUJOB_STEPSTATS_INTERVAL"
DEFAULT_FLUSH_INTERVAL = 1.0

ANNOTATION_PREFIX = "tpujob."


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation("tpujob.<name>")`` where jax is
    already loaded, else nothing: this module never imports jax itself
    (the executor and the controller import it and have none)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


# this process's set-up seconds by span. Module-level because set-up runs
# before any recorder exists (bootstrap, the mesh) and is per process by
# nature: one incarnation, one set-up.
_setup: Dict[str, float] = {}


@contextlib.contextmanager
def setup_span(name: str):
    """Attribute the enclosed host seconds to set-up span ``name`` (one of
    SETUP_SPANS) and show it in a running profiler trace. Host clock only:
    work the span merely enqueues on the device is not waited for."""
    if name not in SETUP_SPANS:
        raise ValueError(f"unknown set-up span {name!r} (one of {SETUP_SPANS})")
    t0 = time.perf_counter()
    try:
        with _annotation(name):
            yield
    finally:
        _setup[name] = _setup.get(name, 0.0) + time.perf_counter() - t0


def mark_pre_bootstrap() -> None:
    """Record ``pre_bootstrap``: from the process's start as the OS has it
    (``/proc/self/stat``, in clock ticks since boot) to now — the
    interpreter, ``import jax`` and the program's own imports, which no
    clock inside the program can see. Left out where /proc is not there."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # the command may hold spaces: fields are counted after its ")"
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return
    _setup["pre_bootstrap"] = max(0.0, age)


def setup_seconds() -> Dict[str, float]:
    return dict(_setup)


def _reset_for_tests() -> None:
    _setup.clear()


class StepStatsRecorder:
    """Accumulates per-step bucket attribution inside a training loop.

    Usage (the shape ops/elastic.py wires)::

        stats = StepStatsRecorder.from_env()
        with stats.phase("input"):
            batch = next(batches)
        with stats.phase("compute"):     # first compute → "compile"
            state, m = trainer.train_step(state, batch)
        stats.step_done(step)

    ``clock`` is injectable for deterministic tests. A recorder with no
    path still accumulates (callers read :meth:`snapshot`) but never
    touches the filesystem.
    """

    def __init__(self, path: str = "", *, interval: Optional[float] = None,
                 window: int = 64, clock=time.perf_counter):
        self.path = path or ""
        self.interval = (DEFAULT_FLUSH_INTERVAL if interval is None
                         else max(0.0, interval))
        self._clock = clock
        self._buckets: Dict[str, float] = {k: 0.0 for k in TRAIN_BUCKETS}
        self._step = 0    # global step (checkpoint-resumed jobs pass it in)
        self._steps = 0   # steps run by THIS incarnation (resets on restart)
        self._times: collections.deque = collections.deque(maxlen=window)
        self._step_start = clock()
        self._compiled = False
        self._profile: Optional[Dict[str, str]] = None
        # the last steps' named scalars, newest last: device values that a
        # flush reads only once their step has finished
        self._counters: collections.deque = collections.deque(maxlen=3)
        self._closing = False
        self._last_flush = 0.0
        self._warned = False

    @classmethod
    def from_env(cls, env=None) -> "StepStatsRecorder":
        env = os.environ if env is None else env
        try:
            interval = float(env.get(ENV_STATS_INTERVAL, "") or
                             DEFAULT_FLUSH_INTERVAL)
        except ValueError:
            interval = DEFAULT_FLUSH_INTERVAL
        return cls(env.get(ENV_STATS_FILE, ""), interval=interval)

    @property
    def enabled(self) -> bool:
        return bool(self.path)

    @contextlib.contextmanager
    def phase(self, bucket: str):
        """Attribute the enclosed wall time to ``bucket``. The FIRST
        ``compute`` phase lands in ``compile`` instead: the first step's
        wall time is trace+compile+run, and charging it to compute would
        poison every small-N step average (the restart warmup's compile
        share must be visible as ITS OWN bucket)."""
        if bucket == "compute" and not self._compiled:
            self._compiled = True
            bucket = "compile"
        t0 = self._clock()
        try:
            with _annotation(bucket):
                yield
        finally:
            dt = self._clock() - t0
            self._buckets[bucket] = self._buckets.get(bucket, 0.0) + dt

    def step_done(self, step: Optional[int] = None) -> None:
        """One step finished: record its wall time (everything since the
        previous ``step_done``, untracked loop overhead included) and
        flush if the cadence says so."""
        now = self._clock()
        self._times.append((now - self._step_start) * 1e3)
        self._step_start = now
        self._steps += 1
        self._step = self._step + 1 if step is None else int(step)
        if self.path and now - self._last_flush >= self.interval:
            self.flush(now=now)

    def set_counters(self, metrics: Optional[Dict[str, Any]]) -> None:
        """Keep the step's named scalars (those of ``metrics`` that are
        TRAIN_COUNTERS) for the blob. They may be device values still being
        computed: nothing here waits for them."""
        kept = {k: v for k, v in (metrics or {}).items()
                if k in TRAIN_COUNTERS}
        if kept:
            self._counters.append(kept)

    def _finished_counters(self) -> Optional[Dict[str, float]]:
        """The newest kept step's scalars whose values are all there
        (``is_ready`` of a jax array; a plain number always is). Once the
        recorder is closing the newest step's are waited for: the loop has
        ended, and its last step is what the blob should say."""
        for kept in reversed(self._counters):
            if self._closing or all(getattr(v, "is_ready", lambda: True)()
                                    for v in kept.values()):
                return {k: float(v) for k, v in kept.items()}
        return None

    def set_profile(self, req_id: str, state: str, directory: str) -> None:
        """Record the on-demand profile ack (rides the blob so the
        operator side sees capture progress through pod status). Flushed
        immediately: profile transitions are rare and the requester is
        polling for exactly this."""
        self._profile = {"id": req_id, "state": state, "dir": directory}
        if self.path:
            self.flush(force=True)

    def step_p50_ms(self) -> float:
        if not self._times:
            return 0.0
        ordered = sorted(self._times)
        return ordered[len(ordered) // 2]

    def snapshot(self) -> Dict[str, Any]:
        """The bounded blob (exactly what lands in status.train_stats)."""
        from mpi_operator_tpu.runtime import bootstrap, compile_cache

        return bounded_train_stats(
            step=self._step, steps=self._steps,
            step_p50_ms=self.step_p50_ms(), buckets=self._buckets,
            profile=self._profile,
            # present only when the persistent compile cache is on for
            # this process (ISSUE 16) — lets the operator side read the
            # `compile` bucket as warm-vs-cold instead of just big-vs-small
            compile_cache=(compile_cache.cache_stats()
                           if compile_cache.is_configured() else None),
            setup=_setup,
            setup_overlapped=bootstrap.setup_overlapped_seconds(),
            counters=self._finished_counters(),
        )

    def flush(self, force: bool = False, now: Optional[float] = None) -> None:
        if not self.path:
            return
        now = self._clock() if now is None else now
        if not force and now - self._last_flush < self.interval:
            return
        self._last_flush = now
        payload = self.snapshot()
        payload["pid"] = os.getpid()
        payload["t"] = time.time()
        try:
            tmp = f"{self.path}.{os.getpid()}.tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f)
            os.replace(tmp, self.path)  # readers never see a torn blob
        except OSError:
            if not self._warned:
                # a full disk must not take the training loop down; one
                # warning, then silence (the mirror just goes stale)
                self._warned = True
                log.warning("step-stats flush to %s failed", self.path,
                            exc_info=True)

    def close(self) -> None:
        self._closing = True
        if self.path:
            self.flush(force=True)


def read_stats(path: str) -> Optional[Dict[str, Any]]:
    """Read a flushed stats blob; None when absent/unreadable/partial
    (the atomic replace makes 'partial' near-impossible, but a reader
    must never crash an executor loop on a torn file)."""
    try:
        with open(path, encoding="utf-8") as f:
            out = json.load(f)
    except (OSError, ValueError):
        return None
    return out if isinstance(out, dict) else None


# ---------------------------------------------------------------------------
# the verify-gate smoke
# ---------------------------------------------------------------------------


def smoke() -> int:
    """<30s goodput smoke: one hollow gang with a seeded INPUT-stall
    timeline must produce dominant bucket ``input`` in its job rollup,
    and a second gang's seeded straggler worker must fire the skew Event
    naming the exact pod and node. Prints one JSON line; exit 0 iff every
    bar held."""
    from mpi_operator_tpu.api.client import TPUJobClient
    from mpi_operator_tpu.controller.controller import (
        ControllerOptions,
        TPUJobController,
    )
    from mpi_operator_tpu.controller.goodput import GoodputAggregator
    from mpi_operator_tpu.executor.hollow import (
        HollowFleet,
        HollowTimeline,
        TrainLoadModel,
    )
    from mpi_operator_tpu.machinery.events import EventRecorder
    from mpi_operator_tpu.machinery.store import ObjectStore
    from mpi_operator_tpu.scheduler.gang import GangScheduler

    t0 = time.time()
    store = ObjectStore()
    recorder = EventRecorder(store)
    ctrl = TPUJobController(store, recorder, ControllerOptions(threadiness=2))
    sched = GangScheduler(store, recorder)
    train = TrainLoadModel(step_ms=20.0, compile_s=0.2, seed=7)
    train.set_stall("default/stall", "input", 0.7)
    train.set_straggler("default/skew-worker-1", 3.0)
    fleet = HollowFleet(
        store, 2,
        timeline=HollowTimeline(run_s=60.0, train=train,
                                train_stats_interval_s=0.1),
        capacity_chips=8, heartbeat_interval=0.5,
    )
    agg = GoodputAggregator(store, recorder, interval=0.1)
    out: Dict[str, Any] = {"metric": "stepstats_smoke", "ok": False}
    try:
        ctrl.run()
        sched.start()
        fleet.start()
        agg.start()
        client = TPUJobClient(store)
        for name, workers in (("stall", 2), ("skew", 3)):
            client.create({
                "kind": "TPUJob", "metadata": {"name": name},
                "spec": {
                    "slice": {"accelerator": "cpu", "chips_per_host": 1},
                    "worker": {"replicas": workers, "template": {
                        "containers": [{"image": "x",
                                        "command": ["train"]}]}},
                },
            })

        def telemetry(name):
            job = store.try_get("TPUJob", "default", name)
            return (job.status.train_telemetry or {}) if job else {}

        deadline = time.time() + 25.0
        dominant = straggler = ""
        while time.time() < deadline:
            dominant = telemetry("stall").get("dominant_stall", "")
            straggler = telemetry("skew").get("straggler", "")
            if dominant == "input" and straggler:
                break
            time.sleep(0.1)
        out["dominant_stall"] = dominant
        out["straggler"] = straggler
        out["goodput_stall"] = telemetry("stall").get("goodput")
        out["goodput_skew"] = telemetry("skew").get("goodput")
        # the skew Event must name the exact pod AND its node
        pod = store.try_get("Pod", "default", "skew-worker-1")
        node = pod.spec.node_name if pod else ""
        events = [
            e for e in store.list("Event")
            if e.reason == "Straggler"
            and "skew-worker-1" in e.message and node and node in e.message
        ]
        out["skew_event"] = bool(events)
        out["event_message"] = events[0].message if events else ""
        out["elapsed_s"] = round(time.time() - t0, 1)
        out["ok"] = bool(
            dominant == "input"
            and straggler.startswith("default/skew-worker-1")
            and events
            and 0.0 < (out["goodput_stall"] or 0.0) < 1.0
        )
        print(json.dumps(out), flush=True)
        return 0 if out["ok"] else 1
    finally:
        agg.stop()
        fleet.stop()
        sched.stop()
        ctrl.stop()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpu-stepstats",
        description="Workload step-stats plumbing (see module docstring); "
                    "--smoke runs the verify-gate goodput check.",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="<30s goodput smoke: seeded input-stall hollow "
                         "gang → dominant bucket 'input'; seeded "
                         "straggler → skew Event naming pod+node")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    if args.smoke:
        return smoke()
    ap.print_help()
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
