"""Hollow node agents: kubemark for the TPU control plane.

≙ kubernetes' kubemark/hollow-node: to measure the control plane at 1k
nodes / 10k jobs you do not need 1k machines — you need 1k agents that
exercise every CONTROL-PLANE path for real (watch, bind pickup, status
patch-batches, Node heartbeats) while faking only the one thing that
needs hardware: running the process. This module supplies that fake:

- :class:`HollowExecutor` duck-types the LocalExecutor surface the
  NodeAgent drives (start/stop/join_reapers/wait_idle/status_sink), but
  instead of ``subprocess.Popen`` it walks each claimed pod through a
  SCRIPTED phase timeline — Pending → Running after ``pending_s`` →
  Succeeded/Failed after ``run_s`` (seeded per-pod jitter + failure
  rate) — mirroring every transition through the SAME StatusBatcher /
  ``patch_pod_status`` machinery a real agent uses, so the store sees
  byte-identical traffic shapes and the chaos invariants
  (tests/invariants.py) hold over hollow trails too.
- ``NodeAgent(..., hollow=HollowTimeline(...))`` (the ``--hollow`` agent
  flag) runs the REAL agent loop — registration, heartbeat ticks, batch
  flushes, eviction handling — over a hollow executor: one process, one
  node, zero workload processes.
- :class:`HollowFleet` packs N hollow nodes into ONE process for the
  scale bench: a single shared watch (fan-in, not N long-polls), a
  single timer wheel (not N threads), heartbeats staggered across the
  interval and shipped in CHUNKED patch-batches together with the dirty
  pod mirrors — one host simulates 1k nodes / 100k pods against a real
  StoreServer (``BENCH_CP_MODES=scale``).

Run a fleet standalone against a live store::

  python -m mpi_operator_tpu.executor.hollow \\
      --store http://127.0.0.1:8475 --nodes 1000 --chips 32
"""

from __future__ import annotations

import heapq
import logging
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from mpi_operator_tpu.machinery.objects import (
    ANNOTATION_MAINTENANCE_AT,
    NODE_NAMESPACE,
    TRAIN_BUCKETS,
    Node,
    Pod,
    PodPhase,
    bounded_serve_stats,
    bounded_train_stats,
    patch_pod_status,
)
from mpi_operator_tpu.machinery.store import (
    ADDED,
    DELETED,
    MODIFIED,
    AlreadyExists,
    Conflict,
    NotFound,
)

log = logging.getLogger("tpujob.hollow")


@dataclass
class HollowTimeline:
    """The scripted pod lifecycle (≙ kubemark's pod lifecycle knobs).

    ``pending_s``: bind-pickup → Running delay (scheduler-visible launch
    latency). ``run_s`` + uniform ``run_jitter_s``: Running → terminal.
    ``failure_rate``: probability the terminal phase is Failed with
    ``failure_exit_code`` (drawn from a PER-POD rng seeded by ``seed`` +
    the pod's identity, so a rerun of the same fleet is deterministic).

    Serving pods (label ``tpujob.dev/job-role: serve``) follow a SECOND
    timeline: Pending → Running (ready=False) → ready after
    ``serve_warmup_s`` (the readiness gate — scripted model load) → stay
    Running forever, mirroring synthetic ``status.serve_stats`` samples
    every ``serve_stats_interval_s`` drawn from ``load`` (a shared
    :class:`ServeLoadModel`). No terminal transition: long-lived is the
    point.
    """

    pending_s: float = 0.0
    run_s: float = 0.2
    run_jitter_s: float = 0.0
    failure_rate: float = 0.0
    failure_exit_code: int = 1
    seed: int = 0
    serve_warmup_s: float = 0.2
    serve_stats_interval_s: float = 0.5
    load: Optional["ServeLoadModel"] = None
    # training telemetry (the workload telemetry plane, ISSUE 15): when a
    # TrainLoadModel is attached, every batch worker pod mirrors synthetic
    # ``status.train_stats`` blobs (stall-attributed bucket seconds + step
    # counters) every ``train_stats_interval_s`` — the hollow twin of the
    # real step loop's stepstats file, so goodput/straggler aggregation
    # benches at fleet scale with zero training processes
    train: Optional["TrainLoadModel"] = None
    train_stats_interval_s: float = 0.5
    # checkpoint-resume (the soak bench, ISSUE 18): when set, a batch
    # pod's scripted runtime is a stable per-POD total (seeded by pod
    # identity, not incarnation uid) and progress accrues across
    # incarnations — a checkpoint-then-migrated gang finishes the
    # REMAINDER of its work instead of starting over, which is the
    # operator's whole migration contract. Off by default: restart tests
    # rely on each incarnation re-running the full clock.
    checkpoint_resume: bool = False
    _ckpt_done: Dict[str, float] = field(default_factory=dict, repr=False)
    _ckpt_run_start: Dict[str, float] = field(default_factory=dict,
                                              repr=False)
    _ckpt_lock: threading.Lock = field(default_factory=threading.Lock,
                                       repr=False)

    def pod_rng(self, namespace: str, name: str, uid: str) -> random.Random:
        return random.Random(f"{self.seed}:{namespace}/{name}:{uid}")

    # -- checkpoint-resume bookkeeping (fleet-shared: a migrated pod
    # lands on a DIFFERENT node's executor, so progress lives here) -----

    def ckpt_remaining(self, key: str, total: float) -> float:
        with self._ckpt_lock:
            return max(0.05, total - self._ckpt_done.get(key, 0.0))

    def ckpt_mark_running(self, key: str) -> None:
        with self._ckpt_lock:
            self._ckpt_run_start.setdefault(key, time.monotonic())

    def ckpt_pause(self, key: str) -> None:
        """Pod torn down mid-run (eviction): bank the progress."""
        with self._ckpt_lock:
            t0 = self._ckpt_run_start.pop(key, None)
            if t0 is not None:
                self._ckpt_done[key] = (self._ckpt_done.get(key, 0.0)
                                        + (time.monotonic() - t0))

    def ckpt_finish(self, key: str) -> None:
        with self._ckpt_lock:
            self._ckpt_run_start.pop(key, None)
            self._ckpt_done.pop(key, None)


# serving-pod identity labels (duplicated string constants — the executor
# deliberately does not import the controller packages, same posture as
# the agent; controller/serve.py's tests pin the values stay identical)
LABEL_ROLE = "tpujob.dev/job-role"
LABEL_SERVE_NAME = "tpujob.dev/serve-name"
LABEL_JOB_NAME = "tpujob.dev/job-name"
ROLE_SERVE = "serve"


class ServeLoadModel:
    """Synthetic closed-loop serving load for hollow fleets.

    The bench's traffic generator declares OFFERED aggregate QPS per serve
    (``set_offered``); running hollow serving pods register themselves and
    draw their share (offered / registered pods) plus derived queue depth
    and p99 from an M/M/1-shaped utilization curve against
    ``capacity_qps`` per pod. The loop this closes is the real one the
    autoscaler lives in: more replicas → lower per-pod utilization →
    lower latency/queue → scale-down pressure, and vice versa — so a
    BENCH_CP_MODES=serve run exercises the actual feedback dynamics, not
    a canned metrics tape.
    """

    def __init__(self, *, capacity_qps: float = 100.0,
                 base_ms: float = 20.0):
        self.capacity_qps = capacity_qps
        self.base_ms = base_ms
        self._lock = threading.Lock()
        self._offered: Dict[str, float] = {}      # serve key → total QPS
        self._pods: Dict[str, set] = {}           # serve key → pod keys

    def set_offered(self, serve_key: str, qps: float) -> None:
        with self._lock:
            self._offered[serve_key] = max(0.0, qps)

    def offered(self, serve_key: str) -> float:
        with self._lock:
            return self._offered.get(serve_key, 0.0)

    def register(self, serve_key: str, pod_key: str) -> None:
        with self._lock:
            self._pods.setdefault(serve_key, set()).add(pod_key)

    def unregister(self, serve_key: str, pod_key: str) -> None:
        with self._lock:
            pods = self._pods.get(serve_key)
            if pods is not None:
                pods.discard(pod_key)
                if not pods:
                    del self._pods[serve_key]

    def serving_pods(self, serve_key: str) -> int:
        with self._lock:
            return len(self._pods.get(serve_key, ()))

    def sample(self, serve_key: str) -> Dict[str, float]:
        """One pod's current stats: its share of the offered load and the
        utilization-derived queue/latency (clamped — an overloaded pod
        reports a deep-but-finite queue, like a bounded request queue)."""
        with self._lock:
            offered = self._offered.get(serve_key, 0.0)
            n = len(self._pods.get(serve_key, ()))
        per_pod = offered / n if n else 0.0
        u = per_pod / self.capacity_qps if self.capacity_qps > 0 else 0.0
        if u < 0.95:
            queue = u / (1.0 - u)
        else:
            queue = 19.0 + (u - 0.95) * 200.0  # saturated: queue blows up
        queue = min(queue, 500.0)
        p99 = self.base_ms * (1.0 + 3.0 * u + queue)
        return {
            "qps": round(per_pod, 3),
            "queue_depth": round(queue, 3),
            "p99_ms": round(p99, 3),
        }


class TrainLoadModel:
    """Synthetic per-pod training timelines for hollow fleets — the batch
    twin of :class:`ServeLoadModel` (the workload telemetry plane,
    ISSUE 15).

    Each registered worker pod advances a seeded synthetic step clock on
    every stats tick: wall time splits into the TRAIN_BUCKETS scheme by
    a steady-state profile (mostly ``compute``), the first tick charges a
    one-shot ``compile`` phase, and two seeded fault knobs exist so the
    goodput aggregator has something real to attribute:

    - :meth:`set_stall` shifts a fraction of a whole JOB's step wall time
      into one named bucket (e.g. an input-pipeline stall: steps stretch
      and the stolen time accrues to ``input``);
    - :meth:`set_straggler` multiplies ONE pod's step time (a slow host:
      its step p50 diverges from the gang median — the skew signal).

    Cumulative counters are PER POD INCARNATION (keyed by pod uid at
    registration), so a relaunched gang restarts its counters from zero —
    exactly the counter-reset shape the aggregator's deltas must absorb.

    The persistent-compile-cache twin (ISSUE 16): the FIRST incarnation
    of a pod key charges the full ``compile_s`` (cold — jax writes the
    cache); every LATER incarnation of the same pod key charges only
    ``warm_compile_s`` (warm — the relaunch reads the node-local cache),
    and the blob's ``compile_cache`` field reports the matching hit/miss
    counts. Hollow restart benches therefore show the same
    restart_to_first_step_seconds collapse the real cache produces.
    """

    # steady-state wall-time split of a healthy step
    PROFILE = {"compute": 0.86, "input": 0.05, "sync": 0.06, "ckpt": 0.03}

    def __init__(self, *, step_ms: float = 50.0, compile_s: float = 1.0,
                 warm_compile_s: Optional[float] = None, seed: int = 0):
        self.step_ms = step_ms
        self.compile_s = compile_s
        # measured shape on the real CPU twin: a warm restart pays ~1/10
        # of the cold compile (deserialize + link, not recompile)
        self.warm_compile_s = (compile_s / 10.0 if warm_compile_s is None
                               else warm_compile_s)
        self.seed = seed
        # pod keys that have EVER finished a compile — deliberately NOT
        # per-uid: the cache dir outlives incarnations, that's the point
        self._warm: set = set()
        self._lock = threading.Lock()
        # (pod_key, uid) → {"steps": float, "buckets": {...}, "p50": ms}
        self._pods: Dict[tuple, Dict[str, Any]] = {}
        self._stalls: Dict[str, tuple] = {}       # job key → (bucket, frac)
        self._stragglers: Dict[str, float] = {}   # pod key → step factor

    def set_stall(self, job_key: str, bucket: str, fraction: float) -> None:
        if bucket not in TRAIN_BUCKETS:
            raise ValueError(f"unknown stall bucket {bucket!r} "
                             f"(one of {TRAIN_BUCKETS})")
        if not 0.0 < fraction < 1.0:
            raise ValueError("stall fraction must be in (0, 1)")
        with self._lock:
            self._stalls[job_key] = (bucket, fraction)

    def clear_stall(self, job_key: str) -> None:
        with self._lock:
            self._stalls.pop(job_key, None)

    def set_straggler(self, pod_key: str, factor: float) -> None:
        if factor <= 0:
            raise ValueError("straggler factor must be > 0")
        with self._lock:
            self._stragglers[pod_key] = factor

    def clear_straggler(self, pod_key: str) -> None:
        with self._lock:
            self._stragglers.pop(pod_key, None)

    def forget(self, pod_key: str, uid: str) -> None:
        with self._lock:
            self._pods.pop((pod_key, uid), None)

    def advance(self, job_key: str, pod_key: str, uid: str,
                dt: float) -> Dict[str, Any]:
        """Advance one pod's synthetic clock by ``dt`` wall seconds and
        return its bounded train_stats blob. Deterministic per (seed,
        pod identity): two runs of one seeded fleet produce identical
        tapes."""
        with self._lock:
            st = self._pods.get((pod_key, uid))
            if st is None:
                rng = random.Random(f"{self.seed}:{pod_key}:{uid}")
                # the compile-cache twin: a pod key that compiled before
                # restarts WARM (the node-local cache survived the pod)
                warm = pod_key in self._warm
                st = self._pods[(pod_key, uid)] = {
                    "steps": 0.0,
                    "buckets": {k: 0.0 for k in TRAIN_BUCKETS},
                    "jitter": 1.0 + rng.uniform(-0.03, 0.03),
                    "compiled": False,
                    "warm": warm,
                    "compile_s": (self.warm_compile_s if warm
                                  else self.compile_s),
                }
            stall = self._stalls.get(job_key)
            factor = self._stragglers.get(pod_key, 1.0)
        remaining = dt
        if not st["compiled"]:
            # one-shot compile charge at the head of the incarnation
            spent = min(st["compile_s"], remaining)
            st["buckets"]["compile"] += spent
            remaining -= spent
            if st["buckets"]["compile"] >= st["compile_s"] - 1e-9:
                st["compiled"] = True
                with self._lock:
                    self._warm.add(pod_key)
        base_s = self.step_ms / 1e3 * st["jitter"] * factor
        if stall is not None:
            # the stall steals `frac` of every step's wall time: the
            # effective step stretches and the stolen share accrues to
            # the named bucket
            bucket, frac = stall
            step_s = base_s / max(1e-9, 1.0 - frac)
        else:
            bucket, frac = "", 0.0
            step_s = base_s
        if remaining > 0:
            st["steps"] += remaining / step_s
            healthy = remaining * (base_s / step_s)
            for k, share in self.PROFILE.items():
                st["buckets"][k] += healthy * share
            if stall is not None:
                st["buckets"][bucket] += remaining - healthy
        p50 = step_s * 1e3
        return bounded_train_stats(
            step=int(st["steps"]), steps=int(st["steps"]),
            step_p50_ms=p50, buckets=st["buckets"],
            # mirror the real worker's warm-vs-cold signal: one synthetic
            # program, hit on a warm restart, missed on a cold start
            compile_cache={"hits": 1, "misses": 0} if st["warm"]
            else {"hits": 0, "misses": 1},
        )


@dataclass
class MaintenanceSchedule:
    """Seeded rolling-maintenance notices for a hollow fleet (ISSUE 14):
    the rehearsal harness for the disruption plane. ``fraction`` of the
    fleet (chosen by a seeded rng — two runs of one seed pick the same
    victims in the same order) receives a ``tpujob.dev/maintenance-at``
    notice: the first at ``start_s`` after fleet start, one more every
    ``stagger_s`` (the rolling wave), each with ``notice_s`` of warning
    before its deadline. The DrainController takes it from there."""

    fraction: float = 0.2
    notice_s: float = 10.0
    start_s: float = 2.0
    stagger_s: float = 0.5
    seed: int = 0

    def victims(self, node_names: List[str]) -> List[str]:
        k = max(1, round(self.fraction * len(node_names)))
        rng = random.Random(f"maintenance:{self.seed}")
        return rng.sample(sorted(node_names), min(k, len(node_names)))


class _TimerWheel:
    """One thread serving many scheduled callbacks (heapq): 100k hollow
    pods cannot afford a threading.Timer thread each. Handles are dicts
    with a ``cancelled`` flag — cancel is O(1), the heap entry is skipped
    at fire time.

    ``clock`` (anything with ``to_wall(virtual_seconds)``, e.g.
    ``machinery.scenario.VirtualClock``) lets callers schedule in
    SCENARIO time: ``schedule(delay, fn, virtual=True)`` converts the
    delay through the clock, so a compressed soak's maintenance wave
    fires at deterministic scenario offsets instead of wall-clock ones.
    """

    def __init__(self, clock: Any = None):
        self._cond = threading.Condition()
        self._heap: List[tuple] = []
        self._seq = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._clock = clock

    def start(self) -> "_TimerWheel":
        with self._cond:
            if self._thread is None:
                self._stop = False
                self._thread = threading.Thread(
                    target=self._run, name="hollow-timer-wheel", daemon=True
                )
                self._thread.start()
        return self

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
            t = self._thread
            self._thread = None
        if t is not None:
            t.join(timeout=2.0)

    def schedule(self, delay: float, fn, *,
                 virtual: bool = False) -> Dict[str, Any]:
        if virtual and self._clock is not None:
            delay = self._clock.to_wall(delay)
        handle = {"cancelled": False, "fn": fn}
        with self._cond:
            self._seq += 1
            heapq.heappush(
                self._heap, (time.monotonic() + max(0.0, delay),
                             self._seq, handle)
            )
            self._cond.notify()
        return handle

    @staticmethod
    def cancel(handle: Dict[str, Any]) -> None:
        handle["cancelled"] = True
        handle["fn"] = None  # drop the closure (and its pod) promptly

    def pending(self) -> int:
        with self._cond:
            return sum(
                1 for (_, _, h) in self._heap if not h["cancelled"]
            )

    def _run(self) -> None:
        while True:
            with self._cond:
                if self._stop:
                    return
                if not self._heap:
                    self._cond.wait(0.5)  # bounded: observes stop
                    continue
                due, _, handle = self._heap[0]
                now = time.monotonic()
                if due > now:
                    self._cond.wait(min(due - now, 0.5))
                    continue
                heapq.heappop(self._heap)
                fn = None if handle["cancelled"] else handle["fn"]
            if fn is None:
                continue
            try:
                fn()
            except Exception:
                # one pod's transition must not stall the whole wheel
                log.exception("hollow timer callback failed; continuing")


class HollowExecutor:
    """Scripted phase transitions behind the LocalExecutor surface.

    Claims pods exactly like the real executor (bound to ``node_name``,
    Pending), then walks them through the :class:`HollowTimeline` instead
    of spawning processes. Mirrors ride ``status_sink`` (the NodeAgent's
    StatusBatcher → one patch-batch per tick) when present, direct
    uid+rv-guarded ``patch_pod_status`` otherwise — the same write paths,
    guards included, as the real agent.
    """

    def __init__(self, store, *, node_name: str,
                 timeline: Optional[HollowTimeline] = None,
                 status_sink=None, wheel: Optional[_TimerWheel] = None,
                 external_events: bool = False,
                 logs_dir: str = ""):
        self.store = store
        self.node_name = node_name
        self.timeline = timeline or HollowTimeline()
        self.status_sink = status_sink
        self.logs_dir = logs_dir
        self.log_url_base: Optional[str] = None  # NodeAgent stamps; unused
        # fleet mode: the fleet owns ONE watch and routes events here via
        # handle_event() — N nodes, one long-poll, not N
        self._external_events = external_events
        self._own_wheel = wheel is None
        self._wheel = wheel or _TimerWheel()
        self._lock = threading.Lock()
        # pod key → uid of the incarnation whose timeline is scheduled or
        # finished (relist replays / duplicate deliveries are no-ops)
        self._seen: Dict[str, str] = {}
        # pod key → live wheel handles (cancelled on delete/evict)
        self._handles: Dict[str, List[Dict[str, Any]]] = {}
        # serving pods: pod key → its serve key (for load-model
        # unregistration) and pod key → the CURRENT recurring stats-tick
        # handle (replaced on every re-arm so handle lists stay bounded)
        self._serve_keys: Dict[str, str] = {}
        self._stats_handles: Dict[str, Dict[str, Any]] = {}
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._watch_q = None

    # -- lifecycle (the NodeAgent-driven surface) ---------------------------

    def start(self) -> None:
        self._wheel.start()
        if not self._external_events:
            self._watch_q = self.store.watch(None)
            t = threading.Thread(
                target=self._run, name=f"hollow-{self.node_name}",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
            # adopt pods bound before the watch began (level-triggered,
            # same as LocalExecutor.start's adoption pass)
            for pod in self.store.list("Pod"):
                self.observe(pod)

    def stop(self) -> None:
        self._stop.set()
        if self._watch_q is not None:
            self.store.stop_watch(self._watch_q)
        with self._lock:
            handles = [h for hs in self._handles.values() for h in hs]
            handles += list(self._stats_handles.values())
            self._handles.clear()
            self._stats_handles.clear()
        for h in handles:
            _TimerWheel.cancel(h)
        if self._own_wheel:
            self._wheel.stop()

    def join_reapers(self, timeout: float = 2.0) -> None:
        """No reap threads exist — transitions ride the timer wheel; the
        surface exists so NodeAgent.stop() runs unchanged."""

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Block until no scheduled transition is outstanding."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not any(
                    not h["cancelled"]
                    for hs in self._handles.values() for h in hs
                ):
                    return True
            time.sleep(0.02)
        return False

    # -- event intake -------------------------------------------------------

    def _run(self) -> None:
        import queue

        while not self._stop.is_set():
            try:
                ev = self._watch_q.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                self.handle_event(ev)
            except Exception:
                log.exception("hollow event handling failed; continuing")

    def handle_event(self, ev) -> None:
        """One watch event (fleet routing entry point)."""
        if ev.kind != "Pod":
            return
        if ev.type == DELETED:
            self._forget(ev.obj)
        elif ev.type in (ADDED, MODIFIED):
            self.observe(ev.obj)

    def observe(self, pod: Pod) -> None:
        """Level-triggered pickup: schedule the timeline for a newly bound
        incarnation; cancel it when the pod finished externally (eviction
        — the kubelet-kill equivalent: the 'process' dies with it)."""
        if pod.spec.node_name != self.node_name:
            return
        key = f"{pod.metadata.namespace}/{pod.metadata.name}"
        uid = pod.metadata.uid
        if pod.is_finished():
            # external terminal (monitor eviction, drain): kill the
            # scripted timeline exactly like a SIGKILL kills a process;
            # _seen keeps the uid so a relist replay cannot resurrect it
            with self._lock:
                self._seen[key] = uid
                handles = self._handles.pop(key, [])
                stats = self._stats_handles.pop(key, None)
                serve_key = self._serve_keys.pop(key, None)
            for h in handles:
                _TimerWheel.cancel(h)
            if stats is not None:
                _TimerWheel.cancel(stats)
            if serve_key is not None and self.timeline.load is not None:
                self.timeline.load.unregister(serve_key, key)
            if self.timeline.train is not None:
                self.timeline.train.forget(key, uid)
            return
        if pod.status.phase not in (PodPhase.PENDING, PodPhase.RUNNING):
            return
        with self._lock:
            if self._seen.get(key) == uid:
                return  # duplicate delivery / relist replay
            self._seen[key] = uid
            self._handles[key] = []
        # a pod already RUNNING on first sight is a restarted hollow
        # agent/fleet adopting its prior claims (the real agent's analog
        # is _evict_orphans — here the scripted 'process' can simply
        # resume): skip the Running mirror, arm only the terminal
        # transition, or the pod would stay Running forever
        self._schedule_timeline(
            pod, key, uid,
            already_running=pod.status.phase == PodPhase.RUNNING,
        )

    def _forget(self, pod: Pod) -> None:
        key = f"{pod.metadata.namespace}/{pod.metadata.name}"
        with self._lock:
            self._seen.pop(key, None)
            handles = self._handles.pop(key, [])
            stats = self._stats_handles.pop(key, None)
            serve_key = self._serve_keys.pop(key, None)
        for h in handles:
            _TimerWheel.cancel(h)
        if stats is not None:
            _TimerWheel.cancel(stats)
        if serve_key is not None and self.timeline.load is not None:
            self.timeline.load.unregister(serve_key, key)
        if self.timeline.train is not None:
            self.timeline.train.forget(key, pod.metadata.uid)
        if self.timeline.checkpoint_resume and serve_key is None:
            # torn down mid-run (eviction): bank the progress so the
            # replacement incarnation runs only the remainder (no-op if
            # the pod already reached terminal — ckpt_finish cleared it)
            self.timeline.ckpt_pause(key)

    # -- the scripted lifecycle ---------------------------------------------

    def _schedule_timeline(self, pod: Pod, key: str, uid: str,
                           already_running: bool = False) -> None:
        if pod.metadata.labels.get(LABEL_ROLE) == ROLE_SERVE:
            self._schedule_serve_timeline(pod, key, uid, already_running)
            return
        tl = self.timeline
        rng = tl.pod_rng(pod.metadata.namespace, pod.metadata.name, uid)
        failed = rng.random() < tl.failure_rate
        ns, name = pod.metadata.namespace, pod.metadata.name
        if tl.checkpoint_resume:
            # stable per-POD total seeded by identity (not incarnation
            # uid): every incarnation agrees on how much work the pod
            # holds, and a checkpoint-then-migrated replacement runs
            # only the remainder
            srng = tl.pod_rng(ns, name, "ckpt")
            total = tl.run_s + srng.uniform(0.0, tl.run_jitter_s)
            run_s = tl.ckpt_remaining(key, total)
        else:
            run_s = tl.run_s + rng.uniform(0.0, tl.run_jitter_s)
        rv = pod.metadata.resource_version or 0

        def to_running():
            if tl.checkpoint_resume:
                tl.ckpt_mark_running(key)
            self._mirror(ns, name, uid, rv, {
                "phase": PodPhase.RUNNING, "ready": True, "reason": "",
                "pod_ip": "127.0.0.1",
            })

        def train_tick():
            # synthetic train_stats mirror (workload telemetry, ISSUE 15):
            # rides the same recurring-handle discipline as serve stats —
            # the recurrence dies with the incarnation, never past it
            with self._lock:
                if self._seen.get(key) != uid or self._stop.is_set():
                    return
            tl_ = self.timeline
            job_key = f"{ns}/{pod.metadata.labels.get(LABEL_JOB_NAME, '')}"
            # advance() already emits the bounded shape; re-bounding at
            # the mirror edge keeps the blessed OBS004 form visible here
            stats = bounded_train_stats(**tl_.train.advance(
                job_key, key, uid, tl_.train_stats_interval_s))
            # rv=0: a stats mirror may always apply to the live
            # incarnation (same posture as the serve stats tick)
            self._mirror(ns, name, uid, 0, {"train_stats": stats})
            handle = self._wheel.schedule(tl_.train_stats_interval_s,
                                          train_tick)
            with self._lock:
                if self._seen.get(key) == uid:
                    self._stats_handles[key] = handle
                else:
                    _TimerWheel.cancel(handle)

        def to_terminal():
            with self._lock:
                if self._seen.get(key) != uid:
                    return  # deleted/recreated while the timer was armed
                self._handles.pop(key, None)
            if tl.checkpoint_resume:
                tl.ckpt_finish(key)
            if failed:
                self._mirror(ns, name, uid, rv, {
                    "phase": PodPhase.FAILED, "ready": False,
                    "reason": f"ExitCode{tl.failure_exit_code}",
                    "message": "hollow scripted failure",
                    "exit_code": tl.failure_exit_code,
                })
            else:
                self._mirror(ns, name, uid, rv, {
                    "phase": PodPhase.SUCCEEDED, "ready": False,
                    "reason": "", "exit_code": 0,
                })

        handles = []
        if not already_running:
            handles.append(self._wheel.schedule(tl.pending_s, to_running))
            handles.append(
                self._wheel.schedule(tl.pending_s + run_s, to_terminal)
            )
        else:
            # adopted mid-run: remaining runtime unknowable — restart the
            # scripted clock from now (a restarted real process would
            # also start over; under checkpoint_resume run_s is already
            # the banked remainder, so the clock starts accruing now)
            if tl.checkpoint_resume:
                tl.ckpt_mark_running(key)
            handles.append(self._wheel.schedule(run_s, to_terminal))
        stats_handle = None
        if tl.train is not None and pod.metadata.labels.get(LABEL_JOB_NAME):
            # first synthetic train_stats tick once the pod is "running";
            # the tick re-arms itself (replacing _stats_handles[key], the
            # serve-stats recurrence discipline)
            first_delay = (tl.train_stats_interval_s if already_running
                           else tl.pending_s + tl.train_stats_interval_s)
            stats_handle = self._wheel.schedule(first_delay, train_tick)
        with self._lock:
            if self._seen.get(key) == uid and key in self._handles:
                self._handles[key].extend(handles)
                if stats_handle is not None:
                    self._stats_handles[key] = stats_handle
            else:
                # evicted/deleted between scheduling and recording
                for h in handles:
                    _TimerWheel.cancel(h)
                if stats_handle is not None:
                    _TimerWheel.cancel(stats_handle)

    def _schedule_serve_timeline(self, pod: Pod, key: str, uid: str,
                                 already_running: bool = False) -> None:
        """The long-lived serving lifecycle: Running (not ready) →
        readiness gate after warmup → recurring synthetic serve_stats
        mirrors, forever. Termination only ever comes from OUTSIDE
        (eviction, drain, controller teardown) — handled by observe()'s
        finish branch like any kubelet kill."""
        tl = self.timeline
        ns, name = pod.metadata.namespace, pod.metadata.name
        rv = pod.metadata.resource_version or 0
        serve_key = f"{ns}/{pod.metadata.labels.get(LABEL_SERVE_NAME, '')}"
        with self._lock:
            self._serve_keys[key] = serve_key

        def stats_tick():
            with self._lock:
                if self._seen.get(key) != uid or self._stop.is_set():
                    return  # evicted/replaced: the recurrence dies here
            stats = bounded_serve_stats(
                **(tl.load.sample(serve_key) if tl.load is not None else {})
            )
            # rv=0: no precondition — a stats mirror may always apply to
            # the live incarnation (patch_pod_status still enforces the
            # uid + write-once-terminal guards on the re-read path)
            self._mirror(ns, name, uid, 0, {"serve_stats": stats})
            handle = self._wheel.schedule(tl.serve_stats_interval_s,
                                          stats_tick)
            with self._lock:
                if self._seen.get(key) == uid:
                    self._stats_handles[key] = handle
                else:
                    _TimerWheel.cancel(handle)

        def to_running():
            self._mirror(ns, name, uid, rv, {
                "phase": PodPhase.RUNNING, "ready": False, "reason": "",
                "pod_ip": "127.0.0.1",
            })

        def to_ready():
            with self._lock:
                if self._seen.get(key) != uid:
                    return
            if tl.load is not None:
                tl.load.register(serve_key, key)
            self._mirror(ns, name, uid, 0,
                         {"phase": PodPhase.RUNNING, "ready": True})
            stats_tick()

        handles = []
        if not already_running:
            handles.append(self._wheel.schedule(tl.pending_s, to_running))
            handles.append(self._wheel.schedule(
                tl.pending_s + tl.serve_warmup_s, to_ready))
        else:
            # adopted mid-serve (restarted fleet): the model is loaded;
            # re-register and resume the stats stream after one warmup
            handles.append(self._wheel.schedule(tl.serve_warmup_s, to_ready))
        with self._lock:
            if self._seen.get(key) == uid and key in self._handles:
                self._handles[key].extend(handles)
            else:
                for h in handles:
                    _TimerWheel.cancel(h)

    def _mirror(self, ns: str, name: str, uid: str, rv: int,
                changes: Dict[str, Any]) -> None:
        """One status transition, through the real write machinery: the
        batcher (one patch-batch per agent tick, Conflict fallback with
        incarnation + write-once-terminal guards) or the direct
        uid-pinned ``patch_pod_status`` path."""
        if self._stop.is_set():
            return
        if self.status_sink is not None:
            self.status_sink.enqueue(ns, name, uid, rv, changes)
            return
        try:
            patch_pod_status(
                self.store, ns, name, uid, changes, expected_rv=rv,
                what="hollow-mirror",
            )
        except Exception:
            log.warning("hollow mirror of %s/%s failed", ns, name,
                        exc_info=True)


class HollowFleet:
    """N hollow nodes in one process (the kubemark cluster shape).

    Shared machinery instead of N× everything: ONE store watch routed to
    per-node executors by ``spec.node_name``, ONE timer wheel, ONE
    StatusBatcher, and a flusher that ships Node heartbeats (staggered
    round the interval) together with the dirty pod mirrors as CHUNKED
    patch-batch requests — store load is O(transitions + nodes/interval)
    requests regardless of pod count, which is what lets one host drive
    1k nodes / 100k pods against a real StoreServer.
    """

    def __init__(self, store, nodes: int, *,
                 name_prefix: str = "hollow-",
                 timeline: Optional[HollowTimeline] = None,
                 capacity_chips: int = 32,
                 advertise: str = "127.0.0.1",
                 heartbeat_interval: float = 10.0,
                 batch_items: int = 256,
                 maintenance: Optional[MaintenanceSchedule] = None,
                 clock: Any = None):
        from mpi_operator_tpu.executor.agent import StatusBatcher

        self.store = store
        self.timeline = timeline or HollowTimeline()
        self.maintenance = maintenance
        self.capacity_chips = capacity_chips
        self.advertise = advertise
        self.heartbeat_interval = heartbeat_interval
        self.batch_items = batch_items
        # the scenario engine's time-scalable clock (VirtualClock duck
        # type: to_wall(virtual_s)); when set, MaintenanceSchedule knobs
        # are read as SCENARIO seconds — a 6-hour wave compresses into a
        # minutes-long deterministic run instead of a wall-clock one
        self.clock = clock
        self.node_names = [f"{name_prefix}{i:04d}" for i in range(nodes)]
        self._wake = threading.Event()
        self.batcher = StatusBatcher(on_dirty=self._wake.set)
        self.wheel = _TimerWheel(clock=clock)
        self.executors: Dict[str, HollowExecutor] = {
            name: HollowExecutor(
                store, node_name=name, timeline=self.timeline,
                status_sink=self.batcher, wheel=self.wheel,
                external_events=True,
            )
            for name in self.node_names
        }
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._watch_q = None
        # node → next heartbeat due (monotonic), staggered across the
        # interval so 1k nodes do not beat in one thundering tick
        self._hb_due: Dict[str, float] = {}
        self.stats = {"heartbeats": 0, "mirrors": 0, "batches": 0}

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "HollowFleet":
        self.wheel.start()
        for ex in self.executors.values():
            ex.start()  # external_events: no watch, just arms the wheel
        self._register_nodes()
        now = time.monotonic()
        n = max(1, len(self.node_names))
        for i, name in enumerate(self.node_names):
            self._hb_due[name] = now + (i / n) * self.heartbeat_interval
        self._watch_q = self.store.watch(None)
        pump = threading.Thread(
            target=self._pump, name="hollow-fleet-pump", daemon=True
        )
        flush = threading.Thread(
            target=self._flush_loop, name="hollow-fleet-flush", daemon=True
        )
        pump.start()
        flush.start()
        self._threads += [pump, flush]
        # adopt pods bound before the watch began
        for pod in self.store.list("Pod"):
            ex = self.executors.get(pod.spec.node_name or "")
            if ex is not None:
                ex.observe(pod)
        if self.maintenance is not None:
            self.arm_maintenance(self.maintenance)
        log.info("hollow fleet up: %d nodes, %d chips each",
                 len(self.node_names), self.capacity_chips)
        return self

    def arm_maintenance(self, sched: MaintenanceSchedule) -> None:
        """Schedule the rolling notice wave on the shared timer wheel
        (``start_s`` counts from THIS call — benches arm it once the
        workload is live instead of at fleet start). With a scenario
        ``clock``, every schedule knob — start, stagger, AND the notice
        window itself — is scenario time: the wave's shape is invariant
        under ``--time-scale``, which is what makes compressed multi-hour
        soaks deterministic."""
        for i, name in enumerate(sched.victims(self.node_names)):
            delay = sched.start_s + i * sched.stagger_s

            def fire(node=name, notice=sched.notice_s):
                wall_notice = (self.clock.to_wall(notice)
                               if self.clock is not None else notice)
                try:
                    self.announce_maintenance(node,
                                              time.time() + wall_notice)
                except Exception:
                    log.warning("maintenance notice for %s failed", node,
                                exc_info=True)

            self.wheel.schedule(delay, fire, virtual=True)

    def announce_maintenance(self, node: str, at_ts: float) -> None:
        """Stamp the maintenance-notice annotation (the cloud provider's
        'this host dies at T' event, as the disruption plane consumes it).
        Metadata patch → needs an admin-tier store handle."""
        self.store.patch(
            "Node", NODE_NAMESPACE, node,
            {"metadata": {"annotations": {
                ANNOTATION_MAINTENANCE_AT: str(at_ts),
            }}},
        )
        log.info("maintenance notice: node %s dies at %.0f", node, at_ts)

    def kill_node(self, name: str) -> None:
        """Drop one hollow node dead, mid-flight (the spot-reclaim /
        host-loss fault): its executor stops (every armed pod transition
        cancelled — the 'processes' die with the host), its heartbeats
        cease (the monitor will see it go stale), and events are no
        longer routed to it. The Node object is NOT deleted and nothing
        is mirrored — a reclaimed host does not get to say goodbye; the
        control plane must notice on its own."""
        ex = self.executors.pop(name, None)
        if ex is None:
            raise KeyError(f"no hollow node {name!r} in this fleet")
        self._hb_due.pop(name, None)
        ex.stop()
        log.warning("hollow node %s killed (no further heartbeats)", name)

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._watch_q is not None:
            self.store.stop_watch(self._watch_q)
        for t in self._threads:
            t.join(timeout=5.0)
        for ex in self.executors.values():
            ex.stop()
        self.wheel.stop()

    # -- internals -----------------------------------------------------------

    def _node_status(self, name: str) -> Dict[str, Any]:
        return {
            "address": self.advertise,
            "capacity_chips": self.capacity_chips,
            "ready": True,
            "last_heartbeat": time.time(),
        }

    def _register_nodes(self) -> None:
        for name in self.node_names:
            node = Node()
            node.metadata.namespace = NODE_NAMESPACE
            node.metadata.name = name
            node.status.address = self.advertise
            node.status.capacity_chips = self.capacity_chips
            node.status.ready = True
            node.status.last_heartbeat = time.time()
            try:
                self.store.create(node)
            except AlreadyExists:
                # restarted fleet: the first heartbeat patch refreshes it
                pass

    def _pump(self) -> None:
        import queue

        while not self._stop.is_set():
            try:
                ev = self._watch_q.get(timeout=0.2)
            except queue.Empty:
                continue
            if ev.kind != "Pod":
                continue
            try:
                # oplint: disable=LEV001 — the hollow kubelet is an
                # edge-driven simulator routing each delivery to the
                # executor that owns its node; on a DELETED edge the
                # object is already gone, so the delivered payload is the
                # ONLY place node_name still exists (a re-read would 404
                # and strand the teardown)
                ex = self.executors.get(ev.obj.spec.node_name or "")
                if ex is not None:
                    ex.handle_event(ev)
            except Exception:
                log.exception("hollow fleet routing failed; continuing")

    def _flush_loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(timeout=0.1)
            self._wake.clear()
            if self._stop.is_set():
                return
            try:
                self._flush_once()
            except Exception:
                # store briefly unreachable past the client's retry window:
                # mirrors were requeued, heartbeats re-due next pass
                log.warning("hollow fleet flush failed; retrying",
                            exc_info=True)

    def _flush_once(self) -> None:
        now = time.monotonic()
        hb_nodes = [n for n, due in self._hb_due.items() if due <= now]
        entries = self.batcher.drain()
        if not hb_nodes and not entries:
            return
        # (wire item, originating batcher entry | None-for-heartbeats)
        tagged: List[tuple] = []
        for n in hb_nodes:
            self._hb_due[n] = now + self.heartbeat_interval
            tagged.append(({
                "kind": "Node", "namespace": NODE_NAMESPACE, "name": n,
                "subresource": "status",
                "patch": {"status": self._node_status(n)},
            }, None))
        for e in entries:
            patch: Dict[str, Any] = {"status": e["changes"]}
            if e["rv"]:
                patch["metadata"] = {"resource_version": e["rv"]}
            tagged.append(({
                "kind": "Pod", "namespace": e["namespace"],
                "name": e["name"], "subresource": "status", "patch": patch,
            }, e))
        self.stats["heartbeats"] += len(hb_nodes)
        self.stats["mirrors"] += len(entries)
        # chunked: one giant 100k-item batch would stall the store's
        # handler (and every other tenant) for its whole apply
        for ofs in range(0, len(tagged), self.batch_items):
            chunk = tagged[ofs:ofs + self.batch_items]
            self.stats["batches"] += 1
            try:
                results = self.store.patch_batch([it for it, _ in chunk])
            except Exception:
                # the REQUEST failed: nothing in this or later chunks
                # committed — requeue their mirrors for the next pass and
                # re-due EVERY heartbeat this pass claimed (it was marked
                # sent before the wire attempt; leaving it for a full
                # interval could flap the node past the monitor's grace —
                # a redundant re-send is an idempotent status patch)
                self.batcher.requeue(
                    [e for _, e in tagged[ofs:] if e is not None]
                )
                for n in hb_nodes:
                    self._hb_due[n] = now
                raise
            for (_item, e), res in zip(chunk, results):
                if e is None:
                    continue  # heartbeat misses self-heal next beat
                self._settle_pod(e, res)

    def _settle_pod(self, e: Dict[str, Any], res: Any) -> None:
        """Per-item result handling — the NodeAgent._tick contract:
        Conflict → guarded re-read via patch_pod_status (incarnation +
        write-once-terminal checks), NotFound → the pod is gone, forget
        its anchor."""
        try:
            if isinstance(res, Conflict):
                committed = patch_pod_status(
                    self.store, e["namespace"], e["name"], e["uid"],
                    e["changes"], what="hollow-fleet-mirror",
                )
                if committed is not None:
                    self.batcher.note_committed(e, committed)
            elif isinstance(res, NotFound):
                self.batcher.forget(e["namespace"], e["name"])
            elif isinstance(res, Exception):
                log.warning("hollow mirror of %s/%s rejected: %s",
                            e["namespace"], e["name"], res)
            else:
                self.batcher.note_committed(e, res)
        except Exception:
            self.batcher.requeue([e])
            raise


class HollowNodeTarget:
    """One hollow node as a chaos process target (the ``targets=`` duck
    type ChaosController kills): ``reclaim``/``maintenance-fire`` against
    a hollow fleet SIGKILL nothing — they call :meth:`HollowFleet.
    kill_node`, which is the same observable event (heartbeats stop,
    armed pod transitions die) without a process to kill."""

    def __init__(self, fleet: HollowFleet, node: str):
        self.fleet = fleet
        self.node = node

    def kill(self) -> None:
        self.fleet.kill_node(self.node)

    def term(self) -> None:
        self.kill()

    def restart(self) -> None:
        raise RuntimeError("a reclaimed hollow node does not come back")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpu-hollow-fleet",
        description="Simulate N hollow nodes against a live store "
                    "(kubemark for the TPU control plane).",
    )
    ap.add_argument("--store", required=True,
                    help="the shared store ('http://HOST:PORT')")
    ap.add_argument("--nodes", type=int, default=100)
    ap.add_argument("--chips", type=int, default=32)
    ap.add_argument("--prefix", default="hollow-")
    ap.add_argument("--heartbeat", type=float, default=10.0)
    ap.add_argument("--run-s", type=float, default=0.5,
                    help="scripted Running duration per pod")
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch-items", type=int, default=128,
                    help="max patches per batch request flush")
    ap.add_argument("--maintenance-fraction", type=float, default=0.0,
                    help="fraction of the fleet that receives a seeded "
                         "rolling maintenance notice (0 = none)")
    ap.add_argument("--maintenance-notice", type=float, default=10.0,
                    help="seconds of warning each notice carries before "
                         "its deadline")
    ap.add_argument("--maintenance-start", type=float, default=5.0,
                    help="seconds after fleet start the first notice fires")
    ap.add_argument("--maintenance-stagger", type=float, default=0.5,
                    help="seconds between successive notices (the wave)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="scenario seconds per wall second (>1 compresses "
                         "the maintenance wave: its knobs are read as "
                         "SCENARIO time, so a multi-hour wave replays "
                         "deterministically in minutes)")
    ap.add_argument("--token-file", default=None)
    ap.add_argument("--monitoring-port", type=int, default=None,
                    help="serve /metrics + /healthz on this port (agent "
                         "tick latency etc. — the SLO monitor scrapes the "
                         "fleet process like any other); default: off")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    from mpi_operator_tpu.machinery.http_store import (
        HttpStoreClient,
        read_token_file,
    )

    # a generous request timeout: one chunked flush against a store busy
    # with a 10k-job storm may legitimately take several seconds
    store = HttpStoreClient(args.store, timeout=60.0,
                            token=read_token_file(args.token_file))
    clock = None
    if args.time_scale != 1.0:
        from mpi_operator_tpu.machinery.scenario import VirtualClock

        clock = VirtualClock(scale=args.time_scale)
    fleet = HollowFleet(
        store, args.nodes, name_prefix=args.prefix,
        timeline=HollowTimeline(run_s=args.run_s,
                                failure_rate=args.failure_rate,
                                seed=args.seed),
        capacity_chips=args.chips, heartbeat_interval=args.heartbeat,
        batch_items=args.batch_items, clock=clock,
        maintenance=(
            MaintenanceSchedule(
                fraction=args.maintenance_fraction,
                notice_s=args.maintenance_notice,
                start_s=args.maintenance_start,
                stagger_s=args.maintenance_stagger,
                seed=args.seed,
            )
            if args.maintenance_fraction > 0 else None
        ),
    ).start()
    ops = None
    if args.monitoring_port is not None:
        from mpi_operator_tpu.opshell.server import OpsServer

        ops = OpsServer(args.monitoring_port)
        ops.start()
        logging.info("metrics on :%d/metrics", ops.port)
    print(f"hollow fleet of {args.nodes} nodes running", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    if ops is not None:
        ops.stop()
    fleet.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
