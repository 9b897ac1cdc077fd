"""TPUJob controller: level-triggered reconciliation.

≙ /root/reference/v2/pkg/controller/mpi_job_controller.go (1531 LoC, the core
of the reference operator). The reconcile contract is preserved:

  syncHandler (:443-608): lister get → deepcopy → default → validate →
  finished-cleanup → dependents (service, config, gang, workers) → status
  mirror — all idempotent getOrCreate with ownership adoption checks
  (:625-631, :730-734), driven by a rate-limited workqueue fed by watches on
  the job and every owned kind (handleObject :300-339).

TPU-first redesign (SURVEY.md §7.3-4):
- **Launcher-less**: no launcher pod, no SSH secret, no kubectl-delivery.
  Worker 0 is the coordinator; its exit status plays the role the launcher's
  does in updateMPIJobStatus (:921-996).
- **Bootstrap = env injection**: instead of hostfiles + OMPI_MCA_* env
  (:176-200) the controller injects TPUJOB_* rendezvous env (coordinator
  address, host id/count, slice geometry) consumed by
  runtime/bootstrap.py — the jax.distributed.initialize contract.
- **Gang = slice placement**: a PodGroup with min_member == workers (no +1 —
  there is no launcher) plus ICI-topology host coordinates stamped on every
  pod (controller/placement.py).
- **RunPolicy is actually implemented** (suspend, backoffLimit,
  activeDeadlineSeconds, ttlSecondsAfterFinished) — the reference declares it
  but its v1/v2 controllers never read it (SURVEY.md §2.2, §5.3).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional

from mpi_operator_tpu.api import conditions as cond
from mpi_operator_tpu.api.defaults import set_defaults
from mpi_operator_tpu.api.types import (
    CleanPodPolicy,
    ConditionType,
    Container,
    ObjectMeta,
    OwnerReference,
    ReplicaStatus,
    ReplicaType,
    RestartPolicy,
    TPUJob,
)
from mpi_operator_tpu.api.validation import validate_tpujob
from mpi_operator_tpu.controller.placement import (
    PlacementError,
    SlicePlacement,
    place_workers,
)
from mpi_operator_tpu.machinery import trace
from mpi_operator_tpu.machinery.events import NORMAL, WARNING, EventRecorder
from mpi_operator_tpu.machinery.objects import (
    ANNOTATION_PROFILE_REQUEST,
    REASON_MAINTENANCE,
    ConfigMap,
    Pod,
    PodGroup,
    PodGroupSpec,
    PodPhase,
    PodSpec,
    Service,
    ServiceSpec,
)
from mpi_operator_tpu.machinery.cache import InformerCache
from mpi_operator_tpu.machinery.store import (
    AlreadyExists,
    Conflict,
    NotFound,
    ObjectStore,
    WatchEvent,
    diff_merge_patch,
)
from mpi_operator_tpu.machinery.workqueue import (
    RateLimitingQueue,
    ShardedRateLimitingQueue,
)
from mpi_operator_tpu.opshell import metrics

log = logging.getLogger("tpujob.controller")

# Pod labels (≙ the group/job/replica labels of newWorker :1246-1260)
LABEL_JOB_NAME = "tpujob.dev/job-name"
LABEL_ROLE = "tpujob.dev/job-role"
LABEL_REPLICA_INDEX = "tpujob.dev/replica-index"
# restart generation the pod was launched for (status.restart_count at
# creation): the observable that lets the chaos invariant checker prove
# "at most one gang generation launching at a time" from the event trail
# alone (tests/invariants.py) — without it, two overlapping generations
# are indistinguishable from one
LABEL_GENERATION = "tpujob.dev/generation"
ROLE_WORKER = "worker"

# Rendezvous env contract (≙ the OMPI/Intel env of :176-200; consumed by
# runtime/bootstrap.py the way mpirun consumes the hostfile env).
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
# spec.compile_cache projection ("1"/"0", ISSUE 16): the WORKER reads this
# gate at bootstrap (runtime/compile_cache.configure_from_env) and turns
# jax's persistent compilation cache off for the job when it is "0"
ENV_COMPILE_CACHE = "TPUJOB_COMPILE_CACHE"

DEFAULT_COORDINATOR_PORT = 8476

# Deliberately duplicated from ops/elastic.py (EXIT_RESTART): the controller
# must not import the jax-heavy training stack. tests/test_controller.py
# asserts the two stay identical.
EXIT_RESTART = 75

# ConfigMap keys (≙ hostfile / discover_hosts.sh, :1088-1138)
CONFIG_HOSTFILE = "hostfile"
CONFIG_DISCOVER_HOSTS = "discover_hosts.sh"
CONFIG_COORDINATOR = "coordinator"
# the on-demand profiling channel (ISSUE 15): the tpujob.dev/profile-
# request annotation, projected verbatim into the config dir the elastic
# membership check already polls — stamping the annotation reaches every
# worker through the SAME file-sync path a rescale does
CONFIG_PROFILE = "profile"

EVENT_VALIDATION_ERROR = "ValidationError"
EVENT_PLACEMENT_ERROR = "PlacementError"


@dataclass
class ControllerOptions:
    """≙ the operator flags (v2/cmd/mpi-operator/app/options/options.go:46-74)."""

    namespace: Optional[str] = None  # None = cluster-scoped
    threadiness: int = 2
    # workqueue shard count (the 10k-job dispatch bottleneck fix): None =
    # one shard per worker thread (dispatch parallelism tracks the pool),
    # 1 = the classic single RateLimitingQueue, N = explicit. Same key
    # never processed concurrently regardless of the shape.
    queue_shards: Optional[int] = None
    coordinator_port: int = DEFAULT_COORDINATOR_PORT
    gang_scheduling: bool = True
    # Event TTL sweep (the controller's housekeeping pass): Events older
    # than this are pruned — kube's apiserver does the same (default 1h),
    # and without it the append-only audit stream grows the store without
    # bound. None disables (embedded/test controllers keep full trails);
    # the operator CLI turns it on by default.
    event_ttl: Optional[float] = None
    event_gc_interval: float = 60.0


class TPUJobController:
    """Level-triggered reconciler over an ObjectStore.

    ≙ MPIJobController (mpi_job_controller.go:208-245). ``_write_status`` is
    the injectable status-update hook the reference exposes for tests
    (updateStatusHandler field :243-244).
    """

    def __init__(
        self,
        store: ObjectStore,
        recorder: Optional[EventRecorder] = None,
        options: Optional[ControllerOptions] = None,
        cache: Optional["InformerCache"] = None,
    ):
        self.store = store
        # informer-style read path (≙ the listers syncHandler reads instead
        # of the apiserver): when a started InformerCache is supplied, every
        # read goes to it — writes still hit the store, and the cache
        # observes them through its watch, exactly like client-go. Without
        # one, reads fall through to the store (tests, runlocal).
        self.cache = cache
        self.read = cache if cache is not None else store
        self.options = options or ControllerOptions()
        self.recorder = recorder or EventRecorder(store)
        shards = self.options.queue_shards
        if shards is None:
            shards = max(1, self.options.threadiness)
        self.queue = (
            ShardedRateLimitingQueue(shards) if shards > 1
            else RateLimitingQueue()
        )
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()
        self._watch_q = None
        # (job uid, restart generation) pairs already warned about a
        # non-retryable drain-wait — the event fires once per generation
        self._drain_noted: set = set()
        # injectable, ≙ updateStatusHandler (:243-244)
        self._write_status = self._default_write_status
        # in-flight port reservations: two reconcile threads assigning ports
        # concurrently must not both pick the same one before either status
        # persists (cleared when the job disappears)
        self._port_lock = threading.Lock()
        self._ports_inflight: Dict[str, int] = {}
        # TTL-cached TPUJob snapshot for port probing (see
        # _assign_coordinator_port): (jobs, taken_at_monotonic) or None
        self._ports_snapshot = None
        # job key → span context of the latest watch write that enqueued
        # it: the reconcile span's causal parent ("why did this reconcile
        # run"). Last-writer-wins per key matches the workqueue's own
        # coalescing; popped at reconcile start, bounded by live keys.
        self._trace_lock = threading.Lock()
        self._trace_links: Dict[str, object] = {}
        # job uid → trace id this controller stamped (bounded memo; see
        # _ensure_trace_id)
        self._stamped_traces: Dict[str, str] = {}

    # ------------------------------------------------------------------
    # run loop (≙ Run + runWorker + processNextWorkItem :347-438)
    # ------------------------------------------------------------------

    def run(self) -> None:
        """Start the watch pump + worker threads. Non-blocking; stop()."""
        if self.cache is not None:
            # the workqueue is fed FROM the informer (≙ the event handlers
            # client-go registers on the SharedInformer, :300-339): handler
            # callbacks fire only after the cache applied the event, so a
            # worker dequeuing the key is guaranteed a cache at-or-after
            # that event. A separate direct store watch could enqueue a
            # fresh job BEFORE the cache observed it — the worker's cache
            # miss would read as "deleted", return success, and nothing
            # would ever re-enqueue it.
            self.cache.add_event_handler(
                lambda etype, obj: self._pump_obj(obj)
            )
        else:
            self._watch_q = self.store.watch(None)
            pump = threading.Thread(
                target=self._pump, name="tpujob-watch-pump", daemon=True
            )
            pump.start()
            self._threads.append(pump)
        for i in range(self.options.threadiness):
            t = threading.Thread(
                target=self._run_worker, args=(i,),
                name=f"tpujob-worker-{i}", daemon=True,
            )
            t.start()
            self._threads.append(t)
        # prime: enqueue all existing jobs (informer initial list) — from
        # the cache once it has synced (≙ WaitForCacheSync before workers)
        prime = threading.Thread(target=self._prime, name="tpujob-prime", daemon=True)
        prime.start()
        self._threads.append(prime)
        if self.options.event_ttl is not None:
            hk = threading.Thread(
                target=self._housekeeping_loop, name="tpujob-housekeeping",
                daemon=True,
            )
            hk.start()
            self._threads.append(hk)

    def _wait_cache_synced(self) -> bool:
        """Block until the informer cache (if any) has its initial snapshot,
        or stop() was called. True = safe to reconcile."""
        if self.cache is None:
            return True
        while not self._stop.is_set():
            if self.cache.wait_for_sync(0.2):
                return True
        return False

    def _prime(self) -> None:
        if not self._wait_cache_synced():
            return
        for job in self.read.list("TPUJob", self.options.namespace):
            self.enqueue(job.metadata.key())

    def stop(self) -> None:
        self._stop.set()
        self.queue.shut_down()
        if self._watch_q is not None:
            self.store.stop_watch(self._watch_q)
        for t in self._threads:
            t.join(timeout=5)

    def enqueue(self, key: str) -> None:
        self.queue.add(key)

    def _pump(self) -> None:
        """Direct-watch pump (cache-less wiring only): watch events → job
        keys (≙ the event handlers of :300-339)."""
        while not self._stop.is_set():
            try:
                ev: WatchEvent = self._watch_q.get(timeout=0.2)
            except queue.Empty:
                continue
            if ev.kind == "Event":
                continue
            # same delivery-context contract the informer path gets from
            # the cache drain: the handler sees the event's origin span
            trace.set_delivery(getattr(ev, "trace", None))
            try:
                self._pump_obj(ev.obj)
            finally:
                trace.clear_delivery()

    def _pump_obj(self, obj) -> None:
        """One object observation → the TPUJob key to reconcile (job events
        enqueue directly; owned-object events enqueue the controller owner
        via the handleObject rule)."""
        ns = obj.metadata.namespace
        if self.options.namespace is not None and ns != self.options.namespace:
            return
        if obj.kind == "TPUJob":
            self._note_trigger(obj.metadata.key())
            self.enqueue(obj.metadata.key())
            return
        owner = self._controller_owner(obj)
        if owner is not None:
            self._note_trigger(f"{ns}/{owner.name}")
            self.enqueue(f"{ns}/{owner.name}")

    def _note_trigger(self, key: str) -> None:
        """Remember the delivering watch event's origin span (if any) as
        the causal parent of the reconcile this enqueue wakes."""
        link = trace.get_delivery()
        if link is not None:
            with self._trace_lock:
                self._trace_links[key] = link

    @staticmethod
    def _controller_owner(obj) -> Optional[OwnerReference]:
        for ref in obj.metadata.owner_references:
            if ref.controller and ref.kind == "TPUJob":
                return ref
        return None

    def _run_worker(self, worker: int = 0) -> None:
        # a worker reconciling against a cold cache would observe an empty
        # world — and e.g. recreate every pod of a live job (AlreadyExists
        # storms) or mark a running job freshly Created
        if not self._wait_cache_synced():
            return
        while True:
            # bounded get (oplint BLK001): the old unbounded get() relied on
            # shut_down()'s notify_all alone to ever unblock this thread —
            # a stop() racing a worker BETWEEN its loop check and the wait
            # was safe, but any future stop path that forgets shut_down()
            # (or a queue bug swallowing the wake) parked the worker forever
            # with no way to observe _stop. The watch pump at _pump already
            # polls at 0.2s for exactly this reason. ``worker`` is the
            # sharded queue's home-shard index (ignored by the single queue).
            key = self.queue.get(timeout=0.2, shard=worker)
            if key is None:
                if self._stop.is_set() or self.queue.shutting_down:
                    return
                continue
            try:
                # sync_handler owns the Conflict/AlreadyExists → requeue
                # mapping (stale cached reads); only unexpected errors
                # reach the backstop below
                ok = self.sync_handler(key)
            except Exception:
                log.exception("sync %s failed", key)
                ok = False
            if ok:
                self.queue.forget(key)
            else:
                self.queue.add_rate_limited(key)
            self.queue.done(key)

    # ------------------------------------------------------------------
    # reconcile (≙ syncHandler :443-608)
    # ------------------------------------------------------------------

    def sync_handler(self, key: str) -> bool:
        """One reconcile. Returns True on success (forget), False to requeue
        (≙ syncHandler returning err → AddRateLimited in processNextWorkItem
        :381-438; Conflicts and ownership errors both requeue).

        The reconcile runs under a ``controller.reconcile`` span parented
        on the watch write that enqueued this key (the causal "why"), and
        its wall time lands in the reconcile-latency histogram where the
        span closes."""
        with self._trace_lock:
            link = self._trace_links.pop(key, None)
        t0 = time.perf_counter()
        try:
            with trace.start_span(
                "controller.reconcile", parent=link, attrs={"job": key}
            ):
                return self._sync(key)
        except (Conflict, AlreadyExists):
            # Conflict: stale read lost an update race. AlreadyExists: the
            # cache had not yet observed a dependent this controller created
            # moments ago (the informer lag client-go controllers absorb the
            # same way) — requeue; the rate limiter spaces the retry past
            # the watch latency.
            return False
        except RuntimeError as e:
            log.warning("sync %s: %s", key, e)
            return False
        finally:
            dt = time.perf_counter() - t0
            metrics.reconcile_latency.observe(dt)
            log.debug("sync %s took %.1fms", key, dt * 1e3)

    def _sync(self, key: str) -> bool:
        namespace, name = key.split("/", 1)
        job = self.read.try_get("TPUJob", namespace, name)
        if job is None:
            with self._port_lock:  # release the port reservation
                self._ports_inflight.pop(key, None)
            # ≙ the kube garbage collector's cascade delete: the job is
            # gone, so every dependent it owned must go too. Before this,
            # deleting a RUNNING job stranded its pods (and their worker
            # processes) forever — the orphan the chaos invariant checker
            # flags (tests/invariants.py no_orphaned_dependents).
            self._reap_orphans(namespace, name)
            return True  # deleted; nothing left to do (≙ :460-467)
        set_defaults(job)  # store returned a deep copy (≙ DeepCopy + Default :470-475)

        errs = validate_tpujob(job)
        if errs:
            # invalid specs are dropped, not requeued (≙ :482-487)
            self.recorder.event(job, WARNING, EVENT_VALIDATION_ERROR, "; ".join(errs))
            return True

        if not cond.is_finished(job.status):
            self._ensure_trace_id(job)

        workers = self._list_workers(job)

        if cond.is_finished(job.status):
            self._cleanup_finished(job, workers)
            return True

        # --- suspend (RunPolicy.Suspend; implemented, unlike the reference) ---
        if job.spec.run_policy.suspend:
            return self._sync_suspended(job, workers)
        if cond.is_suspended(job.status):
            cond.update_job_conditions(
                job.status, ConditionType.SUSPENDED, cond.REASON_RESUMED, "resumed", False
            )
            self.recorder.event(job, NORMAL, cond.REASON_RESUMED, "job resumed")

        # --- Created condition + start time (≙ :532-543) ---
        if cond.update_job_conditions(
            job.status,
            ConditionType.CREATED,
            cond.REASON_CREATED,
            f"TPUJob {key} is created",
        ):
            metrics.jobs_created.inc()
            self.recorder.event(job, NORMAL, cond.REASON_CREATED, "job created")
        cond.ensure_timestamps(job.status)

        # --- activeDeadlineSeconds (RunPolicy; SURVEY.md §5.3 gap, closed) ---
        deadline = job.spec.run_policy.active_deadline_seconds
        if (
            deadline is not None
            and job.status.start_time is not None
            and time.time() - job.status.start_time > deadline
        ):
            self._fail_job(
                job,
                workers,
                cond.REASON_DEADLINE,
                f"job exceeded activeDeadlineSeconds={deadline}",
            )
            return self._write_status(job)

        # --- gang placement (≙ getOrCreatePodGroups :572-576 + ICI layout) ---
        try:
            placement = place_workers(job.spec.slice, job.spec.worker.replicas)
        except PlacementError as e:
            self.recorder.event(job, WARNING, EVENT_PLACEMENT_ERROR, str(e))
            return True  # spec problem: drop like a validation error

        # --- dependents, all idempotent getOrCreate ---
        self._get_or_create_service(job)
        self._get_or_create_configmap(job, workers)
        if self.options.gang_scheduling:
            self._get_or_create_podgroup(job)
        workers = self._reconcile_workers(job, placement)

        # --- status mirror (≙ updateMPIJobStatus call :602) ---
        self._update_status(job, workers)
        return self._write_status(job)

    def _ensure_trace_id(self, job: TPUJob) -> None:
        """The job's trace anchor: admission (api/client.py) stamps the
        ``tpujob.dev/trace-id`` annotation; this backstop covers jobs
        created straight through the store (tests, benches, old clients).
        Either way, the current reconcile span re-homes into the job's
        trace so everything this pass causes groups under it."""
        tid = job.metadata.annotations.get(trace.ANNOTATION_TRACE_ID)
        if not tid:
            # memo by uid: a cached read lagging our own stamp must reuse
            # the minted id, not write a fresh one per reconcile until the
            # informer echo lands (under _trace_lock — worker threads
            # trimming the bounded memo concurrently must not double-pop)
            with self._trace_lock:
                tid = self._stamped_traces.get(job.metadata.uid)
        if not tid:
            tid = trace.new_trace_id()
            try:
                self.store.patch(
                    "TPUJob", job.namespace, job.name,
                    # uid-pinned like every identity-sensitive write: a
                    # recreated same-name job must mint its own trace
                    {"metadata": {
                        "uid": job.metadata.uid,
                        "annotations": {trace.ANNOTATION_TRACE_ID: tid},
                    }},
                )
            except (NotFound, Conflict):
                return  # deleted/recreated under us; next reconcile retries
            with self._trace_lock:
                self._stamped_traces[job.metadata.uid] = tid
                while len(self._stamped_traces) > 4096:
                    self._stamped_traces.pop(
                        next(iter(self._stamped_traces))
                    )
        job.metadata.annotations[trace.ANNOTATION_TRACE_ID] = tid
        sp = trace.TRACER.current_span()
        if sp is not None:
            sp.adopt_trace(tid)

    # ------------------------------------------------------------------
    # dependents
    # ------------------------------------------------------------------

    def _reap_orphans(self, namespace: str, name: str) -> None:
        """Delete every dependent of a deleted job. Selection is by the
        job-name label every dependent carries, guarded by the controller
        owner ref (never GC an object some other owner claims); reads ride
        the lister, so a job with no leftovers costs zero store traffic.
        Idempotent and level-triggered: each dependent's own DELETED event
        re-enqueues this job key until nothing is left."""
        for kind in ("Pod", "ConfigMap", "Service", "PodGroup"):
            for obj in self.read.list(
                kind, namespace, selector={LABEL_JOB_NAME: name}
            ):
                owner = self._controller_owner(obj)
                if owner is None or owner.name != name:
                    continue
                self.store.try_delete(kind, namespace, obj.metadata.name)

    def _owner_ref(self, job: TPUJob) -> OwnerReference:
        return OwnerReference(name=job.name, uid=job.metadata.uid, controller=True)

    def _check_owned(self, job: TPUJob, obj) -> bool:
        """Adoption check (≙ :625-631): an existing dependent not controlled
        by this job is a fatal ownership conflict → warning event + requeue."""
        owner = self._controller_owner(obj)
        if owner is None or owner.uid != job.metadata.uid:
            msg = (
                f"{obj.kind} {obj.metadata.key()} already exists and is not "
                f"controlled by TPUJob {job.name}"
            )
            self.recorder.event(job, WARNING, "IneligibleOwnership", msg)
            raise RuntimeError(msg)
        return True

    def _selector(self, job: TPUJob) -> Dict[str, str]:
        return {LABEL_JOB_NAME: job.name}

    def _list_workers(self, job: TPUJob) -> List[Pod]:
        pods = self.read.list("Pod", job.namespace, selector=self._selector(job))
        pods.sort(key=lambda p: int(p.metadata.labels.get(LABEL_REPLICA_INDEX, "0")))
        return pods

    def _get_or_create_service(self, job: TPUJob) -> Service:
        """Headless service giving workers stable DNS (≙ newWorkersService
        :1141-1171)."""
        existing = self.read.try_get("Service", job.namespace, job.service_name())
        if existing is not None:
            self._check_owned(job, existing)
            return existing
        svc = Service(
            metadata=ObjectMeta(
                name=job.service_name(),
                namespace=job.namespace,
                labels=self._selector(job),
                owner_references=[self._owner_ref(job)],
            ),
            spec=ServiceSpec(cluster_ip="None", selector=self._selector(job)),
        )
        return self.store.create(svc)

    # ports probed above options.coordinator_port before wrapping
    PORT_RANGE = 1024
    # max age of the used-port snapshot (seconds): _ports_inflight covers
    # everything this leader assigned, so the snapshot only needs to age
    # fast enough to learn a PREVIOUS leader's assignments after failover
    _PORTS_SNAPSHOT_TTL = 30.0

    def _assign_coordinator_port(self, job: TPUJob) -> int:
        """Per-job rendezvous port, recorded in status (once assigned it is
        stable for the job's lifetime — workers compiled against it must
        find the same coordinator after every gang restart). Hash-placed in
        [base, base+PORT_RANGE) with linear probing against the ports of
        other live jobs; the reference needs no analogue because every pod
        has its own DNS name, whereas one LocalExecutor host shares one
        loopback interface."""
        key = job.metadata.key()
        with self._port_lock:
            if job.status.coordinator_port:
                self._ports_inflight[key] = job.status.coordinator_port
                return job.status.coordinator_port
            reserved = self._ports_inflight.get(key)
            if reserved is not None:
                # a prior attempt whose status write lost a Conflict: the
                # pods already carry this port, so it must stick
                job.status.coordinator_port = reserved
                return reserved
        # list OUTSIDE the lock (LCK001): self.read is a raw store when no
        # cache is wired, and a network round-trip under _port_lock would
        # serialize every concurrent reconcile behind it. Sound because a
        # concurrent assignment ALWAYS lands in _ports_inflight under the
        # lock before its status write — re-checked below — so a port
        # missing from this (possibly stale) snapshot cannot be lost.
        # The snapshot is a TTL-cached PORT SET (10k-job round): one full
        # list per NEW job made first-assignment cost O(jobs²) across a
        # submission storm, and caching the deepcopied job objects still
        # cost O(jobs) per refresh — the set of busy ports is all this
        # probe needs. A port freshly assigned by THIS controller is
        # always visible through _ports_inflight regardless of snapshot
        # age (the leader is the only assigner), so staleness only risks
        # probing onto a port a *finished* job recently freed — harmless:
        # assignment is best-effort hash probing by design.
        now = time.monotonic()
        with self._port_lock:
            snap = self._ports_snapshot
        if snap is None or now - snap[1] > self._PORTS_SNAPSHOT_TTL:
            listed = {
                (j.metadata.uid, j.status.coordinator_port)
                for j in self.read.list("TPUJob")
                if j.status.coordinator_port
                and not cond.is_finished(j.status)
            }
            with self._port_lock:
                self._ports_snapshot = (listed, now)
                snap = self._ports_snapshot
        with self._port_lock:
            reserved = self._ports_inflight.get(key)
            if reserved is not None:
                job.status.coordinator_port = reserved
                return reserved
            used = {
                p for uid, p in snap[0] if uid != job.metadata.uid
            }
            used |= {
                p for k, p in self._ports_inflight.items() if k != key
            }
            base = self.options.coordinator_port
            start = zlib.crc32(key.encode()) % self.PORT_RANGE
            port = base + start  # all taken: best effort
            for probe in range(self.PORT_RANGE):
                cand = base + (start + probe) % self.PORT_RANGE
                if cand not in used:
                    port = cand
                    break
            self._ports_inflight[key] = port
            job.status.coordinator_port = port
            return port

    def coordinator_address(self, job: TPUJob) -> str:
        return f"{job.worker_hostname(0)}:{self._assign_coordinator_port(job)}"

    def _config_data(self, job: TPUJob, workers: List[Pod]) -> Dict[str, str]:
        """hostfile + discover_hosts.sh parity (≙ newConfigMap :1088-1113 and
        updateDiscoverHostsInConfigMap :1116-1138: static hostfile of stable
        DNS names; dynamic script listing only *Running* pods, sorted)."""
        slots = job.spec.slots_per_worker
        hostfile = "".join(
            f"{job.worker_hostname(i)} slots={slots}\n"
            for i in range(job.spec.worker.replicas)
        )
        running = sorted(
            int(p.metadata.labels[LABEL_REPLICA_INDEX])
            for p in workers
            if p.status.phase == PodPhase.RUNNING
        )
        discover = "#!/bin/sh\n" + "".join(
            f"echo {job.worker_hostname(i)}:{slots}\n" for i in running
        )
        data = {
            CONFIG_HOSTFILE: hostfile,
            CONFIG_DISCOVER_HOSTS: discover,
            CONFIG_COORDINATOR: self.coordinator_address(job),
        }
        req = job.metadata.annotations.get(ANNOTATION_PROFILE_REQUEST, "")
        if req:
            data[CONFIG_PROFILE] = req
        return data

    def _get_or_create_configmap(self, job: TPUJob, workers: List[Pod]) -> ConfigMap:
        data = self._config_data(job, workers)
        existing = self.read.try_get("ConfigMap", job.namespace, job.config_name())
        if existing is not None:
            self._check_owned(job, existing)
            if existing.data != data:
                # merge-patch of just the changed keys (nulls delete):
                # one request, and a cached copy lagging our own last
                # write can never 409 the reconcile
                return self.store.patch(
                    "ConfigMap", job.namespace, job.config_name(),
                    {"data": diff_merge_patch(existing.data, data)},
                )
            metrics.store_writes_elided.inc(component="controller")
            return existing
        cm = ConfigMap(
            metadata=ObjectMeta(
                name=job.config_name(),
                namespace=job.namespace,
                labels=self._selector(job),
                owner_references=[self._owner_ref(job)],
            ),
            data=data,
        )
        return self.store.create(cm)

    @staticmethod
    def _desired_min_member(job: TPUJob) -> int:
        sp = job.spec.run_policy.scheduling_policy
        if sp and sp.min_available is not None:
            return sp.min_available
        return job.spec.worker.replicas

    def _get_or_create_podgroup(self, job: TPUJob) -> PodGroup:
        """Gang unit: min_member == workers — all-or-nothing slice allocation
        (≙ newPodGroup :1215-1237 with minMember = workers+1 :573; no +1 here
        because there is no launcher pod). A schedulingPolicy.minAvailable
        overrides, on both the create and the reconcile-update path."""
        desired = self._desired_min_member(job)
        existing = self.read.try_get("PodGroup", job.namespace, job.podgroup_name())
        if existing is not None:
            self._check_owned(job, existing)
            if existing.spec.min_member != desired:
                return self.store.patch(
                    "PodGroup", job.namespace, job.podgroup_name(),
                    {"spec": {"min_member": desired}},
                )
            return existing
        sp = job.spec.run_policy.scheduling_policy
        pg = PodGroup(
            metadata=ObjectMeta(
                name=job.podgroup_name(),
                namespace=job.namespace,
                labels=self._selector(job),
                owner_references=[self._owner_ref(job)],
            ),
            spec=PodGroupSpec(
                min_member=desired,
                priority_class=sp.priority_class if sp else "",
            ),
        )
        return self.store.create(pg)

    def _new_worker(self, job: TPUJob, index: int, placement: SlicePlacement) -> Pod:
        """≙ newWorker (:1246-1296): stable hostname/subdomain behind the
        headless service, labels for selection, controller env injected after
        user env (controller values win for the rendezvous contract)."""
        tmpl = job.spec.worker.template
        container = Container.from_dict(tmpl.container.to_dict())
        env = dict(container.env)
        env.update(
            {
                ENV_JOB_NAME: job.name,
                ENV_NAMESPACE: job.namespace,
                ENV_COORDINATOR: self.coordinator_address(job),
                ENV_NUM_HOSTS: str(job.spec.worker.replicas),
                ENV_HOST_ID: str(index),
                ENV_CHIPS_PER_HOST: str(job.spec.slice.chips_per_host),
                ENV_ACCELERATOR: job.spec.slice.accelerator,
                ENV_TOPOLOGY: "x".join(map(str, placement.topology)),
                ENV_HOST_MESH: "x".join(map(str, placement.host_mesh)),
                ENV_HOST_COORD: "x".join(map(str, placement.host_coords[index])),
                ENV_SLICE_ID: str(placement.slice_ids[index]),
                ENV_NUM_SLICES: str(placement.num_slices),
                ENV_COMPILE_CACHE: (
                    "0" if job.spec.compile_cache is False else "1"
                ),
            }
        )
        container.env = env
        labels = dict(tmpl.labels)
        labels.update(self._selector(job))
        labels[LABEL_ROLE] = ROLE_WORKER
        labels[LABEL_REPLICA_INDEX] = str(index)
        # restart_generation, NOT restart_count: free preemption restarts
        # don't burn the backoff budget but ARE new launch generations —
        # labeling them with the unchanged count would blind the
        # single-generation invariant in exactly the preemption scenarios
        # the chaos suite injects
        labels[LABEL_GENERATION] = str(job.status.restart_generation)
        annotations = dict(tmpl.annotations)
        annotations.update(placement.annotations_for(index))
        # trace propagation: the pod carries its job's trace id, so every
        # component holding the pod (scheduler bind, agent launch, monitor
        # eviction) can open spans in the job's trace with no live header
        # chain — robust across the process crashes chaos injects
        tid = job.metadata.annotations.get(trace.ANNOTATION_TRACE_ID)
        if tid:
            annotations[trace.ANNOTATION_TRACE_ID] = tid
        # ExitCode policy is controller-owned: the pod itself never restarts
        # (≙ setRestartPolicy :1394-1400)
        pod_restart = (
            RestartPolicy.NEVER
            if job.spec.worker.restart_policy == RestartPolicy.EXIT_CODE
            else job.spec.worker.restart_policy
        )
        return Pod(
            metadata=ObjectMeta(
                name=job.worker_name(index),
                namespace=job.namespace,
                labels=labels,
                annotations=annotations,
                owner_references=[self._owner_ref(job)],
            ),
            spec=PodSpec(
                container=container,
                hostname=job.worker_name(index),
                subdomain=job.service_name(),
                restart_policy=pod_restart,
                node_selector=dict(tmpl.node_selector),
                scheduler_name=tmpl.scheduler_name,
                priority_class=tmpl.priority_class
                or (
                    job.spec.run_policy.scheduling_policy.priority_class
                    if job.spec.run_policy.scheduling_policy
                    else ""
                ),
            ),
        )

    def _reconcile_workers(self, job: TPUJob, placement: SlicePlacement) -> List[Pod]:
        """Per-index get-or-create + elastic scale-down of indices >= replicas
        (≙ getOrCreateWorker :817-877, scale-down :833-849).

        Under ExitCode policy, a RUNNING over-index pod is left to exit on
        its own: the elastic protocol has every worker observe the shrunken
        hostfile and exit EXIT_RESTART at the *same gang-synchronized step*
        (ops/elastic.py). Killing it here would sever a live collective and
        crash the survivors with a permanent (non-75) exit code. The
        reference can kill immediately because Horovod re-forms rings around
        lost peers; an XLA gang cannot."""
        replicas = job.spec.worker.replicas
        graceful = job.spec.worker.restart_policy == RestartPolicy.EXIT_CODE
        existing = {p.metadata.name: p for p in self._list_workers(job)}
        # scale-UP grace, symmetric to the scale-down grace below: a worker
        # created into a still-running old gang cannot join its rendezvous
        # (the live coordinator was started with the old process count) and
        # would crash non-retryably. While any old-size pod is RUNNING,
        # defer new creations; the drain restart relaunches the full gang.
        old_gang_live = graceful and any(
            p.status.phase == PodPhase.RUNNING
            and p.spec.container.env.get(ENV_NUM_HOSTS) != str(replicas)
            for p in existing.values()
        )
        out: List[Pod] = []
        for i in range(replicas):
            name = job.worker_name(i)
            pod = existing.pop(name, None)
            if pod is None:
                if old_gang_live:
                    continue
                pod = self.store.create(self._new_worker(job, i, placement))
            else:
                self._check_owned(job, pod)
            out.append(pod)
        # anything left in `existing` has index >= replicas → scale down
        for name, pod in existing.items():
            self._check_owned(job, pod)
            if graceful and pod.status.phase == PodPhase.RUNNING:
                continue  # it will exit EXIT_RESTART itself; reap next sync
            self.store.try_delete("Pod", job.namespace, name)
        return out

    # ------------------------------------------------------------------
    # status (≙ updateMPIJobStatus :921-996, launcher→worker-0)
    # ------------------------------------------------------------------

    def _update_status(self, job: TPUJob, workers: List[Pod]) -> None:
        rs = ReplicaStatus()
        for p in workers:
            if p.status.phase == PodPhase.RUNNING:
                rs.active += 1
            elif p.status.phase == PodPhase.SUCCEEDED:
                rs.succeeded += 1
            elif p.status.phase == PodPhase.FAILED:
                rs.failed += 1
                if p.is_evicted():
                    rs.evicted += 1
        job.status.replica_statuses = {ReplicaType.WORKER: rs}

        replicas = job.spec.worker.replicas
        coordinator = next(
            (p for p in workers if p.metadata.labels.get(LABEL_REPLICA_INDEX) == "0"),
            None,
        )
        if coordinator is not None:
            metrics.job_info.set(
                1, coordinator=coordinator.metadata.name, namespace=job.namespace
            )

        # --- success: coordinator (worker 0) exited 0 (≙ launcher Succeeded) ---
        if coordinator is not None and coordinator.status.phase == PodPhase.SUCCEEDED:
            if cond.update_job_conditions(
                job.status,
                ConditionType.SUCCEEDED,
                cond.REASON_SUCCEEDED,
                f"TPUJob {job.metadata.key()} successfully completed",
            ):
                metrics.jobs_successful.inc()
                self.recorder.event(job, NORMAL, cond.REASON_SUCCEEDED, "job succeeded")
            cond.ensure_timestamps(job.status)
            return

        # --- failures: gang-coherent restart (≙ :935-983, redesigned) ---
        # The reference restarts per-pod because Horovod re-forms rings
        # around lost peers. An XLA gang cannot: losing one member makes the
        # survivors' collectives fail with ordinary (non-retryable) exit
        # codes. So failure handling is gang-scoped: if ANY pod failed
        # retryably (evicted, exit>=128, EXIT_RESTART), companion failures
        # are collateral and the WHOLE gang restarts — but the fail-vs-
        # restart VERDICT waits until no pod is still running (drain: peers
        # exit via the elastic protocol or their own collective error;
        # activeDeadlineSeconds backstops a straggler that never exits).
        # The drain sync executes the restart exactly once per generation,
        # so backoffLimit counts restart generations, not per-pod failure
        # observations.
        failed = [p for p in workers if p.status.phase == PodPhase.FAILED]
        if failed:
            retryable = any(self._pod_retryable(job, p) for p in failed)
            all_pods = self._list_workers(job)  # incl. over-index stragglers
            # a maintenance-evicted member marks the whole generation as a
            # MIGRATION (the planned-disruption flavor of Restarting): the
            # condition machine treats the two restart-ish states as one
            # slot, so `ctl describe` shows Migrating while the
            # checkpoint-then-migrate drains and relaunches
            migrating = retryable and any(
                p.status.reason == REASON_MAINTENANCE for p in failed
            )
            if migrating and cond.update_job_conditions(
                job.status,
                ConditionType.MIGRATING,
                cond.REASON_MIGRATING,
                f"gang is migrating off a draining node "
                f"({failed[0].status.message or 'maintenance'})",
            ):
                self.recorder.event(
                    job, NORMAL, cond.REASON_MIGRATING,
                    "gang migrating off a draining node",
                )
            elif not migrating and retryable and cond.update_job_conditions(
                job.status,
                ConditionType.RESTARTING,
                cond.REASON_RESTARTING,
                "worker pod(s) failed retryably; gang will restart",
            ):
                self.recorder.event(
                    job, WARNING, cond.REASON_RESTARTING, "job restarting"
                )
            cond.ensure_timestamps(job.status)
            if any(p.status.phase == PodPhase.RUNNING for p in all_pods):
                # drain before the VERDICT, not just before the restart: a
                # companion's ordinary crash often lands before the root
                # cause is recorded (a lost node's pods are only marked
                # Evicted after the heartbeat grace window — NodeMonitor),
                # so deciding fail-vs-restart now would misread collateral
                # rc=1 exits as a permanent app failure. Survivors exit on
                # their own (collective error / elastic protocol);
                # activeDeadlineSeconds backstops a straggler.
                if not retryable:
                    self._note_drain_wait(job, failed)
                return
            if retryable:
                # Preemption is the scheduler's doing, not the workload
                # failing: a preempted generation restarts for free — it
                # neither burns backoffLimit nor counts as a restart (kube
                # preemption never charges a Job's restart policy either).
                # A busy cluster preempting a low-priority job 3 times must
                # not permanently FAIL it with backoffLimit=2. The free pass
                # requires every RETRYABLE failure in the generation to be a
                # PLANNED disruption (preemption or a drain's maintenance
                # migration) — non-retryable companions (rc=1 collective
                # errors) are collateral of the eviction, but a pod that
                # failed retryably on its own (exit 137, EXIT_RESTART)
                # means the workload was crashing anyway and the generation
                # must still count toward backoffLimit.
                preempted = any(
                    p.is_planned_disruption() for p in failed
                ) and all(
                    p.is_planned_disruption()
                    or not self._pod_retryable(job, p)
                    for p in failed
                )
                backoff = job.spec.run_policy.backoff_limit
                if (
                    not preempted
                    and backoff is not None
                    and job.status.restart_count >= backoff
                ):
                    self._fail_job(
                        job,
                        workers,
                        cond.REASON_BACKOFF,
                        f"restart count {job.status.restart_count} reached "
                        f"backoffLimit={backoff}",
                    )
                    return
                if not preempted:
                    job.status.restart_count += 1
                    metrics.jobs_restarted.inc()
                # every EXECUTED generation restart counts here, free
                # preemption restarts included: the restart-storm tripwire
                # (tests/test_stress.py) and the `ctl`-visible rate ride
                # this, and a storm of "free" restarts is still a storm
                job.status.restart_generation += 1
                metrics.gang_restarts.inc()
                # a restart executed: the next generation gets its own
                # drain-wait note even when the restart was free (the
                # (uid, restart_count) key would otherwise collide across
                # preempted generations and suppress the once-per-generation
                # hang explanation)
                self._drain_noted.discard(
                    (job.metadata.uid, job.status.restart_count)
                )
                # the gang-restart span (an `ctl trace --last-incident`
                # anchor): child of this reconcile — whose parent is the
                # eviction/failure write that triggered it — and parent of
                # the teardown deletes below, so the relaunch chain the
                # deletes cause links back to the restart that caused THEM
                first_fail = failed[0]
                with trace.start_span(
                    "controller.gang_restart",
                    attrs={
                        "job": job.metadata.key(),
                        "generation": job.status.restart_generation,
                        "free": preempted,
                        "first_failed": first_fail.metadata.name,
                        "reason": first_fail.status.reason or "Error",
                    },
                ):
                    # delete every terminal pod — a succeeded
                    # non-coordinator must re-run too, or the relaunched
                    # gang waits on a member that never comes back; next
                    # reconcile recreates the gang at the (possibly
                    # rescaled) size
                    for p in all_pods:
                        self.store.try_delete(
                            "Pod", p.metadata.namespace, p.metadata.name
                        )
                return
            first = failed[0]
            reason = cond.REASON_EVICTED if first.is_evicted() else cond.REASON_FAILED
            msg = (
                f"worker pod {first.metadata.name} failed with reason "
                f"{first.status.reason or 'Error'}: {first.status.message or ''}"
            )
            self._fail_job(job, workers, reason, msg)
            return

        # --- running: every worker Running (≙ worker-readiness→Running,
        # mpi_job_controller_test.go:771-935) ---
        if replicas and rs.active == replicas:
            if cond.update_job_conditions(
                job.status,
                ConditionType.RUNNING,
                cond.REASON_RUNNING,
                f"all {replicas} workers are running",
            ):
                self.recorder.event(job, NORMAL, cond.REASON_RUNNING, "job running")

    def _pod_retryable(self, job: TPUJob, pod: Pod) -> bool:
        """Eviction/preemption is always retryable (TPU preemption is routine;
        ≙ the evicted-requeue of syncHandler :506-529). Otherwise the replica
        restart policy decides; ExitCode retries system exit codes >= 128
        (SIGKILL'd / infrastructure, matching kubeflow-common convention) and
        EXIT_RESTART (75, EX_TEMPFAIL) — the elastic protocol's own
        "re-run me at the new gang size" code (ops/elastic.py; ≙ the
        discover_hosts.sh re-form loop, SURVEY.md §3.5)."""
        if pod.is_evicted():
            return True
        rp = job.spec.worker.restart_policy
        if rp in (RestartPolicy.ALWAYS, RestartPolicy.ON_FAILURE):
            return True
        if rp == RestartPolicy.EXIT_CODE:
            ec = pod.status.exit_code
            return ec is not None and (ec >= 128 or ec == EXIT_RESTART)
        return False

    def _note_drain_wait(self, job: TPUJob, failed: List[Pod]) -> None:
        """Non-retryable failure observed while peers still run: the verdict
        waits for drain (a late node-loss eviction can still flip it to a
        restart). Say so ONCE per generation in the event trail — without
        activeDeadlineSeconds, a survivor that never exits would otherwise
        leave the job hanging with no visible explanation."""
        key = (job.metadata.uid, job.status.restart_count)
        if key in self._drain_noted:
            return
        if len(self._drain_noted) > 1024:
            self._drain_noted.clear()  # bounded; a re-note is benign
        self._drain_noted.add(key)
        first = failed[0]
        self.recorder.event(
            job, WARNING, "TPUJobDraining",
            f"worker pod {first.metadata.name} failed "
            f"({first.status.reason or 'Error'}); waiting for the remaining "
            f"workers to drain before the fail-vs-restart verdict — set "
            f"runPolicy.activeDeadlineSeconds to bound this wait",
        )

    def _fail_job(
        self, job: TPUJob, workers: List[Pod], reason: str, message: str
    ) -> None:
        if cond.update_job_conditions(
            job.status, ConditionType.FAILED, reason, message
        ):
            metrics.jobs_failed.inc()
            self.recorder.event(job, WARNING, reason, message)
        cond.ensure_timestamps(job.status)

    # ------------------------------------------------------------------
    # finished / suspend handling
    # ------------------------------------------------------------------

    def _sync_suspended(self, job: TPUJob, workers: List[Pod]) -> bool:
        for p in workers:
            self.store.try_delete("Pod", p.metadata.namespace, p.metadata.name)
        self.store.try_delete("PodGroup", job.namespace, job.podgroup_name())
        if cond.update_job_conditions(
            job.status,
            ConditionType.SUSPENDED,
            cond.REASON_SUSPENDED,
            "job is suspended",
        ):
            self.recorder.event(job, NORMAL, cond.REASON_SUSPENDED, "job suspended")
        rs = job.status.replica_statuses.setdefault(ReplicaType.WORKER, ReplicaStatus())
        rs.active = 0
        return self._write_status(job)

    def _cleanup_finished(self, job: TPUJob, workers: List[Pod]) -> None:
        """≙ the finished branch of syncHandler (:492-530): apply
        cleanPodPolicy, drop the gang, honor ttlSecondsAfterFinished."""
        policy = job.spec.run_policy.clean_pod_policy
        for p in workers:
            delete = policy == CleanPodPolicy.ALL or (
                policy == CleanPodPolicy.RUNNING and p.status.phase == PodPhase.RUNNING
            )
            if delete:
                self.store.try_delete("Pod", p.metadata.namespace, p.metadata.name)
        self.store.try_delete("PodGroup", job.namespace, job.podgroup_name())

        ttl = job.spec.run_policy.ttl_seconds_after_finished
        if ttl is not None and job.status.completion_time is not None:
            age = time.time() - job.status.completion_time
            if age >= ttl:
                self.store.try_delete("TPUJob", job.namespace, job.name)
            else:
                self.queue.add_after(job.metadata.key(), ttl - age + 0.01)

    # ------------------------------------------------------------------
    # housekeeping: Event TTL sweep (≙ the apiserver's event TTL — kube
    # prunes its events after 1h; without this the append-only audit
    # stream grows the store without bound)
    # ------------------------------------------------------------------

    def _housekeeping_loop(self) -> None:
        while not self._stop.wait(self.options.event_gc_interval):
            try:
                self.prune_events()
            except Exception:
                log.exception("event TTL sweep failed")  # next pass retries

    def prune_events(self, now: Optional[float] = None) -> int:
        """Delete Events older than ``options.event_ttl``; returns the
        pruned count (also exported as tpu_operator_events_pruned_total).
        Recent events — the trail `ctl describe`/`ctl events` renders —
        survive untouched; reads go straight to the store because Events
        are deliberately not informer-cached (cache.DEFAULT_KINDS)."""
        ttl = self.options.event_ttl
        if ttl is None:
            return 0
        cutoff = (time.time() if now is None else now) - ttl
        pruned = 0
        for ev in self.store.list("Event", self.options.namespace):
            if ev.timestamp and ev.timestamp < cutoff:
                if self.store.try_delete(
                    "Event", ev.metadata.namespace, ev.metadata.name
                ) is not None:
                    pruned += 1
        if pruned:
            metrics.events_pruned.inc(pruned)
            log.info("event TTL sweep pruned %d events (ttl %.0fs)",
                     pruned, ttl)
        return pruned

    # ------------------------------------------------------------------
    # status write (injectable; ≙ updateStatusHandler :243-244)
    # ------------------------------------------------------------------

    def _default_write_status(self, job: TPUJob) -> bool:
        """Persist status only when it changed (≙ UpdateStatus-on-change,
        :602 + :921-996 tail — the no-op elision that keeps an idle
        cluster at ZERO store writes, the write-side twin of the lister's
        zero-read guarantee), via ONE status-subresource merge-patch
        carrying just the changed keys (nulls for removed ones). No rv
        precondition: this controller is the only TPUJob-status writer
        (leader-elected), so patching latest is exactly right and the old
        GET+PUT Conflict/requeue cycle disappears."""
        stored = self.read.try_get("TPUJob", job.namespace, job.name)
        if stored is None:
            return True
        if stored.metadata.uid != job.metadata.uid:
            # the job this reconcile computed for was deleted and a new
            # same-name incarnation exists: stamping the OLD incarnation's
            # status (restart_count, Failed/Restarting conditions) onto the
            # fresh job would e.g. pre-burn its backoffLimit — and the
            # absorbed restart_count would never self-heal
            return True
        old, new = stored.status.to_dict(), job.status.to_dict()
        # train_telemetry is the goodput aggregator's field — this
        # controller NEVER writes it, so it must never appear in the
        # diff: a reconcile snapshot that predates the aggregator's
        # rollup patch would otherwise emit train_telemetry: null (or a
        # stale blob) and erase the other writer's work
        old.pop("train_telemetry", None)
        new.pop("train_telemetry", None)
        if old == new:
            metrics.store_writes_elided.inc(component="controller")
            return True
        try:
            self.store.patch(
                "TPUJob", job.namespace, job.name,
                # uid-pinned (checked atomically with the merge): the
                # recreation race between the read above and this write —
                # or a deposed leader's in-flight write landing over the
                # new leader's — bounces as Conflict instead of silently
                # cross-stamping incarnations
                {"status": diff_merge_patch(old, new),
                 "metadata": {"uid": job.metadata.uid}},
                subresource="status",
            )
        except NotFound:
            return True  # deleted under us; nothing to mirror
        except Conflict:
            return False  # recreated under us: requeue reads the new world
        return True
