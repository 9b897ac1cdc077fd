"""Dependent-object types the controller materializes.

≙ the corev1/volcano objects the reference reconciler creates for each MPIJob
(v2/pkg/controller/mpi_job_controller.go): worker/launcher Pods (:1246-1392),
headless Service (:1141-1171), ConfigMap (:1088-1138), PodGroup (:1215-1237),
and the Events recorded throughout. Secrets (SSH keys, :1175-1210) have no TPU
analogue — rendezvous replaces rank-spawn — so there is no Secret type.

Only the fields the framework actually schedules/observes are modeled; each
type reuses the api ObjectMeta so ownership/adoption logic is uniform.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from mpi_operator_tpu.api.types import Condition, Container, ObjectMeta, _Dictable
from mpi_operator_tpu.machinery.store import Conflict, NotFound


class PodPhase:
    """≙ corev1.PodPhase, the signal updateMPIJobStatus consumes
    (mpi_job_controller.go:921-996)."""

    PENDING = "Pending"
    RUNNING = "Running"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"

    ALL_VALUES = (PENDING, RUNNING, SUCCEEDED, FAILED)


# eviction reason for planned maintenance moves (the disruption plane's
# checkpoint-then-migrate verb): retryable like "Evicted", free like
# "Preempted" — the move is the infrastructure's doing, so it advances
# restart_generation but never restart_count
REASON_MAINTENANCE = "Maintenance"


@dataclass
class PodSpec(_Dictable):
    container: Container = field(default_factory=Container)
    hostname: str = ""
    subdomain: str = ""
    node_name: str = ""
    node_selector: Dict[str, str] = field(default_factory=dict)
    restart_policy: str = "Never"
    scheduler_name: str = ""
    priority_class: str = ""


@dataclass
class PodStatus(_Dictable):
    phase: str = PodPhase.PENDING
    ready: bool = False
    reason: str = ""
    message: str = ""
    exit_code: Optional[int] = None
    pod_ip: str = ""
    host_ip: str = ""
    start_time: Optional[float] = None
    # where the executor streams this pod's stdout (stderr sits next to it
    # with a .err suffix) — the kubelet-log-dir equivalent that `ctl logs`
    # reads; the path is local to the node named in spec.node_name
    log_path: str = ""
    # serving-pod telemetry the executor mirrors alongside the phase
    # (qps / queue_depth / p99_ms): the per-pod sample stream the serve
    # autoscaler aggregates — kubelet resource-metrics shaped, carried in
    # status so it rides the existing patch-batch machinery and watch
    # fan-out instead of needing a second metrics pipeline
    serve_stats: Optional[Dict[str, float]] = None
    # training-pod telemetry, the batch twin of serve_stats (the workload
    # telemetry plane, ISSUE 15): cumulative stall-attributed wall-second
    # buckets + step counters this incarnation, mirrored by the executor
    # from the worker's step-stats file (runtime/stepstats.py) or scripted
    # by a hollow timeline. ALWAYS built through bounded_train_stats —
    # an unbounded dict here would bloat every watch event carrying the
    # pod (oplint OBS004)
    train_stats: Optional[Dict[str, object]] = None


@dataclass
class Pod(_Dictable):
    kind: str = "Pod"
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    spec: PodSpec = field(default_factory=PodSpec)
    status: PodStatus = field(default_factory=PodStatus)

    def is_finished(self) -> bool:
        return self.status.phase in (PodPhase.SUCCEEDED, PodPhase.FAILED)

    def is_evicted(self) -> bool:
        """≙ isEvicted check on launcher pods (status.go:99-106 + controller
        :935-950): Failed with an eviction-flavored reason. Covers
        infrastructure eviction (node loss, drain), priority preemption,
        and planned maintenance moves — all always-retryable."""
        return self.status.phase == PodPhase.FAILED and self.status.reason in (
            "Evicted", "Preempted", REASON_MAINTENANCE,
        )

    def is_preempted(self) -> bool:
        """Preemption specifically: retryable like any eviction, but it must
        NOT burn the job's backoffLimit — being preempted is the scheduler's
        doing, not the workload failing (kube preemption never counts
        against a Job's restart policy either)."""
        return (
            self.status.phase == PodPhase.FAILED
            and self.status.reason == "Preempted"
        )

    def is_planned_disruption(self) -> bool:
        """The free-restart class: preemption AND maintenance migration.
        Both are the control plane's doing — a job moved off a node with a
        maintenance window must not burn its backoffLimit budget any more
        than a preempted one (the DrainController's checkpoint-then-migrate
        contract: restart_generation advances, restart_count does not)."""
        return self.status.phase == PodPhase.FAILED and self.status.reason in (
            "Preempted", REASON_MAINTENANCE,
        )


@dataclass
class ServiceSpec(_Dictable):
    cluster_ip: str = "None"  # headless, ≙ newWorkersService :1141-1147
    selector: Dict[str, str] = field(default_factory=dict)


@dataclass
class Service(_Dictable):
    kind: str = "Service"
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    spec: ServiceSpec = field(default_factory=ServiceSpec)


@dataclass
class ConfigMap(_Dictable):
    kind: str = "ConfigMap"
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    data: Dict[str, str] = field(default_factory=dict)


@dataclass
class PodGroupSpec(_Dictable):
    min_member: int = 0
    # priority class name or integer string; resolved by the scheduler
    # (scheduler/gang.py resolve_priority_class) to order pending gangs
    priority_class: str = ""


@dataclass
class PodGroup(_Dictable):
    """Gang-scheduling unit, ≙ volcano PodGroup (newPodGroup :1215-1237).
    On TPU this doubles as the slice-allocation request: min_member hosts that
    must be placed atomically on one slice."""

    kind: str = "PodGroup"
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    spec: PodGroupSpec = field(default_factory=PodGroupSpec)


# Nodes are cluster-scoped in kubernetes; this store is namespaced, so they
# live under one well-known pseudo-namespace
NODE_NAMESPACE = "nodes"

# The planned-disruption notice contract (the disruption plane, ISSUE 14):
# a node carrying this annotation has a maintenance window — the value is
# the ABSOLUTE unix timestamp the hardware goes away. Stamped by
# `ctl drain <node> [--deadline S]` or a hollow fleet's seeded maintenance
# schedule; consumed by the DrainController (cordon → migrate → escalate
# at the deadline), the scheduler (imminent-maintenance placement penalty)
# and the node monitor (drain-owned nodes are not double-evicted).
# Cleared by `ctl uncordon` when the node returns from maintenance.
ANNOTATION_MAINTENANCE_AT = "tpujob.dev/maintenance-at"

# The sick-hardware flag (the rescheduler, ISSUE 18): stamped on a node
# when the goodput plane names one of its pods a straggler and the
# rescheduler moves the gang off it. Value is the unix timestamp of the
# flagging. The scheduler DEPRIORITIZES flagged nodes (middle placement
# tier: clean > straggler-flagged > maintenance-doomed) rather than
# excluding them — suspected-slow hardware still hosts when nothing
# else has room. Cleared by `ctl uncordon` once the host is vindicated
# or repaired (runbook row "rescheduler migrating too much").
ANNOTATION_STRAGGLER_NODE = "tpujob.dev/straggler-node"


class NodeConditionType:
    """Node conditions (operator-owned, like the cordon flag):

    Draining — an active maintenance drain is evacuating this node. Set by
    the DrainController when it adopts a maintenance notice; flipped
    inactive (reason=Drained) once no live pod remains bound.
    """

    DRAINING = "Draining"

    ALL_VALUES = (DRAINING,)


def maintenance_at(node: "Node"):
    """The node's maintenance deadline as a float, or None when absent or
    unparseable (a malformed stamp is surfaced by the DrainController as a
    warning Event, never silently treated as a real window)."""
    raw = node.metadata.annotations.get(ANNOTATION_MAINTENANCE_AT)
    if raw is None:
        return None
    try:
        return float(raw)
    except (TypeError, ValueError):
        return None


def node_has_maintenance(node: "Node") -> bool:
    return ANNOTATION_MAINTENANCE_AT in node.metadata.annotations


def node_draining(node: "Node") -> bool:
    """True while the Draining condition is active (an in-flight drain)."""
    for c in node.status.conditions:
        if c.type == NodeConditionType.DRAINING:
            return bool(c.status)
    return False

# The single-process binding sentinel: the scheduler binds to it when no
# Node objects exist (dev/standalone shape), the LocalExecutor claims it,
# and agents must REJECT it as an identity. A cross-plane contract, so it
# lives here rather than inside the scheduler package.
LOCAL_NODE = "local"


@dataclass
class NodeStatus(_Dictable):
    # where this node can be reached (coordinator rendezvous resolution —
    # the headless-service-DNS role the reference gets from kube DNS,
    # ≙ newWorkersService :1141-1171 giving workers stable resolvable names)
    address: str = ""
    # base URL of the node agent's log endpoint; the agent stamps
    # f"{log_url}/<file>" into pod.status.log_path so `ctl logs` reads
    # cross-node (≙ `kubectl logs` riding the kubelet API)
    log_url: str = ""
    last_heartbeat: float = 0.0
    ready: bool = False
    # cordon flag (≙ kubectl cordon / node.spec.unschedulable): set by
    # `ctl cordon/drain`, PRESERVED across agent heartbeats, cleared by
    # `ctl uncordon`. A cordoned node keeps running its pods (drain evicts
    # them) but receives no new bindings.
    unschedulable: bool = False
    # chips this node can host (None = unbounded); the scalar-mode gang
    # scheduler admits against the sum over live nodes
    capacity_chips: Optional[int] = None
    # operator-owned conditions (the Draining state machine); like the
    # cordon flag, the NODE token tier may not touch these — agents
    # heartbeat via merge-patches that never mention the key
    conditions: List[Condition] = field(default_factory=list)


@dataclass
class Node(_Dictable):
    """A registered execution node (the kubelet's Node object). Node agents
    (executor/agent.py) self-register and heartbeat; the NodeMonitor marks
    stale nodes NotReady and evicts their pods (≙ the node controller's
    pod eviction that the reference leans on for worker-loss recovery)."""

    kind: str = "Node"
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    status: NodeStatus = field(default_factory=NodeStatus)


@dataclass
class ObjectRef(_Dictable):
    kind: str = ""
    namespace: str = ""
    name: str = ""
    uid: str = ""


@dataclass
class Event(_Dictable):
    """≙ corev1.Event as used by the reference's recorder (user-facing audit
    log, asserted by the integration eventChecker, v2/test/integration/
    main_test.go:116-178)."""

    kind: str = "Event"
    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    involved: ObjectRef = field(default_factory=ObjectRef)
    type: str = "Normal"  # Normal | Warning
    reason: str = ""
    message: str = ""
    timestamp: float = 0.0


def patch_pod_status(
    store,
    namespace: str,
    name: str,
    uid: str,
    changes: Dict,
    *,
    expected_rv=None,
    attempts: int = 5,
    what: str = "patch-pod-status",
):
    """THE pod status-mirror write (kubelet semantics over the PATCH verb),
    shared by the executor's phase mirror and evict_pod so the guards can
    never fork:

    - **incarnation guard**: ``uid`` must still match — a gang restart
      deleting and recreating the pod same-name must not inherit its
      predecessor's exit;
    - **write-once terminal**: a finished pod is never overwritten (an
      external eviction's retryable reason must survive the reaper of the
      process the eviction then killed).

    Fast path: when the caller holds a snapshot it already verified the
    guards against, ``expected_rv`` rides the patch as an rv precondition —
    a match PROVES the object is byte-identical to that snapshot, so the
    guards hold and the write is ONE request (no GET leg, the
    GET+PUT+409-retry loop collapsed). Only on Conflict does it fall back
    to read-and-re-check, which is exactly what the old loop did every
    time. Returns the committed pod, or None when the pod is gone, a new
    incarnation, or already terminal."""
    body = {"status": dict(changes)}
    if expected_rv:
        try:
            return store.patch(
                "Pod", namespace, name,
                {"metadata": {"resource_version": expected_rv}, **body},
                subresource="status",
            )
        except NotFound:
            return None
        except Conflict:
            pass  # snapshot went stale: re-read and re-check the guards
    for _ in range(attempts):
        try:
            cur = store.get("Pod", namespace, name)
        except NotFound:
            return None
        if uid and cur.metadata.uid != uid:
            return None
        if cur.is_finished():
            return None
        try:
            return store.patch(
                "Pod", namespace, name,
                {"metadata": {
                    "resource_version": cur.metadata.resource_version,
                 }, **body},
                subresource="status",
            )
        except NotFound:
            return None
        except Conflict:
            continue
    import logging

    logging.getLogger("tpujob.machinery").warning(
        "%s: status patch of Pod %s/%s lost the write race %dx; left as-is",
        what, namespace, name, attempts,
    )
    return None


def evict_pod(store, pod: "Pod", message: str, *,
              reason: str = "Evicted") -> bool:
    """Mark a pod Evicted — THE eviction primitive (reason=Evicted is what
    controller._pod_retryable treats as always-retryable, driving the
    gang-coherent restart). Shared by the node monitor (lost nodes),
    `ctl drain`, and the agent's restart reconciliation so the semantics
    can never fork. Returns False when the pod is already gone/finished.
    Callers own their own events/metrics.

    Rides patch_pod_status: the caller's snapshot anchors the rv fast
    path, so the common eviction is one status-subresource PATCH — which
    also means the NODE token tier can evict its own pods without
    full-object write rights."""
    if pod.is_finished():
        # the snapshot itself is terminal: the rv fast path would otherwise
        # trust it and overwrite the write-once terminal status
        return False
    return patch_pod_status(
        store, pod.metadata.namespace, pod.metadata.name, pod.metadata.uid,
        {
            "phase": PodPhase.FAILED,
            "ready": False,
            "reason": reason,  # "Evicted" | "Preempted" (is_evicted)
            "message": message,
        },
        expected_rv=pod.metadata.resource_version,
        what="evict_pod",
    ) is not None


# The on-demand profiling contract (the workload telemetry plane, ISSUE
# 15): `ctl profile <job> --steps N` stamps this TPUJob annotation with a
# JSON request ({"id", "steps", "at"}); the controller projects it into
# the job ConfigMap's "profile" key (the same membership channel the
# elastic protocol already polls), each worker captures a jax.profiler
# trace for N steps into the job's artifact dir and acks completion
# through its train_stats "profile" entry. `ctl profile --status/--fetch`
# read the acks back. Cleared by stamping a new request (one in-flight
# request per job; the id disambiguates).
ANNOTATION_PROFILE_REQUEST = "tpujob.dev/profile-request"


# ---------------------------------------------------------------------------
# bounded status-stats blobs (the workload telemetry plane, ISSUE 15)
# ---------------------------------------------------------------------------

# the stall-attribution bucket scheme — every wall-second of a training
# step classifies into exactly one of these (worker-side) or "restart"
# (controller-side downtime, charged from conditions by the goodput
# aggregator). Shared by the real step loop (runtime/stepstats.py), the
# hollow timelines, and the aggregator, so the attribution can never fork.
TRAIN_BUCKETS = ("compile", "input", "compute", "sync", "ckpt")
# the controller-side bucket: wall time a job spent torn down between
# generations (evict → relaunch), which no worker process can observe
BUCKET_RESTART = "restart"
# the worker's set-up, before its first step: host seconds from the
# process's start (as the OS records it) to the first batch, by what the
# program was doing (runtime/stepstats.setup_span). Once per incarnation,
# so every restart, rescale and rescheduler move shows what it paid.
SETUP_SPANS = ("pre_bootstrap", "cache_config", "rendezvous", "attach",
               "mesh", "ckpt_open", "init_state", "restore")
# what set-up did on another thread WHILE those spans ran: its own wall
# seconds, beside `setup` and never inside it (the spans stay additive).
# `ckpt_import` is the background `import orbax.checkpoint`
# (runtime/bootstrap.py); against `ckpt_open` it says how much of the
# import a start hid.
SETUP_OVERLAPPED = ("ckpt_import",)
# named scalars of a training step that its loss returns beside its value
# and the blob carries under `counters`: the routed feed-forward's
# (parallel/moe.py) — how many of the step's assignments fell to experts
# held here (mean a layer), the fullest held expert over the mean (worst
# layer), assignments that found no row (0: the layer is dropless), the
# rows of the assignment buffer that the passes in row order visit (mean a
# layer: the held rows' tiles where those passes are bounded, every row
# where they are not); and the state-space layers' (models/mamba2.py) — the
# share of the scan's (row, chunk, head) whose decay over the whole chunk
# exceeds 0.1, so that state is handed from chunk to chunk (mean a layer).
TRAIN_COUNTERS = ("moe.assignments_held", "moe.load_max_over_mean",
                  "moe.assignments_dropped", "moe.rows_worked",
                  "ssm.carry_share")

_PROFILE_KEYS = ("id", "state", "dir")


def _r3(v) -> float:
    try:
        return round(float(v), 3)
    except (TypeError, ValueError):
        return 0.0


def _i(v) -> int:
    try:
        return int(v or 0)
    except (TypeError, ValueError):
        return 0


def bounded_serve_stats(qps=0.0, queue_depth=0.0, p99_ms=0.0,
                        **_ignored) -> Dict[str, float]:
    """THE constructor for a pod's ``status.serve_stats`` blob (oplint
    OBS004): exactly three rounded floats, whatever the caller passed.
    Status blobs ride EVERY watch event delivering the pod, so their size
    is a fan-out multiplier — bounding happens at construction, not by
    reviewer vigilance."""
    return {
        "qps": _r3(qps),
        "queue_depth": _r3(queue_depth),
        "p99_ms": _r3(p99_ms),
    }


def bounded_train_stats(step=0, steps=0, step_p50_ms=0.0, buckets=None,
                        profile=None, compile_cache=None, setup=None,
                        setup_overlapped=None, counters=None,
                        **_ignored) -> Dict[str, object]:
    """THE constructor for a pod's ``status.train_stats`` blob (oplint
    OBS004). Fixed key set, rounded floats, bucket keys clamped to the
    :data:`TRAIN_BUCKETS` scheme, profile ack clamped to short strings
    — an unbounded dict here would bloat every watch event carrying the
    pod (the same reason serve_stats is three floats).

    ``step`` is the global step (survives restarts via checkpoint
    resume); ``steps`` counts steps run by THIS incarnation and
    ``buckets`` are THIS incarnation's cumulative attributed seconds —
    both reset on relaunch, which the aggregator's reset-aware deltas
    expect (like a Prometheus counter across a process restart).
    ``setup`` is this incarnation's set-up seconds by span, clamped to
    :data:`SETUP_SPANS`; ``setup_overlapped`` the seconds of what ran on
    another thread meanwhile, clamped to :data:`SETUP_OVERLAPPED`;
    ``counters`` the newest finished step's named scalars, clamped to
    :data:`TRAIN_COUNTERS`."""
    # the source may be a file written by an UNTRUSTED workload process
    # (the executor mirrors whatever the worker flushed): wrong-typed
    # fields degrade to zeros/absence, never an exception out of the
    # executor's poll loop
    if not isinstance(buckets, dict):
        buckets = {}
    out: Dict[str, object] = {
        "step": _i(step),
        "steps": _i(steps),
        "step_p50_ms": _r3(step_p50_ms),
        "buckets": {
            k: _r3(buckets.get(k, 0.0)) for k in TRAIN_BUCKETS
        },
    }
    if isinstance(profile, dict) and profile:
        out["profile"] = {
            k: str(profile.get(k, ""))[:256] for k in _PROFILE_KEYS
        }
    if isinstance(compile_cache, dict) and compile_cache:
        # persistent-compile-cache hit/miss counts (ISSUE 16): present
        # only when the worker configured the cache, so the `compile`
        # bucket can be read as warm (hits, near-zero seconds) vs cold
        # (misses, the full warmup). Two bounded ints, per incarnation.
        out["compile_cache"] = {
            "hits": _i(compile_cache.get("hits")),
            "misses": _i(compile_cache.get("misses")),
        }
    # this incarnation's set-up seconds by span, and beside them what ran
    # on another thread meanwhile: only what ran (a fresh start has no
    # `restore`; an import still running has its seconds so far), only
    # the fixed key sets; the step's counters ride the same way
    for field, given, keys in (("setup", setup, SETUP_SPANS),
                               ("setup_overlapped", setup_overlapped,
                                SETUP_OVERLAPPED),
                               ("counters", counters, TRAIN_COUNTERS)):
        if isinstance(given, dict):
            kept = {k: _r3(given[k]) for k in keys if k in given}
            if kept:
                out[field] = kept
    return out


KINDS = ("TPUJob", "TPUServe", "Alert", "Pod", "Service", "ConfigMap",
         "PodGroup", "Event", "Node")
