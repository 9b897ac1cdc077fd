"""Declarative fleet scenarios: a day in the life of the cluster,
compressed into minutes and replayable bit-for-bit.

Every bench mode so far torments ONE subsystem at a time; production is
all of them at once, for hours. This module extends the ChaosScript
timeline grammar (machinery/chaos.py) from a fault catalog into a full
WORKLOAD DSL, so `BENCH_CP_MODES=soak` can run a scripted "day" against
the deployed shape — diurnal serving load, seeded batch arrivals with a
tenant mix, a rolling maintenance wave, and scripted faults (including
the zero-warning `reclaim`) — with the SLO plane as the only judge:

- :class:`VirtualClock` — ``scale`` scenario seconds pass per wall
  second. Every schedule in the DSL is written in SCENARIO time; the
  clock converts at the edges (timer-wheel delays, notice deadlines), so
  a six-hour day compresses into a minutes-long run whose event ORDER
  and CONTENT are invariant under the compression factor.
- :class:`Scenario` — the parsed, validated document. Like ChaosScript,
  parsing fails fast on unknown sections, unknown knobs, or nonsense
  values: a typo'd curve silently doing nothing would make a "passing"
  soak meaningless. All randomness (arrival times, job names,
  maintenance victims) is resolved by :meth:`Scenario.events` from the
  document seed — two calls return the identical timeline, which is the
  determinism anchor the soak bench asserts by running twice on one
  seed.
- :class:`ScenarioEngine` — walks the precomputed timeline on a thread:
  serve QPS set-points drive the hollow fleet's :class:`ServeLoadModel`,
  arrivals create real TPUJobs through the validating client, waves arm
  the fleet's :meth:`arm_maintenance` (whose knobs the threaded clock
  reads as scenario time), and the embedded chaos section rides an
  ordinary :class:`ChaosController` with wall-converted fire times.
  Like the chaos controller, ``executed`` is an audit trail — a soak
  leaves a replayable record, not a vibe.

Scenario format (YAML or JSON; ALL times/rates are scenario seconds)::

    seed: 1807
    scale: 60.0          # one wall second = one scenario minute
    duration: 21600      # a six-hour day
    serves:
      - {serve: soak/web, curve: diurnal, peak_qps: 400, trough_qps: 40,
         period: 21600, interval: 300}
    arrivals:
      - {tenant: etl, rate_per_hour: 40, pods: 2, chips: 1, end: 18000}
    maintenance:
      - {at: 7200, fraction: 0.2, notice: 600, stagger: 120}
    chaos:
      - {at: 10800, fault: reclaim, target: hollow-0003}
"""

from __future__ import annotations

import logging
import math
import random
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from mpi_operator_tpu.machinery.chaos import (
    ChaosAction,
    ChaosController,
    ChaosScript,
    ChaosScriptError,
)

log = logging.getLogger("tpujob.scenario")

# the tenant-mix label arrivals stamp on their jobs (fairness dashboards
# and the soak's per-tenant assertions read it back)
LABEL_TENANT = "tpujob.dev/tenant"

CURVES = ("diurnal", "flat")


class ScenarioError(ValueError):
    """Malformed scenario document (the ChaosScript fail-fast posture)."""


class VirtualClock:
    """Scenario time ↔ wall time. ``scale`` is scenario seconds per wall
    second (scale 60: a scripted hour takes a wall minute). Conversions
    are stateless — only :meth:`now` anchors to construction time — so
    one clock can be shared by the engine, the hollow fleet's timer
    wheel, and the bench without ordering constraints."""

    def __init__(self, scale: float = 1.0):
        scale = float(scale)
        if not scale > 0:
            raise ValueError(f"time scale must be > 0, got {scale}")
        self.scale = scale
        self._t0 = time.monotonic()

    def to_wall(self, virtual_s: float) -> float:
        return float(virtual_s) / self.scale

    def to_virtual(self, wall_s: float) -> float:
        return float(wall_s) * self.scale

    def now(self) -> float:
        """Scenario seconds elapsed since this clock was created."""
        return (time.monotonic() - self._t0) * self.scale


def _reject_unknown(section: str, i: int, doc: Dict[str, Any],
                    allowed: set) -> None:
    unknown = set(doc) - allowed
    if unknown:
        raise ScenarioError(
            f"{section}[{i}]: unknown keys {sorted(unknown)} (they would "
            f"be silently ignored; valid: {sorted(allowed)})"
        )


def _num(section: str, i: int, doc: Dict[str, Any], key: str,
         default: Optional[float] = None, *, minimum: float = 0.0) -> float:
    if key not in doc:
        if default is None:
            raise ScenarioError(f"{section}[{i}]: {key!r} is required")
        return default
    try:
        v = float(doc[key])
    except (TypeError, ValueError):
        raise ScenarioError(
            f"{section}[{i}]: {key!r} must be a number, got {doc[key]!r}"
        ) from None
    if v < minimum:
        raise ScenarioError(f"{section}[{i}]: {key!r} must be >= {minimum}")
    return v


@dataclass(frozen=True)
class ServeCurve:
    """One serve's offered-QPS schedule. ``diurnal`` is the classic
    day-shape: trough at t=0, peak half a ``period`` later (a raised
    cosine); ``flat`` pins ``peak_qps``. The engine samples the curve
    every ``interval`` scenario seconds into set-point events."""

    serve: str              # "<ns>/<name>" — the ServeLoadModel key
    curve: str = "diurnal"
    peak_qps: float = 100.0
    trough_qps: float = 0.0
    period: float = 86400.0
    interval: float = 60.0
    start: float = 0.0
    end: Optional[float] = None

    def qps_at(self, t: float) -> float:
        if self.curve == "flat":
            return self.peak_qps
        phase = 2.0 * math.pi * ((t - self.start) / self.period)
        mid = (self.peak_qps + self.trough_qps) / 2.0
        amp = (self.peak_qps - self.trough_qps) / 2.0
        return mid - amp * math.cos(phase)


@dataclass(frozen=True)
class ArrivalProcess:
    """A seeded Poisson arrival stream of batch gangs for one tenant:
    exponential interarrivals at ``rate_per_hour`` between ``start`` and
    ``end`` (scenario seconds), each submitting a ``pods``-member gang of
    ``chips`` chips per host."""

    tenant: str
    rate_per_hour: float
    pods: int = 1
    chips: int = 1
    start: float = 0.0
    end: Optional[float] = None


@dataclass(frozen=True)
class MaintenanceWave:
    """A rolling maintenance wave armed at ``at``: ``fraction`` of the
    fleet (seeded choice) gets a notice with ``notice`` scenario seconds
    of warning, one node every ``stagger``."""

    at: float
    fraction: float = 0.1
    notice: float = 600.0
    stagger: float = 60.0


class Scenario:
    """A validated scenario document. Parse once; :meth:`events` resolves
    every seeded draw into one deterministic, sorted timeline."""

    def __init__(self, *, seed: int, scale: float, duration: float,
                 serves: List[ServeCurve],
                 arrivals: List[ArrivalProcess],
                 maintenance: List[MaintenanceWave],
                 chaos: Optional[ChaosScript]):
        self.seed = seed
        self.scale = scale
        self.duration = duration
        self.serves = serves
        self.arrivals = arrivals
        self.maintenance = maintenance
        self.chaos = chaos

    @classmethod
    def parse(cls, doc: Dict[str, Any]) -> "Scenario":
        if not isinstance(doc, dict):
            raise ScenarioError("scenario must be a mapping")
        unknown = set(doc) - {"seed", "scale", "duration", "serves",
                              "arrivals", "maintenance", "chaos"}
        if unknown:
            raise ScenarioError(f"unknown top-level keys {sorted(unknown)}")
        seed = doc.get("seed", 0)
        if not isinstance(seed, int):
            raise ScenarioError(f"seed must be an integer, got {seed!r}")
        top = {"scale": doc.get("scale", 1.0),
               "duration": doc.get("duration")}
        scale = _num("scenario", 0, top, "scale", 1.0)
        if scale <= 0:
            raise ScenarioError("scale must be > 0")
        duration = _num("scenario", 0, top, "duration")
        if duration <= 0:
            raise ScenarioError("duration must be > 0")

        serves: List[ServeCurve] = []
        for i, s in enumerate(doc.get("serves") or []):
            if not isinstance(s, dict):
                raise ScenarioError(f"serves[{i}]: must be a mapping")
            _reject_unknown("serves", i, s, {
                "serve", "curve", "peak_qps", "trough_qps", "period",
                "interval", "start", "end",
            })
            serve = str(s.get("serve", ""))
            if "/" not in serve:
                raise ScenarioError(
                    f"serves[{i}]: 'serve' must be '<namespace>/<name>', "
                    f"got {serve!r}"
                )
            curve = str(s.get("curve", "diurnal"))
            if curve not in CURVES:
                raise ScenarioError(
                    f"serves[{i}]: unknown curve {curve!r} (one of {CURVES})"
                )
            serves.append(ServeCurve(
                serve=serve, curve=curve,
                peak_qps=_num("serves", i, s, "peak_qps", 100.0),
                trough_qps=_num("serves", i, s, "trough_qps", 0.0),
                period=_num("serves", i, s, "period", duration,
                            minimum=1e-9),
                interval=_num("serves", i, s, "interval", 60.0,
                              minimum=1e-9),
                start=_num("serves", i, s, "start", 0.0),
                end=(_num("serves", i, s, "end") if "end" in s else None),
            ))

        arrivals: List[ArrivalProcess] = []
        for i, a in enumerate(doc.get("arrivals") or []):
            if not isinstance(a, dict):
                raise ScenarioError(f"arrivals[{i}]: must be a mapping")
            _reject_unknown("arrivals", i, a, {
                "tenant", "rate_per_hour", "pods", "chips", "start", "end",
            })
            tenant = str(a.get("tenant", ""))
            if not tenant:
                raise ScenarioError(f"arrivals[{i}]: 'tenant' is required")
            rate = _num("arrivals", i, a, "rate_per_hour")
            if rate <= 0:
                raise ScenarioError(
                    f"arrivals[{i}]: rate_per_hour must be > 0"
                )
            pods = int(a.get("pods", 1))
            chips = int(a.get("chips", 1))
            if pods < 1 or chips < 1:
                raise ScenarioError(
                    f"arrivals[{i}]: pods and chips must be >= 1"
                )
            arrivals.append(ArrivalProcess(
                tenant=tenant, rate_per_hour=rate, pods=pods, chips=chips,
                start=_num("arrivals", i, a, "start", 0.0),
                end=(_num("arrivals", i, a, "end") if "end" in a else None),
            ))

        waves: List[MaintenanceWave] = []
        for i, w in enumerate(doc.get("maintenance") or []):
            if not isinstance(w, dict):
                raise ScenarioError(f"maintenance[{i}]: must be a mapping")
            _reject_unknown("maintenance", i, w,
                            {"at", "fraction", "notice", "stagger"})
            fraction = _num("maintenance", i, w, "fraction", 0.1)
            if not 0.0 < fraction <= 1.0:
                raise ScenarioError(
                    f"maintenance[{i}]: fraction must be in (0, 1]"
                )
            waves.append(MaintenanceWave(
                at=_num("maintenance", i, w, "at"),
                fraction=fraction,
                notice=_num("maintenance", i, w, "notice", 600.0,
                            minimum=1e-9),
                stagger=_num("maintenance", i, w, "stagger", 60.0),
            ))

        chaos = None
        if doc.get("chaos"):
            # the embedded fault timeline reuses the ChaosScript grammar
            # VERBATIM (knob whitelists included): one validator, one
            # set of error messages, and the new `reclaim` verb comes for free
            try:
                chaos = ChaosScript.parse(
                    {"seed": seed, "actions": doc["chaos"]}
                )
            except ChaosScriptError as e:
                raise ScenarioError(f"chaos: {e}") from None
        return cls(seed=seed, scale=scale, duration=duration,
                   serves=serves, arrivals=arrivals, maintenance=waves,
                   chaos=chaos)

    @classmethod
    def load(cls, path: str) -> "Scenario":
        import yaml  # YAML is a superset of JSON: one loader serves both

        with open(path) as f:
            try:
                doc = yaml.safe_load(f)
            except yaml.YAMLError as e:
                raise ScenarioError(f"{path}: {e}") from None
        try:
            return cls.parse(doc)
        except ScenarioError as e:
            raise ScenarioError(f"{path}: {e}") from None

    # -- the deterministic timeline -----------------------------------------

    def events(self) -> List[Tuple[float, str, Dict[str, Any]]]:
        """The full resolved timeline: sorted (scenario_t, kind, payload)
        tuples with every random draw already taken from the document
        seed. Chaos actions are NOT in this list — they ride their own
        :class:`ChaosController` (see :meth:`ScenarioEngine.start`) so
        the fault catalog's apply logic is reused, not reimplemented."""
        out: List[Tuple[float, str, Dict[str, Any]]] = []
        for c in self.serves:
            end = min(self.duration, self.end_or(c.end))
            t = c.start
            while t < end:
                out.append((t, "serve-qps", {
                    "serve": c.serve, "qps": round(c.qps_at(t), 3),
                }))
                t += c.interval
        for a in self.arrivals:
            rng = random.Random(f"{self.seed}:arrivals:{a.tenant}")
            end = min(self.duration, self.end_or(a.end))
            t, i = a.start, 0
            while True:
                t += rng.expovariate(a.rate_per_hour / 3600.0)
                if t >= end:
                    break
                out.append((t, "submit", {
                    "name": f"{a.tenant}-{i:04d}", "tenant": a.tenant,
                    "pods": a.pods, "chips": a.chips,
                }))
                i += 1
        for w in self.maintenance:
            out.append((w.at, "maintenance-wave", {
                "fraction": w.fraction, "notice": w.notice,
                "stagger": w.stagger,
            }))
        # stable order under ties: kind then payload repr — the same
        # document always replays the same sequence
        out.sort(key=lambda e: (e[0], e[1], repr(e[2])))
        return out

    def end_or(self, end: Optional[float]) -> float:
        return self.duration if end is None else end


class ScenarioEngine:
    """Drives one :class:`Scenario` against a store (and optionally a
    hollow fleet) in wall time, through a shared :class:`VirtualClock`.

    ``fleet`` is any :class:`~mpi_operator_tpu.executor.hollow.
    HollowFleet`-shaped object; serve curves need its timeline to carry a
    :class:`ServeLoadModel`, maintenance waves ride its
    ``arm_maintenance``. Chaos process/store targets default to the
    fleet's nodes (killable via ``kill_node``) and can be extended or
    overridden with ``chaos_targets``. Missing plumbing fails loudly at
    fire time and lands in ``executed`` — the ChaosController posture: a
    scenario that silently skipped half its script would make a passing
    soak meaningless."""

    def __init__(self, scenario: Scenario, store, *,
                 fleet=None, namespace: str = "soak",
                 clock: Optional[VirtualClock] = None,
                 chaos_proxy=None, chaos_targets: Optional[Dict] = None,
                 chaos_fabric=None, submit=None):
        self.scenario = scenario
        self.store = store
        self.fleet = fleet
        self.namespace = namespace
        self.clock = clock or VirtualClock(scenario.scale)
        self.chaos_proxy = chaos_proxy
        self.chaos_targets = dict(chaos_targets or {})
        self.chaos_fabric = chaos_fabric
        self._submit = submit
        self.events = scenario.events()
        self.submitted: List[str] = []  # "<ns>/<name>" of created jobs
        # (scenario_t, kind, detail, error | None): the audit trail
        self.executed: List[Tuple[float, str, str, Optional[str]]] = []
        self.chaos: Optional[ChaosController] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._t0 = 0.0

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ScenarioEngine":
        self._t0 = time.monotonic()
        if self.scenario.chaos is not None:
            targets = dict(self.chaos_targets)
            if self.fleet is not None:
                from mpi_operator_tpu.executor.hollow import HollowNodeTarget

                for name in self.fleet.node_names:
                    targets.setdefault(
                        name, HollowNodeTarget(self.fleet, name)
                    )
            self.chaos = ChaosController(
                self._wall_chaos(self.scenario.chaos),
                proxy=self.chaos_proxy, targets=targets,
                fabric=self.chaos_fabric, store=self.store,
            ).arm()
        self._thread = threading.Thread(
            target=self._run, name="scenario-engine", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.chaos is not None:
            self.chaos.stop()

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)
        if self.chaos is not None:
            self.chaos.join(timeout)

    def done(self) -> bool:
        return (self._thread is not None and not self._thread.is_alive()
                and (self.chaos is None or self.chaos.done()))

    def errors(self) -> List[str]:
        out = [f"t={t:.0f} {kind} {detail}: {err}"
               for t, kind, detail, err in self.executed if err]
        if self.chaos is not None:
            out += [f"chaos t={t:.1f} {a.fault}: {e}"
                    for t, a, e in self.chaos.executed if e]
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Freeze the engine's store into a :func:`snapshot_store` document
        — the reachable start states ``analysis convcheck`` judges come
        from here."""
        return snapshot_store(self.store)

    def _wall_chaos(self, script: ChaosScript) -> ChaosScript:
        """The embedded fault timeline, converted to wall time: `at`,
        active-rule deadlines AND injected delay amounts all compress —
        a scripted 30s network delay in a 60x day is a 0.5s delay, or
        the compressed run would be proportionally sicker than the day
        it models."""
        acts = [ChaosAction(
            at=self.clock.to_wall(a.at), fault=a.fault, target=a.target,
            match=a.match, prob=a.prob,
            seconds=self.clock.to_wall(a.seconds),
            until=(None if a.until is None
                   else self.clock.to_wall(a.until)),
            a=a.a, b=a.b,
        ) for a in script.actions]
        return ChaosScript(script.seed, acts)

    # -- the timeline walk --------------------------------------------------

    def _run(self) -> None:
        for vt, kind, payload in self.events:
            delay = self._t0 + self.clock.to_wall(vt) - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            err = None
            try:
                self._apply(kind, payload)
            except Exception as e:  # one failed event must not end the day
                err = f"{type(e).__name__}: {e}"
                log.warning("scenario event %s %s failed: %s",
                            kind, payload, err)
            self.executed.append((vt, kind, self._detail(kind, payload),
                                  err))

    @staticmethod
    def _detail(kind: str, payload: Dict[str, Any]) -> str:
        if kind == "serve-qps":
            return f"{payload['serve']}@{payload['qps']}"
        if kind == "submit":
            return payload["name"]
        return repr(payload)

    def _apply(self, kind: str, payload: Dict[str, Any]) -> None:
        if kind == "serve-qps":
            load = getattr(getattr(self.fleet, "timeline", None),
                           "load", None)
            if load is None:
                raise RuntimeError(
                    "serve curves need a fleet whose HollowTimeline "
                    "carries a ServeLoadModel"
                )
            load.set_offered(payload["serve"], payload["qps"])
            return
        if kind == "submit":
            if self._submit is not None:
                self._submit(payload)
            else:
                self._create_job(payload)
            self.submitted.append(f"{self.namespace}/{payload['name']}")
            return
        if kind == "maintenance-wave":
            if self.fleet is None:
                raise RuntimeError("maintenance waves need a fleet")
            from mpi_operator_tpu.executor.hollow import MaintenanceSchedule

            # start_s=0: the wave's own `at` already positioned it; the
            # schedule knobs are scenario seconds — the fleet's clock
            # (threaded through its timer wheel) converts them
            self.fleet.arm_maintenance(MaintenanceSchedule(
                fraction=payload["fraction"],
                notice_s=payload["notice"],
                start_s=0.0,
                stagger_s=payload["stagger"],
                seed=self.scenario.seed,
            ))
            return
        raise RuntimeError(f"unknown scenario event kind {kind!r}")

    def _create_job(self, payload: Dict[str, Any]) -> None:
        from mpi_operator_tpu.api.client import TPUJobClient

        TPUJobClient(self.store).create({
            "kind": "TPUJob",
            "metadata": {
                "name": payload["name"], "namespace": self.namespace,
                "labels": {LABEL_TENANT: payload["tenant"]},
            },
            "spec": {
                "slice": {"accelerator": "cpu",
                          "chips_per_host": payload["chips"]},
                # the admission plane insists the two names for one
                # quantity agree — a multi-chip arrival without this is
                # rejected at create
                "slots_per_worker": payload["chips"],
                "run_policy": {"clean_pod_policy": "None"},
                "worker": {"replicas": payload["pods"], "template": {
                    "containers": [{"image": "soak/noop",
                                    "command": ["true"]}],
                }},
            },
        })


# ---------------------------------------------------------------------------
# store snapshots — the export seam for offline analysis (convcheck)
# ---------------------------------------------------------------------------

SNAPSHOT_VERSION = 1


def snapshot_store(store) -> Dict[str, Any]:
    """Export every object in the store as a plain-dict document.

    The document is the reachable-state seam between the scenario plane and
    offline analysis: ``analysis convcheck`` replays its start-state corpus
    from exactly this shape, so a paused soak run can be frozen mid-rollout /
    mid-drain and judged for convergence without re-running the day."""
    from mpi_operator_tpu.machinery import serialize

    objects = []
    for kind in sorted(serialize.KIND_CLASSES):
        for obj in store.list(kind):
            objects.append({"kind": kind, "object": serialize.encode(obj)})
    return {"version": SNAPSHOT_VERSION, "objects": objects}


def restore_store(store, doc: Dict[str, Any]) -> int:
    """Load a :func:`snapshot_store` document into ``store`` (create-only:
    the target is expected empty). Fails closed — an unknown kind, a wrong
    version or a malformed entry raises :class:`ScenarioError` rather than
    silently building a half-world. Returns the object count."""
    from mpi_operator_tpu.machinery import serialize

    if not isinstance(doc, dict):
        raise ScenarioError(f"snapshot must be a mapping, got "
                            f"{type(doc).__name__}")
    version = doc.get("version")
    if version != SNAPSHOT_VERSION:
        raise ScenarioError(f"unsupported snapshot version {version!r} "
                            f"(want {SNAPSHOT_VERSION})")
    entries = doc.get("objects")
    if not isinstance(entries, list):
        raise ScenarioError("snapshot 'objects' must be a list")
    n = 0
    for i, entry in enumerate(entries):
        if not isinstance(entry, dict) or not isinstance(
                entry.get("object"), dict):
            raise ScenarioError(f"snapshot objects[{i}] is malformed")
        kind = entry.get("kind")
        try:
            obj = serialize.decode(kind, entry["object"])
        except KeyError:
            raise ScenarioError(f"snapshot objects[{i}] has unknown kind "
                                f"{kind!r}") from None
        except Exception as e:
            raise ScenarioError(
                f"snapshot objects[{i}] ({kind}) failed to decode: {e}"
            ) from None
        # the snapshot carries authoritative uids; keep them so uid-pinned
        # patches in the replayed loops still match
        store.create(obj)
        n += 1
    return n


def smoke() -> int:
    """The <30s scenario smoke (verify SKILL.md static gate): a 90-
    scenario-second "day" at 30x compression — a diurnal serve curve, a
    seeded arrival stream, and a rolling maintenance wave — against an
    in-process store + controllers + 4-node hollow fleet. Bars: the
    resolved timeline is deterministic (two resolutions identical), every
    engine event applied cleanly, the serve load model saw a nonzero
    set-point, at least one arrival job Succeeded, and the wave's notice
    landed (a node carries the maintenance annotation). One JSON line;
    exit 0 iff all hold."""
    import json

    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.controller.controller import TPUJobController
    from mpi_operator_tpu.controller.disruption import DrainController
    from mpi_operator_tpu.executor.hollow import (
        HollowFleet,
        HollowTimeline,
        ServeLoadModel,
    )
    from mpi_operator_tpu.machinery.events import EventRecorder
    from mpi_operator_tpu.machinery.objects import (
        ANNOTATION_MAINTENANCE_AT,
        NODE_NAMESPACE,
    )
    from mpi_operator_tpu.machinery.store import ObjectStore
    from mpi_operator_tpu.scheduler.gang import GangScheduler

    t0 = time.time()
    doc = {
        "seed": 7, "scale": 30.0, "duration": 90.0,
        "serves": [{"serve": "soak/web", "curve": "diurnal",
                    "peak_qps": 80.0, "trough_qps": 10.0,
                    "period": 90.0, "interval": 15.0}],
        "arrivals": [{"tenant": "etl", "rate_per_hour": 360.0,
                      "pods": 2, "chips": 1, "end": 60.0}],
        "maintenance": [{"at": 30.0, "fraction": 0.25, "notice": 30.0,
                         "stagger": 5.0}],
    }
    scenario = Scenario.parse(doc)
    deterministic = scenario.events() == Scenario.parse(doc).events()
    clock = VirtualClock(scenario.scale)
    store = ObjectStore()
    recorder = EventRecorder(store)
    load = ServeLoadModel()
    ctrl = TPUJobController(store, recorder)
    sched = GangScheduler(store, recorder)
    drain = DrainController(store, recorder, interval=0.1)
    fleet = HollowFleet(
        store, 4, timeline=HollowTimeline(run_s=0.3, load=load),
        capacity_chips=4, heartbeat_interval=0.5, clock=clock,
    )
    ctrl.run()
    sched.start()
    fleet.start()
    drain.start()
    engine = ScenarioEngine(scenario, store, fleet=fleet, clock=clock)
    out = {"metric": "scenario_smoke", "ok": False,
           "events": len(engine.events)}
    try:
        engine.start()
        deadline = time.time() + 20.0
        while time.time() < deadline and not engine.done():
            time.sleep(0.1)
        # let the last arrivals finish their 0.3s scripted run
        deadline = time.time() + 10.0
        succeeded = 0
        while time.time() < deadline:
            succeeded = sum(
                1 for key in engine.submitted
                if cond.is_succeeded(store.get(
                    "TPUJob", *key.split("/", 1)).status)
            )
            if succeeded == len(engine.submitted):
                break
            time.sleep(0.1)
        noticed = [
            n.metadata.name for n in store.list("Node", NODE_NAMESPACE)
            if ANNOTATION_MAINTENANCE_AT in n.metadata.annotations
        ]
        out.update({
            "deterministic": deterministic,
            "submitted": len(engine.submitted),
            "succeeded": succeeded,
            "offered_qps": load.offered("soak/web"),
            "noticed_nodes": len(noticed),
            "errors": engine.errors()[:5],
            "elapsed_s": round(time.time() - t0, 1),
        })
        out["ok"] = bool(
            deterministic
            and engine.done()
            and not engine.errors()
            and engine.submitted
            and succeeded == len(engine.submitted)
            and load.offered("soak/web") > 0
            and noticed
        )
    except Exception as e:
        log.exception("scenario smoke failed")
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        engine.stop()
        drain.stop()
        fleet.stop()
        sched.stop()
        ctrl.stop()
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpu-scenario",
        description="Declarative fleet-scenario engine (the soak bench's "
                    "workload DSL).",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="run the <30s in-process scenario smoke: a 30x-"
                         "compressed 90s day against a hollow fleet; "
                         "exit 0 iff every bar holds")
    ap.add_argument("--validate", metavar="FILE",
                    help="parse a scenario file and print its resolved "
                         "event count (exit 2 on a malformed document)")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    if args.validate:
        import json

        try:
            scenario = Scenario.load(args.validate)
        except ScenarioError as e:
            print(f"invalid scenario: {e}")
            return 2
        events = scenario.events()
        print(json.dumps({
            "ok": True, "seed": scenario.seed, "scale": scenario.scale,
            "duration": scenario.duration, "events": len(events),
            "chaos_actions": (len(scenario.chaos.actions)
                              if scenario.chaos else 0),
        }))
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
