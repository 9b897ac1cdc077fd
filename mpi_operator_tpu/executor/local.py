"""LocalExecutor: a process-level kubelet for TPUJob worker pods.

Watches the ObjectStore for Pods, launches each pod's container command as an
OS process with the pod's env (the controller-injected TPUJOB_* rendezvous
contract included), and mirrors the process lifecycle back into pod status:

  PENDING → (spawn) → RUNNING → SUCCEEDED | FAILED(exit code)

which is exactly the signal the controller's status mirror consumes
(≙ kubelet feeding updateMPIJobStatus,
/root/reference/v2/pkg/controller/mpi_job_controller.go:921-996).

Local DNS shim: pod hostnames like ``<job>-worker-0.<job>-worker`` only
resolve inside a cluster's headless service; locally every "host" shares the
loopback interface, so the coordinator address env is rewritten to
127.0.0.1 (ports disambiguate jobs). This mirrors what the reference's
Intel entrypoint does when it pre-resolves worker hostnames
(examples/pi/intel-entrypoint.sh:27-33) — resolution is an executor concern,
not a workload concern.
"""

from __future__ import annotations

import logging
import os
import queue
import subprocess
import tempfile
import threading
import uuid
from typing import Dict, Optional

from mpi_operator_tpu.machinery import trace
from mpi_operator_tpu.machinery.objects import (
    NODE_NAMESPACE,
    Pod,
    PodPhase,
    bounded_train_stats,
    patch_pod_status,
)
from mpi_operator_tpu.machinery.store import (
    ADDED,
    DELETED,
    MODIFIED,
    NotFound,
    ObjectStore,
)
from mpi_operator_tpu.runtime.emulation import pin_host_device_count
from mpi_operator_tpu.runtime.stepstats import ENV_STATS_FILE, read_stats

log = logging.getLogger("tpujob.executor")


# dlopen + symbol resolution happen HERE, at import time in the parent:
# the pre-exec hook below runs in the forked child of a heavily threaded
# process, where glibc's allocator/loader locks may be held by a thread
# that no longer exists — an import or CDLL there can deadlock the child
# between fork and exec and the pod never starts. Linux-only; None elsewhere.
try:
    import ctypes as _ctypes
    import signal as _signal

    _LIBC = _ctypes.CDLL("libc.so.6", use_errno=True)
    _LIBC.prctl  # resolve the symbol now, not after fork
    _SIGKILL = int(_signal.SIGKILL)
# oplint: disable=EXC001 — non-Linux / no-glibc platform probe: _LIBC=None
# IS the handled outcome (the hook degrades to a no-op), nothing to log
except Exception:
    _LIBC = None
    _SIGKILL = 9


def _die_with_parent() -> None:
    """Child-side pre-exec hook: SIGKILL this process when the executor
    dies (PR_SET_PDEATHSIG). An executor crash therefore behaves like a
    node crash — no orphan workers silently holding ports/collectives —
    which is exactly what the NodeAgent's restart reconciliation and the
    NodeMonitor's eviction already assume. Only async-signal-safe-ish work
    allowed here (see _LIBC above)."""
    if _LIBC is None:
        return
    try:
        _LIBC.prctl(1, _SIGKILL)  # PR_SET_PDEATHSIG = 1
    # oplint: disable=EXC001 — post-fork pre-exec hook: logging here can
    # deadlock on the logging module's lock held by a vanished thread;
    # only async-signal-safe-ish work is allowed (see _LIBC above)
    except Exception:
        pass

ENV_COORDINATOR = "TPUJOB_COORDINATOR_ADDRESS"
ENV_CONFIG_DIR = "TPUJOB_CONFIG_DIR"
LABEL_JOB_NAME = "tpujob.dev/job-name"
# restart generation the pod was launched for (duplicated from
# controller/controller.py, same as LABEL_JOB_NAME: the executor must not
# import the controller) — launch spans carry it so `ctl trace` can tell
# the checkpoint-resume relaunch from the original generation
LABEL_GENERATION = "tpujob.dev/generation"


class LocalExecutor:
    """Runs every Pod in the store as a local OS process."""

    def __init__(
        self,
        store: ObjectStore,
        *,
        loopback_rewrite: bool = True,
        extra_env: Optional[Dict[str, str]] = None,
        workdir: Optional[str] = None,
        require_binding: bool = False,
        logs_dir: Optional[str] = None,
        node_name: Optional[str] = None,
        log_url_base: Optional[str] = None,
        status_sink=None,
        eviction_grace: float = 5.0,
        stepstats_poll: float = 1.0,
    ):
        self.store = store
        self.loopback_rewrite = loopback_rewrite
        # kubelet semantics: with a scheduler in play, only bound pods run
        # (spec.node_name set by scheduler/gang.py's atomic admission)
        self.require_binding = require_binding
        # node identity (executor/agent.py): claim ONLY pods bound to this
        # node — the per-node kubelet role; None = run every bound pod
        # (single-node LocalExecutor behavior)
        self.node_name = node_name
        # when set, pod.status.log_path gets f"{base}/<file>" instead of a
        # local filesystem path, so `ctl logs` works cross-node through the
        # agent's log endpoint
        self.log_url_base = log_url_base.rstrip("/") if log_url_base else None
        self.extra_env = dict(extra_env or {})
        self.workdir = workdir
        # when set (agent mode), status mirrors are enqueued here instead of
        # written directly: the NodeAgent flushes the sink together with its
        # Node heartbeat as ONE patch-batch request per tick
        self.status_sink = status_sink
        # eviction termination grace (≙ terminationGracePeriodSeconds): an
        # evicted pod gets SIGTERM first so checkpoint-capable workloads
        # force-save before the SIGKILL lands (ops/elastic.py routes the
        # signal into a gang-synchronized checkpoint-and-exit). 0 = the old
        # immediate-SIGKILL behavior.
        self.eviction_grace = eviction_grace
        self._procs: Dict[str, subprocess.Popen] = {}  # pod key → process
        # pod key → SIGKILL backstop timer of an in-progress graceful
        # termination: a deletion landing inside the grace window (the
        # controller's gang restart deletes evicted pods moments after the
        # monitor/scheduler marked them) must NOT hard-kill the draining
        # process — that would snatch the force-checkpoint window the
        # SIGTERM just granted (kube honors the grace period on delete too)
        self._terminating: Dict[str, threading.Timer] = {}
        # pod key → deleted-but-still-draining predecessor process: a
        # recreated same-name pod (the next restart generation) must not
        # launch until this process exits — the job's coordinator port is
        # stable across generations, so two live generations would collide
        # on the bind (EADDRINUSE → non-retryable crash → burnt backoff)
        self._draining: Dict[str, subprocess.Popen] = {}
        # pod key → (uid, rv) of our last committed status write: anchors
        # the next patch's rv precondition so the mirror stays 1 request
        # (only this executor writes a bound pod's status in steady state).
        # Own lock: _set_phase runs both inside and outside _lock.
        self._status_rv: Dict[str, tuple] = {}
        self._rv_lock = threading.Lock()
        # workload telemetry (ISSUE 15): each launched pod gets a
        # $TPUJOB_STEPSTATS_FILE pointing into the log dir; a poll thread
        # mirrors the worker's flushed blob into pod.status.train_stats —
        # the kubelet-reads-cAdvisor shape, so workers never need store
        # credentials. pod key → {path, ns, name, uid, mtime}
        self.stepstats_poll = stepstats_poll
        self._stats_files: Dict[str, Dict] = {}
        self.logs: Dict[str, tuple] = {}  # pod key → (stdout, stderr)
        # kubelet log dir: pod stdout/stderr stream to files here while the
        # pod runs; the stdout path is stamped into pod.status.log_path so
        # `ctl logs` (any process on this node) can read it
        self.logs_dir = logs_dir or tempfile.mkdtemp(prefix="tpujob-logs-")
        self._config_root = tempfile.mkdtemp(prefix="tpujob-config-")
        # the persistent compile cache must never hang off these temporary
        # directories (a cache that moves never hits): the worker places it
        # (runtime/compile_cache.py), from the $JAX_COMPILATION_CACHE_DIR
        # that _launch passes through with the rest of the environment
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list = []
        self._watch_q = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._watch_q = self.store.watch(None)
        t = threading.Thread(target=self._run, name="local-executor", daemon=True)
        t.start()
        self._threads.append(t)
        if self.stepstats_poll > 0:
            ts = threading.Thread(
                target=self._stats_loop, name="stepstats-poll", daemon=True
            )
            ts.start()
            self._threads.append(ts)
        # adopt objects that existed before the watch began (configs first:
        # pods read the projected dir at launch)
        for cm in self.store.list("ConfigMap"):
            self._project_config(cm)
        for pod in self.store.list("Pod"):
            self._maybe_launch(pod)

    def stop(self) -> None:
        self._stop.set()
        if self._watch_q is not None:
            self.store.stop_watch(self._watch_q)
        with self._lock:
            # draining predecessors included: their grace ends with the
            # executor (same as every other managed process)
            for p in (*self._procs.values(), *self._draining.values()):
                if p.poll() is None:
                    p.kill()

    def join_reapers(self, timeout: float = 2.0) -> None:
        """Wait for in-flight reap threads to finish recording their pods'
        exits (stop() just killed the processes, so they return promptly).
        A stopping NodeAgent calls this before its final batcher flush —
        otherwise the terminal mirrors the reapers are about to enqueue
        would land in a sink nobody drains again."""
        import time

        deadline = time.time() + timeout
        for t in list(self._threads):
            if t.is_alive() and t.name.startswith("reap-"):
                t.join(timeout=max(0.0, deadline - time.time()))

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Block until no managed process is still running (for tests/CLI)."""
        import time

        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._lock:
                if all(p.poll() is not None for p in self._procs.values()):
                    return True
            time.sleep(0.05)
        return False

    # -- internals ---------------------------------------------------------

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                ev = self._watch_q.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                # the delivering event's origin span (the binding patch,
                # the eviction write) parents the launch/evict spans below
                trace.set_delivery(getattr(ev, "trace", None))
                try:
                    if ev.kind == "ConfigMap" and ev.type in (ADDED, MODIFIED):
                        self._project_config(ev.obj)
                    elif ev.kind == "Pod" and ev.type in (ADDED, MODIFIED):
                        self._kill_if_evicted(ev.obj)
                        self._maybe_launch(ev.obj)
                    elif ev.kind == "Pod" and ev.type == DELETED:
                        self._forget(ev.obj)
                finally:
                    trace.clear_delivery()
            except Exception:
                # this thread is the PDEATHSIG parent of every pod process:
                # if it dies, the kernel SIGKILLs all of them. A bad event
                # must never take down the node's workload.
                log.exception("executor event handling failed; continuing")

    def _stats_loop(self) -> None:
        """Mirror each live pod's flushed step-stats blob into
        pod.status.train_stats (the workload telemetry plane, ISSUE 15).
        mtime-gated: an idle worker (or one with stepstats off) costs one
        stat() per poll, zero store writes."""
        while not self._stop.wait(self.stepstats_poll):
            with self._lock:
                entries = list(self._stats_files.items())
            for key, ent in entries:
                try:
                    mtime = os.stat(ent["path"]).st_mtime
                except OSError:
                    continue  # worker never flushed (stepstats dormant)
                if mtime <= ent["mtime"]:
                    continue
                raw = read_stats(ent["path"])
                if raw is None:
                    continue  # torn/unreadable: next poll retries
                ent["mtime"] = mtime
                try:
                    # re-bound at the mirror edge (oplint OBS004), INSIDE
                    # the guard: the file is written by an untrusted
                    # workload — a wrong-typed field must cost one skipped
                    # mirror, never this thread (which serves every pod
                    # on the node)
                    changes = {"train_stats": bounded_train_stats(**raw)}
                    self._mirror_train_stats(ent, changes)
                except Exception:
                    log.warning("train_stats mirror of %s failed", key,
                                exc_info=True)

    def _mirror_train_stats(self, ent: Dict, changes: Dict) -> None:
        if self.status_sink is not None:
            # agent mode: coalesced into the next tick's patch-batch
            # beside the phase mirrors and the heartbeat
            self.status_sink.enqueue(
                ent["ns"], ent["name"], ent["uid"], 0, changes,
            )
            return
        patch_pod_status(
            self.store, ent["ns"], ent["name"], ent["uid"],
            changes, what="stepstats-mirror",
        )

    def _pod_key(self, pod: Pod) -> str:
        return f"{pod.metadata.namespace}/{pod.metadata.name}"

    def _config_dir(self, namespace: str, job_name: str) -> str:
        return os.path.join(self._config_root, namespace, job_name)

    def _project_config(self, cm) -> None:
        """Project a job ConfigMap to files (≙ the kubelet's configMap volume
        sync that elastic Horovod leans on — proposals/elastic-horovod.md:29
        accepts ~1min lag; here it's immediate). Workers read
        $TPUJOB_CONFIG_DIR/hostfile etc. (ops/elastic.declared_world_size)."""
        job_name = cm.metadata.labels.get(LABEL_JOB_NAME, "")
        if not job_name:
            return
        d = self._config_dir(cm.metadata.namespace, job_name)
        os.makedirs(d, exist_ok=True)
        for fname, content in cm.data.items():
            # unique tmp per writer: start()'s adoption pass and the watch
            # thread can project the same ConfigMap concurrently — a shared
            # tmp name let one writer replace the file out from under the
            # other (FileNotFoundError on the loser's os.replace)
            tmp = os.path.join(d, f".{fname}.{uuid.uuid4().hex[:8]}.tmp")
            with open(tmp, "w") as f:
                f.write(content)
            os.replace(tmp, os.path.join(d, fname))  # atomic swap, no torn reads

    def _kill_if_evicted(self, pod: Pod) -> None:
        """Eviction means KILL, not just a status mark (kubelet semantics):
        `ctl drain` / the NodeMonitor force a pod to Failed while its
        process may still be alive here — left running it would keep the
        gang's collectives healthy and the drain would never converge. The
        reaper still runs but terminal status is write-once (_set_phase),
        so the Evicted marker — the retryable signal — survives the
        SIGKILL's rc=-9."""
        if not pod.is_finished():
            return
        key = self._pod_key(pod)
        with self._lock:
            proc = self._procs.get(key)
            already_terminating = key in self._terminating
        if already_terminating:
            # the grace sequence already ran (re-delivered event / relist
            # replay): _kill_externally_finished would return immediately
            # — don't mint a duplicate evict span for it (same noise rule
            # as the launch path's _procs pre-check); the locked re-check
            # inside still guards the real race
            return
        if proc is not None and proc.poll() is None:
            # the kill/grace sequence below is job-scoped work caused by
            # the eviction write delivering right now: span it so `ctl
            # trace` shows WHERE the eviction landed on the node
            with trace.start_span(
                "executor.evict",
                parent=trace.get_delivery(),
                trace_id=pod.metadata.annotations.get(
                    trace.ANNOTATION_TRACE_ID
                ),
                attrs={"pod": key,
                       "reason": pod.status.reason or pod.status.phase,
                       "grace": self.eviction_grace},
            ):
                self._kill_externally_finished(pod, key, proc)

    def _kill_externally_finished(self, pod: Pod, key: str, proc) -> None:
        with self._lock:
            if key in self._terminating:
                # the grace sequence already ran for this process; a
                # re-delivered event (watch-gap relists replay every
                # live object as MODIFIED) must not SIGTERM it again —
                # workloads may treat a second SIGTERM as abort-now,
                # forfeiting the force-checkpoint the grace granted —
                # nor leak the armed backstop timer by overwriting it
                return
        if self.eviction_grace > 0:
            # SIGTERM-then-SIGKILL (≙ the kubelet's graceful pod
            # termination): a preempted checkpointing trainer uses the
            # grace window to force-save at a gang-uniform step, so the
            # relaunched gang resumes instead of replaying from the
            # last periodic save. The backstop timer makes the grace a
            # bound, not a trust: a wedged process still dies.
            log.info(
                "pod %s externally finished (%s); SIGTERM with %.1fs "
                "grace", key, pod.status.reason or pod.status.phase,
                self.eviction_grace,
            )
            proc.terminate()
            timer = threading.Timer(
                self.eviction_grace,
                lambda: proc.poll() is None and proc.kill(),
            )
            timer.daemon = True
            with self._lock:
                self._terminating[key] = timer
            timer.start()
        else:
            log.info("pod %s externally finished (%s); killing its "
                     "process", key, pod.status.reason or pod.status.phase)
            proc.kill()

    def _forget(self, pod: Pod) -> None:
        """Pod deleted (controller restart path / cleanup policy): kill any
        live process and drop all per-pod state, so a recreated pod with the
        same name launches fresh and long-lived executors don't leak."""
        key = self._pod_key(pod)
        with self._lock:
            proc = self._procs.pop(key, None)
            self.logs.pop(key, None)
            draining = self._terminating.pop(key, None)
            self._stats_files.pop(key, None)
        with self._rv_lock:
            self._status_rv.pop(key, None)
        if proc is not None and proc.poll() is None:
            if draining is not None:
                # eviction already granted this process a termination grace
                # (SIGTERM sent, SIGKILL backstop armed): the deletion must
                # not revoke the force-checkpoint window — the armed timer
                # still bounds the process's lifetime, and _maybe_launch
                # holds the key's next incarnation until the reaper
                # confirms this process exited
                with self._lock:
                    self._draining[key] = proc
                return
            proc.kill()

    def _maybe_launch(self, pod: Pod) -> None:
        if pod.status.phase != PodPhase.PENDING:
            return
        if self.require_binding and not pod.spec.node_name:
            return  # waiting for gang admission; binding event re-triggers
        if self.node_name is not None and pod.spec.node_name != self.node_name:
            return  # bound to another node — its agent claims it
        key = self._pod_key(pod)
        if key in self._procs:
            # racy pre-check (re-checked under the lock in _launch): a
            # duplicate delivery / relist replay of a running pod must not
            # mint a noise span
            return
        # the launch span lives in the job's trace (the pod annotation),
        # parented on the event that triggered it — the scheduler's
        # binding patch on generation 0, the recreation after a gang
        # restart on later ones (the checkpoint-resume relaunch `ctl
        # trace` must attribute)
        with trace.start_span(
            "executor.launch",
            parent=trace.get_delivery(),
            trace_id=pod.metadata.annotations.get(trace.ANNOTATION_TRACE_ID),
            attrs={
                "pod": key,
                "node": pod.spec.node_name or "local",
                "generation": pod.metadata.labels.get(LABEL_GENERATION, ""),
            },
        ):
            self._launch(pod, key)

    def _launch(self, pod: Pod, key: str) -> None:
        with self._lock:
            if key in self._procs:
                return
            predecessor = self._draining.get(key)
            if predecessor is not None:
                if predecessor.poll() is None:
                    # the previous generation's process is still inside its
                    # eviction grace: launching now would collide on the
                    # job's stable coordinator port. The predecessor's
                    # reaper re-invokes _maybe_launch once it exits.
                    return
                self._draining.pop(key, None)
            container = pod.spec.container
            argv = list(container.command) + list(container.args)
            if not argv:
                self._set_phase(pod, PodPhase.FAILED, reason="NoCommand")
                return
            env = dict(os.environ)
            env.update(self.extra_env)
            env.update(container.env)
            if self.loopback_rewrite and ENV_COORDINATOR in env:
                addr = env[ENV_COORDINATOR]
                _, _, port = addr.rpartition(":")
                env[ENV_COORDINATOR] = (
                    f"{self._resolve_coordinator_host(pod, addr)}:{port}"
                )
            # The executor owns the device inventory (≙ kubelet device
            # plugin): for cpu-family pods, pin the emulated chip count to
            # the pod's declared chips_per_host, overriding any inherited
            # XLA_FLAGS (e.g. a test harness's 8-device mesh).
            job_name = pod.metadata.labels.get(LABEL_JOB_NAME, "")
            if job_name:
                env[ENV_CONFIG_DIR] = self._config_dir(
                    pod.metadata.namespace, job_name
                )
            if env.get("TPUJOB_ACCELERATOR", "") == "cpu":
                try:
                    chips = max(1, int(env.get("TPUJOB_CHIPS_PER_HOST", "1") or "1"))
                except ValueError:
                    chips = 1  # malformed env must not kill the watch loop
                env["XLA_FLAGS"] = pin_host_device_count(
                    env.get("XLA_FLAGS", ""), chips
                )
            # stream to files (kubelet log dir) instead of pipes: logs
            # survive the executor process and are readable mid-run by
            # `ctl logs`; stdout and stderr stay separate so callers can
            # parse structured stdout (e.g. the bench JSON line) unmixed.
            # The path is unique per incarnation: a restarted same-name pod
            # must not truncate the file an old reaper is about to read
            # (pod.status.log_path always names the current incarnation)
            os.makedirs(self.logs_dir, exist_ok=True)
            base = os.path.join(
                self.logs_dir,
                f"{pod.metadata.namespace}-{pod.metadata.name}"
                f"-{uuid.uuid4().hex[:8]}",
            )
            log_path = base + ".log"
            # the stepstats contract: the worker flushes its bounded blob
            # here (runtime/stepstats.py) and _stats_loop mirrors it into
            # pod.status.train_stats — path is per-incarnation like the
            # log files, so a restarted pod never inherits stale stats
            stats_path = base + ".stats.json"
            env[ENV_STATS_FILE] = stats_path
            handles = []
            try:
                f_out = open(log_path, "w")
                handles.append(f_out)
                f_err = open(base + ".err", "w")
                handles.append(f_err)
                proc = subprocess.Popen(
                    argv,
                    env=env,
                    cwd=self.workdir,
                    stdout=f_out,
                    stderr=f_err,
                    text=True,
                    preexec_fn=_die_with_parent,
                )
            except OSError as e:
                log.warning("pod %s failed to start: %s", key, e)
                self._set_phase(pod, PodPhase.FAILED, reason=f"StartError: {e}")
                return
            finally:
                # the child holds the fds now (or the spawn failed): either
                # way these handles are done
                for f in handles:
                    f.close()
            self._procs[key] = proc
            self._stats_files[key] = {
                "path": stats_path, "ns": pod.metadata.namespace,
                "name": pod.metadata.name, "uid": pod.metadata.uid,
                "mtime": 0.0,
            }
        stamped = log_path
        if self.log_url_base:
            stamped = f"{self.log_url_base}/{os.path.basename(log_path)}"
        self._set_phase(pod, PodPhase.RUNNING, ip="127.0.0.1", log_path=stamped)
        t = threading.Thread(
            target=self._reap, args=(pod, proc, base), name=f"reap-{key}",
            daemon=True,
        )
        t.start()
        # prune finished reap threads so per-pod state doesn't accumulate
        self._threads = [th for th in self._threads if th.is_alive()]
        self._threads.append(t)

    def _resolve_coordinator_host(self, pod: Pod, addr: str) -> str:
        """The DNS role: ``<job>-worker-0.<subdomain>`` only resolves inside
        a cluster's headless service. Single-node executors rewrite to
        loopback (ports disambiguate jobs). A node agent resolves through
        the store instead: coordinator pod → its bound node → that node's
        advertised address (binding precedes launch under gang admission,
        so the lookup is race-free)."""
        if self.node_name is None:
            return "127.0.0.1"
        host, _, _ = addr.rpartition(":")
        coord_pod_name = host.split(".", 1)[0]
        coord = self.store.try_get(
            "Pod", pod.metadata.namespace, coord_pod_name
        )
        if coord is not None and coord.spec.node_name:
            node = self.store.try_get("Node", NODE_NAMESPACE, coord.spec.node_name)
            if node is not None and node.status.address:
                return node.status.address
        return "127.0.0.1"

    def _reap(self, pod: Pod, proc: subprocess.Popen, base: str) -> None:
        proc.wait()
        key = self._pod_key(pod)
        with self._lock:
            timer = self._terminating.pop(key, None)
            was_draining = self._draining.get(key) is proc
            if was_draining:
                self._draining.pop(key)
        if timer is not None:
            timer.cancel()  # exited inside its grace: no backstop needed
        out = err = ""
        try:
            with open(base + ".log") as f:
                out = f.read()
            with open(base + ".err") as f:
                err = f.read()
        except OSError:
            pass  # log files are best-effort; phase/exit code still land
        self.logs[self._pod_key(pod)] = (out, err)
        try:
            if proc.returncode == 0:
                self._set_phase(pod, PodPhase.SUCCEEDED, exit_code=0)
            else:
                tail = (err or out or "").strip()[-1024:]  # ≙ truncateMessage(:1524)
                self._set_phase(
                    pod, PodPhase.FAILED, reason=f"ExitCode{proc.returncode}",
                    message=tail, exit_code=proc.returncode,
                )
        except Exception:
            # store gone mid-teardown (closed sqlite, hard outage past the
            # client's retry window): the mirror is lost but the thread
            # must not die noisily — the monitor's eviction is the backstop
            log.warning("pod %s exit mirror failed", self._pod_key(pod),
                        exc_info=True)
        log.info(
            "pod %s exited rc=%d", self._pod_key(pod), proc.returncode
        )
        if was_draining:
            # the next generation may already be bound and waiting on this
            # exit (its binding event fired while we were draining, and
            # _maybe_launch deferred it): level-trigger the launch now
            try:
                cur = self.store.try_get(
                    "Pod", pod.metadata.namespace, pod.metadata.name
                )
                if cur is not None:
                    self._maybe_launch(cur)
            except Exception:
                log.warning("post-drain relaunch check for %s failed", key,
                            exc_info=True)

    def _set_phase(
        self,
        pod: Pod,
        phase: str,
        *,
        reason: str = "",
        ip: str = "",
        message: str = "",
        exit_code: Optional[int] = None,
        log_path: str = "",
    ) -> None:
        # status mirror over the PATCH verb (status subresource — the only
        # write scope the NODE token tier needs): one request in the
        # common case, with the same guards the old GET+PUT loop enforced —
        # incarnation (uid) and write-once terminal — carried by
        # patch_pod_status's rv precondition + conflict re-check. The
        # snapshot anchoring the rv is the watch event that triggered the
        # launch (binding is its freshest write) or our own last committed
        # status, so the precondition almost never misses.
        changes = {
            "phase": phase,
            "ready": phase == PodPhase.RUNNING,
            "reason": reason,
        }
        if message:
            changes["message"] = message
        if ip:
            changes["pod_ip"] = ip
        if exit_code is not None:
            changes["exit_code"] = exit_code
        if log_path:
            changes["log_path"] = log_path
        key = self._pod_key(pod)
        if self.status_sink is not None:
            # agent mode: the sink coalesces this with every other dirty
            # mirror and the Node heartbeat into ONE patch-batch request
            # per tick (O(pods) requests → O(1)); ordering per pod is
            # preserved, commit is asynchronous but prompt (the sink wakes
            # its flusher). The sink owns the rv anchoring there
            # (StatusBatcher._committed) — _status_rv is the DIRECT path's
            # anchor only.
            self.status_sink.enqueue(
                pod.metadata.namespace, pod.metadata.name, pod.metadata.uid,
                pod.metadata.resource_version or 0, changes,
            )
            return
        with self._rv_lock:
            known = self._status_rv.get(key)
        expected_rv = pod.metadata.resource_version or 0
        if known is not None and known[0] == pod.metadata.uid:
            expected_rv = max(expected_rv, known[1])
        from mpi_operator_tpu.machinery.objects import patch_pod_status

        committed = patch_pod_status(
            self.store, pod.metadata.namespace, pod.metadata.name,
            pod.metadata.uid, changes, expected_rv=expected_rv,
            what="set-phase",
        )
        if committed is not None:
            with self._rv_lock:
                self._status_rv[key] = (
                    committed.metadata.uid,
                    committed.metadata.resource_version,
                )
