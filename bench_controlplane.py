"""Control-plane benchmark: reconcile storm against the sqlite-backed HTTP
store, with and without the informer cache (machinery/cache.py).

The metric the informer/lister subsystem exists to move: before it, every
reconcile issued full ``store.list``/``get`` round-trips — over HTTP in the
distributed deployment — so store read load scaled as
O(jobs × pods × resyncs). With listers, steady-state controller reads come
from the watch-fed cache and the store sees only writes plus one long-poll.

Shape: N synthetic TPUJobs × M workers each (default 200 × 8 — the ISSUE 1
acceptance point) are created through a real HttpStoreClient against a real
StoreServer backed by SqliteStore. The controller converges them (service,
configmap, podgroup, workers, status), the gang scheduler binds every gang,
and then a steady-state storm re-reconciles every job for R rounds while
measuring per-sync latency and the server's read counters. Run it via::

  python bench_controlplane.py                      # both modes + compare

Knobs: BENCH_CP_JOBS, BENCH_CP_PODS, BENCH_CP_ROUNDS, BENCH_CP_MODES
("store", "informer", "write", "replica", "hist", "traceoverhead",
"scale", "serve", "fanout", "slo", or a comma list). No jax required —
this is the pure-python control plane. The **slo** mode (ISSUE 13) is
the alerting plane's acceptance run: a seeded store-latency fault must
fire the matching burn-rate alert within its documented detection bound,
clear after heal, dump a flight-recorder bundle `ctl trace
--last-incident` renders rc=0, and the monitor's scrape tax must stay
≤2% of reconcile p50 — detection run TWICE on one seed. The **scale** mode (ISSUE 10) drives a
hollow-node fleet (BENCH_CP_SCALE_NODES × simulated nodes,
BENCH_CP_SCALE_JOBS jobs) against the sharded+fair-queued stack and reads
p50/p99 out of the PR 9 histograms with p99 SLOs as the tripwire;
**fanout** proves watch fan-out encode cost is O(events), not
O(watchers×events). The **serve** mode (ISSUE 11) runs the serving
workload class on a hollow fleet: a diurnal+spike offered-load curve
against an autoscaled TPUServe sharing the cluster with a batch backlog —
asserting the autoscaler tracks the curve (≥4× spike, scale-to-zero), a
mid-run rolling update opens zero unready windows, serve-readiness p99
meets its SLO, and the batch backlog still completes via
preempt-then-free-restart (visible in `ctl trace`).
The **hist** mode proves the exported latency histograms (ISSUE 9) agree
with the direct timers within bucket resolution; **traceoverhead** bounds
the tracing tax (reconcile p50 traced vs untraced, acceptance ≤5%).

The **write mode** (BENCH_CP_MODES=write) measures the write-path twin of
the informer work: status updates as server-side merge-patch (1 request)
vs the GET+PUT optimistic loop (2+), simulated agent ticks (Node heartbeat
+ dirty pod mirrors) as one patch-batch vs per-object round-trips —
O(pods) → O(1) — plus the idle-writes-are-zero check, at 200 jobs × 8
pods with BENCH_CP_AGENTS (default 16) simulated agents churning.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mpi_operator_tpu.api.types import (  # noqa: E402
    Container,
    ObjectMeta,
    PodTemplate,
    ReplicaSpec,
    RunPolicy,
    SliceSpec,
    TPUJob,
    TPUJobSpec,
)
from mpi_operator_tpu.controller.controller import (  # noqa: E402
    ControllerOptions,
    TPUJobController,
)
from mpi_operator_tpu.machinery.cache import InformerCache  # noqa: E402
from mpi_operator_tpu.machinery.events import EventRecorder  # noqa: E402
from mpi_operator_tpu.machinery.http_store import (  # noqa: E402
    HttpStoreClient,
    StoreServer,
)
from mpi_operator_tpu.machinery.sqlite_store import SqliteStore  # noqa: E402
from mpi_operator_tpu.scheduler.gang import GangScheduler  # noqa: E402


def _make_job(i: int, pods: int, clean: str = "None") -> TPUJob:
    return TPUJob(
        metadata=ObjectMeta(name=f"storm-{i:04d}", namespace="bench"),
        spec=TPUJobSpec(
            slots_per_worker=1,
            run_policy=RunPolicy(clean_pod_policy=clean),
            worker=ReplicaSpec(
                replicas=pods,
                restart_policy="Never",
                template=PodTemplate(
                    container=Container(image="bench/noop", command=["true"])
                ),
            ),
            slice=SliceSpec(accelerator="cpu", chips_per_host=1),
        ),
    )


def _reads(stats: dict) -> int:
    """Store-side read requests: object gets + lists. Watch long-polls are
    reported separately — they are the informer's O(1) replacement, not the
    per-reconcile load this benchmark measures."""
    return stats.get("get", 0) + stats.get("list", 0)


def _percentile(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1, max(0, int(round(p * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


def _slo_ms(name: str, scale: float = 1.0) -> float:
    """A p99 tripwire threshold in ms from THE SLO config file
    (controller/slo_defaults.json or $TPUJOB_SLO_CONFIG) — the same file
    the runtime burn-rate monitor evaluates, so bench and monitor can
    never disagree on an objective. The entry's env knob (e.g.
    BENCH_CP_SLO_RECONCILE_P99_MS) still overrides, ABSOLUTE (it beats
    ``scale`` — a deployment that exported a bound meant exactly it)."""
    from mpi_operator_tpu.controller.slo_monitor import load_slo_config

    return load_slo_config().threshold_ms(name, scale=scale)


def run_mode(mode: str, jobs: int, pods: int, rounds: int) -> dict:
    """One full converge + storm in ``mode`` ('store' = direct reads,
    'informer' = lister reads) against a fresh sqlite-backed HTTP store."""
    tmp = tempfile.mkdtemp(prefix=f"bench-cp-{mode}-")
    backing = SqliteStore(os.path.join(tmp, "store.db"))
    server = StoreServer(backing, "127.0.0.1", 0).start()
    client = HttpStoreClient(server.url, timeout=30.0, watch_poll_timeout=5.0)
    cache = None
    try:
        if mode == "informer":
            cache = InformerCache(client).start()
            if not cache.wait_for_sync(30.0):
                raise RuntimeError("informer cache never synced")
        recorder = EventRecorder(client)
        controller = TPUJobController(
            client, recorder, ControllerOptions(threadiness=0), cache=cache
        )
        scheduler = GangScheduler(client, recorder, cache=cache)

        keys = []
        for i in range(jobs):
            job = client.create(_make_job(i, pods))
            keys.append(job.metadata.key())

        # converge: drive sync_handler + scheduler.sync directly (no worker
        # threads — deterministic measurement) until a full pass of syncs
        # succeeds twice; informer mode needs the watch to carry each pass's
        # writes back into the cache before the next pass settles
        t_conv = time.perf_counter()
        clean_passes = 0
        for _ in range(30):
            ok = all([controller.sync_handler(k) for k in keys])
            scheduler.sync()
            clean_passes = clean_passes + 1 if ok else 0
            if clean_passes >= 2:
                break
            if cache is not None:
                time.sleep(0.3)  # let the watch land this pass's writes
        converge_s = time.perf_counter() - t_conv
        if cache is not None:
            time.sleep(0.5)  # quiesce: cache observes the final writes

        # steady-state storm: every job re-reconciled, rounds times over
        stats0 = server.stats()
        lat = []
        t0 = time.perf_counter()
        for _ in range(rounds):
            for k in keys:
                t = time.perf_counter()
                controller.sync_handler(k)
                lat.append(time.perf_counter() - t)
            scheduler.sync()
        elapsed = time.perf_counter() - t0
        stats1 = server.stats()

        lat.sort()
        reads = _reads(stats1) - _reads(stats0)
        writes = _writes(stats1) - _writes(stats0)
        return {
            "metric": "controlplane_reconcile",
            "mode": mode,
            "jobs": jobs,
            "pods_per_job": pods,
            "rounds": rounds,
            "syncs": len(lat),
            "sync_p50_ms": round(_percentile(lat, 0.50) * 1e3, 3),
            "sync_p99_ms": round(_percentile(lat, 0.99) * 1e3, 3),
            "store_read_qps": round(reads / elapsed, 1),
            "store_reads_per_sync": round(reads / max(1, len(lat)), 2),
            "store_writes": writes,
            "watch_polls": stats1.get("watch", 0) - stats0.get("watch", 0),
            "storm_elapsed_s": round(elapsed, 2),
            "converge_s": round(converge_s, 2),
        }
    finally:
        if cache is not None:
            cache.stop()
        client.close()
        server.stop()
        backing.close()


def _writes(stats: dict) -> int:
    """Store-side write requests (patch_batch counts as ONE request — that
    collapse is the point; its per-item patches are server-internal)."""
    return sum(stats.get(w, 0) for w in ("create", "update", "delete",
                                         "patch", "patch_batch"))


def run_write_mode(jobs: int, pods: int, agents: int) -> dict:
    """The write-path benchmark: converge the cluster once (informer reads,
    patch writes), then measure

    - **status update**: old GET+PUT optimistic loop vs status-subresource
      PATCH, p50/p99 and store requests per update;
    - **agent tick**: old per-object round-trips (Node GET+PUT + per-dirty-
      pod GET+PUT) vs ONE patch-batch, requests per tick;
    - **agent churn**: ``agents`` threads ticking concurrently with a
      job's worth of dirty mirrors each, both write paths, wall + QPS +
      server-bounced conflicts;
    - **idle**: after everything drains, a 5s window must show ZERO writes
      (the elision guarantee, mirroring the zero-read one).
    """
    import threading

    from mpi_operator_tpu.machinery.objects import NODE_NAMESPACE, Node

    tmp = tempfile.mkdtemp(prefix="bench-cp-write-")
    backing = SqliteStore(os.path.join(tmp, "store.db"))
    server = StoreServer(backing, "127.0.0.1", 0).start()
    client = HttpStoreClient(server.url, timeout=30.0, watch_poll_timeout=5.0)
    cache = InformerCache(client).start()
    try:
        if not cache.wait_for_sync(30.0):
            raise RuntimeError("informer cache never synced")
        recorder = EventRecorder(client)
        controller = TPUJobController(
            client, recorder, ControllerOptions(threadiness=0), cache=cache
        )
        scheduler = GangScheduler(client, recorder, cache=cache)
        keys = []
        for i in range(jobs):
            job = client.create(_make_job(i, pods))
            keys.append(job.metadata.key())
        stats0 = server.stats()
        clean = 0
        for _ in range(30):
            ok = all([controller.sync_handler(k) for k in keys])
            scheduler.sync()
            clean = clean + 1 if ok else 0
            if clean >= 2:
                break
            time.sleep(0.3)
        time.sleep(0.5)
        stats_conv = server.stats()
        converge_writes = _writes(stats_conv) - _writes(stats0)

        all_pods = client.list("Pod", "bench")
        # ---- status update: GET+PUT loop vs one PATCH --------------------
        n_updates = min(400, len(all_pods))
        s0 = server.stats()
        put_lat = []
        for i, p in enumerate(all_pods[:n_updates]):
            t = time.perf_counter()
            cur = client.get("Pod", p.metadata.namespace, p.metadata.name)
            cur.status.message = f"put {i}"
            client.update(cur)
            put_lat.append(time.perf_counter() - t)
        s1 = server.stats()
        patch_lat = []
        for i, p in enumerate(all_pods[:n_updates]):
            t = time.perf_counter()
            client.patch(
                "Pod", p.metadata.namespace, p.metadata.name,
                {"status": {"message": f"patch {i}"}}, subresource="status",
            )
            patch_lat.append(time.perf_counter() - t)
        s2 = server.stats()
        put_req = (_reads(s1) - _reads(s0)) + (_writes(s1) - _writes(s0))
        patch_req = (_reads(s2) - _reads(s1)) + (_writes(s2) - _writes(s1))
        put_lat.sort()
        patch_lat.sort()

        # ---- agent ticks: per-object round-trips vs one patch-batch ------
        for a in range(agents):
            node = Node()
            node.metadata.namespace = NODE_NAMESPACE
            node.metadata.name = f"bench-agent-{a:02d}"
            node.status.ready = True
            node.status.last_heartbeat = time.time()
            client.try_get("Node", NODE_NAMESPACE, node.metadata.name) \
                or client.create(node)
        shard = [all_pods[a::agents] for a in range(agents)]

        def old_tick(cl, a: int, dirty: list) -> None:
            cur = cl.get("Node", NODE_NAMESPACE, f"bench-agent-{a:02d}")
            cur.status.last_heartbeat = time.time()
            cl.update(cur)
            for p in dirty:
                cp = cl.get("Pod", p.metadata.namespace, p.metadata.name)
                cp.status.message = "old-tick"
                cl.update(cp)

        def new_tick(cl, a: int, dirty: list) -> None:
            items = [{
                "kind": "Node", "namespace": NODE_NAMESPACE,
                "name": f"bench-agent-{a:02d}", "subresource": "status",
                "patch": {"status": {"last_heartbeat": time.time()}},
            }]
            items += [{
                "kind": "Pod", "namespace": p.metadata.namespace,
                "name": p.metadata.name, "subresource": "status",
                "patch": {"status": {"message": "new-tick"}},
            } for p in dirty]
            cl.patch_batch(items)

        dirty_per_tick = pods  # a job's worth of mirrors lands each tick
        s0 = server.stats()
        old_tick(client, 0, shard[0][:dirty_per_tick])
        s1 = server.stats()
        new_tick(client, 0, shard[0][:dirty_per_tick])
        s2 = server.stats()
        tick_req_old = (_reads(s1) - _reads(s0)) + (_writes(s1) - _writes(s0))
        tick_req_new = (_reads(s2) - _reads(s1)) + (_writes(s2) - _writes(s1))

        churn = {}
        ticks = 20
        for label, tick in (("old", old_tick), ("new", new_tick)):
            clients = [
                HttpStoreClient(server.url, timeout=30.0,
                                watch_poll_timeout=5.0)
                for _ in range(agents)
            ]
            s0 = server.stats()
            t0 = time.perf_counter()

            def run_agent(a, cl):
                for _ in range(ticks):
                    tick(cl, a, shard[a][:dirty_per_tick])

            threads = [
                threading.Thread(target=run_agent, args=(a, cl))
                for a, cl in enumerate(clients)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            s1 = server.stats()
            req = (_reads(s1) - _reads(s0)) + (_writes(s1) - _writes(s0))
            churn[label] = {
                "elapsed_s": round(elapsed, 2),
                "requests": req,
                "requests_per_tick": round(req / (agents * ticks), 2),
                "store_qps": round(req / elapsed, 1),
                "conflicts": s1.get("conflict", 0) - s0.get("conflict", 0),
            }
            for cl in clients:
                cl.close()

        # ---- idle: the elision guarantee ---------------------------------
        for _ in range(2):  # settle reconciles of everything above
            for k in keys:
                controller.sync_handler(k)
            scheduler.sync()
            time.sleep(0.3)
        s0 = server.stats()
        time.sleep(5.0)
        for k in keys:
            controller.sync_handler(k)  # a full reconcile pass, all no-ops
        scheduler.sync()
        s1 = server.stats()
        idle_writes = _writes(s1) - _writes(s0)

        return {
            "metric": "controlplane_write_path",
            "jobs": jobs,
            "pods_per_job": pods,
            "agents": agents,
            "converge_writes_per_job": round(converge_writes / jobs, 2),
            "status_put_p50_ms": round(_percentile(put_lat, 0.50) * 1e3, 3),
            "status_put_p99_ms": round(_percentile(put_lat, 0.99) * 1e3, 3),
            "status_put_requests_per_update": round(put_req / n_updates, 2),
            "status_patch_p50_ms": round(_percentile(patch_lat, 0.50) * 1e3, 3),
            "status_patch_p99_ms": round(_percentile(patch_lat, 0.99) * 1e3, 3),
            "status_patch_requests_per_update": round(
                patch_req / n_updates, 2),
            "agent_tick_requests_old": tick_req_old,
            "agent_tick_requests_new": tick_req_new,
            "churn_ticks_per_agent": ticks,
            "churn_dirty_pods_per_tick": dirty_per_tick,
            "churn_old": churn["old"],
            "churn_new": churn["new"],
            "idle_writes": idle_writes,
        }
    finally:
        cache.stop()
        client.close()
        server.stop()
        backing.close()


def run_hist_mode(writes: int) -> dict:
    """The histogram read-back check (BENCH_CP_MODES=hist, run it
    standalone so the exported counts are this workload's): drive the
    write path (status-subresource PATCHes — the PERF round 7 workload),
    then read p50/p99 BACK OUT of the /metrics-exported
    ``tpu_operator_store_request_latency_seconds`` histogram via the
    strict exposition parser, and check they agree with the direct
    perf_counter timers within one bucket step. This is the acceptance
    proof that the numbers PERF.md claims are the numbers a Prometheus
    scraping /metrics would compute."""
    from mpi_operator_tpu.machinery.objects import Pod
    from mpi_operator_tpu.opshell import metrics

    tmp = tempfile.mkdtemp(prefix="bench-cp-hist-")
    backing = SqliteStore(os.path.join(tmp, "store.db"))
    server = StoreServer(backing, "127.0.0.1", 0).start()
    client = HttpStoreClient(server.url, timeout=30.0, watch_poll_timeout=5.0)
    try:
        for i in range(writes):
            client.create(Pod(metadata=ObjectMeta(
                name=f"h-{i:05d}", namespace="bench")))
        before = metrics.store_request_latency.count(
            verb="patch", backend="SqliteStore")
        lat = []
        for i in range(writes):
            t = time.perf_counter()
            client.patch(
                "Pod", "bench", f"h-{i:05d}",
                {"status": {"message": f"hist {i}"}}, subresource="status",
            )
            lat.append(time.perf_counter() - t)
        lat.sort()
        # (a) the agreement proof: the SAME client-observed latencies PERF
        # measures, pushed through a histogram with the standard buckets,
        # rendered to exposition text, strict-parsed back, and quantiled —
        # direct timer vs histogram read-back must agree within one bucket
        # step (the histogram's resolution limit)
        client_hist = metrics._Histogram(
            "bench_client_patch_latency_seconds",
            "client-observed status-patch latency (the PERF write-path "
            "measurement point)",
        )
        for v in lat:
            client_hist.observe(v)
        client_text = client_hist.render() + "\n"
        # (b) the deployment view: what a Prometheus scraping /metrics
        # computes from the server-side verb×backend histogram (handler
        # time — the client−server delta is the loopback HTTP cost)
        text = metrics.REGISTRY.render()
        metrics.parse_exposition(text)  # the endpoint must stay machine-valid
        out = {
            "metric": "controlplane_histogram_readback",
            "writes": writes,
            "hist_observations": metrics.store_request_latency.count(
                verb="patch", backend="SqliteStore") - before,
        }
        buckets = (0.0, *client_hist.buckets, float("inf"))
        for q, name in ((0.50, "p50"), (0.99, "p99")):
            direct = _percentile(lat, q)
            hist = metrics.exposition_quantile(
                client_text, "bench_client_patch_latency_seconds", q)
            server_hist = metrics.exposition_quantile(
                text, "tpu_operator_store_request_latency_seconds", q,
                verb="patch", backend="SqliteStore",
            )
            i = max(1, min(len(buckets) - 2,
                           next(k for k, b in enumerate(buckets)
                                if direct <= b)))
            lo, hi = buckets[i - 1], buckets[min(len(buckets) - 1, i + 1)]
            out[f"direct_{name}_ms"] = round(direct * 1e3, 3)
            out[f"hist_{name}_ms"] = round(hist * 1e3, 3)
            out[f"server_hist_{name}_ms"] = round(server_hist * 1e3, 3)
            out[f"{name}_agrees_within_bucket"] = bool(lo <= hist <= hi)
        return out
    finally:
        client.close()
        server.stop()
        backing.close()


def run_trace_overhead(jobs: int, pods: int, rounds: int) -> dict:
    """The tracing-tax bound (BENCH_CP_MODES=traceoverhead): INTERLEAVED
    off/on/off/on informer reconcile storms (spans exported to JSONL like
    a real deployment), best-of-two per mode so run-to-run drift (sqlite
    file aging, allocator warm-up — easily ±15% between back-to-back
    storms) cancels out of the comparison; reported as a p50 regression
    percentage. Acceptance (ISSUE 9): ≤5%."""
    import shutil

    from mpi_operator_tpu.machinery import trace as tr

    d = tempfile.mkdtemp(prefix="bench-cp-traces-")
    results = {"off": [], "on": []}
    try:
        for _ in range(2):
            tr.TRACER.disable()
            results["off"].append(run_mode("informer", jobs, pods, rounds))
            tr.configure("bench", dir=d)
            results["on"].append(run_mode("informer", jobs, pods, rounds))
    finally:
        tr.TRACER.disable()
    spans = len(tr.load_spans(d))
    shutil.rmtree(d, ignore_errors=True)
    off = min(results["off"], key=lambda r: r["sync_p50_ms"])
    on = min(results["on"], key=lambda r: r["sync_p50_ms"])
    p50_off, p50_on = off["sync_p50_ms"], on["sync_p50_ms"]
    return {
        "metric": "controlplane_trace_overhead",
        "jobs": jobs,
        "pods_per_job": pods,
        "rounds": rounds,
        "runs_per_mode": 2,
        "sync_p50_ms_traced_off": p50_off,
        "sync_p50_ms_traced_on": p50_on,
        "sync_p99_ms_traced_off": off["sync_p99_ms"],
        "sync_p99_ms_traced_on": on["sync_p99_ms"],
        "p50_regression_pct": round(
            (p50_on - p50_off) / max(1e-9, p50_off) * 100.0, 1),
        "spans_exported": spans,
    }


def run_replica_mode(writes: int) -> dict:
    """The HA cost as a number (BENCH_CP_MODES=replica): write p50/p99
    at replication factor 1 (single node, no shipping) vs 3 (leased
    leader + synchronous majority log-shipping), plus the
    failover-to-first-successful-write time — SIGKILL the leader under
    auto-failover and measure until a write acks on the new one."""
    import shutil

    from mpi_operator_tpu.api.types import ObjectMeta as _Meta
    from mpi_operator_tpu.machinery.objects import Pod as _Pod
    from mpi_operator_tpu.machinery.replicated_store import ReplicaSet

    def _pod(name):
        return _Pod(metadata=_Meta(name=name, namespace="bench"))

    out: dict = {"metric": "controlplane_replica", "writes": writes}
    for rf in (1, 3):
        tmp = tempfile.mkdtemp(prefix=f"bench-replica-rf{rf}-")
        rs = ReplicaSet(rf, dir=tmp)
        try:
            assert rs.elect("n0")
            client = rs.client()
            lat = []
            for i in range(writes):
                t = time.perf_counter()
                client.create(_pod(f"w-{i:05d}"))
                lat.append(time.perf_counter() - t)
            for i in range(writes):
                t = time.perf_counter()
                client.patch(
                    "Pod", "bench", f"w-{i:05d}",
                    {"status": {"message": "bench"}}, subresource="status",
                )
                lat.append(time.perf_counter() - t)
            lat.sort()
            out[f"rf{rf}_write_p50_ms"] = round(
                _percentile(lat, 0.50) * 1e3, 3)
            out[f"rf{rf}_write_p99_ms"] = round(
                _percentile(lat, 0.99) * 1e3, 3)
        finally:
            rs.stop()
            shutil.rmtree(tmp, ignore_errors=True)
    out["rf3_over_rf1_p50"] = round(
        out["rf3_write_p50_ms"] / max(1e-9, out["rf1_write_p50_ms"]), 2)

    # failover: kill the leader mid-traffic, clock until the first write
    # acks on the new leader (median of 3 trials)
    trials = []
    for trial in range(3):
        tmp = tempfile.mkdtemp(prefix="bench-replica-failover-")
        rs = ReplicaSet(3, dir=tmp, lease_duration=0.5, retry_period=0.05,
                        seed=trial)
        try:
            assert rs.elect("n0")
            rs.start()
            client = rs.client()
            client._attempts = 64
            client.create(_pod("pre-failover"))
            rs.crash("n0")
            t0 = time.perf_counter()
            client.create(_pod(f"post-failover-{trial}"))
            trials.append(time.perf_counter() - t0)
        finally:
            rs.stop()
            shutil.rmtree(tmp, ignore_errors=True)
    out["failover_first_write_ms"] = round(
        sorted(trials)[len(trials) // 2] * 1e3, 1)
    out["failover_trials_ms"] = [round(t * 1e3, 1) for t in trials]
    out["lease_duration_s"] = 0.5
    return out


def _hist_quantile_delta(hist, q, before, after, **labels):
    """Quantile of a histogram's observations BETWEEN two snapshots
    (cumulative (le,count) pairs from _Histogram.snapshot) — isolates this
    bench run from whatever the process observed earlier."""
    from mpi_operator_tpu.opshell.metrics import histogram_quantile

    b = dict(before)
    delta = [(le, c - b.get(le, 0)) for le, c in after]
    return histogram_quantile(q, delta)


def run_scale_mode(nodes: int, jobs: int, pods: int) -> dict:
    """The 10k-job scale run (BENCH_CP_MODES=scale), in the DEPLOYED
    three-process shape: a sqlite-backed `tpu-store` server process
    (preencoded watch fan-out + APF fair queuing on), a hollow-fleet
    process simulating ``nodes`` agents, and THIS process as the leader —
    informer cache, sharded-workqueue controller, gang scheduler.
    (A single shared process understates the result badly: at 1k nodes
    the three planes' GIL contention dominates every latency.) ``jobs``
    TPUJobs × ``pods`` workers are submitted with wave backpressure and
    driven to Succeeded; reconcile/bind/watch-lag p50/p99 come OUT OF
    THE PR 9 HISTOGRAMS (the numbers /metrics would export), and the
    p99 SLOs are the tripwire this bench exists to arm."""
    import math
    import socket
    import subprocess
    import threading

    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.opshell import metrics

    run_s = float(os.environ.get("BENCH_CP_SCALE_RUN_S", "0.2"))
    wave = int(os.environ.get("BENCH_CP_SCALE_WAVE", "500"))
    threadiness = int(os.environ.get("BENCH_CP_SCALE_WORKERS", "8"))
    # p99 SLO tripwires from the ONE config file the runtime monitor
    # evaluates (controller/slo_defaults.json; calibrated on this
    # sandbox's round-10 run — 570 / 225 / 4404 ms at 1k nodes / 10k
    # jobs — with ~2× headroom). A regression that blows these is a
    # scalability bug, not noise. Env overrides preserved per entry.
    slo_reconcile = _slo_ms("reconcile-latency")
    slo_bind = _slo_ms("scheduler-bind")
    slo_lag = _slo_ms("watch-lag")
    chips = max(2, math.ceil(jobs * pods / max(1, nodes)) + 2)

    tmp = tempfile.mkdtemp(prefix="bench-cp-scale-")
    with socket.socket() as s:  # free port for the store process
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ, PYTHONPATH=os.path.dirname(
        os.path.abspath(__file__)))
    store_proc = subprocess.Popen(
        [sys.executable, "-m", "mpi_operator_tpu.machinery.http_store",
         "--store", f"sqlite:{os.path.join(tmp, 'store.db')}",
         "--listen", f"127.0.0.1:{port}", "--log-capacity", "65536",
         "--fair-queue", "inflight=32,queue=512,max_wait=60"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    fleet_proc = None
    client = HttpStoreClient(url, timeout=60.0, watch_poll_timeout=5.0,
                             conn_refused_retries=20)
    cache = None
    controller = None
    stop = threading.Event()
    snaps = {
        "reconcile": metrics.reconcile_latency.snapshot(),
        "bind": metrics.scheduler_bind_latency.snapshot(),
        "lag": metrics.watch_delivery_lag.snapshot(),
    }
    try:
        deadline = time.time() + 30
        while time.time() < deadline:  # store process up?
            try:
                client.list("Node")
                break
            except Exception:
                time.sleep(0.2)
        fleet_proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.executor.hollow",
             "--store", url, "--nodes", str(nodes),
             "--chips", str(chips), "--run-s", str(run_s),
             "--heartbeat", "15", "--seed", "10"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        cache = InformerCache(client).start()
        if not cache.wait_for_sync(30.0):
            raise RuntimeError("informer cache never synced")
        recorder = EventRecorder(client)
        controller = TPUJobController(
            client, recorder,
            ControllerOptions(threadiness=threadiness,
                              queue_shards=threadiness),
            cache=cache,
        )
        scheduler = GangScheduler(client, recorder, cache=cache)
        # O(1)-per-event progress probe off the informer stream (listing
        # 10k cached jobs per poll would make the BENCH the noisy
        # tenant); Succeeded is terminal write-once, so a name set is
        # exact
        done_names = set()

        def note_done(etype, obj):
            if obj.kind == "TPUJob" and cond.is_succeeded(obj.status):
                done_names.add(obj.metadata.name)

        cache.add_event_handler(note_done)
        # fleet registration visible before the first gangs admit
        deadline = time.time() + 60
        while time.time() < deadline:
            if len(cache.list("Node")) >= nodes:
                break
            time.sleep(0.2)
        controller.run()

        def sched_loop():
            while not stop.is_set():
                try:
                    scheduler.sync()
                except Exception:
                    pass  # transient conflicts; next pass heals
                stop.wait(0.2)

        st = threading.Thread(target=sched_loop, daemon=True)
        st.start()

        t0 = time.perf_counter()
        submitted = 0
        done = 0
        deadline = time.time() + float(os.environ.get(
            "BENCH_CP_SCALE_DEADLINE_S", max(600.0, jobs * 0.25)))
        while time.time() < deadline:
            done = len(done_names)
            while submitted < jobs and submitted - done < wave:
                # CleanPodPolicy=All (the batch-workload default): a
                # finished job's pods/podgroup are reaped, so the
                # scheduler's per-pass working set stays O(in-flight),
                # not O(all jobs ever) — at 10k jobs the difference
                # between a ~1.5k-object and a ~30k-object deepcopy per
                # 0.2s pass in the leader process
                client.create(_make_job(submitted, pods, clean="All"))
                submitted += 1
            if done >= jobs:
                break
            time.sleep(0.5)
        elapsed = time.perf_counter() - t0
        # authoritative final count (one full list, off the clock)
        done = sum(1 for j in cache.list("TPUJob", "bench")
                   if cond.is_succeeded(j.status))
        out = {
            "metric": "controlplane_scale",
            "processes": "store / hollow-fleet / operator (deployed shape)",
            "nodes": nodes,
            "jobs": jobs,
            "pods_per_job": pods,
            "hollow_run_s": run_s,
            "jobs_succeeded": done,
            "elapsed_s": round(elapsed, 1),
            "jobs_per_s": round(done / max(1e-9, elapsed), 1),
            "queue_shards": threadiness,
        }
        for q, tag in ((0.50, "p50"), (0.99, "p99")):
            out[f"reconcile_{tag}_ms"] = round(_hist_quantile_delta(
                metrics.reconcile_latency, q, snaps["reconcile"],
                metrics.reconcile_latency.snapshot()) * 1e3, 2)
            out[f"bind_{tag}_ms"] = round(_hist_quantile_delta(
                metrics.scheduler_bind_latency, q, snaps["bind"],
                metrics.scheduler_bind_latency.snapshot()) * 1e3, 2)
            out[f"watch_lag_{tag}_ms"] = round(_hist_quantile_delta(
                metrics.watch_delivery_lag, q, snaps["lag"],
                metrics.watch_delivery_lag.snapshot()) * 1e3, 2)
        out["slo"] = {
            "reconcile_p99_ms": slo_reconcile,
            "bind_p99_ms": slo_bind,
            "watch_lag_p99_ms": slo_lag,
        }
        out["slo_ok"] = bool(
            done >= jobs
            and out["reconcile_p99_ms"] <= slo_reconcile
            and out["bind_p99_ms"] <= slo_bind
            and out["watch_lag_p99_ms"] <= slo_lag
        )
        return out
    finally:
        stop.set()
        if controller is not None:
            controller.stop()
        if cache is not None:
            cache.stop()
        client.close()
        for proc in (fleet_proc, store_proc):
            if proc is not None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()


def run_torture_mode(nodes: int, jobs: int, pods: int, seed: int) -> dict:
    """The fleet×chaos torture run (BENCH_CP_MODES=torture, ISSUE 12):
    the FULLY deployed shape — three wire-replicated `tpu-store` replica
    processes (peer RPCs through chaos proxies), a real `tpu-operator`
    process (controller + gang scheduler + node monitor over the
    multi-endpoint client), and a hollow fleet process (≥100 nodes /
    ≥500 jobs) — while a seeded chaos script partitions the leader from
    a follower and then SIGKILLs the leader mid-run. The bar: NO acked
    write lost at its exact rv, every job Succeeded post-failover, the
    scale-mode p99 SLO tripwires green (read from the operator's real
    /metrics exposition), and ONE connected trace spanning a pre-kill
    write → its replication ship → the winning election → a
    post-failover reconcile (`ctl trace --last-incident` renders it
    rc=0). The caller runs this TWICE on one seed (determinism)."""
    import math
    import shutil
    import signal as _signal
    import subprocess
    import threading
    import urllib.request

    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.machinery import trace
    from mpi_operator_tpu.machinery.chaos import (
        ChaosController,
        ChaosProxy,
        ChaosScript,
        NamedProxyFabric,
    )
    from mpi_operator_tpu.machinery.objects import ConfigMap
    from mpi_operator_tpu.machinery.store import AlreadyExists
    from mpi_operator_tpu.machinery.replica_wire import (
        free_ports,
        wait_for_wire_leader,
    )
    from mpi_operator_tpu.api.types import ObjectMeta as _Meta
    from mpi_operator_tpu.opshell.metrics import exposition_quantile

    run_s = float(os.environ.get("BENCH_CP_TORTURE_RUN_S", "0.2"))
    wave = int(os.environ.get("BENCH_CP_TORTURE_WAVE", "200"))
    threadiness = int(os.environ.get("BENCH_CP_SCALE_WORKERS", "4"))
    # tripwires from THE SLO config file (same source as the runtime
    # monitor + scale mode). The reconcile bar is 2× the config's: a
    # DELIBERATE leader SIGKILL puts the ~2-lease failover window's
    # reconciles into p99 by design — the bar is that the window stays
    # bounded (sub-2s), not that chaos is free (measured 955 ms at
    # 100×500 with one kill). An env override stays absolute.
    slo_reconcile = _slo_ms("reconcile-latency", scale=2.0)
    slo_bind = _slo_ms("scheduler-bind")
    slo_lag = _slo_ms("watch-lag")
    chips = max(2, math.ceil(jobs * pods / max(1, nodes)) + 2)

    tmp = tempfile.mkdtemp(prefix="bench-cp-torture-")
    trace_dir = os.path.join(tmp, "traces")
    os.makedirs(trace_dir, exist_ok=True)
    ids = ["n0", "n1", "n2"]
    # one reservation pass holding every socket open (replica_wire owns
    # the collision-safe allocator): sequential bind/close pairs can be
    # handed the same ephemeral port twice
    allocated = free_ports(4)
    ports = dict(zip(ids, allocated))
    mport = allocated[3]
    direct = {nid: f"http://127.0.0.1:{ports[nid]}" for nid in ids}
    tok_path = os.path.join(tmp, "peer.token")
    with open(tok_path, "w") as f:
        f.write("torture-peer-secret\n")
    # per-directed-pair proxies carry the PEER traffic so the scripted
    # partition has a fabric to cut; client traffic dials direct. The
    # bench process stays LIGHT (proxies + chaos + probes only) — the
    # operator is its own real process, so proxy forwarding latency is
    # not coupled to reconcile work.
    proxies = {
        f"{a}->{b}": ChaosProxy(direct[b], seed=seed).start()
        for a in ids for b in ids if a != b
    }
    fabric = NamedProxyFabric(proxies)
    advertise = ",".join(f"{nid}={direct[nid]}" for nid in ids)
    env = dict(os.environ,
               PYTHONPATH=os.path.dirname(os.path.abspath(__file__)),
               TPUJOB_TRACE_DIR=trace_dir)

    def spawn_store(nid: str) -> "subprocess.Popen":
        peers = ",".join(
            f"{o}={direct[o] if o == nid else proxies[f'{nid}->{o}'].url}"
            for o in ids
        )
        return subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.machinery.http_store",
             "--store", f"sqlite:{os.path.join(tmp, nid + '.db')}",
             "--listen", f"127.0.0.1:{ports[nid]}",
             "--log-capacity", "65536",
             "--replica-id", nid, "--peers", peers,
             "--advertise", advertise,
             "--peer-token-file", tok_path,
             # a 0.5s lease churns under load (proxied peer RPCs ride the
             # chaos seam): 2s rides out spikes; the ONE deliberate kill
             # still fails over in ~2 leases
             "--replica-lease-duration", "2.0",
             "--replica-retry-period", "0.2",
             "--replica-seed", str(seed)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, nid + ".log"), "w"),
        )

    def wait_leader(timeout: float = 20.0):
        # ONE probe implementation for smoke + bench (replica_wire owns
        # the status-probe protocol)
        return wait_for_wire_leader(direct, timeout)

    class StoreLeaderTarget:
        """kill = SIGKILL the current leader PROCESS (resolved at fire
        time via the status probe — the real deployed failure)."""

        def __init__(self):
            self.killed = None
            self.killed_at = None  # wall clock, for the trace bar

        def kill(self):
            lead = wait_leader(5.0)
            if lead is None:
                raise RuntimeError("no leader to kill")
            self.killed = lead
            self.killed_at = time.time()
            store_procs[lead].send_signal(_signal.SIGKILL)
            store_procs[lead].wait()

        def term(self):
            self.kill()

    store_procs = {}
    fleet_proc = operator_proc = None
    urls = list(direct.values())
    client = wclient = None
    stop_writer = threading.Event()
    acked = {}
    out: dict = {
        "metric": "controlplane_torture", "nodes": nodes, "jobs": jobs,
        "pods_per_job": pods, "seed": seed, "ok": False,
    }
    try:
        for nid in ids:
            store_procs[nid] = spawn_store(nid)
        first_leader = wait_leader()
        if first_leader is None:
            out["error"] = "no initial leader"
            return out
        client = HttpStoreClient(urls, timeout=60.0,
                                 conn_refused_retries=20,
                                 retry_base_delay=0.05)
        wclient = HttpStoreClient(urls, timeout=10.0,
                                  conn_refused_retries=20,
                                  retry_base_delay=0.05)
        # the REAL operator binary: controller + gang scheduler + node
        # monitor + informer, multi-endpoint store client
        operator_proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.opshell",
             "--store", ",".join(urls), "--executor", "none",
             "--threadiness", str(threadiness),
             "--monitoring-port", str(mport),
             # hollow heartbeats every 5s; 30s grace rides out the
             # failover window without spurious NodeLost evictions
             "--node-grace", "30", "--event-ttl", "600"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "operator.log"), "w"),
        )
        fleet_proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.executor.hollow",
             "--store", ",".join(urls), "--nodes", str(nodes),
             "--chips", str(chips), "--run-s", str(run_s),
             "--heartbeat", "5", "--seed", str(seed)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "fleet.log"), "w"),
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if len(client.list("Node")) >= nodes:
                    break
            except Exception:
                pass
            time.sleep(0.5)

        def writer():
            """Marker writes: the no-acked-write-lost probe. Only
            DEFINITE acks join the must-survive set; indeterminate
            outcomes burn the name (the documented contract)."""
            i = 0
            while not stop_writer.is_set():
                try:
                    o = wclient.create(ConfigMap(metadata=_Meta(
                        name=f"m{i:05d}", namespace="torture")))
                    acked[o.metadata.name] = o.metadata.resource_version
                except Exception:
                    pass
                i += 1
                stop_writer.wait(0.05)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()

        # arm the chaos once traffic flows: partition the leader from
        # one follower, then SIGKILL the leader mid-partition
        other = next(o for o in ids if o != first_leader)
        script = ChaosScript.parse({
            "seed": seed,
            "actions": [
                {"at": 10.0, "fault": "partition", "a": first_leader,
                 "b": other, "duration": 6.0},
                {"at": 13.0, "fault": "kill", "target": "leader"},
            ],
        })
        target = StoreLeaderTarget()
        chaos = ChaosController(
            script, targets={"leader": target}, fabric=fabric,
        ).arm()

        t0 = time.perf_counter()
        submitted = 0
        done = 0
        deadline = time.time() + float(os.environ.get(
            "BENCH_CP_TORTURE_DEADLINE_S", max(600.0, jobs * 0.6)))
        while time.time() < deadline:
            try:
                done = sum(1 for j in client.list("TPUJob", "bench")
                           if cond.is_succeeded(j.status))
            except Exception:
                pass  # failover window: last count stands this tick
            while submitted < jobs and submitted - done < wave:
                try:
                    client.create(_make_job(submitted, pods, clean="All"))
                except AlreadyExists:
                    # an indeterminate create that actually COMMITTED
                    # (leader died between commit and response): the job
                    # exists — counting it submitted is the only exit, or
                    # this index re-rejects forever and the run wedges
                    pass
                except Exception:
                    break  # failover window: retry this index next tick
                submitted += 1
            if done >= jobs and chaos.done():
                break
            time.sleep(1.0)
        elapsed = time.perf_counter() - t0
        chaos.join(10.0)
        chaos_errors = [e for _, _, e in chaos.executed if e]
        stop_writer.set()
        # a writer blocked in a failover-window request can outlive a
        # short join; the verification below iterates `acked`, so wait
        # generously and then SNAPSHOT it (a late in-flight ack would
        # otherwise mutate the dict mid-iteration)
        wt.join(30.0)
        new_leader = wait_leader()
        out.update({
            "hollow_run_s": run_s,
            "jobs_succeeded": done,
            "elapsed_s": round(elapsed, 1),
            "jobs_per_s": round(done / max(1e-9, elapsed), 1),
            "leader_killed": target.killed,
            "new_leader": new_leader,
            "chaos_errors": chaos_errors,
            "acked_markers": len(acked),
        })

        # --- SLOs, read from the OPERATOR's real /metrics exposition ---
        expo = ""
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=10.0
            ) as r:
                expo = r.read().decode()
        except Exception as e:
            out["metrics_error"] = str(e)
        for q, tag in ((0.50, "p50"), (0.99, "p99")):
            for key, family in (
                ("reconcile", "tpu_operator_reconcile_latency_seconds"),
                ("bind", "tpu_operator_scheduler_bind_latency_seconds"),
                ("watch_lag", "tpu_operator_watch_delivery_lag_seconds"),
            ):
                try:
                    out[f"{key}_{tag}_ms"] = round(
                        exposition_quantile(expo, family, q) * 1e3, 2)
                except (KeyError, ValueError):
                    out[f"{key}_{tag}_ms"] = -1.0
        out["slo"] = {"reconcile_p99_ms": slo_reconcile,
                      "bind_p99_ms": slo_bind,
                      "watch_lag_p99_ms": slo_lag}
        slo_ok = (0 <= out["reconcile_p99_ms"] <= slo_reconcile
                  and 0 <= out["bind_p99_ms"] <= slo_bind
                  and 0 <= out["watch_lag_p99_ms"] <= slo_lag)
        out["slo_ok"] = bool(slo_ok)

        # --- the acked-write bar: every DEFINITE ack at its exact rv ---
        lost = []
        lead_client = HttpStoreClient(direct[new_leader], timeout=30.0) \
            if new_leader else None
        acked_snapshot = dict(acked)
        try:
            for name, rv in acked_snapshot.items():
                try:
                    got = lead_client.get("ConfigMap", "torture", name)
                    if got.metadata.resource_version != rv:
                        lost.append((name, rv,
                                     got.metadata.resource_version))
                except Exception as e:
                    lost.append((name, rv, f"missing: {e}"))
        finally:
            if lead_client is not None:
                lead_client.close()
        out["acked_lost"] = lost[:10]

        # --- the connected failover trace ------------------------------
        time.sleep(0.5)  # let the subprocess 0.2s flushers drain
        spans = trace.load_spans(trace_dir)
        elections = [s for s in spans
                     if s.get("name") == "replica.election"
                     and (s.get("attrs") or {}).get("won")]
        trace_ok, trace_why = False, ""
        if not elections:
            trace_why = "no winning election span"
        else:
            win = max(elections, key=lambda s: s.get("start") or 0)
            comps = trace.connected_components(spans, link_traces=True)
            comp = next(c for c in comps if win["span_id"] in c)
            in_comp = [s for s in spans if s["span_id"] in comp]
            names = {s["name"] for s in in_comp}
            kill_wall = target.killed_at or 0
            post_rec = [s for s in in_comp
                        if s["name"] == "controller.reconcile"
                        and (s.get("start") or 0) > kill_wall]
            if not win.get("parent_id"):
                trace_why = "election span unanchored"
            elif "replica.ship" not in names:
                trace_why = "no ship span connected"
            elif "store.request" not in names:
                trace_why = "no write span connected"
            elif not post_rec:
                trace_why = "no post-failover reconcile connected"
            else:
                trace_ok = True
        out["trace_connected"] = trace_ok
        if trace_why:
            out["trace_why"] = trace_why

        # --- ctl renders the incident rc=0 ------------------------------
        from mpi_operator_tpu.opshell import ctl

        import contextlib
        import io

        old_trace_dir = os.environ.get("TPUJOB_TRACE_DIR")
        os.environ["TPUJOB_TRACE_DIR"] = trace_dir
        try:
            # the render itself is operator-facing; the bench only needs
            # the rc — swallow the (large) timeline so the bench's stdout
            # stays one JSON line per mode
            with contextlib.redirect_stdout(io.StringIO()):
                rc = ctl.main(["--store", direct[new_leader or "n1"],
                               "trace", "--last-incident"])
        finally:
            if old_trace_dir is None:
                os.environ.pop("TPUJOB_TRACE_DIR", None)
            else:
                os.environ["TPUJOB_TRACE_DIR"] = old_trace_dir
        out["ctl_trace_rc"] = rc

        out["ok"] = bool(
            done >= jobs
            and not lost
            and not chaos_errors
            and target.killed is not None
            and new_leader is not None
            and new_leader != target.killed
            and len(acked) >= 20
            and slo_ok
            and trace_ok
            and rc == 0
        )
        return out
    finally:
        stop_writer.set()
        for c in (client, wclient):
            if c is not None:
                c.close()
        for proxy in proxies.values():
            proxy.stop()
        procs = [operator_proc, fleet_proc] + list(store_procs.values())
        for proc in procs:
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        if os.environ.get("BENCH_CP_TORTURE_KEEP"):
            print(f"torture dir kept: {tmp}", file=sys.stderr)
        else:
            shutil.rmtree(tmp, ignore_errors=True)


def run_serve_mode() -> dict:
    """The serving workload class under traffic (BENCH_CP_MODES=serve,
    ISSUE 11): a hollow fleet hosts ONE autoscaled TPUServe sharing the
    cluster with a batch backlog, driven by a diurnal-plus-spike offered-
    load curve through the closed loop the autoscaler actually lives in
    (ServeLoadModel: more replicas → lower per-pod load → lower latency).

    Asserted (the slo block):
    - the autoscaler TRACKS the curve: peak ready replicas >= 4× the
      baseline, and the quiet tail scales to ZERO;
    - a mid-run rolling update completes with ZERO unready windows
      (ready gangs never dip below desired while rolling);
    - serve-readiness p99 (creation → every member ready, from the PR 9
      histogram) within BENCH_CP_SLO_SERVE_READY_P99_MS;
    - the batch backlog still FINISHES: serving scale-up preempts batch
      gangs (priority high > default), preempted jobs restart for free
      and reach Succeeded — the preempt+resume visible in `ctl trace`.
    """
    import io
    import contextlib
    import threading

    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.api.client import TPUServeClient
    from mpi_operator_tpu.controller.autoscaler import (
        ANNOTATION_OFFERED_QPS,
        ServeAutoscaler,
    )
    from mpi_operator_tpu.controller.serve import (
        LABEL_SERVE_NAME,
        TPUServeController,
        group_replicas,
        replica_ready,
    )
    from mpi_operator_tpu.executor.hollow import (
        HollowFleet,
        HollowTimeline,
        ServeLoadModel,
    )
    from mpi_operator_tpu.machinery import trace
    from mpi_operator_tpu.opshell import ctl, metrics

    nodes = int(os.environ.get("BENCH_CP_SERVE_NODES", "10"))
    batch_jobs = int(os.environ.get("BENCH_CP_SERVE_BATCH_JOBS", "24"))
    batch_pods = int(os.environ.get("BENCH_CP_SERVE_BATCH_PODS", "4"))
    batch_run_s = float(os.environ.get("BENCH_CP_SERVE_BATCH_RUN_S", "4.0"))
    spike_qps = float(os.environ.get("BENCH_CP_SERVE_SPIKE_QPS", "1200"))
    base_qps = float(os.environ.get("BENCH_CP_SERVE_BASE_QPS", "80"))
    # the cold-start bar from THE SLO config file (serve-ready entry;
    # env override preserved) — the same objective the runtime monitor
    # burn-rate-alerts on
    slo_ready_p99_ms = _slo_ms("serve-ready")

    tmp = tempfile.mkdtemp(prefix="bench-cp-serve-")
    trace_dir = os.path.join(tmp, "traces")
    trace.TRACER.configure("bench-serve", dir=trace_dir)
    backing = SqliteStore(os.path.join(tmp, "store.db"))
    server = StoreServer(backing, "127.0.0.1", 0,
                         log_capacity=65536).start()
    client = HttpStoreClient(server.url, timeout=30.0,
                             watch_poll_timeout=2.0)
    fleet_client = HttpStoreClient(server.url, timeout=30.0,
                                   watch_poll_timeout=2.0)
    load = ServeLoadModel(capacity_qps=150.0, base_ms=20.0)
    timeline = HollowTimeline(
        pending_s=0.05, run_s=batch_run_s, seed=11,
        serve_warmup_s=0.4, serve_stats_interval_s=0.25, load=load,
    )
    snaps = {"ready": metrics.serve_ready_latency.snapshot()}
    preempted0 = metrics.gangs_preempted.get()
    cache = InformerCache(client).start()
    recorder = EventRecorder(client)
    controller = TPUJobController(
        client, recorder, ControllerOptions(threadiness=4), cache=cache)
    serve_controller = TPUServeController(client, recorder, cache=cache)
    scheduler = GangScheduler(client, recorder, cache=cache,
                              preemption_grace=0.5)
    autoscaler = ServeAutoscaler(client, recorder, cache=cache,
                                 interval=0.5)
    fleet = None
    serve_key = "bench/svc"
    samples = []          # (t, offered, desired, ready)
    rollout_dips = []
    try:
        if not cache.wait_for_sync(30.0):
            raise RuntimeError("informer cache never synced")
        fleet = HollowFleet(fleet_client, nodes, timeline=timeline,
                            capacity_chips=4,
                            heartbeat_interval=2.0).start()
        deadline = time.time() + 30
        while time.time() < deadline:
            if len(cache.list("Node")) >= nodes:
                break
            time.sleep(0.1)
        controller.run()
        serve_controller.run()
        scheduler.start()
        autoscaler.start()

        sc = TPUServeClient(client, namespace="bench")
        sc.create({
            "kind": "TPUServe",
            "metadata": {"name": "svc", "namespace": "bench"},
            "spec": {
                "replicas": 1,
                "workers_per_replica": 1,
                "slice": {"accelerator": "cpu", "chips_per_host": 2},
                "autoscale": {
                    "min_replicas": 0, "max_replicas": 12,
                    "target_qps_per_replica": 100.0,
                    "scale_up_stabilization_s": 0.0,
                    "scale_down_stabilization_s": 3.0,
                    "scale_to_zero_after_s": 6.0,
                    "cold_start_grace_s": 2.0,
                },
            },
        })
        # the batch backlog, submitted up front: it must share the
        # cluster AND eventually finish despite the serving spike
        for i in range(batch_jobs):
            job = _make_job(i, batch_pods, clean="All")
            job.spec.slice.chips_per_host = 2
            job.spec.slots_per_worker = 2
            job.spec.worker.restart_policy = "OnFailure"
            client.create(job)

        def offered(qps: float) -> None:
            load.set_offered(serve_key, qps)
            client.patch("TPUServe", "bench", "svc", {"metadata": {
                "annotations": {ANNOTATION_OFFERED_QPS: str(qps)}}})

        def serve_counts():
            pods = [p for p in client.list(
                "Pod", "bench", selector={LABEL_SERVE_NAME: "svc"})
                if not p.is_finished()]
            ready = sum(1 for m in group_replicas(pods).values()
                        if replica_ready(m, 1))
            serve = client.get("TPUServe", "bench", "svc")
            return serve, ready

        def observe(tag: str, qps: float) -> int:
            serve, ready = serve_counts()
            samples.append({
                "t": round(time.time() - t0, 1), "phase": tag,
                "offered_qps": qps,
                "desired": serve.spec.replicas, "ready": ready,
            })
            return ready

        t0 = time.time()
        # --- phase 1: diurnal baseline ---
        offered(base_qps)
        while time.time() - t0 < 8.0:
            observe("baseline", base_qps)
            time.sleep(0.5)
        baseline_ready = max(1, observe("baseline", base_qps))
        # --- phase 2: the spike (serving must displace batch) ---
        offered(spike_qps)
        peak_ready = 0
        while time.time() - t0 < 30.0:
            peak_ready = max(peak_ready, observe("spike", spike_qps))
            time.sleep(0.5)
        # --- phase 3: settle to a mid plateau, then roll the template ---
        offered(300.0)
        plateau_deadline = time.time() + 20
        while time.time() < plateau_deadline:
            serve, ready = serve_counts()
            if serve.spec.replicas == 3 and ready == 3 \
                    and serve.status.updated_replicas == 3:
                break
            observe("settle", 300.0)
            time.sleep(0.5)
        rollout_desired = 3
        s2 = sc.get("svc")
        s2.spec.template.container.env = {"MODEL": "v2"}
        sc.update(s2)
        rollout_deadline = time.time() + 30
        rollout_converged = False
        while time.time() < rollout_deadline:
            serve, ready = serve_counts()
            observe("rollout", 300.0)
            if ready < min(rollout_desired, serve.spec.replicas or 0):
                rollout_dips.append({"t": round(time.time() - t0, 1),
                                     "ready": ready,
                                     "desired": serve.spec.replicas})
            st = serve.status
            if (st.serve_generation == 1
                    and st.updated_replicas == (serve.spec.replicas or 0)
                    and st.replicas == (serve.spec.replicas or 0)
                    and ready == (serve.spec.replicas or 0)):
                rollout_converged = True
                break
            time.sleep(0.25)
        # --- phase 4: traffic dies; scale-to-zero ---
        offered(0.0)
        zero_deadline = time.time() + 30
        scaled_to_zero = False
        while time.time() < zero_deadline:
            serve, ready = serve_counts()
            observe("quiet", 0.0)
            if (serve.spec.replicas or 0) == 0 and serve.status.replicas == 0:
                scaled_to_zero = True
                break
            time.sleep(0.5)
        # --- batch must still finish (preempted gangs resumed) ---
        batch_deadline = time.time() + float(os.environ.get(
            "BENCH_CP_SERVE_BATCH_DEADLINE_S", "120"))
        done = 0
        while time.time() < batch_deadline:
            done = sum(
                1 for j in client.list("TPUJob", "bench")
                if cond.is_succeeded(j.status)
            )
            if done >= batch_jobs:
                break
            time.sleep(1.0)
        elapsed = time.time() - t0

        preempted = metrics.gangs_preempted.get() - preempted0
        # the preempt→restart causality, straight from the span trail: a
        # FREE gang restart is the resume half of a preemption
        trace.TRACER.flush()
        spans = trace.load_spans(trace_dir)
        free_restarts = [
            s for s in spans if s.get("name") == "controller.gang_restart"
            and (s.get("attrs") or {}).get("free")
        ]
        ctl_trace_rc = None
        ctl_trace_has_restart = False
        if free_restarts:
            job_key = free_restarts[0]["attrs"]["job"]
            job_name = job_key.split("/", 1)[1]
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                ctl_trace_rc = ctl.main([
                    "--store", server.url, "-n", "bench",
                    "trace", job_name, "--trace-dir", trace_dir,
                ])
            ctl_trace_has_restart = "gang_restart" in buf.getvalue()

        ready_p99_ms = round(_hist_quantile_delta(
            metrics.serve_ready_latency, 0.99, snaps["ready"],
            metrics.serve_ready_latency.snapshot()) * 1e3, 1)
        ready_latencies = sorted(
            round(float((s.get("attrs") or {}).get("ready_latency_s", 0)), 2)
            for s in spans if s.get("name") == "serve.replica_ready"
        )
        out = {
            "metric": "controlplane_serve",
            "nodes": nodes,
            "chips": nodes * 4,
            "batch_jobs": batch_jobs,
            "batch_pods_per_job": batch_pods,
            "baseline_ready": baseline_ready,
            "peak_ready": peak_ready,
            "spike_factor": round(peak_ready / max(1, baseline_ready), 1),
            "rollout_converged": rollout_converged,
            "rollout_unready_windows": len(rollout_dips),
            "scaled_to_zero": scaled_to_zero,
            "batch_succeeded": done,
            "gangs_preempted": int(preempted),
            "free_gang_restarts": len(free_restarts),
            "ctl_trace_rc": ctl_trace_rc,
            "ctl_trace_shows_restart": ctl_trace_has_restart,
            "serve_ready_p99_ms": ready_p99_ms,
            "ready_latencies_s": ready_latencies,
            "elapsed_s": round(elapsed, 1),
            "timeline": samples[-60:],
        }
        out["slo"] = {
            "spike_factor_min": 4.0,
            "serve_ready_p99_ms": slo_ready_p99_ms,
            "rollout_unready_windows": 0,
        }
        out["slo_ok"] = bool(
            out["spike_factor"] >= 4.0
            and rollout_converged
            and not rollout_dips
            and scaled_to_zero
            and done >= batch_jobs
            and preempted > 0
            and ready_p99_ms <= slo_ready_p99_ms
            and ctl_trace_rc == 0
            and ctl_trace_has_restart
        )
        return out
    finally:
        for comp in (autoscaler, serve_controller, controller):
            try:
                comp.stop()
            except Exception:
                pass
        scheduler.stop()
        if fleet is not None:
            fleet.stop()
        cache.stop()
        client.close()
        fleet_client.close()
        server.stop()
        backing.close()
        trace.TRACER.disable()


def run_drain_mode(seed: int) -> dict:
    """The disruption plane under rolling maintenance (BENCH_CP_MODES=
    drain, ISSUE 14): a hollow fleet hosts a DisruptionBudget-protected
    TPUServe plus a live batch backlog while a seeded maintenance wave
    rolls over 20% of the nodes (notice → cordon → checkpoint-then-migrate
    → deadline), with ONE extra notice deliberately too short to drain in
    time (the escalation bar).

    Asserted (the slo block):
    - every batch job reaches Succeeded DESPITE the wave, with
      restart_count UNCHANGED (0) — planned moves never burn the
      backoffLimit budget — while >=1 gang shows restart_generation > 0
      (the migrations actually happened);
    - ZERO windows with serve ready below the DisruptionBudget;
    - every noticed node drains EMPTY and the deliberate overrun is
      hard-evicted (drains_total{outcome=escalated} >= 1);
    - SLOs green: reconcile/bind p99 within the slo_defaults.json bars,
      drain-migration p99 within its objective threshold;
    - the trace renders the story: ONE connected component holds the
      notice (drain.node) → migration (drain.migrate_gang) → restart
      (controller.gang_restart) chain, the escalated node's component
      holds drain.escalate → drain.hard_evict → restart (the
      maintenance-fire chain), and `ctl trace <job>` exits 0.
    """
    import io
    import contextlib
    import threading

    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.api.client import TPUServeClient
    from mpi_operator_tpu.controller.disruption import DrainController
    from mpi_operator_tpu.controller.node_monitor import NodeMonitor
    from mpi_operator_tpu.controller.serve import TPUServeController
    from mpi_operator_tpu.executor.hollow import (
        HollowFleet,
        HollowTimeline,
        MaintenanceSchedule,
    )
    from mpi_operator_tpu.machinery import trace
    from mpi_operator_tpu.machinery.objects import (
        ANNOTATION_MAINTENANCE_AT,
        NODE_NAMESPACE,
    )
    from mpi_operator_tpu.opshell import ctl, metrics

    nodes = int(os.environ.get("BENCH_CP_DRAIN_NODES", "100"))
    fraction = float(os.environ.get("BENCH_CP_DRAIN_FRACTION", "0.2"))
    batch_jobs = int(os.environ.get("BENCH_CP_DRAIN_BATCH_JOBS", "40"))
    batch_pods = int(os.environ.get("BENCH_CP_DRAIN_BATCH_PODS", "2"))
    batch_run_s = float(os.environ.get("BENCH_CP_DRAIN_BATCH_RUN_S", "6.0"))
    notice_s = float(os.environ.get("BENCH_CP_DRAIN_NOTICE_S", "10.0"))
    serve_replicas = 6
    budget = 5
    slo_reconcile = _slo_ms("reconcile-latency")
    slo_bind = _slo_ms("scheduler-bind")
    slo_drain = _slo_ms("drain-migration")

    tmp = tempfile.mkdtemp(prefix="bench-cp-drain-")
    trace_dir = os.path.join(tmp, "traces")
    trace.TRACER.configure("bench-drain", dir=trace_dir)
    backing = SqliteStore(os.path.join(tmp, "store.db"))
    server = StoreServer(backing, "127.0.0.1", 0,
                         log_capacity=65536).start()
    client = HttpStoreClient(server.url, timeout=30.0,
                             watch_poll_timeout=2.0)
    fleet_client = HttpStoreClient(server.url, timeout=30.0,
                                   watch_poll_timeout=2.0)
    timeline = HollowTimeline(pending_s=0.05, run_s=batch_run_s,
                              run_jitter_s=2.0, seed=seed,
                              serve_warmup_s=0.3)
    snaps = {
        "reconcile": metrics.reconcile_latency.snapshot(),
        "bind": metrics.scheduler_bind_latency.snapshot(),
        "drain": metrics.drain_migration_latency.snapshot(),
    }
    escalated0 = metrics.drains_total.get(outcome="escalated")
    cache = InformerCache(client).start()
    recorder = EventRecorder(client)
    controller = TPUJobController(
        client, recorder, ControllerOptions(threadiness=4), cache=cache)
    serve_controller = TPUServeController(client, recorder, cache=cache)
    scheduler = GangScheduler(client, recorder, cache=cache)
    monitor = NodeMonitor(client, recorder, cache=cache)
    drain = DrainController(client, recorder, interval=0.2, cache=cache)
    fleet = None
    samples = []
    min_ready = [serve_replicas]
    try:
        if not cache.wait_for_sync(30.0):
            raise RuntimeError("informer cache never synced")
        fleet = HollowFleet(fleet_client, nodes, timeline=timeline,
                            capacity_chips=4,
                            heartbeat_interval=2.0).start()
        deadline = time.time() + 60
        while time.time() < deadline:
            if len(cache.list("Node")) >= nodes:
                break
            time.sleep(0.1)
        controller.run()
        serve_controller.run()
        scheduler.start()
        monitor.start()
        drain.start()

        TPUServeClient(client, namespace="bench").create({
            "kind": "TPUServe",
            "metadata": {"name": "svc", "namespace": "bench"},
            "spec": {
                "replicas": serve_replicas, "workers_per_replica": 1,
                "slice": {"accelerator": "cpu", "chips_per_host": 2},
                "disruption_budget": budget, "max_surge": 2,
                "max_unavailable": 1,
            },
        })
        for i in range(batch_jobs):
            job = _make_job(i, batch_pods, clean="None")
            job.spec.worker.restart_policy = "OnFailure"
            client.create(job)

        def serve_ready() -> int:
            s = client.try_get("TPUServe", "bench", "svc")
            return s.status.ready_replicas if s else 0

        def succeeded() -> int:
            return sum(1 for j in client.list("TPUJob", "bench")
                       if cond.is_succeeded(j.status))

        t0 = time.time()
        deadline = time.time() + 60
        while time.time() < deadline and serve_ready() < serve_replicas:
            time.sleep(0.2)
        if serve_ready() < serve_replicas:
            raise RuntimeError("serve never reached full readiness")
        deadline = time.time() + 30
        while time.time() < deadline and not any(
            p.status.phase == "Running"
            for p in cache.list("Pod", "bench")
        ):
            time.sleep(0.2)

        # --- the rolling wave: 20% of the fleet, seeded, staggered -----
        sched_m = MaintenanceSchedule(fraction=fraction, notice_s=notice_s,
                                      start_s=0.5, stagger_s=0.4,
                                      seed=seed)
        victims = sched_m.victims(fleet.node_names)
        fleet.arm_maintenance(sched_m)
        # ... plus ONE deliberate overrun: a node with live pods and a
        # notice far too short to drain gracefully → must hard-evict
        overrun = None
        deadline = time.time() + 30
        while overrun is None and time.time() < deadline:
            for p in cache.list("Pod", "bench"):
                n = p.spec.node_name
                if (n and n not in victims and not p.is_finished()
                        and p.status.phase == "Running"):
                    overrun = n
                    break
            time.sleep(0.1)
        if overrun is None:
            raise RuntimeError("no node eligible for the overrun probe")
        # zero-warning reclaim: the deadline is already PAST when the
        # notice lands, so the first drain tick must hard-evict (a
        # graceful migration is store-instant and would beat any
        # realistically short window)
        fleet.announce_maintenance(overrun, time.time() - 0.1)

        # --- drive to completion, sampling the budget every 100ms ------
        sample_stop = threading.Event()

        def sampler():
            while not sample_stop.is_set():
                r = serve_ready()
                min_ready[0] = min(min_ready[0], r)
                samples.append({"t": round(time.time() - t0, 1),
                                "ready": r})
                sample_stop.wait(0.1)

        st = threading.Thread(target=sampler, daemon=True)
        st.start()
        run_deadline = time.time() + float(os.environ.get(
            "BENCH_CP_DRAIN_DEADLINE_S", "180"))
        done = 0
        while time.time() < run_deadline:
            done = succeeded()
            if done >= batch_jobs:
                break
            time.sleep(0.5)
        # every noticed node must drain EMPTY (cordoned, nothing live)
        all_noticed = victims + [overrun]
        drained_deadline = time.time() + 60
        remaining = all_noticed
        while time.time() < drained_deadline:
            live = {p.spec.node_name for p in cache.list("Pod")
                    if p.spec.node_name and not p.is_finished()}
            remaining = [n for n in all_noticed if n in live]
            if not remaining:
                break
            time.sleep(0.5)
        # serve settles back to full strength off the drained nodes
        settle_deadline = time.time() + 60
        while time.time() < settle_deadline \
                and serve_ready() < serve_replicas:
            time.sleep(0.2)
        sample_stop.set()
        st.join(timeout=2)
        elapsed = time.time() - t0

        jobs_all = client.list("TPUJob", "bench")
        migrated = [j for j in jobs_all
                    if j.status.restart_generation > 0]
        burned = [j.metadata.name for j in jobs_all
                  if j.status.restart_count > 0]
        escalated = metrics.drains_total.get(
            outcome="escalated") - escalated0

        # --- the trace story -------------------------------------------
        trace.TRACER.flush()
        spans = trace.load_spans(trace_dir)
        comps = trace.connected_components(spans, link_traces=True)
        by_id = {s["span_id"]: s for s in spans if "span_id" in s}

        def component_names(comp):
            return {by_id[sid]["name"] for sid in comp if sid in by_id}

        migrate_chain = any(
            {"drain.node", "drain.migrate_gang",
             "controller.gang_restart"} <= component_names(c)
            for c in comps
        )
        fire_chain = any(
            {"drain.node", "drain.escalate", "drain.hard_evict",
             "controller.gang_restart"} <= component_names(c)
            for c in comps
        )
        ctl_trace_rc = None
        if migrated:
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                ctl_trace_rc = ctl.main([
                    "--store", server.url, "-n", "bench",
                    "trace", migrated[0].metadata.name,
                    "--trace-dir", trace_dir,
                ])

        out = {
            "metric": "controlplane_drain",
            "seed": seed,
            "nodes": nodes,
            "noticed_nodes": len(all_noticed),
            "batch_jobs": batch_jobs,
            "batch_succeeded": done,
            "gangs_migrated": len(migrated),
            "jobs_with_burned_backoff": burned,
            "serve_replicas": serve_replicas,
            "disruption_budget": budget,
            "min_ready_during_wave": min_ready[0],
            "budget_violation_windows": sum(
                1 for s in samples if s["ready"] < budget),
            "drains_escalated": int(escalated),
            "nodes_never_drained": remaining,
            "trace_migrate_chain_connected": migrate_chain,
            "trace_fire_chain_connected": fire_chain,
            "ctl_trace_rc": ctl_trace_rc,
            "elapsed_s": round(elapsed, 1),
            "timeline_tail": samples[-40:],
        }
        for q, tag in ((0.50, "p50"), (0.99, "p99")):
            out[f"reconcile_{tag}_ms"] = round(_hist_quantile_delta(
                metrics.reconcile_latency, q, snaps["reconcile"],
                metrics.reconcile_latency.snapshot()) * 1e3, 2)
            out[f"bind_{tag}_ms"] = round(_hist_quantile_delta(
                metrics.scheduler_bind_latency, q, snaps["bind"],
                metrics.scheduler_bind_latency.snapshot()) * 1e3, 2)
            out[f"drain_migration_{tag}_ms"] = round(_hist_quantile_delta(
                metrics.drain_migration_latency, q, snaps["drain"],
                metrics.drain_migration_latency.snapshot()) * 1e3, 2)
        out["slo"] = {
            "reconcile_p99_ms": slo_reconcile,
            "bind_p99_ms": slo_bind,
            "drain_migration_p99_ms": slo_drain,
            "budget_violation_windows": 0,
        }
        out["ok"] = bool(
            done >= batch_jobs
            and not burned
            and migrated
            and min_ready[0] >= budget
            and out["budget_violation_windows"] == 0
            and escalated >= 1
            and not remaining
            and migrate_chain
            and fire_chain
            and ctl_trace_rc == 0
            and out["reconcile_p99_ms"] <= slo_reconcile
            and out["bind_p99_ms"] <= slo_bind
            and out["drain_migration_p99_ms"] <= slo_drain
        )
        return out
    finally:
        try:
            sample_stop.set()
        except NameError:
            pass
        drain.stop()
        monitor.stop()
        for comp in (serve_controller, controller):
            try:
                comp.stop()
            except Exception:
                pass
        scheduler.stop()
        if fleet is not None:
            fleet.stop()
        cache.stop()
        client.close()
        fleet_client.close()
        server.stop()
        backing.close()
        trace.TRACER.disable()


def _soak_scenario(seed: int, day: float, scale: float,
                   reclaim_target: str) -> dict:
    """One compressed fleet-day (ISSUE 18): a diurnal serve curve, two
    seeded batch tenants (2-chip fragmenters + 4-chip whole-node gangs),
    one rolling maintenance wave, one zero-warning reclaim."""
    return {
        "seed": seed, "scale": scale, "duration": day,
        "serves": [{"serve": "soak/web", "curve": "diurnal",
                    "peak_qps": 80.0, "trough_qps": 10.0,
                    "period": day, "interval": day / 24.0}],
        "arrivals": [
            # the fragmenters: LONG-lived 2-pod × 2-chip gangs the
            # least-loaded scheduler scatters across half-full nodes —
            # without the rescheduler the scatter persists for hours of
            # scenario time (fast-churning jobs would defragment the
            # baseline by natural attrition and hide the effect)
            {"tenant": "etl", "rate_per_hour": 5.0, "pods": 2,
             "chips": 2, "end": day * 0.8},
            # the whole-node gangs the fragmentation blocks — frequent
            # enough that the sampler catches them queued (the A/B gate
            # is starved-while-queued windows, which needs demand), but
            # below saturation: starvation must come from SCATTER, not
            # from a fleet with genuinely zero free chips (the
            # rescheduler cannot conjure capacity, only compact it)
            {"tenant": "train", "rate_per_hour": 3.0, "pods": 1,
             "chips": 4, "end": day * 0.8},
        ],
        "maintenance": [{"at": day * 0.35, "fraction": 0.2,
                         "notice": 600.0, "stagger": 120.0}],
        "chaos": [{"at": day * 0.7, "fault": "reclaim",
                   "target": reclaim_target}],
    }


def _soak_arm(seed: int, *, rescheduler: bool, judge: bool) -> dict:
    """One arm of the soak A/B (BENCH_CP_MODES=soak, ISSUE 18): the
    deployed multi-process shape — three wire-replicated `tpu-store`
    processes and a real `tpu-operator` process (with or without
    `--no-rescheduler`) — hosting a scenario-driven hollow fleet (the
    fleet rides the bench process over its own wire client so the
    scenario engine can set serve load, arm waves and fire the reclaim).
    When ``judge`` is set, an SLOMonitor with compressed burn windows
    scrapes the operator's real /metrics and its Alert objects are the
    acceptance bar: every page must be explained by a scripted
    disruption and carry a flight-recorder bundle that renders rc=0."""
    import shutil
    import subprocess
    import threading
    import urllib.request

    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.api.client import TPUServeClient
    from mpi_operator_tpu.api.types import ALERT_NAMESPACE
    from mpi_operator_tpu.controller.slo_monitor import (
        SLOMonitor,
        load_slo_config,
    )
    from mpi_operator_tpu.executor.hollow import (
        HollowFleet,
        HollowTimeline,
        ServeLoadModel,
    )
    from mpi_operator_tpu.machinery.objects import (
        ANNOTATION_MAINTENANCE_AT,
        NODE_NAMESPACE,
    )
    from mpi_operator_tpu.machinery.replica_wire import (
        free_ports,
        wait_for_wire_leader,
    )
    from mpi_operator_tpu.machinery.scenario import (
        Scenario,
        ScenarioEngine,
        VirtualClock,
    )
    from mpi_operator_tpu.machinery import trace
    from mpi_operator_tpu.machinery.telemetry import ScrapeTarget
    from mpi_operator_tpu.opshell import ctl

    day = float(os.environ.get("BENCH_CP_SOAK_DAY_S", "21600"))
    scale = float(os.environ.get("BENCH_CP_SOAK_SCALE", "360"))
    nodes = int(os.environ.get("BENCH_CP_SOAK_NODES", "14"))
    serve_replicas = 4
    budget = 3
    reclaim_target = "hollow-0005"
    scenario = Scenario.parse(
        _soak_scenario(seed, day, scale, reclaim_target))
    clock = VirtualClock(scale)

    tmp = tempfile.mkdtemp(prefix="bench-cp-soak-")
    trace_dir = os.path.join(tmp, "traces")
    os.makedirs(trace_dir, exist_ok=True)
    # the judge's slo.alert spans are what `ctl trace --last-incident`
    # renders — they must land in the same dir the subprocesses write to
    trace.TRACER.configure("bench-soak", dir=trace_dir)
    ids = ["n0", "n1", "n2"]
    allocated = free_ports(4)
    ports = dict(zip(ids, allocated))
    mport = allocated[3]
    direct = {nid: f"http://127.0.0.1:{ports[nid]}" for nid in ids}
    urls = list(direct.values())
    tok_path = os.path.join(tmp, "peer.token")
    with open(tok_path, "w") as f:
        f.write("soak-peer-secret\n")
    advertise = ",".join(f"{nid}={direct[nid]}" for nid in ids)
    env = dict(os.environ,
               PYTHONPATH=os.path.dirname(os.path.abspath(__file__)),
               TPUJOB_TRACE_DIR=trace_dir)

    def spawn_store(nid: str) -> "subprocess.Popen":
        peers = ",".join(f"{o}={direct[o]}" for o in ids)
        return subprocess.Popen(
            [sys.executable, "-m",
             "mpi_operator_tpu.machinery.http_store",
             "--store", f"sqlite:{os.path.join(tmp, nid + '.db')}",
             "--listen", f"127.0.0.1:{ports[nid]}",
             "--log-capacity", "65536",
             "--replica-id", nid, "--peers", peers,
             "--advertise", advertise,
             "--peer-token-file", tok_path,
             "--replica-lease-duration", "2.0",
             "--replica-retry-period", "0.2",
             "--replica-seed", str(seed)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, nid + ".log"), "w"),
        )

    store_procs: dict = {}
    operator_proc = None
    fleet = engine = monitor = None
    client = fleet_client = None
    sample_stop = threading.Event()
    out: dict = {"arm": "rescheduler" if rescheduler else "baseline",
                 "ok": False}
    # budget/fragmentation samples + page sightings, appended by the
    # sampler thread, read after join
    samples: list = []
    pages: dict = {}
    first_pending: dict = {}
    bound_at: dict = {}
    chips_by_job: dict = {}
    t0 = time.time()
    try:
        for nid in ids:
            store_procs[nid] = spawn_store(nid)
        if wait_for_wire_leader(direct, 20.0) is None:
            out["error"] = "no wire leader"
            return out
        client = HttpStoreClient(urls, timeout=30.0,
                                 conn_refused_retries=20,
                                 retry_base_delay=0.05)
        fleet_client = HttpStoreClient(urls, timeout=30.0,
                                       conn_refused_retries=20,
                                       retry_base_delay=0.05)
        operator_proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.opshell",
             "--store", ",".join(urls), "--executor", "none",
             "--threadiness", "4",
             "--monitoring-port", str(mport),
             # the reclaim's free eviction must come from the drain
             # plane's escalation, not a NodeLost sweep: keep the grace
             # far beyond the drain interval
             "--node-grace", "30", "--event-ttl", "600",
             # the rescheduler's governance defaults assume a real day;
             # the compressed one needs the budget window compressed the
             # same way (2 moves/60s would be 2 moves per WHOLE day)
             "--reschedule-interval", "0.5",
             "--reschedule-max-moves", "4",
             "--reschedule-window", "15",
             # the judge runs in THIS process with compressed windows;
             # two monitors would flap each other's uid-pinned alerts
             "--no-slo-monitor"]
            + ([] if rescheduler else ["--no-rescheduler"]),
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "operator.log"), "w"),
        )
        fleet = HollowFleet(
            fleet_client, nodes,
            timeline=HollowTimeline(
                pending_s=0.05, run_s=8.0, run_jitter_s=4.0, seed=seed,
                serve_warmup_s=0.3,
                load=ServeLoadModel(capacity_qps=200.0),
                # migrations resume from checkpoint (the operator's
                # contract) — without this every defrag move re-runs the
                # victim's whole clock and the A/B punishes the mover
                checkpoint_resume=True,
            ),
            capacity_chips=4, heartbeat_interval=2.0, clock=clock,
        ).start()
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if len(client.list("Node", NODE_NAMESPACE)) >= nodes:
                    break
            except Exception:
                pass
            time.sleep(0.2)

        TPUServeClient(client, namespace="soak").create({
            "kind": "TPUServe",
            "metadata": {"name": "web", "namespace": "soak"},
            "spec": {
                # whole-node replicas: a single node loss (the reclaim)
                # can cost at most ONE replica, which the budget absorbs
                "replicas": serve_replicas, "workers_per_replica": 1,
                "slice": {"accelerator": "cpu", "chips_per_host": 4},
                "disruption_budget": budget, "max_surge": 1,
                "max_unavailable": 1,
            },
        })

        def serve_ready() -> int:
            s = client.try_get("TPUServe", "soak", "web")
            return s.status.ready_replicas if s else 0

        deadline = time.time() + 60
        while time.time() < deadline and serve_ready() < serve_replicas:
            time.sleep(0.2)
        if serve_ready() < serve_replicas:
            raise RuntimeError("serve never reached full readiness")

        if judge:
            monitor = SLOMonitor(
                client,
                [ScrapeTarget("operator",
                              f"http://127.0.0.1:{mport}/metrics")],
                load_slo_config().scaled(1.0 / 600.0), interval=0.25,
                incident_dir=os.path.join(trace_dir, "incidents"),
            ).start()

        def observe():
            ns_ = client.list("Node", NODE_NAMESPACE)
            ps = [p for p in client.list("Pod") if not p.is_finished()]
            used = GangScheduler._node_used(ps)
            free = [
                max(0, (n.status.capacity_chips or 0)
                    - used.get(n.metadata.name, 0))
                for n in ns_
                if n.status.ready and not n.status.unschedulable
                and ANNOTATION_MAINTENANCE_AT not in n.metadata.annotations
            ]
            return sum(free), max(free or [0]), ps

        min_ready = [serve_replicas]

        def sampler():
            while not sample_stop.is_set():
                t = time.time() - engine_t0[0]
                try:
                    total, contig, ps = observe()
                    r = serve_ready()
                    min_ready[0] = min(min_ready[0], r)
                    pend = set()
                    for p in ps:
                        jn = p.metadata.labels.get("tpujob.dev/job-name")
                        if not jn or p.metadata.namespace != "soak":
                            continue
                        if not p.spec.node_name:
                            pend.add(jn)
                            first_pending.setdefault(jn, t)
                    for jn in list(first_pending):
                        if jn not in pend and jn not in bound_at:
                            bound_at[jn] = t
                    for j in client.list("TPUJob", "soak"):
                        chips_by_job[j.metadata.name] = \
                            j.spec.slice.chips_per_host
                    samples.append({
                        "t": round(t, 1), "free": total,
                        "contig": contig, "ready": r,
                        # a whole-node gang is QUEUED right now: the
                        # window where contiguous capacity is the number
                        # that matters (the A/B gate below)
                        "demand4": any(chips_by_job.get(jn) == 4
                                       for jn in pend),
                    })
                    if judge:
                        for a in client.list("Alert", ALERT_NAMESPACE):
                            if a.is_firing():
                                w = pages.setdefault(
                                    a.metadata.name, [t, t])
                                w[1] = t
                except Exception:
                    pass  # one missed sample must not end the day
                sample_stop.wait(0.2)

        engine_t0 = [time.time()]
        engine = ScenarioEngine(scenario, client, fleet=fleet,
                                clock=clock)
        st = threading.Thread(target=sampler, daemon=True)
        engine.start()
        engine_t0[0] = time.time()
        st.start()
        run_deadline = time.time() + day / scale + 60
        while time.time() < run_deadline and not engine.done():
            time.sleep(0.25)
        out["engine_done"] = engine.done()
        out["engine_errors"] = engine.errors()[:5]

        # drain out: every arrival gang must still finish
        def succeeded() -> int:
            n = 0
            for key in engine.submitted:
                ns_, name = key.split("/", 1)
                j = client.try_get("TPUJob", ns_, name)
                if j is not None and cond.is_succeeded(j.status):
                    n += 1
            return n
        deadline = time.time() + 60
        done = 0
        while time.time() < deadline:
            done = succeeded()
            if done >= len(engine.submitted):
                break
            time.sleep(0.5)
        sample_stop.set()
        st.join(timeout=3)

        jobs_all = client.list("TPUJob", "soak")
        burned = [j.metadata.name for j in jobs_all
                  if (j.status.restart_count or 0) > 0]
        waits = sorted(bound_at[j] - first_pending[j]
                       for j in bound_at if j in first_pending)
        out.update({
            "submitted": len(engine.submitted),
            "succeeded": done,
            "jobs_with_burned_backoff": burned,
            "min_ready_during_day": min_ready[0],
            "budget_violation_windows": sum(
                1 for s in samples if s["ready"] < budget),
            "contig_mean": round(statistics.fmean(
                s["contig"] for s in samples), 2) if samples else 0.0,
            "free_mean": round(statistics.fmean(
                s["free"] for s in samples), 2) if samples else 0.0,
            "queue_wait_p50_s": round(_percentile(waits, 0.5), 2)
            if waits else 0.0,
            "queue_wait_max_s": round(waits[-1], 2) if waits else 0.0,
        })
        # demand-conditioned fragmentation: raw contig means are polluted
        # by occupancy differences between the arms (the rescheduler's
        # own cordons + the unblocked gangs it lets run), so the gate is
        # "while a whole-node gang was queued, how often was the fleet
        # fragmented below it" — the exact window the gauge exists for
        demand = [s for s in samples if s.get("demand4")]
        starved = [s for s in demand if s["contig"] < 4]
        out.update({
            "demand_windows": len(demand),
            "starved_windows": len(starved),
            "starved_fraction": round(len(starved) / len(demand), 3)
            if demand else 0.0,
            "contig_under_demand": round(statistics.fmean(
                s["contig"] for s in demand), 2) if demand else 0.0,
        })

        # --- the pages: each one explained + bundled, or the day fails -
        if judge:
            wave_t = day * 0.35 / scale
            wave_end = wave_t + 600.0 / scale + 30.0
            reclaim_t = day * 0.7 / scale
            explained_windows = [(wave_t - 2.0, wave_end),
                                 (reclaim_t - 2.0, reclaim_t + 30.0)]
            # the scripted fragmentation is itself an explanation for
            # bind-latency pages: a gang the scenario starved binds
            # LATE, and that bind's latency burns the scheduler-bind
            # objective — the page is the antagonist doing its job, not
            # a mystery. Explained iff the sampler actually RECORDED a
            # starved-demand window within the burn horizon before the
            # firing (measured evidence, not a blanket waiver).
            starved_ts = [s["t"] for s in samples
                          if s.get("demand4") and s["contig"] < 4]

            def explained(name: str, first: float) -> bool:
                if any(lo <= first <= hi for lo, hi in explained_windows):
                    return True
                if name == "scheduler-bind":
                    return any(first - 60.0 <= st_ <= first
                               for st_ in starved_ts)
                return False

            unexplained = [
                name for name, (first, _last) in sorted(pages.items())
                if not explained(name, first)
            ]
            bundle_rcs = []
            for name in sorted(pages):
                a = client.try_get("Alert", ALERT_NAMESPACE, name)
                has_bundle = bool(
                    a is not None and a.status.incident
                    and os.path.exists(a.status.incident))
                rc = None
                if has_bundle:
                    import io
                    import contextlib
                    trace.TRACER.flush()
                    with contextlib.redirect_stdout(io.StringIO()):
                        rc = ctl.main(["--store", urls[0], "trace",
                                       "--last-incident",
                                       "--trace-dir", trace_dir])
                bundle_rcs.append({"page": name, "bundle": has_bundle,
                                   "ctl_trace_rc": rc})
            out["pages"] = {n: [round(a, 1), round(b, 1)]
                            for n, (a, b) in sorted(pages.items())}
            out["unexplained_pages"] = unexplained
            out["bundles"] = bundle_rcs
            out["pages_ok"] = bool(
                not unexplained
                and all(b["bundle"] and b["ctl_trace_rc"] == 0
                        for b in bundle_rcs))
        # --- the rescheduler's own numbers, from the REAL /metrics ----
        if rescheduler:
            expo = ""
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/metrics", timeout=10.0
                ) as r:
                    expo = r.read().decode()
            except Exception as e:
                out["metrics_error"] = str(e)
            out["contig_gauge_exported"] = (
                "tpu_operator_schedulable_contiguous_chips" in expo)
            resched_n = 0
            for line in expo.splitlines():
                if line.startswith("tpu_operator_reschedules_total{"):
                    try:
                        resched_n += int(float(line.rsplit(" ", 1)[1]))
                    except ValueError:
                        pass
            out["reschedules_total"] = resched_n

        out["elapsed_s"] = round(time.time() - t0, 1)
        out["ok"] = bool(
            out["engine_done"]
            and not out["engine_errors"]
            and out["submitted"] > 0
            and done >= len(engine.submitted)
            and not burned
            and out["budget_violation_windows"] == 0
            and (not judge or out["pages_ok"])
            and (not rescheduler
                 or (out["contig_gauge_exported"]
                     and out["reschedules_total"] >= 1))
        )
        return out
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        sample_stop.set()
        if monitor is not None:
            monitor.stop()
        if engine is not None:
            engine.stop()
        if fleet is not None:
            fleet.stop()
        for c in (client, fleet_client):
            if c is not None:
                c.close()
        procs = [operator_proc] + list(store_procs.values())
        for proc in procs:
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        trace.TRACER.disable()
        if os.environ.get("BENCH_CP_SOAK_KEEP"):
            print(f"soak dir kept: {tmp}", file=sys.stderr)
        else:
            shutil.rmtree(tmp, ignore_errors=True)


def run_soak_mode(seed: int) -> dict:
    """A day in the life of the fleet (BENCH_CP_MODES=soak, ISSUE 18):
    ONE seeded compressed fleet-day — diurnal serve traffic, two batch
    tenants, a rolling maintenance wave, a zero-warning reclaim — run as
    an A/B against the deployed multi-process shape: once with the
    rescheduler (the SLO plane judging: zero unexplained pages, every
    bundle rendering rc=0, zero burned backoffs, zero budget-violation
    windows) and once with `--no-rescheduler` as the fragmentation
    baseline. The bar the rescheduler must clear, in the same JSON:
    fewer starved windows — samples where a whole-node gang sat queued
    while `schedulable_contiguous_chips` was below its ask — than the
    baseline arm (raw contig means are reported but not gated on: the
    arms run different occupancy, so an unconditioned mean punishes the
    rescheduler for the very gangs it unblocked). The caller runs the
    whole A/B TWICE on one seed (scenario determinism)."""
    with_arm = _soak_arm(seed, rescheduler=True, judge=True)
    base_arm = _soak_arm(seed, rescheduler=False, judge=False)
    delta = round(
        with_arm.get("contig_mean", 0.0) - base_arm.get("contig_mean",
                                                        0.0), 2)
    return {
        "metric": "controlplane_soak",
        "seed": seed,
        "rescheduler": with_arm,
        "baseline": base_arm,
        "contig_mean_delta_chips": delta,
        "ok": bool(with_arm.get("ok") and base_arm.get("ok")
                   and base_arm.get("demand_windows", 0) > 0
                   and with_arm.get("starved_fraction", 1.0)
                   < base_arm.get("starved_fraction", 0.0)),
    }


def run_goodput_mode(seed: int) -> dict:
    """The workload telemetry plane under seeded pathology
    (BENCH_CP_MODES=goodput, ISSUE 15): a hollow fleet runs batch + serve
    while one seeded job suffers an input-pipeline stall and one gang
    hosts a seeded straggler worker; a node drain checkpoint-migrates a
    third gang. Asserted:

    - the stall job's dominant bucket reads ``input`` in its telemetry;
    - the ``goodput-collapse`` burn-rate alert FIRES within its
      documented bound (fast_long + 2 evaluation periods, at the bench's
      compressed window scale) of the gauge first crossing the floor,
      and CLEARS after the stall heals;
    - the Straggler Event names the exact pod and node;
    - ``restart_to_first_step_seconds`` records at least one planned
      MIGRATION outage span (the ROADMAP item 5 baseline).
    """
    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.api.client import TPUJobClient, TPUServeClient
    from mpi_operator_tpu.api.types import ALERT_NAMESPACE
    from mpi_operator_tpu.controller.disruption import DrainController
    from mpi_operator_tpu.controller.goodput import GoodputAggregator
    from mpi_operator_tpu.controller.serve import TPUServeController
    from mpi_operator_tpu.controller.slo_monitor import (
        SLOMonitor,
        load_slo_config,
    )
    from mpi_operator_tpu.executor.hollow import (
        HollowFleet,
        HollowTimeline,
        ServeLoadModel,
        TrainLoadModel,
    )
    from mpi_operator_tpu.machinery.store import ObjectStore
    from mpi_operator_tpu.machinery.telemetry import ScrapeTarget
    from mpi_operator_tpu.opshell import metrics

    window_scale = 1.0 / 300.0  # fast (1s, 12s), slow (6s, 72s), hold 1s
    slo_cfg = load_slo_config().scaled(window_scale)
    floor = slo_cfg.objective("goodput-collapse").bound
    monitor_interval = 0.25
    # the DOCUMENTED detection bound (slo_defaults.json): fast_long + two
    # evaluation periods, measured from the gauge first crossing the floor
    detect_bound_s = slo_cfg.policy.fast[1] + 2 * monitor_interval

    store = ObjectStore()
    recorder = EventRecorder(store)
    train = TrainLoadModel(step_ms=40.0, compile_s=0.4, seed=seed)
    train.set_straggler("bench/skew-worker-1", 2.5)
    load = ServeLoadModel(capacity_qps=100.0)
    load.set_offered("bench/svc", 40.0)
    fleet = HollowFleet(
        store, 6,
        timeline=HollowTimeline(
            run_s=600.0, seed=seed, train=train,
            train_stats_interval_s=0.2,
            serve_warmup_s=0.3, serve_stats_interval_s=0.5, load=load,
        ),
        capacity_chips=8, heartbeat_interval=0.5,
    )
    controller = TPUJobController(store, recorder,
                                  ControllerOptions(threadiness=2))
    serve_ctrl = TPUServeController(store, recorder)
    scheduler = GangScheduler(store, recorder)
    drain = DrainController(store, recorder, interval=0.2)
    agg = GoodputAggregator(store, recorder, interval=0.25)
    monitor = SLOMonitor(store, [ScrapeTarget("bench", "self")], slo_cfg,
                         interval=monitor_interval)
    job_keys = [f"bench/{n}" for n in ("stall", "skew", "mig")]
    mig_before = metrics.restart_to_first_step.count(kind="migration")
    out: Dict[str, Any] = {"metric": "controlplane_goodput", "seed": seed,
                           "ok": False}
    t0 = time.time()
    try:
        controller.run()
        serve_ctrl.run()
        scheduler.start()
        fleet.start()
        drain.start()
        agg.start()
        jc = TPUJobClient(store, namespace="bench")
        for name, workers in (("stall", 2), ("skew", 3), ("mig", 2)):
            jc.create({
                "kind": "TPUJob", "metadata": {"name": name,
                                               "namespace": "bench"},
                "spec": {
                    "slice": {"accelerator": "cpu", "chips_per_host": 1},
                    "worker": {"replicas": workers, "template": {
                        "containers": [{"image": "x",
                                        "command": ["train"]}]}},
                },
            })
        TPUServeClient(store, namespace="bench").create({
            "kind": "TPUServe",
            "metadata": {"name": "svc", "namespace": "bench"},
            "spec": {"replicas": 1, "workers_per_replica": 1,
                     "slice": {"accelerator": "cpu", "chips_per_host": 2}},
        })

        def telemetry(name):
            job = store.try_get("TPUJob", "bench", name)
            return (job.status.train_telemetry or {}) if job else {}

        def wait_for(pred, timeout, what):
            deadline = time.time() + timeout
            while time.time() < deadline:
                if pred():
                    return True
                time.sleep(0.1)
            raise RuntimeError(f"timed out waiting for {what}")

        # --- phase 1: everything healthy and reporting. The monitor
        # starts only once the fleet is past warmup: at the bench's
        # 300x-compressed windows a job's first seconds (compile, no
        # steps yet) dominate fast_long the way they never could at the
        # production 1h window — starting the scrape on a live healthy
        # fleet is also the deployment-normal shape ---
        wait_for(lambda: all(telemetry(n).get("steps", 0) > 0
                             and (telemetry(n).get("goodput") or 0) > floor
                             for n in ("stall", "skew", "mig")),
                 30.0, "all jobs reporting healthy telemetry")
        monitor.start()
        alert_obj = lambda: store.try_get(  # noqa: E731
            "Alert", ALERT_NAMESPACE, "goodput-collapse")
        time.sleep(2.0)  # healthy baseline: no false positive
        a = alert_obj()
        out["false_positive"] = bool(a is not None and a.is_firing())

        # --- phase 2: the seeded input-pipeline stall ---
        train.set_stall("bench/stall", "input", 0.9)
        wait_for(lambda: metrics.job_goodput_ratio.get(
            job="bench/stall") < floor, 30.0, "goodput below the floor")
        breach_at = time.time()
        wait_for(lambda: (a := alert_obj()) is not None and a.is_firing(),
                 detect_bound_s + 5.0, "goodput-collapse firing")
        fired_at = time.time()
        out["detect_s"] = round(fired_at - breach_at, 2)
        out["detect_bound_s"] = round(detect_bound_s, 2)
        out["dominant_stall"] = telemetry("stall").get("dominant_stall")
        out["stall_goodput"] = telemetry("stall").get("goodput")

        # --- phase 3: heal; the alert must clear ---
        train.clear_stall("bench/stall")
        wait_for(lambda: (a := alert_obj()) is not None
                 and not a.is_firing(), 60.0, "goodput-collapse clearing")
        out["clear_s"] = round(time.time() - fired_at, 2)

        # --- the straggler (seeded from t=0) ---
        strag = telemetry("skew").get("straggler", "")
        pod = store.try_get("Pod", "bench", "skew-worker-1")
        node = pod.spec.node_name if pod else ""
        evs = [e for e in store.list("Event")
               if e.reason == "Straggler" and "skew-worker-1" in e.message
               and node and node in e.message]
        out["straggler"] = strag
        out["straggler_event"] = bool(evs)

        # --- phase 4: drain the node hosting mig's coordinator ---
        mig_pod = store.get("Pod", "bench", "mig-worker-0")
        victim = mig_pod.spec.node_name
        out["drained_node"] = victim
        fleet.announce_maintenance(victim, time.time() + 20.0)
        wait_for(
            lambda: metrics.restart_to_first_step.count(
                kind="migration") > mig_before,
            40.0, "restart_to_first_step recorded for the migration",
        )
        snap = metrics.restart_to_first_step.snapshot(kind="migration")
        # mean outage span of this run's migrations (sum/count delta is
        # overkill for one seeded migration; count delta asserted above)
        out["restart_to_first_step_count"] = int(
            metrics.restart_to_first_step.count(kind="migration")
            - mig_before)
        out["restart_to_first_step_p50_s"] = round(
            metrics.histogram_quantile(0.5, snap), 2)
        wait_for(lambda: not cond.is_finished(
            store.get("TPUJob", "bench", "mig").status)
            and telemetry("mig").get("steps", 0) > 0,
            20.0, "migrated gang stepping again")
        out["mig_generation"] = store.get(
            "TPUJob", "bench", "mig").status.restart_generation
        out["mig_restart_count"] = store.get(
            "TPUJob", "bench", "mig").status.restart_count

        out["elapsed_s"] = round(time.time() - t0, 1)
        out["ok"] = bool(
            not out["false_positive"]
            and out["dominant_stall"] == "input"
            and out["detect_s"] <= detect_bound_s
            and strag.startswith("bench/skew-worker-1@")
            and out["straggler_event"]
            and out["restart_to_first_step_count"] >= 1
            and out["mig_generation"] >= 1
            and out["mig_restart_count"] == 0  # the migration was FREE
        )
        return out
    finally:
        monitor.stop()
        agg.stop()
        drain.stop()
        scheduler.stop()
        serve_ctrl.stop()
        controller.stop()
        fleet.stop()
        # the registry is process-global and this mode runs TWICE: run 1's
        # per-job gauges must not leak a stale collapsed value into run
        # 2's scrape (a counter-reset false alert)
        for key in job_keys:
            metrics.job_goodput_ratio.remove(job=key)
            metrics.job_stragglers.remove(job=key)


def run_goodput_llama() -> dict:
    """The REAL (non-hollow) half of the goodput acceptance: a short
    llama gang on the local executor with stepstats enabled end to end —
    train_stats mirrored into pod status, measured stepstats overhead
    <= 2% of step p50, and the `ctl profile` round trip (stamp → workers
    capture a jax.profiler trace → --status → --fetch rc=0)."""
    import io
    import contextlib
    import shutil

    from mpi_operator_tpu.api.client import TPUJobClient
    from mpi_operator_tpu.api import conditions as cond
    from mpi_operator_tpu.controller.goodput import GoodputAggregator
    from mpi_operator_tpu.executor.local import LocalExecutor
    from mpi_operator_tpu.opshell import ctl
    from mpi_operator_tpu.runtime.stepstats import StepStatsRecorder

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench-goodput-llama-")
    ckpt = os.path.join(tmp, "ckpt")
    db = os.path.join(tmp, "store.db")
    store = SqliteStore(db, poll_interval=0.02)
    spec = f"sqlite:{db}"
    recorder = EventRecorder(store)
    controller = TPUJobController(store, recorder,
                                  ControllerOptions(threadiness=2))
    scheduler = GangScheduler(store, recorder)
    executor = LocalExecutor(store, workdir=repo, require_binding=True,
                             stepstats_poll=0.5)
    agg = GoodputAggregator(store, recorder, interval=0.5)
    out: Dict[str, Any] = {"metric": "goodput_llama", "ok": False}
    t0 = time.time()
    steps_total = int(os.environ.get("BENCH_CP_GOODPUT_LLAMA_STEPS", "80"))
    try:
        controller.run()
        scheduler.start()
        executor.start()
        agg.start()
        jc = TPUJobClient(store)
        jc.create({
            "kind": "TPUJob", "metadata": {"name": "llama"},
            "spec": {
                "slice": {"accelerator": "cpu", "chips_per_host": 1},
                "run_policy": {"backoff_limit": 2},
                "worker": {
                    "replicas": 2, "restart_policy": "ExitCode",
                    "template": {"containers": [{
                        "image": "local",
                        "command": ["python", "examples/llama_worker.py"],
                        "env": [
                            {"name": "LLAMA_CONFIG", "value": "tiny"},
                            {"name": "LLAMA_BATCH", "value": "2"},
                            {"name": "LLAMA_SEQ", "value": "32"},
                            {"name": "LLAMA_STEPS",
                             "value": str(steps_total)},
                            {"name": "LLAMA_CKPT", "value": ckpt},
                            {"name": "LLAMA_SAVE_EVERY", "value": "40"},
                            {"name": "LLAMA_CHECK_EVERY", "value": "5"},
                            {"name": "LLAMA_STEP_SLEEP", "value": "0.05"},
                        ],
                    }]},
                },
            },
        })

        def coord_stats():
            p = store.try_get("Pod", "default", "llama-worker-0")
            return (p.status.train_stats or {}) if p else {}

        def wait_for(pred, timeout, what):
            deadline = time.time() + timeout
            while time.time() < deadline:
                if pred():
                    return True
                time.sleep(0.25)
            raise RuntimeError(f"timed out waiting for {what}")

        # real training is stepping AND its stats are mirrored
        wait_for(lambda: coord_stats().get("steps", 0) >= 5, 180.0,
                 "llama train_stats in pod status")

        # --- the profile round trip, through the REAL ctl verbs ---
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = ctl.main(["--store", spec, "profile", "llama",
                           "--steps", "3"])
        out["profile_request_rc"] = rc

        def profile_done():
            with contextlib.redirect_stdout(io.StringIO()):
                return ctl.main(["--store", spec, "profile", "llama",
                                 "--status"]) == 0
        wait_for(profile_done, 120.0, "profile capture acked done")
        prof = coord_stats().get("profile") or {}
        trace_files = []
        if prof.get("dir") and os.path.isdir(prof["dir"]):
            for root, _dirs, files in os.walk(prof["dir"]):
                trace_files += [os.path.join(root, f) for f in files]
        out["trace_files"] = len(trace_files)
        dest = os.path.join(tmp, "fetched")
        with contextlib.redirect_stdout(io.StringIO()):
            out["profile_fetch_rc"] = ctl.main([
                "--store", spec, "profile", "llama", "--fetch",
                "--dest", dest])
        out["fetched_files"] = sum(
            len(fs) for _r, _d, fs in os.walk(dest))

        # the gang must still FINISH (profiling never perturbs outcome)
        wait_for(lambda: cond.is_finished(
            store.get("TPUJob", "default", "llama").status), 240.0,
            "llama job finishing")
        job = store.get("TPUJob", "default", "llama")
        out["succeeded"] = cond.is_succeeded(job.status)
        tel = job.status.train_telemetry or {}
        out["goodput"] = tel.get("goodput")
        out["buckets"] = tel.get("buckets")
        step_p50_ms = float(coord_stats().get("step_p50_ms", 0.0) or 0.0)
        out["step_p50_ms"] = step_p50_ms

        # --- stepstats overhead: the measured per-step recorder cost
        # (the exact call sequence the elastic loop pays: three phases +
        # step_done, flush cadence included) against the REAL step p50 ---
        rec = StepStatsRecorder(os.path.join(tmp, "bench.stats.json"),
                                interval=1.0)
        n = 4000
        t_bench = time.perf_counter()
        for i in range(n):
            with rec.phase("input"):
                pass
            with rec.phase("compute"):
                pass
            with rec.phase("sync"):
                pass
            rec.step_done(i)
        per_step_us = (time.perf_counter() - t_bench) / n * 1e6
        out["stepstats_cost_us_per_step"] = round(per_step_us, 1)
        out["stepstats_overhead_pct"] = round(
            per_step_us / 1e3 / max(1e-9, step_p50_ms) * 100.0, 3)

        out["elapsed_s"] = round(time.time() - t0, 1)
        out["ok"] = bool(
            out["succeeded"]
            and out["profile_request_rc"] == 0
            and out["trace_files"] > 0
            and out["profile_fetch_rc"] == 0
            and out["fetched_files"] > 0
            and step_p50_ms > 0
            and out["stepstats_overhead_pct"] <= 2.0
            and (out["goodput"] or 0) > 0
        )
        return out
    finally:
        agg.stop()
        executor.stop()
        scheduler.stop()
        controller.stop()
        store.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_slo_overhead(jobs: int, pods: int, rounds: int) -> dict:
    """The monitor-tax bound (half of BENCH_CP_MODES=slo): interleaved
    off/on informer reconcile storms — 'on' runs a live SLOMonitor at a
    DENSE 0.25s scrape+evaluate cadence against this process's real
    /metrics endpoint (the registry the storm's controller is writing
    into), 60× denser than the production 15s default — best-of-two per
    mode so run-to-run drift cancels. Acceptance (ISSUE 13): the
    monitor's scrape overhead stays ≤2% of reconcile p50 even at that
    cadence."""
    from mpi_operator_tpu.controller.slo_monitor import (
        SLOMonitor,
        load_slo_config,
    )
    from mpi_operator_tpu.machinery.store import ObjectStore
    from mpi_operator_tpu.machinery.telemetry import ScrapeTarget
    from mpi_operator_tpu.opshell.server import OpsServer

    ops = OpsServer(0)
    ops.start()
    results = {"off": [], "on": []}
    monitor = None
    try:
        for _ in range(2):
            results["off"].append(run_mode("informer", jobs, pods, rounds))
            monitor = SLOMonitor(
                ObjectStore(),
                [ScrapeTarget("operator",
                              f"http://127.0.0.1:{ops.port}/metrics")],
                load_slo_config(), interval=0.25,
            ).start()
            try:
                results["on"].append(
                    run_mode("informer", jobs, pods, rounds))
            finally:
                monitor.stop()
    finally:
        ops.stop()
    off = min(results["off"], key=lambda r: r["sync_p50_ms"])
    on = min(results["on"], key=lambda r: r["sync_p50_ms"])
    pct = round((on["sync_p50_ms"] - off["sync_p50_ms"])
                / max(1e-9, off["sync_p50_ms"]) * 100.0, 1)
    return {
        "metric": "controlplane_slo_overhead",
        "jobs": jobs, "pods_per_job": pods, "rounds": rounds,
        "scrape_interval_s": 0.25,
        "sync_p50_ms_monitor_off": off["sync_p50_ms"],
        "sync_p50_ms_monitor_on": on["sync_p50_ms"],
        "p50_overhead_pct": pct,
        "overhead_ok": bool(pct <= 2.0),
    }


def run_slo_detection(seed: int) -> dict:
    """The detection e2e (BENCH_CP_MODES=slo, ISSUE 13): the deployed
    shape — a `tpu-store` process and a hollow-fleet process (both
    exporting /metrics via --monitoring-port), the operator plane in
    THIS process behind a ChaosProxy on its store seam, and the SLO
    monitor scraping all three over real HTTP with compressed burn
    windows (scale 1/600: fast 0.5s/6s, slow 3s/36s).

    A seeded chaos fault — 0.6s injected store latency for 10s, the
    'store seam degraded' incident — must blow the reconcile-latency
    objective past its 1s good-event bound; the bar:

    - NO false positive during the clean baseline;
    - the matching alert FIRES within the documented detection bound
      (fast_long + 2 evaluation periods + scrape slack);
    - it CLEARS after the heal within the clear bound (windows drain +
      clean hold);
    - the firing carries a flight-recorder bundle and
      `ctl trace --last-incident` renders it rc=0.

    The caller runs this TWICE on one seed (chaos determinism)."""
    import io
    import contextlib
    import shutil
    import subprocess
    import threading

    from mpi_operator_tpu.controller.slo_monitor import (
        SLOMonitor,
        load_slo_config,
    )
    from mpi_operator_tpu.machinery import trace
    from mpi_operator_tpu.machinery.chaos import (
        ChaosController,
        ChaosProxy,
        ChaosScript,
    )
    from mpi_operator_tpu.machinery.replica_wire import free_ports
    from mpi_operator_tpu.machinery.telemetry import ScrapeTarget
    from mpi_operator_tpu.opshell import ctl
    from mpi_operator_tpu.opshell.server import OpsServer

    nodes = int(os.environ.get("BENCH_CP_SLO_NODES", "16"))
    fault_s = float(os.environ.get("BENCH_CP_SLO_FAULT_S", "10"))
    delay_s = 0.6
    scale = 1.0 / 600.0
    config = load_slo_config().scaled(scale)
    interval = 0.25
    # the documented detection-latency bound: the fast pair's LONG
    # window must fill past the burn threshold, plus two evaluation
    # periods and scrape slack
    detect_bound_s = config.policy.fast[1] + 2 * interval + 2.0
    # the clear bound: every window drains the breach, then the hold
    clear_bound_s = (config.policy.slow[1] + config.policy.clear_hold_s
                     + 2 * interval + 4.0)

    tmp = tempfile.mkdtemp(prefix="bench-cp-slo-")
    trace_dir = os.path.join(tmp, "traces")
    incident_dir = os.path.join(tmp, "incidents")
    trace.TRACER.configure("bench-slo", dir=trace_dir)
    ports = free_ports(3)
    store_port, store_mon, fleet_mon = ports
    store_url = f"http://127.0.0.1:{store_port}"
    env = dict(os.environ, PYTHONPATH=os.path.dirname(
        os.path.abspath(__file__)), TPUJOB_TRACE_DIR=trace_dir)
    out: dict = {"metric": "controlplane_slo_detection", "seed": seed,
                 "nodes": nodes, "ok": False,
                 "detect_bound_s": round(detect_bound_s, 1),
                 "clear_bound_s": round(clear_bound_s, 1)}
    store_proc = fleet_proc = None
    proxy = None
    monitor = None
    cache = controller = None
    ops = None
    clients = []
    stop = threading.Event()
    try:
        store_proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.machinery.http_store",
             "--store", f"sqlite:{os.path.join(tmp, 'store.db')}",
             "--listen", f"127.0.0.1:{store_port}",
             "--log-capacity", "16384",
             "--monitoring-port", str(store_mon)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "store.log"), "w"),
        )
        direct = HttpStoreClient(store_url, timeout=30.0,
                                 conn_refused_retries=20,
                                 watch_poll_timeout=2.0)
        clients.append(direct)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                direct.list("Node")
                break
            except Exception:
                time.sleep(0.2)
        fleet_proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_operator_tpu.executor.hollow",
             "--store", store_url, "--nodes", str(nodes),
             "--chips", "8", "--run-s", "0.2", "--heartbeat", "5",
             "--seed", str(seed), "--monitoring-port", str(fleet_mon)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "fleet.log"), "w"),
        )
        # the operator plane reaches the store THROUGH the chaos seam:
        # injected latency lands exactly where a degraded store would
        proxy = ChaosProxy(store_url, seed=seed).start()
        pclient = HttpStoreClient(proxy.url, timeout=60.0,
                                  conn_refused_retries=20,
                                  watch_poll_timeout=2.0)
        clients.append(pclient)
        cache = InformerCache(pclient).start()
        if not cache.wait_for_sync(30.0):
            raise RuntimeError("informer cache never synced")
        recorder = EventRecorder(pclient)
        controller = TPUJobController(
            pclient, recorder, ControllerOptions(threadiness=4),
            cache=cache)
        scheduler = GangScheduler(pclient, recorder, cache=cache)
        deadline = time.time() + 30
        while time.time() < deadline:
            if len(cache.list("Node")) >= nodes:
                break
            time.sleep(0.2)
        controller.run()

        def sched_loop():
            while not stop.is_set():
                try:
                    scheduler.sync()
                except Exception:
                    pass  # transient conflicts; next pass heals
                stop.wait(0.2)

        threading.Thread(target=sched_loop, daemon=True).start()

        # a steady job stream keeps reconcile events flowing through
        # every phase (burn windows need event mass to judge)
        submitted = [0]

        def job_stream():
            while not stop.is_set():
                try:
                    direct.create(_make_job(submitted[0], 1, clean="All"))
                    submitted[0] += 1
                except Exception:
                    pass  # the stream is load, not the bar
                stop.wait(0.3)

        threading.Thread(target=job_stream, daemon=True).start()

        # the monitor: scrapes operator (this process, over real HTTP),
        # store, fleet; writes alerts through the DIRECT client — the
        # alerting plane must not ride the seam it is alerting about
        ops = OpsServer(0)
        ops.start()
        monitor = SLOMonitor(
            direct,
            [ScrapeTarget("operator",
                          f"http://127.0.0.1:{ops.port}/metrics"),
             ScrapeTarget("store",
                          f"http://127.0.0.1:{store_mon}/metrics"),
             ScrapeTarget("fleet",
                          f"http://127.0.0.1:{fleet_mon}/metrics")],
            config, interval=interval, incident_dir=incident_dir,
        ).start()

        def firing_alerts():
            try:
                return sorted(
                    a.metadata.name for a in direct.list(
                        "Alert", "monitoring")
                    if a.is_firing()
                )
            except Exception:
                return []

        # --- clean baseline: no false positives --------------------------
        baseline_s = max(8.0, config.policy.slow[0] + 2.0)
        t0 = time.time()
        false_positives = set()
        while time.time() - t0 < baseline_s:
            false_positives.update(firing_alerts())
            time.sleep(0.5)
        out["false_positives"] = sorted(false_positives)

        # --- the seeded fault --------------------------------------------
        script = ChaosScript.parse({
            "seed": seed,
            "actions": [{"at": 0.0, "fault": "delay",
                         "seconds": delay_s, "duration": fault_s}],
        })
        fault_at = time.time()
        chaos = ChaosController(script, proxy=proxy).arm()
        fired_at = None
        deadline = fault_at + detect_bound_s + 2.0
        while time.time() < deadline and fired_at is None:
            if "reconcile-latency" in firing_alerts():
                fired_at = time.time()
            time.sleep(0.2)
        chaos.join(5.0)
        out["fired"] = fired_at is not None
        out["detection_s"] = (round(fired_at - fault_at, 2)
                              if fired_at else None)
        out["also_firing"] = [n for n in firing_alerts()
                              if n != "reconcile-latency"]
        if fired_at is None:
            out["error"] = "alert never fired"
            return out
        alert = direct.get("Alert", "monitoring", "reconcile-latency")
        out["window"] = alert.status.window
        out["burn"] = alert.status.burn
        bundle = alert.status.incident
        out["bundle_ok"] = bool(bundle and os.path.exists(bundle))

        # --- heal: the alert must clear --------------------------------
        heal_at = fault_at + fault_s
        resolved_at = None
        deadline = heal_at + clear_bound_s + 10.0
        while time.time() < deadline and resolved_at is None:
            if "reconcile-latency" not in firing_alerts():
                a = direct.get("Alert", "monitoring", "reconcile-latency")
                if a.status.state == "Resolved":
                    resolved_at = time.time()
                    break
            time.sleep(0.25)
        out["resolved"] = resolved_at is not None
        out["clear_s"] = (round(resolved_at - heal_at, 2)
                          if resolved_at else None)

        # --- ctl renders the incident (bundle linked) rc=0 --------------
        trace.TRACER.flush()
        old_inc = os.environ.get("TPUJOB_INCIDENT_DIR")
        os.environ["TPUJOB_INCIDENT_DIR"] = incident_dir
        try:
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = ctl.main(["--store", store_url, "trace",
                               "--last-incident", "--trace-dir", trace_dir])
        finally:
            if old_inc is None:
                os.environ.pop("TPUJOB_INCIDENT_DIR", None)
            else:
                os.environ["TPUJOB_INCIDENT_DIR"] = old_inc
        out["ctl_trace_rc"] = rc
        out["ctl_trace_links_bundle"] = "incident bundle:" in buf.getvalue()
        out["jobs_submitted"] = submitted[0]
        out["ok"] = bool(
            not false_positives
            and out["fired"]
            and out["detection_s"] <= detect_bound_s
            and out["bundle_ok"]
            and out["resolved"]
            and out["clear_s"] <= clear_bound_s
            and rc == 0
            and out["ctl_trace_links_bundle"]
        )
        return out
    finally:
        stop.set()
        if monitor is not None:
            monitor.stop()
        if controller is not None:
            controller.stop()
        if cache is not None:
            cache.stop()
        if ops is not None:
            ops.stop()
        for c in clients:
            c.close()
        if proxy is not None:
            proxy.stop()
        for proc in (fleet_proc, store_proc):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        trace.TRACER.disable()
        if os.environ.get("BENCH_CP_SLO_KEEP"):
            print(f"slo dir kept: {tmp}", file=sys.stderr)
        else:
            shutil.rmtree(tmp, ignore_errors=True)


def run_fanout_mode() -> dict:
    """The O(events) fan-out proof (BENCH_CP_MODES=fanout): a fixed event
    stream delivered to 10 vs ``BENCH_CP_FANOUT_WATCHERS`` (default 500)
    long-poll watchers, with the per-event wire bytes PREENCODED at append
    (the new path) vs re-encoded per watcher (preencode=False, the old
    path). Measured: server-side encode+assembly wall time from
    http_store.watch_encode_stats. Acceptance: growing watchers 10→500
    raises the preencoded cost <2× while the legacy path grows ~linearly
    with watchers (~50×)."""
    import json as _json
    import threading
    import urllib.request

    from mpi_operator_tpu.machinery.http_store import (
        reset_watch_encode_stats,
        watch_encode_stats,
    )
    from mpi_operator_tpu.machinery.objects import Pod
    from mpi_operator_tpu.machinery.store import ObjectStore

    events = int(os.environ.get("BENCH_CP_FANOUT_EVENTS", "200"))
    big = int(os.environ.get("BENCH_CP_FANOUT_WATCHERS", "500"))

    def drive(preencode: bool, watchers: int) -> dict:
        server = StoreServer(ObjectStore(), "127.0.0.1", 0,
                             log_capacity=events * 2 + 64,
                             preencode=preencode).start()
        stop = threading.Event()
        seen = [0] * watchers
        registered = [False] * watchers

        def watcher(i: int) -> None:
            base = f"http://127.0.0.1:{server.port}/v1/watch"
            try:
                with urllib.request.urlopen(base + "?after=-1",
                                            timeout=30) as r:
                    reg = _json.loads(r.read())
                cursor, inst = reg["next"], reg["instance"]
                registered[i] = True
                while not stop.is_set() and seen[i] < events:
                    with urllib.request.urlopen(
                        f"{base}?after={cursor}&timeout=5&instance={inst}",
                        timeout=20,
                    ) as r:
                        payload = _json.loads(r.read())
                    cursor = payload.get("next", cursor)
                    seen[i] += len(payload.get("events", []))
            except Exception:
                registered[i] = True  # do not wedge the start barrier
                # a dead watcher just stops counting

        threads = [threading.Thread(target=watcher, args=(i,), daemon=True)
                   for i in range(watchers)]
        for t in threads:
            t.start()
        # every watcher must be REGISTERED before the event stream starts:
        # registration hands the current head, so late registrants would
        # silently miss early events and the drain below would never end
        deadline = time.time() + 60
        while time.time() < deadline and not all(registered):
            time.sleep(0.05)
        reset_watch_encode_stats()
        cpu0 = time.process_time()
        writer = HttpStoreClient(server.url, timeout=30.0)
        for i in range(events):
            writer.create(Pod(metadata=ObjectMeta(
                name=f"f-{i:05d}", namespace="bench")))
        # drain until everyone saw everything, or delivery plateaus
        deadline = time.time() + 60 + watchers * 0.1
        last_total, last_change = -1, time.time()
        while time.time() < deadline and min(seen) < events:
            total = sum(seen)
            if total != last_total:
                last_total, last_change = total, time.time()
            elif time.time() - last_change > 10.0:
                break  # plateaued (some watcher died); report what landed
            time.sleep(0.05)
        stats = watch_encode_stats()
        cpu = time.process_time() - cpu0
        stop.set()
        writer.close()
        server.stop()
        for t in threads:
            t.join(timeout=2.0)
        return {
            "watchers": watchers,
            "delivered_min": min(seen),
            "encode_s": round(stats["encode_s"], 4),
            "assembly_s": round(stats["assembly_s"], 4),
            "events_encoded": stats["events_encoded"],
            "payloads": stats["payloads"],
            "process_cpu_s": round(cpu, 3),
        }

    out = {"metric": "controlplane_watch_fanout", "events": events}
    for label, pre in (("preencoded", True), ("reencode", False)):
        small = drive(pre, 10)
        large = drive(pre, big)
        ratio = large["encode_s"] / max(1e-9, small["encode_s"])
        out[label] = {
            "w10": small, f"w{big}": large,
            "encode_cost_ratio": round(ratio, 2),
        }
    out["fanout_is_o_events"] = bool(
        out["preencoded"]["encode_cost_ratio"] < 2.0
    )
    return out


def main() -> None:
    jobs = int(os.environ.get("BENCH_CP_JOBS", "200"))
    pods = int(os.environ.get("BENCH_CP_PODS", "8"))
    rounds = int(os.environ.get("BENCH_CP_ROUNDS", "3"))
    agents = int(os.environ.get("BENCH_CP_AGENTS", "16"))
    writes = int(os.environ.get("BENCH_CP_WRITES", "400"))
    modes = os.environ.get("BENCH_CP_MODES", "store,informer").split(",")
    results = {}
    for mode in modes:
        mode = mode.strip()
        if mode == "write":
            r = run_write_mode(jobs, pods, agents)
        elif mode == "replica":
            r = run_replica_mode(writes)
        elif mode == "hist":
            r = run_hist_mode(writes)
        elif mode == "traceoverhead":
            r = run_trace_overhead(jobs, pods, rounds)
        elif mode == "scale":
            r = run_scale_mode(
                int(os.environ.get("BENCH_CP_SCALE_NODES", "1000")),
                int(os.environ.get("BENCH_CP_SCALE_JOBS", "10000")),
                int(os.environ.get("BENCH_CP_SCALE_PODS", "1")),
            )
        elif mode == "torture":
            # TWO runs on ONE seed: the chaos determinism contract — the
            # bar must hold both times, not once by luck
            seed = int(os.environ.get("BENCH_CP_TORTURE_SEED", "1207"))
            nodes_t = int(os.environ.get("BENCH_CP_TORTURE_NODES", "100"))
            jobs_t = int(os.environ.get("BENCH_CP_TORTURE_JOBS", "500"))
            runs = [
                run_torture_mode(nodes_t, jobs_t, 1, seed)
                for _ in range(int(os.environ.get(
                    "BENCH_CP_TORTURE_RUNS", "2")))
            ]
            r = {
                "metric": "controlplane_torture",
                "seed": seed,
                "runs": runs,
                "ok": all(x.get("ok") for x in runs),
            }
        elif mode == "soak":
            # the whole A/B TWICE on ONE seed (scenario determinism):
            # the compressed day's bar must hold both times, not once by
            # luck (ISSUE 18 acceptance)
            seed = int(os.environ.get("BENCH_CP_SOAK_SEED", "1807"))
            runs = [
                run_soak_mode(seed)
                for _ in range(int(os.environ.get("BENCH_CP_SOAK_RUNS",
                                                  "2")))
            ]
            r = {
                "metric": "controlplane_soak",
                "seed": seed,
                "runs": runs,
                "ok": all(x.get("ok") for x in runs),
            }
        elif mode == "serve":
            r = run_serve_mode()
        elif mode == "drain":
            # TWO runs on ONE seed (the chaos determinism contract): the
            # rolling-maintenance bar must hold both times, not once by
            # luck (ISSUE 14 acceptance → BENCH_CP_r14.json)
            seed = int(os.environ.get("BENCH_CP_DRAIN_SEED", "1407"))
            runs = [
                run_drain_mode(seed)
                for _ in range(int(os.environ.get("BENCH_CP_DRAIN_RUNS",
                                                  "2")))
            ]
            r = {
                "metric": "controlplane_drain",
                "seed": seed,
                "runs": runs,
                "ok": all(x.get("ok") for x in runs),
            }
        elif mode == "slo":
            # TWO detection runs on ONE seed (chaos determinism) + the
            # monitor-overhead A/B, one verdict (ISSUE 13 acceptance)
            seed = int(os.environ.get("BENCH_CP_SLO_SEED", "1307"))
            overhead = run_slo_overhead(
                int(os.environ.get("BENCH_CP_SLO_OVERHEAD_JOBS", "100")),
                4, 2)
            runs = [
                run_slo_detection(seed)
                for _ in range(int(os.environ.get("BENCH_CP_SLO_RUNS",
                                                  "2")))
            ]
            r = {
                "metric": "controlplane_slo",
                "seed": seed,
                "overhead": overhead,
                "runs": runs,
                "ok": bool(overhead["overhead_ok"]
                           and all(x.get("ok") for x in runs)),
            }
        elif mode == "goodput":
            # TWO seeded hollow runs (the chaos determinism contract) +
            # ONE real llama run (overhead + profile round trip), one
            # verdict (ISSUE 15 acceptance → BENCH_CP_r15.json)
            seed = int(os.environ.get("BENCH_CP_GOODPUT_SEED", "1507"))
            runs = [
                run_goodput_mode(seed)
                for _ in range(int(os.environ.get(
                    "BENCH_CP_GOODPUT_RUNS", "2")))
            ]
            llama = run_goodput_llama()
            r = {
                "metric": "controlplane_goodput",
                "seed": seed,
                "runs": runs,
                "llama": llama,
                "ok": bool(all(x.get("ok") for x in runs)
                           and llama.get("ok")),
            }
        elif mode == "fanout":
            r = run_fanout_mode()
        else:
            r = run_mode(mode, jobs, pods, rounds)
        results[mode] = r
        print(json.dumps(r), flush=True)
    if "store" in results and "informer" in results:
        s, i = results["store"], results["informer"]
        print(json.dumps({
            "metric": "controlplane_informer_speedup",
            "jobs": jobs,
            "pods_per_job": pods,
            "p50_speedup": round(
                s["sync_p50_ms"] / max(1e-9, i["sync_p50_ms"]), 2
            ),
            "p99_speedup": round(
                s["sync_p99_ms"] / max(1e-9, i["sync_p99_ms"]), 2
            ),
            "read_qps_store_mode": s["store_read_qps"],
            "read_qps_informer_mode": i["store_read_qps"],
        }), flush=True)


if __name__ == "__main__":
    main()
