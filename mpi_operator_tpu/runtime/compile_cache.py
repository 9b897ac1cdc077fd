"""Persistent XLA compilation cache: warm restarts skip the warmup
(the workload speed layer, ISSUE 16).

Every recovery path the operator optimizes — gang restart, elastic
rescale, checkpoint-then-migrate, autoscaler cold start — relaunches the
worker process, and the relaunched process repays the full trace+compile
warmup before its first step. The program being compiled is
byte-identical across incarnations: same model, same mesh, same jax.
jax's persistent compilation cache turns that repayment into a disk
read, IF the cache directory is the same one every time — the directory
is part of jax's cache key, so a directory that moves never hits.

Where the cache lives is decided in ONE place, :func:`configure_from_env`,
called by the worker bootstrap (runtime/bootstrap.initialize) before
anything of the job compiles:

- ``$JAX_COMPILATION_CACHE_DIR`` set — jax read it at import; this module
  sets no other directory and appends nothing to it. That is how a
  deployment (or a benchmark driver) places the cache from outside: the
  executor passes its own environment through to every worker.
- unset — :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored path inside
  the checkout. Never a temporary directory, a pid or a time.
- the job's ``spec.compile_cache: false`` (projected by the controller as
  ``$TPUJOB_COMPILE_CACHE=0``) turns caching off for that job.

Nothing here may initialize a jax backend: multi-host workers configure
the cache BEFORE ``jax.distributed.initialize``, which refuses to run
once a backend exists. jax keys every entry by its version, backend and
compile options already, so one directory serves every job safely.

The hit/miss listener lets the telemetry plane tell a warm restart from
a cold one: :func:`cache_stats` rides the ``compile_cache`` field of the
bounded train_stats blob (machinery/objects.py) into
``pod.status.train_stats``.

Failure modes, by design of jax's cache (verified in
tests/test_compile_cache.py):

- a corrupted/truncated entry is a WARNING + cache miss + fresh compile,
  never a crashed step loop (jax re-writes the entry);
- an unwritable dir degrades to no caching (jax warns), same contract as
  a full disk on the stepstats flush.

``python -m mpi_operator_tpu.runtime.compile_cache --smoke`` is the <30s
verify-gate check: one tiny jitted workload run twice (two processes,
one cache dir) — the second run must report cache HITS and its
stall-attributed ``compile`` bucket must collapse.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Dict, Mapping, Optional

# jax's own variable: read by jax at import, passed through by the executor
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# the controller's projection of spec.compile_cache ("1"/"0")
ENV_CACHE_ENABLED = "TPUJOB_COMPILE_CACHE"
# where the cache lives when nobody placed it from outside (.gitignore
# lists it)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)

# jax's cache-event names (jax._src.monitoring); stable since 0.4.x
_EVENT_HIT = "/jax/compilation_cache/cache_hits"
_EVENT_MISS = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_configured_dir: Optional[str] = None
_listener_installed = False
_counts = {"hits": 0, "misses": 0}


def _on_event(event: str, **_kw) -> None:
    if event == _EVENT_HIT:
        _counts["hits"] += 1
    elif event == _EVENT_MISS:
        _counts["misses"] += 1


def configure_from_env(env: Optional[Mapping[str, str]] = None
                       ) -> Optional[str]:
    """Turn the persistent compilation cache on for this process and
    start counting hits/misses. Returns the cache directory, or None when
    the job opted out (``$TPUJOB_COMPILE_CACHE=0``). Idempotent, and
    initializes no backend (see the module docstring)."""
    global _configured_dir, _listener_installed
    import jax

    env = os.environ if env is None else env
    with _lock:
        if env.get(ENV_CACHE_ENABLED, "1") == "0":
            jax.config.update("jax_compilation_cache_dir", None)
            _configured_dir = None
            return None
        # what jax read from $JAX_COMPILATION_CACHE_DIR at import
        cache_dir = jax.config.jax_compilation_cache_dir
        if not cache_dir:
            cache_dir = DEFAULT_CACHE_DIR
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # cache EVERYTHING: the default thresholds skip small/fast
        # compiles, but the restart warmup this exists to kill is the sum
        # of many entries — and a tiny CPU twin would never cross the
        # default 1s floor at all
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        if not _listener_installed:
            from jax._src import monitoring

            monitoring.register_event_listener(_on_event)
            _listener_installed = True
        _configured_dir = cache_dir
    return cache_dir


def is_configured() -> bool:
    return _configured_dir is not None


def cache_dir() -> Optional[str]:
    return _configured_dir


def cache_stats() -> Dict[str, int]:
    """Cumulative hit/miss counts for THIS process (one incarnation —
    the same reset-on-relaunch contract as the stepstats buckets). A
    warm restart shows hits ≈ entries, misses ≈ 0; a cold start is the
    inverse. Rides the train_stats blob's ``compile_cache`` field."""
    return {"hits": _counts["hits"], "misses": _counts["misses"]}


def _reset_for_tests() -> None:
    global _configured_dir
    import jax

    with _lock:
        jax.config.update("jax_compilation_cache_dir", None)
        _configured_dir = None
        _counts["hits"] = 0
        _counts["misses"] = 0


# ---------------------------------------------------------------------------
# the verify-gate smoke
# ---------------------------------------------------------------------------

# the child workload: a tiny jitted train-ish step under a
# StepStatsRecorder, so "the compile bucket collapses" is measured by the
# SAME attribution machinery the real step loop flushes
_CHILD_SRC = """
import json, os, sys, time
sys.path.insert(0, {repo!r})
from mpi_operator_tpu.runtime import compile_cache
from mpi_operator_tpu.runtime.stepstats import StepStatsRecorder

compile_cache.configure_from_env()
import jax, jax.numpy as jnp

# unrolled depth so XLA compile time dominates trace time — the smoke's
# warm/cold ratio bar measures the CACHED part (compile), not tracing
@jax.jit
def step(w, x):
    y = x
    for _ in range(8):
        y = jnp.tanh(y @ w) + y
    return w - 1e-3 * (y.T @ y), jnp.sum(y * y)

w = jnp.ones((64, 64), jnp.float32)
x = jnp.ones((8, 64), jnp.float32)
stats = StepStatsRecorder()
for i in range(3):
    with stats.phase("compute"):
        w, loss = step(w, x)
        jax.block_until_ready(loss)
    stats.step_done(i + 1)
blob = stats.snapshot()
print(json.dumps({{"buckets": blob["buckets"],
                   "cache": blob.get("compile_cache")}}))
"""


def smoke() -> int:
    """<30s warm-restart smoke: run the tiny jitted workload twice
    against ONE cache dir (two processes — a restart, not a re-jit).
    Bars: run 1 reports cache misses and no hits (cold); run 2 reports
    hits and zero misses (warm) and its ``compile`` bucket collapses to
    under half of run 1's. Prints one JSON line; exit 0 iff all hold."""
    import subprocess
    import sys
    import tempfile
    import time

    t0 = time.time()
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    out: Dict[str, object] = {"metric": "compile_cache_smoke", "ok": False}
    with tempfile.TemporaryDirectory(prefix="tpujob-cc-smoke-") as root:
        env = dict(os.environ)
        env[ENV_JAX_CACHE_DIR] = root
        env.setdefault("JAX_PLATFORMS", "cpu")
        runs = []
        for i in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", _CHILD_SRC.format(repo=repo)],
                env=env, capture_output=True, text=True, timeout=120,
            )
            if proc.returncode != 0:
                out["error"] = proc.stderr[-2000:]
                print(json.dumps(out), flush=True)
                return 1
            runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        cold, warm = runs
        out["cold_compile_s"] = cold["buckets"]["compile"]
        out["warm_compile_s"] = warm["buckets"]["compile"]
        out["cold_cache"] = cold["cache"]
        out["warm_cache"] = warm["cache"]
        out["elapsed_s"] = round(time.time() - t0, 1)
        out["ok"] = bool(
            cold["cache"] and cold["cache"]["misses"] > 0
            and cold["cache"]["hits"] == 0
            and warm["cache"] and warm["cache"]["hits"] > 0
            and warm["cache"]["misses"] == 0
            and warm["buckets"]["compile"]
            < 0.5 * cold["buckets"]["compile"]
        )
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpu-compile-cache",
        description="Persistent XLA compile cache plumbing (see module "
                    "docstring); --smoke runs the verify-gate warm-"
                    "restart check.",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="<30s warm-restart smoke: tiny jitted workload "
                         "twice against one cache dir; the second run "
                         "must hit the cache and collapse its compile "
                         "bucket")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    if args.smoke:
        return smoke()
    ap.print_help()
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
