"""Persistent compile cache plumbing (runtime/compile_cache.py, ISSUE 16).

Fast tier: where the cache lives — placed from outside by
``$JAX_COMPILATION_CACHE_DIR`` (passed through the executor untouched) or at
the one fixed in-checkout path — the job-level off switch, and the
train_stats blob field. Slow tier: real child processes compiling against a
shared cache dir — the warm-restart win and corruption robustness."""

import json
import os
import subprocess
import sys
import time

import pytest

from mpi_operator_tpu.api.types import Container, ObjectMeta
from mpi_operator_tpu.executor.local import LocalExecutor
from mpi_operator_tpu.machinery.objects import (
    Pod,
    PodPhase,
    PodSpec,
    bounded_train_stats,
)
from mpi_operator_tpu.machinery.store import ObjectStore
from mpi_operator_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fast: where the cache lives
# ---------------------------------------------------------------------------

# what a worker does at bootstrap, reporting what it ended up with
_WORKER_SRC = """
import json, jax
from jax._src import xla_bridge
from mpi_operator_tpu.runtime import compile_cache
print(json.dumps({
    "returned": compile_cache.configure_from_env(),
    "config": jax.config.jax_compilation_cache_dir,
    "backend_initialized": xla_bridge.backends_are_initialized(),
}))
"""


def _configure_in_pods(executors):
    """Run _WORKER_SRC as one pod on each LocalExecutor (concurrently);
    returns what each printed."""
    for ex in executors:
        ex.start()
    try:
        for ex in executors:
            ex.store.create(Pod(
                metadata=ObjectMeta(name="w-0", namespace="default"),
                spec=PodSpec(container=Container(
                    command=[sys.executable, "-c", _WORKER_SRC],
                )),
            ))
        deadline = time.time() + 60
        for ex in executors:
            while time.time() < deadline:
                pod = ex.store.get("Pod", "default", "w-0")
                if pod.status.phase in (PodPhase.SUCCEEDED, PodPhase.FAILED):
                    break
                time.sleep(0.05)
            assert pod.status.phase == PodPhase.SUCCEEDED, ex.logs
        return [json.loads(ex.logs["default/w-0"][0]) for ex in executors]
    finally:
        for ex in executors:
            ex.stop()


def test_cache_dir_placed_from_outside_is_left_alone(tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR reaches the worker through the executor
    and wins: no other directory set in code, no subdirectory appended —
    and configuring initializes no backend (the multi-host rendezvous
    comes after it and refuses to run once one exists)."""
    outside = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, outside)
    ex = LocalExecutor(ObjectStore(), workdir=REPO,
                       logs_dir=str(tmp_path / "logs"))
    (got,) = _configure_in_pods([ex])
    assert got == {"returned": outside, "config": outside,
                   "backend_initialized": False}


def test_default_cache_dir_is_fixed_and_the_same_for_every_executor(
        tmp_path, monkeypatch):
    """Unset, the cache lives at ONE fixed path inside the checkout: the
    directory is part of jax's cache key, so a root hung off an executor's
    temporary logs directory would move on every start and never hit."""
    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR, raising=False)
    executors = [
        LocalExecutor(ObjectStore(), workdir=REPO,
                      logs_dir=str(tmp_path / f"logs{i}"))
        for i in range(2)
    ]
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.DEFAULT_CACHE_DIR == fixed
    for got in _configure_in_pods(executors):
        assert got == {"returned": fixed, "config": fixed,
                       "backend_initialized": False}


def test_job_opt_out_turns_caching_off():
    """spec.compile_cache: false → $TPUJOB_COMPILE_CACHE=0 → no cache for
    that job, whatever the environment placed."""
    import jax

    try:
        assert compile_cache.configure_from_env(env={}) is not None
        assert compile_cache.is_configured()
        got = compile_cache.configure_from_env(
            env={compile_cache.ENV_CACHE_ENABLED: "0"})
        assert got is None
        assert jax.config.jax_compilation_cache_dir is None
        assert not compile_cache.is_configured()
    finally:
        compile_cache._reset_for_tests()


def test_blob_field_absent_when_unconfigured():
    # the exact-key contract of the stepstats blob (tests/test_stepstats)
    # must hold for every pre-ISSUE-16 consumer: no compile_cache key
    # unless the cache is actually configured and counting
    blob = bounded_train_stats(step=3, steps=10, compile_cache=None)
    assert "compile_cache" not in blob
    blob = bounded_train_stats(step=3, steps=10, compile_cache={})
    assert "compile_cache" not in blob


def test_blob_field_bounded_when_present():
    blob = bounded_train_stats(
        step=3, steps=10,
        compile_cache={"hits": 7.9, "misses": "2", "junk": "dropped"},
    )
    assert blob["compile_cache"] == {"hits": 7, "misses": 2}


# ---------------------------------------------------------------------------
# slow: real child processes against one cache dir
# ---------------------------------------------------------------------------


def _run_child(cache_root, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[compile_cache.ENV_JAX_CACHE_DIR] = str(cache_root)
    env.update(extra_env or {})
    src = compile_cache._CHILD_SRC.format(repo=REPO)
    proc = subprocess.run(
        [sys.executable, "-c", src],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.slow
def test_warm_restart_hits_cache_and_collapses_compile(tmp_path):
    cold, _ = _run_child(tmp_path)
    warm, _ = _run_child(tmp_path)
    assert cold["cache"]["misses"] > 0 and cold["cache"]["hits"] == 0
    assert warm["cache"]["hits"] > 0 and warm["cache"]["misses"] == 0
    # the whole tentpole: the warm incarnation's compile bucket collapses
    assert warm["buckets"]["compile"] < 0.5 * cold["buckets"]["compile"], (
        cold["buckets"], warm["buckets"],
    )


@pytest.mark.slow
def test_corrupted_entry_degrades_to_fresh_compile(tmp_path):
    """A truncated/garbage cache entry (node crash mid-write, disk fault)
    must mean a warning + miss + recompile — NEVER a crashed worker."""
    cold, _ = _run_child(tmp_path)
    n_corrupted = 0
    for dirpath, _dirs, files in os.walk(tmp_path):
        for f in files:
            with open(os.path.join(dirpath, f), "wb") as fh:
                fh.write(b"\x00garbage not a cache entry\xff" * 8)
            n_corrupted += 1
    assert n_corrupted > 0, "cold run wrote no cache entries"
    warm, stderr = _run_child(tmp_path)
    # every read is now a failed-deserialize: counted as misses, process
    # exits 0, and the step loop still ran all its steps
    assert warm["cache"]["hits"] == 0
    assert warm["cache"]["misses"] > 0
    assert warm["buckets"]["compute"] >= 0


@pytest.mark.slow
def test_smoke_gate_passes():
    proc = subprocess.run(
        [sys.executable, "-m", "mpi_operator_tpu.runtime.compile_cache",
         "--smoke"],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] is True
