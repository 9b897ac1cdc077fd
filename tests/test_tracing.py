"""The program's names and spans for a profiler trace (ISSUE 26): the
recorder's phases and the set-up spans as ``tpujob.*`` annotations, set-up's
seconds in the bounded blob, ``StepProfiler``'s ack, the scopes in the
compiled step and the kernels' names.

Everything here is counts and names on the CPU. What the names cost and
read on the chip is the benchmark's to say (PERF.md §5, §6).
"""

import contextlib
import dataclasses
import glob
import json
import os
import re
import subprocess
import sys
import time
import unittest.mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.machinery.objects import (
    SETUP_SPANS,
    TRAIN_BUCKETS,
    bounded_train_stats,
)
from mpi_operator_tpu.models import llama, mnist
from mpi_operator_tpu.ops import (
    ElasticConfig,
    Trainer,
    TrainerConfig,
    run_elastic,
)
from mpi_operator_tpu.ops import profiling
from mpi_operator_tpu.ops.data import make_global_batch
from mpi_operator_tpu.runtime import MeshPlan, build_mesh, stepstats
from mpi_operator_tpu.runtime.stepstats import StepStatsRecorder, read_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import named_trace  # noqa: E402  (the reader's rule, held to the real HLO)


def _mesh():
    return build_mesh(MeshPlan.data_parallel(1), devices=jax.devices()[:1])


@pytest.fixture
def annotations(monkeypatch):
    """Every TraceAnnotation opened, by name, in order."""
    opened = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    return opened


@pytest.fixture
def fresh_setup():
    stepstats._reset_for_tests()
    yield
    stepstats._reset_for_tests()


# -- annotations ------------------------------------------------------------

def test_phase_opens_an_annotation_named_for_its_bucket(annotations):
    stats = StepStatsRecorder()
    for bucket in ("input", "compute", "compute", "sync", "ckpt"):
        with stats.phase(bucket):
            pass
    # the first compute is the compile, in the trace as in the buckets
    assert annotations == ["tpujob.input", "tpujob.compile", "tpujob.compute",
                           "tpujob.sync", "tpujob.ckpt"]
    assert {a.split(".", 1)[1] for a in annotations} == set(TRAIN_BUCKETS)


def test_phase_buckets_are_what_they_were(annotations):
    ticks = iter(range(100))
    stats = StepStatsRecorder(clock=lambda: float(next(ticks)))
    with stats.phase("compute"):
        pass
    with stats.phase("compute"):
        pass
    with pytest.raises(RuntimeError):
        with stats.phase("input"):
            raise RuntimeError("the feed broke")
    # one tick a phase, the failed one too
    assert stats.snapshot()["buckets"] == {
        "compile": 1.0, "input": 1.0, "compute": 1.0, "sync": 0.0,
        "ckpt": 0.0}


def test_setup_span_opens_an_annotation_and_adds_up(annotations, fresh_setup):
    with stepstats.setup_span("mesh"):
        time.sleep(0.01)
    with stepstats.setup_span("mesh"):
        time.sleep(0.01)
    with stepstats.setup_span("attach"):
        pass
    assert annotations == ["tpujob.mesh", "tpujob.mesh", "tpujob.attach"]
    seconds = stepstats.setup_seconds()
    assert set(seconds) == {"mesh", "attach"}
    assert 0.02 <= seconds["mesh"] < 5.0 and seconds["attach"] < 1.0


def test_setup_span_refuses_a_name_outside_the_fixed_set(fresh_setup):
    with pytest.raises(ValueError, match="unknown set-up span"):
        with stepstats.setup_span("warmup"):
            pass
    assert stepstats.setup_seconds() == {}


def test_setup_span_counts_a_span_that_raised(fresh_setup):
    with pytest.raises(KeyError):
        with stepstats.setup_span("restore"):
            raise KeyError("no such step")
    assert "restore" in stepstats.setup_seconds()


def _python(code, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, **env})


def test_without_jax_there_is_no_annotation_and_no_import_of_it():
    """The executor and the controller import stepstats and have no jax:
    phases and spans work there, and nothing pulls jax in."""
    proc = _python(
        "import sys\n"
        "import mpi_operator_tpu.executor.local\n"
        "import mpi_operator_tpu.controller.goodput\n"
        "from mpi_operator_tpu.runtime import stepstats\n"
        "r = stepstats.StepStatsRecorder()\n"
        "with r.phase('input'): pass\n"
        "with stepstats.setup_span('mesh'): pass\n"
        "assert isinstance(stepstats._annotation('x'), "
        "__import__('contextlib').nullcontext)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print(sorted(r.snapshot()['setup']))\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "['mesh']"


def test_pre_bootstrap_is_the_process_age_as_the_os_has_it():
    proc = _python(
        "import time; time.sleep(0.5)\n"
        "from mpi_operator_tpu.runtime import stepstats\n"
        "stepstats.mark_pre_bootstrap()\n"
        "print(stepstats.setup_seconds()['pre_bootstrap'])\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the interpreter's start and the sleep, to the tick of 10 ms
    assert 0.45 <= float(proc.stdout) < 60.0


def test_bootstrap_initialize_marks_its_spans(monkeypatch, fresh_setup):
    from mpi_operator_tpu.runtime import bootstrap

    bootstrap._reset_for_tests()
    try:
        bootstrap.initialize(environ={"TPUJOB_ACCELERATOR": "cpu",
                                      "TPUJOB_COMPILE_CACHE": "0"})
        seconds = stepstats.setup_seconds()
        # one host, no chip declared: no rendezvous, no attach to time
        assert set(seconds) == {"pre_bootstrap", "cache_config"}
        assert seconds["pre_bootstrap"] > 0
        # idempotent: a second call marks nothing again
        stepstats._reset_for_tests()
        bootstrap.initialize()
        assert stepstats.setup_seconds() == {}
    finally:
        bootstrap._reset_for_tests()


# -- the bounded blob ---------------------------------------------------------

def test_bounded_train_stats_keeps_only_the_fixed_spans_and_rounds():
    blob = bounded_train_stats(setup={
        "pre_bootstrap": 5.12345, "attach": "6.5", "mesh": 0.0004,
        "warmup": 3.0, "x" * 1000: 1.0, "restore": None})
    assert blob["setup"] == {"pre_bootstrap": 5.123, "attach": 6.5,
                             "mesh": 0.0, "restore": 0.0}
    assert list(blob["setup"]) == [
        k for k in SETUP_SPANS if k in blob["setup"]]


@pytest.mark.parametrize("setup", [None, {}, "a string", [1.0, 2.0], 7,
                                   {"warmup": 1.0}])
def test_bounded_train_stats_survives_a_wrong_setup(setup):
    blob = bounded_train_stats(step=3, setup=setup)
    assert blob.get("setup", {}) == {}
    assert blob["step"] == 3 and set(blob["buckets"]) == set(TRAIN_BUCKETS)


def test_bounded_train_stats_round_trips_a_mirrored_blob(fresh_setup):
    """The executor re-bounds whatever the worker's file says."""
    with stepstats.setup_span("ckpt_open"):
        pass
    raw = json.loads(json.dumps(StepStatsRecorder().snapshot()))
    raw["setup"]["evil"] = "x" * 10 ** 6
    again = bounded_train_stats(**raw)
    assert set(again["setup"]) == {"ckpt_open"}
    assert len(json.dumps(again)) < 1000


# -- run_elastic --------------------------------------------------------------

def _elastic(tmp_path, total_steps):
    mesh = _mesh()
    cfg = mnist.Config(hidden=16)
    trainer = Trainer(
        lambda p, b: mnist.loss_fn(cfg, p, b), mnist.logical_axes(cfg), mesh,
        TrainerConfig(learning_rate=1e-3))
    host = {"image": np.zeros((4, 28, 28, 1), np.float32),
            "label": np.zeros((4,), np.int32)}

    def batches():
        while True:
            yield make_global_batch(mesh, host)

    return run_elastic(
        trainer, batches(), total_steps=total_steps,
        config=ElasticConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                             save_interval_steps=100),
        init_state=lambda: trainer.init_state(
            mnist.init(cfg, jax.random.PRNGKey(0))),
        membership=lambda: 1, current_world=1)


def test_run_elastic_leaves_its_setup_spans_in_the_stats_file(
        tmp_path, monkeypatch, fresh_setup):
    stats_file = tmp_path / "stats.json"
    monkeypatch.setenv(stepstats.ENV_STATS_FILE, str(stats_file))
    assert _elastic(tmp_path, 2).start_step == 0
    fresh = read_stats(str(stats_file))["setup"]
    assert set(fresh) == {"ckpt_open", "init_state"}
    assert all(v >= 0 for v in fresh.values())

    stepstats._reset_for_tests()  # the next incarnation is a new process
    assert _elastic(tmp_path, 3).start_step == 2
    resumed = read_stats(str(stats_file))["setup"]
    assert set(resumed) == {"ckpt_open", "restore"}


def test_run_elastic_acks_the_env_capture_with_its_directory(
        tmp_path, monkeypatch, fresh_setup):
    stats_file = tmp_path / "stats.json"
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(stepstats.ENV_STATS_FILE, str(stats_file))
    monkeypatch.setenv(profiling.ENV_DIR, str(trace_dir))
    monkeypatch.setenv(profiling.ENV_START, "2")
    monkeypatch.setenv(profiling.ENV_STEPS, "2")
    _elastic(tmp_path, 5)
    ack = read_stats(str(stats_file))["profile"]
    assert ack == {"id": "env", "state": "done",
                   "dir": str(trace_dir / "host0")}
    found = glob.glob(
        os.path.join(ack["dir"], "**", "*.xplane.pb"), recursive=True)
    assert len(found) == 1
    # the program's phases are on the host's line of that trace
    spans = {name for name, _s, _d in named_trace.read_xplane(found[0])["host"]}
    assert {"tpujob.input", "tpujob.compute"} <= spans


def test_step_profiler_acks_capturing_then_done(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    stats = StepStatsRecorder()
    prof = profiling.StepProfiler(str(tmp_path), stats=stats)
    prof.start_step, prof.num_steps = 3, 2
    host_dir = str(tmp_path / "host0")
    prof.observe(2)
    assert "profile" not in stats.snapshot()
    prof.observe(3)
    assert stats.snapshot()["profile"] == {
        "id": "env", "state": "capturing", "dir": host_dir}
    prof.observe(4)
    prof.observe(5)
    assert stats.snapshot()["profile"]["state"] == "done"
    prof.observe(6)
    prof.close()
    assert calls == [("start", host_dir), ("stop",)]


def test_step_profiler_closed_mid_capture_acks_done(monkeypatch, tmp_path):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    stats = StepStatsRecorder()
    prof = profiling.StepProfiler(str(tmp_path), stats=stats)
    prof.start_step, prof.num_steps = 1, 5
    prof.observe(1)
    prof.close()
    assert stats.snapshot()["profile"]["state"] == "done"
    # without a recorder, and without a directory, it stays silent
    profiling.StepProfiler(str(tmp_path)).observe(10)
    off = profiling.StepProfiler("", stats=stats)
    off.observe(10)
    assert not off.enabled


# -- names in the compiled step -------------------------------------------------

def _step_op_names(ce_chunk):
    cfg = dataclasses.replace(llama.tiny(), remat_layers=True)
    mesh = _mesh()
    trainer = Trainer(
        lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh, ce_chunk=ce_chunk),
        llama.logical_axes(cfg), mesh, TrainerConfig(grad_clip_norm=1.0))
    state = trainer.init_state(llama.init(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
    text = trainer.compile(state, batch).as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("ce_chunk", [2048, 32], ids=["plain", "chunked"])
def test_compiled_step_names_every_phase_and_scope(ce_chunk):
    """The reader's rule on the real thing: every phase, and every scope of
    the model, is the name of some operation of the compiled step."""
    cells = set()
    for op_name in _step_op_names(ce_chunk):
        names = named_trace.names_of("", op_name)
        phase = named_trace.phase_of(op_name, names)
        scope = next((s for s in named_trace.SCOPES if s in names), None)
        cells.add((scope, phase))
    phases = {p for _s, p in cells}
    assert set(named_trace.PHASES) <= phases
    for scope in ("attention", "mlp"):
        assert {(scope, "forward"), (scope, "backward"),
                (scope, "recompute")} <= cells
    assert {("embed", "forward"), ("head_loss", "forward"),
            ("head_loss", "backward"), ("optimizer", "optimizer")} <= cells
    # the chunked loss replays its chunk; the plain one replays nothing
    assert (("head_loss", "recompute") in cells) == (ce_chunk == 32)


def test_scopes_leave_the_steps_numbers_alone():
    """Scopes are metadata: the same step with every scope switched off
    gives the same loss and the same new parameters, bit for bit."""
    cfg = dataclasses.replace(llama.tiny(), remat_layers=True)
    mesh = _mesh()
    batch = {"tokens": jnp.arange(128, dtype=jnp.int32).reshape(2, 64) % 7}

    def one_step():
        trainer = Trainer(
            lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh),
            llama.logical_axes(cfg), mesh, TrainerConfig(), donate=False)
        state = trainer.init_state(llama.init(cfg, jax.random.PRNGKey(0)))
        new, metrics = trainer.train_step(state, batch)
        return jax.tree.leaves(new.params), metrics["loss"]

    named_params, named_loss = one_step()
    with unittest.mock.patch.object(
            jax, "named_scope", lambda name: contextlib.nullcontext()):
        bare_params, bare_loss = one_step()
    assert float(named_loss) == float(bare_loss)
    for a, b in zip(named_params, bare_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_three_pallas_calls_carry_their_names():
    from mpi_operator_tpu.kernels.flash_attention import flash_attention

    q = jnp.zeros((1, 16, 2, 8))
    kv = jnp.zeros((1, 16, 1, 8))

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=True, block_q=8,
                               block_k=8).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    names = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    assert names == ["flash_fwd", "flash_dq", "flash_dkv"]
    assert tuple(names) == named_trace.KERNELS


# -- the example worker's line ------------------------------------------------

def test_llama_workers_last_line_still_parses(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           stepstats.ENV_STATS_FILE: str(tmp_path / "stats.json"),
           "LLAMA_CKPT": str(tmp_path / "ckpt"), "LLAMA_STEPS": "4"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "llama_worker.py")],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["outcome"] == "done" and report["step"] == 4
    # the rate is tokens a step over the recorder's median step
    blob = read_stats(str(tmp_path / "stats.json"))
    assert report["tokens_per_sec"] == round(
        report["global_batch"] * report["seq_len"]
        / (blob["step_p50_ms"] / 1e3), 1)
    assert report["setup"] == blob["setup"]
    assert {"pre_bootstrap", "cache_config", "mesh", "ckpt_open",
            "init_state"} == set(report["setup"])
    # the seconds to the first batch are the spans' sum (each rounded)
    assert report["first_dispatch_s"] == pytest.approx(
        sum(report["setup"].values()), abs=0.02)
