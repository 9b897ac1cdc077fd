"""Orbax off the start's critical path (ISSUE 27): `bootstrap.initialize`
imports it on a background thread, `ops/checkpoint.py` joins that thread at
the first use of orbax, and `CheckpointManager` makes that first use as late
as is honest — on a directory with no entry, the first save.

Where a test has to see a process that has not imported orbax yet it runs in
a subprocess: the test session itself has long since loaded it."""

import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.machinery.objects import (
    SETUP_OVERLAPPED,
    SETUP_SPANS,
    bounded_train_stats,
)
from mpi_operator_tpu.ops import CheckpointManager
from mpi_operator_tpu.runtime import bootstrap, stepstats
from mpi_operator_tpu.runtime.stepstats import StepStatsRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _state(scale=1.0):
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) * scale,
            "step": jnp.asarray(7, dtype=jnp.int32)}


@pytest.fixture
def no_background_import():
    """The process as a library caller has it: no `bootstrap.initialize`
    ran, so no background import is there to join."""
    bootstrap._reset_for_tests()
    stepstats._reset_for_tests()
    yield
    bootstrap._reset_for_tests()
    stepstats._reset_for_tests()


# -- the late first use -------------------------------------------------------

@pytest.mark.parametrize("made_before", [False, True],
                         ids=["absent", "empty"])
def test_a_directory_with_no_entry_has_no_step_and_needs_no_orbax(
        tmp_path, made_before):
    directory = tmp_path / "ckpt"
    if made_before:
        directory.mkdir()
    proc = _python(
        "import sys\n"
        "from mpi_operator_tpu.ops import CheckpointManager\n"
        f"mgr = CheckpointManager({str(directory)!r})\n"
        "assert mgr.latest_step() is None\n"
        "mgr.close()\n"
        "assert 'orbax.checkpoint' not in sys.modules, 'orbax was imported'\n"
        "assert 'orbax' not in sys.modules, 'orbax was imported'\n"
        "print('ok')\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"
    # the constructor made it: a directory that cannot be written fails
    # the start, not the first save
    assert directory.is_dir() and not os.listdir(directory)


def test_a_directory_that_cannot_be_made_fails_at_the_start(tmp_path):
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    with pytest.raises(OSError):
        CheckpointManager(str(blocker / "ckpt"))


def test_a_committed_step_is_found_through_orbax(
        tmp_path, no_background_import):
    first = CheckpointManager(str(tmp_path), save_interval_steps=1)
    assert first.save(3, _state())
    first.wait()
    first.close()
    mgr = CheckpointManager(str(tmp_path), save_interval_steps=1)
    assert mgr._manager is None  # not built by the constructor
    assert mgr.latest_step() == 3
    assert mgr._manager is not None  # ... but by the question
    mgr.close()


@pytest.mark.parametrize("entry", ["5.orbax-checkpoint-tmp-1700000000",
                                   "stray-file", "profiles"])
def test_anything_else_in_the_directory_is_orbax_s_to_judge(
        tmp_path, no_background_import, entry):
    """A leftover temporary step, a stray file, the job's `profiles/`: no
    rule of orbax's on what counts as a committed step is copied, so the
    answer is whatever orbax's own manager says of the same directory."""
    import orbax.checkpoint as ocp

    if entry == "stray-file":
        (tmp_path / entry).write_text("x")
    else:
        (tmp_path / entry).mkdir()
    reference = ocp.CheckpointManager(str(tmp_path))
    expected = reference.latest_step()
    reference.close()
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == expected
    assert mgr._manager is not None
    assert expected is None  # what orbax 0.11 answers today
    mgr.close()


def test_save_close_reopen_restore_round_trips(tmp_path, no_background_import):
    mgr = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1)
    assert mgr.latest_step() is None and mgr._manager is None
    assert mgr.save(7, _state())  # the first use: builds the manager
    assert mgr._manager is not None
    mgr.wait()
    assert mgr.latest_step() == 7
    mgr.close()

    again = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1)
    restored = again.restore(_state(scale=0.0))
    for want, got in zip(jax.tree.leaves(_state()),
                         jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    again.close()


def test_restore_and_wait_build_the_manager_too(
        tmp_path, no_background_import):
    mgr = CheckpointManager(str(tmp_path))
    mgr.wait()  # nothing in flight; orbax says so itself
    assert mgr._manager is not None
    mgr.close()
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        mgr.restore(_state())
    assert mgr._manager is not None
    mgr.close()


def test_close_on_a_manager_never_built_is_silent(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.close()
    mgr.close()
    assert mgr._manager is None


# -- the background load ------------------------------------------------------

def test_two_calls_of_initialize_start_one_thread_and_reset_forgets_it(
        no_background_import):
    def import_threads():
        return [t for t in threading.enumerate()
                if t.name == "import-orbax.checkpoint"]

    before = len(import_threads())
    try:
        assert bootstrap._orbax_import is None
        bootstrap.initialize(environ={"TPUJOB_ACCELERATOR": "cpu",
                                      "TPUJOB_COMPILE_CACHE": "0"})
        started = bootstrap._orbax_import
        assert started is not None and started.name == "orbax.checkpoint"
        assert started._thread.daemon
        bootstrap.initialize()
        assert bootstrap._orbax_import is started
        # the module comes through the join, whole
        assert bootstrap.orbax_checkpoint() is sys.modules["orbax.checkpoint"]
        assert not started._thread.is_alive()
        assert len(import_threads()) == before
        assert bootstrap.setup_overlapped_seconds() == {
            "ckpt_import": started.seconds}
    finally:
        bootstrap._reset_for_tests()
    assert bootstrap._orbax_import is None
    assert bootstrap.setup_overlapped_seconds() == {}


def test_a_start_that_fails_after_the_thread_does_not_start_another(
        no_background_import):
    """`initialize` that raises (here: several hosts and no coordinator)
    has not finished, so a second call runs it again: with the one thread."""
    environ = {"TPUJOB_ACCELERATOR": "cpu", "TPUJOB_COMPILE_CACHE": "0",
               bootstrap.ENV_NUM_HOSTS: "2"}
    with pytest.raises(RuntimeError):
        bootstrap.initialize(environ=environ)
    started = bootstrap._orbax_import
    assert started is not None
    with pytest.raises(RuntimeError):
        bootstrap.initialize(environ=environ)
    assert bootstrap._orbax_import is started


def test_an_import_that_fails_raises_at_the_first_use_and_not_before(
        tmp_path, monkeypatch, no_background_import):
    (tmp_path / "tpujob_test_broken_module.py").write_text(
        "raise RuntimeError('boom at import')\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    failing = bootstrap._BackgroundImport("tpujob_test_broken_module")
    monkeypatch.setattr(bootstrap, "_orbax_import", failing)
    failing._thread.join(60)
    assert not failing._thread.is_alive()
    # the failure is kept, not raised: the start goes on, and a fresh
    # directory is still answered
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    assert mgr.latest_step() is None
    # ... until orbax is needed: the same exception, on the caller's thread
    caller = threading.current_thread()
    for use in (lambda: mgr.save(1, _state()), mgr.wait,
                lambda: mgr.restore(_state())):
        with pytest.raises(RuntimeError, match="boom at import") as caught:
            use()
        assert caught.value is failing._error
        assert threading.current_thread() is caller
    mgr.close()  # never built: silent
    assert failing._ended is not None and failing.seconds >= 0.0


def test_with_no_initialize_before_it_the_manager_imports_then_and_there(
        tmp_path):
    proc = _python(
        "import sys\n"
        "import jax.numpy as jnp\n"
        "from mpi_operator_tpu.ops import CheckpointManager\n"
        "from mpi_operator_tpu.runtime import bootstrap\n"
        f"mgr = CheckpointManager({str(tmp_path)!r}, save_interval_steps=1)\n"
        "assert mgr.latest_step() is None\n"
        "assert 'orbax.checkpoint' not in sys.modules\n"
        "assert mgr.save(1, {'w': jnp.ones((4,))})\n"
        "mgr.wait()\n"
        "assert bootstrap._orbax_import is None\n"
        "assert bootstrap.setup_overlapped_seconds() == {}\n"
        "print(mgr.latest_step(), float(mgr.restore({'w': jnp.zeros((4,))})"
        "['w'].sum()))\n"
        "mgr.close()\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["1", "4.0"]


def test_the_module_is_loaded_by_the_first_save_after_initialize(tmp_path):
    """A job that saves does not pay the import at its first save: the
    thread that `bootstrap.initialize` started has loaded orbax while the
    main thread never imported it, `ckpt_open` on the fresh directory held
    no orbax at all, and the blob says what the start hid."""
    proc = _python(
        "import json, sys, threading\n"
        "import jax, jax.numpy as jnp\n"
        "from mpi_operator_tpu.ops import CheckpointManager\n"
        "from mpi_operator_tpu.runtime import bootstrap, stepstats\n"
        "assert 'orbax' not in sys.modules\n"
        "bootstrap.initialize(environ={'TPUJOB_ACCELERATOR': 'cpu',\n"
        "                              'TPUJOB_COMPILE_CACHE': '0'})\n"
        "started = bootstrap._orbax_import\n"
        "assert started is not None\n"
        "with stepstats.setup_span('ckpt_open'):\n"
        f"    mgr = CheckpointManager({str(tmp_path)!r},\n"
        "                            save_interval_steps=1)\n"
        "    assert mgr.latest_step() is None\n"
        "assert mgr._manager is None\n"
        # the steps before the first save: the main thread works on
        "x = jax.jit(lambda a: a @ a)(jnp.ones((64, 64))).block_until_ready()\n"
        "started._thread.join(120)\n"
        "assert not started._thread.is_alive()\n"
        "assert 'orbax.checkpoint' in sys.modules\n"
        "assert mgr._manager is None\n"
        "assert mgr.save(1, {'w': x})\n"
        "mgr.wait()\n"
        "assert mgr.latest_step() == 1\n"
        "mgr.close()\n"
        "blob = stepstats.StepStatsRecorder().snapshot()\n"
        "print(json.dumps(blob))\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json

    blob = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(blob["setup"]) <= set(SETUP_SPANS)
    assert "ckpt_import" not in blob["setup"]
    assert set(blob["setup_overlapped"]) == {"ckpt_import"}
    # the import's seconds are not in the span beside it
    assert blob["setup_overlapped"]["ckpt_import"] > 0.0
    assert blob["setup"]["ckpt_open"] < blob["setup_overlapped"]["ckpt_import"]


def test_every_caller_gets_the_one_module_however_many_ask_at_once(
        no_background_import, monkeypatch):
    """Orbax's own save thread, a profiler callback, the step loop: the
    join may be made from several threads at once."""
    monkeypatch.setattr(bootstrap, "_orbax_import",
                        bootstrap._BackgroundImport("orbax.checkpoint"))
    got, errors = [], []

    def ask():
        try:
            got.append(bootstrap.orbax_checkpoint())
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert len(got) == 32
    assert all(m is sys.modules["orbax.checkpoint"] for m in got)


def test_importing_orbax_initializes_no_backend():
    """The rendezvous contract in `bootstrap.initialize`: everything before
    `jax.distributed.initialize` must leave jax's backends uninitialized,
    and the background import starts before it."""
    proc = _python(
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "import orbax.checkpoint\n"
        "assert not xla_bridge.backends_are_initialized(), 'a backend is up'\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('ok')\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def test_the_background_import_initializes_no_backend_either():
    proc = _python(
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from mpi_operator_tpu.runtime import bootstrap\n"
        "bootstrap.initialize(environ={'TPUJOB_ACCELERATOR': 'cpu',\n"
        "                              'TPUJOB_COMPILE_CACHE': '0'})\n"
        "bootstrap.orbax_checkpoint()\n"
        "assert not xla_bridge.backends_are_initialized(), 'a backend is up'\n"
        "print('ok')\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


# -- the blob -----------------------------------------------------------------

def test_the_thread_s_seconds_ride_beside_setup_not_inside_it(
        no_background_import, monkeypatch):
    done = bootstrap._BackgroundImport("orbax.checkpoint")
    done._thread.join(60)
    done._ended = done._started + 12.34567
    monkeypatch.setattr(bootstrap, "_orbax_import", done)
    with stepstats.setup_span("ckpt_open"):
        pass
    blob = StepStatsRecorder().snapshot()
    assert set(blob["setup"]) == {"ckpt_open"}
    assert set(blob["setup"]) <= set(SETUP_SPANS)
    assert blob["setup_overlapped"] == {"ckpt_import": 12.346}
    # the executor re-bounds what the worker's file says: it survives
    assert bounded_train_stats(**blob) == blob
    assert SETUP_OVERLAPPED == ("ckpt_import",)
    assert not set(SETUP_OVERLAPPED) & set(SETUP_SPANS)


def test_an_import_still_running_has_its_seconds_so_far(
        no_background_import, monkeypatch):
    """On the chip's machine the import outlasts set-up and, some runs, a
    short job's every flush: the blob says how far it has come, and the
    whole from the first flush after it has ended."""
    running = bootstrap._BackgroundImport("orbax.checkpoint")
    running._thread.join(60)
    whole = running.seconds
    assert whole == running.seconds  # ended: it counts no further
    running._ended = None  # as it reads until the thread's last line
    running._started = time.perf_counter() - 7.0
    monkeypatch.setattr(bootstrap, "_orbax_import", running)
    first = StepStatsRecorder().snapshot()["setup_overlapped"]
    assert set(first) == {"ckpt_import"}
    assert 7.0 <= first["ckpt_import"] < 60.0
    assert bootstrap.setup_overlapped_seconds()["ckpt_import"] >= \
        first["ckpt_import"] - 0.001  # the blob rounds to a millisecond


def test_bounded_train_stats_keeps_only_the_fixed_overlapped_keys():
    blob = bounded_train_stats(setup_overlapped={
        "ckpt_import": "12.3456", "compile": 3.0, "x" * 1000: 1.0})
    assert blob["setup_overlapped"] == {"ckpt_import": 12.346}
    assert bounded_train_stats(
        setup_overlapped={"ckpt_import": None})["setup_overlapped"] == {
            "ckpt_import": 0.0}


@pytest.mark.parametrize("given", [None, {}, "a string", [1.0, 2.0], 7,
                                   {"compile": 1.0}])
def test_bounded_train_stats_survives_a_wrong_setup_overlapped(given):
    blob = bounded_train_stats(step=3, setup={"attach": 1.0},
                               setup_overlapped=given)
    assert "setup_overlapped" not in blob
    assert blob["step"] == 3 and blob["setup"] == {"attach": 1.0}
