"""Runtime layer tests: env contract, context, mesh construction.

Mirrors the reference's env-wiring tests (the launcher env assertions inside
TestNewLauncherAndWorker, /root/reference/v2/pkg/controller/
mpi_job_controller_test.go:937) — here the consumer side is tested too,
which the reference cannot do (its consumer is mpirun)."""

import jax
import pytest

from mpi_operator_tpu.runtime import (
    MeshPlan,
    RuntimeContext,
    build_mesh,
    context_from_env,
    mesh_from_context,
)
from mpi_operator_tpu.runtime import bootstrap
from mpi_operator_tpu.runtime.topology import AXIS_DATA, AXIS_SEQ, AXIS_TENSOR


def test_env_names_match_controller_contract():
    """bootstrap deliberately duplicates the controller's env names (worker
    images don't ship the controller); this pins the two copies together."""
    from mpi_operator_tpu.controller import controller as ctrl

    for name in (
        "ENV_JOB_NAME",
        "ENV_NAMESPACE",
        "ENV_COORDINATOR",
        "ENV_NUM_HOSTS",
        "ENV_HOST_ID",
        "ENV_CHIPS_PER_HOST",
        "ENV_ACCELERATOR",
        "ENV_TOPOLOGY",
        "ENV_HOST_MESH",
        "ENV_HOST_COORD",
    ):
        assert getattr(bootstrap, name) == getattr(ctrl, name), name


def test_local_chips_discovery():
    assert RuntimeContext(chips_per_host=4).local_chips() == 4
    assert RuntimeContext().local_chips() == jax.local_device_count()


def test_mesh_from_context_gang_mismatch_fails_fast():
    ctx = RuntimeContext(num_hosts=3, chips_per_host=4)
    with pytest.raises(RuntimeError, match="rendezvous and placement disagree"):
        mesh_from_context(ctx)


def test_context_from_empty_env_is_local():
    ctx = context_from_env({})
    assert ctx.num_hosts == 1
    assert not ctx.is_distributed
    assert ctx.is_coordinator
    assert ctx.accelerator == ""  # undeclared — NOT "cpu": absence pins nothing


def test_context_parses_controller_env():
    env = {
        bootstrap.ENV_JOB_NAME: "train",
        bootstrap.ENV_NAMESPACE: "ml",
        bootstrap.ENV_COORDINATOR: "train-worker-0.train-worker:8476",
        bootstrap.ENV_NUM_HOSTS: "16",
        bootstrap.ENV_HOST_ID: "5",
        bootstrap.ENV_CHIPS_PER_HOST: "4",
        bootstrap.ENV_ACCELERATOR: "v5p",
        bootstrap.ENV_TOPOLOGY: "4x4x4",
        bootstrap.ENV_HOST_MESH: "2x2x4",
        bootstrap.ENV_HOST_COORD: "0x1x1",
    }
    ctx = context_from_env(env)
    assert ctx.is_distributed and not ctx.is_coordinator
    assert ctx.topology == (4, 4, 4)
    assert ctx.host_mesh == (2, 2, 4)
    assert ctx.host_coord == (0, 1, 1)
    assert ctx.chips_per_host == 4


def test_initialize_single_host_skips_handshake():
    bootstrap._reset_for_tests()
    ctx = bootstrap.initialize(environ={})
    assert ctx.num_hosts == 1
    assert bootstrap.active_context() is ctx
    # idempotent
    assert bootstrap.initialize() is ctx
    bootstrap._reset_for_tests()


def _platform_pins(monkeypatch, environ):
    """The jax_platforms values bootstrap.initialize(environ) sets."""
    pins = []
    real = jax.config.update

    def spy(name, value):
        if name == "jax_platforms":
            pins.append(value)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    bootstrap._reset_for_tests()
    try:
        bootstrap.initialize(environ=environ)
    finally:
        bootstrap._reset_for_tests()
    return pins


def test_platform_choice_cpu_pins_and_absence_pins_nothing(monkeypatch):
    """The platform is chosen in bootstrap.initialize, from the accelerator
    the manifest declared: "cpu" pins the CPU; an ABSENT variable pins
    nothing (it used to pin the CPU, so a chip job whose env got lost
    trained on the host)."""
    assert _platform_pins(monkeypatch, {bootstrap.ENV_ACCELERATOR: "cpu"}) == ["cpu"]
    assert _platform_pins(monkeypatch, {}) == []


def test_tpu_family_on_a_cpu_backend_raises(monkeypatch):
    """accelerator: v5e means the chip or an error — never whatever
    platform $JAX_PLATFORMS allowed (this process's backend is the CPU)."""
    with pytest.raises(RuntimeError, match="refusing to run a TPU job off"):
        _platform_pins(monkeypatch, {bootstrap.ENV_ACCELERATOR: "v5e"})


def test_worker_declared_for_tpu_exits_nonzero_on_cpu():
    """The same check end to end: a worker given TPUJOB_ACCELERATOR=v5e
    under JAX_PLATFORMS=cpu exits non-zero instead of computing."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "pi_worker.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             bootstrap.ENV_ACCELERATOR: "v5e"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "refusing to run a TPU job off the chip" in proc.stderr
    assert "pi is approximately" not in proc.stdout


def test_initialize_distributed_requires_coordinator():
    bootstrap._reset_for_tests()
    with pytest.raises(RuntimeError, match="COORDINATOR"):
        bootstrap.initialize(environ={bootstrap.ENV_NUM_HOSTS: "4"})
    bootstrap._reset_for_tests()


def test_mesh_plan_ordering_and_sizes():
    plan = MeshPlan(axes={AXIS_TENSOR: 2, AXIS_DATA: 4})
    assert plan.total_devices == 8
    # canonical order puts data before tensor regardless of dict order
    assert [n for n, _ in plan.ordered()] == [AXIS_DATA, AXIS_TENSOR]


def test_mesh_plan_rejects_unknown_axis():
    with pytest.raises(ValueError, match="unknown mesh axis"):
        MeshPlan(axes={"rows": 2})


def test_build_mesh_cpu():
    plan = MeshPlan(axes={AXIS_DATA: 2, AXIS_SEQ: 4})
    mesh = build_mesh(plan)
    assert mesh.axis_names == (AXIS_DATA, AXIS_SEQ)
    assert mesh.devices.shape == (2, 4)


def test_build_mesh_device_count_mismatch():
    with pytest.raises(ValueError, match="disagree"):
        build_mesh(MeshPlan(axes={AXIS_DATA: 3}))


def test_mesh_from_context_defaults_to_pure_dp():
    ctx = RuntimeContext()
    mesh = mesh_from_context(ctx)
    assert mesh.axis_names == (AXIS_DATA,)
    assert mesh.devices.size == jax.device_count()


def test_mesh_plan_parse():
    from mpi_operator_tpu.runtime.topology import MeshPlan

    plan = MeshPlan.parse("fsdp=4,tensor=2")
    assert plan.axes == {"fsdp": 4, "tensor": 2} and plan.dcn == {}
    plan = MeshPlan.parse("data=2", dcn="data=2")
    assert plan.dcn == {"data": 2} and plan.total_devices == 4
    import pytest

    with pytest.raises(ValueError):
        MeshPlan.parse("fsdp=banana")
    with pytest.raises(ValueError):
        MeshPlan.parse("warp=2")  # not in the axis vocabulary
    with pytest.raises(ValueError):
        MeshPlan.parse("fsdp=0")
    with pytest.raises(ValueError):
        MeshPlan.parse("fsdp=2,fsdp=4")  # duplicate axis is a typo


def test_default_checkpoint_dir_contract():
    """The shared-checkpoint-volume contract: the node agent advertises the
    volume via TPUJOB_CKPT_DIR; the per-job path is <base>/<ns>/<job> so a
    gang re-placed onto other nodes resumes from the same path, and two
    tenants' same-named jobs never collide. No volume → None (workloads
    fall back to their explicit paths or plain non-elastic loops)."""
    from mpi_operator_tpu.runtime.bootstrap import (
        ENV_CKPT_DIR,
        context_from_env,
        default_checkpoint_dir,
    )

    ctx = context_from_env(
        {"TPUJOB_NAME": "llama", "TPUJOB_NAMESPACE": "team-a"}
    )
    assert default_checkpoint_dir(ctx, {}) is None
    got = default_checkpoint_dir(ctx, {ENV_CKPT_DIR: "/mnt/ckpt"})
    assert got == "/mnt/ckpt/team-a/llama"
    other = context_from_env(
        {"TPUJOB_NAME": "llama", "TPUJOB_NAMESPACE": "team-b"}
    )
    assert default_checkpoint_dir(other, {ENV_CKPT_DIR: "/mnt/ckpt"}) \
        == "/mnt/ckpt/team-b/llama"
