"""The resume path at the properties a full-width job needs (tier-1: the
elastic/checkpoint modules proper are slow-tier).

- run_elastic restores INTO an abstract template — a materialized one is a
  second copy of the state in device memory, and two ~9.5 GB states do not
  fit a 16 GB chip;
- a checkpoint save is cut into bounded files, so it commits on a machine
  whose RLIMIT_FSIZE is far below the state's size (EFBIG on one 0.7 GB
  OCDBT data file killed the first full-width save on such a machine).
"""

import os
import subprocess
import sys

import jax
import numpy as np

from mpi_operator_tpu.models import mnist
from mpi_operator_tpu.ops import (
    CheckpointManager,
    ElasticConfig,
    Trainer,
    TrainerConfig,
    run_elastic,
)
from mpi_operator_tpu.ops.data import make_global_batch
from mpi_operator_tpu.runtime import MeshPlan, build_mesh
from mpi_operator_tpu.runtime.topology import AXIS_DATA, AXIS_FSDP

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_run_elastic_restores_through_an_abstract_template(
        tmp_path, monkeypatch):
    mesh = build_mesh(MeshPlan(axes={AXIS_DATA: 4, AXIS_FSDP: 2}))
    cfg = mnist.Config(hidden=32)
    trainer = Trainer(
        lambda p, b: mnist.loss_fn(cfg, p, b), mnist.logical_axes(cfg), mesh,
        TrainerConfig(learning_rate=1e-3),
    )
    host = {
        "image": np.zeros((16, 28, 28, 1), np.float32),
        "label": np.zeros((16,), np.int32),
    }

    def batches():
        while True:
            yield make_global_batch(mesh, host)

    built = []  # per init_state call: did it run on real arrays?

    def init_state():
        params = mnist.init(cfg, jax.random.PRNGKey(0))
        built.append(not isinstance(
            jax.tree.leaves(params)[0], jax.core.Tracer))
        return trainer.init_state(params)

    def run(total_steps):
        return run_elastic(
            trainer, batches(), total_steps=total_steps,
            config=ElasticConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                                 save_interval_steps=100),
            init_state=init_state, membership=lambda: 1, current_world=1,
        )

    first = run(2)
    assert first.start_step == 0 and built == [True]

    templates = []
    real_restore = CheckpointManager.restore

    def spy(self, template, **kw):
        templates.append(template)
        return real_restore(self, template, **kw)

    monkeypatch.setattr(CheckpointManager, "restore", spy)
    del built[:]
    second = run(3)
    assert second.start_step == 2 and second.last_step == 3
    # the resume never built a real state beside the restored one ...
    assert built and not any(built)
    # ... it restored into shapes, dtypes and this mesh's shardings
    (template,) = templates
    leaves = jax.tree.leaves(template)
    assert leaves and all(
        isinstance(leaf, jax.ShapeDtypeStruct) and leaf.sharding is not None
        for leaf in leaves
    )
    want = trainer.state_sharding(template)
    assert jax.tree.map(lambda leaf: leaf.sharding, template) == want
    assert jax.tree.map(lambda x: x.sharding, second.state) == want


# a save of ~6 MB of noise under a 1 MiB file-size limit, in a child so the
# limit dies with it: commits, keeps every file inside the limit, and
# restores bit-equal
_LIMITED_SAVE_SRC = """
import os, resource, sys
LIMIT = 1 << 20
resource.setrlimit(resource.RLIMIT_FSIZE, (LIMIT, LIMIT))
import jax, numpy as np
from mpi_operator_tpu.ops import CheckpointManager
rng = np.random.default_rng(0)
state = {"a": jax.numpy.asarray(rng.standard_normal((1024, 1024), np.float32)),
         "b": jax.numpy.asarray(rng.standard_normal((3, 700, 256), np.float32)),
         "step": jax.numpy.asarray(7, np.int32)}
mgr = CheckpointManager(sys.argv[1], async_save=True)
assert mgr.save(7, state, force=True)
mgr.wait()
sizes = [os.path.getsize(os.path.join(d, f))
         for d, _, fs in os.walk(sys.argv[1]) for f in fs]
assert max(sizes) <= LIMIT, max(sizes)
assert sum(sizes) > 5 * LIMIT, sum(sizes)
back = mgr.restore(state)
mgr.close()
for k in state:
    assert np.array_equal(np.asarray(back[k]), np.asarray(state[k])), k
print("bit-equal", len(sizes))
"""


def test_checkpoint_commits_under_a_small_file_size_limit(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _LIMITED_SAVE_SRC, str(tmp_path / "ckpt")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.startswith("bit-equal")
