"""Llama decoder training worker — the BASELINE Llama acceptance config,
elastic-capable, through the operator path.

≙ the reference's elastic Horovod job
(/root/reference/examples/horovod/tensorflow-mnist-elastic.yaml:20-27:
horovodrun --host-discovery-script) re-targeted per BASELINE.md: a
Llama-3-architecture decoder under data-parallel sharded jit, trained via
ops.elastic.run_elastic — on membership change every worker checkpoints,
exits EXIT_RESTART (75), and the controller relaunches the gang at the new
size; the run resumes from the checkpoint with reshard-on-load.

Config via env so one manifest scales from the CPU e2e test to a TPU slice:
  LLAMA_CONFIG  tiny | bench | 8b   (default tiny)
  LLAMA_BATCH   per-chip batch      (default 2)
  LLAMA_SEQ     sequence length     (default 64)
  LLAMA_STEPS   total train steps   (default 6)
  LLAMA_CKPT    checkpoint dir      (default: no elasticity, plain loop)
  LLAMA_SAVE_EVERY / LLAMA_CHECK_EVERY  elastic cadence (default 2 / 10;
                the membership check is a gang-wide broadcast collective, so
                its cadence trades rescale latency against per-step sync)
  LLAMA_STEP_SLEEP  seconds of pacing between steps (default 0) — gives the
                rescale e2e test a deterministic window to mutate replicas
                while the tiny-config gang is still mid-training
  LLAMA_PROGRESS_EVERY  print a coordinator progress line every N batches
                (default off) — chaos/preemption tests watch the log for it
                to fault-inject only once training is genuinely stepping
  LLAMA_MESH    parallelism spec, e.g. "fsdp=2" or "fsdp=4,tensor=2"
                (default: pure DP over all chips). LLAMA_MESH_DCN adds
                slice counts for multi-slice gangs ("data=2"). This is how
                the manifest chooses FSDP/TP/SP without code changes.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import functools
import json

import jax

from mpi_operator_tpu.models import llama
from mpi_operator_tpu.ops import Trainer, TrainerConfig
from mpi_operator_tpu.ops.data import make_global_batch, synthetic_tokens
from mpi_operator_tpu.ops.elastic import ElasticConfig, run_elastic
from mpi_operator_tpu.runtime import (
    MeshPlan,
    bootstrap,
    compile_cache,
    mesh_from_context,
    stepstats,
)

CONFIGS = {
    "tiny": llama.tiny,
    "bench": llama.bench_single_chip,
    "8b": llama.llama3_8b,
}


def _memory():
    """Per local device, the allocator's bytes in use and their peak (None
    where the backend reports nothing, as the CPU does)."""
    out = []
    for d in jax.local_devices():
        ms = d.memory_stats()
        out.append(ms and {"in_use": ms["bytes_in_use"],
                           "peak": ms["peak_bytes_in_use"]})
    return out


def main():
    ctx = bootstrap.initialize()
    mesh_spec = os.environ.get("LLAMA_MESH", "").strip()
    dcn_spec = os.environ.get("LLAMA_MESH_DCN", "").strip()
    if dcn_spec and not mesh_spec:
        raise SystemExit("LLAMA_MESH_DCN requires LLAMA_MESH to be set")
    plan = MeshPlan.parse(mesh_spec, dcn_spec) if mesh_spec else None
    mesh = mesh_from_context(ctx, plan)

    cfg = CONFIGS[os.environ.get("LLAMA_CONFIG", "tiny")]()
    per_chip = int(os.environ.get("LLAMA_BATCH", "2"))
    seq_len = int(os.environ.get("LLAMA_SEQ", "64"))
    steps = int(os.environ.get("LLAMA_STEPS", "6"))
    # explicit manifest path wins; otherwise the per-job directory on the
    # shared checkpoint volume the node agent advertised (--ckpt-dir) — the
    # path a restarted gang finds again even when re-placed on other nodes
    ckpt_dir = os.environ.get("LLAMA_CKPT", "")
    if not ckpt_dir:
        ckpt_dir = bootstrap.default_checkpoint_dir(ctx) or ""

    trainer = Trainer(
        lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh),
        llama.logical_axes(cfg),
        mesh,
        TrainerConfig(learning_rate=3e-4, optimizer="adamw", grad_clip_norm=1.0),
    )
    global_batch = per_chip * jax.device_count()
    pace = float(os.environ.get("LLAMA_STEP_SLEEP", "0") or 0)
    # LLAMA_PROGRESS_EVERY=N: print a progress line every N batches (the
    # coordinator only). Harness hook: crash/preemption e2e tests watch the
    # log for it to know training is past compile and actually stepping
    # before they inject the fault.
    progress_every = int(os.environ.get("LLAMA_PROGRESS_EVERY", "0") or 0)

    # set-up facts for the report: the first batch is drawn once the state
    # is built or restored (set-up's spans end there: their sum is the
    # seconds from the process's start), the second once step 1 has been
    # dispatched
    setup = {}

    def batches_iter():
        for i, b in enumerate(synthetic_tokens(
            global_batch=global_batch, seq_len=seq_len, vocab=cfg.vocab
        )):
            if i == 0:
                setup["first_dispatch_s"] = round(
                    sum(stepstats.setup_seconds().values()), 2)
                setup["memory_after_init"] = _memory()
            elif i == 1:
                setup["memory_after_step1"] = _memory()
            if pace:
                time.sleep(pace)
            if progress_every and i and i % progress_every == 0 \
                    and ctx.is_coordinator:
                print(f"progress: batch {i}", flush=True)
            yield make_global_batch(mesh, b)

    batches = batches_iter()

    def init_state():
        # drawn under jit with the mesh layout as out_shardings, so each
        # chip generates only its own shard: an unsharded draw puts the
        # whole model on the first chip before init_state spreads it
        draw = jax.jit(
            functools.partial(llama.init, cfg),
            out_shardings=trainer.params_sharding(),
        )
        return trainer.init_state(draw(jax.random.PRNGKey(0)))

    if ckpt_dir:
        result = run_elastic(
            trainer,
            batches,
            total_steps=steps,
            config=ElasticConfig(
                checkpoint_dir=ckpt_dir,
                save_interval_steps=int(os.environ.get("LLAMA_SAVE_EVERY", "2")),
                membership_check_every=int(os.environ.get("LLAMA_CHECK_EVERY", "10")),
            ),
            init_state=init_state,
        )
        outcome, last_step = result.outcome, result.last_step
        start_step = result.start_step
        loss = (result.metrics or {}).get("loss")
    else:
        with stepstats.setup_span("init_state"):
            state = init_state()
        for _ in range(steps):
            state, metrics = trainer.train_step(state, next(batches))
        jax.block_until_ready(metrics["loss"])
        outcome, last_step, loss = "done", steps, float(metrics["loss"])
        start_step = 0

    # run_elastic's recorder flushed its last blob here on close
    stats = stepstats.read_stats(
        os.environ.get(stepstats.ENV_STATS_FILE, "")) or {}
    # tokens a step over the recorder's median step: under async dispatch
    # the host's step-to-step time is the device's once the queue is full.
    # Absent without a recorder (no checkpoint dir, or no stats file)
    step_ms = stats.get("step_p50_ms") or 0.0
    rate = ({"tokens_per_sec": round(
        global_batch * seq_len / (step_ms / 1e3), 1)} if step_ms else {})
    if ctx.is_coordinator:
        print(
            json.dumps(
                {
                    "workload": "llama",
                    "outcome": outcome,
                    "step": last_step,
                    # step this incarnation RESUMED from (0 = fresh start):
                    # crash/preemption e2e asserts start_step > 0 on the
                    # second incarnation — checkpoint recovery actually ran
                    "start_step": start_step,
                    "loss": loss,
                    **rate,
                    "hosts": ctx.num_hosts,
                    "backend": jax.default_backend(),
                    "device_kind": jax.devices()[0].device_kind,
                    "devices": jax.device_count(),
                    "mesh": ",".join(
                        f"{a}={s}" for a, s in mesh.shape.items() if s > 1
                    ),
                    "params": llama.param_count(cfg),
                    "vocab": cfg.vocab,
                    "global_batch": global_batch,
                    "seq_len": seq_len,
                    "compile_cache": compile_cache.cache_stats(),
                    "buckets": stats.get("buckets"),
                    # this incarnation's set-up seconds by span
                    "setup": stats.get("setup"),
                    # and what ran beside them on another thread (orbax's
                    # background import)
                    "setup_overlapped": stats.get("setup_overlapped"),
                    **setup,
                    "memory_at_exit": _memory(),
                }
            ),
            flush=True,
        )
    if ckpt_dir:
        raise SystemExit(result.exit_code)


if __name__ == "__main__":
    main()
