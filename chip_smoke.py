"""chip_smoke.py: the quickest proof that the operator path still starts on
the chip.

    python3 chip_smoke.py            # on a TPU host, from the checkout root

One TPUJob — examples/llama.yaml re-targeted at the chips jax finds here:
``llama.bench_single_chip()`` at full width, batch 8 x 2048 per chip, f32
moments, ``fsdp=N`` over N > 1 chips — driven through the entry points a
user calls: ``runlocal.run_job`` -> controller -> gang scheduler ->
LocalExecutor -> examples/llama_worker.py -> ``bootstrap.initialize`` ->
``run_elastic`` -> ``Trainer.train_step``, with a checkpoint directory so
orbax saves (one periodic async save, the final fenced one). Then the SAME
job again as a second incarnation on the same directory with more total
steps: it restores at full width, hits the compile cache and continues.
The restart is the operator's product; the second leg is the only thing
that exercises restore, the abstract template and a warm cache.

It refuses to pass off the chip, and it stands in for nothing: it imports
the program at the top, so alone in an empty directory it fails.

Output contract: the LAST line of stdout is one JSON object with exactly
the keys RESULT_KEYS / DEVICE_KEYS name; everything else this learns goes
on earlier lines. Exit code 0 iff every phase passed. The seconds on the
earlier lines are set-up facts, not metrics.

``--tiny-cpu`` runs the same control flow with the tiny config on the CPU
backend and prints counts only (tests/test_chip_smoke.py pins the flow and
the last line there before chip time is spent).

This process never initializes a jax backend — a parent that holds the chip
starves the worker. What it must know about the device it asks a throwaway
child that exits before the job starts.
"""

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
from importlib import metadata

from mpi_operator_tpu.api.conditions import is_succeeded
from mpi_operator_tpu.opshell.runlocal import load_job, run_job
from mpi_operator_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.abspath(__file__))

# the last line of stdout, exactly: {"ok": true, "device": {"platform": ...,
# "kind": ..., "count": ...}} with the device as jax reports it
RESULT_KEYS = ("ok", "device")
DEVICE_KEYS = ("platform", "kind", "count")

MAX_FILE_BYTES = 64 << 20  # no file under the checkpoint dir may pass this
COLD_STEPS, WARM_STEPS, SAVE_EVERY = 4, 6, 3  # saves at 3, 4 | 6
BENCH_PARAMS = 788_580_352  # llama.param_count(llama.bench_single_chip())

_PROBE = (
    "import json, jax; d = jax.devices(); print(json.dumps({"
    "'platform': d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
)


def result_line(device) -> str:
    values = {"ok": True, "device": {k: device[k] for k in DEVICE_KEYS}}
    return json.dumps({k: values[k] for k in RESULT_KEYS})


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def probe_device(env) -> dict:
    """jax's view of the device, from a child that exits before the job
    starts (so the chip is free again for the worker)."""
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True,
        text=True, timeout=300,
    )
    if proc.returncode != 0:
        fail(f"jax found no device (probe exit {proc.returncode}):\n"
             f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def build_job(device: dict, ckpt_dir: str, steps: int, tiny: bool):
    n = device["count"]
    job = load_job(os.path.join(REPO, "examples", "llama.yaml"))
    job.metadata.name = "chip-smoke"
    job.spec.worker.replicas = 1
    job.spec.slice.accelerator = "cpu" if tiny else "v5e"
    job.spec.slice.chips_per_host = n
    job.spec.slots_per_worker = n
    container = job.spec.worker.template.container
    # the interpreter this script runs under, not whatever `python` is on PATH
    container.command = [sys.executable, "examples/llama_worker.py"]
    container.env.update({
        "LLAMA_CONFIG": "tiny" if tiny else "bench",
        "LLAMA_BATCH": "2" if tiny else "8",
        "LLAMA_SEQ": "32" if tiny else "2048",
        "LLAMA_STEPS": str(steps),
        "LLAMA_SAVE_EVERY": str(SAVE_EVERY),
        "LLAMA_CKPT": ckpt_dir,
    })
    if n > 1:
        container.env["LLAMA_MESH"] = f"fsdp={n}"
    return job


def run_incarnation(name: str, device: dict, ckpt_dir: str, steps: int,
                    tiny: bool) -> dict:
    """One pass of the job through run_job; returns the worker's report."""
    job = build_job(device, ckpt_dir, steps, tiny)
    final, logs = run_job(job, timeout=500.0, workdir=REPO)
    out, err = logs.get("default/chip-smoke-worker-0", ("", ""))
    if not is_succeeded(final.status):
        conds = [(c.type, c.reason) for c in final.status.conditions]
        fail(f"{name}: job did not succeed: {conds}\n--- worker stderr "
             f"tail ---\n{err[-4000:]}")
    report = json.loads(out.strip().splitlines()[-1])
    want_backend = "cpu" if tiny else "tpu"
    checks = {
        "outcome": report["outcome"] == "done",
        "step": report["step"] == steps,
        "backend": report["backend"] == want_backend,
        "device_kind": report["device_kind"] == device["kind"],
        "devices": report["devices"] == device["count"],
        "loss": report["loss"] is not None
        and 0.0 < report["loss"] < 2 * math.log(report["vocab"]),
    }
    if not tiny:
        checks["full_width"] = (
            report["params"] == BENCH_PARAMS
            and report["global_batch"] == 8 * device["count"]
            and report["seq_len"] == 2048
        )
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        fail(f"{name}: worker report fails {bad}: {json.dumps(report)}")
    return report


def checkpoint_files(ckpt_dir: str):
    """(file count, largest file's size, saved steps) under ckpt_dir."""
    sizes = [
        os.path.getsize(os.path.join(d, f))
        for d, _dirs, files in os.walk(ckpt_dir) for f in files
    ]
    saved = sorted(int(s) for s in os.listdir(ckpt_dir) if s.isdigit())
    return len(sizes), max(sizes, default=0), saved


def check_memory_even(report: dict) -> None:
    """Nothing may land only on the first chip: per-chip bytes in use agree
    within 5% after init and after step 1, and chip 0's peak after init is
    below the whole model's f32 parameter bytes."""
    for when in ("memory_after_init", "memory_after_step1"):
        used = [m["in_use"] for m in report[when]]
        if max(used) > 1.05 * min(used):
            fail(f"{when}: per-chip bytes in use uneven: {used}")
    peak0 = report["memory_after_init"][0]["peak"]
    if peak0 >= 4 * report["params"]:
        fail(f"chip 0 peaked at {peak0} B after init, not below the whole "
             f"model's {4 * report['params']} B of parameters")


def show(name: str, report: dict, ckpt_dir: str, tiny: bool) -> None:
    """Print what one incarnation reported, on lines before the last."""
    shown = {k: report[k] for k in (
        "start_step", "step", "compile_cache", "mesh", "params",
        "global_batch", "seq_len")}
    if not tiny:  # the loss, seconds and bytes: facts of a chip run only
        shown.update({k: report.get(k) for k in (
            "loss", "first_dispatch_s", "setup", "setup_overlapped",
            "buckets", "memory_after_init", "memory_after_step1",
            "memory_at_exit")})
    n_files, largest, saved = checkpoint_files(ckpt_dir)
    print(f"chip_smoke: {name}: {json.dumps(shown)}")
    print(f"chip_smoke: {name}: checkpoints {saved}, {n_files} files, "
          f"largest {largest} bytes", flush=True)
    if largest > MAX_FILE_BYTES:
        fail(f"{name}: a checkpoint file of {largest} bytes exceeds "
             f"{MAX_FILE_BYTES}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="same control flow, tiny config on the CPU "
                         "backend, counts only (for the tier-1 test)")
    tiny = ap.parse_args(argv).tiny_cpu

    env = dict(os.environ)
    if tiny:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # one CPU device, whatever a harness forced
    device = probe_device(env)
    want = "cpu" if tiny else "tpu"
    if device["platform"] != want or device["count"] not in (1, 2, 4):
        print(f"chip_smoke: needs 1, 2 or 4 {want} devices; jax found "
              f"{device['count']} x {device['kind']!r} on backend "
              f"{device['platform']!r}", file=sys.stderr)
        return 1

    versions = {p: metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu", "orbax-checkpoint")}
    cache_dir = (os.environ.get(compile_cache.ENV_JAX_CACHE_DIR)
                 or compile_cache.DEFAULT_CACHE_DIR)
    print(f"chip_smoke: {device['count']} x {device['kind']!r} "
          f"({device['platform']}); {json.dumps(versions)}; compile cache "
          f"at {cache_dir}", flush=True)

    # a fresh directory outside the checkout and outside chiprun_out/ (a
    # stale one would restore at its last step and run nothing); ~9.5 GB
    # per full-width save, three saves; gone on every exit path
    ckpt_dir = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    try:
        cold = run_incarnation("cold", device, ckpt_dir, COLD_STEPS, tiny)
        show("cold", cold, ckpt_dir, tiny)
        warm = run_incarnation("warm", device, ckpt_dir, WARM_STEPS, tiny)
        show("warm", warm, ckpt_dir, tiny)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    if cold["start_step"] != 0:
        fail(f"cold incarnation resumed at {cold['start_step']}")
    if not (warm["start_step"] == cold["step"] > 0):
        fail(f"warm incarnation started at step {warm['start_step']}, the "
             f"cold one ended at {cold['step']}: it did not restore")
    cc = warm["compile_cache"]
    if not (cc["hits"] > 0 and cc["misses"] == 0):
        fail(f"warm incarnation's compile cache was not warm: {cc}")
    if device["count"] > 1 and not tiny:
        check_memory_even(cold)
        check_memory_even(warm)

    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            fail("the parent process initialized a jax backend")
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
