"""Profile the llama bench step and dump per-HLO-op stats.

Dev tool (not part of the package): mirrors PERF.md's recipe — capture a
jax.profiler trace of the compiled train step, convert with xprof's
hlo_stats, and write /tmp/llama_hlo_stats.json for op-level analysis
(time by boundedness, per-fusion GFLOP/s). The workload comes from
bench.llama_setup so the profile measures exactly the step bench.py times.
Run on the TPU chip.
"""

import os
import sys

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import glob
import json

import jax

from bench import llama_per_chip_batch, llama_setup
from mpi_operator_tpu.runtime import compile_cache


def main():
    compile_cache.configure_from_env()
    per_chip_batch = llama_per_chip_batch()
    seq_len = int(os.environ.get("BENCH_SEQ", "2048"))
    _, trainer, state, batch, _ = llama_setup(per_chip_batch, seq_len)

    for _ in range(3):
        state, m = trainer.train_step(state, batch)
    jax.block_until_ready(m["loss"])

    logdir = "/tmp/llama_profile"
    os.system(f"rm -rf {logdir}")
    with jax.profiler.trace(logdir):
        for _ in range(5):
            state, m = trainer.train_step(state, batch)
        jax.block_until_ready(m["loss"])

    xplane = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    print("xplane:", xplane)
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data(xplane, "hlo_stats", {})
    obj = json.loads(data) if isinstance(data, (str, bytes)) else data
    with open("/tmp/llama_hlo_stats.json", "w") as f:
        json.dump(obj, f)
    print("wrote /tmp/llama_hlo_stats.json")


if __name__ == "__main__":
    main()
