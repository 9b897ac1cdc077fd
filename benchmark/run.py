"""One run of one cell, to the driver's contract:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds ONE TPUJob from the cell's configuration and traffic files and
drives it through ``opshell.runlocal.run_job`` -> controller -> gang
scheduler -> LocalExecutor -> ``benchmark/worker.py`` ->
``bootstrap.initialize`` -> ``run_elastic`` -> ``Trainer.train_step``. This
process never touches a JAX backend: a parent that holds the chip starves
the worker. The worker finds the device itself and refuses another than
the configuration's accelerator, or another count than the cell's chips.

The last line of standard output is the contract's object, built from the
constants below and nothing else; what else a run learns goes to standard
error. Exit code 0 only with that line printed.
"""

import time

_T0 = time.monotonic()  # set-up is counted from here

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device",
               "breakdown", "compared")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = DEVICE_KEYS + ("busy_s", "window_s")
TRACE_FIRST_STEP, TRACE_STEPS = 3, 3  # window steps 3..5 are traced


def fail(msg):
    print(f"benchmark: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def load_cell(spec_file, workload):
    with open(spec_file) as f:
        spec = json.load(f)
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        fail(f"no workload {workload!r} in {spec_file}")
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    base = os.path.dirname(os.path.abspath(spec_file))
    return spec, cell, os.path.join(base, config["file"])


def build_job(conf, chips, env):
    from mpi_operator_tpu.api.schema import parse_tpujob

    return parse_tpujob({
        "apiVersion": "tpujob.dev/v1", "kind": "TPUJob",
        "metadata": {"name": "bench", "namespace": "default"},
        "spec": {
            "slice": {"accelerator": conf["accelerator"],
                      "chips_per_host": chips},
            "slots_per_worker": chips,
            "run_policy": {"backoff_limit": 0},
            "worker": {
                "replicas": 1, "restart_policy": "Never",
                "template": {"containers": [{
                    "name": "bench", "image": "local",
                    "command": [sys.executable,
                                os.path.join(HERE, "worker.py")],
                    "env": [{"name": k, "value": v}
                            for k, v in sorted(env.items())],
                }]},
            },
        },
    })


def metric_values(names, record):
    """{name: {"value", "unit"}} from each metric's own reader; a reader
    that finds nothing to read leaves its metric out."""
    out = {}
    for m in names:
        reader = importlib.import_module(f"metrics.{m['name']}")
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def reports_in(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def result_line(values, trace):
    device_keys = TRACE_DEVICE_KEYS if trace else DEVICE_KEYS
    values = dict(values,
                  device={k: values["device"][k] for k in device_keys})
    keys = [k for k in RESULT_KEYS if k != "breakdown" or trace]
    return json.dumps({k: values[k] for k in keys})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the benchmark's description (tests name a tiny one)")
    ap.add_argument("--control", action="store_true",
                    help="switch on the program's lower-precision path: "
                         "the run that has to come out not correct")
    ap.add_argument("--fault", default="",
                    choices=("", "state_unchanged", "half_batch",
                             "no_exchange"),
                    help="plant a fault under the timed path (tests)")
    ap.add_argument("--keep", default="",
                    help="copy what the run wrote (logs, trace, report) "
                         "here before it is removed: for a look by hand")
    args = ap.parse_args(argv)

    spec, cell, config_file = load_cell(args.spec, args.workload)
    base = os.path.dirname(os.path.abspath(args.spec))
    bench_dir = os.path.join(base, spec["paths"][0])
    with open(config_file) as f:
        conf = json.load(f)
    traffic_file = os.path.join(bench_dir, "traffic",
                                cell["traffic"] + ".json")
    with open(traffic_file) as f:
        traffic = json.load(f)
    with open(os.path.join(bench_dir, "limits", cell["name"] + ".json")) as f:
        limits = json.load(f)["limits"]

    # everything a run writes but the compile cache goes under one fresh
    # directory outside the checkout, removed on every way out
    run_dir = tempfile.mkdtemp(prefix="bench-run-")
    os.environ["TMPDIR"] = run_dir
    tempfile.tempdir = None
    try:
        return run_cell(args, spec, cell, conf, traffic, limits,
                        config_file, traffic_file, run_dir)
    finally:
        if args.keep:
            shutil.copytree(run_dir, args.keep, dirs_exist_ok=True)
        shutil.rmtree(run_dir, ignore_errors=True)


def run_cell(args, spec, cell, conf, traffic, limits, config_file,
             traffic_file, run_dir):
    from mpi_operator_tpu.api.conditions import is_succeeded
    from mpi_operator_tpu.opshell.runlocal import run_job

    import check

    chips = cell["chips"]
    trace_dir = os.path.join(run_dir, "trace") if args.trace else ""
    run_file = os.path.join(run_dir, "run.json")
    report_file = os.path.join(run_dir, "report.json")
    with open(run_file, "w") as f:
        json.dump({
            "config_file": config_file, "traffic_file": traffic_file,
            "chips": chips, "seed": args.seed, "seconds": args.seconds,
            "control": args.control, "fault": args.fault,
            "run_dir": run_dir, "trace_dir": trace_dir,
            "report_file": report_file,
        }, f)
    env = {"BENCH_RUN_FILE": run_file}
    if args.trace:
        first = check.STEPS + TRACE_FIRST_STEP
        env.update({"TPUJOB_PROFILE_DIR": trace_dir,
                    "TPUJOB_PROFILE_START": str(first),
                    "TPUJOB_PROFILE_STEPS": str(TRACE_STEPS)})
    job = build_job(conf, chips, env)
    t_submit = time.monotonic()
    final, logs = run_job(job, timeout=1100.0, workdir=ROOT)
    out, err = logs.get("default/bench-worker-0", ("", ""))
    if not is_succeeded(final.status) or not os.path.exists(report_file):
        conds = [(c.type, c.reason) for c in final.status.conditions]
        fail(f"the job did not succeed: {conds}\n--- worker stderr ---\n"
             f"{err[-6000:]}")
    with open(report_file) as f:
        report = json.load(f)

    counts = importlib.import_module(f"counts.{conf['counts']}")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = report["device"]["kind"]
    if kind not in peaks and conf["accelerator"] != "cpu":
        fail(f"no peaks for device kind {kind!r} in peaks.json")
    record = {
        "cell": cell, "conf": conf, "traffic": traffic, "chips": chips,
        "report": report, "trace": report.get("trace"),
        "peaks": peaks.get(kind), "counts": counts,
        "t0": _T0, "t_submit": t_submit,
    }
    names = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = metric_values(
        [m for m in names if reports_in(m, cell["name"])], record)

    numbers = {k: tuple(v) for k, v in report["numbers"].items()}
    correct, compared, not_compared = check.verdict(numbers, limits)
    device = dict(report["device"])
    breakdown = None
    if args.trace:
        trace = report["trace"]
        if not trace or not trace["busy_s"] > 0:
            fail("the traced steps show no operation on a device")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        breakdown = {"device_ops": trace["device_ops"][:10],
                     "idle_gaps": trace["idle_gaps"][:10]}
    step_ms = sorted(report["step_ms"])
    median_ms = step_ms[len(step_ms) // 2]
    notes = {
        "steps": report["steps"], "window_s": report["window_s"],
        # a run that reads far off says where: the steps that took over
        # 1.25 medians, by their place in the window, and the feed's seconds
        "step_ms_median": median_ms, "input_s": report["input_s"],
        "slow_steps": [[i, ms] for i, ms in enumerate(report["step_ms"])
                       if ms > 1.25 * median_ms][:10],
        "reference_s": report["reference_s"],
        "after_window_s": time.monotonic() - report["marks"]["window_close"],
        "numbers": {k: v[0] for k, v in numbers.items()},
        "delta_gap_leaf": numbers["delta_gap"][1],
        "not_compared": not_compared,
        "program": report["program"], "reference": report["reference"],
    }
    print("benchmark: " + json.dumps(notes), file=sys.stderr)
    print("benchmark: compared (value, limit): " + json.dumps(compared),
          file=sys.stderr, flush=True)
    print(result_line({
        "correct": correct, "attempted": report["steps"], "failed": 0,
        "metrics": metrics, "device": device, "breakdown": breakdown,
        "compared": compared,
    }, args.trace), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
