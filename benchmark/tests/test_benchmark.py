"""The benchmark's own tests: fast, no chip. Run them with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They live under the benchmark's path because a benchmark PR changes no file
outside it; ``pytest tests/`` (tier-1) does not collect them.
"""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import check  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TINY_SPEC = os.path.join(HERE, "BENCHMARK.tiny.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# -- BENCHMARK.json -------------------------------------------------------

def _named():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_named()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_entry_is_well_formed(group, entry):
    assert NAME.match(entry["name"])
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[group]
    assert set(entry) <= allowed
    for key in ("why", "layer", "source"):
        if key in entry and group in ("configs", "workloads", "per_layer"):
            if key != "source" or group == "configs":
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    if group == "configs":
        assert os.path.exists(os.path.join(ROOT, entry["file"]))
        assert entry["file"].startswith(SPEC["paths"][0] + "/")
        conf = json.load(open(os.path.join(ROOT, entry["file"])))
        assert conf["reduced"] == entry["reduced"]
        for mod in ("adapter", "reference", "counts"):
            pkg = {"adapter": "adapters"}.get(mod, mod)
            assert os.path.exists(
                os.path.join(BENCH, pkg, conf[mod] + ".py"))
    if group == "workloads":
        assert NAME.match(entry["traffic"]) and entry["chips"] in (1, 4)
        assert entry["config"] in {c["name"] for c in SPEC["configs"]}
        traffic = json.load(open(os.path.join(
            BENCH, "traffic", entry["traffic"] + ".json")))
        generator = importlib.import_module(
            "generators." + traffic["generator"])
        assert generator.tokens_per_step(traffic, entry["chips"]) > 0
        limits = json.load(open(os.path.join(
            BENCH, "limits", entry["name"] + ".json")))["limits"]
        assert all(0 < v < 1 for v in limits.values())
        assert {"loss1_gap", "delta_gap"} <= set(limits)
        assert any(k.startswith("grad_gap.") for k in limits)
    if group in ("end_to_end", "per_layer"):
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
        assert hasattr(importlib.import_module(
            "metrics." + entry["name"]), "read")
    if group == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    if group == "per_layer":
        e2e = {m["name"]: m for m in SPEC["end_to_end"]}
        cells = [w["name"] for w in SPEC["workloads"]]
        for cell in entry.get("workloads", cells):
            assert cell in cells
            assert run.reports_in(e2e[entry["moves"]], cell)


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert [m["name"] for m in SPEC["end_to_end"]] == [
        "tokens_per_s_per_chip", "setup_s"]
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert any("mfu" in m["name"].split("_") for m in SPEC["per_layer"])


# -- counts ---------------------------------------------------------------

@pytest.mark.parametrize("name,seq,gflop", [
    ("mistral-7b-v0.3-l2", 2048, 3.52), ("mistral-7b-v0.3-l2", 16384, 4.23),
    ("codestral-22b-v0.1-l4", 2048, 10.9),
])
def test_train_flops_per_token(name, seq, gflop):
    counts = importlib.import_module("counts.mistral")
    c = _conf(name)
    # by hand: 6 x matmul parameters (head included, embedding not) plus
    # 3 x layers x 4 x q_dim x (T + 1) / 2 for the causal attention
    d, q, kv = c["hidden_size"], c["num_attention_heads"] * 128, 8 * 128
    per_layer = d * (q + 2 * kv) + q * d + 3 * d * c["intermediate_size"]
    n = c["num_hidden_layers"]
    hand = 6 * (n * per_layer + d * c["vocab_size"]) \
        + 3 * n * 4 * q * (seq + 1) / 2
    assert counts.train_flops_per_token(c, seq) == pytest.approx(hand)
    assert hand / 1e9 == pytest.approx(gflop, abs=0.05)


@pytest.mark.parametrize("name,millions", [
    ("mistral-7b-v0.3-l2", 704.7), ("codestral-22b-v0.1-l4", 1963.0)])
def test_param_count(name, millions):
    counts = importlib.import_module("counts.mistral")
    reference = importlib.import_module("reference.mistral")
    c = _conf(name)
    assert counts.param_count(c) / 1e6 == pytest.approx(millions, abs=0.5)
    total = 0
    for shape, _std in reference.param_shapes(c).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    assert total == counts.param_count(c)


def test_attention_work_is_a_small_share_at_2k_and_large_at_16k():
    counts = importlib.import_module("counts.mistral")
    c = _conf("mistral-7b-v0.3-l2")
    for seq, share in ((2048, 0.03), (16384, 0.19)):
        flops, nbytes = counts.attention_step_work(c, 16384 // seq, seq)
        whole = 16384 * counts.train_flops_per_token(c, seq)
        assert flops / whole == pytest.approx(share, abs=0.01)
        assert nbytes > 0


# -- the trace's arithmetic -----------------------------------------------

PALLAS = ('%closed_call.16 = (bf16[8,32,2048,128]{3,2,1,0:T(8,128)(2,1)}, '
          'f32[8,32,2048,1]{3,2,1,0}) custom-call(bf16[8,32,2048,128]{3,2,1,0} '
          '%pad_maximum_fusion.10), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("text,kind", [
    (PALLAS, "pallas"),
    ("%custom-call.14 = f32[] custom-call(f32[] %x), "
     'custom_call_target="Sharding"', "custom-call"),
    ("%all-gather-start.3 = (f32[4]{0}, f32[16]{0:T(8,128)S(1)}) "
     "all-gather-start(f32[4]{0} %p), dimensions={0}", "all-gather"),
    ("%reduce-scatter.2 = f32[4]{0} reduce-scatter(f32[16]{0} %p)",
     "reduce-scatter"),
    # an operand's name is not the operation
    ("%fusion.9 = bf16[8,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} "
     "%all-gather-done.3, f32[] %custom-call.14), kind=kLoop", "fusion"),
    ("%while.3 = (s32[]{:T(128)}, f32[2,4096]{1,0}) while(%tuple.1), "
     "condition=%c, body=%b", "while"),
    ("fusion.12", "fusion"), ("all-gather.1", "all-gather"),
])
def test_op_kind(text, kind):
    assert trace_reduce.op_kind(text) == kind


def test_short_name_keeps_name_and_result():
    assert trace_reduce.short_name(PALLAS) == (
        "closed_call.16 (bf16[8,32,2048,128],f32[8,32,2048,1])")
    assert trace_reduce.short_name("fusion.12") == "fusion.12"


def test_trace_reduce_on_hand_built_events():
    ms = 1_000_000
    dev0 = {"ops": [("while.1", 0, 50 * ms),  # holds the next two
                    ("fusion.1", 0, 40 * ms), ("all-gather.1", 40 * ms, 10 * ms),
                    (PALLAS, 60 * ms, 20 * ms), ("fusion.2", 80 * ms, 20 * ms)],
            "modules": [("jit__bare_step", 0, 100 * ms),
                        ("jit_convert", 0, 1 * ms)]}
    # chip 1: the collective wholly under a fusion, and a step the trace cut
    dev1 = {"ops": [("fusion.1", 0, 60 * ms), ("all-gather.1", 40 * ms, 10 * ms),
                    (PALLAS, 60 * ms, 20 * ms), ("fusion.2", 80 * ms, 20 * ms)],
            "modules": [("jit__bare_step", 0, 100 * ms),
                        ("jit__bare_step", 100 * ms, 0)]}
    host = [("bench.wait", 45 * ms, 20 * ms), ("bench.input", 0, 5 * ms)]
    r = trace_reduce.reduce_events({"/device:TPU:0": dev0,
                                    "/device:TPU:1": dev1}, host)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx((0.090 + 0.100) / 2)
    assert r["collective_s"] == pytest.approx(0.010)
    assert r["collective_exposed_s"] == pytest.approx(0.010 / 2)
    assert r["whole_steps"] == 1 and r["step_s"] == pytest.approx(0.100)
    assert r["flash_step_s"] == pytest.approx(0.020)
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(0.010 / 2)]]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.050)]
    assert "while.1" not in dict(r["device_ops"])
    assert trace_reduce.reduce_events({}, host) is None


# -- readers that take their number from the trace ------------------------

def _record(trace, seq=2048, rows=8):
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    return {"trace": trace, "peaks": peaks["TPU v5 lite"],
            "conf": _conf("mistral-7b-v0.3-l2"),
            "traffic": {"rows_per_chip": rows, "seq_len": seq},
            "counts": importlib.import_module("counts.mistral")}


@pytest.mark.parametrize("metric,trace,value", [
    # 16384 tokens x 3.52 GFLOP in half a second of a 197 TF/s chip
    ("step_mfu", {"step_s": 0.5}, 100 * 16384 * 3.5233e9 / (0.5 * 197e12)),
    ("step_mfu", {"step_s": None}, None),
    ("step_mfu", None, None),
    ("collective_exposed_share", {"collective_s": 0.2, "window_s": 4.0,
                                  "collective_exposed_s": 0.1}, 2.5),
    # a cell with no collective reads nothing, never 0
    ("collective_exposed_share", {"collective_s": 0.0, "window_s": 4.0,
                                  "collective_exposed_s": 0.0}, None),
    ("device_idle_share", {"busy_s": 0.9, "window_s": 1.0}, 10.0),
    ("flash_roofline_share", {"flash_step_s": None}, None),
])
def test_trace_readers(metric, trace, value):
    got = importlib.import_module("metrics." + metric).read(_record(trace))
    assert got == (None if value is None else pytest.approx(value, rel=1e-3))


# -- the rule that sets limits ---------------------------------------------

def _err(tmp_path, name, **numbers):
    path = tmp_path / name
    path.write_text("noise\nbenchmark: " + json.dumps({"numbers": numbers})
                    + "\n")
    return str(path)


def test_set_limits_takes_the_cells_own_readings(tmp_path, capsys):
    import set_limits

    sound = [_err(tmp_path, f"s{i}", a=x, b=1e-4, delta_gap=1e-5, c=1e-3)
             for i, x in enumerate((1e-6, 3e-6, 2e-6))]
    control = [_err(tmp_path, "c", a=1e-5, b=2e-4, delta_gap=2e-5, c=2e-3)]
    fault = [_err(tmp_path, "h", a=2e-5, b=5e-3, delta_gap=0.2, c=5e-3)]
    set_limits.main(["--cell", "x", "--origin", "a test", "--sound", *sound,
                     "--control", *control, "--fault", "half_batch", *fault])
    out = json.loads(capsys.readouterr().out)
    # a: the control reads 3.3 times the largest sound run: it is the upper
    # reading, although the fault reads higher; the limit lies between
    assert out["readings"]["a"]["upper_from"] == "control"
    assert 3e-6 < out["limits"]["a"] < 1e-5
    # b: the control is under three times, the fault over ten
    assert out["readings"]["b"]["upper_from"] == "half_batch"
    assert out["limits"]["delta_gap"] < 0.2
    # c: nothing reads far enough above the sound runs: not compared
    assert "c" in out["not_compared"] and "c" not in out["limits"]
    # and no reading can be handed in from elsewhere
    with pytest.raises(SystemExit):
        set_limits.main(["--cell", "x", "--origin", "o", "--sound", *sound,
                         "--control", *control, "--lower", "a", "1", "why"])


def test_set_limits_refuses_a_control_that_fails_no_limit(tmp_path):
    import set_limits

    sound = [_err(tmp_path, "s", a=1e-6, delta_gap=0.5)]
    control = [_err(tmp_path, "c", a=2e-6, delta_gap=0.5)]
    with pytest.raises(SystemExit, match="fails no limit"):
        set_limits.main(["--cell", "x", "--origin", "o", "--sound", *sound,
                         "--control", *control])


# -- reference against the program, and the control -----------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """Three steps of the program's Trainer and of the reference on the
    CPU at the tiny size, plus the reference with bf16 products in the
    program's place."""
    import jax
    import jax.numpy as jnp

    import weights
    from mpi_operator_tpu.ops import Trainer, TrainerConfig
    from mpi_operator_tpu.runtime.topology import MeshPlan, build_mesh

    conf = json.load(open(os.path.join(HERE, "tiny-cpu.json")))
    adapter = importlib.import_module("adapters.llama")
    reference = importlib.import_module("reference.mistral")
    opt = conf["assumed"]["optimizer"]
    shapes = reference.param_shapes(conf)
    key = weights.seed_key(2 ** 31 + 11)
    tr = json.load(open(os.path.join(HERE, "traffic", "tiny.json")))
    generator = importlib.import_module("generators." + tr["generator"])
    batches = [generator.batch(conf, tr, 7, s, 1) for s in (1, 2, 3)]

    def ref_run(dtype):
        return check.reference_steps(
            lambda p, b: reference.loss(conf, p, b, compute_dtype=dtype),
            weights.draw(shapes, key),
            lambda k: weights.draw_leaf(shapes, k, key), batches, opt)

    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
    cfg = adapter.config(dict(conf, assumed=dict(
        conf["assumed"], compute_dtype="float32")))
    trainer = Trainer(
        adapter.loss_fn(cfg, mesh), adapter.logical_axes(cfg), mesh,
        TrainerConfig(learning_rate=opt["learning_rate"],
                      beta1=opt["beta1"], beta2=opt["beta2"],
                      weight_decay=opt["weight_decay"],
                      grad_clip_norm=opt["grad_clip_norm"]))
    state = trainer.init_state(adapter.to_tree(weights.draw(shapes, key)))
    program = {"loss": []}
    for i, batch in enumerate(batches):
        state, metrics = trainer.train_step(state, batch)
        program["loss"].append(float(metrics["loss"]))
        if i == 0:
            program["gnorm"] = float(metrics["grad_norm"])
            mu = adapter.to_flat(state.opt_state[1][0].mu)
            program["grad_norm"] = {
                k: float(jnp.linalg.norm(v)) / (1 - opt["beta1"])
                for k, v in mu.items()}
    flat = adapter.to_flat(state.params)
    program["delta_norm"] = {
        k: float(jnp.linalg.norm(flat[k] - weights.draw_leaf(shapes, k, key)))
        for k in shapes}
    return program, ref_run(jnp.float32), ref_run(jnp.bfloat16)


def test_reference_agrees_with_the_program_in_float32(tiny_readings):
    program, ref, _ = tiny_readings
    numbers = check.compare(program, ref)
    assert all(v < 2e-4 for v, _leaf in numbers.values()), numbers


def test_bf16_in_the_programs_place_fails_the_same_comparison(tiny_readings):
    program, ref, control = tiny_readings
    limits = {k: 2e-4 for k in check.compare(program, ref)}
    ok, _, _ = check.verdict(check.compare(program, ref), limits)
    bad, compared, _ = check.verdict(check.compare(control, ref), limits)
    assert ok and not bad, compared


def test_verdict_fails_on_a_missing_number_or_a_nan():
    limits = {"a": 0.1, "b": 0.1}
    fine = {"a": (0.01, None), "b": (0.0, None), "c": (9.0, None)}
    assert check.verdict(fine, limits) == (
        True, {"a": [0.01, 0.1], "b": [0.0, 0.1]}, ["c"])
    assert not check.verdict({"a": (0.01, None)}, limits)[0]
    assert not check.verdict(
        {"a": (float("nan"), None), "b": (0.0, None)}, limits)[0]


def test_a_leaf_that_has_not_moved_reads_one():
    ref = {"loss": [1.0], "grad_norm": {"a": 1.0, "b": 2.0, "c": 1e-9},
           "delta_norm": {"a": 0.5, "b": 0.5, "c": 0.5}}
    still = dict(ref, delta_norm={"a": 0.0, "b": 0.5, "c": 0.5})
    numbers = check.compare(still, ref)
    assert numbers["delta_gap"] == (1.0, "a")
    # c's gradient is nought to rounding: its change is not compared
    twice = dict(ref, delta_norm={"a": 0.5, "b": 0.5, "c": 1.0})
    assert check.compare(twice, ref)["delta_gap"][0] == 0.0
    assert numbers["grad_gap.a"][0] == 0.0


# -- the harness end to end, tiny, on the CPU -----------------------------

def _run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--spec", TINY_SPEC,
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0",
         *extra], env=env, capture_output=True, text=True, timeout=300)
    return proc


def test_last_line_has_exactly_the_contracts_keys():
    proc = _run("--workload", "tiny.one")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "benchmark: compared (value, limit): ")


@pytest.mark.parametrize("cell,fault", [
    ("tiny.one", "state_unchanged"), ("tiny.one", "half_batch"),
    ("tiny.four", "no_exchange")])
def test_a_fault_under_the_timed_path_reads_not_correct(cell, fault):
    """The whole of a run but the look for a chip, with the timed path
    broken underneath: ``correct`` has to come out false."""
    proc = _run("--workload", cell, "--fault", fault)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert any(v > lim for v, lim in line["compared"].values())


def test_traced_line_carries_the_trace_keys():
    values = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 1, "busy_s": 1.0,
                         "window_s": 1.0, "extra": 0},
              "breakdown": {"device_ops": [], "idle_gaps": []},
              "compared": {}}
    line = json.loads(run.result_line(values, trace=1))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert list(line["device"]) == ["platform", "kind", "count",
                                    "memory_peak_bytes", "busy_s", "window_s"]


def test_a_tpu_cell_fails_off_the_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b.steady-2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
