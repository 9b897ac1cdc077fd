"""The Mellum 2 configuration's side of the benchmark: its file against the
catalog's rules, the hand-worked counts, the plain reference against the
program in float32 at a tiny size of the same shape (a period of 3 window
layers and 1 full one, 8 experts of which 2 a token and half held, a window
shorter than T) with bf16 in the program's place failing the same
comparison, the new readers on hand-built records, and one run of the tiny
cell through the whole harness on the CPU. Fast enough for tier-1, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mellum.py -q
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import check  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
MELLUM_SPEC = os.path.join(HERE, "BENCHMARK.mellum-tiny.json")
NAME = "mellum2-12b-a2.5b-l4-e16"
CELL = "mellum2.steady-8k"
NEW_METRICS = ("moe_ms", "moe_router_ms", "moe_dispatch_combine_ms",
               "moe_experts_roofline_share", "banded_flash_roofline_share",
               "moe_load_max_over_mean", "moe_assignments_held_share")
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "sliding_window", "rms_norm_eps")


def _conf(name=NAME):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(HERE, "tiny-mellum-cpu.json")) as f:
        return json.load(f)


# -- the configuration's file and the cell's entries ------------------------

def test_the_file_keeps_every_width_and_states_its_cuts():
    c = _conf()
    published = {"hidden_size": 2304, "intermediate_size": 7168,
                 "moe_intermediate_size": 896, "head_dim": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 4,
                 "num_experts_per_tok": 8, "sliding_window": 1024,
                 "rms_norm_eps": 1e-6}
    assert {k: c[k] for k in WIDTHS} == published
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "mlp_layer_types", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                              "vocab_size": 98304}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 24576)
    # one whole period, in the published order and ratio
    assert c["layer_types"] == 3 * ["sliding_attention"] + ["full_attention"]
    assert c["mlp_layer_types"] == 4 * ["sparse"]
    held = c["stands_for"]["experts_held"]
    assert (held["first"], held["count"], held["of"]) == (0, 16, 64)
    assert c["stands_for"]["chips_sharing_a_layer"] == 4
    assert set(c["assumed"]["not_in_the_config_so_not_computed"]) == {
        "qk_norm", "auxiliary_loss", "multi_token_head"}
    yarn = c["rope_parameters"]["full_attention"]
    assert (yarn["factor"], yarn["attention_factor"]) == (
        16, 1.2772588722239782)


def test_the_cell_and_its_metrics_are_entered_at_the_lists_ends():
    assert SPEC["configs"][-1]["name"] == NAME
    cell = SPEC["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, NAME, "steady-8k", 1)
    assert tuple(m["name"] for m in SPEC["per_layer"][-7:]) == NEW_METRICS
    for m in SPEC["per_layer"][-7:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
    traffic = json.load(open(os.path.join(BENCH, "traffic", "steady-8k.json")))
    assert (traffic["generator"], traffic["rows_per_chip"],
            traffic["seq_len"]) == ("uniform_tokens", 2, 8192)


def _with(conf, path, value):
    """A copy of ``conf`` with the key at ``path`` set to ``value``."""
    if len(path) == 1:
        return dict(conf, **{path[0]: value})
    return dict(conf, **{path[0]: _with(conf[path[0]], path[1:], value)})


@pytest.mark.parametrize("path,value", [
    # the program rotates window layers at the plain theta and nothing else
    (("rope_parameters", "sliding_attention", "rope_type"), "yarn"),
    (("rope_parameters", "sliding_attention", "rope_theta"), 10000),
    (("rope_parameters", "full_attention", "rope_type"), "llama3"),
    (("mlp_layer_types",), ["sparse", "dense", "sparse", "sparse"]),
])
def test_the_adapter_refuses_what_the_program_does_not_compute(path, value):
    adapter = importlib.import_module("adapters.mellum")
    assert adapter.config(_conf()).yarn_full.factor == 16
    with pytest.raises(ValueError):
        adapter.config(_with(_conf(), path, value))


# -- counts -----------------------------------------------------------------

def test_counts_by_hand():
    counts = importlib.import_module("counts.mellum")
    reference = importlib.import_module("reference.mellum")
    c = _conf()
    # a layer outside its experts: q 2304 x 4096, k and v 2304 x 512 each,
    # o 4096 x 2304, the router 2304 x 64; an expert 3 x 2304 x 896
    dense = 2304 * (4096 + 2 * 512) + 4096 * 2304 + 2304 * 64
    expert = 3 * 2304 * 896
    assert counts.dense_params_per_layer(c) == dense
    hand = (4 * (dense + 2 * 2304 + 16 * expert) + 2 * 24576 * 2304 + 2304)
    assert counts.param_count(c) == hand
    assert hand / 1e6 == pytest.approx(595.2, abs=0.05)
    total = 0
    for shape, _std in reference.param_shapes(c).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    assert total == hand
    # forward, a token, at T = 8192: a layer's matmuls with 2 of its 8
    # assignments held; a window query meets min(i + 1, 1024) keys
    layer = 2 * (dense + 2 * expert)
    window_keys = (1024 * 1025 / 2 + (8192 - 1024) * 1024) / 8192
    window, full = 4 * 4096 * window_keys, 4 * 4096 * 8193 / 2
    head = 2 * 2304 * 24576
    assert layer / 1e6 == pytest.approx(67.5, abs=0.05)
    assert window / 1e6 == pytest.approx(15.7, abs=0.05)
    assert full / 1e6 == pytest.approx(67.1, abs=0.05)
    assert head / 1e6 == pytest.approx(113.2, abs=0.05)
    fwd = 4 * layer + 3 * window + full + head
    assert fwd / 1e6 == pytest.approx(497.7, abs=0.05)
    assert counts.train_flops_per_token(c, 8192) == pytest.approx(3 * fwd)
    assert 16384 * 3 * fwd / 1e12 == pytest.approx(24.5, abs=0.05)


def test_attention_and_expert_work():
    counts = importlib.import_module("counts.mellum")
    c = _conf()
    by_kind = counts.attention_step_work_by_kind(c, 2, 8192)
    flops, nbytes = counts.attention_step_work(c, 2, 8192)
    assert flops == sum(f for f, _ in by_kind.values())
    # the full layer does four times a window layer's score work
    w, f = by_kind["sliding_attention"][0] / 3, by_kind["full_attention"][0]
    assert f / w == pytest.approx(4096.5 / 960.06, rel=1e-3)
    assert nbytes == 4 * 16384 * (6 * 4096 + 6 * 512) * 2
    # a window as long as the sequence is the causal mask
    assert counts.keys_per_query(dict(c, sliding_window=8192),
                                 "sliding_attention", 8192) == 8193 / 2
    # 32,768 rows: three products forward, six backward
    flops, nbytes = counts.expert_step_work(c, 32768)
    assert flops == 9 * 2 * 32768 * 2304 * 896
    assert nbytes == ((5 * 2304 + 7 * 896) * 32768
                      + 3 * 16 * 3 * 2304 * 896) * 2
    assert counts.held_assignments_per_token(c) == 2.0


# -- the new readers ----------------------------------------------------------

MS = 1_000_000_000  # picoseconds
STEP = "jit(_bare_step)/"
FWD = STEP + "model/jvp()/while/body/closed_call/"
BWD = STEP + "model/transpose(jvp())/while/body/closed_call/checkpoint/"
FUSION = "%fusion.{} = bf16[8,2048]{{1,0}} fusion(bf16[8,2048]{{1,0}} %p), kind=kLoop"
PALLAS = ('%{}.{} = bf16[8,32,2048,128]{{3,2,1,0}} custom-call(bf16[8] %p), '
          'custom_call_target="tpu_custom_call"')


def _devices():
    ops = [
        (FUSION.format(1), 0, 2 * MS, FWD + "mlp/moe/moe_router/dot_general:"),
        (FUSION.format(2), 2 * MS, 5 * MS, FWD + "mlp/moe/moe_dispatch/sort:"),
        # the compiler's own grouped product: its name, no scope
        (PALLAS.format("ragged-dot-none", 1), 7 * MS, 18 * MS,
         "ragged-dot-none:"),
        (FUSION.format(10), 25 * MS, 2 * MS,
         FWD + "mlp/moe/moe_experts/mul:"),
        (FUSION.format(3), 27 * MS, 3 * MS, FWD + "mlp/moe/moe_combine/gather:"),
        (PALLAS.format("flash_fwd", 6), 30 * MS, 10 * MS,
         FWD + "attention/attention_window/flash_fwd/pallas_call:"),
        (PALLAS.format("ragged-dot-none", 2), 40 * MS, 18 * MS,
         "ragged-dot-none:"),
        (FUSION.format(11), 58 * MS, 2 * MS,
         BWD + "rematted_computation/mlp/moe/moe_experts/mul:"),
        (PALLAS.format("ragged-dot-none", 3), 60 * MS, 18 * MS,
         "ragged-dot-none:"),
        (FUSION.format(12), 78 * MS, 2 * MS,
         BWD + "mlp/moe/moe_experts/mul:"),
        (PALLAS.format("flash_dq", 10), 80 * MS, 8 * MS,
         BWD + "attention/attention_full/flash_dq/pallas_call:"),
        (PALLAS.format("flash_dkv", 10), 88 * MS, 12 * MS,
         BWD + "attention/attention_full/flash_dkv/pallas_call:"),
        # another program's operation, after the step
        (FUSION.format(8), 100 * MS, 1 * MS, "jit(convert)/moe/convert:"),
    ]
    modules = [("jit__bare_step", 0, 100 * MS), ("jit_convert", 100 * MS, MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def _record(counters=None, kernels=None):
    from metrics import op_names

    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    stats = {"counters": counters} if counters else {}
    record = {"trace": {}, "peaks": peaks["TPU v5 lite"], "conf": _conf(),
              "traffic": {"rows_per_chip": 2, "seq_len": 8192},
              "report": {"stepstats": stats},
              "counts": importlib.import_module("counts.mellum"),
              "op_names": op_names.reduce_by_name(_devices())}
    if kernels:
        record["named_trace"] = {"steps": 1, "kernels_ms": kernels}
    return record


def test_time_by_any_name_in_op_name():
    from metrics import op_names

    r = _record()
    assert op_names.ms(r, "moe") == pytest.approx(16.0)
    assert op_names.ms(r, "moe", op_names.GROUPED_PRODUCT) == pytest.approx(
        70.0)
    assert op_names.by_phase(r, "moe_experts") == {
        "forward": pytest.approx(2.0), "recompute": pytest.approx(2.0),
        "backward": pytest.approx(2.0)}
    assert op_names.by_phase(r, op_names.GROUPED_PRODUCT) == {
        "unscoped": pytest.approx(54.0)}
    assert op_names.ms(r, "moe_dispatch", "moe_combine") == pytest.approx(8.0)
    assert op_names.ms(r, "attention_full") == pytest.approx(20.0)
    assert op_names.ms(r, "flash_fwd") == pytest.approx(10.0)
    assert op_names.ms(r, "no_such_scope") is None
    assert op_names.reduce_by_name({}) is None


@pytest.mark.parametrize("metric,value", [
    ("moe_ms", 70.0), ("moe_router_ms", 2.0),
    ("moe_dispatch_combine_ms", 8.0),
    # 4 layers x 9 x 2 x 32768 x 2304 x 896 FLOP at 197 TF/s over 60 ms
    ("moe_experts_roofline_share",
     100 * 4 * 18 * 32768 * 2304 * 896 / 197e12 / 0.060),
    ("moe_load_max_over_mean", 1.08),
    ("moe_assignments_held_share", 25.0),
])
def test_new_readers(metric, value):
    r = _record({"moe.assignments_held": 32768.0,
                 "moe.load_max_over_mean": 1.08,
                 "moe.assignments_dropped": 0.0})
    got = importlib.import_module("metrics." + metric).read(r)
    assert got == pytest.approx(value, rel=1e-6)
    assert not (metric.endswith("roofline_share") and got > 100)


def test_banded_flash_roofline_share_counts_the_band_as_a_band():
    counts = importlib.import_module("counts.mellum")
    r = _record(kernels={"flash_fwd": 20.0, "flash_dq": 25.0,
                         "flash_dkv": 35.0})
    flops, _ = counts.attention_step_work(_conf(), 2, 8192)
    got = importlib.import_module(
        "metrics.banded_flash_roofline_share").read(r)
    assert got == pytest.approx(100 * flops / 197e12 / 0.080)
    assert got < 50  # the whole sequence as the mask would read 2.4x this


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_names_reads_nothing(metric):
    """The parent's program has no such scope or counter, and a run with
    no trace has no file: every new reader returns None and none raises."""
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    for report, trace in (({"stepstats": None}, None),
                          ({"stepstats": {"profile": {"dir": "/nowhere"}}},
                           {"step_s": 0.5}),
                          ({}, {"step_s": 0.5})):
        record = {"trace": trace, "peaks": peaks["TPU v5 lite"],
                  "conf": _conf(), "report": report,
                  "traffic": {"rows_per_chip": 2, "seq_len": 8192},
                  "counts": importlib.import_module("counts.mellum")}
        assert importlib.import_module(
            "metrics." + metric).read(record) is None


# -- the reference against the program, tiny, on the CPU --------------------

@pytest.fixture(scope="module")
def tiny_readings():
    """(the program in float32, the program under the control (fp8 in
    every product, ``run.py --control``), the reference in float32, the
    reference with bf16 products): the readings
    ``correct`` compares, after three steps on the same seeded rows."""
    import jax
    import jax.numpy as jnp

    import weights
    from mpi_operator_tpu.ops import Trainer, TrainerConfig
    from mpi_operator_tpu.runtime.topology import MeshPlan, build_mesh

    conf = _tiny()
    adapter = importlib.import_module("adapters.mellum")
    reference = importlib.import_module("reference.mellum")
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

    def program_run(control):
        cfg = adapter.config(dict(conf, assumed=dict(
            conf["assumed"], compute_dtype="float32")), control=control)
        trainer = Trainer(
            adapter.loss_fn(cfg, mesh), adapter.logical_axes(cfg), mesh,
            TrainerConfig(learning_rate=opt["learning_rate"],
                          beta1=opt["beta1"], beta2=opt["beta2"],
                          weight_decay=opt["weight_decay"],
                          grad_clip_norm=opt["grad_clip_norm"]))
        state = trainer.init_state(adapter.to_tree(weights.draw(shapes, key)))
        program = {"loss": [], "counters": None}
        for i, batch in enumerate(batches):
            state, metrics = trainer.train_step(state, batch)
            program["loss"].append(float(metrics["loss"]))
            if i == 0:
                program["counters"] = {
                    k: float(v) for k, v in metrics.items() if "moe." in k}
                program["gnorm"] = float(metrics["grad_norm"])
                mu = adapter.to_flat(state.opt_state[1][0].mu)
                program["grad_norm"] = {
                    k: float(jnp.linalg.norm(v)) / (1 - opt["beta1"])
                    for k, v in mu.items()}
        flat = adapter.to_flat(state.params)
        program["delta_norm"] = {
            k: float(jnp.linalg.norm(
                flat[k] - weights.draw_leaf(shapes, k, key))) for k in shapes}
        return program

    return (program_run(False), program_run(True), ref_run(jnp.float32),
            ref_run(jnp.bfloat16))


def test_reference_agrees_with_the_program_in_float32(tiny_readings):
    program, _, ref, _ = tiny_readings
    numbers = check.compare(program, ref)
    assert {"grad_gap.router", "grad_gap.w_gate", "grad_gap.wq"} <= set(
        numbers)
    # float32 on both sides: the sort, the grouped product, the banded
    # kernels' off-TPU path and YaRN against a plain loop over the experts
    # and a mask on positions. 2e-5 is ten times what they read (8e-7 at
    # most) and a tenth of what bf16 reads below.
    assert all(v < 2e-5 for v, _leaf in numbers.values()), numbers
    assert program["counters"]["moe.assignments_dropped"] == 0
    assert 44 <= program["counters"]["moe.assignments_held"] <= 84


def test_bf16_in_the_programs_place_fails_the_same_comparison(tiny_readings):
    program, _, ref, control = tiny_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    ok, _, _ = check.verdict(check.compare(program, ref), limits)
    bad, compared, _ = check.verdict(check.compare(control, ref), limits)
    assert ok and not bad, compared


def test_the_control_fails_it_too_on_the_experts_and_the_attention(
        tiny_readings):
    """``run.py --control`` (``adapters/mellum.config(conf, control=True)``)
    switches on the program's own fp8 expert products and rounds the
    attention's projections and the head to fp8: held to the float32
    program's limits it comes out not correct, on the experts' own leaves
    and on the attention's."""
    program, control, ref, _ = tiny_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    bad, compared, _ = check.verdict(check.compare(control, ref), limits)
    assert not bad
    assert all(compared[f"grad_gap.{k}"][0] > 2e-5
               for k in ("w_gate", "w_up", "w_down", "wq", "wo"))


def test_the_control_rounds_to_three_mantissa_bits_straight_through():
    import jax
    import jax.numpy as jnp

    adapter = importlib.import_module("adapters.mellum")
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 8), jnp.float32)
    rounded = adapter._fp8(w)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    want = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # e4m3's grid, but for its subnormals (under 2**-6 of 448)
    normal = jnp.abs(w / scale) >= 2.0 ** -6
    assert bool(jnp.all(jnp.where(normal, rounded == want, True)))
    assert 0.01 < float(jnp.linalg.norm(rounded - w) / jnp.linalg.norm(w)) < 0.05
    # the gradient goes to the matrix it was rounded from, whole
    grad = jax.grad(lambda a: jnp.sum(adapter._fp8(a) * 2.0))(w)
    assert bool(jnp.all(grad == 2.0))
    # the sound path reads its parameters as they are
    cfg = adapter.config(_conf())
    assert cfg.matmul_precision == "bf16"
    assert adapter.config(_conf(), control=True).matmul_precision == "fp8"


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "mellum.py")) as f:
        source = f.read()
    assert "mpi_operator_tpu" not in source.split('"""', 2)[2]
    assert "HIGHEST" in source


# -- the tiny cell through the whole harness ---------------------------------

def test_the_tiny_cell_runs_and_its_counters_reach_the_report(tmp_path):
    keep = str(tmp_path / "keep")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--spec", MELLUM_SPEC,
         "--workload", "tiny.mellum", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0", "--keep", keep],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert "grad_gap.router" in line["compared"]
    report = json.load(open(os.path.join(keep, "report.json")))
    counters = report["stepstats"]["counters"]
    assert counters["moe.assignments_dropped"] == 0
    # 2 rows x 32 ids x 2 experts a token, half the experts held
    assert 40 <= counters["moe.assignments_held"] <= 88
    record = {"report": report, "conf": _tiny(),
              "traffic": {"rows_per_chip": 2, "seq_len": 32}}
    assert importlib.import_module(
        "metrics.moe_assignments_held_share").read(record) == pytest.approx(
            100 * counters["moe.assignments_held"] / 128)
    assert importlib.import_module(
        "metrics.moe_load_max_over_mean").read(record) >= 1.0
