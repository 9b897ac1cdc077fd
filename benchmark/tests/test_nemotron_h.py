"""The Nemotron-H configuration's side of the benchmark: its file against
the catalog's rules, the hand-worked counts, the plain reference (the scan
as the literal recurrence) against the program (the chunked scan) in float32
at a tiny size of the same shape (a pattern holding all three kinds of
layer, 8 experts of which 2 a token and half held, four chunks a sequence)
with bf16 in the program's place failing the same comparison and a scan
that does not pass its states failing it too, the new readers on hand-built
records, and one run of the tiny cell through the whole harness on the CPU.
Entries of ``BENCHMARK.json`` are found by name, never by their place in a
list. Fast enough for tier-1, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_nemotron_h.py -q
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
TINY_SPEC = os.path.join(HERE, "BENCHMARK.nemotron-tiny.json")
NAME = "nemotron-3-nano-30b-a3b-l9-e8"
CELL = "nemotron3nano.steady-8k"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
          "/blob/main/config.json")
NEW_METRICS = {
    "mamba_ms": ("ms", "lower", "device_trace", "state-space layers"),
    "ssm_scan_ms": ("ms", "lower", "device_trace", "state-space layers"),
    "ssm_scan_roofline_share": ("%", "higher", "device_trace",
                                "state-space layers"),
    "ssm_conv_gate_ms": ("ms", "lower", "device_trace", "state-space layers"),
    "moe_shared_ms": ("ms", "lower", "device_trace", "routed feed-forward"),
    "ssm_carry_share": ("%", "higher", "program_counter",
                        "state-space layers"),
}
PUBLISHED = {
    "hidden_size": 2688, "num_attention_heads": 32, "num_key_value_heads": 2,
    "head_dim": 128, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "intermediate_size": 1856, "expand": 2,
    "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
}


def _conf(name=NAME):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(HERE, "tiny-nemotron-cpu.json")) as f:
        return json.load(f)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["source_url"] == SOURCE)


# -- the configuration's file and the cell's entries ------------------------

def test_nemotron_the_file_keeps_every_width_and_states_its_cuts():
    c = _conf()
    assert {k: c[k] for k in PUBLISHED} == PUBLISHED
    assert c["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                            "n_routed_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
            c["n_routed_experts"], c["vocab_size"]) == (
        9, "MEMEM*EME", 8, 16384)
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (52, 128, 131072)
    # the published first nine layers, every kind in its ratio
    assert pub["hybrid_override_pattern"].startswith(
        c["hybrid_override_pattern"])
    assert len(pub["hybrid_override_pattern"]) == 52
    assert [pub["hybrid_override_pattern"].count(x) for x in "ME*"] == [
        23, 23, 6]
    stands = c["stands_for"]
    assert stands["chips_sharing_a_layer"] == 16
    assert stands["pipeline_stages"] == 6
    assert stands["experts_held"] == {"first": 0, "count": 8, "of": 128}
    held = stands["vocabulary_held"]
    assert (held["first"], held["count"], held["of"]) == (0, 16384, 131072)
    assumed = c["assumed"]
    # the absent positional embedding first among what is assumed
    assert list(assumed)[0] == "position_embedding"
    assert assumed["position_embedding"]["kind"] == "none"
    assert set(assumed["published_and_unused"]) >= {
        "expand", "rope_theta", "partial_rotary_factor", "intermediate_size"}
    assert set(assumed["init_rules_not_computed"]) == {
        "time_step_min", "time_step_max", "time_step_floor"}
    assert set(assumed["not_in_the_config_so_not_computed"]) == {
        "auxiliary_loss", "bias_update"}
    assert {"A_log_std", "dt_bias_std", "conv_bias_std", "score_bias_std",
            "carry_share", "rescale_prenorm_residual"} <= set(assumed["draw"])
    # the projections that write to the residual stream start 1 / sqrt(52)
    # smaller, the others at fan_in**-0.5
    shapes = importlib.import_module("reference.nemotron_h").param_shapes(c)
    assert shapes["m_out_proj"][1] == pytest.approx(4096 ** -0.5 / 52 ** 0.5)
    assert shapes["shared_down"][1] == pytest.approx(3712 ** -0.5 / 52 ** 0.5)
    assert shapes["w_down"][1] == pytest.approx(1856 ** -0.5 / 52 ** 0.5)
    assert shapes["wo"][1] == pytest.approx(4096 ** -0.5 / 52 ** 0.5)
    assert shapes["m_in_proj"][1] == pytest.approx(2688 ** -0.5)


def test_every_number_of_the_catalogs_row_is_in_the_file_under_its_key():
    row, c = _catalog_row(), _conf()
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


def test_the_cell_and_its_metrics_are_entered_by_name():
    by = lambda group: {e["name"]: e for e in SPEC[group]}
    entry = by("configs")[NAME]
    assert entry["source"] == SOURCE == _conf()["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == _conf()["reduced"]
    cell = by("workloads")[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady-8k", 1)
    assert len(cell["why"]) <= 200
    metrics = by("per_layer")
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, source, layer)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
    # the same traffic file as the other routed configuration's cell
    assert by("workloads")["mellum2.steady-8k"]["traffic"] == cell["traffic"]
    traffic = json.load(open(os.path.join(BENCH, "traffic", "steady-8k.json")))
    assert (traffic["generator"], traffic["rows_per_chip"],
            traffic["seq_len"]) == ("uniform_tokens", 2, 8192)
    assert traffic["seq_len"] % _conf()["chunk_size"] == 0


def _with(conf, path, value):
    """A copy of ``conf`` with the key at ``path`` set to ``value``."""
    if len(path) == 1:
        return dict(conf, **{path[0]: value})
    return dict(conf, **{path[0]: _with(conf[path[0]], path[1:], value)})


@pytest.mark.parametrize("path,value", [
    (("hybrid_override_pattern",), "MEMEM-EME"),  # a letter it does not know
    (("n_group",), 8), (("topk_group",), 4),  # a group limit on the choice
    (("mlp_hidden_act",), "silu"), (("n_shared_experts",), 2),
    (("num_hidden_layers",), 8),  # not the pattern's length
    (("assumed", "position_embedding", "kind"), "rope"),
    (("mamba_proj_bias",), True),
])
def test_nemotron_the_adapter_refuses_what_the_program_does_not_compute(path, value):
    adapter = importlib.import_module("adapters.nemotron_h")
    cfg = adapter.config(_conf())
    assert cfg.layer_kinds == ("mamba", "experts", "mamba", "experts",
                               "mamba", "attention", "experts", "mamba",
                               "experts")
    assert (cfg.router_score, cfg.router_scale, cfg.experts_gated,
            cfg.d_shared) == ("sigmoid", 2.5, False, 3712)
    with pytest.raises(ValueError):
        adapter.config(_with(_conf(), path, value))


# -- counts -----------------------------------------------------------------

def test_nemotron_counts_by_hand():
    counts = importlib.import_module("counts.nemotron_h")
    reference = importlib.import_module("reference.nemotron_h")
    c = _conf()
    # a Mamba layer: in 2688 x 10304 (z 4096 | xBC 6144 | dt 64), out 4096 x
    # 2688, the convolution's 6144 x 4 taps and 6144 biases, three numbers a
    # head, the gated norm's 4096 and the layer's norm
    mamba = (2688 * 10304 + 4096 * 2688 + 6144 * 5 + 3 * 64 + 4096 + 2688)
    assert counts.mamba_params(c) == mamba
    assert mamba / 1e6 == pytest.approx(38.74, abs=0.005)
    attention = 2688 * (4096 + 2 * 256) + 4096 * 2688 + 2688
    assert attention / 1e6 == pytest.approx(23.40, abs=0.005)
    expert, shared, router = 2 * 2688 * 1856, 2 * 2688 * 3712, 2688 * 128
    assert (counts.expert_params(c), counts.shared_params(c),
            counts.router_params(c)) == (expert, shared, router)
    assert (expert / 1e6, shared / 1e6, router / 1e6) == (
        pytest.approx(9.98, abs=0.005), pytest.approx(19.96, abs=0.005),
        pytest.approx(0.34, abs=0.005))
    hand = (4 * mamba + attention
            + 4 * (8 * expert + shared + router + 128 + 2688)
            + 2 * 16384 * 2688 + 2688)
    assert counts.param_count(c) == hand
    assert hand / 1e6 == pytest.approx(667.0, abs=0.05)
    total = 0
    for shape, _std in reference.param_shapes(c).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    assert total == hand
    # sixteen held experts, an eight-way share, would not fit at 16 B a
    # parameter: (hand + 4 x 8 x expert) x 16 B = 15.8 GB
    assert (hand + 32 * expert) * 16 / 1e9 == pytest.approx(15.8, abs=0.05)

    # forward, a token, at T = 8192
    projections = 4 * 2 * (2688 * 10304 + 4096 * 2688)
    inside = (128 + 1) / 2 * 2 * (8 * 128 + 64 * 64)  # a causal chunk
    scan = 4 * (inside + 2 * 2 * 64 * 64 * 128)
    whole_chunks = 4 * (128 * 2 * (8 * 128 + 64 * 64) + 2 * 2 * 64 * 64 * 128)
    shared_f, routed_f = 4 * 2 * shared, 4 * 2 * (6 * 8 / 128) * expert
    head = 2 * 2688 * 16384
    attn_proj = 2 * (attention - 2688)
    attn_products = 4 * 4096 * 8193 / 2
    routers = 4 * 2 * router
    assert projections / 1e6 == pytest.approx(309.7, abs=0.05)
    assert scan / 1e6 == pytest.approx(11.0, abs=0.05)
    assert whole_chunks / 1e6 == pytest.approx(13.6, abs=0.05)
    assert attn_products / 1e6 == pytest.approx(67.1, abs=0.05)
    fwd = (projections + scan + shared_f + routed_f + head + attn_proj
           + attn_products + routers)
    assert counts.train_flops_per_token(c, 8192) == pytest.approx(3 * fwd)
    # 715.0 M with a chunk's mask counted as causal, as flash counts its
    # mask; with every chunk counted whole it is the issue's 718 M
    assert fwd / 1e6 == pytest.approx(715.0, abs=0.05)
    assert (fwd - scan + whole_chunks) / 1e6 == pytest.approx(717.6, abs=0.05)
    shares = {"mamba": (projections + scan) / fwd, "shared": shared_f / fwd,
              "head": head / fwd, "attention": attn_products / fwd,
              "routed": routed_f / fwd}
    assert shares == {
        "mamba": pytest.approx(0.45, abs=0.005),
        "shared": pytest.approx(0.22, abs=0.005),
        "head": pytest.approx(0.12, abs=0.005),
        "attention": pytest.approx(0.09, abs=0.005),
        "routed": pytest.approx(0.04, abs=0.005)}


def test_scan_attention_and_expert_work():
    counts = importlib.import_module("counts.nemotron_h")
    c = _conf()
    flops, nbytes = counts.ssd_step_work(c, 2, 8192)
    per_token = (129 / 2 * 2 * (1024 + 4096) + 4 * 64 * 64 * 128)
    assert flops == 4 * 3 * 16384 * per_token
    # x and y 4096 each, B and C 1024 each in bf16, dt 64 in float32:
    # forward 5 reads and 1 write, backward those and dy, and 4 writes
    row = (4096 + 2048) * 2 + 64 * 4
    assert nbytes == 4 * 16384 * ((row + 8192) * 2 + row)
    # bound by the bytes on a v5e, not by the products
    assert nbytes / 819e9 > flops / 197e12
    flops, nbytes = counts.attention_step_work(c, 2, 8192)
    assert flops == 3 * 16384 * 4 * 4096 * 8193 / 2
    assert nbytes == 16384 * (6 * 4096 + 6 * 256) * 2
    # 6,144 rows: two products forward, four backward
    flops, nbytes = counts.expert_step_work(c, 6144)
    assert flops == 6 * 2 * 6144 * 2688 * 1856
    assert nbytes == ((5 * 2688 + 5 * 1856) * 6144
                      + 3 * 8 * 2 * 2688 * 1856) * 2
    assert counts.held_assignments_per_token(c) == 0.375
    assert 16384 * 0.375 / 8 == 768  # rows a held expert a step


# -- the new readers ----------------------------------------------------------

MS = 1_000_000_000  # picoseconds
STEP = "jit(_bare_step)/"
FWD = STEP + "model/jvp()/while/body/closed_call/"
BWD = STEP + "model/transpose(jvp())/while/body/closed_call/checkpoint/"
FUSION = "%fusion.{} = bf16[8,2048]{{1,0}} fusion(bf16[8,2048]{{1,0}} %p), kind=kLoop"


def _devices():
    ops = [
        (FUSION.format(1), 0, 10 * MS, FWD + "mamba/ssm_in_proj/dot_general:"),
        (FUSION.format(2), 10 * MS, 2 * MS, FWD + "mamba/ssm_conv/mul:"),
        (FUSION.format(3), 12 * MS, 8 * MS, FWD + "mamba/ssm_scan/dot_general:"),
        (FUSION.format(4), 20 * MS, 3 * MS, FWD + "mamba/ssm_gate_norm/mul:"),
        (FUSION.format(5), 23 * MS, 5 * MS,
         FWD + "mamba/ssm_out_proj/dot_general:"),
        (FUSION.format(6), 28 * MS, 6 * MS,
         FWD + "mlp/moe/moe_shared/dot_general:"),
        (FUSION.format(7), 34 * MS, 1 * MS,
         FWD + "mlp/moe/moe_router/dot_general:"),
        (FUSION.format(8), 35 * MS, 8 * MS,
         BWD + "rematted_computation/mamba/ssm_scan/dot_general:"),
        (FUSION.format(9), 43 * MS, 24 * MS,
         BWD + "mamba/ssm_scan/dot_general:"),
        (FUSION.format(10), 67 * MS, 4 * MS, BWD + "mamba/ssm_conv/mul:"),
        (FUSION.format(11), 71 * MS, 12 * MS,
         BWD + "mlp/moe/moe_shared/dot_general:"),
        (FUSION.format(12), 83 * MS, 17 * MS,
         BWD + "mamba/ssm_in_proj/dot_general:"),
        # another program's operation, after the step
        (FUSION.format(13), 100 * MS, 1 * MS, "jit(convert)/mamba/convert:"),
    ]
    modules = [("jit__bare_step", 0, 100 * MS), ("jit_convert", 100 * MS, MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def _record(counters=None):
    from metrics import op_names

    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    stats = {"counters": counters} if counters else {}
    return {"trace": {}, "peaks": peaks["TPU v5 lite"], "conf": _conf(),
            "traffic": {"rows_per_chip": 2, "seq_len": 8192},
            "report": {"stepstats": stats},
            "counts": importlib.import_module("counts.nemotron_h"),
            "op_names": op_names.reduce_by_name(_devices())}


@pytest.mark.parametrize("metric,value", [
    ("mamba_ms", 81.0), ("ssm_scan_ms", 40.0), ("ssm_conv_gate_ms", 9.0),
    ("moe_shared_ms", 18.0),
    # the scans' bytes at 819 GB/s (they bound it) over 40 ms
    ("ssm_scan_roofline_share",
     100 * 4 * 16384 * ((12544 + 8192) * 2 + 12544) / 819e9 / 0.040),
    ("ssm_carry_share", 31.25),
])
def test_nemotron_new_readers(metric, value):
    r = _record({"ssm.carry_share": 0.3125, "moe.assignments_held": 6144.0})
    got = importlib.import_module("metrics." + metric).read(r)
    assert got == pytest.approx(value, rel=1e-6)
    assert not (metric.endswith("roofline_share") and got > 100)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_nemotron_a_program_without_the_names_reads_nothing(metric):
    """The parent's program has no such scope or counter, another
    configuration's counts have no ``ssd_step_work``, and a run with no
    trace has no file: every new reader returns None and none raises."""
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    for report, trace in (({"stepstats": None}, None),
                          ({"stepstats": {"profile": {"dir": "/nowhere"}}},
                           {"step_s": 0.5}),
                          ({}, {"step_s": 0.5})):
        for counts in ("counts.nemotron_h", "counts.mellum"):
            record = {"trace": trace, "peaks": peaks["TPU v5 lite"],
                      "conf": _conf(), "report": report,
                      "traffic": {"rows_per_chip": 2, "seq_len": 8192},
                      "counts": importlib.import_module(counts)}
            assert importlib.import_module(
                "metrics." + metric).read(record) is None
    from metrics import op_names

    mellum = dict(_record(), counts=importlib.import_module("counts.mellum"))
    assert op_names.ms(mellum, "ssm_scan") == pytest.approx(40.0)
    assert importlib.import_module(
        "metrics.ssm_scan_roofline_share").read(mellum) is None


# -- the reference against the program, tiny, on the CPU --------------------

def _program_run(conf, control, batches, key, patch_scan=None):
    import jax
    import jax.numpy as jnp

    import weights
    from mpi_operator_tpu.ops import Trainer, TrainerConfig
    from mpi_operator_tpu.runtime.topology import MeshPlan, build_mesh

    adapter = importlib.import_module("adapters.nemotron_h")
    reference = importlib.import_module("reference.nemotron_h")
    opt = conf["assumed"]["optimizer"]
    shapes = reference.param_shapes(conf)
    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
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
                k: float(v) for k, v in metrics.items()
                if k.startswith(("moe.", "ssm."))}
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


@pytest.fixture(scope="module")
def nemotron_readings():
    """(the program in float32, the program under the control (fp8 in
    every product, ``run.py --control``), the program with a scan that
    does not pass its states from chunk to chunk, the reference in float32,
    the reference with bf16 products): the readings ``correct`` compares,
    after three steps on the same seeded rows."""
    import jax.numpy as jnp

    import weights
    from mpi_operator_tpu.kernels import ssd

    conf = _tiny()
    reference = importlib.import_module("reference.nemotron_h")
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

    sound = _program_run(conf, False, batches, key)
    control = _program_run(conf, True, batches, key)
    real_scan = ssd.scan
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "scan", lambda *a, **kw: real_scan(
            *a, **kw, pass_states=False))
        faulty = _program_run(conf, False, batches, key)
    return sound, control, faulty, ref_run(jnp.float32), ref_run(jnp.bfloat16)


def test_nemotron_reference_agrees_with_the_program_in_float32(nemotron_readings):
    program, _, _, ref, _ = nemotron_readings
    numbers = check.compare(program, ref)
    assert {"grad_gap.m_A_log", "grad_gap.m_in_proj", "grad_gap.m_conv_w",
            "grad_gap.router", "grad_gap.w_up", "grad_gap.shared_down",
            "grad_gap.wq", "grad_gap.m_dt_bias", "grad_gap.m_D"} <= set(
        numbers)
    # float32 on both sides: the chunked scan against the recurrence,
    # shifted multiply-adds against a grouped convolution, the sort and the
    # grouped product against a plain loop over the experts. 2e-5 is some
    # ten times what they read and far under what bf16 reads below.
    assert all(v < 2e-5 for v, _leaf in numbers.values()), numbers
    counters = program["counters"]
    assert counters["moe.assignments_dropped"] == 0
    # 2 rows x 32 ids x 2 experts a token, half the experts held
    assert 40 <= counters["moe.assignments_held"] <= 88
    # the draw: some heads hand state on, some forget within a chunk
    assert 0.1 <= counters["ssm.carry_share"] <= 0.9
    # the correction bias is a buffer: no gradient, and no step moves it
    assert program["grad_norm"]["router_bias"] == 0
    assert program["delta_norm"]["router_bias"] < 1e-8


def test_nemotron_bf16_in_the_programs_place_fails_the_same_comparison(nemotron_readings):
    program, _, _, ref, bf16 = nemotron_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    ok, _, _ = check.verdict(check.compare(program, ref), limits)
    bad, compared, _ = check.verdict(check.compare(bf16, ref), limits)
    assert ok and not bad, compared


def test_a_scan_that_does_not_pass_its_states_fails_it(nemotron_readings):
    """At the configuration's draw enough heads carry state across chunks
    that leaving the pass out shows: on the loss, and on the Mamba layers'
    own leaves."""
    program, _, faulty, ref, _ = nemotron_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    bad, compared, _ = check.verdict(check.compare(faulty, ref), limits)
    assert not bad
    mamba = {k: v[0] for k, v in compared.items() if ".m_" in k}
    assert compared["loss1_gap"][0] > 1e-4, compared
    assert all(mamba[f"grad_gap.{k}"] > 1e-3
               for k in ("m_D", "m_conv_w", "m_in_proj", "m_out_proj")), mamba


def test_the_control_fails_it_too_on_every_kind_of_layer(nemotron_readings):
    """``run.py --control`` (``adapters/nemotron_h.config(conf,
    control=True)``) switches on the program's own fp8 routed expert
    products and rounds every other matrix of a bf16 product to fp8: held
    to the float32 program's limits it comes out not correct, on the Mamba
    layers' leaves, the experts' and the attention's."""
    program, control, _, ref, _ = nemotron_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    bad, compared, _ = check.verdict(check.compare(control, ref), limits)
    assert not bad
    assert all(compared[f"grad_gap.{k}"][0] > 2e-5
               for k in ("m_in_proj", "m_out_proj", "w_up", "w_down",
                         "shared_up", "wq", "wo"))


def test_the_control_rounds_the_matrices_and_nothing_else():
    import jax
    import jax.numpy as jnp

    import weights

    adapter = importlib.import_module("adapters.nemotron_h")
    reference = importlib.import_module("reference.nemotron_h")
    conf = _tiny()
    flat = weights.draw(reference.param_shapes(conf), weights.seed_key(3))
    rounded = adapter.to_flat(adapter._control(adapter.to_tree(flat)))
    for name, w in flat.items():
        same = bool(jnp.all(rounded[name] == w))
        assert same != (name in adapter._MATRICES), name
    w = flat["m_in_proj"]
    rel = float(jnp.linalg.norm(rounded["m_in_proj"] - w) / jnp.linalg.norm(w))
    assert 0.01 < rel < 0.05  # three mantissa bits
    grad = jax.grad(lambda a: jnp.sum(adapter._fp8(a) * 2.0))(w)
    assert bool(jnp.all(grad == 2.0))  # straight through
    assert adapter.config(_conf()).matmul_precision == "bf16"
    assert adapter.config(_conf(), control=True).matmul_precision == "fp8"
    # every leaf of the reference has its place in the program's tree
    assert set(adapter.to_flat(adapter.to_tree(flat))) == set(flat)


def test_nemotron_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "nemotron_h.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "mpi_operator_tpu" not in body
    assert "HIGHEST" in body
    # the scan is the recurrence: a loop over positions, no chunked form
    assert "lax.scan(step" in body and "cumsum" not in body.split(
        "def quadratic")[0]


def test_the_recurrence_and_its_dual_quadratic_form_agree():
    """The fallback, should the recurrence be too slow on the chip, is tied
    to it: forward and gradient, heads that forget and heads that keep."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("reference.nemotron_h")
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, t, h, p, n = 2, 24, 4, 8, 16
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -jnp.exp(jnp.array([-9.0, -3.0, 0.5, 7.0]))
    bm = jax.random.normal(ks[2], (b, t, h, n))
    cm = jax.random.normal(ks[3], (b, t, h, n))
    cot = jax.random.normal(ks[4], (b, t, h, p))
    through = lambda fn: jax.value_and_grad(
        lambda *args: jnp.sum(fn(*args) * cot), argnums=(0, 1, 2, 3, 4))(
            x, dt, a, bm, cm)
    want, want_g = through(lambda *v: reference.recurrence(*v, block=8))
    got, got_g = through(lambda *v: reference.quadratic(*v, q_block=8))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, rtol=2e-4,
                                   atol=2e-5 * float(jnp.max(jnp.abs(w))))


# -- the tiny cell through the whole harness ---------------------------------

def test_nemotron_the_tiny_cell_runs_and_its_counters_reach_the_report(tmp_path):
    keep = str(tmp_path / "keep")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--spec", TINY_SPEC,
         "--workload", "tiny.nemotron", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0", "--keep", keep],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"grad_gap.m_A_log", "grad_gap.router",
            "grad_gap.shared_up"} <= set(line["compared"])
    report = json.load(open(os.path.join(keep, "report.json")))
    counters = report["stepstats"]["counters"]
    assert counters["moe.assignments_dropped"] == 0
    assert 40 <= counters["moe.assignments_held"] <= 88
    assert 0.1 <= counters["ssm.carry_share"] <= 0.9
    record = {"report": report, "conf": _tiny(),
              "traffic": {"rows_per_chip": 2, "seq_len": 32}}
    assert importlib.import_module(
        "metrics.ssm_carry_share").read(record) == pytest.approx(
            100 * counters["ssm.carry_share"])
    assert importlib.import_module(
        "metrics.moe_assignments_held_share").read(record) == pytest.approx(
            100 * counters["moe.assignments_held"] / 128)
