"""The Laguna configuration's side of the benchmark (``laguna-xs.2-l5-e32``,
cell ``lagunaxs2.steady-8k``): its file against the catalog's rules, the
hand-worked counts, the adapter's tree and its round trip, the control, the
plain reference against the program in float32 at a tiny size of the same
shape (a leading dense full layer, then three window layers and a full one;
4 query heads on full layers and 6 on window layers over 2 KV heads; half of
a full layer's head rotated under YaRN at its own theta; a gate on every
head; sigmoid routing with no correction bias over 8 experts of which 2 a
token and half held, beside a shared expert) with bf16 in the program's
place and the fp8 control each failing the same comparison, YaRN's factor
on the rotated dims alone, the shares of the experts adding up to the uncut
layer, the new readers on hand-built records, and the tiny cell through the
whole harness on the CPU, sound and under the control. Entries of
``BENCHMARK.json`` are found by name, never by their place in a list. Fast
enough for tier-1, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_laguna.py -q
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
LAGUNA_SPEC = os.path.join(HERE, "BENCHMARK.laguna-tiny.json")
LAGUNA = "laguna-xs.2-l5-e32"
LAGUNA_CELL = "lagunaxs2.steady-8k"
LAGUNA_SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
LAGUNA_METRICS = {
    "head_gate_ms": ("ms", "lower", "trainer"),
    "rope_by_kind_ms": ("ms", "lower", "trainer"),
    "heads_by_kind_flash_roofline_share": ("%", "higher", "kernels"),
}
LAGUNA_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 8192,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "sliding_window": 512,
    "partial_rotary_factor": 0.5,
}


def _conf(name=LAGUNA):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(HERE, "tiny-laguna-cpu.json")) as f:
        return json.load(f)


def _modules():
    return (importlib.import_module("adapters.laguna"),
            importlib.import_module("reference.laguna"),
            importlib.import_module("counts.laguna"))


# -- the configuration's file and the cell's entries ------------------------

def test_laguna_the_file_keeps_every_width_and_states_its_cuts():
    c = _conf()
    assert {k: c[k] for k in LAGUNA_WIDTHS} == LAGUNA_WIDTHS
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "mlp_layer_types", "num_attention_heads_per_layer",
                            "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        5, 32, 12544)
    published = c["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (40, 256, 100352)
    # the cut lists are the published ones' first five: the first stage
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert c[key] == published[key][:5], key
    assert c["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert c["vocab_size"] * 8 == published["vocab_size"]
    assert c["vocab_size"] % 128 == 0
    stands = c["stands_for"]
    assert (stands["chips_sharing_a_layer"], stands["pipeline_stages"],
            stands["chips"]) == (8, 8, 64)
    assert published["num_hidden_layers"] == 8 * c["num_hidden_layers"]
    assert stands["experts_held"] == {"first": 0, "count": 32, "of": 256}
    assumed = c["assumed"]
    assert (assumed["compute_dtype"], assumed["param_dtype"],
            assumed["moment_dtype"], assumed["remat_layers"]) == (
        "bfloat16", "float32", "float32", True)
    assert {"router", "gate", "rotation"} <= set(assumed)
    assert set(assumed["not_in_the_config_so_not_computed"]) == {
        "qk_norm", "auxiliary_loss", "multi_token_head"}
    opt = assumed["optimizer"]
    assert (opt["beta1"], opt["beta2"], opt["weight_decay"],
            opt["grad_clip_norm"]) == (0.9, 0.95, 0.0, 0.25)
    _, reference, _ = _modules()
    shapes = reference.param_shapes(c)
    assert shapes["d_wq"] == ((1, 2048, 48 * 128), 2048 ** -0.5)
    assert shapes["d_wg"] == ((1, 2048, 48), 2048 ** -0.5)
    assert shapes["d_w_gate"] == ((1, 2048, 8192), 2048 ** -0.5)
    assert shapes["window_wq"] == ((3, 2048, 64 * 128), 2048 ** -0.5)
    assert shapes["window_wo"] == ((3, 64 * 128, 2048), (64 * 128) ** -0.5)
    assert shapes["window_wg"] == ((3, 2048, 64), 2048 ** -0.5)
    assert shapes["full_wq"] == ((1, 2048, 48 * 128), 2048 ** -0.5)
    assert shapes["full_wk"] == ((1, 2048, 8 * 128), 2048 ** -0.5)
    assert shapes["window_w_down"] == ((3, 32, 512, 2048), 512 ** -0.5)
    assert shapes["full_router"] == ((1, 2048, 256), 2048 ** -0.5)
    assert shapes["full_shared_up"] == ((1, 2048, 512), 2048 ** -0.5)
    assert not [n for n in shapes if "bias" in n]


def test_laguna_every_number_of_the_catalogs_row_is_in_the_file():
    path = os.environ.get("MODEL_CONFIG_CATALOG", "")
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == LAGUNA_SOURCE)
    c = _conf()
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


def test_laguna_the_cell_and_its_metrics_are_entered_by_name():
    by = lambda group: {e["name"]: e for e in SPEC[group]}
    entry = by("configs")[LAGUNA]
    assert entry["source"] == LAGUNA_SOURCE == _conf()["source"]
    assert entry["file"] == f"benchmark/configs/{LAGUNA}.json"
    assert entry["reduced"] == _conf()["reduced"]
    cell = by("workloads")[LAGUNA_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        LAGUNA, "steady-8k", 1)
    assert len(cell["why"]) <= 200
    metrics = by("per_layer")
    for name, (unit, better, layer) in LAGUNA_METRICS.items():
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, "device_trace", layer)
        assert m["workloads"] == [LAGUNA_CELL]
        assert m["moves"] == "tokens_per_s_per_chip"


def _with(conf, path, value):
    """A copy of ``conf`` with the key at ``path`` set to ``value``."""
    if len(path) == 1:
        return dict(conf, **{path[0]: value})
    return dict(conf, **{path[0]: _with(conf[path[0]], path[1:], value)})


@pytest.mark.parametrize("path,value", [
    (("attention_bias",), True), (("tie_word_embeddings",), True),
    (("gating",), False),  # no gate: another model
    (("moe_apply_router_weight_on_input",), True),
    (("topk_method",), "noaux_tc"),  # a correction bias in the choice
    (("norm_topk_prob",), False),
    (("mlp_layer_types",), ["sparse"] * 5),  # no leading dense layer
    (("mlp_layer_types",), ["dense", "sparse", "dense", "sparse", "sparse"]),
    # unlike heads inside one kind
    (("num_attention_heads_per_layer",), [48, 64, 64, 48, 48]),
    (("num_attention_heads_per_layer",), [64, 64, 64, 64, 64]),
    (("rope_parameters", "sliding_attention", "rope_type"), "yarn"),
    # an odd number of a head's dims turned: no pair for the last
    (("rope_parameters", "full_attention", "partial_rotary_factor"),
     65 / 128),
])
def test_laguna_the_adapter_refuses_what_the_program_does_not_compute(
        path, value):
    adapter, _, _ = _modules()
    cfg = adapter.config(_conf())
    assert (cfg.layer_kinds, cfg.n_dense_layers, cfg.lead_kind,
            cfg.n_layers, cfg.window) == (
        ("window", "window", "window", "full"), 1, "full", 5, 512)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (48, 8, 128)
    # query heads, theta, rotated dims
    assert cfg.attention("full") == (48, 5e5, 64)
    assert cfg.attention("window") == (64, 1e4, 128)
    assert cfg.head_gate and cfg.stacked_by_kind
    assert (cfg.yarn_full.factor, cfg.yarn_full.original_len,
            cfg.yarn_full.beta_fast, cfg.yarn_full.beta_slow) == (
        64.0, 4096, 64.0, 1.0)
    assert cfg.yarn_full.attention_factor == pytest.approx(1.4158883)
    assert (cfg.router_score, cfg.router_bias, cfg.router_scale,
            cfg.experts_gated, cfg.d_shared, cfg.n_experts, cfg.experts_held,
            cfg.experts_per_token, cfg.d_expert, cfg.d_ff) == (
        "sigmoid", False, 2.5, True, 512, 256, 32, 8, 512, 8192)
    with pytest.raises(ValueError):
        adapter.config(_with(_conf(), path, value))


# -- counts -----------------------------------------------------------------

def test_laguna_counts_by_hand():
    _, reference, counts = _modules()
    c = _conf()
    d, kv = 2048, 8 * 128
    full = 2048 * 48 * 128 * 2 + 2 * d * kv + d * 48 + 2 * d
    window = 2048 * 64 * 128 * 2 + 2 * d * kv + d * 64 + 2 * d
    assert (full, window) == (29_462_528, 37_883_904)
    dense = 3 * 2048 * 8192
    expert, shared, router = 3 * 2048 * 512, 3 * 2048 * 512, 2048 * 256
    assert (counts.dense_params(c), counts.expert_params(c),
            counts.shared_params(c), counts.router_params(c)) == (
        dense, expert, shared, router)
    routed = router + shared + 32 * expert
    assert (full + dense, window + routed, full + routed) == (
        79_794_176, 142_217_216, 133_795_840)
    hand = (full + dense + 3 * (window + routed) + full + routed
            + 2 * 12544 * 2048 + 2048)
    assert hand == 691_623_936 == counts.param_count(c)
    total = 0
    for shape, _std in reference.param_shapes(c).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    assert total == hand
    assert hand * 16 / 1e9 == pytest.approx(11.07, abs=0.005)
    # sixteen experts, the fallback cut if 32 did not fit, would be 490.3 M
    sixteen = hand - 4 * 16 * expert
    assert sixteen / 1e6 == pytest.approx(490.3, abs=0.05)

    # forward, a token, at T = 8192: attention ~74 % (projections 43,
    # full-layer scores 25, window-layer scores 6), the dense layer 12,
    # shared and routed experts 6, the head 6
    t = 8192
    proj = 2 * (2 * (full - 2 * d) + 3 * (window - 2 * d))
    keys_full = (t + 1) / 2
    keys_window = (512 * 513 / 2 + (t - 512) * 512) / t
    scores_full = 2 * 4 * 48 * 128 * keys_full
    scores_window = 3 * 4 * 64 * 128 * keys_window
    routed_f = 4 * 2 * (router + shared + (8 * 32 / 256) * expert)
    head = 2 * 2048 * 12544
    fwd = proj + scores_full + scores_window + 2 * dense + routed_f + head
    assert counts.train_flops_per_token(c, t) == pytest.approx(3 * fwd)
    shares = [proj / fwd, scores_full / fwd, scores_window / fwd,
              2 * dense / fwd, routed_f / fwd, head / fwd]
    assert shares == pytest.approx([0.43, 0.25, 0.061, 0.126, 0.068, 0.064],
                                   abs=0.005)
    assert (proj + scores_full + scores_window) / fwd == pytest.approx(
        0.74, abs=0.005)
    assert 3 * fwd * 16384 / 1e12 == pytest.approx(39.4, abs=0.05)
    assert counts.held_assignments_per_token(c) == 1.0
    assert 16384 * 8 / 256 == 512  # rows a held expert a step


def test_laguna_attention_work_by_hand():
    _, _, counts = _modules()
    c = _conf()
    flops, nbytes = counts.attention_step_work(c, 2, 8192)
    tokens, kv = 16384, 8 * 128
    keys_full = 8193 / 2
    keys_window = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    # forward two products, backward four, each over 128 of a head
    assert flops == pytest.approx(3 * tokens * 4 * 128 * (
        2 * 48 * keys_full + 3 * 64 * keys_window))

    def layer_bytes(heads):
        q = heads * 128
        return tokens * ((2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)) * 2

    assert nbytes == 2 * layer_bytes(48) + 3 * layer_bytes(64)
    # the window layers' scores cost under a quarter of the full layers'
    window_flops = 3 * tokens * 4 * 128 * 3 * 64 * keys_window
    assert window_flops / flops == pytest.approx(0.196, abs=0.005)
    # bound by the products on a v5e, not by the bytes
    assert flops / 197e12 > nbytes / 819e9


# -- the adapter: the tree, the round trip, the control ----------------------

def test_laguna_the_adapter_puts_each_leaf_in_its_kinds_stack():
    import jax
    import numpy as np

    import weights
    from mpi_operator_tpu.models import llama

    adapter, reference, _ = _modules()
    conf = _tiny()
    shapes = reference.param_shapes(conf)
    flat = weights.draw(shapes, weights.seed_key(3))
    tree = adapter.to_tree(flat)
    cfg = adapter.config(conf)
    want = jax.tree.map(lambda a: a.shape,
                        llama.init(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, tree) == want
    assert tree["layers"]["window"]["wq"]["w"].shape == (3, 48, 6 * 16)
    assert tree["layers"]["full"]["wg"]["w"].shape == (1, 48, 4)
    assert tree["lead"]["wg"]["w"].shape == (1, 48, 4)
    assert "bias" not in tree["layers"]["full"]["router"]
    back = adapter.to_flat(tree)
    assert set(back) == set(flat) == set(shapes)
    for name in flat:
        np.testing.assert_array_equal(back[name], flat[name], err_msg=name)
    marks = jax.tree.map(lambda a: object(), tree)  # no arrays: shardings
    assert adapter.to_flat(marks)["window_wg"] is \
        marks["layers"]["window"]["wg"]["w"]


def test_laguna_the_control_rounds_the_matrices_and_nothing_else():
    import jax
    import jax.numpy as jnp

    import weights

    adapter, reference, _ = _modules()
    conf = _tiny()
    flat = weights.draw(reference.param_shapes(conf), weights.seed_key(3))
    rounded = adapter.to_flat(adapter._control(adapter.to_tree(flat),
                                               jnp.float32))
    for name, w in flat.items():
        same = bool(jnp.all(rounded[name] == w))
        assert same != (name in adapter._MATRICES), name
    assert {"d_wq", "d_wg", "d_w_gate", "d_w_down", "full_wq", "full_wk",
            "full_wv", "full_wg", "full_wo", "window_wq", "window_wg",
            "window_w_up", "full_w_down", "window_shared_gate",
            "full_shared_down", "lm_head"} <= set(adapter._MATRICES)
    w = flat["window_wq"]
    rel = float(jnp.linalg.norm(rounded["window_wq"] - w)
                / jnp.linalg.norm(w))
    assert 0.01 < rel < 0.05  # three mantissa bits
    grad = jax.grad(lambda a: jnp.sum(adapter._fp8(a) * 2.0))(w)
    assert bool(jnp.all(grad == 2.0))  # straight through
    assert adapter.config(_conf()).matmul_precision == "bf16"
    assert adapter.config(_conf(), control=True).matmul_precision == "fp8"


def test_laguna_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "laguna.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "mpi_operator_tpu" not in body and "adapters" not in body
    assert "HIGHEST" in body


# -- the new readers ----------------------------------------------------------

MS = 1_000_000_000  # picoseconds
STEP = "jit(_bare_step)/"
FWD = STEP + "model/jvp()/while/body/closed_call/"
BWD = STEP + "model/transpose(jvp())/while/body/closed_call/checkpoint/"
FUSION = "%fusion.{} = bf16[8,2048]{{1,0}} fusion(bf16[8,2048]{{1,0}} %p), kind=kLoop"
KERNEL = ('%{0}.{1} = bf16[2,64,8192,128]{{3,2,1,0:T(8,128)(2,1)}} '
          'custom-call(bf16[2,64,8192,128]{{3,2,1,0}} %p), '
          'custom_call_target="tpu_custom_call"')
FULL, WINDOW = "attention/attention_full/", "attention/attention_window/"


def _laguna_devices():
    ops = [
        (FUSION.format(1), 0, 2 * MS, FWD + "lead/" + FULL + "attention_rope/mul:"),
        (KERNEL.format("flash_fwd", 2), 2 * MS, 6 * MS, FWD + "lead/" + FULL + "flash_fwd:"),
        (FUSION.format(3), 8 * MS, 1 * MS,
         FWD + "lead/" + FULL + "attention_gate/dot_general:"),
        (FUSION.format(4), 9 * MS, 3 * MS, FWD + WINDOW + "attention_rope/concatenate:"),
        (KERNEL.format("flash_fwd", 5), 12 * MS, 4 * MS, FWD + WINDOW + "flash_fwd:"),
        (FUSION.format(6), 16 * MS, 2 * MS, FWD + WINDOW + "attention_gate/logistic:"),
        (FUSION.format(7), 18 * MS, 5 * MS, FWD + "mlp/moe/moe_shared/dot_general:"),
        (KERNEL.format("flash_dq", 8), 23 * MS, 10 * MS, BWD + WINDOW + "flash_dq:"),
        (KERNEL.format("flash_dkv", 9), 33 * MS, 12 * MS, BWD + FULL + "flash_dkv:"),
        (FUSION.format(10), 45 * MS, 4 * MS, BWD + WINDOW + "attention_gate/mul:"),
        (FUSION.format(11), 49 * MS, 3 * MS,
         BWD + "rematted_computation/" + FULL + "attention_rope/mul:"),
        # another program's operation, after the step
        (FUSION.format(12), 100 * MS, 1 * MS, "jit(convert)/attention_gate/convert:"),
    ]
    modules = [("jit__bare_step", 0, 100 * MS), ("jit_convert", 100 * MS, MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def _laguna_record():
    import named_trace
    from metrics import op_names

    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    devices = _laguna_devices()
    return {"trace": {}, "peaks": peaks["TPU v5 lite"], "conf": _conf(),
            "traffic": {"rows_per_chip": 2, "seq_len": 8192},
            "report": {"stepstats": {}},
            "counts": importlib.import_module("counts.laguna"),
            "op_names": op_names.reduce_by_name(devices),
            "named_trace": named_trace.reduce_named(devices, {})}


def test_laguna_new_readers():
    _, _, counts = _modules()
    r = _laguna_record()
    read = lambda name: importlib.import_module("metrics." + name).read(r)
    # every layer's gate, the leading one's too, every phase
    assert read("head_gate_ms") == pytest.approx(1 + 2 + 4)
    assert read("rope_by_kind_ms") == pytest.approx(2 + 3 + 3)
    flops, nbytes = counts.attention_step_work(_conf(), 2, 8192)
    least = max(flops / 197e12, nbytes / 819e9)
    share = read("heads_by_kind_flash_roofline_share")
    assert share == pytest.approx(100 * least / ((6 + 4 + 10 + 12) * 1e-3))
    assert 0 < share


@pytest.mark.parametrize("metric", sorted(LAGUNA_METRICS))
def test_laguna_a_program_without_the_names_reads_nothing(metric):
    """The parent's program has no such scope and a run with no trace has
    no file: every new reader returns None, not 0, and none raises."""
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    read = importlib.import_module("metrics." + metric).read
    for report, trace in (({"stepstats": None}, None),
                          ({"stepstats": {"profile": {"dir": "/nowhere"}}},
                           {"step_s": 0.5}),
                          ({}, {"step_s": 0.5})):
        record = {"trace": trace, "peaks": peaks["TPU v5 lite"],
                  "conf": _conf(), "report": report,
                  "traffic": {"rows_per_chip": 2, "seq_len": 8192},
                  "counts": importlib.import_module("counts.laguna")}
        assert read(record) is None
    from metrics import op_names

    devices = {"/device:TPU:0": {
        "ops": [(FUSION.format(1), 0, 10 * MS,
                 FWD + "attention/attention_full/dot_general:")],
        "modules": [("jit__bare_step", 0, 10 * MS)]}}
    record = dict(_laguna_record(), op_names=op_names.reduce_by_name(devices))
    if metric != "heads_by_kind_flash_roofline_share":  # kernels by name
        assert read(record) is None


# -- the reference against the program, tiny, on the CPU --------------------

def _laguna_program_run(conf, control, batches, key, dtype="float32"):
    import jax
    import jax.numpy as jnp

    import weights
    from mpi_operator_tpu.ops import Trainer, TrainerConfig
    from mpi_operator_tpu.runtime.topology import MeshPlan, build_mesh

    adapter, reference, _ = _modules()
    opt = conf["assumed"]["optimizer"]
    shapes = reference.param_shapes(conf)
    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
    cfg = adapter.config(dict(conf, assumed=dict(
        conf["assumed"], compute_dtype=dtype)), control=control)
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
                k: float(v) for k, v in metrics.items() if k.startswith("moe.")}
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


def _laguna_batches(conf):
    tr = json.load(open(os.path.join(HERE, "traffic", "tiny.json")))
    generator = importlib.import_module("generators." + tr["generator"])
    return [generator.batch(conf, tr, 7, s, 1) for s in (1, 2, 3)]


@pytest.fixture(scope="module")
def laguna_readings():
    """(the program in float32, the program under the control (fp8 in
    every product, ``run.py --control``), the reference in float32, the
    reference with bf16 products): the readings ``correct`` compares, after
    three steps on the same seeded rows."""
    import jax.numpy as jnp

    import weights

    conf = _tiny()
    _, reference, _ = _modules()
    opt = conf["assumed"]["optimizer"]
    shapes = reference.param_shapes(conf)
    key = weights.seed_key(2 ** 31 + 11)
    batches = _laguna_batches(conf)

    def ref_run(dtype):
        return check.reference_steps(
            lambda p, b: reference.loss(conf, p, b, compute_dtype=dtype),
            weights.draw(shapes, key),
            lambda k: weights.draw_leaf(shapes, k, key), batches, opt)

    return (_laguna_program_run(conf, False, batches, key),
            _laguna_program_run(conf, True, batches, key),
            ref_run(jnp.float32), ref_run(jnp.bfloat16))


def test_laguna_reference_agrees_with_the_program_in_float32(laguna_readings):
    program, _, ref, _ = laguna_readings
    numbers = check.compare(program, ref)
    assert {"grad_gap.d_wq", "grad_gap.d_wg", "grad_gap.d_w_gate",
            "grad_gap.full_wq", "grad_gap.full_wk", "grad_gap.full_wg",
            "grad_gap.window_wq", "grad_gap.window_wg", "grad_gap.window_wo",
            "grad_gap.window_router", "grad_gap.window_w_gate",
            "grad_gap.full_shared_gate", "gnorm_gap", "delta_gap",
            "loss3_gap"} <= set(numbers)
    # float32 on both sides: the chunked attention against blocks of
    # queries, the sort and the grouped product against a plain loop over
    # the experts, stacks by kind against runs of layers. They read under
    # 2e-6 here; 2e-5 is ten times that and far under what bf16 reads
    # below.
    assert all(v < 2e-5 for v, _leaf in numbers.values()), numbers
    counters = program["counters"]
    assert counters["moe.assignments_dropped"] == 0
    # 2 rows x 32 ids x 2 experts a token, half the experts held
    assert 40 <= counters["moe.assignments_held"] <= 88


def test_laguna_bf16_in_the_programs_place_fails_the_same_comparison(
        laguna_readings):
    program, _, ref, bf16 = laguna_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    ok, _, _ = check.verdict(check.compare(program, ref), limits)
    bad, compared, _ = check.verdict(check.compare(bf16, ref), limits)
    assert ok and not bad, compared


def test_laguna_the_control_fails_it_too_on_every_part_of_a_layer(
        laguna_readings):
    """``run.py --control``: every matrix of every product rounded to fp8:
    held to the float32 program's limits it comes out not correct, on the
    attention's and the gate's leaves of both kinds, the dense layer's, the
    experts' and the shared expert's."""
    program, control, ref, _ = laguna_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    bad, compared, _ = check.verdict(check.compare(control, ref), limits)
    assert not bad
    assert all(compared[f"grad_gap.{k}"][0] > 2e-5
               for k in ("d_wq", "d_wg", "d_w_gate", "full_wq", "full_wg",
                         "window_wq", "window_wo", "window_wg",
                         "window_w_up", "full_shared_gate"))


def test_laguna_yarns_factor_scales_the_rotated_dims_alone():
    """Cos and sin of the rotated half carry YaRN's factor, the unrotated
    half carries none: the program's loss and gradients match the
    reference's to float32's rounding, and a reference that folds the
    factor's square into the whole score, as a whole-head rotation may,
    reads ten times the tolerance and more away from both."""
    import weights

    _, reference, _ = _modules()
    conf = _tiny()
    assert conf["rope_parameters"]["full_attention"]["attention_factor"] != 1
    shapes = reference.param_shapes(conf)
    key = weights.seed_key(41)
    batch = _laguna_batches(conf)[0]
    opt = conf["assumed"]["optimizer"]
    ref = check.reference_steps(
        lambda p, b: reference.loss(conf, p, b), weights.draw(shapes, key),
        lambda k: weights.draw_leaf(shapes, k, key), [batch], opt)
    program = _laguna_program_run(conf, False, [batch], key)
    rope = reference.rope

    def whole_head_fold(x, freqs, factor):
        # no factor on the tables; q and k each times it whole: the factor's
        # square on every dim of the score
        return rope(x, freqs, 1.0) * factor

    reference.rope = whole_head_fold
    try:
        fold = check.reference_steps(
            lambda p, b: reference.loss(conf, p, b),
            weights.draw(shapes, key),
            lambda k: weights.draw_leaf(shapes, k, key), [batch], opt)
    finally:
        reference.rope = rope
    sound = check.compare(program, ref)
    folded = check.compare(fold, ref)
    assert max(v for v, _ in sound.values()) < 2e-5, sound
    assert folded["loss1_gap"][0] > 2e-4, folded["loss1_gap"]
    assert max(folded[f"grad_gap.{k}"][0] for k in (
        "d_wq", "d_wk", "full_wq", "full_wk")) > 2e-4


def test_laguna_disjoint_shares_add_up_to_the_uncut_layer():
    """Two disjoint shares of the routed experts (0-3 and 4-7 of 8), the
    shared expert counted once, add up to the layer with all eight held:
    in the reference, and in the program's layer against the reference's
    uncut one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_operator_tpu.parallel import moe

    _, reference, _ = _modules()
    conf = dict(_tiny(), num_experts=8,
                stands_for={"experts_held": {"first": 0, "count": 8,
                                             "of": 8}})
    d, f, n = 48, 24, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 8)
    w = {"router": jax.random.normal(ks[0], (d, 8)) * d ** -0.5,
         "w_gate": jax.random.normal(ks[1], (8, d, f)) * d ** -0.5,
         "w_up": jax.random.normal(ks[2], (8, d, f)) * d ** -0.5,
         "w_down": jax.random.normal(ks[3], (8, f, d)) * f ** -0.5,
         "shared_gate": jax.random.normal(ks[4], (d, f)) * d ** -0.5,
         "shared_up": jax.random.normal(ks[5], (d, f)) * d ** -0.5,
         "shared_down": jax.random.normal(ks[6], (f, d)) * f ** -0.5}
    u = jax.random.normal(ks[7], (n, d))
    one = lambda a: a
    shared = reference.swiglu(u, w["shared_gate"], w["shared_up"],
                              w["shared_down"], one)
    with jax.default_matmul_precision("highest"):
        uncut = reference.routed_experts(conf, u, w, one) + shared
        parts = [reference.routed_experts(
            conf, u, {**w, **{k: w[k][lo:lo + 4] for k in
                              ("w_gate", "w_up", "w_down")}}, one,
            first=lo, count=4) for lo in (0, 4)]
        np.testing.assert_allclose(parts[0] + parts[1] + shared, uncut,
                                   atol=1e-5)
        # the program's layer: the shares with the shared expert in one of
        # them, float32 throughout
        tree = lambda lo, with_shared: {
            "router": {"w": w["router"]},
            **{k: {"w": w[k][lo:lo + 4]} for k in
               ("w_gate", "w_up", "w_down")},
            **({k: {"w": w[k]} for k in
                ("shared_gate", "shared_up", "shared_down")}
               if with_shared else {})}
        apply = lambda lo, with_shared: moe.apply(
            tree(lo, with_shared), u[None], experts_per_token=2,
            first_expert=lo, router_scale=2.5, router_score="sigmoid",
            compute_dtype=jnp.float32, matmul_precision="bf16")[0][0]
        got = apply(0, True) + apply(4, False)
    np.testing.assert_allclose(got, uncut, atol=1e-4)
    # a share alone is not the layer
    assert float(jnp.max(jnp.abs(apply(0, True) - uncut))) > 1e-2


# -- the tiny cell through the whole harness ---------------------------------

def _laguna_tiny_run(tmp_path, *flags):
    keep = str(tmp_path / "keep")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--spec", LAGUNA_SPEC,
         "--workload", "tiny.laguna", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0", "--keep", keep, *flags],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), keep


def test_laguna_the_tiny_cell_runs_and_its_counters_reach_the_report(
        tmp_path):
    line, keep = _laguna_tiny_run(tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert {"grad_gap.final_norm", "grad_gap.full_w_up",
            "grad_gap.window_router"} <= set(line["compared"])
    report = json.load(open(os.path.join(keep, "report.json")))
    counters = report["stepstats"]["counters"]
    assert counters["moe.assignments_dropped"] == 0
    assert 40 <= counters["moe.assignments_held"] <= 88


def test_laguna_the_control_fails_the_tiny_cells_limits(tmp_path):
    line, _ = _laguna_tiny_run(tmp_path, "--control")
    assert line["correct"] is False
    over = [n for n, (value, limit) in line["compared"].items()
            if value > limit]
    assert over, line["compared"]
