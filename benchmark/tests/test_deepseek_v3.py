"""The DeepSeek-V3-style configuration's side of the benchmark
(``kanana-2-30b-a3b-l6-e16``, cell ``kanana2.steady-8k``): its file against
the catalog's rules, the hand-worked counts, the adapter's permutation of
the rotated columns and its round trip, the correction bias as a buffer that
is no compared leaf, the plain reference against the program in float32 at a
tiny size of the same shape (a leading dense layer, two routed ones, latent
attention 24 / 16 wide over a latent of 32, 8 experts of which 2 a token and
half held, a gated shared expert) with bf16 in the program's place and the
fp8 control each failing the same comparison, the new readers on hand-built
records, and the tiny cell through the whole harness on the CPU, sound and
under the control. Entries of ``BENCHMARK.json`` are found by name, never by
their place in a list. Fast enough for tier-1, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_deepseek_v3.py -q
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
KANANA_SPEC = os.path.join(HERE, "BENCHMARK.kanana-tiny.json")
KANANA = "kanana-2-30b-a3b-l6-e16"
KANANA_CELL = "kanana2.steady-8k"
KANANA_SOURCE = ("https://huggingface.co/kakaocorp/"
                 "kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
KANANA_METRICS = {
    "latent_attention_ms": ("ms", "lower", "trainer"),
    "latent_proj_ms": ("ms", "lower", "trainer"),
    "latent_flash_roofline_share": ("%", "higher", "kernels"),
    "lead_dense_ms": ("ms", "lower", "trainer"),
    "moe_shared_gated_ms": ("ms", "lower", "routed feed-forward"),
}
KANANA_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "num_attention_heads": 32,
    "num_key_value_heads": 32, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
    "num_experts_per_tok": 6, "n_shared_experts": 2,
    "routed_scaling_factor": 2.448, "rms_norm_eps": 1e-6,
    "first_k_dense_replace": 1, "rope_theta": 1000000, "head_dim": 64,
}


def _conf(name=KANANA):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(HERE, "tiny-kanana-cpu.json")) as f:
        return json.load(f)


def _modules():
    return (importlib.import_module("adapters.deepseek_v3"),
            importlib.import_module("reference.deepseek_v3"),
            importlib.import_module("counts.deepseek_v3"))


# -- the configuration's file and the cell's entries ------------------------

def test_kanana_the_file_keeps_every_width_and_states_its_cuts():
    c = _conf()
    assert {k: c[k] for k in KANANA_WIDTHS} == KANANA_WIDTHS
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (6, 16, 16032)
    assert c["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 128, "vocab_size": 128256}
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    stands = c["stands_for"]
    assert (stands["chips_sharing_a_layer"], stands["pipeline_stages"],
            stands["chips"]) == (8, 8, 64)
    assert stands["experts_held"] == {"first": 0, "count": 16, "of": 128}
    assumed = c["assumed"]
    assert (assumed["compute_dtype"], assumed["param_dtype"],
            assumed["moment_dtype"], assumed["remat_layers"]) == (
        "bfloat16", "float32", "float32", True)
    assert set(assumed["not_in_the_config_so_not_computed"]) == {
        "auxiliary_loss", "bias_update", "multi_token_head"}
    assert set(assumed["published_and_unused"]) >= {"head_dim"}
    opt = assumed["optimizer"]
    assert (opt["beta1"], opt["beta2"], opt["weight_decay"]) == (
        0.9, 0.95, 0.0)
    # the draw the file states is the one the reference makes
    _, reference, _ = _modules()
    assert (assumed["draw"]["score_bias_std"],
            assumed["draw"]["score_bias_seed"]) == (
        reference.SCORE_BIAS_STD, reference.SCORE_BIAS_SEED)
    shapes = reference.param_shapes(c)
    assert shapes["wq"] == ((5, 2048, 32 * 192), 2048 ** -0.5)
    assert shapes["wkv_a"] == ((5, 2048, 512 + 64), 2048 ** -0.5)
    assert shapes["wkv_b"] == ((5, 512, 32 * 256), 512 ** -0.5)
    assert shapes["wo"] == ((5, 4096, 2048), 4096 ** -0.5)
    assert shapes["d_w_gate"] == ((1, 2048, 6144), 2048 ** -0.5)
    assert shapes["w_down"] == ((5, 16, 768, 2048), 768 ** -0.5)
    assert shapes["shared_up"] == ((5, 2048, 1536), 2048 ** -0.5)
    assert shapes["router"] == ((5, 2048, 128), 2048 ** -0.5)


def test_kanana_every_number_of_the_catalogs_row_is_in_the_file():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == KANANA_SOURCE)
    c = _conf()
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


def test_kanana_the_cell_and_its_metrics_are_entered_by_name():
    by = lambda group: {e["name"]: e for e in SPEC[group]}
    entry = by("configs")[KANANA]
    assert entry["source"] == KANANA_SOURCE == _conf()["source"]
    assert entry["file"] == f"benchmark/configs/{KANANA}.json"
    assert entry["reduced"] == _conf()["reduced"]
    cell = by("workloads")[KANANA_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        KANANA, "steady-8k", 1)
    assert len(cell["why"]) <= 200
    metrics = by("per_layer")
    for name, (unit, better, layer) in KANANA_METRICS.items():
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, "device_trace", layer)
        assert m["workloads"] == [KANANA_CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
    # the accepted entries keep their lists
    assert metrics["moe_shared_ms"]["workloads"] == ["nemotron3nano.steady-8k"]
    assert KANANA_CELL not in metrics["banded_flash_roofline_share"][
        "workloads"]
    # the nine that list no cells are reported here too, step_mfu among them
    everywhere = [m["name"] for m in SPEC["per_layer"] if "workloads" not in m]
    assert len(everywhere) == 9 and "step_mfu" in everywhere


def _with(conf, path, value):
    """A copy of ``conf`` with the key at ``path`` set to ``value``."""
    if len(path) == 1:
        return dict(conf, **{path[0]: value})
    return dict(conf, **{path[0]: _with(conf[path[0]], path[1:], value)})


@pytest.mark.parametrize("path,value", [
    (("q_lora_rank",), 1536),  # a compressed query
    (("rope_scaling",), {"type": "yarn", "factor": 40}),
    (("n_group",), 8), (("topk_group",), 4),  # a group limit on the choice
    (("scoring_func",), "softmax"), (("topk_method",), "greedy"),
    (("rope_interleave",), False), (("hidden_act",), "gelu"),
    (("moe_layer_freq",), 2), (("attention_bias",), True),
    (("qk_head_dim",), 128), (("num_key_value_heads",), 8),
    (("first_k_dense_replace",), 0),
])
def test_kanana_the_adapter_refuses_what_the_program_does_not_compute(
        path, value):
    adapter, _, _ = _modules()
    cfg = adapter.config(_conf())
    assert (cfg.layer_kinds, cfg.n_dense_layers, cfg.n_layers) == (
        ("latent",), 1, 6)
    assert (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim, cfg.qk_head_dim, cfg.d_ff) == (
        512, 128, 64, 128, 192, 6144)
    assert (cfg.router_score, cfg.router_scale, cfg.experts_gated,
            cfg.d_shared, cfg.n_experts, cfg.experts_held,
            cfg.experts_per_token, cfg.d_expert) == (
        "sigmoid", 2.448, True, 1536, 128, 16, 6, 768)
    with pytest.raises(ValueError):
        adapter.config(_with(_conf(), path, value))


# -- counts -----------------------------------------------------------------

def test_kanana_counts_by_hand():
    _, reference, counts = _modules()
    c = _conf()
    attention = (2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
                 + 4096 * 2048)
    assert attention == 26_345_984 == counts.attention_params(c)
    dense_layer = attention + 2 * 2048 + 3 * 2048 * 6144
    assert dense_layer == 64_098_816
    expert, shared, router = 3 * 2048 * 768, 3 * 2048 * 1536, 2048 * 128
    assert (counts.expert_params(c), counts.shared_params(c),
            counts.router_params(c)) == (expert, shared, router)
    assert (expert, shared, router + 128) == (4_718_592, 9_437_184, 262_272)
    routed_layer = attention + 4096 + router + 128 + shared + 16 * expert
    assert routed_layer == 111_547_008
    hand = dense_layer + 5 * routed_layer + 2 * 16032 * 2048 + 2048
    assert hand == 687_502_976 == counts.param_count(c)
    # the leaves the reference draws, and the bias's 128 a routed layer (a
    # buffer: held, counted, and no leaf)
    total = 0
    for shape, _std in reference.param_shapes(c).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    assert total + 5 * 128 == hand
    assert hand * 16 / 1e9 == pytest.approx(11.0, abs=0.05)
    # the uncut formulas give the model its name: 30.7 B
    whole = dict(c, num_hidden_layers=48, n_routed_experts=128,
                 vocab_size=128256)
    assert counts.param_count(whole) / 1e9 == pytest.approx(30.7, abs=0.05)
    # a whole routed layer is 640 M = 10.2 GB: a chip cannot hold two; four
    # chips a layer would be 32 experts, 15.1 GB with four routed layers
    uncut_layer = routed_layer + 112 * expert
    assert uncut_layer * 16 / 1e9 == pytest.approx(10.2, abs=0.05)
    four_way = dense_layer + 4 * (routed_layer + 16 * expert) + 2 * (
        128256 // 4) * 2048
    assert four_way * 16 / 1e9 > 15.0

    # forward, a token, at T = 8192
    projections = 2 * (attention - 512)
    products = 2 * 32 * (192 + 128) * 8193 / 2
    shared_f, router_f = 2 * shared, 2 * router
    routed_f = 2 * (6 * 16 / 128) * expert
    dense_f, head = 2 * 3 * 2048 * 6144, 2 * 2048 * 16032
    assert projections / 1e6 == pytest.approx(52.7, abs=0.05)
    assert products / 1e6 == pytest.approx(83.9, abs=0.05)
    assert (shared_f / 1e6, router_f / 1e6, routed_f / 1e6) == (
        pytest.approx(18.9, abs=0.05), pytest.approx(0.5, abs=0.05),
        pytest.approx(7.1, abs=0.05))
    routed = projections + products + shared_f + router_f + routed_f
    assert routed / 1e6 == pytest.approx(163.1, abs=0.05)
    assert (projections + products) / routed == pytest.approx(0.84, abs=0.005)
    fwd = 5 * routed + projections + products + dense_f + head
    assert fwd / 1e6 == pytest.approx(1093, abs=1)
    assert counts.train_flops_per_token(c, 8192) == pytest.approx(3 * fwd)
    assert 6 * products / fwd == pytest.approx(0.46, abs=0.005)
    assert 6 * (projections + products) / fwd == pytest.approx(0.75, abs=0.005)
    assert 5 * (shared_f + routed_f) / fwd == pytest.approx(0.12, abs=0.005)
    # 53.7 TFLOP a step of 16,384 tokens
    assert 3 * fwd * 16384 / 1e12 == pytest.approx(53.7, abs=0.05)


def test_kanana_attention_and_expert_work():
    _, _, counts = _modules()
    c = _conf()
    flops, nbytes = counts.attention_step_work(c, 2, 8192)
    # forward two products, backward four: scores, dq and dk 192 wide,
    # weighted values, dp and dv 128 wide, over the 4096.5 keys a query meets
    assert flops == 6 * 16384 * 2 * 32 * 8193 / 2 * (
        (192 + 128) + (192 + 192 + 128 + 128))
    # q, k at 192 and v, o at 128 forward; q, k, v, o, do read and dq, dk, dv
    # written backward; bf16, every head its own keys and values
    assert nbytes == 6 * 16384 * 32 * 2 * (
        (2 * 192 + 2 * 128) + (2 * 192 + 3 * 128) + (2 * 192 + 128))
    # bound by the products on a v5e, not by the bytes
    assert flops / 197e12 > nbytes / 819e9
    flops, nbytes = counts.expert_step_work(c, 12288)
    assert flops == 6 * 3 * 12288 * 2048 * 768
    assert nbytes == ((5 * 2048 + 7 * 768) * 12288
                      + 3 * 16 * 3 * 2048 * 768) * 2
    assert counts.held_assignments_per_token(c) == 0.75
    assert 16384 * 0.75 / 16 == 768  # rows a held expert a step


# -- the adapter: the rotated columns, the round trip, the buffer ------------

def test_kanana_the_adapter_puts_the_rotated_columns_in_the_programs_order():
    """Published column 2j of a head's rotated part is the program's j,
    2j + 1 its R / 2 + j, in ``wq`` (every head's last R) and in ``wkv_a``
    (its last R); nothing else moves; ``to_flat`` puts them back; and a
    sharding passes through as it came."""
    import jax
    import numpy as np

    import weights

    adapter, reference, _ = _modules()
    conf = _tiny()
    shapes = reference.param_shapes(conf)
    flat = weights.draw(shapes, weights.seed_key(3))
    tree = adapter.to_tree(flat)
    nope, rot, r, heads = 16, 8, 32, 4
    for stack, prefix in (("layers", ""), ("lead", "d_")):
        wq = np.asarray(flat[prefix + "wq"]).reshape(-1, 48, heads, nope + rot)
        got = np.asarray(tree[stack]["wq"]["w"]).reshape(wq.shape)
        np.testing.assert_array_equal(got[..., :nope], wq[..., :nope])
        np.testing.assert_array_equal(got[..., nope:nope + rot // 2],
                                      wq[..., nope::2])
        np.testing.assert_array_equal(got[..., nope + rot // 2:],
                                      wq[..., nope + 1::2])
        wkv = np.asarray(flat[prefix + "wkv_a"])
        got = np.asarray(tree[stack]["wkv_a"]["w"])
        np.testing.assert_array_equal(got[..., :r], wkv[..., :r])
        np.testing.assert_array_equal(got[..., r:r + rot // 2],
                                      wkv[..., r::2])
        np.testing.assert_array_equal(got[..., r + rot // 2:],
                                      wkv[..., r + 1::2])
    back = adapter.to_flat(tree)
    assert set(back) == set(flat) == set(shapes)
    for name in flat:
        np.testing.assert_array_equal(back[name], flat[name], err_msg=name)
    moved = {n for n in flat if not np.array_equal(
        flat[n], adapter._rotated_order(flat)[n])}
    assert moved == {"wq", "wkv_a", "d_wq", "d_wkv_a"}
    marks = jax.tree.map(lambda a: object(), tree)  # no arrays: shardings
    flat_marks = adapter.to_flat(marks)
    assert flat_marks["wq"] is marks["layers"]["wq"]["w"]
    assert flat_marks["d_wkv_a"] is marks["lead"]["wkv_a"]["w"]


def test_kanana_the_correction_bias_is_a_buffer_and_no_compared_leaf():
    import numpy as np

    import weights

    adapter, reference, _ = _modules()
    conf = _tiny()
    shapes = reference.param_shapes(conf)
    assert not [n for n in shapes if "bias" in n]
    bias = reference.score_bias(5, 128)
    assert bias.shape == (5, 128) and bias.dtype == np.float32
    np.testing.assert_array_equal(bias, reference.score_bias(5, 128))
    assert float(bias.std()) == pytest.approx(0.01, rel=0.1)
    assert abs(float(bias.mean())) < 2e-3
    # whatever the run's seed, the program's tree holds the same buffer
    for seed in (3, 2 ** 31 + 5):
        tree = adapter.to_tree(weights.draw(shapes, weights.seed_key(seed)))
        np.testing.assert_array_equal(tree["layers"]["router"]["bias"],
                                      reference.score_bias(2, 8))
    assert "bias" not in tree["lead"].get("router", {})


def test_kanana_the_control_rounds_the_matrices_and_nothing_else():
    import jax
    import jax.numpy as jnp

    import weights

    adapter, reference, _ = _modules()
    conf = _tiny()
    flat = weights.draw(reference.param_shapes(conf), weights.seed_key(3))
    rounded = adapter.to_flat(adapter._control(adapter.to_tree(flat)))
    for name, w in flat.items():
        same = bool(jnp.all(rounded[name] == w))
        assert same != (name in adapter._MATRICES), name
    assert {"wq", "wkv_a", "wkv_b", "wo", "d_wq", "d_wo", "shared_gate",
            "shared_up", "shared_down", "lm_head"} <= set(adapter._MATRICES)
    w = flat["wkv_b"]
    rel = float(jnp.linalg.norm(rounded["wkv_b"] - w) / jnp.linalg.norm(w))
    assert 0.01 < rel < 0.05  # three mantissa bits
    grad = jax.grad(lambda a: jnp.sum(adapter._fp8(a) * 2.0))(w)
    assert bool(jnp.all(grad == 2.0))  # straight through
    assert adapter.config(_conf()).matmul_precision == "bf16"
    assert adapter.config(_conf(), control=True).matmul_precision == "fp8"


def test_kanana_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "deepseek_v3.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "mpi_operator_tpu" not in body and "adapters" not in body
    assert "HIGHEST" in body
    # interleaved pairs, as published; the program's half-split form is not
    # here
    assert "x[..., 0::2], x[..., 1::2]" in body


# -- the new readers ----------------------------------------------------------

MS = 1_000_000_000  # picoseconds
STEP = "jit(_bare_step)/"
FWD = STEP + "model/jvp()/while/body/closed_call/"
BWD = STEP + "model/transpose(jvp())/while/body/closed_call/checkpoint/"
FUSION = "%fusion.{} = bf16[8,2048]{{1,0}} fusion(bf16[8,2048]{{1,0}} %p), kind=kLoop"
KERNEL = ('%{0}.{1} = bf16[2,32,8192,128]{{3,2,1,0:T(8,128)(2,1)}} '
          'custom-call(bf16[2,32,8192,192]{{3,2,1,0}} %p), '
          'custom_call_target="tpu_custom_call"')
LATENT = "attention/attention_latent/"


def _kanana_devices():
    ops = [
        (FUSION.format(1), 0, 4 * MS, FWD + "lead/" + LATENT + "latent_q/dot_general:"),
        (KERNEL.format("flash_fwd", 2), 4 * MS, 6 * MS,
         FWD + "lead/" + LATENT + "flash_fwd:"),
        (FUSION.format(3), 10 * MS, 5 * MS, FWD + "lead/mlp/dot_general:"),
        (FUSION.format(4), 15 * MS, 3 * MS, FWD + LATENT + "latent_kv_down/dot_general:"),
        (FUSION.format(5), 18 * MS, 2 * MS, FWD + LATENT + "latent_kv_up/dot_general:"),
        (FUSION.format(6), 20 * MS, 1 * MS, FWD + LATENT + "latent_rope/mul:"),
        (KERNEL.format("flash_fwd", 7), 21 * MS, 6 * MS, FWD + LATENT + "flash_fwd:"),
        (FUSION.format(8), 27 * MS, 3 * MS, FWD + LATENT + "latent_out/dot_general:"),
        (FUSION.format(9), 30 * MS, 7 * MS, FWD + "mlp/moe/moe_shared/dot_general:"),
        (FUSION.format(10), 37 * MS, 1 * MS, FWD + "mlp/moe/moe_router/dot_general:"),
        (KERNEL.format("flash_dq", 11), 38 * MS, 16 * MS, BWD + LATENT + "flash_dq:"),
        (KERNEL.format("flash_dkv", 12), 54 * MS, 20 * MS, BWD + LATENT + "flash_dkv:"),
        (FUSION.format(13), 74 * MS, 8 * MS, BWD + LATENT + "latent_q/dot_general:"),
        (FUSION.format(14), 82 * MS, 14 * MS, BWD + "mlp/moe/moe_shared/dot_general:"),
        (FUSION.format(15), 96 * MS, 4 * MS,
         BWD + "rematted_computation/lead/" + LATENT + "latent_out/dot_general:"),
        # another program's operation, after the step
        (FUSION.format(16), 100 * MS, 1 * MS, "jit(convert)/lead/convert:"),
    ]
    modules = [("jit__bare_step", 0, 100 * MS), ("jit_convert", 100 * MS, MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def _kanana_record(counts="counts.deepseek_v3"):
    import named_trace
    from metrics import op_names

    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]
    devices = _kanana_devices()
    return {"trace": {}, "peaks": peaks["TPU v5 lite"], "conf": _conf(),
            "traffic": {"rows_per_chip": 2, "seq_len": 8192},
            "report": {"stepstats": {}},
            "counts": importlib.import_module(counts),
            "op_names": op_names.reduce_by_name(devices),
            "named_trace": named_trace.reduce_named(devices, {})}


def test_kanana_new_readers():
    _, _, counts = _modules()
    r = _kanana_record()
    read = lambda name: importlib.import_module("metrics." + name).read(r)
    # everything under attention_latent, the leading layer's too
    assert read("latent_attention_ms") == pytest.approx(
        4 + 6 + 3 + 2 + 1 + 6 + 3 + 16 + 20 + 8 + 4)
    # the five scopes around the kernels, every phase
    assert read("latent_proj_ms") == pytest.approx(4 + 3 + 2 + 1 + 3 + 8 + 4)
    assert read("lead_dense_ms") == pytest.approx(4 + 6 + 5 + 4)
    assert read("moe_shared_gated_ms") == pytest.approx(21.0)
    flops, nbytes = counts.attention_step_work(_conf(), 2, 8192)
    least = max(flops / 197e12, nbytes / 819e9)
    share = read("latent_flash_roofline_share")
    assert share == pytest.approx(100 * least / 0.048)
    assert 0 < share


@pytest.mark.parametrize("metric", sorted(KANANA_METRICS))
def test_kanana_a_program_without_the_names_reads_nothing(metric):
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
                  "counts": importlib.import_module("counts.deepseek_v3")}
        assert read(record) is None
    # a trace of a program that names none of this PR's scopes
    from metrics import op_names

    devices = {"/device:TPU:0": {
        "ops": [(FUSION.format(1), 0, 10 * MS,
                 FWD + "attention/attention_full/dot_general:")],
        "modules": [("jit__bare_step", 0, 10 * MS)]}}
    record = dict(_kanana_record(), op_names=op_names.reduce_by_name(devices))
    if metric != "latent_flash_roofline_share":  # reads the kernels by name
        assert read(record) is None


# -- the reference against the program, tiny, on the CPU --------------------

def _kanana_program_run(conf, control, batches, key, dtype="float32"):
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
    program["bias_moved"] = float(jnp.max(jnp.abs(
        state.params["layers"]["router"]["bias"]
        - reference.score_bias(2, 8))))
    return program


@pytest.fixture(scope="module")
def kanana_readings():
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
    tr = json.load(open(os.path.join(HERE, "traffic", "tiny.json")))
    generator = importlib.import_module("generators." + tr["generator"])
    batches = [generator.batch(conf, tr, 7, s, 1) for s in (1, 2, 3)]

    def ref_run(dtype):
        return check.reference_steps(
            lambda p, b: reference.loss(conf, p, b, compute_dtype=dtype),
            weights.draw(shapes, key),
            lambda k: weights.draw_leaf(shapes, k, key), batches, opt)

    return (_kanana_program_run(conf, False, batches, key),
            _kanana_program_run(conf, True, batches, key),
            ref_run(jnp.float32), ref_run(jnp.bfloat16))


def test_kanana_reference_agrees_with_the_program_in_float32(kanana_readings):
    program, _, ref, _ = kanana_readings
    numbers = check.compare(program, ref)
    assert {"grad_gap.wq", "grad_gap.wkv_a", "grad_gap.kv_a_norm",
            "grad_gap.wkv_b", "grad_gap.wo", "grad_gap.d_wq",
            "grad_gap.d_w_gate", "grad_gap.router", "grad_gap.w_gate",
            "grad_gap.shared_gate", "grad_gap.shared_down", "gnorm_gap",
            "delta_gap", "loss3_gap"} <= set(numbers)
    assert not [n for n in numbers if "bias" in n]
    # float32 on both sides: half-split rotation over permuted columns
    # against interleaved pairs, the chunked attention against blocks of
    # queries, the sort and the grouped product against a plain loop over
    # the experts. 2e-5 is some ten times what they read and far under what
    # bf16 reads below.
    assert all(v < 2e-5 for v, _leaf in numbers.values()), numbers
    counters = program["counters"]
    assert counters["moe.assignments_dropped"] == 0
    # 2 rows x 32 ids x 2 experts a token, half the experts held
    assert 40 <= counters["moe.assignments_held"] <= 88
    # the correction bias is a buffer: no step moves it
    assert program["bias_moved"] == 0.0


def test_kanana_bf16_in_the_programs_place_fails_the_same_comparison(
        kanana_readings):
    program, _, ref, bf16 = kanana_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    ok, _, _ = check.verdict(check.compare(program, ref), limits)
    bad, compared, _ = check.verdict(check.compare(bf16, ref), limits)
    assert ok and not bad, compared


def test_kanana_the_control_fails_it_too_on_every_part_of_a_layer(
        kanana_readings):
    """``run.py --control``: the program's own fp8 dense and routed expert
    products, and every other matrix of a bf16 product rounded to fp8: held
    to the float32 program's limits it comes out not correct, on latent
    attention's leaves, the dense layer's, the experts' and the shared
    expert's."""
    program, control, ref, _ = kanana_readings
    limits = {k: 2e-5 for k in check.compare(program, ref)}
    bad, compared, _ = check.verdict(check.compare(control, ref), limits)
    assert not bad
    assert all(compared[f"grad_gap.{k}"][0] > 2e-5
               for k in ("wq", "wkv_a", "wkv_b", "wo", "d_wq", "d_w_gate",
                         "d_w_down", "w_up", "w_down", "shared_gate",
                         "shared_down"))


# -- the tiny cell through the whole harness ---------------------------------

def _kanana_tiny_run(tmp_path, *flags):
    keep = str(tmp_path / "keep")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--spec", KANANA_SPEC,
         "--workload", "tiny.kanana", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0", "--keep", keep, *flags],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), keep


def test_kanana_the_tiny_cell_runs_and_its_counters_reach_the_report(tmp_path):
    line, keep = _kanana_tiny_run(tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert {"grad_gap.wkv_a", "grad_gap.d_w_gate", "grad_gap.router",
            "grad_gap.shared_gate"} <= set(line["compared"])
    assert not [n for n in line["compared"] if "bias" in n]
    report = json.load(open(os.path.join(keep, "report.json")))
    counters = report["stepstats"]["counters"]
    assert counters["moe.assignments_dropped"] == 0
    assert 40 <= counters["moe.assignments_held"] <= 88
    record = {"report": report, "conf": _tiny(),
              "traffic": {"rows_per_chip": 2, "seq_len": 32}}
    assert importlib.import_module(
        "metrics.moe_assignments_held_share").read(record) == pytest.approx(
            100 * counters["moe.assignments_held"] / 128)


def test_kanana_the_control_fails_the_tiny_cells_limits(tmp_path):
    line, _ = _kanana_tiny_run(tmp_path, "--control")
    assert line["correct"] is False
    over = [n for n, (value, limit) in line["compared"].items()
            if value > limit]
    assert over, line["compared"]
