"""The one decoder over periods of unlike layers (models/llama.py): window
and full attention with RoPE by kind, the routed feed-forward's counters
out of the loss, and the dense model's program left as it was."""

import dataclasses
import hashlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models import llama
from mpi_operator_tpu.ops import (
    ElasticConfig, Trainer, TrainerConfig, run_elastic)
from mpi_operator_tpu.ops.data import make_global_batch
from mpi_operator_tpu.parallel import moe
from mpi_operator_tpu.runtime import MeshPlan, build_mesh, stepstats
from mpi_operator_tpu.runtime.topology import (
    AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR)

TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)


@pytest.fixture(scope="module")
def routed():
    cfg = llama.tiny_routed()
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


# the published settings of the full layers' RoPE that the benchmark's
# configuration states: theta 5e5, head 128, factor 16 over 8192 positions
MELLUM_YARN = llama.Yarn(factor=16.0, original_len=8192, beta_fast=32.0,
                         beta_slow=1.0, attention_factor=1.2772588722239782)


def test_yarn_ramp_by_hand():
    # dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 5e5): dim(32) = 18.08,
    # dim(1) = 34.98
    assert llama.yarn_ramp(128, 5e5, MELLUM_YARN) == (18, 35)


@pytest.mark.parametrize("k,scale", [
    (10, 1.0),                                   # below the ramp: kept
    (25, (1 - 7 / 17) + (7 / 17) / 16),          # on it: (25 - 18) / 17
    (40, 1 / 16),                                # past it: divided
])
def test_yarn_frequencies_by_hand(k, scale):
    freqs = llama.rope_frequencies(128, 5e5, MELLUM_YARN)
    assert float(freqs[k]) == pytest.approx(
        5e5 ** (-2 * k / 128) * scale, rel=1e-5)
    plain = llama.rope_frequencies(128, 5e5)
    assert float(plain[k]) == pytest.approx(5e5 ** (-2 * k / 128), rel=1e-5)


def test_yarn_tables_are_plain_and_the_factor_is_on_the_scores(routed):
    """cos and sin times the factor on q and k alike is the factor's square
    on their product: the tables stay cos and sin (1.277 is no bf16 number,
    and a bf16 table of it reads low at every position), and the scores'
    scale carries the square in float32."""
    cos, sin = llama._rope_tables(16, 128, 5e5, jnp.float32, MELLUM_YARN)
    assert float(cos[0, 0]) == 1.0
    angle = 7 * 5e5 ** (-2 * 40 / 128) / 16
    assert float(sin[7, 40]) == pytest.approx(math.sin(angle), rel=1e-4)
    # the tiny model's full layer: a factor of 1 moves the logits
    cfg, params = routed
    unit = dataclasses.replace(cfg, yarn_full=dataclasses.replace(
        cfg.yarn_full, attention_factor=1.0))
    gap = jnp.abs(llama.apply(cfg, params, TOKENS)
                  - llama.apply(unit, params, TOKENS))
    assert float(jnp.max(gap)) > 1e-3


def test_a_routed_loss_returns_its_counters(routed):
    cfg, params = routed
    loss, counters = llama.loss_fn(cfg, params, {"tokens": TOKENS})
    assert set(counters) == {moe.ASSIGNMENTS_HELD, moe.LOAD_MAX_OVER_MEAN,
                             moe.ASSIGNMENTS_DROPPED, moe.TOKEN_ROWS_READ}
    # half the experts held: about half of the 2 x 24 x 2 assignments
    assert 30 < float(counters[moe.ASSIGNMENTS_HELD]) < 66
    assert float(counters[moe.ASSIGNMENTS_DROPPED]) == 0
    # on the CPU the gathers in token order read every assignment's row
    assert float(counters[moe.TOKEN_ROWS_READ]) == 2 * 24 * 2
    assert np.isfinite(float(loss))
    # a dense model's loss is its value alone
    dense = llama.tiny()
    out = llama.loss_fn(dense, llama.init(dense, jax.random.PRNGKey(0)),
                        {"tokens": TOKENS})
    assert out.shape == ()


def test_flash_and_dense_paths_agree_on_a_period(routed):
    cfg, params = routed
    auto = llama.apply(cfg, params, TOKENS)
    dense = llama.apply(dataclasses.replace(cfg, attention_impl="dense"),
                        params, TOKENS)
    np.testing.assert_allclose(auto, dense, atol=2e-2)
    assert auto.shape == (2, 24, cfg.vocab)


def test_the_window_and_the_kinds_reach_the_attention(routed):
    """A model whose every layer is full differs from the period's, and so
    does one without YaRN: neither setting is dropped on the way."""
    cfg, params = routed
    base = llama.apply(cfg, params, TOKENS)
    wide = llama.apply(dataclasses.replace(cfg, window=24), params, TOKENS)
    plain = llama.apply(dataclasses.replace(cfg, yarn_full=None), params,
                        TOKENS)
    assert float(jnp.max(jnp.abs(base - wide))) > 1e-3
    assert float(jnp.max(jnp.abs(base - plain))) > 1e-3
    # positions inside the first window see the same keys either way, and
    # YaRN only touches the full layer, the last: nothing earlier moves
    np.testing.assert_allclose(base[:, :8], wide[:, :8], atol=2e-2)


def test_periods_are_the_layers_in_order():
    """Two periods of (full, full) are four full layers: the scan over
    periods walks the stacked weights in the order the plain scan does."""
    one = dataclasses.replace(llama.tiny(), n_layers=4,
                              compute_dtype=jnp.float32)
    two = dataclasses.replace(one, layer_kinds=("full", "full"))
    params = llama.init(one, jax.random.PRNGKey(2))
    np.testing.assert_allclose(
        llama.apply(one, params, TOKENS), llama.apply(two, params, TOKENS),
        atol=1e-5)


@pytest.mark.parametrize("change,match", [
    (dict(layer_kinds=("full", "banded")), "layer_kinds"),
    (dict(n_layers=3, layer_kinds=("full", "full")), "periods"),
    (dict(window=8), "window"),
    (dict(layer_kinds=("window",)), "window"),
    (dict(n_experts=8, experts_per_token=9, d_expert=4), "routed"),
    (dict(n_experts=8, experts_per_token=2, d_expert=4, first_expert=6,
          n_experts_held=4), "routed"),
])
def test_a_configuration_that_cannot_be_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(llama.tiny(), **change)


def test_a_window_over_a_sequence_mesh_raises(routed):
    cfg, params = routed
    mesh = build_mesh(MeshPlan(axes={AXIS_DATA: 1, AXIS_SEQ: 2}),
                      jax.devices()[:2])
    with pytest.raises(ValueError, match="ring"):
        llama.apply(cfg, params, TOKENS, mesh=mesh)


def test_param_count_counts_the_experts_held(routed):
    cfg, params = routed
    assert llama.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(params))


# -- the dense model's step is the program it was ---------------------------

# sha256 of the lowered text of Trainer._bare_step for llama.tiny() on one
# CPU device, as the commit before the period scan lowered it (2 x 32 ids
# plain; 2 x 4096 with remat_layers, where the chunked loss is on). A
# change that means to alter the dense decoder's step updates these.
DENSE_STEP_DIGESTS = {
    (False, 32):
        "40f64e3e07493cacb6520d6aefd03e9622b134c0e71ac4774af4af21a85f9050",
    (True, 4096):
        "c676add91bda82c64fdee8fe4b539c97ffb6aaae6c5ff66c15fda9c074c65f33",
}


# the same for llama.tiny_routed(), as PR 32's commit lowered it (on the
# CPU: ``lax.ragged_dot``, there a masked dense product, and the passes in
# row order over every row in ``jax.numpy``; PR 32 moved it: gathers that
# say their indices are in bounds, the activation and the rows read twice
# behind hand-written transposes, two gathers of numbers as sorts, one
# counter more; PR 39 took that counter, `moe.rows_worked`, out again)
# and one counter more since, `moe.token_rows_read`: on the CPU a constant a
# layer, the gathers in token order staying ``jax.numpy`` there
ROUTED_STEP_DIGESTS = {
    (False, 32):
        "31ee2af673e458ed114502c7873b7db0a0ed0a373c4090c490a15129ce70da1e",
    (True, 4096):
        "8b4bdce639592921cfab9b25f7b5935b147871bbee64bd2e7a126eb02c058547",
}


def _lowered_step(cfg, seq):
    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
    trainer = Trainer(lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh),
                      llama.logical_axes(cfg), mesh, TrainerConfig())
    state = trainer.init_state(llama.init(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jnp.zeros((2, seq), jnp.int32)}
    return trainer._jit_wrap(trainer._bare_step, state, batch).lower(
        state, batch).as_text()


@pytest.mark.parametrize("remat,seq", sorted(DENSE_STEP_DIGESTS))
def test_the_dense_step_lowers_to_the_text_it_lowered_to(remat, seq):
    text = _lowered_step(
        dataclasses.replace(llama.tiny(), remat_layers=remat), seq)
    assert "ragged" not in text and "stablehlo.sort" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        DENSE_STEP_DIGESTS[remat, seq]


@pytest.mark.parametrize("remat,seq", sorted(ROUTED_STEP_DIGESTS))
def test_the_routed_step_lowers_to_the_text_it_lowered_to(remat, seq):
    text = _lowered_step(
        dataclasses.replace(llama.tiny_routed(), remat_layers=remat), seq)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        ROUTED_STEP_DIGESTS[remat, seq]


# -- the counters' way out of the step ---------------------------------------

def _routed_trainer(cfg, mesh):
    return Trainer(lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh),
                   llama.logical_axes(cfg), mesh, TrainerConfig(),
                   donate=False)  # the fixture's weights are used again


def test_the_step_puts_the_losss_scalars_into_its_metrics(routed):
    cfg, params = routed
    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
    trainer = _routed_trainer(cfg, mesh)
    state, metrics = trainer.train_step(
        trainer.init_state(params), {"tokens": TOKENS})
    assert {"loss", "grad_norm", moe.ASSIGNMENTS_HELD,
            moe.LOAD_MAX_OVER_MEAN, moe.ASSIGNMENTS_DROPPED,
            moe.TOKEN_ROWS_READ} == set(metrics)
    _, counters = llama.loss_fn(cfg, params, {"tokens": TOKENS})
    assert float(metrics[moe.ASSIGNMENTS_HELD]) == float(
        counters[moe.ASSIGNMENTS_HELD])
    assert int(state.step) == 1


def test_the_counters_reach_the_blob(routed, tmp_path, monkeypatch):
    """Through run_elastic: the newest finished step's named scalars are
    in the stats file the executor mirrors, under ``counters``."""
    cfg, params = routed
    stats_file = tmp_path / "stats.json"
    monkeypatch.setenv(stepstats.ENV_STATS_FILE, str(stats_file))
    monkeypatch.setenv(stepstats.ENV_STATS_INTERVAL, "0")
    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
    trainer = _routed_trainer(cfg, mesh)

    def batches():
        while True:
            yield make_global_batch(mesh, {"tokens": np.asarray(TOKENS)})

    result = run_elastic(
        trainer, batches(), total_steps=3,
        config=ElasticConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                             save_interval_steps=10 ** 9),
        init_state=lambda: trainer.init_state(params),
        membership=lambda: 1, current_world=1)
    assert result.outcome == "done"
    blob = json.loads(stats_file.read_text())
    assert set(blob["counters"]) == {
        moe.ASSIGNMENTS_HELD, moe.LOAD_MAX_OVER_MEAN, moe.ASSIGNMENTS_DROPPED,
        moe.TOKEN_ROWS_READ}
    assert blob["counters"][moe.ASSIGNMENTS_DROPPED] == 0
    assert blob["counters"][moe.ASSIGNMENTS_HELD] == pytest.approx(
        result.metrics[moe.ASSIGNMENTS_HELD], abs=1e-3)


class _NotYet:
    def is_ready(self):
        return False


def test_a_flush_reads_only_a_finished_steps_counters():
    rec = stepstats.StepStatsRecorder()
    assert "counters" not in rec.snapshot()
    rec.set_counters({"loss": 1.0})  # no counter among them: nothing kept
    assert "counters" not in rec.snapshot()
    rec.set_counters({moe.ASSIGNMENTS_HELD: 7.0, "loss": 1.0})
    rec.set_counters({moe.ASSIGNMENTS_HELD: _NotYet()})
    # the newest step still runs: the one before it is what the blob says
    assert rec.snapshot()["counters"] == {moe.ASSIGNMENTS_HELD: 7.0}
    rec.set_counters({moe.ASSIGNMENTS_HELD: 9.0, "unknown.counter": 1.0})
    assert rec.snapshot()["counters"] == {moe.ASSIGNMENTS_HELD: 9.0}


def test_a_closing_recorder_waits_for_the_last_step():
    class Late(_NotYet):
        def __float__(self):  # what float() of a device value does: waits
            return 11.0

    rec = stepstats.StepStatsRecorder()
    rec.set_counters({moe.ASSIGNMENTS_HELD: 7.0})
    rec.set_counters({moe.ASSIGNMENTS_HELD: Late()})
    assert rec.snapshot()["counters"] == {moe.ASSIGNMENTS_HELD: 7.0}
    rec.close()
    assert rec.snapshot()["counters"] == {moe.ASSIGNMENTS_HELD: 11.0}


# -- layers that are one mixer each ------------------------------------------

@pytest.fixture(scope="module")
def hybrid():
    cfg = dataclasses.replace(llama.tiny_hybrid(),
                              compute_dtype=jnp.float32)
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def test_single_mixers_stack_by_kind(hybrid):
    cfg, params = hybrid
    layers = params["layers"]
    assert set(layers) == {"mamba", "experts", "attention"}
    lead = {kind: jax.tree.leaves(tree)[0].shape[0]
            for kind, tree in layers.items()}
    assert lead == {"mamba": 2, "experts": 2, "attention": 1}
    assert "w_gate" not in layers["experts"]
    assert {"shared_up", "shared_down"} <= set(layers["experts"])
    assert layers["experts"]["router"]["bias"].shape == (2, 8)
    assert llama.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    axes = llama.logical_axes(cfg)
    jax.tree.map(lambda a, ax: None if a.ndim == len(ax) else 1 / 0,
                 params, axes, is_leaf=lambda x: isinstance(x, tuple))


def test_two_periods_of_single_mixers_are_the_layers_in_pattern_order(hybrid):
    """Ten layers as two periods of five: the scan's second step takes each
    kind's later layers. Against the same layers walked one by one."""
    cfg, _ = hybrid
    cfg = dataclasses.replace(cfg, n_layers=10)
    params = llama.init(cfg, jax.random.PRNGKey(2))
    want = llama.apply(cfg, params, TOKENS)
    # the same model as one period of ten: kind i-th layers in order
    flat = dataclasses.replace(cfg, layer_kinds=cfg.layer_kinds * 2)
    np.testing.assert_allclose(llama.apply(flat, params, TOKENS), want,
                               atol=1e-5, rtol=1e-5)
    # and the order matters: the first period's layers swapped for the
    # second's give another model
    swapped = jax.tree.map(lambda a: a[::-1], params["layers"])
    other = llama.apply(cfg, dict(params, layers=swapped), TOKENS)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3


def test_the_single_attention_layer_has_no_positions():
    """Causal attention that rotates nothing: the last position's result
    does not change when the tokens before it change places (one layer:
    a second would read the first's results, which follow their prefixes)."""
    cfg = dataclasses.replace(
        llama.tiny(), layer_kinds=("attention",), n_layers=1,
        compute_dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    assert set(params["layers"]) == {"attention"}
    moved = TOKENS.at[:, :-1].set(TOKENS[:, :-1][:, ::-1])
    a = llama.apply(cfg, params, TOKENS)[:, -1]
    b = llama.apply(cfg, params, moved)[:, -1]
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    # a pair's attention rotates: there the order shows
    pair = llama.tiny()
    pair = dataclasses.replace(pair, compute_dtype=jnp.float32)
    pp = llama.init(pair, jax.random.PRNGKey(0))
    assert float(jnp.max(jnp.abs(
        llama.apply(pair, pp, TOKENS)[:, -1]
        - llama.apply(pair, pp, moved)[:, -1]))) > 1e-3


def test_the_hybrid_steps_scalars_reach_its_metrics(hybrid):
    cfg, params = hybrid
    mesh = build_mesh(MeshPlan.data_parallel(1), jax.devices()[:1])
    trainer = _routed_trainer(cfg, mesh)
    _, metrics = trainer.train_step(
        trainer.init_state(params), {"tokens": TOKENS})
    assert {"loss", "grad_norm", moe.ASSIGNMENTS_HELD,
            moe.LOAD_MAX_OVER_MEAN, moe.ASSIGNMENTS_DROPPED,
            moe.TOKEN_ROWS_READ, "ssm.carry_share"} == set(metrics)
    assert 0.0 <= float(metrics["ssm.carry_share"]) <= 1.0
    assert "ssm.carry_share" in stepstats.TRAIN_COUNTERS


@pytest.mark.parametrize("change", [
    dict(layer_kinds=("mamba", "full")),  # a pair among single mixers
    dict(layer_kinds=("mamba", "linear")),  # a kind it does not know
    dict(n_dense_layers=1),  # leading dense pairs before single mixers
    dict(router_score="tanh"),
    dict(n_experts=0, n_experts_held=0),  # an 'experts' layer, no experts
    dict(ssm_heads=0), dict(ssm_groups=3), dict(ssm_chunk=0),
    dict(n_layers=7),  # no whole number of periods
])
def test_config_refuses_what_the_decoder_does_not_compute(change):
    with pytest.raises(ValueError):
        dataclasses.replace(llama.tiny_hybrid(), **change)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused(hybrid):
    cfg, params = hybrid
    with pytest.raises(ValueError, match="whole number of chunks"):
        llama.apply(cfg, params, TOKENS[:, :20])


# -- latent attention, and leading dense pairs before the routed ones --------

import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:  # the plain reference and the adapter live there
    sys.path.insert(0, _BENCH)


@pytest.fixture(scope="module")
def latent():
    cfg = dataclasses.replace(llama.tiny_latent(),
                              compute_dtype=jnp.float32)
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def test_latent_pairs_have_their_tree_and_the_dense_ones_a_stack(latent):
    cfg, params = latent
    attention = {"attn_norm", "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo"}
    assert set(params["lead"]) == attention | {
        "mlp_norm", "w_gate", "w_up", "w_down"}
    assert set(params["layers"]) == attention | {
        "mlp_norm", "router", "w_gate", "w_up", "w_down", "shared_gate",
        "shared_up", "shared_down"}
    shape = lambda tree, name: tree[name]["w"].shape
    # keys and queries 24 = 16 + 8 wide, values 16, a latent of 32
    assert shape(params["layers"], "wq") == (2, 48, 4 * 24)
    assert shape(params["layers"], "wkv_a") == (2, 48, 32 + 8)
    assert shape(params["layers"], "wkv_b") == (2, 32, 4 * (16 + 16))
    assert shape(params["layers"], "wo") == (2, 4 * 16, 48)
    assert shape(params["lead"], "wq") == (1, 48, 4 * 24)
    assert shape(params["lead"], "w_gate") == (1, 48, 96)
    assert shape(params["layers"], "w_gate") == (2, 4, 48, 24)
    assert shape(params["layers"], "shared_gate") == (2, 48, 48)
    assert llama.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    axes = llama.logical_axes(cfg)
    jax.tree.map(lambda a, ax: None if a.ndim == len(ax) else 1 / 0,
                 params, axes, is_leaf=lambda x: isinstance(x, tuple))


def test_the_latent_flash_and_dense_paths_agree(latent):
    cfg, params = latent
    dense = dataclasses.replace(cfg, attention_impl="dense")
    np.testing.assert_allclose(
        llama.apply(cfg, params, TOKENS), llama.apply(dense, params, TOKENS),
        atol=1e-5, rtol=1e-5)


def test_the_leading_pairs_and_the_periods_are_two_scans(latent):
    """Whatever the depth: the leading dense pairs are scanned, not
    unrolled, and so are the routed ones; the router's counters are the
    routed layers' alone."""
    cfg, _ = latent
    deep = dataclasses.replace(cfg, n_layers=7, n_dense_layers=3)
    params = llama.init(deep, jax.random.PRNGKey(3))
    jaxpr = jax.make_jaxpr(lambda p: llama.loss_fn(
        deep, p, {"tokens": TOKENS})[0])(params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [3, 4]
    _, counters = llama._forward(deep, params, TOKENS, mesh=None, rules=None,
                                 return_features=False)
    assert {v.shape for v in counters.values()} == {(4,)}
    _, scalars = llama.loss_fn(deep, params, {"tokens": TOKENS})
    np.testing.assert_allclose(
        scalars[moe.ASSIGNMENTS_HELD],
        jnp.mean(counters[moe.ASSIGNMENTS_HELD]))


def test_the_leading_pairs_feed_forward_is_the_dense_one(latent):
    """Nought in the leading stack's ``w_down`` leaves the layer its
    attention alone, and the routed layers' experts are untouched by it."""
    cfg, params = latent
    want = llama.apply(cfg, params, TOKENS)
    lead = dict(params["lead"], w_down={"w": jnp.zeros_like(
        params["lead"]["w_down"]["w"])})
    got = llama.apply(cfg, dict(params, lead=lead), TOKENS)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-3
    assert cfg.d_ff == 96 and cfg.routed


def test_one_rotated_key_serves_every_head_and_values_do_not_rotate(latent):
    """The last position's result follows the order of the tokens before it
    (the rotated parts carry position); with the rotated columns of ``wq``
    nought it does not: what is left of a score is the unrotated part, and
    values and the latent carry no position."""
    cfg, _ = latent
    # one layer: a second would read the first's results, which follow
    # their prefixes
    cfg = dataclasses.replace(cfg, n_layers=1, n_dense_layers=0)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    assert "lead" not in params
    moved = TOKENS.at[:, :-1].set(TOKENS[:, :-1][:, ::-1])
    last = lambda p, t: llama.apply(cfg, p, t)[:, -1]
    assert float(jnp.max(jnp.abs(
        last(params, TOKENS) - last(params, moved)))) > 1e-3
    wq = params["layers"]["wq"]["w"]
    blind = dict(params, layers=dict(params["layers"], wq={
        "w": wq.reshape(*wq.shape[:-1], 4, 24).at[..., 16:].set(0.0)
        .reshape(wq.shape)}))
    np.testing.assert_allclose(last(blind, TOKENS), last(blind, moved),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("change,match", [
    (dict(kv_lora_rank=0), "latent attention"),
    (dict(qk_rope_dim=7), "latent attention"),
    (dict(v_head_dim=0), "latent attention"),
    (dict(n_experts=0, n_experts_held=0, d_shared=0), "n_dense_layers"),
    (dict(n_layers=4, layer_kinds=("latent", "full")), "n_dense_layers"),
    (dict(n_dense_layers=3), "periods"),
    (dict(n_layers=4, n_dense_layers=0, layer_kinds=("latent",) * 3),
     "periods"),
])
def test_a_latent_configuration_that_cannot_be_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(llama.tiny_latent(), **change)


def test_latent_attention_over_a_sequence_mesh_raises(latent):
    cfg, params = latent
    mesh = build_mesh(MeshPlan(axes={AXIS_DATA: 1, AXIS_SEQ: 2}),
                      jax.devices()[:2])
    with pytest.raises(ValueError, match="ring"):
        llama.apply(cfg, params, TOKENS, mesh=mesh)


# tiny_latent through Trainer against the plain reference
# (benchmark/reference/deepseek_v3.py, float32 at ``highest``): the loss and
# EVERY element of every leaf's gradient, read from Adam's first moment
# after one step without a clip (mu = (1 - beta1) g exactly), on one device
# and on four virtual ones.

LATENT_ROWS = jax.random.randint(jax.random.PRNGKey(11), (4, 32), 0, 256)


def _latent_conf():
    with open(os.path.join(_BENCH, "tests", "tiny-kanana-cpu.json")) as f:
        return json.load(f)


def _latent_step(axes, dtype):
    """(loss, {flat leaf: gradient}) of the program's first step on a mesh
    of ``axes``, its compute dtype ``dtype``."""
    import weights

    adapter = importlib.import_module("adapters.deepseek_v3")
    reference = importlib.import_module("reference.deepseek_v3")
    conf = _latent_conf()
    cfg = adapter.config(dict(conf, assumed=dict(
        conf["assumed"], compute_dtype=dtype)))
    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshPlan(axes=axes), jax.devices()[:n])
    trainer = Trainer(
        adapter.loss_fn(cfg, mesh), adapter.logical_axes(cfg), mesh,
        TrainerConfig(learning_rate=1e-3, beta1=0.9, grad_clip_norm=0.0))
    flat = weights.draw(reference.param_shapes(conf), weights.seed_key(7))
    state = trainer.init_state(adapter.to_tree(flat))
    state, metrics = trainer.train_step(
        state, make_global_batch(mesh, {"tokens": np.asarray(LATENT_ROWS)}))
    moments = [n.mu for n in jax.tree.leaves(
        state.opt_state, is_leaf=lambda n: hasattr(n, "mu"))
        if hasattr(n, "mu")]
    mu = adapter.to_flat(moments[0])
    return float(metrics["loss"]), {k: np.asarray(v) / (1 - 0.9)
                                    for k, v in mu.items()}


@pytest.fixture(scope="module")
def latent_reference():
    import weights

    reference = importlib.import_module("reference.deepseek_v3")
    conf = _latent_conf()
    flat = weights.draw(reference.param_shapes(conf), weights.seed_key(7))
    loss, grads = jax.value_and_grad(
        lambda p: reference.loss(conf, p, {"tokens": LATENT_ROWS}))(flat)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def _worst_leaf(got, want):
    """The widest gap of any element of any leaf, against the leaf's
    largest element."""
    return max(float(np.max(np.abs(got[k] - want[k])) / np.max(np.abs(want[k])))
               for k in want)


def test_the_preset_is_the_tiny_configurations_shape():
    adapter = importlib.import_module("adapters.deepseek_v3")
    assert adapter.config(_latent_conf()) == dataclasses.replace(
        llama.tiny_latent(), remat_layers=True)


@pytest.mark.parametrize("axes", [
    {AXIS_DATA: 1}, {AXIS_FSDP: 4}, {AXIS_FSDP: 2, AXIS_TENSOR: 2}],
    ids=["one", "fsdp4", "fsdp2-tensor2"])
def test_the_latent_step_is_the_plain_references(latent_reference, axes):
    """Float32 on both sides: half-split rotation over permuted columns
    against interleaved pairs, the flash path's chunked form against blocks
    of queries, the sort and the grouped products against a loop over the
    experts; over four devices the kernels' and the experts' ``shard_map``
    and the partitioner's collectives besides. 2e-5 of a leaf's largest
    element is some twenty times what they read (1e-6) and a five-hundredth
    of what bf16 reads below."""
    want_loss, want = latent_reference
    loss, got = _latent_step(axes, "float32")
    assert set(got) == set(want)
    assert abs(loss - want_loss) / want_loss < 2e-6
    assert _worst_leaf(got, want) < 2e-5


def test_bf16_in_the_latent_programs_place_fails_it(latent_reference):
    want_loss, want = latent_reference
    loss, got = _latent_step({AXIS_DATA: 1}, "bfloat16")
    assert abs(loss - want_loss) / want_loss > 2e-6
    assert _worst_leaf(got, want) > 1e-2


# -- query heads, theta and rotation by kind; the head gate; a leading dense
# pair before a period of several kinds ------------------------------------

@pytest.fixture(scope="module")
def gated():
    cfg = dataclasses.replace(llama.tiny_gated(), compute_dtype=jnp.float32)
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def test_pairs_of_unlike_heads_stack_by_kind_behind_the_lead(gated):
    cfg, params = gated
    assert cfg.stacked_by_kind and cfg.lead == "full"
    layers = params["layers"]
    assert set(layers) == {"full", "window"}
    shape = lambda tree, name: tree[name]["w"].shape
    # 4 query heads on full layers, 6 on window layers, over 2 KV heads
    assert shape(layers["window"], "wq") == (3, 48, 6 * 16)
    assert shape(layers["window"], "wo") == (3, 6 * 16, 48)
    assert shape(layers["window"], "wg") == (3, 48, 6)
    assert shape(layers["window"], "wk") == (3, 48, 2 * 16)
    assert shape(layers["full"], "wq") == (1, 48, 4 * 16)
    assert shape(layers["full"], "wg") == (1, 48, 4)
    assert shape(params["lead"], "wq") == (1, 48, 4 * 16)
    assert shape(params["lead"], "wg") == (1, 48, 4)
    assert shape(params["lead"], "w_gate") == (1, 48, 96)
    assert shape(layers["full"], "w_gate") == (1, 4, 48, 24)
    assert "bias" not in layers["window"]["router"]  # sigmoid, no bias
    assert llama.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    axes = llama.logical_axes(cfg)
    assert axes["layers"]["window"]["wg"]["w"] == (None, "embed", "heads")
    jax.tree.map(lambda a, ax: None if a.ndim == len(ax) else 1 / 0,
                 params, axes, is_leaf=lambda x: isinstance(x, tuple))


def test_the_gated_flash_and_dense_paths_agree(gated):
    cfg, params = gated
    dense = dataclasses.replace(cfg, attention_impl="dense")
    np.testing.assert_allclose(
        llama.apply(cfg, params, TOKENS), llama.apply(dense, params, TOKENS),
        atol=1e-5, rtol=1e-5)


def test_the_gate_and_each_kinds_rope_reach_the_attention(gated):
    """Each of the by-kind settings moves the logits: the gate (a model
    without it reads other logits), the full layers' theta and rotated
    share, the window layers' heads being their own stack."""
    cfg, params = gated
    base = llama.apply(cfg, params, TOKENS)
    moved = lambda c, p=params: float(jnp.max(jnp.abs(
        llama.apply(c, p, TOKENS) - base)))
    ungated = jax.tree.map(lambda a: a, params)
    for tree in (ungated["lead"], *ungated["layers"].values()):
        del tree["wg"]
    assert moved(dataclasses.replace(cfg, head_gate=False), ungated) > 1e-3
    full = lambda **change: dataclasses.replace(cfg, attention_by_kind=tuple(
        (k, a._replace(**change) if k == "full" else a)
        for k, a in cfg.attention_by_kind))
    assert moved(full(theta=0.0)) > 1e-3  # the plain theta
    assert moved(full(rotated=0)) > 1e-3  # the whole head
    yarn = dataclasses.replace(cfg.yarn_full, attention_factor=1.0)
    assert moved(dataclasses.replace(cfg, yarn_full=yarn)) > 1e-3


def test_a_partial_rotation_turns_the_first_dims_and_scales_them_alone():
    """The first ``rotated`` dims turn at frequencies over those dims alone
    and carry YaRN's factor on cos and sin; the rest pass untouched and
    unscaled. A whole head keeps the factor off its tables (it is on the
    scores' scale there)."""
    yarn = llama.Yarn(factor=4.0, original_len=16, beta_fast=8.0,
                      attention_factor=1.2)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 3, 16))
    got = llama._rope_first(llama._rope, x, 8, 5e5, yarn)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    cos, sin = llama._rope_tables(5, 8, 5e5, jnp.float32, yarn)
    x1, x2 = x[..., :4], x[..., 4:8]
    c, s = cos[None, :, None], sin[None, :, None]
    np.testing.assert_allclose(
        got[..., :8], 1.2 * jnp.concatenate([x1 * c - x2 * s,
                                             x1 * s + x2 * c], -1),
        rtol=1e-6, atol=1e-6)
    whole = llama._rope_first(llama._rope, x, 16, 5e5, yarn)
    np.testing.assert_allclose(whole, llama._rope(x, 5e5, yarn), rtol=1e-6)
    # the heads-major layout turns the same numbers
    np.testing.assert_allclose(
        llama._rope_first(llama._rope_bhtd, x.transpose(0, 2, 1, 3), 8, 5e5,
                          yarn).transpose(0, 2, 1, 3), got, rtol=1e-6)


@pytest.mark.parametrize("change,match", [
    # a lead before a period of several kinds names its kind
    (dict(lead_kind=""), "n_dense_layers"),
    (dict(lead_kind="latent"), "n_dense_layers"),
    (dict(attention_by_kind=(("window", llama.KindAttention(heads=5)),)),
     "window attention"),
    (dict(attention_by_kind=(("full", llama.KindAttention(rotated=5)),)),
     "full attention"),
    (dict(attention_by_kind=(("full", llama.KindAttention(theta=-1.0)),)),
     "full attention"),
    (dict(attention_by_kind=(("latent", llama.KindAttention(heads=4)),)),
     "by kind"),
    (dict(n_experts=0, n_experts_held=0), "n_dense_layers"),
])
def test_config_refuses_the_gated_shapes_it_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(llama.tiny_gated(), **change)
    with pytest.raises(ValueError, match="by kind"):
        dataclasses.replace(llama.tiny_latent(), head_gate=True)


@pytest.mark.parametrize("name", ["tiny_hybrid", "tiny_gated"])
def test_a_stack_by_kind_lowers_to_one_text_whatever_the_string_hash(name):
    """The kinds' stacks enter the scan in a fixed order: without it the
    step's text, and so its compile cache key, followed the process's
    string hash, and a fresh process missed the entry another wrote."""
    import subprocess
    import sys as _sys

    code = (
        "import dataclasses, hashlib, jax, jax.numpy as jnp\n"
        "from mpi_operator_tpu.models import llama\n"
        f"cfg = llama.{name}()\n"
        "p = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))\n"
        "t = jax.ShapeDtypeStruct((2, 32), jnp.int32)\n"
        "text = jax.jit(lambda p, t: llama.loss_fn(cfg, p, {'tokens': t})[0]"
        ").lower(p, t).as_text()\n"
        "print(hashlib.sha256(text.encode()).hexdigest())\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digests = set()
    for seed in ("1", "2", "3"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=root)
        out = subprocess.run([_sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.add(out.stdout.strip())
    assert len(digests) == 1
