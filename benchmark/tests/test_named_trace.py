"""``named_trace.py`` and the readers on it: the arithmetic on hand-built
events, the file's wire format on a hand-built file, and every reader on a
record that has nothing for it. Fast, no chip, no JAX.

    python -m pytest benchmark/tests/test_named_trace.py -q
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import named_trace  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TRACE_METRICS = ("flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "forward_ms",
                 "backward_ms", "recompute_ms", "optimizer_ms",
                 "head_loss_ms", "unscoped_ms")
SETUP_METRICS = ("worker_import_s", "device_attach_s", "init_state_s",
                 "bootstrap_unattributed_s", "ckpt_open_s")

MS = 1_000_000_000  # picoseconds
STEP = "jit(_bare_step)/"
FWD = STEP + "model/jvp()/while/body/closed_call/"
BWD = STEP + "model/transpose(jvp())/while/body/closed_call/checkpoint/"
FUSION = "%fusion.{} = bf16[8,2048]{{1,0}} fusion(bf16[8,2048]{{1,0}} %p), kind=kLoop"
PALLAS = ('%{}.{} = bf16[8,32,2048,128]{{3,2,1,0}} custom-call(bf16[8] %p), '
          'custom_call_target="tpu_custom_call"')
WHILE = "%while.8 = (s32[], bf16[8]) while((s32[], bf16[8]) %tuple.1), body=%b"


@pytest.mark.parametrize("op_name,phase,scope", [
    (FWD + "attention/flash_fwd/pallas_call:", "forward", "attention"),
    (FWD + "mlp/jit(silu)/logistic:", "forward", "mlp"),
    (STEP + "model/jvp(embed)/gather:", "forward", "embed"),
    (STEP + "model/jvp(head_loss)/jit(log_softmax)/reduce_max:", "forward",
     "head_loss"),
    (BWD + "attention/flash_dkv/pallas_call:", "backward", "attention"),
    (BWD + "mlp/dot_general:", "backward", "mlp"),
    (STEP + "model/transpose(jvp(head_loss))/dot_general:", "backward",
     "head_loss"),
    (BWD + "rematted_computation/mlp/dot_general:", "recompute", "mlp"),
    # the chunked loss replays its chunk inside its own backward pass
    (STEP + "model/transpose(jvp(head_loss))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general:", "recompute",
     "head_loss"),
    (STEP + "optimizer/mul:", "optimizer", "optimizer"),
    # a transpose the model itself asks for is no backward pass
    (FWD + "attention/transpose:", "forward", "attention"),
    (STEP + "convert_element_type:", None, None),
    ("", None, None),
])
def test_phase_and_scope_of_an_op_name(op_name, phase, scope):
    names = named_trace.names_of("%fusion.1 = f32[] fusion()", op_name)
    assert named_trace.phase_of(op_name, names) == phase
    assert next((s for s in named_trace.SCOPES if s in names), None) == scope


def test_a_kernel_is_named_by_its_op_name_or_by_its_instruction():
    by_op = named_trace.names_of(PALLAS.format("custom-call", 3),
                                 BWD + "attention/flash_dq/pallas_call:")
    by_text = named_trace.names_of(PALLAS.format("flash_dq", 10), "")
    assert "flash_dq" in by_op and "flash_dq" in by_text
    assert "flash_dq" not in named_trace.names_of(
        PALLAS.format("checkpoint", 20), BWD + "attention/pallas_call:")


def _events():
    """One chip: a cut run of the step, then a whole one of 100 ms with 2 ms
    idle at 50 ms, then a short program of something else."""
    ops = [
        # in the cut run: left out of every millisecond
        (FUSION.format(9), -30 * MS, 30 * MS, BWD + "mlp/dot_general:"),
        # the layers' loop holds the next three: not counted itself
        (WHILE, 0, 40 * MS, FWD + "while:"),
        (FUSION.format(1), 0, 10 * MS, STEP + "model/jvp(embed)/gather:"),
        (PALLAS.format("flash_fwd", 6), 10 * MS, 10 * MS,
         FWD + "attention/flash_fwd/pallas_call:"),
        (FUSION.format(2), 20 * MS, 20 * MS, FWD + "mlp/dot_general:"),
        (FUSION.format(3), 40 * MS, 10 * MS,
         STEP + "model/jvp(head_loss)/dot_general:"),
        # 2 ms idle here, under tpujob.input
        (FUSION.format(4), 52 * MS, 8 * MS,
         BWD + "rematted_computation/mlp/dot_general:"),
        (PALLAS.format("flash_dq", 10), 60 * MS, 6 * MS,
         BWD + "attention/flash_dq/pallas_call:"),
        (PALLAS.format("flash_dkv", 10), 66 * MS, 9 * MS,
         BWD + "attention/flash_dkv/pallas_call:"),
        (FUSION.format(5), 75 * MS, 5 * MS,
         STEP + "model/transpose(jvp(head_loss))/dot_general:"),
        (FUSION.format(6), 80 * MS, 15 * MS, STEP + "optimizer/mul:"),
        (FUSION.format(7), 95 * MS, 5 * MS, STEP + "convert_element_type:"),
        # after the step: another program's operation
        (FUSION.format(8), 100 * MS, 1 * MS, "jit(convert)/convert:"),
    ]
    modules = [("jit__bare_step", -30 * MS, 30 * MS),
               ("jit__bare_step", 0, 100 * MS),
               ("jit_convert", 100 * MS, 1 * MS)]
    host = [("tpujob.compute", 0, 49 * MS), ("tpujob.input", 49 * MS, 4 * MS),
            ("tpujob.compute", 53 * MS, 40 * MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}, host


def test_phases_partition_the_step():
    r = named_trace.reduce_named(*_events())
    assert r["steps"] == 1 and r["step_ms"] == pytest.approx(100.0)
    assert r["phases_ms"] == {
        "forward": pytest.approx(50.0), "backward": pytest.approx(20.0),
        "recompute": pytest.approx(8.0), "optimizer": pytest.approx(15.0),
        # one unscoped operation of 5 ms and the 2 ms the device idled
        "unscoped": pytest.approx(7.0)}
    assert sum(r["phases_ms"].values()) == pytest.approx(r["step_ms"])


def test_containers_are_not_counted_twice_and_a_cut_run_is_left_out():
    r = named_trace.reduce_named(*_events())
    # with the while's own 40 ms, or the cut run's 30, these would be more
    assert sum(r["scopes_ms"].values()) == pytest.approx(98.0)
    assert r["scopes_ms"]["mlp"] == pytest.approx(28.0)
    assert r["table_ms"]["mlp/forward"] == pytest.approx(20.0)
    assert r["table_ms"]["mlp/recompute"] == pytest.approx(8.0)
    assert "mlp/backward" not in r["table_ms"]


def test_scopes_and_kernels():
    r = named_trace.reduce_named(*_events())
    assert r["scopes_ms"] == {
        "embed": pytest.approx(10.0), "attention": pytest.approx(25.0),
        "mlp": pytest.approx(28.0), "head_loss": pytest.approx(15.0),
        "optimizer": pytest.approx(15.0), "_none": pytest.approx(5.0)}
    assert r["kernels_ms"] == {
        "flash_fwd": pytest.approx(10.0), "flash_dq": pytest.approx(6.0),
        "flash_dkv": pytest.approx(9.0)}


def test_a_kernel_is_its_mosaic_call_alone():
    """XLA hands the kernel's ``op_name`` to operations it makes around the
    call (seen on the chip: a ``reduce`` beside ``flash_fwd``): they count
    under the scope and the phase, not as the kernel."""
    devices, host = _events()
    devices["/device:TPU:0"]["ops"].append(
        ("%reduce.24 = f32[8,32,2048]{2,1,0} reduce(f32[8] %p), dimensions={3}",
         50 * MS, 2 * MS, FWD + "attention/flash_fwd/pallas_call:"))
    r = named_trace.reduce_named(devices, host)
    assert r["kernels_ms"]["flash_fwd"] == pytest.approx(10.0)
    assert r["scopes_ms"]["attention"] == pytest.approx(27.0)
    assert r["phases_ms"]["forward"] == pytest.approx(52.0)
    assert sum(r["kernels_ms"].values()) == pytest.approx(25.0)


def test_a_gap_goes_to_the_span_that_covers_most_of_it():
    devices, host = _events()
    r = named_trace.reduce_named(devices, host)
    # the window runs from the first operation to the last; its one gap
    # (50..52 ms) lies 2 ms under tpujob.input and 0 under tpujob.compute
    assert r["idle_gaps_s"] == {"tpujob.input": pytest.approx(0.002)}
    r = named_trace.reduce_named(devices, [])
    assert r["idle_gaps_s"] == {"host:_other": pytest.approx(0.002)}


def test_two_chips_are_averaged_and_no_whole_step_gives_no_milliseconds():
    devices, host = _events()
    devices["/device:TPU:1"] = devices["/device:TPU:0"]
    r = named_trace.reduce_named(devices, host)
    assert r["chips"] == 2 and r["steps"] == 2
    assert r["step_ms"] == pytest.approx(100.0)
    assert r["kernels_ms"]["flash_dkv"] == pytest.approx(9.0)
    assert r["idle_gaps_s"] == {"tpujob.input": pytest.approx(0.002)}
    ops = devices["/device:TPU:0"]["ops"]
    r = named_trace.reduce_named(
        {"/device:TPU:0": {"ops": ops, "modules": []}}, host)
    assert r["steps"] == 0 and "phases_ms" not in r
    assert named_trace.reduce_named({}, host) is None


# -- the file's wire format -------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        n, b = n >> 7, n & 0x7F
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def test_read_xplane_on_a_hand_built_file(tmp_path):
    stat_meta = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
                 _msg((1, 8), (2, _msg((1, 8), (2, STEP + "optimizer/mul:"))))]
    event_meta = [
        # op_name as a string, and as a reference to a stat's name
        _msg((1, 1), (2, _msg((1, 1), (2, FUSION.format(1)), (5, _msg(
            (1, 7), (5, FWD + "mlp/dot_general:")))))),
        _msg((1, 2), (2, _msg((1, 2), (2, FUSION.format(2)),
                              (5, _msg((1, 7), (7, 8)))))),
        _msg((1, 3), (2, _msg((1, 3), (2, "jit__bare_step(123)")))),
    ]
    ops = _msg((2, "XLA Ops"), (3, 1000),
               (4, _msg((1, 1), (2, 5 * MS), (3, 2 * MS))),
               (4, _msg((1, 2), (2, 7 * MS), (3, 1 * MS))))
    modules = _msg((2, "XLA Modules"), (3, 1000),
                   (4, _msg((1, 3), (2, 5 * MS), (3, 3 * MS))))
    other = _msg((2, "Steps"), (3, 1000), (4, _msg((1, 3), (2, 0), (3, 9))))
    device = _msg((2, "/device:TPU:0"), (3, ops), (3, modules), (3, other),
                  *[(4, m) for m in event_meta], *[(5, m) for m in stat_meta])
    host = _msg(
        (2, "/host:CPU"),
        (3, _msg((2, "python3"), (3, 1000),
                 (4, _msg((1, 1), (2, 4 * MS), (3, 3 * MS))),
                 (4, _msg((1, 2), (2, 0), (3, 1 * MS))))),
        (4, _msg((1, 1), (2, _msg((1, 1), (2, "tpujob.compute"))))),
        (4, _msg((1, 2), (2, _msg((1, 2), (2, "$builtins len"))))))
    idle = _msg((2, "#Chip0 Misc"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host), (1, idle)))
    ev = named_trace.read_xplane(str(path))
    t0 = 1000 * 1000  # the lines' timestamp, in picoseconds
    assert ev["devices"] == {"/device:TPU:0": {
        "ops": [(FUSION.format(1), t0 + 5 * MS, 2 * MS,
                 FWD + "mlp/dot_general:"),
                (FUSION.format(2), t0 + 7 * MS, 1 * MS,
                 STEP + "optimizer/mul:")],
        "modules": [("jit__bare_step(123)", t0 + 5 * MS, 3 * MS)]}}
    assert ev["host"] == [("tpujob.compute", t0 + 4 * MS, 3 * MS)]
    r = named_trace.reduce_dir(str(tmp_path))
    assert r["step_ms"] == pytest.approx(3.0)
    assert r["phases_ms"]["forward"] == pytest.approx(2.0)
    assert r["phases_ms"]["optimizer"] == pytest.approx(1.0)
    assert named_trace.reduce_dir(str(tmp_path / "nothing")) is None


# -- the readers --------------------------------------------------------------

def _record(stepstats, trace=None, marks=None):
    return {"report": {"stepstats": stepstats,
                       "marks": marks or {"worker_start": 10.0,
                                          "first_batch": 40.0}},
            "trace": trace}


@pytest.mark.parametrize("metric", TRACE_METRICS + SETUP_METRICS)
@pytest.mark.parametrize("stepstats", [None, {}, {"buckets": {}},
                                       {"profile": {}, "setup": {}}])
def test_a_reader_finds_nothing_in_a_record_of_an_older_program(
        metric, stepstats):
    """The parent of PR 26 acks no capture and reports no set-up: every new
    reader returns None there and does not raise, traced run or not."""
    read = importlib.import_module("metrics." + metric).read
    assert read(_record(stepstats)) is None
    assert read(_record(stepstats, trace={"step_s": 0.5})) is None


@pytest.mark.parametrize("metric", TRACE_METRICS)
def test_a_trace_reader_finds_nothing_without_a_trace(metric, tmp_path):
    stats = {"profile": {"id": "env", "state": "done", "dir": str(tmp_path)},
             "setup": {"pre_bootstrap": 1.0}}
    read = importlib.import_module("metrics." + metric).read
    assert read(_record(stats)) is None  # --trace 0
    # traced, and the directory holds no file
    assert read(_record(stats, trace={"step_s": 0.5})) is None


def test_trace_readers_read_the_reduction_once_a_run():
    record = _record({}, trace={"step_s": 0.1})
    record["named_trace"] = named_trace.reduce_named(*_events())
    got = {m: importlib.import_module("metrics." + m).read(record)
           for m in TRACE_METRICS}
    assert got == {
        "flash_fwd_ms": pytest.approx(10.0), "flash_dq_ms": pytest.approx(6.0),
        "flash_dkv_ms": pytest.approx(9.0), "forward_ms": pytest.approx(50.0),
        "backward_ms": pytest.approx(20.0), "recompute_ms": pytest.approx(8.0),
        "optimizer_ms": pytest.approx(15.0),
        "head_loss_ms": pytest.approx(15.0), "unscoped_ms": pytest.approx(7.0)}


def test_setup_readers_add_up_to_the_two_marks():
    setup = {"pre_bootstrap": 12.5, "cache_config": 0.25, "attach": 6.0,
             "mesh": 0.5, "ckpt_open": 1.5, "init_state": 8.0}
    record = _record({"setup": setup})
    got = {m: importlib.import_module("metrics." + m).read(record)
           for m in SETUP_METRICS}
    assert got == {"worker_import_s": 12.5, "device_attach_s": 6.25,
                   "init_state_s": 8.0, "ckpt_open_s": 1.5,
                   "bootstrap_unattributed_s": pytest.approx(30.0 - 28.75)}


def test_the_new_metrics_follow_the_accepted_ones_in_both_cells():
    names = [m["name"] for m in SPEC["per_layer"]]
    new = list(TRACE_METRICS + SETUP_METRICS)
    assert names[10:10 + len(new)] == new
    assert names[:10] == [
        "submit_to_worker_start_s", "bootstrap_s", "compile_cache_misses",
        "first_step_compile_s", "input_wait_share", "step_time_p95_ms",
        "step_mfu", "flash_roofline_share", "device_idle_share",
        "peak_hbm_gb"]
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for name in new:
        assert by_name[name]["workloads"] == [
            "mistral7b.steady-2k", "mistral7b.long-16k"]
    for name in TRACE_METRICS:
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
    for name in SETUP_METRICS:
        assert by_name[name]["source"] == "program_span"
        assert by_name[name]["moves"] == "setup_s"
