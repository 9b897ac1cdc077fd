"""The two readers of the routed feed-forward's token sums: their entries
in BENCHMARK.json (appended, listing the three routed cells), the device
time under the kernel's name on a hand-built trace, the counter's share on
a hand-built record, and None from a program without either (the parent's).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_sum_readers.py -q
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

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ROUTED_CELLS = ["mellum2.steady-8k", "nemotron3nano.steady-8k",
                "kanana2.steady-8k"]
TOKEN_SUM_READERS = ("moe_token_sum_ms", "moe_token_rows_share")

PS = 1_000_000_000  # picoseconds a millisecond
STEP = "jit(_bare_step)/model/"
PALLAS = ('%{}.{} = bf16[16384,2304]{{1,0}} custom-call(bf16[131072,2304] '
          '%p), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.{} = bf16[8,2048]{{1,0}} fusion(bf16[8,2048]{{1,0}} %p), kind=kLoop"


def test_the_two_entries_are_appended_for_the_routed_cells():
    metrics = SPEC["per_layer"]
    assert tuple(m["name"] for m in metrics[-2:]) == TOKEN_SUM_READERS
    for m, unit, source in zip(metrics[-2:], ("ms", "%"),
                               ("device_trace", "program_counter")):
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": "routed feed-forward",
                     "moves": "tokens_per_s_per_chip",
                     "workloads": ROUTED_CELLS}
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert all(cells[c]["chips"] == 1 for c in ROUTED_CELLS)


def _record(counters=None, with_kernel=True):
    from metrics import op_names

    ops = [(FUSION.format(1), 0, 3 * PS,
            STEP + "jvp()/mlp/moe/moe_dispatch/sort:")]
    if with_kernel:
        ops += [
            # the combine's forward, its replay, the dispatch's transpose
            (PALLAS.format("moe_token_sum", 1), 3 * PS, 2 * PS,
             STEP + "jvp()/mlp/moe/moe_combine/moe_token_sum/pallas_call:"),
            (PALLAS.format("moe_token_sum", 2), 5 * PS, 2 * PS,
             STEP + "transpose(jvp())/checkpoint/rematted_computation/mlp/"
             "moe/moe_combine/moe_token_sum/pallas_call:"),
            (PALLAS.format("moe_token_sum", 3), 7 * PS, 1 * PS,
             STEP + "transpose(jvp())/checkpoint/mlp/moe/moe_dispatch/"
             "moe_token_sum/pallas_call:"),
        ]
    devices = {"/device:TPU:0": {
        "ops": ops, "modules": [("jit__bare_step", 0, 10 * PS)]}}
    return {"trace": {}, "conf": {"num_experts_per_tok": 8},
            "traffic": {"rows_per_chip": 2, "seq_len": 8192},
            "report": {"stepstats": {"counters": counters} if counters
                       else {}},
            "op_names": op_names.reduce_by_name(devices)}


def _read(name, record):
    return importlib.import_module("metrics." + name).read(record)


def test_the_kernels_time_is_every_call_in_every_phase():
    r = _record()
    assert _read("moe_token_sum_ms", r) == pytest.approx(5.0)
    from metrics import op_names

    assert op_names.by_phase(r, "moe_token_sum") == {
        "forward": pytest.approx(2.0), "recompute": pytest.approx(2.0),
        "backward": pytest.approx(1.0)}
    # the kernel's time is inside the two scopes moe_dispatch_combine_ms
    # reads
    assert _read("moe_dispatch_combine_ms", r) == pytest.approx(8.0)


def test_the_share_is_the_rows_read_over_every_assignment():
    r = _record({"moe.token_rows_read": 45056.0})
    assert _read("moe_token_rows_share", r) == pytest.approx(
        100 * 45056 / (2 * 8192 * 8))
    every = _record({"moe.token_rows_read": 2 * 8192 * 8.0})
    assert _read("moe_token_rows_share", every) == pytest.approx(100.0)


@pytest.mark.parametrize("name", TOKEN_SUM_READERS)
def test_a_program_without_the_kernel_or_the_counter_reads_none(name):
    """The parent's program: no kernel of that name, no such counter; and a
    run without a trace or a blob."""
    assert _read(name, _record({"moe.assignments_held": 1.0},
                               with_kernel=False)) is None
    bare = {"trace": None, "conf": {"num_experts_per_tok": 8},
            "traffic": {"rows_per_chip": 2, "seq_len": 8192},
            "report": {"stepstats": None}}
    assert _read(name, bare) is None
