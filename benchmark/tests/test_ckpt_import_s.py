"""``ckpt_import_s`` (PR 27): the reader on records with and without the
blob's ``setup_overlapped``, and its entry in ``BENCHMARK.json``. Fast, no
chip, no JAX.

    python -m pytest benchmark/tests/test_ckpt_import_s.py -q
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


def _read(stepstats, trace=None):
    record = {"report": {"stepstats": stepstats,
                         "marks": {"worker_start": 10.0,
                                   "first_batch": 40.0}},
              "trace": trace}
    return importlib.import_module("metrics.ckpt_import_s").read(record)


@pytest.mark.parametrize("stepstats", [
    None, {}, {"buckets": {}}, {"setup": {"ckpt_open": 12.5}},
    {"setup_overlapped": None}, {"setup_overlapped": {}},
    # a program with the lazy manager and no thread's seconds in its blob
    {"setup": {"ckpt_open": 0.001}, "setup_overlapped": {}},
])
def test_it_finds_nothing_in_a_record_of_an_older_program(stepstats):
    """The parent of PR 27 reports no `setup_overlapped`: the reader returns
    None there and does not raise, traced run or not."""
    assert _read(stepstats) is None
    assert _read(stepstats, trace={"step_s": 0.5}) is None


def test_it_reads_the_thread_s_seconds_and_nothing_of_the_spans():
    stats = {"setup": {"attach": 8.5, "ckpt_open": 0.002},
             "setup_overlapped": {"ckpt_import": 13.25}}
    assert _read(stats) == 13.25
    assert _read(stats, trace={"step_s": 0.5}) == 13.25
    ckpt_open = importlib.import_module("metrics.ckpt_open_s").read(
        {"report": {"stepstats": stats}, "trace": None})
    assert ckpt_open == 0.002  # the span beside it is read as before


def test_its_entry_is_the_last_and_sits_beside_ckpt_open_s():
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    assert SPEC["per_layer"][-1]["name"] == "ckpt_import_s"
    entry = by_name["ckpt_import_s"]
    assert entry == {
        "name": "ckpt_import_s", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "checkpoint",
        "moves": "setup_s",
        "workloads": ["mistral7b.steady-2k", "mistral7b.long-16k"]}
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == by_name["ckpt_open_s"][key]
