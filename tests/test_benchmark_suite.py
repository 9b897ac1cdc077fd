"""The benchmark's own tests (``benchmark/tests/``), collected into tier-1:
a benchmark PR may add no file outside the benchmark's paths, so they live
there (PERF.md §7)."""

from benchmark.tests.test_benchmark import *  # noqa: F401,F403
from benchmark.tests.test_named_trace import *  # noqa: F401,F403
from benchmark.tests.test_ckpt_import_s import *  # noqa: F401,F403
from benchmark.tests.test_mellum import *  # noqa: F401,F403


def test_its_entry_is_the_last_and_sits_beside_ckpt_open_s():  # noqa: F811
    """A stopgap, and it says when to go. ``test_ckpt_import_s``'s test of
    this name pins PR 27's entry as ``per_layer``'s last, which no later
    entry can leave true, and a PR may edit no file of the benchmark: this
    takes its place in tier-1 until a ``benchmark`` issue corrects the
    original to "after ``ckpt_open_s``, unchanged" (PERF.md section 7, row
    18c). It holds the entry to where PR 27 put it, and it fails once the
    original passes again, so that it cannot outlive its reason."""
    import json
    import os

    import pytest

    from benchmark.tests import test_ckpt_import_s as original

    with pytest.raises(AssertionError):
        # corrected? then delete this function: the import above runs it
        original.test_its_entry_is_the_last_and_sits_beside_ckpt_open_s()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    by_name = {m["name"]: m for m in per_layer}
    assert [m["name"] for m in per_layer].index("ckpt_import_s") == 24
    entry = by_name["ckpt_import_s"]
    assert entry == {
        "name": "ckpt_import_s", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "checkpoint",
        "moves": "setup_s",
        "workloads": ["mistral7b.steady-2k", "mistral7b.long-16k"]}
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == by_name["ckpt_open_s"][key]
