"""The benchmark's own tests (``benchmark/tests/``), collected into tier-1:
a benchmark PR may add no file outside the benchmark's paths, so they live
there (PERF.md §7)."""

from benchmark.tests.test_benchmark import *  # noqa: F401,F403
from benchmark.tests.test_named_trace import *  # noqa: F401,F403
from benchmark.tests.test_ckpt_import_s import *  # noqa: F401,F403
