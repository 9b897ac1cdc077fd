"""The learned-selection configuration's benchmark tests
(``benchmark/tests/test_keye.py``), collected into tier-1 as
``tests/test_benchmark_suite.py`` collects the others: a file of their own,
so that a parallel run gives their two minutes to a worker of their own.
"""

from benchmark.tests.test_keye import *  # noqa: F401,F403
