"""On-hardware test configuration.

Unlike tests/ (which pins a virtual 8-device CPU mesh), this directory runs
on whatever accelerator JAX finds — it exists to execute compiled Pallas
kernels and the operator path on a real TPU chip. Collected separately on
purpose, and as ONE invocation:

    python -m pytest tests_tpu/ -q     # on a TPU host

A chip belongs to one process at a time. The kernel tests take it in this
pytest process; the operator check (test_operator_on_tpu.py, a call of
chip_smoke.py) starts a worker process that needs it. So nothing here may
touch jax's backend at import or collection time, the operator check runs
first, and the kernel tests find out whether they are on a TPU only when
they run. Off-TPU the kernel tests skip themselves (tests/ already covers
the interpret path); the operator check fails, as chip_smoke.py does.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_OPERATOR_CHECK = "test_operator_on_tpu"


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: _OPERATOR_CHECK not in item.nodeid)


@pytest.fixture(autouse=True)
def _kernel_tests_need_the_chip(request):
    if _OPERATOR_CHECK in request.node.nodeid:
        return
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU chip")
