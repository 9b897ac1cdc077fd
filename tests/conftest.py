"""Test configuration.

All tests run on a virtual 8-device CPU mesh (the envtest-equivalent trick
from SURVEY.md §4: real semantics, no TPU hardware). JAX_PLATFORMS is set
for the child processes tests start; the jax.config update below pins THIS
process even where the variable was read before conftest ran. XLA_FLAGS
still applies because no backend has been initialized yet at conftest
import time.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
