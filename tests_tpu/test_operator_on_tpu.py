"""The operator path driving a REAL TPU workload: one call of chip_smoke.py.

tests/test_e2e.py proves the control plane with CPU gangs; chip_smoke.py
(repo root) proves the missing link on hardware — a full-width TPUJob through
controller → gang scheduler → local executor → worker → run_elastic on the
actual chip, cold then resumed. This file is that script's seat in the
hardware tier, so there is one copy of the on-chip operator check.

The script's worker needs the chip, and a chip belongs to one process at a
time: conftest.py runs this test before anything initializes jax's TPU
backend in the pytest process. Off the chip it fails rather than skips —
the script refuses to pass there, and says what it found.
"""

import json
import os
import subprocess
import sys

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_passes_on_the_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=1200, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert tuple(last) == chip_smoke.RESULT_KEYS
    assert last["ok"] is True and last["device"]["platform"] == "tpu"
