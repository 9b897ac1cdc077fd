"""chip_smoke.py's contract with whoever runs it, pinned off the chip.

The script's real run needs a TPU (tests_tpu/test_operator_on_tpu.py calls
it there). Its ``--tiny-cpu`` argument runs the same control flow — probe
child, TPUJob through run_job cold then resumed, the checks, the last line
— with the tiny config on the CPU backend, so the flow and the output
contract are proven before chip time is spent: a previous bring-up passed
on the chip and was refused for extra keys on its last line.
"""

import json
import os
import shutil
import subprocess
import sys

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args=(), cwd=REPO, script=SCRIPT):
    # as the driver calls it: nothing on PYTHONPATH, the script's directory
    # is all it can import from
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600, env=env,
    )


def test_tiny_cpu_run_passes_and_ends_on_the_contract_line():
    proc = _run(["--tiny-cpu"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert proc.stdout.endswith("\n")
    *earlier, last = proc.stdout[:-1].split("\n")
    # nothing follows the result line, and everything before it is the
    # script's own labelled output (no worker or logger writes to stdout)
    assert earlier and all(ln.startswith("chip_smoke: ") for ln in earlier)
    result = json.loads(last)
    # exactly the contract's keys, from the constants the line is built from
    assert chip_smoke.RESULT_KEYS == ("ok", "device")
    assert chip_smoke.DEVICE_KEYS == ("platform", "kind", "count")
    assert tuple(result) == chip_smoke.RESULT_KEYS
    assert tuple(result["device"]) == chip_smoke.DEVICE_KEYS
    assert result == {"ok": True,
                      "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert last == chip_smoke.result_line(result["device"])
    # the second incarnation resumed where the first stopped, cache warm
    cold, warm = (
        json.loads(ln.split(": ", 2)[2]) for ln in earlier
        if ln.startswith(("chip_smoke: cold: {", "chip_smoke: warm: {"))
    )
    assert (cold["start_step"], cold["step"]) == (0, chip_smoke.COLD_STEPS)
    assert (warm["start_step"], warm["step"]) == (
        chip_smoke.COLD_STEPS, chip_smoke.WARM_STEPS)
    assert warm["compile_cache"]["hits"] > 0
    assert warm["compile_cache"]["misses"] == 0
    # counts only off the chip: no seconds, bytes or loss under any name
    assert not {"loss", "first_dispatch_s", "buckets"} & (set(cold) | set(warm))


def test_plain_run_off_the_chip_fails_and_prints_no_result():
    proc = _run()
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "backend 'cpu'" in proc.stderr  # names what it found


def test_alone_in_an_empty_directory_it_fails(tmp_path):
    """It drives the program; it does not stand in for it."""
    shutil.copy(SCRIPT, tmp_path)
    proc = _run(cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "mpi_operator_tpu" in proc.stderr


def test_result_line_carries_nothing_but_the_contract():
    with open(SCRIPT) as f:
        assert "claim" not in f.read()
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": 1})
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
