"""Device milliseconds a step under any name the program gives its
operations: a scope, a transform, a kernel. The helper of the readers
beside it whose names ``named_trace.SCOPES`` and ``KERNELS`` (constants of
a file that is there) do not list. Built on ``named_trace.read_xplane``,
``names_of`` and ``phase_of``; the same whole steps, the same rule for
containers. A name counts once an operation, all phases together (forward,
backward, replay); ``by_phase`` splits it.

No reader here raises where there is nothing to read: a run without a
trace, a program without the scope (one older than this name) or without
the stats blob's word on where the trace is reads None."""

import glob
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:  # run by hand, as named_trace.py can be
    sys.path.insert(0, BENCH)

import named_trace  # noqa: E402
import trace_reduce  # noqa: E402

# what the TPU compiler calls the Mosaic kernel it makes of a
# ``lax.ragged_dot`` (instruction and ``op_name`` alike, with no scope of
# the program's: looked at by hand, PR 28)
GROUPED_PRODUCT = "ragged-dot-none"


def _trace_file(record):
    stats = record["report"].get("stepstats") or {}
    trace_dir = (stats.get("profile") or {}).get("dir")
    if not record.get("trace") or not trace_dir:
        return None
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def reduce_by_name(devices):
    """{"steps": n, "ms": {name: {phase: ms a step a chip}}} over the whole
    runs of the step's program on every device plane; None where there is
    no whole step."""
    steps, totals = 0, {}
    for lines in devices.values():
        runs = [(s, s + d) for _n, s, d in lines["modules"]]
        longest = max((b - a for a, b in runs), default=0)
        whole = [(a, b) for a, b in runs if b - a >= 0.95 * longest > 0]
        steps += len(whole)
        for text, s, d, op_name in lines["ops"]:
            if trace_reduce.op_kind(text) in trace_reduce.CONTAINERS:
                continue
            if not any(a <= s < b for a, b in whole):
                continue
            names = named_trace.names_of(text, op_name)
            phase = named_trace.phase_of(op_name, names) or "unscoped"
            for name in names:
                by_phase = totals.setdefault(name, {})
                by_phase[phase] = by_phase.get(phase, 0) + d
    if not steps:
        return None
    return {"steps": steps,
            "ms": {name: {ph: ps * 1e-9 / steps for ph, ps in by.items()}
                   for name, by in totals.items()}}


def of_record(record):
    """The reduction, made once a run and kept on the record."""
    if "op_names" not in record:
        path = _trace_file(record)
        record["op_names"] = (
            reduce_by_name(named_trace.read_xplane(path)["devices"])
            if path else None)
    return record["op_names"]


def by_phase(record, name):
    """{phase: ms a step} under ``name``; None where nothing ran under it."""
    reduced = of_record(record)
    if not reduced:
        return None
    return reduced["ms"].get(name) or None


def ms(record, *names):
    """Milliseconds a step under the names, added up (they must not nest);
    None where nothing ran under any of them."""
    found = [by_phase(record, n) for n in names]
    if not any(found):
        return None
    return sum(sum(f.values()) for f in found if f)


def counters(record):
    """The newest finished step's named scalars from the program's stats
    blob (``counters``); None where the program reports none."""
    stats = record["report"].get("stepstats") or {}
    return stats.get("counters") or None


if __name__ == "__main__":
    import json

    files = sorted(glob.glob(
        os.path.join(sys.argv[1], "**", "*.xplane.pb"), recursive=True))
    reduced = reduce_by_name(named_trace.read_xplane(files[-1])["devices"])
    wanted = sys.argv[2:] or sorted(reduced["ms"])
    json.dump({n: reduced["ms"].get(n) for n in wanted}, sys.stdout, indent=1)
    print()
