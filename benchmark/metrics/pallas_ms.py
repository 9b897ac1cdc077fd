"""Device milliseconds a step of Pallas kernels by the names they are built
with: the Mosaic call alone, not what XLA makes around it under the same
``op_name`` (a scope and a kernel may share a name: the model's scope
``sparse_select`` holds the kernel ``sparse_select`` and the arithmetic
around it). The helper of the readers beside it; ``named_trace.KERNELS``
(a constant of a file that is there) lists the three causal flash kernels
alone. The same whole steps, the same names, as ``op_names``; all phases
together. A run without a trace, or a program without the kernel, reads
None."""

import named_trace
import trace_reduce
from metrics import op_names


def reduce_kernels(devices):
    """{kernel name: ms a step a chip} over the whole runs of the step's
    program; None where there is no whole step."""
    steps, totals = 0, {}
    for lines in devices.values():
        runs = [(s, s + d) for _n, s, d in lines["modules"]]
        longest = max((b - a for a, b in runs), default=0)
        whole = [(a, b) for a, b in runs if b - a >= 0.95 * longest > 0]
        steps += len(whole)
        for text, s, d, op_name in lines["ops"]:
            if trace_reduce.op_kind(text) != "pallas":
                continue
            if not any(a <= s < b for a, b in whole):
                continue
            for name in named_trace.names_of(text, op_name):
                totals[name] = totals.get(name, 0) + d
    if not steps:
        return None
    return {name: ps * 1e-9 / steps for name, ps in totals.items()}


def kernel_ms(record, *names):
    """Milliseconds a step of the kernels of these names, added up; None
    where none of them ran."""
    if "pallas_ms" not in record:
        path = op_names._trace_file(record)
        record["pallas_ms"] = (
            reduce_kernels(named_trace.read_xplane(path)["devices"])
            if path else None)
    found = [(record["pallas_ms"] or {}).get(n) for n in names]
    if not any(found):
        return None
    return sum(f for f in found if f)


def roofline_share(record, work, *names):
    """100 x the least time the chip needs for ``work`` (a counts function
    of (conf, rows, seq_len) -> (flops, bytes), at the bf16 peak or the
    HBM peak, whichever is longer) over the kernels' traced time; None
    where there is nothing to read."""
    spent = kernel_ms(record, *names)
    if not spent or work is None or not record["peaks"]:
        return None
    tr = record["traffic"]
    flops, nbytes = work(record["conf"], tr["rows_per_chip"], tr["seq_len"])
    least = max(flops / record["peaks"]["bf16_flops_per_s"],
                nbytes / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (spent * 1e-3)
