"""A step's device time by the names the program gives it, and the idle gaps
by the program's own spans. ``read_xplane`` reads the profiler's file with
nothing but Python; ``reduce_named`` is arithmetic on events in picoseconds
and is what the tests drive with hand-built events; the ``*_ms`` and
``setup_s`` functions are what the readers under ``metrics/`` call.

What a v5e trace holds (looked at by hand, PR 26): an event on a device
plane's ``XLA Ops`` line is named by its HLO text, which has no metadata in
it, and its own stats are its offsets only. The operation's ``op_name`` -
``jit(_bare_step)/model/transpose(jvp())/while/body/closed_call/checkpoint/
attention/flash_dq/pallas_call`` - is the ``tf_op`` stat of the event's
METADATA, which ``jax.profiler.ProfileData`` does not show; so the file is
read here as protobuf wire format (``XSpace`` of tsl's ``xplane.proto``: a
few nested messages of varints and strings). A Pallas kernel built with
``name=`` is also named by it as an instruction (``%flash_dq.10 = ...``).

The program's names (PERF.md section 3): ``Trainer._bare_step`` puts scope
``model`` around ``value_and_grad`` and ``optimizer`` around the update;
``models/llama.py`` puts ``embed``, ``attention``, ``mlp`` and ``head_loss``
inside. JAX writes the phase itself: the backward pass reads
``transpose(jvp(...))``, the replay under ``jax.checkpoint``
``rematted_computation``. XLA gives a fusion the ``op_name`` of one of the
operations it fused, so a fusion across two scopes is counted under one.

The trace's place is what the program itself says: ``StepProfiler`` acks
its capture in the stats blob (``profile.dir``), which the benchmark's
worker copies whole into its report. A program without that ack (one older
than PR 26), or a run without a trace, reads nothing here.
"""

import glob
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

PHASES = ("forward", "backward", "recompute", "optimizer")
SCOPES = ("embed", "attention", "mlp", "head_loss", "optimizer")
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
SPAN_PREFIX = "tpujob."
_SPLIT = re.compile(r"[/()]")
_STEM = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")


# -- the file ---------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(number, value) of each field of one protobuf message: an int for a
    varint or a fixed-width field, the bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf):
    """One XPlane: (name, [(line name, timestamp_ns, [event bytes])],
    {event metadata id: (name, tf_op)})."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            event_meta.update([_map_entry(v)])
        elif num == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(meta) if n == 2), "")
    metadata = {}
    for key, meta in event_meta.items():
        ev_name, op_name = "", ""
        for num, v in _fields(meta):
            if num == 2:
                ev_name = _text(v)
            elif num == 5:  # an XStat of the metadata
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == "tf_op":
                    # a string, or a reference to a stat's name
                    op_name = (_text(stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7), ""))
        metadata[key] = (ev_name, op_name)
    return name, lines, metadata


def _line(buf):
    name, timestamp_ns, events = "", 0, []
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            timestamp_ns = v
        elif num == 4:
            events.append(v)
    return name, timestamp_ns, events


def read_xplane(path):
    """{"devices": {plane: {"ops": [(text, start_ps, duration_ps, op_name)],
    "modules": [(name, start_ps, duration_ps)]}}, "host": [(name, start_ps,
    duration_ps)]}: the device's operations and program runs, and the
    program's ``tpujob.*`` spans on the host."""
    with open(path, "rb") as f:
        space = f.read()
    devices, host = {}, []
    for num, plane in _fields(space):
        if num != 1:
            continue
        plane_name, lines, metadata = _plane(plane)
        on_device = plane_name.startswith("/device:TPU:")
        if not on_device and not plane_name.startswith("/host:"):
            continue
        found = {"ops": [], "modules": []}
        for line in lines:
            line_name, timestamp_ns, events = _line(line)
            key = {trace_reduce.OPS_LINE: "ops",
                   trace_reduce.MODULES_LINE: "modules"}.get(line_name)
            if on_device and key is None:
                continue
            for event in events:
                ev = dict(_fields(event))
                name, op_name = metadata.get(ev.get(1), ("", ""))
                start = timestamp_ns * 1000 + ev.get(2, 0)
                if not on_device:
                    if name.startswith(SPAN_PREFIX):
                        host.append((name, start, ev.get(3, 0)))
                elif key == "ops":
                    found["ops"].append((name, start, ev.get(3, 0), op_name))
                else:
                    found["modules"].append((name, start, ev.get(3, 0)))
        if on_device and found["ops"]:
            devices[plane_name] = found
    return {"devices": devices, "host": host}


# -- the arithmetic ---------------------------------------------------------

def names_of(text, op_name):
    """The names an operation goes by: every part of its ``op_name`` (scopes,
    transforms, a kernel's name) and its instruction's stem."""
    names = set(_SPLIT.split(op_name.partition(":")[0]))
    stem = _STEM.match(text)
    if stem:
        names.add(stem.group(1))
    return names


def phase_of(op_name, names):
    """Replay before backward before optimizer before forward: the replay
    runs inside the backward pass, and both inside scope ``model``."""
    if "rematted_computation" in names:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "optimizer" in names:
        return "optimizer"
    if "model" in names:
        return "forward"
    return None


def reduce_named(devices, host):
    """Milliseconds a step a chip, over the whole runs of the step's program
    that the trace holds: ``step_ms``; ``phases_ms`` (the four phases and
    ``unscoped``, the step's device time that none of them claims: they add
    up to ``step_ms``); ``scopes_ms`` (a second cut, all phases);
    ``table_ms`` (scope/phase); ``kernels_ms``. And ``idle_gaps_s``: seconds
    a chip of device idle in the traced window, each gap put to the
    ``tpujob.*`` span that covers most of it. None where no operation ran,
    ``steps`` 0 and no milliseconds where the trace holds no whole step."""
    if not devices or not any(d["ops"] for d in devices.values()):
        return None
    start = min(s for d in devices.values() for _t, s, _d, _o in d["ops"])
    end = max(s + du for d in devices.values() for _t, s, du, _o in d["ops"])
    steps, step_ps = 0, 0
    phases = dict.fromkeys(PHASES, 0)
    scopes, table, kernels, gaps = {}, {}, {}, {}
    for lines in devices.values():
        # the step is the long program, and a run of it that the trace cut
        # is shorter than the whole ones (as trace_reduce.py has it)
        runs = [(s, s + d) for _n, s, d in lines["modules"]]
        longest = max((b - a for a, b in runs), default=0)
        whole = [(a, b) for a, b in runs if b - a >= 0.95 * longest > 0]
        steps += len(whole)
        step_ps += sum(b - a for a, b in whole)
        spans = []
        for text, s, d, op_name in lines["ops"]:
            if d > 0:
                spans.append((s, s + d))
            # a container's body is events of its own
            kind = trace_reduce.op_kind(text)
            if kind in trace_reduce.CONTAINERS:
                continue
            if not any(a <= s < b for a, b in whole):
                continue
            names = names_of(text, op_name)
            phase = phase_of(op_name, names)
            if phase:
                phases[phase] += d
            scope = next((x for x in SCOPES if x in names), "_none")
            scopes[scope] = scopes.get(scope, 0) + d
            cell = f"{scope}/{phase or 'unscoped'}"
            table[cell] = table.get(cell, 0) + d
            # the Mosaic call alone: XLA hands a kernel's `op_name` to what
            # it makes around it too (a `reduce` beside `flash_fwd`)
            if kind == "pallas":
                for kernel in KERNELS:
                    if kernel in names:
                        kernels[kernel] = kernels.get(kernel, 0) + d
        merged = trace_reduce._merge(spans)
        edges = [start] + [x for ab in merged for x in ab] + [end]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, best_cover = "host:_other", 0
            for name, s, d in host:
                cover = min(b, s + d) - max(a, s)
                if cover > best_cover:
                    best, best_cover = name, cover
            gaps[best] = gaps.get(best, 0) + (b - a)
    out = {"chips": len(devices), "steps": steps,
           "idle_gaps_s": {k: v * 1e-12 / len(devices)
                           for k, v in gaps.items()}}
    if steps:
        ms = lambda ps: ps * 1e-9 / steps
        out["step_ms"] = ms(step_ps)
        out["phases_ms"] = {k: ms(v) for k, v in phases.items()}
        out["phases_ms"]["unscoped"] = ms(step_ps - sum(phases.values()))
        out["scopes_ms"] = {k: ms(v) for k, v in scopes.items()}
        out["table_ms"] = {k: ms(v) for k, v in sorted(table.items())}
        out["kernels_ms"] = {k: ms(v) for k, v in kernels.items()}
    return out


def reduce_dir(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return None
    ev = read_xplane(files[-1])
    return reduce_named(ev["devices"], ev["host"])


# -- what the readers call ----------------------------------------------------

def of_record(record):
    """The reduction of the run's trace, made once a run and kept on the
    record; None without a trace or without the program's word on where it
    is."""
    if "named_trace" not in record:
        stats = record["report"].get("stepstats") or {}
        trace_dir = (stats.get("profile") or {}).get("dir")
        record["named_trace"] = (
            reduce_dir(trace_dir) if record.get("trace") and trace_dir
            else None)
    return record["named_trace"]


def _ms(record, group, name):
    named = of_record(record)
    if not named or not named["steps"]:
        return None
    return named[group].get(name)


def kernel_ms(record, name):
    return _ms(record, "kernels_ms", name)


def phase_ms(record, name):
    return _ms(record, "phases_ms", name)


def scope_ms(record, name):
    return _ms(record, "scopes_ms", name)


def setup_spans(record):
    """The program's set-up seconds by span, from its stats blob."""
    stats = record["report"].get("stepstats") or {}
    return stats.get("setup") or None


def setup_s(record, *spans):
    """Seconds of the named set-up spans (one that did not run counts 0);
    None where the program reports no set-up."""
    setup = setup_spans(record)
    if not setup:
        return None
    return sum(setup.get(s, 0.0) for s in spans)


if __name__ == "__main__":
    import json

    print(json.dumps(reduce_dir(sys.argv[1]), indent=1))
