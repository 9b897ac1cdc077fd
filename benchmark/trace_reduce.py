"""From a ``jax.profiler`` trace to numbers. ``events_from_xplane`` reads
the profiler's file with nothing but JAX; ``reduce_events`` is arithmetic on
(name, start, duration) triples in nanoseconds and is what the tests drive
with hand-built events.

What a v5e trace holds (looked at by hand, PR 25): device planes are
``/device:TPU:<n>``. A plane's ``XLA Ops`` line has one event for each
operation that ran on the core, named by its whole HLO text
(``%fusion.245 = (bf16[8,2047]...) fusion(...), kind=kOutput, ...``); a
``while`` holds its body's operations as further events inside its own
span. ``XLA Modules`` has one event for each run of a compiled program: the
step, cut short where the trace began or ended inside it. The Pallas flash
kernels are ``custom-call``s whose text says
``custom_call_target="tpu_custom_call"``; they carry no name of their own
(``%closed_call.16``, ``%checkpoint.20``), so they are read as one. Host
spans are the benchmark's own ``TraceAnnotation``s on the host plane's
``python3`` line.

The traced window is taken from the first device operation's start to the
last one's end over all chips: the worker keeps a step queued behind the one
that runs, so the profiler starts and stops while the device is at work.
"""

import glob
import os
import re

COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all"}
CONTAINERS = {"while", "conditional", "call"}  # their bodies are events too
HOST_SPANS = ("bench.input", "bench.dispatch", "bench.wait")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_KIND = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def op_kind(text):
    """The operation of an event's HLO text: ``fusion``, ``all-gather``
    (for its ``-start`` and ``-done`` too), ``pallas`` for a Mosaic custom
    call. A bare name (``fusion.12``) gives its stem."""
    name, eq, rest = text.partition(" = ")
    if eq:
        m = _KIND.search(" " + rest)
        kind = m.group(1) if m else "?"
        if kind == "custom-call" and 'target="tpu_custom_call"' in rest:
            return "pallas"
    else:
        kind = re.match(r"%?([a-z\-]*[a-z])", name)
        kind = kind.group(1) if kind else "?"
    for suffix in ("-start", "-done"):
        if kind.endswith(suffix) and kind[:-len(suffix)] in COLLECTIVES:
            return kind[:-len(suffix)]
    return kind


def short_name(text):
    """``fusion.245 (bf16[8,2047],bf16[8,2047,32768])``: name and result."""
    name, eq, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not eq:
        return name
    m = _KIND.search(" " + rest)
    shape = _LAYOUT.sub("", rest[:m.start()] if m else "").replace(" ", "")
    return f"{name} {shape}"[:96]


def events_from_xplane(path):
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}
    with each event a (name, start_ns, duration_ns) triple."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    lines[key] += [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
            if lines["ops"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name in HOST_SPANS]
    return {"devices": devices, "host": host}


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(merged):
    return sum(b - a for a, b in merged)


def _minus(merged, other):
    """Length of ``merged`` not covered by ``other`` (both merged)."""
    covered = 0
    for a, b in merged:
        for c, d in other:
            covered += max(0, min(b, d) - max(a, c))
    return _length(merged) - covered


def _spans(events):
    return [(s, s + d) for _n, s, d in events if d > 0]


def reduce_events(devices, host):
    """The trace's numbers, in seconds, averaged over the chips; None where
    no operation ran on a device. ``step_s`` and ``flash_step_s`` are per
    whole step: over the runs of the step's program that the trace holds
    from start to end."""
    if not devices or not any(d["ops"] for d in devices.values()):
        return None
    start = min(s for d in devices.values() for _n, s, _d in d["ops"])
    end = max(s + du for d in devices.values() for _n, s, du in d["ops"])
    n = len(devices)
    busy = exposed = collective = flash_in_steps = step_s = 0.0
    steps, by_op, gaps = 0, {}, {}
    for lines in devices.values():
        ops = [(op_kind(name), name, s, d) for name, s, d in lines["ops"]]
        merged = _merge((s, s + d) for _k, _n, s, d in ops if d > 0)
        busy += _length(merged)
        coll = _merge((s, s + d) for k, _n, s, d in ops if k in COLLECTIVES)
        rest = _merge((s, s + d) for k, _n, s, d in ops
                      if k not in COLLECTIVES and k not in CONTAINERS)
        collective += _length(coll)
        exposed += _minus(coll, rest)
        for kind, name, _s, d in ops:
            if kind not in CONTAINERS:
                key = short_name(name)
                by_op[key] = by_op.get(key, 0.0) + d
        # the step is the long program (the feed's copies are short), and
        # a run of it that the trace cut is shorter than the whole ones
        runs = [(s, s + d) for _n, s, d in lines["modules"]]
        longest = max((b - a for a, b in runs), default=0)
        whole = [(a, b) for a, b in runs if b - a >= 0.95 * longest > 0]
        steps += len(whole)
        step_s += sum(b - a for a, b in whole)
        flash_in_steps += sum(
            d for k, _n, s, d in ops if k == "pallas"
            and any(a <= s < b for a, b in whole))
        # each idle gap goes to the host span that covers most of it
        edges = [start] + [x for ab in merged for x in ab] + [end]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, best_cover = "host:_other", 0
            for name, s, d in host:
                cover = min(b, s + d) - max(a, s)
                if cover > best_cover:
                    best, best_cover = name, cover
            gaps[best] = gaps.get(best, 0.0) + (b - a)
    per_chip = 1e-9 / n
    top = lambda d: [[k, v * per_chip] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "chips": n, "window_s": (end - start) * 1e-9,
        "busy_s": busy * per_chip, "collective_s": collective * per_chip,
        "collective_exposed_s": exposed * per_chip,
        "whole_steps": steps / n,
        "step_s": step_s * 1e-9 / steps if steps else None,
        "flash_step_s": flash_in_steps * 1e-9 / steps if steps else None,
        "device_ops": top(by_op), "idle_gaps": top(gaps),
    }


def reduce_dir(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return None
    ev = events_from_xplane(files[-1])
    return reduce_events(ev["devices"], ev["host"])
