"""Set a cell's limits from chip readings, by the builder's rule: for each
number the lower reading is the largest that sound runs gave, the upper the
smallest among the control's (where that is three times the lower or more)
and each planted fault's (ten times or more; a state left unchanged reads 1
on the change and needs no run: three times). A number with no upper
reading is not compared. The limit sits between the two, nearer the lower
in the logarithm but with the more room above it.

    python3 benchmark/set_limits.py --cell <name> --sound a.err b.err ... \\
        --control c.err ... --fault half_batch h.err ... > limits/<name>.json

Each file is the standard error of one run of run.py.
"""

import argparse
import json
import sys

ROOM = 0.6  # the limit is lower * (upper / lower) ** ROOM


def numbers_of(path):
    with open(path) as f:
        for line in f:
            if line.startswith("benchmark: {"):
                return json.loads(line[len("benchmark: "):])["numbers"]
    raise SystemExit(f"{path}: no readings")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--origin", required=True,
                    help='where the readings come from: "my chip runs, PR n"')
    ap.add_argument("--sound", nargs="+", required=True)
    ap.add_argument("--control", nargs="+", required=True)
    ap.add_argument("--fault", nargs="+", action="append", default=[],
                    metavar="NAME FILE", help="a fault's name, then its runs")
    args = ap.parse_args(argv)
    sound = [numbers_of(p) for p in args.sound]
    control = [numbers_of(p) for p in args.control]
    faults = {f[0]: [numbers_of(p) for p in f[1:]] for f in args.fault}
    limits, readings, not_compared = {}, {}, {}
    every = sound + control + [r for runs in faults.values() for r in runs]
    # a number gets a limit only where every run read it
    for name in sorted(set.intersection(*(set(run) for run in every))):
        lower = max(run[name] for run in sound)
        r = {"lower": lower, "sound_runs": len(sound),
             "control_min": min(run[name] for run in control)}
        uppers = {}
        if r["control_min"] >= 3 * lower:
            uppers["control"] = r["control_min"]
        for fault, runs in faults.items():
            r[fault + "_min"] = min(run[name] for run in runs)
            if r[fault + "_min"] >= 10 * lower:
                uppers[fault] = r[fault + "_min"]
        if name == "delta_gap" and 1.0 >= 3 * lower:
            uppers["state_unchanged"] = 1.0
        if not uppers:
            not_compared[name] = r
            continue
        r["upper_from"] = min(uppers, key=uppers.get)
        r["upper"] = uppers[r["upper_from"]]
        limits[name] = float(f"{lower * (r['upper'] / lower) ** ROOM:.3g}")
        readings[name] = r
    failed_by = lambda run: [n for n, lim in limits.items() if run[n] > lim]
    for kind, runs in [("control", control), *faults.items()]:
        for i, run in enumerate(runs):
            if not failed_by(run):
                raise SystemExit(f"{kind} run {i} fails no limit: no limit "
                                 "will hold; compare another number")
    for i, run in enumerate(sound):
        if failed_by(run):
            raise SystemExit(f"sound run {i} fails {failed_by(run)}")
    json.dump({"cell": args.cell, "origin": args.origin, "limits": limits,
               "readings": readings, "not_compared": not_compared},
              sys.stdout, indent=1)
    print()


if __name__ == "__main__":
    main()
