#!/usr/bin/env python3
"""Read the numbers that decide `correct`, for setting their limits.

  python3 bench/calibrate.py --workload <name> --seeds <n> --control-seeds <k>
                             --seconds <s> --out <file.json>

In one process, so the chips are held once: `--seeds` runs of the program
on fresh seeds (the lower readings) and `--control-seeds` runs with the
cell's control in the program's place (`bench/control.py`: the upper
readings), each a whole run of `bench/run.py` with a short window at the
cell's own load. Writes every run's compared numbers to `--out` and prints
the largest program reading and the smallest control reading of each.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench import control, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seed-base", type=int, default=3_100_000_000)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    out = {"workload": args.workload, "program": [], "control": []}
    plan = [("program", None, i) for i in range(args.seeds)] + \
        [("control", control.install, args.seeds + i)
         for i in range(args.control_seeds)]
    for side, patch, i in plan:
        seed = args.seed_base + 7919 * i
        r = run.run(["--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds)], patch=patch)
        out[side].append({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"], "metrics": r["metrics"]})
        print(side, seed, json.dumps(r["checks"]), flush=True)
    runs = out["program"] + out["control"]
    for name in runs[0]["checks"] if runs else ():
        lo = max((p["checks"][name]["value"] for p in out["program"]),
                 default=None)
        hi = min((c["checks"][name]["value"] for c in out["control"]),
                 default=None)
        out.setdefault("summary", {})[name] = {"lower": lo, "upper": hi}
        print(f"{name}: lower reading {lo!r}, upper reading {hi!r}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
