"""Read the numbers that ``correct`` compares, for setting their limits.

    python bench/read_limits.py --workload <name> --seeds 1,2,...
        [--control-seeds 7,8,9] [--seconds 2]

For each seed of ``--seeds`` the program runs a short window at the
cell's own sizes and load, and the sampled calls are compared with the
plain reference, as a benchmark run does.  For each of
``--control-seeds`` the bfloat16 reference stands in for the program.
Prints one JSON line per run, then, per number, the largest reading of
the program (the lower reading of a limit) and the smallest of the
control (its upper reading).  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.find_cell(args.workload)
    harness.require_chip(cell.chips)
    harness.enable_compile_cache()
    got = {"program": [], "control": []}
    for side, seeds, cls in (("program", args.seeds, harness.Program),
                             ("control", args.control_seeds,
                              harness.Control)):
        for seed in seeds:
            st, system, decide = harness.prepare(cell, seed,
                                                 system_cls=cls)
            w = harness.window(cell, st, system, decide, args.seconds, seed,
                               st["traffic"]["compared_calls"])
            del system
            numbers = harness.compare(cell, st, w["kept"], decide)
            got[side].append(numbers)
            print(json.dumps({"side": side, "seed": seed,
                              "calls": w["done"], "failed": w["failed"],
                              **numbers}), flush=True)
    for name in (got["program"] or got["control"])[0]:
        lower = max((n[name] for n in got["program"]), default=None)
        upper = min((n[name] for n in got["control"]), default=None)
        print(json.dumps({"number": name, "lower": lower, "upper": upper,
                          "limit": cell.config["limits"].get(name)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
