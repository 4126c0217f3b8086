"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics with the profiler
off; ``--trace 1`` traces the window and reports its per-layer metrics,
with the device's busy and window seconds and a breakdown.  Both compare
the sampled outputs of the window with the plain reference afterwards
and print each number compared beside its limit, last on standard error
and last in the result line.  The last line of standard output is the
result as one JSON object.  Without a TPU, or with fewer chips than the
cell asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    harness.log(f"set-up: JAX imported at "
                f"{time.perf_counter() - T_START:.3f} s")
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    d = result["device"]
    print(f"[bench] {args.workload} seed {args.seed} on {d['count']} x "
          f"{d['kind']} ({d['platform']}): correct {result['correct']}, "
          f"{result['attempted']} decisions attempted, "
          f"{result['failed']} failed", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
