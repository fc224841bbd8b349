#!/usr/bin/env python3
"""Read the numbers a cell's check compares, for many seeds and variants in
one process, on the chip: the readings that its limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --variants program,control [--seconds 1]

For each variant and seed it runs the cell as ``bench/run.py`` does, with a
short window, and prints one JSON line: the variant, the seed and each
compared number.  ``program`` is the system under test; the other variants
are the drivers' control and planted faults (see each driver's docstring).
The benchmark's own runs never run them.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--variants", default="program")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for variant in args.variants.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            try:
                result = run.execute(args.workload, seed, args.seconds, False,
                                     variant=variant)
            except run.NoChip as e:
                print(f"bench/calibrate.py: {e}", file=sys.stderr)
                return 2
            print(json.dumps({
                "variant": variant, "seed": seed,
                "correct": result["correct"],
                "checks": {k: c["value"]
                           for k, c in result["checks"].items()},
                "step_ms": result["metrics"]["step_ms"]["value"]}),
                flush=True)
            run.jax.clear_caches()     # each run's program holds its graph
    return 0


if __name__ == "__main__":
    sys.exit(main())
