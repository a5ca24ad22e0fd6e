"""Reads, on the chip, the numbers a cell's limits are set from.

    python3 benchmark/limits.py --workload <name> --seeds 12 --control-seeds 3 [--first-seed N]

One process: per seed the program's numbers against the plain reference
(their largest is the lower reading), and for the first seeds the
control's and each fault's (their smallest is the upper reading). One JSON
line each; the limits then go into ``benchmark/cells/<name>.json`` by hand
(``PERF.md`` section 2 gives the readings). Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_300_000_011)
    parser.add_argument("--seconds", type=float, default=12.0,
                        help="window per seed, for runners that need one")
    args = parser.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload, args.first_seed, args.seconds, False)
    harness.setup_compile_cache()
    devices = harness.require_devices(cell.chips)
    runner = importlib.import_module(f"benchmark.runners.{cell.runner}")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    for row in runner.limit_readings(cell, devices, seeds, args.control_seeds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
