"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` in this process, on the machine
it is started on. The cell's configuration, traffic mix and per-cell
numbers are data files found by name; the runner named in the mix drives
the program. The last line of stdout is the result.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    harness.setup_compile_cache()
    devices = harness.require_devices(cell.chips)
    runner = importlib.import_module(f"benchmark.runners.{cell.runner}")
    outcome = runner.run(cell, devices, T0)
    return harness.emit(cell, outcome, devices)


if __name__ == "__main__":
    sys.exit(main())
