#!/usr/bin/env python3
"""The control of a cell's comparison, on the machine it is started on:

    python benchmark/control.py --workload <cell> --seed <n> --seconds <s>

One sound run of the cell, and then the reference with one guarantee
broken put in the program's place, once for each fault of
``reference.break_guarantee`` (where the cell has a detector stream)
and then, for the jobs of a view kind that ``references/<kind>.py``
answers, once for each of that kind's own ``faults()``
(``<kind>.<name>``): the same publishes, judged against what the broken
reference says. Prints the sound run's numbers and each
control's, and exits 0 only where the sound run is correct and every
control is not. The benchmark's own runs do not run this; PERF.md's
limits were set from its readings.
"""

from __future__ import annotations

import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import bench, manifest, reference  # noqa: E402
from harness.service import BenchFailure  # noqa: E402


def controls_of(cell: manifest.Cell) -> tuple[str, ...]:
    """Every fault a cell's comparison has to catch."""
    return reference.controls(cell.config, cell.kinds)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    try:
        cell = manifest.load_cell(ROOT, args.workload)
        line, _report = bench.run_cell(
            cell, args.seed, args.seconds, False, ROOT, STARTED, controls=controls_of(cell),
        )
    except (BenchFailure, manifest.ManifestError) as err:
        print(f"control: no result: {err}", file=sys.stderr)
        return 1
    sound = {k: e["value"] for k, e in line["checks"].items() if isinstance(e, dict) and "limit" in e}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": line["device"],
        "sound": {"correct": line["correct"], "failed": line["failed"], **sound},
        "controls": line["controls"],
        "compared": line["checks"]["compared"],
    }), flush=True)
    caught = all(not reading["correct"] for reading in line["controls"].values())
    return 0 if line["correct"] and caught else 1


if __name__ == "__main__":
    raise SystemExit(main())
