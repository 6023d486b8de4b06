#!/usr/bin/env python3
"""The benchmark's command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started
on. This process never imports jax: the service child owns the chip and
names it. Its last line on stdout is the result; no TPU, no result.
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

from harness import bench, manifest  # noqa: E402
from harness.service import BenchFailure  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not (ROOT / "src" / "esslivedata_tpu").is_dir():
        print("benchmark: the program is not beside the benchmark", file=sys.stderr)
        return 2
    try:
        cell = manifest.load_cell(ROOT, args.workload)
        line, report = bench.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), ROOT, STARTED
        )
    except (BenchFailure, manifest.ManifestError) as err:
        print(f"benchmark: no result: {err}", file=sys.stderr)
        return 1
    for text in report:
        print(text, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
