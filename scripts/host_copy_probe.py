#!/usr/bin/env python3
"""What a host copy of a staged wire costs on this machine, by how its
destination was come by (numpy only; nothing of the package, no device).

The staging copy in front of ``device_put`` (``ops/event_batch.py``)
used to be ``x.copy()``: a fresh array per call. Past glibc's ceiling
for its dynamic mmap threshold (32 MiB) every such array is a new
mapping whose pages are faulted in while it is filled, and under it five
threads allocating at once fall off the same cliff. This probe reads the
cliff on whatever host it is run on (PR 35 read it on the chip's host:
``x.copy()`` of 16 / 32 / 64 MiB 0.82 / 38.14 / 79.05 ms, ``np.copyto``
into a kept buffer 0.86 / 2.46 / 3.37 ms; ADR 0130 is what followed):

- ``fresh``: ``src.copy()``, the copy dropped before the next one;
- ``fresh_two_alive``: the same with the previous copy still referenced
  (what a transfer in flight does to the allocator);
- ``reused_buffer``: ``np.copyto`` into one buffer that is kept;
- eighteen 16 MiB copies on five threads (LOKI's nine raw wires on the
  job pool), fresh against kept.

    python3 scripts/host_copy_probe.py [--out FILE] [--repeats 25]

prints the medians and writes the JSON (every timing in ms).
"""

from __future__ import annotations

import argparse
import json
import statistics
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MIB = 1 << 20
SIZES_MIB = (16, 32, 64)
POOL_COPIES, POOL_THREADS, POOL_MIB = 18, 5, 16


def timed_ms(action) -> float:
    start = time.perf_counter()
    action()
    return (time.perf_counter() - start) * 1e3


def summary(times_ms: list[float], nbytes: int | None = None) -> dict:
    out = {
        "median_ms": statistics.median(times_ms),
        "min_ms": min(times_ms),
        "max_ms": max(times_ms),
    }
    if nbytes is not None:
        out["GBps_median"] = nbytes / out["median_ms"] / 1e6
    return out


def source(mib: int) -> np.ndarray:
    return np.arange(mib * MIB // 4, dtype=np.int32)


def one_thread(mib: int, repeats: int) -> dict:
    src = source(mib)
    fresh = [timed_ms(src.copy) for _ in range(repeats)]

    alive: list[np.ndarray] = []

    def copy_beside_the_last() -> None:
        alive.append(src.copy())
        del alive[:-1]

    two_alive = [timed_ms(copy_beside_the_last) for _ in range(repeats)]
    kept = np.empty_like(src)
    np.copyto(kept, src)  # touch its pages once, as a warm-up window does
    reused = [timed_ms(lambda: np.copyto(kept, src)) for _ in range(repeats)]
    return {
        "fresh": summary(fresh, src.nbytes),
        "fresh_two_alive": summary(two_alive, src.nbytes),
        "reused_buffer": summary(reused, src.nbytes),
    }


def five_threads(repeats: int) -> dict:
    sources = [source(POOL_MIB) for _ in range(POOL_COPIES)]
    kept = [np.empty_like(src) for src in sources]
    for dst, src in zip(kept, sources, strict=True):
        np.copyto(dst, src)
    out = {}
    with ThreadPoolExecutor(POOL_THREADS) as pool:
        for name, copy_all in (
            ("fresh", lambda: list(pool.map(np.copy, sources))),
            ("reused_buffers", lambda: list(pool.map(np.copyto, kept, sources))),
        ):
            times = [timed_ms(copy_all) for _ in range(repeats)]
            key = f"{POOL_COPIES}x{POOL_MIB}MiB_on_{POOL_THREADS}_threads_{name}"
            out[key] = {**summary(times), "all_ms": [round(t, 1) for t in times]}
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", help="write the JSON here as well")
    parser.add_argument("--repeats", type=int, default=25)
    args = parser.parse_args()
    result = {f"{mib}MiB": one_thread(mib, args.repeats) for mib in SIZES_MIB}
    result.update(five_threads(args.repeats))
    for name, entry in result.items():
        medians = (
            {k: round(v["median_ms"], 2) for k, v in entry.items()}
            if "median_ms" not in entry
            else round(entry["median_ms"], 2)
        )
        print(f"{name}: median ms {medians}")
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
