"""What the package's camera view should publish for one ``camera``
stream of ad00 frames: ``current``, the sum of the frames of the pulses
since the previous publish, and ``cumulative``, the sum of all the
prefix's frames, pixel by pixel. Nothing of the program is imported:
the frames are the harness's own pool (``traffic.FramePool``), summed
here in float64.

**The view's transform.** The job's ``params`` show the summed image
transposed (``transpose``), then flipped in y (``flip_y``), then in x
(``flip_x``), each where the parameter says so.

**Prefixes.** Every pool entry's frames have a distinct total, so a
publish's pulse prefix is found from the sum of its ``cumulative``
(``prefix_total: "cumulative"``; ``PoolReference.prefix_of``).

**Comparison.** Counts: exact where the sum is an integer below 2**24,
``results.bins_off`` above that; the misses count into
``frame_bins_wrong``.

**Faults** (for ``control.py``): each breaks one frame, the first that
the pool's first pulse sends, as a program that loses or mangles a frame
on its way would: dropped, counted twice, transposed, truncated to uint8.
"""

from __future__ import annotations

import numpy as np
from harness.reference import PoolReference
from harness.traffic import FramePool

CHECK = "frame_bins_wrong"


def shown(image: np.ndarray, params: dict) -> np.ndarray:
    """A camera view's image as the job's parameters show it."""
    if params.get("transpose"):
        image = image.T
    if params.get("flip_y"):
        image = image[::-1, :]
    if params.get("flip_x"):
        image = image[:, ::-1]
    return image


class FramesReference(PoolReference):
    """``entry_sums[e]`` is the sum of pool entry ``e``'s frames; a
    pulse's total is what ``prefix_of`` finds a publish by."""

    def __init__(self, entry_sums: np.ndarray, params: dict) -> None:
        super().__init__(entry_sums.sum(axis=(1, 2)))
        self._sums = entry_sums
        self._params = params

    @staticmethod
    def span(output: str, previous: int, prefix: int) -> tuple[int, int]:
        if output == "current":
            return previous, prefix
        if output == "cumulative":
            return 0, prefix
        raise KeyError(f"a camera view has no output {output!r}")

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        self.span(output, lo, hi)
        return shown(np.tensordot(self.multiplicity(lo, hi), self._sums, axes=1), self._params)


def build(job, config, traffic, pools, alter=None) -> FramesReference:
    """``alter(frame) -> [frames]`` stands in for the first frame of the
    pool's first pulse (a fault); None leaves it as it is."""
    index = {s["name"]: i for i, s in enumerate(config["streams"])}
    pool, _ = pools[index[job["stream"]]]
    if not isinstance(pool, FramePool):
        raise ValueError(f"job {job['name']}: stream {job['stream']!r} sends no frames")
    sums = np.zeros((len(pool), *pool.frames.shape[1:]), np.float64)
    for entry, frames in enumerate(pool.entries):
        for k in frames:
            sums[entry] += pool.frames[k]
    if alter is not None:
        first = pool.frames[pool.entries[0][0]].astype(np.float64)
        sums[0] += sum(alter(first), np.zeros_like(first)) - first
    return FramesReference(sums, job.get("params", {}))


def tolerance(output: str) -> None:
    return None  # counts: exact below 2**24, ``results.bins_off`` above


def check(output: str) -> str:
    return CHECK


def work_bytes(job: dict, config: dict, frames: float, publishes: int) -> int:
    """Per frame: the frame's bytes in, and one read and one write of a
    4 B bin per pixel for each of the two states (window and cumulative):
    16 B a pixel. Per publish, as ``roofline.job_bytes`` reckons a
    detector view's: the fold's four passes over the pixels' 4 B bins and
    the fetch of the two images (4 B a pixel each)."""
    stream = next(s for s in config["streams"] if s["name"] == job["stream"])
    pixels = int(np.prod(stream["frame_shape"]))
    frame_bytes = pixels * np.dtype(stream["dtype"]).itemsize
    return int(frames * (frame_bytes + 16 * pixels) + publishes * (4 * 4 * pixels + 2 * 4 * pixels))


def faults() -> dict:
    def broken(alter):
        return lambda job, config, traffic, pools: build(job, config, traffic, pools, alter)

    return {
        "frame_dropped": broken(lambda frame: []),
        "frame_twice": broken(lambda frame: [frame, frame]),
        "frame_transposed": broken(lambda frame: [frame.T.reshape(frame.shape)]),
        "frame_uint8": broken(lambda frame: [frame % 256]),
    }
