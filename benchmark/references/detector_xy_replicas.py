"""What the package's detector view should publish for one straw-tube
bank of LOKI projected onto its xy plane with position-noise replicas:
the 2-D image and the TOA spectrum, from the formulae and from the sizes
the job's ``view`` states. Nothing of the program is imported, no table
it made is read, and the pixel positions, the screen and the jittered
tables are built here, so that a wrong id base, bank order, panel,
noise draw or weight shows.

**Positions.** A bank is a flat panel of ``layers`` x ``tubes`` x
``straws`` x ``pixels_per_straw`` pixels, its ids counting from
``first_id`` in that (C) order. With ``l, t, s, p`` the pixel's layer,
tube, straw and place along its straw:

    u = (p - (pixels_per_straw - 1) / 2) pixel_pitch           along a straw
    v = (t - (tubes - 1) / 2) tube_pitch + (l mod 2) tube_pitch / 2
        + r cos(phi)                                           across the tubes
    w = (l - (layers - 1) / 2) layer_pitch + r sin(phi)        from layer to layer
    position = centre + u along + v across + w normal

where straw 0 lies on its tube's axis (``r`` = 0) and the others ring it
(``r = straw_radius``, ``phi = 2 pi (s - 1) / (straws - 1)``).

**The screen.** The bank is seen along z: a pixel's screen point is its
(x, y). The screen is ``resolution`` = (ny, nx) equal bins over the
bounds of the *unjittered* pixels, widened by half a bin on every side:

    x_edges = linspace(min x - (max x - min x) / (2 nx), max x + ..., nx + 1)

and likewise in y; a point in [edge k, edge k + 1) is in bin k, and the
flat screen bin is ``y bin * nx + x bin``.

**Replicas.** Every pixel has ``replicas`` = R screen bins. They are
drawn once, from ``numpy.random.default_rng(seed)``, replica after
replica: a normal of sigma ``noise_sigma_m`` for every coordinate of
every pixel (``[n, 3]`` a replica, in the order of the ids), added to
the position before it is projected. A replica whose point falls off
the screen is dropped, not moved onto it.

**Counts.** An event of pixel id ``i`` at time of arrival ``t`` counts
iff ``i`` is one of the bank's ids and ``t`` lies in [0, pulse period).
It then adds exactly 1/R to the image at each on-screen replica bin of
its pixel and 1/R, once per on-screen replica, to the spectrum at its
TOA bin (``toa_bins`` equal bins over the period). ``counts_cumulative``
is the sum of either, a whole multiple of 1/R.
"""

from __future__ import annotations

import numpy as np
from harness.reference import PoolReference, suffix_span
from harness.traffic import pulse_period_ns

#: The accepted cells' own checks, by the output's class.
CHECKS = {
    "spectrum_current": "spectrum_bins_wrong",
    "spectrum_cumulative": "spectrum_bins_wrong",
    "image_current": "image_bins_wrong",
    "image_cumulative": "image_bins_wrong",
}
#: float32 holds every whole multiple of 1/4 below this.
EXACT_BELOW = 2**22


def as_bfloat16(values: np.ndarray) -> np.ndarray:
    """float64 -> the nearest bfloat16 (round to even), as float64:
    what a bin kept in the precision below float32 would read."""
    bits = np.asarray(values, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def bank_positions(bank: dict) -> np.ndarray:
    """[n, 3] pixel centres in m, in the order of the ids."""
    layers, tubes, straws, pixels = (
        bank["layers"], bank["tubes"], bank["straws"], bank["pixels_per_straw"]
    )
    index = np.arange(layers * tubes * straws * pixels)
    p = (index % pixels).astype(np.float64)
    s = (index // pixels % straws).astype(np.float64)
    t = (index // (pixels * straws) % tubes).astype(np.float64)
    layer = (index // (pixels * straws * tubes)).astype(np.float64)
    phi = 2.0 * np.pi * (s - 1.0) / (straws - 1.0)
    r = np.where(s > 0, bank["straw_radius_m"], 0.0)
    u = (p - (pixels - 1) / 2.0) * bank["pixel_pitch_m"]
    v = (
        (t - (tubes - 1) / 2.0) * bank["tube_pitch_m"]
        + (layer % 2) * (bank["tube_pitch_m"] / 2.0)
        + r * np.cos(phi)
    )
    w = (layer - (layers - 1) / 2.0) * bank["layer_pitch_m"] + r * np.sin(phi)
    return np.stack(
        [
            bank["centre_m"][axis] + u * bank["along"][axis]
            + v * bank["across"][axis] + w * bank["normal"][axis]
            for axis in range(3)
        ],
        axis=1,
    )


def screen_edges(view: dict, positions: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(x_edges, y_edges) of the screen over the unjittered pixels."""
    ny, nx = view["resolution"]

    def edges(c: np.ndarray, n: int) -> np.ndarray:
        pad = (c.max() - c.min()) / n if c.max() > c.min() else 1.0
        return np.linspace(float(c.min() - 0.5 * pad), float(c.max() + 0.5 * pad), n + 1)

    return edges(positions[:, 0], nx), edges(positions[:, 1], ny)


def screen_bins(view: dict, x, y, x_edges, y_edges, clip: bool = False) -> np.ndarray:
    """The flat screen bin of every point, -1 off the screen (with
    ``clip``, the nearest edge bin: the fault)."""
    ny, nx = view["resolution"]
    xi = np.searchsorted(x_edges, x, side="right") - 1
    yi = np.searchsorted(y_edges, y, side="right") - 1
    if clip:
        xi, yi = np.clip(xi, 0, nx - 1), np.clip(yi, 0, ny - 1)
    on = (xi >= 0) & (xi < nx) & (yi >= 0) & (yi < ny)
    return np.where(on, yi * nx + xi, -1)


def replica_luts(view: dict, seed: int | None = None, jitter: bool = True,
                 clip: bool = False) -> np.ndarray:
    """[R, n] pixel offset (id - first_id) -> flat screen bin of each
    replica, -1 where it fell off the screen."""
    positions = bank_positions(view["bank"])
    x_edges, y_edges = screen_edges(view, positions)
    rng = np.random.default_rng(view["seed"] if seed is None else seed)
    luts = []
    for _ in range(view["replicas"]):
        moved = positions
        if jitter:
            moved = positions + rng.normal(0.0, view["noise_sigma_m"], positions.shape)
        luts.append(screen_bins(view, moved[:, 0], moved[:, 1], x_edges, y_edges, clip))
    return np.stack(luts)


class XyReplicasReference(PoolReference):
    """Everything is kept in whole on-screen replicas, integers, and
    multiplied by ``unit`` (1/R; 1 for the fault that leaves the weights
    at 1) only when an output is asked for."""

    def __init__(self, shape, toa_bins, images, spectra, unit: float, rounding=None) -> None:
        super().__init__([s.sum() for s in spectra])
        self._units = self.per_pulse  # int64: on-screen replicas of each pool entry's events
        self.per_pulse = self._units * unit  # what counts_cumulative gains in each
        self.shape = shape
        self.toa_bins = toa_bins
        self._unit = unit
        self._spectra = np.stack(spectra)  # [pool entry, TOA bin], units
        # the pool's running image sums, made once: [pool entry + 1, screen bin]
        self._running = np.concatenate(
            [np.zeros((1, shape[0] * shape[1]), np.int64), np.cumsum(np.stack(images), axis=0)]
        )
        self._rounding = rounding or (lambda bins: bins)

    def counts(self, lo: int, hi: int) -> float:
        return float(self.multiplicity(lo, hi) @ self._units) * self._unit

    def _image_upto(self, n: int) -> np.ndarray:
        turns, rest = divmod(n, len(self._spectra))
        return turns * self._running[-1] + self._running[rest]

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        if output.startswith("spectrum_"):
            units = self.multiplicity(lo, hi) @ self._spectra
        elif output.startswith("image_"):
            units = (self._image_upto(hi) - self._image_upto(lo)).reshape(self.shape)
        else:
            raise KeyError(f"detector_xy_replicas has no output {output!r}")
        return self._rounding(units * self._unit)

    span = staticmethod(suffix_span)


def stream_index(config: dict, name: str) -> int:
    return [s["name"] for s in config["streams"]].index(name)


def build(job, config, traffic, pools, *, luts=None, unit=None, rounding=None) -> XyReplicasReference:
    """The job's reference. A fault passes what it changes: the
    replicas' tables ``luts`` ([rows, n]), what an on-screen replica
    adds (``unit``, 1/R by default), how a bin is kept (``rounding``)."""
    view = job["view"]
    replicas = view["replicas"]
    if replicas & (replicas - 1):
        raise ValueError(f"job {job['name']}: 1/{replicas} is no binary fraction; bins would not be exact")
    pool, (first_id, n_pixels) = pools[stream_index(config, job["stream"])]
    bank = view["bank"]
    n_bank = bank["layers"] * bank["tubes"] * bank["straws"] * bank["pixels_per_straw"]
    if n_bank != n_pixels or bank["first_id"] != first_id:
        raise ValueError(f"job {job['name']}: the bank does not cover its stream's ids")
    luts = replica_luts(view) if luts is None else luts
    ny, nx = view["resolution"]
    toa_bins = view["toa_bins"]
    period = pulse_period_ns()
    width = period / toa_bins
    images, spectra = [], []
    for ids, toa in pool:
        pixel = ids.astype(np.int64) - first_id
        ok = (pixel >= 0) & (pixel < n_pixels) & (toa >= 0) & (toa < period)
        pixel = pixel[ok] % luts.shape[1]  # (a neighbour's table may be shorter: the fault)
        toa_bin = (toa[ok].astype(np.float64) // width).astype(np.int64)
        image = np.zeros(ny * nx, np.int64)
        spectrum = np.zeros(toa_bins, np.int64)
        for lut in luts:
            screen = lut[pixel]
            on = screen >= 0
            image += np.bincount(screen[on], minlength=ny * nx)
            spectrum += np.bincount(toa_bin[on], minlength=toa_bins)
        images.append(image)
        spectra.append(spectrum)
    return XyReplicasReference(
        (ny, nx), toa_bins, images, spectra, (1.0 / replicas) if unit is None else unit, rounding
    )


def neighbour_view(job, config) -> dict:
    """The view of the next job of this kind (the last job's neighbour
    is the first): a job that took its neighbour's table reads that
    bank's screen bins by its own pixel offsets."""
    views = [j["view"] for j in config["jobs"] if j["view"]["kind"] == job["view"]["kind"]]
    return views[(views.index(job["view"]) + 1) % len(views)]


def tolerance(output: str):
    return (
        0.0, 0.0,
        "exact: every bin is a whole multiple of 1/R = 1/4 (an event adds 1/4 at each on-screen "
        "replica), and float32 holds every multiple of 1/4 below 2**22 (a TOA bin gets there after "
        "7 314 pulses of 57 344 events, 522 s; no run does), so weights and sums are exact whatever "
        "the order; a bfloat16 accumulator holds them below 2**6 only and misses in every spectrum",
    )


def check(output: str) -> str:
    return CHECKS[output]


def work_bytes(job, config, events: int, publishes: int) -> int:
    """Per event its id and TOA in (8 B) and, per replica, one table
    entry gathered (int32, 4 B), one bin read and one written (8 B):
    56 B at R = 4; per publish as a detector view's: the fold's four
    passes over screen x TOA bins and the fetch of two images, two
    spectra and four scalars, float32."""
    view = job["view"]
    screen = view["resolution"][0] * view["resolution"][1]
    bins = screen * view["toa_bins"]
    fetched = 4 * (2 * screen + 2 * view["toa_bins"] + 4)
    return events * (8 + view["replicas"] * 12) + publishes * (4 * bins * 4 + fetched)


def faults():
    def replica_left_out(job, config, traffic, pools):
        # R - 1 replicas at 1/R each: a quarter of every event is lost
        return build(job, config, traffic, pools, luts=replica_luts(job["view"])[:-1])

    def weights_left_at_one(job, config, traffic, pools):
        # every on-screen replica adds 1, not 1/R
        return build(job, config, traffic, pools, unit=1.0)

    def unjittered_lut(job, config, traffic, pools):
        # no noise drawn: the pixel's own bin, R times
        return build(job, config, traffic, pools, luts=replica_luts(job["view"], jitter=False))

    def jitter_other_seed(job, config, traffic, pools):
        return build(job, config, traffic, pools,
                     luts=replica_luts(job["view"], seed=job["view"]["seed"] + 1))

    def neighbour_bank_lut(job, config, traffic, pools):
        return build(job, config, traffic, pools, luts=replica_luts(neighbour_view(job, config)))

    def offscreen_clipped(job, config, traffic, pools):
        # a replica off the screen is moved into the edge bin, not dropped
        return build(job, config, traffic, pools, luts=replica_luts(job["view"], clip=True))

    def accumulator_bfloat16(job, config, traffic, pools):
        # the same bins kept in the precision below float32
        return build(job, config, traffic, pools, rounding=as_bfloat16)

    return {
        "replica_left_out": replica_left_out,
        "weights_left_at_one": weights_left_at_one,
        "unjittered_lut": unjittered_lut,
        "jitter_other_seed": jitter_other_seed,
        "neighbour_bank_lut": neighbour_bank_lut,
        "offscreen_clipped": offscreen_clipped,
        "accumulator_bfloat16": accumulator_bfloat16,
    }
