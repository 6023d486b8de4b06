"""The plain reference: numpy over the same seeded events.

It states the configuration's guarantees directly: an event counts in a
job's outputs iff its pixel id is one of the stream's pixels that the
job's view keeps and its TOA lies in [0, pulse period); each such event
adds one to one (screen bin, TOA bin). Nothing of the program is
imported and nothing it made (no LUT, no geometry file) is read: the
views are rebuilt here from the sizes the configuration file states.
"""

from __future__ import annotations

import numpy as np

from .traffic import Traffic, make_pool, pulse_period_ns


def screen_lut(view: dict, n_pixels: int) -> tuple[np.ndarray, tuple[int, int]]:
    """pixel offset (id - first_id) -> screen bin, -1 where the view
    drops the pixel; and the image shape (ny, nx)."""
    if view["kind"] == "grid":
        ny, nx = view["shape"]
        if ny * nx != n_pixels:
            raise ValueError("grid view does not cover the stream's pixels")
        return np.arange(n_pixels, dtype=np.int64), (ny, nx)
    if view["kind"] != "nd":
        raise ValueError(f"view kind {view['kind']!r}")
    sizes = view["sizes"]  # dim -> size, C order of the pixel numbering
    shape = tuple(sizes.values())
    if int(np.prod(shape)) != n_pixels:
        raise ValueError("nd view does not cover the stream's pixels")
    coords = dict(zip(sizes, np.unravel_index(np.arange(n_pixels), shape)))
    keep = np.ones(n_pixels, bool)
    for dim, index in view.get("select", {}).items():
        keep &= coords[dim] == index

    def composite(dims):
        index, total = np.zeros(n_pixels, np.int64), 1
        for dim in dims:
            index = index * sizes[dim] + coords[dim]
            total *= sizes[dim]
        return index, total

    row, ny = composite(view["y"])
    col, nx = composite(view.get("x", []))
    return np.where(keep, row * nx + col, -1), (ny, nx)


class JobReference:
    """One job's expected outputs for any pulse prefix."""

    def __init__(self, shape, toa_bins, screens, tbins) -> None:
        self.shape = shape
        self.toa_bins = toa_bins
        self._screens = screens  # per pool entry: screen bin of each counted event
        self._tbins = tbins
        self._running: np.ndarray | None = None
        self._prefix_counts = np.zeros(1, np.int64)
        self.per_pulse = np.array([s.size for s in screens], np.int64)
        self._spectra = np.stack(
            [np.bincount(t, minlength=toa_bins) for t in tbins]
        ).astype(np.int64)

    def multiplicity(self, lo: int, hi: int) -> np.ndarray:
        """How often each pool entry occurs among pulses [lo, hi)."""
        pool = len(self._screens)
        k = np.arange(pool)
        upto = lambda n: n // pool + (k < n % pool)  # noqa: E731
        return upto(hi) - upto(lo)

    def counts(self, lo: int, hi: int) -> int:
        return int(self.multiplicity(lo, hi) @ self.per_pulse)

    def spectrum(self, lo: int, hi: int) -> np.ndarray:
        return self.multiplicity(lo, hi) @ self._spectra

    def _image_upto(self, n: int) -> np.ndarray:
        """The image of pulses [0, n): whole turns of the pool and the
        rest of one, from the pool's running sums (made once)."""
        if self._running is None:
            n_screen = self.shape[0] * self.shape[1]
            self._running = np.zeros((len(self._screens) + 1, n_screen), np.int32)
            for entry, screen in enumerate(self._screens):
                self._running[entry + 1] = self._running[entry] + np.bincount(
                    screen, minlength=n_screen
                )
        turns, rest = divmod(n, len(self._screens))
        return turns * self._running[-1].astype(np.int64) + self._running[rest]

    def image(self, lo: int, hi: int) -> np.ndarray:
        return (self._image_upto(hi) - self._image_upto(lo)).reshape(self.shape)

    def prefix_of(self, counts: float, upto: int) -> tuple[int, float]:
        """The pulse prefix whose cumulative count is nearest ``counts``,
        and how far off it is, in pulses of this job's mean count."""
        if len(self._prefix_counts) <= upto:  # made once for the longest run asked about
            self._prefix_counts = np.concatenate(
                [[0], np.cumsum(self.per_pulse[np.arange(upto) % len(self.per_pulse)])]
            )
        table = self._prefix_counts[: upto + 1]
        n = int(np.clip(np.searchsorted(table, counts), 1, upto))
        if abs(table[n - 1] - counts) < abs(table[n] - counts):
            n -= 1
        return n, abs(float(table[n]) - counts) / max(float(self.per_pulse.mean()), 1.0)


FAULTS = ("drop_event", "half_pulse", "clip_toa", "clip_pixel")


def break_guarantee(pools, fault: str):
    """The control: the same pools with one guarantee broken, as a later
    PR that trades exactness for speed would break it.

    ``drop_event``: one in-range event of one pool pulse is lost.
    ``half_pulse``: every other in-range event of one pool pulse is lost.
    ``clip_toa``: out-of-range TOA is clipped into the frame, not dropped.
    ``clip_pixel``: out-of-range ids are clamped onto the edge pixels.
    """
    period = pulse_period_ns()
    out = []
    for stream_pool, (first_id, n_pixels) in pools:
        pulses = []
        for entry, (ids, toa) in enumerate(stream_pool):
            ids, toa = ids.copy(), toa.copy()
            if fault == "drop_event" and entry == 0:
                ok = np.flatnonzero(
                    (ids >= first_id) & (ids < first_id + n_pixels)
                    & (toa >= 0) & (toa < period)
                )
                ids[ok[len(ok) // 2]] = first_id - 1
            elif fault == "half_pulse" and entry == 0:
                ok = np.flatnonzero(
                    (ids >= first_id) & (ids < first_id + n_pixels)
                    & (toa >= 0) & (toa < period)
                )
                ids[ok[::2]] = first_id - 1
            elif fault == "clip_toa":
                toa = np.clip(toa, 0, int(period) - 1)
            elif fault == "clip_pixel":
                ids = np.clip(ids, first_id, first_id + n_pixels - 1)
            elif fault not in FAULTS:
                raise ValueError(f"unknown fault {fault!r}")
            pulses.append((ids, toa))
        out.append((pulses, (first_id, n_pixels)))
    return out


def make_pools(config: dict, traffic: Traffic, seed: int):
    """[(pool, (first_id, n_pixels))] in the order of the configuration's streams."""
    return [
        (
            make_pool(seed, i, stream["first_id"], stream["n_pixels"], traffic),
            (stream["first_id"], stream["n_pixels"]),
        )
        for i, stream in enumerate(config["streams"])
    ]


def build(config: dict, traffic: Traffic, pools) -> dict[str, JobReference]:
    """job name -> JobReference, from the pools of the job's stream."""
    stream_index = {s["name"]: i for i, s in enumerate(config["streams"])}
    width = pulse_period_ns() / traffic.toa_bins
    refs = {}
    for job in config["jobs"]:
        pool, (first_id, n_pixels) = pools[stream_index[job["stream"]]]
        lut, shape = screen_lut(job["view"], n_pixels)
        screens, tbins = [], []
        for ids, toa in pool:
            pix = ids.astype(np.int64) - first_id
            ok = (pix >= 0) & (pix < n_pixels) & (toa >= 0) & (toa < pulse_period_ns())
            screen = lut[pix[ok]]
            kept = screen >= 0
            screens.append(screen[kept])
            tbins.append((toa[ok][kept].astype(np.float64) // width).astype(np.int64))
        refs[job["name"]] = JobReference(shape, traffic.toa_bins, screens, tbins)
    return refs
