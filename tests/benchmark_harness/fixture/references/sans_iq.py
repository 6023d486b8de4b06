"""What the package's ``sans/iq`` should publish for the toy LOKI: the
monitor-normalised I(Q) of a flat pixel plane, from the formulae and the
sizes the configuration's ``view`` states. Nothing of the program is
imported and no table it made is read.

An event of pixel ``p`` at time of arrival ``t`` counts in Q bin ``q``
iff ``p`` is one of the plane's pixels, ``t`` lies in [0, pulse period)
and Q(p, t) lies in [q.min, q.max):

    lambda = h_over_mn * t_c / (l1 + l2(p))     t_c: centre of t's TOA bin
    Q      = 4 pi sin(theta(p) / 2) / lambda    theta: angle off the +z beam

with ``l2`` the pixel's distance from the sample. ``counts_q`` is that
histogram; ``monitor_counts`` is every event of the bound monitor
stream; ``iq`` is their quotient (no transmission monitor is bound, so
the transmission fraction is 1).
"""

from __future__ import annotations

import numpy as np
from harness.reference import PoolReference
from harness.traffic import pulse_period_ns

CHECKS = {
    "iq_current": "iq_bins_off",
    "iq_cumulative": "iq_bins_off",
    "counts_q_current": "q_counts_wrong",
    "monitor_counts_current": "monitor_counts_wrong",
}


class IqReference(PoolReference):
    def __init__(self, counts_q: np.ndarray, monitor: np.ndarray) -> None:
        super().__init__(counts_q.sum(axis=1))
        self._counts_q = counts_q  # [pool entry, Q bin]
        self._monitor = monitor  # [pool entry]

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        times = self.multiplicity(lo, hi)
        counts, monitor = times @ self._counts_q, times @ self._monitor
        if output.startswith("counts_q_"):
            return counts
        if output.startswith("monitor_counts_"):
            return np.asarray(monitor)
        if output.startswith("iq_"):
            return counts / max(float(monitor), 1.0)
        raise KeyError(f"sans_iq has no output {output!r}")


def pixel_geometry(view: dict) -> tuple[np.ndarray, np.ndarray]:
    """(4 pi sin(theta / 2), l2 in m) of every pixel of the plane, in
    the order of the ids: rows of ``shape[1]`` pixels along x, edge to
    edge of the extent, at ``z_m`` from the sample."""
    ny, nx = view["plane"]["shape"]
    height, width = view["plane"]["extent_m"]
    x, y = np.meshgrid(np.linspace(-width / 2, width / 2, nx), np.linspace(-height / 2, height / 2, ny))
    x, y, z = x.ravel(), y.ravel(), view["plane"]["z_m"]
    theta = np.arctan2(np.hypot(x, y), z)
    return 4.0 * np.pi * np.sin(theta / 2.0), np.sqrt(x * x + y * y + z * z)


def q_histograms(job, config, pools, toa_bin_shift: int = 0) -> np.ndarray:
    """[pool entry, Q bin] of the job's own stream."""
    view = job["view"]
    index = {s["name"]: i for i, s in enumerate(config["streams"])}
    pool, (first_id, n_pixels) = pools[index[job["stream"]]]
    k_factor, l2 = pixel_geometry(view)
    if k_factor.size != n_pixels:
        raise ValueError("the plane does not cover the stream's pixels")
    period = pulse_period_ns()
    toa_edges = np.linspace(0.0, period, view["toa_bins"] + 1)
    centre_s = (toa_edges[:-1] + toa_edges[1:]) / 2.0 * 1e-9
    q_edges = np.linspace(view["q"]["min"], view["q"]["max"], view["q"]["bins"] + 1)
    out = np.zeros((len(pool), view["q"]["bins"]), np.int64)
    for entry, (ids, toa) in enumerate(pool):
        pixel = ids.astype(np.int64) - first_id
        ok = (pixel >= 0) & (pixel < n_pixels) & (toa >= 0) & (toa < period)
        pixel, toa = pixel[ok], toa[ok].astype(np.float64)
        toa_bin = np.floor(toa * (view["toa_bins"] / period)).astype(np.int64)
        toa_bin = np.clip(toa_bin + toa_bin_shift, 0, view["toa_bins"] - 1)
        wavelength = view["h_over_mn"] * centre_s[toa_bin] / (view["l1_m"] + l2[pixel])
        q = k_factor[pixel] / wavelength
        inside = (q >= q_edges[0]) & (q < q_edges[-1])
        q_bin = np.searchsorted(q_edges, q[inside], side="right") - 1
        out[entry] = np.bincount(q_bin, minlength=view["q"]["bins"])
    return out


def monitor_counts(job, config, pools, times: int = 1) -> np.ndarray:
    """[pool entry]: every event of the stream bound as ``monitor``."""
    index = {s["name"]: i for i, s in enumerate(config["streams"])}
    pool, _ = pools[index[job["aux_source_names"]["monitor"]]]
    return times * np.array([toa.size for _ids, toa in pool], np.int64)


def build(job, config, traffic, pools) -> IqReference:
    return IqReference(q_histograms(job, config, pools), monitor_counts(job, config, pools))


def tolerance(output: str):
    if output.startswith("iq_"):
        return (
            2.0**-22, 0.0,
            "a float32 quotient of two float32 sums that are exact below 2**24: one rounding, "
            "2**-24 of the value, with room for a second; bfloat16 or float16 anywhere in it "
            "misses by 2**-12 or more",
        )
    return None


def check(output: str) -> str:
    return CHECKS[output]


def work_bytes(job, config, events: int, publishes: int) -> int:
    """Per event its id and TOA in (8 B), one table entry gathered
    (int16, 2 B), one Q bin read and one written (8 B); per publish the
    fold's four passes over the Q bins and the fetch of two Q spectra
    and two monitor totals, float32."""
    q_bins = job["view"]["q"]["bins"]
    return events * 18 + publishes * (4 * q_bins * 4 + 4 * (2 * q_bins + 2))


def faults():
    return {
        "monitor_twice": lambda job, config, traffic, pools: IqReference(
            q_histograms(job, config, pools), monitor_counts(job, config, pools, times=2)
        ),
        "toa_bin_off_by_one": lambda job, config, traffic, pools: IqReference(
            q_histograms(job, config, pools, toa_bin_shift=1), monitor_counts(job, config, pools)
        ),
    }
