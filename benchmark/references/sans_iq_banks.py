"""What the package's ``sans/iq`` should publish for one straw-tube bank
of LOKI with an incident and a transmission monitor bound: the
monitor-normalised I(Q), from the formulae and from the sizes the job's
``view`` states. Nothing of the program is imported, no table it made
is read, and the pixel positions are built here, so that a wrong id
base, bank order or panel shows.

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

**Counts.** An event of pixel id ``i`` at time of arrival ``t`` counts
in Q bin ``q`` iff ``i`` is one of the bank's ids, ``t`` lies in
[0, pulse period) and Q lies in [q.min, q.max):

    lambda = h_over_mn t_c / (l1 + l2)        t_c: centre of t's TOA bin
    Q      = 4 pi sin(theta / 2) / lambda     theta: angle off the +z beam

with ``l2`` the pixel's distance from the sample, at the origin.

**Normalisation.** Every event of the stream bound as ``monitor`` counts
as incident, every event of the one bound as ``transmission_monitor`` as
transmitted, whatever its TOA. Over the pulses an output holds,
``T = transmitted / incident`` (1 where either is 0; a float64 quotient
of two whole numbers, so it is compared exactly) and
``iq = counts_q / (max(incident, 1) T)``.
"""

from __future__ import annotations

import numpy as np
from harness.reference import PoolReference
from harness.traffic import pulse_period_ns

#: The checks are the accepted cells' own three (every limits file of the
#: benchmark states the same exact limits): the outputs that are whole
#: numbers of events or their float64 quotient count into the spectra's
#: check, bin for bin and exactly; the float32 I(Q) counts into the other,
#: by the tolerance stated below.
CHECKS = {
    "counts_q_current": "spectrum_bins_wrong",
    "monitor_counts_current": "spectrum_bins_wrong",
    "transmission_current": "spectrum_bins_wrong",
    "iq_current": "image_bins_wrong",
    "iq_cumulative": "image_bins_wrong",
}
#: By how much an ``iq_*`` bin may miss the float64 quotient, as a share
#: of its value. PERF.md, section 6 (PR 27), has the readings on both sides.
IQ_REL = 2.0**-18


def as_bfloat16(values: np.ndarray) -> np.ndarray:
    """float64 -> the nearest bfloat16 (round to even), as float64:
    what a quotient kept in the precision below float32 would read."""
    bits = np.asarray(values, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


class IqBanksReference(PoolReference):
    def __init__(self, counts_q, incident, transmitted, bound: bool = True,
                 quotient=lambda iq: iq) -> None:
        super().__init__(counts_q.sum(axis=1))
        self._counts_q = counts_q  # [pool entry, Q bin]
        self._incident = incident  # [pool entry]
        self._transmitted = transmitted  # [pool entry]
        self._bound = bound  # False: as if no transmission monitor were bound
        self._quotient = quotient

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        times = self.multiplicity(lo, hi)
        incident = float(times @ self._incident)
        transmitted = float(times @ self._transmitted)
        fraction = 1.0
        if self._bound and incident > 0 and transmitted > 0:
            fraction = transmitted / incident
        if output.startswith("counts_q_"):
            return times @ self._counts_q
        if output.startswith("monitor_counts_"):
            return np.asarray(incident)
        if output.startswith("transmission_"):
            return np.asarray(fraction)
        if output.startswith("iq_"):
            return self._quotient((times @ self._counts_q) / (max(incident, 1.0) * fraction))
        raise KeyError(f"sans_iq_banks has no output {output!r}")


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


def pixel_geometry(bank: dict) -> tuple[np.ndarray, np.ndarray]:
    """(4 pi sin(theta / 2), l2 in m) of every pixel of the bank."""
    x, y, z = bank_positions(bank).T
    theta = np.arctan2(np.hypot(x, y), z)
    return 4.0 * np.pi * np.sin(theta / 2.0), np.sqrt(x * x + y * y + z * z)


def stream_index(config: dict, name: str) -> int:
    return [s["name"] for s in config["streams"]].index(name)


def q_histograms(job, config, pools, toa_bin_shift: int = 0, bank: dict | None = None) -> np.ndarray:
    """[pool entry, Q bin] of the job's own stream, reduced with the
    geometry of ``bank`` (the job's own where none is given)."""
    view = job["view"]
    bank = bank or view["bank"]
    pool, (first_id, n_pixels) = pools[stream_index(config, job["stream"])]
    k_factor, l2 = pixel_geometry(bank)
    if k_factor.size != n_pixels or bank["first_id"] != first_id:
        raise ValueError(f"job {job['name']}: the bank does not cover its stream's ids")
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


def monitor_counts(job, config, pools, role: str, times: int = 1) -> np.ndarray:
    """[pool entry]: every event of the stream bound as ``role``."""
    pool, _ = pools[stream_index(config, job["aux_source_names"][role])]
    return times * np.array([toa.size for _ids, toa in pool], np.int64)


def build(job, config, traffic, pools, **broken) -> IqBanksReference:
    """The job's reference; ``broken`` is what a fault changes."""
    return IqBanksReference(
        q_histograms(job, config, pools, broken.get("toa_bin_shift", 0), broken.get("bank")),
        monitor_counts(job, config, pools, "monitor", broken.get("monitor_times", 1)),
        monitor_counts(job, config, pools, "transmission_monitor"),
        broken.get("bound", True),
        broken.get("quotient", lambda iq: iq),
    )


def neighbour_bank(job, config) -> dict:
    """The job's own ids and sizes on the panel of the next job's bank
    (the last job's neighbour is the first): what a job reduced with
    its neighbour's geometry reads."""
    views = [j["view"]["bank"] for j in config["jobs"] if j["view"]["kind"] == job["view"]["kind"]]
    own = job["view"]["bank"]
    other = views[(views.index(own) + 1) % len(views)]
    return {**own, **{key: other[key] for key in ("centre_m", "along", "across", "normal")}}


def tolerance(output: str):
    if output.startswith("iq_"):
        return (
            IQ_REL, 0.0,
            "counts over monitor counts and the transmission fraction, in float32: one rounding of "
            "the quotient (2**-24 of the value) and, once a cumulative bin passes 2**24, one "
            "rounding of that sum per window; bfloat16 anywhere in it misses by 2**-9, float16 by 2**-12",
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
    def broken(**what):
        return lambda job, config, traffic, pools: build(job, config, traffic, pools, **what)

    return {
        "monitor_twice": broken(monitor_times=2),
        "toa_bin_off_by_one": broken(toa_bin_shift=1),
        "transmission_unbound": broken(bound=False),
        "bank_off_by_one": lambda job, config, traffic, pools: build(
            job, config, traffic, pools, bank=neighbour_bank(job, config)
        ),
        "quotient_bfloat16": broken(quotient=as_bfloat16),
    }
