"""The plain reference: numpy over the same seeded events.

It states the configuration's guarantees directly: an event counts in a
job's outputs iff its pixel id is one of the stream's pixels that the
job's view keeps and its TOA lies in [0, pulse period); each such event
adds one to one (screen bin, TOA bin). Nothing of the program is
imported and nothing it made (no LUT, no geometry file) is read: the
views are rebuilt here from the sizes the configuration file states.

**A reference kind is a file.** A job's ``view.kind`` says what its
outputs should be. ``grid`` and ``nd`` (a detector view: TOA spectrum
and screen image, integers) are answered here by ``JobReference``; any
other kind ``<kind>`` is answered by ``benchmark/references/<kind>.py``,
loaded by path (``load_kind``). Such a module imports numpy and the
harness's ``traffic`` and ``reference`` modules, nothing of the program,
and has four functions (``Kind``):

``build(job, config, traffic, pools) -> ref``
    The job's expected outputs for any pulse prefix (``Reference``):
    ``per_pulse``, ``counts(lo, hi)``, ``prefix_of(total, upto)`` as
    ``PoolReference`` has them, and ``expected(output, lo, hi)``, an
    array for every output the job lists but its scalars. ``pools`` are
    ``make_pools``' in the order of the configuration's streams.
``tolerance(output) -> None | (rel, abs, reason)``
    ``None``: the output is compared by ``results.bins_off`` (exact
    below 2**24). A float output states by how much it may miss,
    ``abs + rel * |expected|`` bin by bin, and why, in words that are
    printed beside the check; tight enough that a lower precision than
    the configuration states fails it.
``work_bytes(job, config, events, publishes) -> int``
    The least bytes the job's work must move whatever implements it, for
    ``events`` of its own stream staged and ``publishes``
    (``roofline.least_seconds`` asks for a kind it does not know).
``faults() -> {name: build}``
    The guarantees of this kind, each broken once: a function with
    ``build``'s signature that returns the reference with that guarantee
    broken, for ``control.py``. A kind with none cannot be loaded: a
    comparison with no control is not a comparison.

Two more are optional. ``check(output) -> str`` names the check an
output's misses count into (``CHECK_OF_CLASS`` where there is none); each
needs a limit in ``limits/<cell>.json``. A ref's ``span(output,
previous, prefix) -> (lo, hi)`` chooses the pulses an output holds
(``suffix_span`` where there is none).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Callable, Protocol

import numpy as np

from .traffic import FramePool, Traffic, pulse_period_ns, stream_pool

#: The kinds answered in this module.
VIEW_KINDS = ("grid", "nd")
#: The four functions of a kind's module.
KIND_FUNCTIONS = ("build", "tolerance", "work_bytes", "faults")
#: The check an output counts into where its kind names none, by the
#: class the job lists it under.
CHECK_OF_CLASS = {"spectra": "spectrum_bins_wrong", "images": "image_bins_wrong"}


class Reference(Protocol):
    """One job's expected outputs for any pulse prefix."""

    per_pulse: np.ndarray  # events counted in each pool entry

    def counts(self, lo: int, hi: int) -> int: ...

    def prefix_of(self, total: float, upto: int) -> tuple[int, float]: ...

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray: ...


class Kind(Protocol):
    """A module ``benchmark/references/<kind>.py``."""

    def build(self, job: dict, config: dict, traffic: Traffic, pools) -> Reference: ...

    def tolerance(self, output: str) -> tuple[float, float, str] | None: ...

    def work_bytes(self, job: dict, config: dict, events: int, publishes: int) -> int: ...

    def faults(self) -> dict[str, Callable]: ...


def suffix_span(output: str, previous: int, prefix: int) -> tuple[int, int]:
    """``*_current`` holds the pulses since the previous publish, any
    other output all pulses of the prefix."""
    return (previous if output.endswith("_current") else 0, prefix)


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


class PoolReference:
    """What every reference over a cycled pool shares: pulse ``k`` of a
    run carries pool entry ``k % pool_pulses``, so a span of pulses is a
    multiplicity of each entry, and the total of a prefix finds it."""

    def __init__(self, per_pulse) -> None:
        self.per_pulse = np.asarray(per_pulse, np.int64)
        self._prefix_counts = np.zeros(1, np.int64)

    def multiplicity(self, lo: int, hi: int) -> np.ndarray:
        """How often each pool entry occurs among pulses [lo, hi)."""
        pool = len(self.per_pulse)
        k = np.arange(pool)
        upto = lambda n: n // pool + (k < n % pool)  # noqa: E731
        return upto(hi) - upto(lo)

    def counts(self, lo: int, hi: int) -> int:
        return int(self.multiplicity(lo, hi) @ self.per_pulse)

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


class JobReference(PoolReference):
    """A detector view's expected outputs (``grid`` / ``nd``): the
    protocol's first implementation."""

    def __init__(self, shape, toa_bins, screens, tbins) -> None:
        super().__init__([s.size for s in screens])
        self.shape = shape
        self.toa_bins = toa_bins
        self._screens = screens  # per pool entry: screen bin of each counted event
        self._tbins = tbins
        self._running: np.ndarray | None = None
        self._spectra = np.stack(
            [np.bincount(t, minlength=toa_bins) for t in tbins]
        ).astype(np.int64)

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

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        if output.startswith("spectrum_"):
            return self.spectrum(lo, hi)
        if output.startswith("image_"):
            return self.image(lo, hi)
        raise KeyError(f"a detector view has no output {output!r}")

    span = staticmethod(suffix_span)

    @staticmethod
    def tolerance(output: str) -> None:
        return None

    @staticmethod
    def check(output: str) -> str:
        return CHECK_OF_CLASS["spectra" if output.startswith("spectrum_") else "images"]


class KindReference:
    """A ref that a kind's module built, with the module's answers about
    its outputs beside it: what ``results.compare`` asks of any ref."""

    def __init__(self, ref: Reference, kind: Kind, classes: dict[str, str]) -> None:
        self._ref = ref
        self.span = getattr(ref, "span", suffix_span)
        self.tolerance = kind.tolerance
        self.check = {
            output: check_of(kind, output, output_class) for output, output_class in classes.items()
        }.__getitem__

    @property
    def per_pulse(self) -> np.ndarray:
        return self._ref.per_pulse

    def counts(self, lo: int, hi: int) -> int:
        return self._ref.counts(lo, hi)

    def prefix_of(self, total: float, upto: int) -> tuple[int, float]:
        return self._ref.prefix_of(total, upto)

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        return np.asarray(self._ref.expected(output, lo, hi))


def check_of(kind: Kind | None, output: str, output_class: str) -> str:
    """The check that ``output`` of a job of ``kind`` (None: a detector
    view) counts into."""
    if hasattr(kind, "check"):
        return kind.check(output)
    return CHECK_OF_CLASS.get(output_class, f"{output}_wrong")


def load_kind(bench: Path, kind: str) -> Kind:
    """``<bench>/references/<kind>.py`` as a module. ValueError where
    the file is missing or is not a kind."""
    path = Path(bench) / "references" / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"view kind {kind!r}: no file {path.parent.name}/{path.name}")
    spec = importlib.util.spec_from_file_location(f"benchmark_reference_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    lacking = [name for name in KIND_FUNCTIONS if not callable(getattr(module, name, None))]
    if lacking:
        raise ValueError(f"references/{kind}.py lacks {', '.join(lacking)}")
    if not module.faults():
        raise ValueError(f"references/{kind}.py names no fault: a comparison needs a control")
    return module


def compared_classes(config: dict, job: dict) -> dict[str, str]:
    """output -> the class the job lists it under, for every output that
    is compared with the reference (all but the scalars)."""
    doc = job.get("outputs", config["outputs"])
    return {
        output: output_class
        for output_class in ("spectra", "images", "arrays")
        for output in doc.get(output_class, [])
    }


def check_names(config: dict, kinds: dict[str, Kind]) -> list[str]:
    """Every check a cell's comparison counts into, before any run."""
    names = [
        check_of(kinds.get(job["view"]["kind"]), output, output_class)
        for job in config["jobs"]
        for output, output_class in compared_classes(config, job).items()
    ]
    return list(dict.fromkeys([*names, "prefix_off_pulses"]))


FAULTS = ("drop_event", "half_pulse", "clip_toa", "clip_pixel")


def controls(config: dict, kinds: dict[str, Kind]) -> tuple[str, ...]:
    """Every fault a configuration's comparison has to catch: the pools'
    faults where it has a detector stream, then each fault of each of
    its kinds' modules (``<kind>.<name>``)."""
    events = any(s.get("kind", "detector") == "detector" for s in config["streams"])
    return (
        *(FAULTS if events else ()),
        *(f"{kind}.{name}" for kind, module in kinds.items() for name in module.faults()),
    )


def break_guarantee(pools, fault: str):
    """The control: the same pools with one guarantee broken, as a later
    PR that trades exactness for speed would break it.

    ``drop_event``: one in-range event of one pool pulse is lost.
    ``half_pulse``: every other in-range event of one pool pulse is lost.
    ``clip_toa``: out-of-range TOA is clipped into the frame, not dropped.
    ``clip_pixel``: out-of-range ids are clamped onto the edge pixels.

    A stream with no ids (a monitor) and a camera's frames are left as
    they are: these are the detector streams' guarantees.
    """
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    period = pulse_period_ns()
    out = []
    for pool, (first_id, n_pixels) in pools:
        if isinstance(pool, FramePool):
            out.append((pool, (first_id, n_pixels)))
            continue
        pulses = []
        for entry, (ids, toa) in enumerate(pool):
            ids, toa = ids.copy(), toa.copy()
            if not ids.size:
                pass
            elif fault == "drop_event" and entry == 0:
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
            pulses.append((ids, toa))
        out.append((pulses, (first_id, n_pixels)))
    return out


def make_pools(config: dict, traffic: Traffic, seed: int):
    """[(pool, (first_id, n_pixels))] in the order of the configuration's
    streams; (0, 0) for a stream with no pixel ids (a monitor, a camera,
    whose pool is a ``FramePool``)."""
    return [
        (
            stream_pool(seed, i, stream, traffic),
            (stream.get("first_id", 0), stream.get("n_pixels", 0)),
        )
        for i, stream in enumerate(config["streams"])
    ]


def build(config: dict, traffic: Traffic, pools, kinds: dict[str, Kind] | None = None,
          fault: str | None = None) -> dict[str, Reference]:
    """job name -> its reference: a ``JobReference`` from the pools of
    the job's stream for a detector view, what ``kinds[kind].build``
    gives for any other kind. ``fault`` (``<kind>.<name>``) puts that
    kind's reference with one guarantee broken in its jobs' place."""
    stream_index = {s["name"]: i for i, s in enumerate(config["streams"])}
    width = pulse_period_ns() / traffic.toa_bins
    broken_kind, _, broken = (fault or "").partition(".")
    refs = {}
    for job in config["jobs"]:
        kind = job["view"]["kind"]
        if kind not in VIEW_KINDS:
            if kind not in (kinds or {}):
                raise ValueError(f"view kind {kind!r}")
            make = kinds[kind].faults()[broken] if kind == broken_kind else kinds[kind].build
            refs[job["name"]] = KindReference(
                make(job, config, traffic, pools), kinds[kind], compared_classes(config, job)
            )
            continue
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
