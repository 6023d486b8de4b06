"""The client: follows the data topic, stamps every publish with the
time it was read, and after the window turns the record into the
end-to-end metrics and the comparison with the reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .wire import decode_da00

#: An ``arrays`` output no larger than this is kept for every publish;
#: a larger one is sampled like the images.
KEEP_BYTES = 64 * 1024


@dataclass(frozen=True)
class Outputs:
    """The outputs of one job's publish that are read, as the
    configuration file lists them under ``outputs`` (a job may list its
    own). ``scalars`` are kept as floats and not compared; ``spectra``
    (kept for every publish) and ``images`` (kept for the sampled
    publishes) are what a detector view gives; ``arrays`` is any other
    named output, of any shape. A name that ends in ``_current`` holds
    the pulses since the previous publish, any other all pulses.
    ``prefix_total`` is the output whose total tells which pulses a
    publish holds: a scalar, or an array whose sum is taken; a
    ``*_current`` one is summed over the job's publishes so far. It
    marks the publish as received."""

    scalars: tuple[str, ...]
    spectra: tuple[str, ...]
    images: tuple[str, ...]
    prefix_total: str
    arrays: tuple[str, ...] = ()

    @classmethod
    def from_config(cls, config: dict, job: dict | None = None) -> "Outputs":
        """The configuration's outputs, or those of ``job`` where it
        lists its own (``outputs``, ``prefix_total``)."""
        job = job or {}
        doc = job.get("outputs", config["outputs"])
        return cls(
            *(tuple(doc.get(key, ())) for key in ("scalars", "spectra", "images")),
            job.get("prefix_total", config["outputs"]["prefix_total"]),
            tuple(doc.get("arrays", ())),
        )

    @property
    def read(self) -> tuple[str, ...]:
        return self.scalars + self.spectra + self.images + self.arrays


@dataclass
class Publish:
    """One job's outputs of one closed window, as the client read them."""

    job: str
    ordinal: int  # 0, 1, ... per job
    received_ns: int = 0  # CLOCK_MONOTONIC, at the prefix total
    scalars: dict[str, float] = field(default_factory=dict)
    spectra: dict[str, np.ndarray] = field(default_factory=dict)
    images: dict[str, np.ndarray] = field(default_factory=dict)  # kept for the sample only
    arrays: dict[str, np.ndarray] = field(default_factory=dict)  # the large ones: for the sample only
    total: float = 0.0  # the prefix total: events counted in pulses [0, prefix)
    prefix: int = -1  # pulses [0, prefix) are in the prefix total
    off_by: float = 0.0


class ResultReader:
    """Keeps every publish's scalars, spectra and small arrays, and the
    images and large arrays of the publishes that ``sampled`` names plus
    each job's newest (at NMX size all of them would not fit).
    ``outputs`` is each job's. The outputs of one publish share their
    da00 timestamp, which is used as a grouping key and nothing else."""

    def __init__(self, child, jobs_by_number: dict[str, str], clock,
                 outputs: dict[str, Outputs], sampled=lambda job, ordinal: False) -> None:
        self._child = child
        self._jobs = jobs_by_number
        self._clock = clock
        self.outputs = outputs
        self._sampled = sampled
        self._open: dict[tuple[str, int], tuple[Publish, list[int]]] = {}
        self.publishes: dict[str, list[Publish]] = {j: [] for j in jobs_by_number.values()}
        self._running = dict.fromkeys(self.publishes, 0.0)  # of a *_current prefix total
        self.bytes_read = 0

    def drain(self) -> int:
        """Read what has arrived; returns how many publishes completed."""
        done = 0
        while raws := self._child.poll("data", 32):
            now = self._clock()
            for raw in raws:
                self.bytes_read += len(raw)
                source, stamp, variables = decode_da00(raw)
                _wid, _src, number, output = source.split("|")
                job = self._jobs.get(number)
                if job is None or output not in self.outputs[job].read:
                    continue
                outputs = self.outputs[job]
                publish, seen = self._open.setdefault(
                    (job, stamp), (Publish(job, -1), [0])
                )
                seen[0] += 1
                signal = variables["signal"]
                if output in outputs.scalars:
                    publish.scalars[output] = float(signal)
                elif output in outputs.spectra:
                    publish.spectra[output] = np.array(signal, np.float64)
                elif output in outputs.images:
                    publish.images[output] = np.array(signal)
                else:
                    publish.arrays[output] = np.array(signal)
                if output == outputs.prefix_total:
                    publish.received_ns = now
                    publish.total = float(np.sum(signal, dtype=np.float64))
                    if output.endswith("_current"):
                        self._running[job] = publish.total = self._running[job] + publish.total
                if seen[0] == len(outputs.read):
                    del self._open[(job, stamp)]
                    items = self.publishes[job]
                    publish.ordinal = len(items)
                    if items and not self._sampled(job, items[-1].ordinal):
                        items[-1].images.clear()
                        for name in [n for n, a in items[-1].arrays.items() if a.nbytes > KEEP_BYTES]:
                            del items[-1].arrays[name]
                    items.append(publish)
                    done += 1
        return done


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def assign_prefixes(publishes: dict[str, list[Publish]], refs, pulses_sent: int) -> None:
    for job, items in publishes.items():
        for publish in items:
            publish.prefix, publish.off_by = refs[job].prefix_of(publish.total, pulses_sent)


def freshness(publishes, due_ns: np.ndarray, t0: int, t1: int, offered: int,
              window_pulses: int, drained_ns: int) -> list[tuple[float, float]]:
    """(due time after t0 in s, freshness in ms) of every (job, publish)
    pair whose last pulse was due in [t0, t1), whenever it arrived:
    receive time minus that due time. Pulses offered that no publish of
    a job holds by ``drained_ns``, the drain's end, count window by
    window with their age then: a stall or a backlog at the window's
    end moves the tail, it does not fall out of it."""
    out = []

    def pair(last_pulse: int, seen_ns: int) -> None:
        due = int(due_ns[last_pulse - 1])
        if t0 <= due < t1:
            out.append(((due - t0) / 1e9, (seen_ns - due) / 1e6))

    for items in publishes.values():
        held = 0
        for publish in items:
            if 0 < publish.prefix <= len(due_ns):
                pair(publish.prefix, publish.received_ns)
                held = max(held, publish.prefix)
        while held < offered:
            held = min(held + window_pulses, offered)
            pair(held, drained_ns)
    return out


def pulses_covered(items: list[Publish], t0: int, t1: int) -> tuple[int, int]:
    """(prefix of the last publish before t0, of the last inside [t0, t1))."""
    before = [p.prefix for p in items if p.received_ns < t0]
    inside = [p.prefix for p in items if t0 <= p.received_ns < t1]
    lo = max(before, default=0)
    return lo, max(inside, default=lo)


#: float32 holds every integer below this; the outputs are float32.
EXACT_BELOW = 2**24
#: The share of an expected bin value of 2**24 or more by which a
#: float32 output may miss it (16 of its last places); see PERF.md.
ROUNDING = 2.0**-20


def bins_off(got: np.ndarray, want: np.ndarray, rounding: float = ROUNDING) -> int:
    """How many bins of ``got`` differ from the integers ``want``: at
    all where the expected value is below 2**24, which float32 holds
    exactly; by more than ``rounding`` of the expected value where it is
    not (a bin gets there after 6 826 pulses of one hot TOA bin; no run does today)."""
    slack = np.where(want < EXACT_BELOW, 0.0, want * rounding)
    return int(np.count_nonzero(np.abs(got.astype(np.float64) - want) > slack))


def bins_outside(got: np.ndarray, want: np.ndarray, rel: float, abs_: float) -> tuple[int, float]:
    """How many bins of the float output ``got`` miss ``want`` by more
    than ``abs_ + rel * |want|``, and the largest miss as a share of
    that room. A bin that is not finite on one side only is a miss."""
    got, want = got.astype(np.float64), np.asarray(want, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        miss = np.abs(got - want)
        share = miss / (abs_ + rel * np.abs(want))
    same = (miss == 0) | (np.isnan(got) & np.isnan(want)) | ((got == want) & np.isinf(want))
    share = np.where(same, 0.0, np.where(np.isnan(share), np.inf, share))
    return int(np.count_nonzero(share > 1.0)), float(share.max(initial=0.0))


def compare(publishes, refs, limits: dict[str, float], outputs: dict[str, Outputs],
            since_ns: int = 0) -> tuple[dict, int]:
    """The numbers compared over every publish of the run, each beside
    its limit, and how many publishes received from ``since_ns`` on
    arrived wrong. What an output should be, which pulses it holds, how
    it is judged and the check it counts into are its job's reference's
    to say (``harness/reference.py``). Where the reference states no
    tolerance, bins are compared exactly as long as float32 can hold
    them (``bins_off``); a stated one is printed beside the check with
    the largest miss seen, as a share of the room it gives. The float32
    totals pass 2**24 within seconds; the prefix total is used only to
    find the publish's pulse prefix, to the nearest pulse.
    """
    wrong = {name: 0.0 if name == "prefix_off_pulses" else 0 for name in limits}
    stated: dict[str, dict] = {}
    compared = {"spectra": 0, "images": 0}
    if any(out.arrays for out in outputs.values()):
        compared["arrays"] = 0
    bad_publishes = 0
    largest = 0.0  # of a bin judged exactly, to show how far float32 still holds it
    for job, items in publishes.items():
        ref = refs[job]
        previous = 0
        for publish in items:
            bad = 0
            wrong["prefix_off_pulses"] = max(wrong["prefix_off_pulses"], publish.off_by)
            bad += publish.off_by > limits["prefix_off_pulses"] or publish.prefix <= previous
            for output_class in compared:
                for output, got in getattr(publish, output_class).items():
                    lo, hi = ref.span(output, previous, publish.prefix)
                    want = ref.expected(output, lo, max(hi, lo))
                    tolerance, check = ref.tolerance(output), ref.check(output)
                    if got.shape != want.shape:
                        miss = want.size
                    elif tolerance is None:
                        miss = bins_off(got, want)
                        largest = max(largest, float(got.max(initial=0.0)))
                    else:
                        rel, abs_, reason = tolerance
                        miss, share = bins_outside(got, want, rel, abs_)
                        entry = stated.setdefault(check, {
                            "tolerance": {"rel": rel, "abs": abs_}, "worst_share": 0.0,
                            "reason": reason,
                        })
                        entry["worst_share"] = max(entry["worst_share"], share)
                    wrong[check] += miss
                    compared[output_class] += 1
                    bad += miss > 0
            if len(publish.spectra) != len(outputs[job].spectra):
                bad += 1
            bad_publishes += bad > 0 and publish.received_ns >= since_ns
            previous = max(previous, publish.prefix)
    numbers = {
        name: {"value": value, "limit": limits[name], **stated.get(name, {})}
        for name, value in wrong.items()
    }
    numbers["largest_bin"] = {"value": largest, "exact_below": EXACT_BELOW}
    numbers["compared"] = compared
    return numbers, bad_publishes
