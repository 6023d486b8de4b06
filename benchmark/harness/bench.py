"""One run of one cell: set-up, the measured window, the drain, the
comparison. ``benchmark/run.py`` is the command; tests drive ``run_cell``
directly on the toy instrument.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import metrics as layer_metrics
from . import prom, reference, results
from .manifest import Cell
from .service import BenchFailure, GeneratorChild, ServiceChild
from .traffic import stream_events

BASE_WINDOW = 14  # pulses: the batchers' 1 s base window on the 14 Hz grid
#: The adaptive batcher relaxes one step after this long without data.
IDLE_RELAX_S = 5.2
#: A run's hard limit is 360 s (1200 s for the first, which compiles).
RUN_DEADLINE_S = 1100.0
DRAIN_S = 60.0
SAMPLED_PUBLISHES = 3  # per job, drawn from the seed, beside the last
WARM_UP_ROUNDS = 24


def log(message: str) -> None:
    print(f"bench: {message}", file=sys.stderr, flush=True)


def streams_with_topics(config: dict) -> list[dict]:
    """The configuration's streams, each with the topic it is sent on:
    its own, or the configuration's ``detector_topic``."""
    return [{"topic": config["detector_topic"], **stream} for stream in config["streams"]]


def events_per_pulse(config: dict, traffic) -> dict[str, float]:
    """job -> events a pulse of its own stream carries; frames, on
    average, where that is a camera's."""
    of_stream = {
        s["name"]: traffic.frames_per_pulse() if s.get("kind") == "camera" else stream_events(s, traffic)
        for s in config["streams"]
    }
    return {job["name"]: of_stream[job["stream"]] for job in config["jobs"]}


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 repo: Path, started: float, allow_cpu: bool) -> None:
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.repo, self.started, self.allow_cpu = repo, started, allow_cpu
        self.config = cell.config
        self.work = Path(tempfile.mkdtemp(prefix="livedata-bench-"))
        self.child: ServiceChild | None = None
        self.generator: GeneratorChild | None = None
        self.sent = 0

    # -- set-up --------------------------------------------------------------
    def start(self) -> None:
        flags = []
        if self.trace:
            flags = [
                "--profile", str(self.work / "profile"),
                "--profile-seconds", str(RUN_DEADLINE_S),
                "--trace-dump", str(self.work / "ticks.json"),
            ]
        deadline = self.started + RUN_DEADLINE_S
        self.child = ServiceChild(
            self.config, self.repo, self.work, flags, deadline, self.allow_cpu
        )
        bench_dir = Path(__file__).resolve().parent.parent
        self.generator = GeneratorChild(
            {
                "seed": self.seed,
                "traffic": self.cell.traffic.__dict__,
                "streams": streams_with_topics(self.config),
                "broker_dir": str(self.child.broker),
                "log_path": str(self.work / "pulses.i64"),
            },
            bench_dir,
            self.work,
        )
        # The reference's tables are made while the service starts; the
        # comparison itself waits until the service has gone.
        self.pools = reference.make_pools(self.config, self.cell.traffic, self.seed)
        self.refs = self.references()
        self.device = self.child.await_device()
        if self.device["platform"] != "tpu" and not self.allow_cpu:
            raise BenchFailure(
                f"the service computes on {self.device}, not a TPU: no accelerator, no result"
            )
        if self.device["count"] < self.cell.chips:
            raise BenchFailure(f"{self.device} holds fewer chips than {self.cell.chips}")
        jobs = self.child.start_jobs(self.config)
        rng = np.random.default_rng([self.seed, 0x73616D70])
        first = 4  # the warm-up's publishes come first
        picks = {
            job: set(first + rng.choice(max(int(self.seconds), SAMPLED_PUBLISHES),
                                        SAMPLED_PUBLISHES, replace=False))
            for job in jobs.values()
        }
        self.outputs = {
            job["name"]: results.Outputs.from_config(self.config, job)
            for job in self.config["jobs"]
        }
        self.reader = results.ResultReader(
            self.child, jobs, time.monotonic_ns, self.outputs, lambda job, n: n in picks[job]
        )

    def references(self, fault: str | None = None) -> dict:
        """job -> its reference; with ``fault``, the reference with that
        guarantee broken: one of ``reference.FAULTS`` in every stream's
        events, or ``<kind>.<name>`` in the jobs of that kind."""
        pools = self.pools
        if fault in reference.FAULTS:
            pools, fault = reference.break_guarantee(pools, fault), None
        return reference.build(self.config, self.cell.traffic, pools, self.cell.kinds, fault)

    def send(self, pulses: int) -> None:
        self.sent = self.generator.ask(f"send {pulses}")["sent"]

    def compiles(self) -> float:
        return prom.value(self.child.scrape(), "livedata_jit_compiles") or 0.0

    def taken_pulses(self) -> int:
        """Pulses the service has taken into batches, by its own counter:
        the longest run of pulses whose messages it has all taken (a
        pool entry's count is the generator's; a camera may skip pulses)."""
        taken = int(prom.value(self.child.scrape(), "livedata_preprocessed_messages") or 0.0)
        per_entry = self.generator.hello["messages_per_entry"]
        turns, rest = divmod(taken, sum(per_entry))
        return turns * len(per_entry) + int(np.searchsorted(np.cumsum(per_entry), rest, "right"))

    def published(self) -> int:
        """Publishes every job has delivered so far."""
        self.reader.drain()
        return min(len(items) for items in self.reader.publishes.values())

    def last_prefix(self) -> int:
        """The pulse prefix every job has published (0 before any)."""
        out = []
        for job, items in self.reader.publishes.items():
            if not items:
                return 0
            if items[-1].prefix < 0:
                results.assign_prefixes({job: items[-1:]}, self.refs, self.sent)
            out.append(items[-1].prefix)
        return min(out)

    def await_publish(self, count: int, needs_pulses: int, patience_s: float | None) -> bool:
        """Wait until every job has delivered ``count`` publishes. With
        ``patience_s``, give up once that long has passed with fewer
        than ``needs_pulses`` taken into batches: the window is wider
        than what was sent. Publishes are counted and batches are sized
        by the service's own counter, not by what the publishes say, so
        that a service that answers wrongly still gets through set-up
        and is judged by the comparison."""
        begun = time.monotonic()

        def probe():
            if self.published() >= count:
                return "published"
            if patience_s is not None and time.monotonic() - begun > patience_s:
                if self.taken_pulses() < needs_pulses:
                    return "unclosed"
            return None

        return self.child.wait_for(f"publish {count} of every job", probe) == "published"

    def mark(self) -> int:
        self.sent = self.generator.ask("mark")["mark"]
        return self.sent

    def warm_up(self) -> None:
        """One window at a time, each awaited, so that a compile stall
        builds no backlog. The first two ticks compile (two program
        variants); the adaptive batcher answers two successive slow
        batches by doubling the width of the window after next, and
        relaxes a step per idle spell. So after two compiling publishes in
        a row the warm-up idles two spells, and it ends when two
        successive base windows have published with no new compile."""
        self.send(BASE_WINDOW + 1)  # a window and the pulse that closes it
        steady, slow, rounds = 0, 0, 0
        count, taken, compiles = 0, 0, self.compiles()
        while steady < 2:
            rounds += 1
            if rounds > WARM_UP_ROUNDS:
                raise BenchFailure(f"no two steady base windows in {WARM_UP_ROUNDS} warm-up rounds")
            begun = time.monotonic()
            if not self.await_publish(count + 1, taken + 1, 1.5 if count else None):
                log(f"warm-up: the window after pulse {taken} is wider than base; idling")
                time.sleep(IDLE_RELAX_S)
                self.send(BASE_WINDOW)
                continue
            took = time.monotonic() - begun
            now_taken, now_compiles = self.taken_pulses(), self.compiles()
            width = now_taken - taken
            steady = steady + 1 if width == BASE_WINDOW and now_compiles == compiles else 0
            # a tick that compiles (or loads from the cache) is the slow one; a
            # steady tick near the batcher's 0.8 is no reason to idle. ``took``
            # holds the sending too (~0.1 s), so the line is drawn at 0.95:
            # where the batcher escalated after all, the next window shows it
            slow = slow + 1 if took > 0.95 * width / BASE_WINDOW and now_compiles > compiles else 0
            log(f"warm-up: pulses {taken}..{now_taken} published after {took:.2f} s, "
                f"{now_compiles - compiles:.0f} compiles")
            if slow >= 2 or width != BASE_WINDOW:
                time.sleep(2 * IDLE_RELAX_S)
                slow = 0
            count, taken, compiles = self.published(), now_taken, now_compiles
            if steady < 2:
                self.send(BASE_WINDOW)

    # -- the window and after ------------------------------------------------
    def measure(self) -> dict:
        self.warm_up()
        scrape_start = self.child.scrape()
        run_from = self.mark()
        t0 = time.monotonic_ns()
        self.generator.tell(f"run {t0}")
        setup_s = time.monotonic() - self.started
        log(f"window opens after {setup_s:.1f} s of set-up")
        t1 = t0 + int(self.seconds * 1e9)
        while time.monotonic_ns() < t1:
            self.reader.drain()
            time.sleep(0.002)
            if self.child.proc.poll() is not None:
                raise BenchFailure(f"service exited rc={self.child.proc.returncode} in the window")
        t1 = time.monotonic_ns()
        # Drain: the generator keeps running until every pulse due in the
        # window is published (a window closes on a later pulse), however
        # late it was sent. The service is left alone meanwhile, as in the
        # window: the client only reads, and a scrape (which the service
        # answers between its own work) comes once a second.
        offered = run_from + self.cell.traffic.pulses_due(t1 - t0)
        self.mark()
        drain_until = time.monotonic() + DRAIN_S
        taken_at = None  # publishes delivered when the last offered pulse was taken
        next_scrape = time.monotonic() + 1.0
        while time.monotonic() < drain_until:
            delivered = self.published()
            if self.last_prefix() >= offered:
                break
            if time.monotonic() >= next_scrape:
                next_scrape += 1.0
                self.mark()
                if taken_at is None and self.taken_pulses() >= offered:
                    taken_at = delivered
                elif taken_at is not None and delivered > taken_at + 1:
                    break  # what was offered is out, and says something else
            time.sleep(0.002)
        drained_ns = time.monotonic_ns()
        # the scrape that closes the layer metrics' deltas: per-batch ratios,
        # taken once the window's last batch is out so that it delays none
        scrape_end = self.child.scrape()
        stopped = self.generator.ask("stop")
        self.sent = stopped["sent"]
        final = self.child.scrape()
        self.generator.close()
        self.generator = None
        rc = self.child.stop()
        if rc != 0:
            raise BenchFailure(f"the service exited with code {rc}")
        return {
            "t0": t0, "t1": t1, "drained_ns": drained_ns, "setup_s": setup_s,
            "scrape_start": scrape_start,
            "scrape_end": scrape_end, "scrape_final": final, "bytes_sent": stopped["bytes"],
        }

    def close(self) -> None:
        if self.generator is not None:
            self.generator.close()
        if self.child is not None and self.child.proc.poll() is None:
            self.child.stop()
        shutil.rmtree(self.work, ignore_errors=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, repo: Path,
             started: float, allow_cpu: bool = False, controls=()):
    """Returns (result line as a dict, lines for stderr). Raises
    BenchFailure where no result can be given. ``controls`` names faults
    of ``reference.break_guarantee``: each is put in the program's place
    and its readings go under the line's ``controls`` key; so is each
    ``<kind>.<name>`` of a reference kind's own ``faults()``."""
    run = Run(cell, seed, seconds, trace, repo, started, allow_cpu)
    try:
        run.start()
        window = run.measure()
        pulse_log = np.fromfile(run.work / "pulses.i64", np.int64).reshape(-1, 3)
        trace_events = None
        if trace:
            from . import trace_reduce

            # the window is on CLOCK_MONOTONIC, the trace on the epoch
            window["to_epoch_ns"] = time.time_ns() - time.monotonic_ns()
            trace_events = trace_reduce.load(
                run.work / "profile", run.work / "ticks.json", window["to_epoch_ns"]
            )
        return finish(run, window, pulse_log, trace_events, controls)
    except BenchFailure:
        if run.child is not None:
            log(f"tail of the service log:\n{run.child.log_tail()}")
        raise
    finally:
        run.close()


def finish(run: Run, window: dict, pulse_log, trace_events, controls=()):
    cell, reader = run.cell, run.reader
    t0, t1 = window["t0"], window["t1"]
    window_s = (t1 - t0) / 1e9
    sent = len(pulse_log)
    due_ns = pulse_log[:, 1]
    results.assign_prefixes(reader.publishes, run.refs, sent)
    in_window = (due_ns >= t0) & (due_ns < t1)  # a warm-up pulse is due when it is sent
    first_pulse = int(np.argmax(in_window)) if in_window.any() else sent
    offered = first_pulse + int(in_window.sum())  # pulses due before the window closed

    # End to end: every pair due in the window, whenever it arrived.
    pairs = results.freshness(
        reader.publishes, due_ns, t0, t1, offered, BASE_WINDOW, window["drained_ns"]
    )
    fresh = [ms for _, ms in pairs]
    values = {"setup_s": window["setup_s"]}
    if fresh:
        values["freshness_p50_ms"] = results.percentile(fresh, 50)
        values["freshness_p95_ms"] = results.percentile(fresh, 95)

    # Attempted and failed, in base windows per job.
    jobs = len(reader.publishes)
    attempted = jobs * ((offered - first_pulse) // BASE_WINDOW)

    # The comparison, after the window and after the service has gone.
    def judged(refs):
        """(numbers beside their limits, publishes wrong, correct)."""
        results.assign_prefixes(reader.publishes, refs, sent)
        numbers, wrong = results.compare(reader.publishes, refs, cell.limits, run.outputs, t0)
        uncovered = sum(
            -(-max(0, offered - (items[-1].prefix if items else 0)) // BASE_WINDOW)
            for items in reader.publishes.values()
        )
        within = all(e["value"] <= e["limit"] for e in numbers.values() if "limit" in e)
        return numbers, uncovered + wrong, uncovered + wrong == 0 and within

    control_readings = {}
    for fault in controls:
        numbers, failed, correct = judged(run.references(fault))
        control_readings[fault] = {
            "correct": correct, "failed": failed,
            **{k: e["value"] for k, e in numbers.items() if "limit" in e},
        }
    began = time.monotonic()
    numbers, failed, correct = judged(run.refs)
    numbers["compare_s"] = round(time.monotonic() - began, 3)

    unit = {m["name"]: m["unit"] for m in cell.end_to_end}
    if run.trace:
        ctx = {
            "scrape_start": window["scrape_start"], "scrape_end": window["scrape_end"],
            "window_s": window_s, "pulse_log": pulse_log[in_window],
        }
        breakdown, device_extra = None, {}
        if trace_events is not None:
            from . import roofline, trace_reduce

            to_epoch = window["to_epoch_ns"]
            reduced = trace_reduce.reduce(trace_events, t0 + to_epoch, t1 + to_epoch)
            if reduced:
                by_job = {
                    job: results.pulses_covered(items, t0, t1)
                    for job, items in reader.publishes.items()
                }
                per_pulse = events_per_pulse(cell.config, cell.traffic)
                least_s = roofline.least_seconds(
                    cell.config,
                    {j: (c[1] - c[0]) * per_pulse[j] for j, c in by_job.items()},
                    {j: sum(t0 <= p.received_ns < t1 for p in items)
                     for j, items in reader.publishes.items()},
                    run.device["kind"],
                    cell.kinds,
                )
                reduced["tick_roofline_pct"] = 100.0 * least_s / reduced["busy_s"]
                breakdown = reduced.pop("breakdown")
                device_extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
            ctx["trace"] = reduced
        metrics = layer_metrics.evaluate(cell.per_layer, ctx)
    else:
        metrics = {
            name: {"value": float(values[name]), "unit": unit[name]}
            for name in unit if name in values
        }
        breakdown, device_extra = None, {}
    peaks = [
        v for name, labels, v in window["scrape_final"]
        if name == "livedata_hbm_bytes" and labels.get("kind") == "peak_bytes_in_use"
    ]
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": {**run.device, "memory_peak_bytes": int(max(peaks, default=0)), **device_extra},
    }
    if breakdown:
        line["breakdown"] = breakdown
    line["seed"] = run.seed
    line["workload"] = cell.name
    line["pulses"] = {"sent": sent, "bytes": window["bytes_sent"], "first_in_window": first_pulse,
                      "offered": offered, "window_s": window_s,
                      "publishes": {j: len(i) for j, i in reader.publishes.items()}}
    if in_window.any():  # where a stall sat: in the generator, or after it
        late_ns = pulse_log[in_window, 2] - pulse_log[in_window, 1]
        line["pulses"]["generator_late_max_ms"] = float(late_ns.max()) / 1e6
    if pairs:  # every pair: when its last pulse was due (s after t0), and its freshness
        line["pulses"]["freshness_max_ms"] = max(fresh)
        line["pulses"]["pairs"] = [[round(at, 3), round(ms, 3)] for at, ms in sorted(pairs)]
    if control_readings:
        line["controls"] = control_readings
    line["checks"] = numbers
    report = [f"check {name}: {json.dumps(entry)}" for name, entry in numbers.items()]
    report.append(f"check failed_publishes: {failed} of {attempted} (limit 0)")
    return line, report
