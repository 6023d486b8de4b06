#!/usr/bin/env python3
"""Chip smoke: the served path, end to end, on the accelerator.

Drives the services a user would start — ``python -m
esslivedata_tpu.services.detector_data`` and ``...monitor_data`` — over
the file broker at the size the instrument package declares (NMX: one
1280 x 1280 panel x 100 TOA bins, 163.84 M float32 bins per accumulator)
and checks every published result EXACTLY against a plain numpy
reference written here (``np.bincount`` over the same seeded events;
nothing from ``esslivedata_tpu.ops`` is imported).

Phases, one child process on the chip at a time:

1. ``detector``       default flags; K = 2 ``panel_xy`` jobs on one
                      stream (``histogram_method`` scatter and pallas2d).
2. ``detector-fast``  the same with ``--pipeline --batch-decode
                      --warmup`` (pipelined executor, batch decode
                      plane, AOT ``Lowered.compile`` warm-up), plus a
                      third, pixel-weighted job: the one configuration
                      that stages raw ids and so runs the decode
                      prologue's Pallas kernel on the device.
3. ``monitor``        one ``monitor_data/histogram`` job (``method=auto``
                      -> the 1-D Pallas one-hot kernel on TPU).

This process never imports jax: the child owns the chip, and the device
it reports (``livedata_device_info`` on its ``/metrics``) is what the
last line of stdout names. Any failed phase, a child that exits
non-zero, or a device that is not a TPU exits non-zero and prints no
result line. ``--instrument dummy --allow-cpu`` runs the same plumbing
at toy size for the tier-1 test and proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
import uuid
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

import numpy as np

REPO = Path(__file__).resolve().parent
SRC = REPO / "src"
sys.path.insert(0, str(SRC))

PULSE_PERIOD_NS = 1e9 / 14
WINDOW_PULSES = 14  # the batchers' 1 s base window
MAX_WINDOW_SCALE = 8  # AdaptiveMessageBatcher's escalation cap
TOA_BINS = 100  # DetectorViewParams / MonitorParams default
TOA_EDGES = np.linspace(0.0, PULSE_PERIOD_NS, TOA_BINS + 1)
#: Detector pulses per phase: >= 4 windows even if the batcher escalates
#: once, and at NMX's 262 144 events per pulse the 64 x 245 760 in-range
#: events stay below 2**24, so every count is exact in float32.
N_PULSES = 64
#: 1/OUT_OF_RANGE_SHARE of each pulse's events carry an out-of-range
#: pixel id, and as many an out-of-range TOA; the kernels must drop both.
OUT_OF_RANGE_SHARE = 32


@dataclass(frozen=True)
class Deployment:
    """The wire-level facts of one instrument package (mirrors
    config/instruments/<name>/specs.py, which cannot be imported here
    without importing jax)."""

    detector_topic: str
    detector_source: str  # ev44 source_name on the wire
    detector_job_source: str  # the job's source_name
    detector_workflow: tuple[str, str]  # (namespace, name)
    first_id: int
    shape: tuple[int, int]
    monitor_topic: str
    monitor_source: str
    monitor_job_source: str
    events_per_pulse: int
    monitor_events_per_pulse: int
    #: bytes_in_use floor while both detector jobs are live (None: the
    #: backend reports no memory statistics worth a bound).
    min_hbm_bytes: float | None


DEPLOYMENTS = {
    # 2 jobs x (window + cumulative) x 1 638 400 px x 100 bins x 4 B
    # = 2.62 GB of state; 262 144 events/pulse is the operating point of
    # the repo's one chip latency reading.
    "nmx": Deployment(
        detector_topic="nmx_detector",
        detector_source="nmx_detector_panel_0",
        detector_job_source="detector_panel_0",
        detector_workflow=("detector_view", "panel_xy"),
        first_id=1,
        shape=(1280, 1280),
        monitor_topic="nmx_monitor",
        monitor_source="nmx_mon_1",
        monitor_job_source="monitor1",
        events_per_pulse=262_144,
        monitor_events_per_pulse=32_768,
        min_hbm_bytes=2.6e9,
    ),
    "dummy": Deployment(
        detector_topic="dummy_detector",
        detector_source="panel_a",
        detector_job_source="panel_0",
        detector_workflow=("detector_view", "panel_view"),
        first_id=1,
        shape=(64, 64),
        monitor_topic="dummy_monitor",
        monitor_source="mon_src",
        monitor_job_source="monitor_1",
        events_per_pulse=4_096,
        monitor_events_per_pulse=1_024,
        min_hbm_bytes=None,
    ),
}


class SmokeFailure(Exception):
    """A phase did not meet its contract."""


def child_env(environ=None) -> dict[str, str]:
    """The service child's environment: the caller's, minus every CPU
    pin. The child must land on whatever jax finds — the smoke then
    reads the device from the child and fails if it is not a TPU."""
    env = dict(os.environ if environ is None else environ)
    if env.get("JAX_PLATFORMS", "").lower() == "cpu":
        del env["JAX_PLATFORMS"]
    env.pop("LIVEDATA_FORCE_CPU", None)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    ).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    return env


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


# -- seeded traffic and the numpy reference ----------------------------------


def make_toa(rng, n: int) -> np.ndarray:
    """One pulse of TOA (int32 ns): inside the middle half of a uniformly
    drawn bin, so no event sits where float32 and float64 binning could
    disagree; the last ``n // OUT_OF_RANGE_SHARE`` are out of range
    (negative, past the frame)."""
    width = PULSE_PERIOD_NS / TOA_BINS
    toa = (rng.integers(0, TOA_BINS, n) + rng.uniform(0.25, 0.75, n)) * width
    bad = n // OUT_OF_RANGE_SHARE
    toa[n - bad :] = rng.choice([-5.0e5, PULSE_PERIOD_NS + 1.0e4, 9.0e7], bad)
    return toa.astype(np.int32)


def make_pulse(rng, n: int, first_id: int, n_pix: int):
    """One pulse of ev44 payload: ids uniform over the panel, the first
    ``n // OUT_OF_RANGE_SHARE`` out of range (zero, negative, just below,
    just above, a panel further), and ``make_toa``'s TOA."""
    ids = rng.integers(first_id, first_id + n_pix, n, dtype=np.int64)
    bad = n // OUT_OF_RANGE_SHARE
    ids[:bad] = rng.choice(
        [0, -7, first_id - 1, first_id + n_pix, first_id + 2 * n_pix], bad
    )
    return ids.astype(np.int32), make_toa(rng, n)


@dataclass
class Reference:
    """Plain accumulation of the same events: the expected cumulative
    outputs. All counts are integers below 2**24, exact in float32."""

    n_pix: int
    image: np.ndarray = field(init=False)
    spectrum: np.ndarray = field(init=False)
    counts: int = 0

    def __post_init__(self) -> None:
        self.image = np.zeros(self.n_pix, np.int64)
        self.spectrum = np.zeros(TOA_BINS, np.int64)

    def add(self, ids: np.ndarray, toa: np.ndarray, first_id: int) -> None:
        pix = ids.astype(np.int64) - first_id
        ok = (pix >= 0) & (pix < self.n_pix) & (toa >= 0) & (toa < PULSE_PERIOD_NS)
        self.image += np.bincount(pix[ok], minlength=self.n_pix)
        self.spectrum += np.histogram(toa[ok], bins=TOA_EDGES)[0]
        self.counts += int(ok.sum())


# -- one service child --------------------------------------------------------


class ServiceChild:
    """One ``python -m esslivedata_tpu.services.<service>`` process over
    a private file broker, plus the client side of its topics."""

    def __init__(
        self,
        service: str,
        instrument: str,
        flags: list[str],
        work: Path,
        log_path: Path,
        timeout_s: float,
    ) -> None:
        from esslivedata_tpu.kafka.file_broker import (
            FileBrokerConsumer,
            FileBrokerProducer,
            ensure_topics,
        )

        self.instrument = instrument
        self.flags = flags
        self.deadline = time.monotonic() + timeout_s
        self.broker = work / "broker"
        self.topic = {
            name: f"{instrument}_livedata_{name}"
            for name in ("data", "status", "commands", "responses")
        }
        ensure_topics(self.broker, self.topic.values())
        self.producer = FileBrokerProducer(self.broker)
        self._consumers = {}
        for name in ("data", "status", "responses"):
            consumer = FileBrokerConsumer(self.broker)
            consumer.assign([SimpleNamespace(topic=self.topic[name], offset=0)])
            self._consumers[name] = consumer
        self.port = free_port()
        self.log_path = log_path
        self._log = open(log_path, "wb")
        env = child_env()
        # Geometry artifacts synthesized by an earlier checkout must not
        # be trusted: each child builds its own inside the work dir.
        env["LIVEDATA_DATA_DIR"] = str(work / "geometry")
        (work / "geometry").mkdir(parents=True, exist_ok=True)
        self.proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                f"esslivedata_tpu.services.{service}",
                "--instrument",
                instrument,
                "--broker-dir",
                str(self.broker),
                "--metrics-port",
                str(self.port),
                *flags,
            ],
            env=env,
            stdout=self._log,
            stderr=subprocess.STDOUT,
        )

    # -- waiting -----------------------------------------------------------
    def wait_for(self, what: str, probe, interval_s: float = 0.25):
        """Poll ``probe`` until truthy; fails when the child dies or the
        phase deadline passes."""
        while True:
            value = probe()
            if value:
                return value
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"service exited rc={self.proc.returncode} while "
                    f"waiting for {what}"
                )
            if time.monotonic() > self.deadline:
                raise SmokeFailure(f"timed out waiting for {what}")
            time.sleep(interval_s)

    def poll(self, name: str) -> list[bytes]:
        return [m.value() for m in self._consumers[name].consume(64, 0.0)]

    # -- /metrics ----------------------------------------------------------
    def scrape(self) -> dict:
        from esslivedata_tpu.telemetry import parse_prometheus_text

        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.port}/metrics", timeout=30
        ) as response:
            return parse_prometheus_text(response.read().decode())

    def try_scrape(self):
        try:
            return self.scrape()
        except OSError:
            return None

    # -- lifecycle ---------------------------------------------------------
    def stop(self) -> int:
        """SIGTERM, wait, reap. Returns the exit code (kills on a hang)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode

    def log_tail(self, n_bytes: int = 6000) -> str:
        try:
            data = self.log_path.read_bytes()
        except OSError:
            return "<no log>"
        return data[-n_bytes:].decode(errors="replace")


#: Registered when durability/warmup.py is imported, i.e. only under
#: --warmup; absent means nothing was warmed and nothing failed.
WARMUP_FAMILIES = (
    "livedata_durability_warmup_compiles_total",
    "livedata_durability_warmup_failures_total",
    "livedata_durability_warmup_seconds",
)


def metric(parsed: dict, family: str, suffix: str = "", **labels) -> float:
    """Sum of the family's samples whose name ends in ``suffix`` and
    whose labels include ``labels`` (``_bucket`` series excluded)."""
    if family not in parsed:
        if family in WARMUP_FAMILIES:
            return 0.0
        raise SmokeFailure(f"/metrics has no family {family}")
    return sum(
        value
        for name, sample_labels, value in parsed[family].samples
        if name.endswith(suffix)
        and not name.endswith("_bucket")
        and all(sample_labels.get(k) == v for k, v in labels.items())
    )


def device_of(parsed: dict) -> dict:
    samples = parsed["livedata_device_info"].samples
    if len(samples) != 1:
        raise SmokeFailure(f"livedata_device_info has {len(samples)} samples")
    labels = samples[0][1]
    return {
        "platform": labels["platform"],
        "kind": labels["device_kind"],
        "count": int(labels["count"]),
    }


def compile_report(parsed: dict) -> dict:
    """Compile counts and seconds as the service's own instruments saw
    them: hot-path jit-cache misses (trace + compile + first execute)
    and, under --warmup, the AOT warm-up's off-path compiles."""
    return {
        "hot_path_compiles": metric(parsed, "livedata_jit_compiles_total"),
        "hot_path_compiles_by_site_trigger": {
            f"{labels['site']}/{labels['trigger']}": value
            for _name, labels, value in parsed["livedata_jit_compiles_total"].samples
        },
        "hot_path_compile_s": round(
            metric(parsed, "livedata_jit_compile_seconds", "_sum"), 3
        ),
        "warmup_compiles": metric(
            parsed, "livedata_durability_warmup_compiles_total"
        ),
        "warmup_s": round(
            metric(parsed, "livedata_durability_warmup_seconds", "_sum"), 3
        ),
    }


# -- the client side: commands, traffic, results, checks ----------------------


def start_job(child: ServiceChild, namespace: str, name: str, source: str, params: dict):
    from esslivedata_tpu.config.workflow_spec import (
        JobId,
        WorkflowConfig,
        WorkflowId,
    )

    config = WorkflowConfig(
        identifier=WorkflowId(
            instrument=child.instrument, namespace=namespace, name=name
        ),
        job_id=JobId(source_name=source, job_number=uuid.uuid4()),
        params=params,
    )
    child.producer.produce(
        child.topic["commands"],
        json.dumps(
            {"kind": "start_job", "config": config.model_dump(mode="json")}
        ).encode(),
    )
    return config


def await_acks(child: ServiceChild, configs) -> None:
    wanted = {str(c.job_id.job_number) for c in configs}

    def probe():
        for raw in child.poll("responses"):
            doc = json.loads(raw)
            if doc.get("job_number") in wanted:
                if doc.get("status") != "ack":
                    raise SmokeFailure(f"start_job refused: {doc}")
                wanted.discard(doc["job_number"])
        return not wanted

    child.wait_for("start_job acknowledgements", probe)


class PulseFeed:
    """ev44 messages on the 14 Hz grid, in the recent past.

    A window closes when data time moves past it, so after the data
    comes a *closing pulse*: nothing but out-of-range events, far enough
    ahead to close even a fully escalated window.
    """

    #: A closing pulse lands beyond the widest window the batcher can
    #: have escalated to.
    CLOSING_STRIDE = MAX_WINDOW_SCALE * WINDOW_PULSES + 1

    def __init__(self, child, topic, source, n_pulses, make_events, closing_events):
        from esslivedata_tpu.core.timestamp import Timestamp

        self._child, self._topic, self._source = child, topic, source
        self._n_pulses = n_pulses
        self._make_events, self._closing_events = make_events, closing_events
        span = n_pulses + 2 * self.CLOSING_STRIDE
        self._base = Timestamp.now().pulse_index() - span
        self._next = 0

    def _produce(self, pulse: int, ids, toa) -> None:
        from esslivedata_tpu.core.timestamp import Timestamp
        from esslivedata_tpu.kafka import wire

        self._child.producer.produce(
            self._topic,
            wire.encode_ev44(
                self._source,
                pulse,
                np.array([Timestamp.from_pulse_index(self._base + pulse).ns]),
                np.array([0]),
                toa,
                pixel_id=ids,
            ),
        )

    def send(self, n: int) -> None:
        stop = min(self._next + n, self._n_pulses)
        for pulse in range(self._next, stop):
            self._produce(pulse, *self._make_events())
        self._next = stop

    def finish(self) -> None:
        self.send(self._n_pulses)
        self._produce(
            self._n_pulses + self.CLOSING_STRIDE, *self._closing_events
        )


class ResultReader:
    """Follows the data topic, keeping the latest array per
    (job_number, output) and counting publishes per job."""

    def __init__(self, child: ServiceChild) -> None:
        self._child = child
        self.latest: dict[tuple[str, str], np.ndarray] = {}
        self.publishes: dict[str, int] = {}

    def drain(self) -> None:
        from esslivedata_tpu.kafka import wire

        while raws := self._child.poll("data"):
            for raw in raws:
                message = wire.decode_da00(raw)
                _wid, _source, job_number, output = message.source_name.split("|")
                signal_var = next(
                    (v for v in message.variables if v.name == "signal"),
                    message.variables[0],
                )
                self.latest[(job_number, output)] = signal_var.data
                if output == "counts_cumulative":
                    self.publishes[job_number] = (
                        self.publishes.get(job_number, 0) + 1
                    )

    def total(self, job_number: str, output: str = "counts_cumulative"):
        value = self.latest.get((job_number, output))
        return None if value is None else float(np.asarray(value).sum())


def await_total(
    child, reader, configs, expected: int, output="counts_cumulative"
):
    """Wait until every job's latest ``output`` sums to ``expected``."""
    jobs = [str(c.job_id.job_number) for c in configs]
    last_seen = None

    def probe():
        nonlocal last_seen
        reader.drain()
        totals = [reader.total(job, output) for job in jobs]
        if any(t is not None and t > expected for t in totals):
            raise SmokeFailure(
                f"{output} overshot the reference {expected}: {totals}"
            )
        last_seen = totals
        return all(t == expected for t in totals)

    try:
        child.wait_for(f"{output} == {expected} on {len(jobs)} job(s)", probe)
    except SmokeFailure as err:
        raise SmokeFailure(f"{err}; last seen {last_seen}") from None


def check_equal(label: str, got, want) -> None:
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.shape != want.shape:
        raise SmokeFailure(f"{label}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise SmokeFailure(f"{label}: non-finite values")
    bad = np.flatnonzero(got != want)
    if bad.size:
        i = int(bad[0])
        raise SmokeFailure(
            f"{label}: {bad.size} of {got.size} bins differ from the numpy "
            f"reference (first at {i}: got {got[i]}, want {want[i]})"
        )


def await_clean_heartbeats(child: ServiceChild, configs) -> int:
    """Every job heartbeat so far is free of errors and warnings, and
    each job has sent at least one since its data flowed (a job turns
    ``active`` on its first data)."""
    from esslivedata_tpu.kafka import wire

    waiting = {str(c.job_id.job_number) for c in configs}
    seen = 0

    def probe():
        nonlocal seen
        for raw in child.poll("status"):
            doc = json.loads(wire.decode_x5f2(raw).status_json)
            message = doc.get("message")
            if not isinstance(message, dict) or message.get("message_type") != "job":
                continue
            status = message["status"]
            seen += 1
            if status.get("state") in ("error", "warning") or status.get("message"):
                raise SmokeFailure(f"job heartbeat not clean: {status}")
            if status.get("state") == "active":
                waiting.discard(status.get("job_number"))
        return not waiting

    child.wait_for("a job heartbeat after the data", probe)
    return seen


def check_counters(child, n_publishes, step_executes_mark, hbm_floor):
    """The tick program served, nothing was lost, the state is on the
    device. Returns (scrape, report)."""
    parsed = child.scrape()
    ticks = metric(parsed, "livedata_publish_events", kind="tick_publishes")
    if ticks < n_publishes:
        raise SmokeFailure(
            f"tick_publishes {ticks:.0f} < {n_publishes} publishes seen: "
            "windows were served by the fallback ladder, not the tick program"
        )
    steps = metric(parsed, "livedata_publish_events", kind="step_executes")
    if steps > step_executes_mark:
        raise SmokeFailure(
            f"step_executes grew {step_executes_mark:.0f} -> {steps:.0f} "
            "after the first window: separate step dispatches are running"
        )
    for family in (
        "livedata_state_lost",
        "livedata_decode_errors_total",
        "livedata_durability_warmup_failures_total",
    ):
        if (value := metric(parsed, family)) != 0:
            raise SmokeFailure(f"{family} = {value:.0f}, expected 0")
    in_use = max(
        (
            value
            for _n, labels, value in parsed["livedata_hbm_bytes"].samples
            if labels.get("kind") == "bytes_in_use"
        ),
        default=None,
    )
    if hbm_floor is not None and (in_use is None or in_use < hbm_floor):
        raise SmokeFailure(
            f"livedata_hbm_bytes bytes_in_use = {in_use} < {hbm_floor:.3g}: "
            "the state is not resident on the device"
        )
    report = {
        "tick_publishes": ticks,
        "step_executes": steps,
        "hbm_bytes_in_use": in_use,
    }
    return parsed, report


def await_device(child: ServiceChild, allow_cpu: bool) -> dict:
    """The service is up (first heartbeat: its consumers are assigned)
    and its /metrics names the device."""
    child.wait_for("the first service heartbeat", lambda: child.poll("status"))
    parsed = child.wait_for("/metrics", child.try_scrape)
    device = device_of(parsed)
    if device["platform"] != "tpu" and not allow_cpu:
        raise SmokeFailure(
            f"the service computes on {device}, not a TPU: jax found no "
            "accelerator (this smoke has no CPU mode; the tier-1 plumbing "
            "test passes --instrument dummy --allow-cpu)"
        )
    return device


# -- phases --------------------------------------------------------------------


def run_detector_phase(child, dep, seed, allow_cpu, wrong_reference):
    device = await_device(child, allow_cpu)
    n_pix = dep.shape[0] * dep.shape[1]
    rng = np.random.default_rng(seed)
    reference = Reference(n_pix)

    def make_events():
        ids, toa = make_pulse(rng, dep.events_per_pulse, dep.first_id, n_pix)
        reference.add(ids, toa, dep.first_id)
        return ids, toa

    warmup = "--warmup" in child.flags
    feed = PulseFeed(
        child,
        dep.detector_topic,
        dep.detector_source,
        N_PULSES + (WINDOW_PULSES if warmup else 0),
        make_events,
        closing_events=(np.zeros(16, np.int32), np.full(16, 1000, np.int32)),
    )
    if warmup:
        # The AOT warm-up compiles against the batch shape a stream has
        # been carrying, so a job committed before any data has nothing
        # to warm. One window of pre-roll gives the stream its shape; it
        # is consumed with no job subscribed and is not in the reference
        # (the pulse that closes it opens the jobs' first window and is).
        feed.send(WINDOW_PULSES)
        reference = Reference(n_pix)
        feed.send(1)
        child.wait_for(
            "the pre-roll window",
            lambda: metric(child.scrape(), "livedata_preprocessed_messages")
            >= WINDOW_PULSES,
        )
    methods = {"scatter": {}, "pallas2d": {"histogram_method": "pallas2d"}}
    if "--batch-decode" in child.flags:
        # Both jobs above flatten on the host, which sanitizes pixel ids
        # there. The batch decode plane's device prologue (the
        # ops/decode_prologue.py Pallas kernel) only runs where raw
        # (pixel_id, toa) is staged, i.e. for a configuration that cannot
        # flatten on the host: per-pixel weights. On this logical
        # projection every weight is 1, so the same reference holds.
        methods["weighted"] = {"pixel_weighting": True}
    namespace, name = dep.detector_workflow
    configs = [
        start_job(child, namespace, name, dep.detector_job_source, params)
        for params in methods.values()
    ]
    await_acks(child, configs)
    if warmup:
        # One tick group per job (the methods do not fuse) x two program
        # variants (first tick with the static channel, steady without).
        def warmed():
            parsed = child.scrape()
            if metric(parsed, "livedata_durability_warmup_failures_total"):
                raise SmokeFailure("an AOT warm-up request failed")
            return metric(
                parsed, "livedata_durability_warmup_compiles_total"
            ) >= 2 * len(configs)

        child.wait_for("the AOT warm-up of every tick group", warmed)
    reader = ResultReader(child)
    jobs = [str(c.job_id.job_number) for c in configs]
    # The first window on its own (the next window's first pulse closes
    # it); once it has published, step_executes is marked and must not
    # move again.
    t0 = time.monotonic()
    feed.send(WINDOW_PULSES + (0 if warmup else 1))
    child.wait_for(
        "the first window's publish",
        lambda: reader.drain()
        or all(reader.publishes.get(job, 0) >= 1 for job in jobs),
    )
    first_window_s = time.monotonic() - t0
    step_executes_mark = metric(
        child.scrape(), "livedata_publish_events", kind="step_executes"
    )
    feed.finish()
    if wrong_reference:
        reference.image[0] += 1
    await_total(child, reader, configs, reference.counts)
    for job, method in zip(jobs, methods, strict=True):
        for output, want in (
            ("image_cumulative", reference.image),
            ("spectrum_cumulative", reference.spectrum),
            ("counts_cumulative", reference.counts),
        ):
            check_equal(f"{method} {output}", reader.latest[(job, output)], want)
            # ... and the jobs equal each other, array for array.
            check_equal(
                f"{method} vs scatter {output}",
                reader.latest[(job, output)],
                reader.latest[(jobs[0], output)],
            )
    n_publishes = min(reader.publishes[job] for job in jobs)
    parsed, counters = check_counters(
        child,
        n_publishes,
        step_executes_mark,
        # The CPU client reports no memory statistics (--allow-cpu).
        dep.min_hbm_bytes if device["platform"] == "tpu" else None,
    )
    if n_publishes < 4:
        raise SmokeFailure(f"only {n_publishes} windows published, need >= 4")
    heartbeats = await_clean_heartbeats(child, configs)
    return device, {
        "events": N_PULSES * dep.events_per_pulse,
        "events_in_range": reference.counts,
        "windows_published": n_publishes,
        "first_window_s": round(first_window_s, 2),
        "job_heartbeats": heartbeats,
        **counters,
        **compile_report(parsed),
    }


def run_monitor_phase(child, dep, seed, allow_cpu, wrong_reference):
    device = await_device(child, allow_cpu)
    config = start_job(
        child, "monitor_data", "histogram", dep.monitor_job_source, {}
    )
    await_acks(child, [config])
    rng = np.random.default_rng(seed + 1)
    expected = np.zeros(TOA_BINS, np.int64)
    n = dep.monitor_events_per_pulse

    def make_events():
        toa = make_toa(rng, n)
        expected[:] += np.histogram(toa, bins=TOA_EDGES)[0]
        return None, toa  # monitor ev44 carries no pixel ids

    reader = ResultReader(child)
    n_pulses = 4 * WINDOW_PULSES
    PulseFeed(
        child,
        dep.monitor_topic,
        dep.monitor_source,
        n_pulses,
        make_events,
        closing_events=(None, np.full(16, int(9.0e7), np.int32)),
    ).finish()
    if wrong_reference:
        expected[0] += 1
    await_total(child, reader, [config], int(expected.sum()), output="cumulative")
    job = str(config.job_id.job_number)
    check_equal("monitor cumulative", reader.latest[(job, "cumulative")], expected)
    n_publishes = reader.publishes.get(job, 0)
    parsed, counters = check_counters(
        child, n_publishes, float("inf"), None
    )
    if n_publishes < 4:
        raise SmokeFailure(f"only {n_publishes} windows published, need >= 4")
    heartbeats = await_clean_heartbeats(child, [config])
    return device, {
        "events": int(n_pulses * n),
        "events_in_range": int(expected.sum()),
        "windows_published": n_publishes,
        "job_heartbeats": heartbeats,
        **counters,
        **compile_report(parsed),
    }


PHASES = (
    ("detector", "detector_data", [], run_detector_phase),
    (
        "detector-fast",
        "detector_data",
        ["--pipeline", "--batch-decode", "--warmup"],
        run_detector_phase,
    ),
    ("monitor", "monitor_data", [], run_monitor_phase),
)


def versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0]}
    for package in ("jax", "jaxlib", "libtpu"):
        try:
            out[package] = metadata.version(package)
        except metadata.PackageNotFoundError:
            out[package] = None
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--instrument", default="nmx", choices=sorted(DEPLOYMENTS))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--allow-cpu",
        action="store_true",
        help="plumbing test only: accept a service that runs on the CPU "
        "(proves nothing about the chip)",
    )
    parser.add_argument(
        "--phase-timeout",
        type=float,
        default=360.0,
        help="seconds one phase may take, child start-up and compiles included",
    )
    parser.add_argument(
        "--log-dir",
        type=Path,
        default=REPO / "chiprun_out" / "chip_smoke",
        help="where the children's logs go",
    )
    parser.add_argument(
        "--inject-wrong-reference",
        action="store_true",
        help="self-test: corrupt the numpy reference; the run must fail",
    )
    args = parser.parse_args(argv)
    try:
        from esslivedata_tpu import native
    except ImportError as err:
        print(f"chip_smoke: the repository is not beside this script: {err}")
        return 2
    dep = DEPLOYMENTS[args.instrument]
    print(f"chip_smoke: versions {json.dumps(versions())}", flush=True)
    if args.allow_cpu:
        print(
            "chip_smoke: --allow-cpu: plumbing run, proves NOTHING about the chip",
            flush=True,
        )
    native_ok = native.available()
    print(f"chip_smoke: native.available() = {native_ok}", flush=True)
    if not native_ok and shutil.which("g++"):
        print(
            "chip_smoke: FAILED: g++ is present but the native shim did not "
            f"build: {native.unavailable_reason()}"
        )
        return 1
    args.log_dir.mkdir(parents=True, exist_ok=True)
    devices = []
    for name, service, flags, run in PHASES:
        work = Path(tempfile.mkdtemp(prefix=f"chip_smoke-{name}-"))
        child = ServiceChild(
            service,
            args.instrument,
            flags,
            work,
            args.log_dir / f"{name}.log",
            args.phase_timeout,
        )
        t0 = time.monotonic()
        failure = None
        report = {}
        try:
            device, report = run(
                child, dep, args.seed, args.allow_cpu, args.inject_wrong_reference
            )
            devices.append(device)
        except SmokeFailure as err:
            failure = str(err)
        finally:
            rc = child.stop()
            shutil.rmtree(work, ignore_errors=True)
        if failure is None and rc != 0:
            failure = f"the service exited with code {rc}"
        report = {
            "phase": name,
            "flags": flags,
            "wall_s": round(time.monotonic() - t0, 1),
            **report,
        }
        print(f"chip_smoke: {json.dumps(report)}", flush=True)
        if failure is not None:
            print(f"chip_smoke: FAILED in phase {name}: {failure}")
            print(f"--- tail of {child.log_path} ---\n{child.log_tail()}")
            return 1
    if any(device != devices[0] for device in devices):
        print(f"chip_smoke: FAILED: phases ran on different devices: {devices}")
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
