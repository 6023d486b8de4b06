"""Results leave per tick group (ADR 0128): ``JobManager.process_jobs``
hands a group's results to the window's publisher as soon as the group
is collected, while a later group of the tick is still on the chip, and
returns what has not left yet.

Per job nothing may change: one result per closed window, in order,
bit for bit what the end-of-window route gives, over every family of
``harness/tick_contract.py``. What falls back (plan error, carry
adopted), a window with no tick group and a publisher that raises keep
the route they had. The serial loop's spans still tile its tick.
"""

from __future__ import annotations

import numpy as np
import pytest
from every_window_publishes_test import CENSUS
from tick_program_test import (
    _group_windows,
    _make_group_manager,
    _process,
    _record_calls,
    _staged,
    _wire_bytes,
)

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.fakes import FakeMessageSink, FakeMessageSource
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.message import Message, StreamId, StreamKind
from esslivedata_tpu.core.message_batcher import NaiveMessageBatcher
from esslivedata_tpu.core.orchestrating_processor import (
    OrchestratingProcessor,
)
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.harness.tick_contract import REGISTRY as FAMILIES
from esslivedata_tpu.ops.publish import METRICS, CombinedPublish
from esslivedata_tpu.telemetry import REGISTRY, TRACER
from esslivedata_tpu.workflows import WorkflowFactory

T = Timestamp.from_ns
N_GROUPS = 3
#: Windows 0 and 1 hold the compile rounds of a detector view's two
#: program variants; from here on every family's tick is warm.
WARM = 2


def one_job_per_stream(streams, make) -> JobManager:
    """One job of one workflow on each of ``streams``: one tick group
    each, everything else the manager's default."""
    reg = WorkflowFactory()
    spec = WorkflowSpec(
        instrument="pa", name="wf", source_names=list(streams)
    )
    reg.register_spec(spec).attach_factory(
        lambda *, source_name, params: make()
    )
    mgr = JobManager(job_factory=JobFactory(reg))
    for stream in streams:
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name=stream)
            )
        )
    return mgr


def publish_counts() -> dict[str, float]:
    family = REGISTRY.get("livedata_job_publishes_total")
    return {when: family.value(when=when) for when in ("ahead", "end")}


def publishes_added(before) -> dict[str, float]:
    after = publish_counts()
    return {when: after[when] - before[when] for when in after}


def source_of(result) -> str:
    return result.job_id.source_name


@pytest.mark.parametrize("family", list(CENSUS))
def test_ahead_equals_end_of_window_bit_for_bit(family):
    """Per job and in order, over the six families: what the publisher
    is handed ahead plus what is returned equals what a manager with no
    publisher returns, byte for byte on the da00 wire."""
    stream, window = CENSUS[family]
    streams = [f"{stream}{g}" for g in range(N_GROUPS)]
    spec = FAMILIES[family]
    early = one_job_per_stream(streams, lambda: spec.make_workflow("base"))
    late = one_job_per_stream(streams, lambda: spec.make_workflow("base"))
    ticks = family != "correlation"  # no ingest offer: the private path

    def data(w):  # a fresh staging per manager
        return {s: window(10 * w + g) for g, s in enumerate(streams)}

    try:
        for w in range(WARM + 2):
            handed: list[list] = []
            before = publish_counts()
            rest = early.process_jobs(
                data(w), start=T(0), end=T(w + 1), publish=handed.append
            )
            added = publishes_added(before)
            reference = late.process_jobs(data(w), start=T(0), end=T(w + 1))
            assert len(reference) == N_GROUPS
            ahead = [r for results in handed for r in results]
            if ticks and w >= WARM:
                # Every group but the last left while a later one was
                # still uncollected, one publisher call a group.
                assert [len(results) for results in handed] == [1] * (
                    N_GROUPS - 1
                )
                assert added == {"ahead": N_GROUPS - 1, "end": 1}
            elif not ticks:
                assert handed == []
                assert added == {"ahead": 0, "end": N_GROUPS}
            # One result per job per window, in the order of the jobs.
            assert [source_of(r) for r in ahead + rest] == streams
            for got, ref in zip(ahead + rest, reference, strict=True):
                assert got.job_id.source_name == ref.job_id.source_name
                assert got.start == ref.start and got.end == ref.end
                assert list(got.outputs) == list(ref.outputs)
                assert _wire_bytes(got) == _wire_bytes(ref), (
                    f"{family}, window {w}, {source_of(got)}"
                )
    finally:
        early.shutdown()
        late.shutdown()


class TestTheOrderOnTheHost:
    def warm(self, seed, n_groups=N_GROUPS):
        mgr, workflows = _make_group_manager(n_groups)
        windows = _group_windows(seed, WARM + 2, n_groups)
        for w in range(WARM):
            _process(mgr, windows[w], w)
        return mgr, workflows, windows

    def process(self, mgr, window, w, publish):
        return mgr.process_jobs(
            {s: _staged(pid, toa) for s, (pid, toa) in window.items()},
            start=T(0),
            end=T(w + 1),
            publish=publish,
        )

    def test_group_i_is_published_before_group_i_plus_one_is_collected(self):
        mgr, _, windows = self.warm(80)
        calls: list = []
        _record_calls(mgr._tick_combiner, calls)

        def publish(results):
            calls.extend(("publish", source_of(r)) for r in results)

        rest = self.process(mgr, windows[WARM], WARM, publish)
        assert calls == [
            ("dispatch", 0), ("dispatch", 1), ("dispatch", 2),
            ("collect", 0), ("publish", "det0"),
            ("collect", 1), ("publish", "det1"),
            ("collect", 2),
        ]
        assert [source_of(r) for r in rest] == ["det2"]
        mgr.shutdown()

    def test_a_compile_round_publishes_at_the_end(self):
        """A compile round is collected on the spot, inside the dispatch
        pass: nothing is handed over there, today's order stands."""
        mgr, _ = _make_group_manager(N_GROUPS)
        windows = _group_windows(81, 1, N_GROUPS)
        handed: list = []
        before = publish_counts()
        rest = self.process(mgr, windows[0], 0, handed.append)
        assert handed == []
        assert [source_of(r) for r in rest] == ["det0", "det1", "det2"]
        assert publishes_added(before) == {"ahead": 0, "end": N_GROUPS}
        mgr.shutdown()

    def test_a_one_group_tick_publishes_at_the_end(self):
        mgr, _, windows = self.warm(82, n_groups=1)
        handed: list = []
        before = publish_counts()
        rest = self.process(mgr, windows[WARM], WARM, handed.append)
        assert handed == []
        assert [source_of(r) for r in rest] == ["det0"]
        assert publishes_added(before) == {"ahead": 0, "end": 1}
        mgr.shutdown()

    def test_a_window_with_no_tick_group_publishes_at_the_end(self):
        mgr, _ = _make_group_manager(N_GROUPS)
        mgr._tick_combiner = None  # what ``tick_program=False`` builds
        windows = _group_windows(83, 1, N_GROUPS)
        handed: list = []
        before = publish_counts()
        rest = self.process(mgr, windows[0], 0, handed.append)
        assert handed == []
        assert len(rest) == N_GROUPS
        assert publishes_added(before) == {"ahead": 0, "end": N_GROUPS}
        mgr.shutdown()

    def test_a_raising_publisher_leaves_no_group_uncollected(self):
        """The sink's breaker opens under group 1's publish: groups 2
        and 3 are still collected and their states adopted, nothing
        more is published ahead, the error leaves ``process_jobs`` when
        the window is done, and the next window counts on top of every
        state."""
        mgr, _, windows = self.warm(84)
        calls: list = []
        _record_calls(mgr._tick_combiner, calls)
        handed: list = []

        def breaker_open(results):
            handed.append([source_of(r) for r in results])
            raise ConnectionError("producer circuit breaker open")

        slots_before = mgr.event_cache_stats()
        with pytest.raises(ConnectionError, match="breaker open"):
            self.process(mgr, windows[WARM], WARM, breaker_open)
        assert handed == [["det0"]]  # and no second attempt
        assert [c for c in calls if c[0] == "collect"] == [
            ("collect", 0), ("collect", 1), ("collect", 2),
        ]
        # The window's staged generation was closed as always.
        assert mgr.event_cache_stats().keys() == slots_before.keys()
        assert "error" not in {str(s.state) for s in mgr.job_statuses()}
        # No state was lost: the next window's cumulative holds every
        # window's events, the failed one's too.
        METRICS.drain()
        handed.clear()
        good: list = []
        rest = self.process(
            mgr, windows[WARM + 1], WARM + 1, good.extend
        )
        assert METRICS.drain()["tick_publishes"] == N_GROUPS
        # ... equal to that of a twin no publisher ever failed.
        twin, _, _ = self.warm(84)
        for w in (WARM, WARM + 1):
            reference = _process(twin, windows[w], w)
        for got, ref in zip([*good, *rest], reference, strict=True):
            assert _wire_bytes(got) == _wire_bytes(ref), source_of(got)
        twin.shutdown()
        mgr.shutdown()


class TestMembersThatFellBack:
    """A member its group's collect did not serve keeps today's route:
    the accumulate pass, the private publish, one result at the end."""

    def two_groups(self, seed):
        """Group 1: two detector views on ``det0``; group 2: one on
        ``det1``. Warm."""
        from tick_program_test import _det, _make_manager

        from esslivedata_tpu.workflows.detector_view import (
            DetectorViewWorkflow,
            project_logical,
        )

        det = _det()
        mgr, workflows = _make_manager(
            [lambda: DetectorViewWorkflow(projection=project_logical(det))]
            * 3,
            streams=["det0", "det0", "det1"],
        )
        rng = np.random.default_rng(seed)
        windows = [
            {
                s: (
                    rng.integers(0, 144, 600).astype(np.int64),
                    rng.uniform(0, 7e7, 600).astype(np.float32),
                )
                for s in ("det0", "det1")
            }
            for _ in range(WARM + 2)
        ]
        for w in range(WARM):
            assert len(_process(mgr, windows[w], w)) == 3
        return mgr, workflows, windows

    def run(self, mgr, windows, w):
        handed: list = []
        before = publish_counts()
        rest = mgr.process_jobs(
            {s: _staged(pid, toa) for s, (pid, toa) in windows[w].items()},
            start=T(0),
            end=T(w + 1),
            publish=handed.append,
        )
        return handed, rest, publishes_added(before)

    def assert_counts_every_window(self, result, n_windows):
        cumulative = float(result.outputs["counts_cumulative"].values)
        assert cumulative == 600 * n_windows, source_of(result)

    def test_a_plan_error_is_published_once_at_the_end(self):
        mgr, workflows, windows = self.two_groups(85)
        publisher = workflows[1].publish_offer().publisher
        plan = publisher._static_plan
        plans: list = []

        def every_tick_plan_explodes(args, static_token):
            # A window plans this member twice: for its group's tick
            # program, then for the private publish it falls back to.
            plans.append(len(plans) % 2 == 0)
            if plans[-1]:
                raise ValueError("trace-time explosion")
            return plan(args, static_token)

        publisher._static_plan = every_tick_plan_explodes
        # The group's program without the member compiles: a compile
        # round, collected on the spot, everything at the end.
        handed, rest, added = self.run(mgr, windows, WARM)
        assert handed == [] and len(rest) == 3
        handed, rest, added = self.run(mgr, windows, WARM + 1)
        assert plans == [True, False] * 2
        # Member 1 of group 1 dropped out of the tick at plan time and
        # stepped privately; member 0 left ahead, alone.
        assert [len(results) for results in handed] == [1]
        assert handed[0][0] is not None
        assert len(rest) == 2
        assert added == {"ahead": 1, "end": 2}
        results = [*handed[0], *rest]
        assert len({r.job_id for r in results}) == 3  # each job once
        for result in results:
            self.assert_counts_every_window(result, WARM + 2)
        assert "error" not in {str(s.state) for s in mgr.job_statuses()}
        mgr.shutdown()

    def test_an_adopted_carry_is_published_once_at_the_end(self):
        """An unpack failure: the step and the fold ran on the device,
        the member adopts its carry and republishes privately."""
        mgr, _, windows = self.two_groups(86)
        combiner = mgr._tick_combiner
        collect = combiner.collect
        seen: list = []

        def collect_with_a_broken_unpack(pending):
            results = collect(pending)
            seen.append(len(results))
            if len(results) == 2:  # group 1
                results[1] = CombinedPublish(
                    None, results[1].carry, error=RuntimeError("unpack")
                )
            return results

        combiner.collect = collect_with_a_broken_unpack
        handed, rest, added = self.run(mgr, windows, WARM)
        assert seen == [2, 1]
        assert [len(results) for results in handed] == [1]
        assert len(rest) == 2
        assert added == {"ahead": 1, "end": 2}
        results = [*handed[0], *rest]
        assert len({r.job_id for r in results}) == 3
        for result in results:
            # Counted once: the adopted carry holds this window's
            # events and the private pass did not add them again.
            self.assert_counts_every_window(result, WARM + 1)
        mgr.shutdown()


class StagingAccumulator:
    """Preprocessor double: the window's value is the staged batch of
    the one message it was given."""

    is_context = False
    also_context = False

    def __init__(self) -> None:
        self.value = None
        self.released = 0

    def add(self, timestamp, value) -> None:
        self.value = value

    def get(self):
        return _staged(*self.value)

    def release_buffers(self) -> None:
        self.released += 1
        self.value = None


class Accumulators:
    def __init__(self, streams) -> None:
        self.by_stream = {s: StagingAccumulator() for s in streams}

    def make_preprocessor(self, stream: StreamId):
        return self.by_stream.get(stream.name)


class BreakerSink(FakeMessageSink):
    """A sink whose breaker is open for the calls numbered in
    ``open_at``."""

    def __init__(self) -> None:
        super().__init__()
        self.calls = 0
        self.open_at: set[int] = set()

    def publish_messages(self, messages) -> None:
        data = [
            m for m in messages if m.stream.kind is StreamKind.LIVEDATA_DATA
        ]
        if data:
            self.calls += 1
            if self.calls in self.open_at:
                raise ConnectionError("producer circuit breaker open")
        super().publish_messages(messages)


class TestTheSerialLoop:
    """The manager under ``OrchestratingProcessor._process_batch``: the
    loop's publisher is the one the manager calls ahead."""

    STREAMS = tuple(f"det{g}" for g in range(N_GROUPS))

    def make(self, seed, n_windows, pipelined=False):
        mgr, _ = _make_group_manager(N_GROUPS)
        windows = _group_windows(seed, n_windows, N_GROUPS)
        source = FakeMessageSource(
            [[]]  # the poll of the first heartbeat
            + [
                [
                    Message(
                        timestamp=Timestamp.from_pulse_index(100 + w),
                        stream=StreamId(
                            kind=StreamKind.DETECTOR_EVENTS, name=stream
                        ),
                        value=window[stream],
                    )
                    for stream in self.STREAMS
                ]
                for w, window in enumerate(windows)
            ]
        )
        sink = BreakerSink()
        accumulators = Accumulators(self.STREAMS)
        processor = OrchestratingProcessor(
            source=source,
            sink=sink,
            preprocessor_factory=accumulators,
            job_manager=mgr,
            batcher=NaiveMessageBatcher(),
            instrument="dummy",
            service_name=f"publish_ahead_{seed}",
            clock=lambda: 0.0,
            heartbeat_interval_s=1e9,
            pipelined=pipelined,
        )
        processor.process()  # the first heartbeat, out of the way
        sink.clear()
        return processor, sink, accumulators, windows

    def data_messages(self, sink) -> list[tuple]:
        """(source, output, timestamp) of every data message, in the
        order written (a stream is named workflow|source|job|output)."""
        return [
            (*m.stream.name.split("|")[1::2], m.timestamp)
            for m in sink.messages
            if m.stream.kind is StreamKind.LIVEDATA_DATA
        ]

    def cumulative_counts(self, sink) -> dict[str, float]:
        return {
            m.stream.name: float(m.value.values)
            for m in sink.messages
            if m.stream.kind is StreamKind.LIVEDATA_DATA
            and m.stream.name.endswith("counts_cumulative")
        }

    def test_spans_tile_the_tick_and_results_leave_per_group(self):
        spans_family = REGISTRY.get("livedata_tick_span_seconds")
        published = REGISTRY.get("livedata_e2e_latency_seconds")
        was_enabled = TRACER.enabled
        TRACER.enabled = True
        try:
            processor, sink, accumulators, _ = self.make(87, WARM + 1)
            for _ in range(WARM):
                processor.process()
            sink.clear()
            TRACER.clear()
            sink_calls_before = sink.calls
            before = publish_counts()
            ticks_before = spans_family.count(span="tick")
            unspanned_before = spans_family.sum(span="unspanned")
            published_before = published.count(stage="published")
            processor.process()
            spans = TRACER.spans()
        finally:
            TRACER.enabled = was_enabled
        assert publishes_added(before) == {"ahead": 2, "end": 1}
        assert len({s.trace_id for s in spans}) == 1
        assert len({s.thread for s in spans}) == 1
        spans.sort(key=lambda s: s.start_s)
        # Every group is staged and dispatched before the first fetch;
        # then one fetch, finalize and sink a group, in turn.
        assert [s.name for s in spans if s.name != "d2h"] == [
            "decode",
            *["flatten", "h2d", "tick_execute"] * N_GROUPS,
            *["fetch", "finalize", "sink"] * N_GROUPS,
        ]
        for earlier, later in zip(spans, spans[1:]):
            assert (
                earlier.start_s + earlier.duration_s <= later.start_s
            ), f"{earlier.name} overlaps {later.name}"
        # finish_tick ran once, with the tiling's remainder positive.
        assert spans_family.count(span="tick") == ticks_before + 1
        assert spans_family.sum(span="unspanned") > unspanned_before
        # "published" once per window, whatever the number of sinks.
        assert published.count(stage="published") == published_before + 1
        # Three sink calls, one job each, in the order of the groups,
        # all of a job's outputs together.
        assert sink.calls - sink_calls_before == N_GROUPS
        written = self.data_messages(sink)
        sources = [source for source, _, _ in written]
        assert sources == sorted(sources)
        assert set(sources) == set(self.STREAMS)
        assert len({timestamp for _, _, timestamp in written}) == 1
        for acc in accumulators.by_stream.values():
            assert acc.released == WARM + 1
        processor._job_manager.shutdown()

    def test_the_pipelined_loop_passes_its_own_publisher(self):
        """``--pipeline``: the step worker's publisher goes through the
        same argument, and the wire equals the serial loop's."""
        processor, sink, _, _ = self.make(89, WARM + 1, pipelined=True)
        serial, serial_sink, _, _ = self.make(89, WARM + 1)
        try:
            for _ in range(WARM):
                processor.process()
                serial.process()
            assert processor._pipeline.flush(timeout=60.0)
            sink.clear()
            serial_sink.clear()
            sink_calls_before = sink.calls
            before = publish_counts()
            processor.process()
            assert processor._pipeline.flush(timeout=60.0)
            assert publishes_added(before) == {"ahead": 2, "end": 1}
            assert sink.calls - sink_calls_before == N_GROUPS
            assert processor._pipeline.telemetry()["published"] == WARM + 1
            serial.process()
            assert self.data_messages(sink) == self.data_messages(
                serial_sink
            )
            for got, ref in zip(sink.messages, serial_sink.messages):
                assert np.array_equal(got.value.values, ref.value.values)
        finally:
            processor.finalize()
            processor._job_manager.shutdown()
            serial._job_manager.shutdown()

    def test_an_open_breaker_leaves_the_loop_as_it_always_has(self):
        spans_family = REGISTRY.get("livedata_tick_span_seconds")
        processor, sink, accumulators, windows = self.make(88, WARM + 2)
        for _ in range(WARM):
            processor.process()
        calls: list = []
        _record_calls(processor._job_manager._tick_combiner, calls)
        sink.open_at = {sink.calls + 1}  # the first group's publish
        ticks_before = spans_family.count(span="tick")
        with pytest.raises(ConnectionError, match="breaker open"):
            processor.process()
        assert [c for c in calls if c[0] == "collect"] == [
            ("collect", g) for g in range(N_GROUPS)
        ]
        # release() and finish_tick ran as today.
        for acc in accumulators.by_stream.values():
            assert acc.released == WARM + 1
        assert spans_family.count(span="tick") == ticks_before + 1
        # The next window publishes every job, counting every window.
        sink.clear()
        processor.process()
        cumulative = self.cumulative_counts(sink)
        assert len(cumulative) == N_GROUPS
        # ... as does a twin whose breaker never opened.
        twin, twin_sink, _, _ = self.make(88, WARM + 2)
        for _ in range(WARM + 2):
            twin_sink.clear()
            twin.process()
        assert list(cumulative.values()) == list(
            self.cumulative_counts(twin_sink).values()
        )
        twin._job_manager.shutdown()
        processor._job_manager.shutdown()
