"""TickTracer: trace-id lifecycle, cross-thread span correlation,
Chrome trace_event export, ring bound, slow-tick watchdog latch, and
the compile-event recorder's trigger classification."""

from __future__ import annotations

import json
import threading

from esslivedata_tpu.telemetry import REGISTRY, CompileEventRecorder, TickTracer


def make_tracer(**kwargs) -> TickTracer:
    kwargs.setdefault("enabled", True)
    kwargs.setdefault("slow_tick_s", 0.25)
    return TickTracer(**kwargs)


class TestSpans:
    def test_spans_share_the_window_trace_id_across_threads(self):
        """The correlation contract: decode on one worker, tick/fetch
        on another, all against the id allocated at decode."""
        tracer = make_tracer()
        trace_id = tracer.new_trace()
        with tracer.span("decode", trace_id):
            pass

        def step_worker() -> None:
            with tracer.bind(trace_id):
                with tracer.span("tick_execute"):
                    pass
                with tracer.span("fetch"):
                    pass

        thread = threading.Thread(target=step_worker)
        thread.start()
        thread.join()
        spans = tracer.spans(trace_id)
        assert [s.name for s in spans] == ["decode", "tick_execute", "fetch"]
        assert {s.trace_id for s in spans} == {trace_id}
        assert len({s.thread for s in spans}) == 2

    def test_bind_restores_previous_trace(self):
        tracer = make_tracer()
        outer, inner = tracer.new_trace(), tracer.new_trace()
        tracer.set_current(outer)
        with tracer.bind(inner):
            assert tracer.current() == inner
        assert tracer.current() == outer

    def test_disabled_tracer_records_nothing(self):
        tracer = TickTracer(enabled=False)
        trace_id = tracer.new_trace()
        with tracer.span("decode", trace_id):
            pass
        tracer.record("fetch", 0.0, 1.0, trace_id)
        assert tracer.spans() == []
        tracer.finish_tick(trace_id, 100.0)
        assert tracer.slow_ticks == 0

    def test_ring_is_bounded(self):
        tracer = make_tracer(capacity=8)
        trace_id = tracer.new_trace()
        for i in range(100):
            tracer.record(f"s{i}", 0.0, 0.001, trace_id)
        spans = tracer.spans()
        assert len(spans) == 8
        assert spans[-1].name == "s99"

    def test_untraced_span_skips_ring(self):
        tracer = make_tracer()
        tracer.set_current(None)
        with tracer.span("decode"):
            pass
        assert tracer.spans() == []


def span_totals(name: str) -> tuple[float, int]:
    """(sum, count) of ``livedata_tick_span_seconds{span=name}``: the
    histogram is the process's, so tests read deltas."""
    family = REGISTRY.get("livedata_tick_span_seconds")
    return family.sum(span=name), family.count(span=name)


class TestAggregatesAndArgs:
    def test_span_args_ride_the_ring(self):
        tracer = make_tracer()
        trace_id = tracer.new_trace()
        with tracer.span("flatten", trace_id, {"events": 7, "padded": 4096}):
            pass
        with tracer.span("sink", trace_id):
            pass
        flatten, sink = tracer.spans(trace_id)
        assert flatten.args == {"events": 7, "padded": 4096}
        assert sink.args is None

    def test_observe_is_aggregate_only(self):
        """An enclosing or contained phase goes to the histogram and
        never to the ring: the ring stays flat."""
        tracer = make_tracer()
        tracer.set_current(tracer.new_trace())
        before = span_totals("d2h")
        tracer.observe("d2h", 0.25)
        after = span_totals("d2h")
        assert after[1] == before[1] + 1
        assert after[0] - before[0] == 0.25
        assert tracer.spans() == []
        TickTracer(enabled=False).observe("d2h", 1.0)
        assert span_totals("d2h") == after

    def test_finish_tick_observes_tick_and_what_no_span_covered(self):
        tracer = make_tracer(slow_tick_s=10.0)
        trace_id = tracer.new_trace()
        tracer.record("decode", 0.0, 0.010, trace_id)
        with tracer.bind(trace_id):
            tracer.record("fetch", 0.010, 0.030)
        # Another thread's span of the same trace does not tile this
        # thread's tick (pool threads, pipeline workers).
        worker = threading.Thread(
            target=tracer.record, args=("prestage", 0.0, 0.5, trace_id)
        )
        worker.start()
        worker.join()
        tick0, unspanned0 = span_totals("tick"), span_totals("unspanned")
        tracer.finish_tick(trace_id, 0.050, tiled=True)
        tick1, unspanned1 = span_totals("tick"), span_totals("unspanned")
        assert (tick1[1], unspanned1[1]) == (tick0[1] + 1, unspanned0[1] + 1)
        assert abs(tick1[0] - tick0[0] - 0.050) < 1e-12
        assert abs(unspanned1[0] - unspanned0[0] - 0.010) < 1e-12
        # Aggregate only: no ring entry for either.
        assert {s.name for s in tracer.spans()} == {
            "decode", "fetch", "prestage"
        }

    def test_overlapping_stages_report_tick_alone(self):
        """The pipelined path (stages overlap across threads) does not
        ask for ``unspanned``."""
        tracer = make_tracer(slow_tick_s=10.0)
        trace_id = tracer.new_trace()
        tracer.record("decode", 0.0, 0.010, trace_id)
        tick0, unspanned0 = span_totals("tick"), span_totals("unspanned")
        tracer.finish_tick(trace_id, 0.050)
        assert span_totals("tick")[1] == tick0[1] + 1
        assert span_totals("unspanned") == unspanned0

    def test_overlapping_spans_read_as_a_negative_remainder(self):
        """A nested (or double-recorded) span on the loop thread covers
        more than the tick: ``unspanned`` then goes NEGATIVE on the
        scrape instead of reading as perfect tiling."""
        tracer = make_tracer(slow_tick_s=10.0)
        trace_id = tracer.new_trace()
        tracer.record("fetch", 0.0, 0.030, trace_id)
        tracer.record("d2h", 0.020, 0.010, trace_id)  # inside fetch
        before = span_totals("unspanned")
        tracer.finish_tick(trace_id, 0.035, tiled=True)
        after = span_totals("unspanned")
        assert after[1] == before[1] + 1
        assert abs(after[0] - before[0] - (0.035 - 0.040)) < 1e-12

    def test_a_covering_aggregate_lowers_unspanned_by_its_duration(self):
        """``accumulate_wait``: the loop thread waits for the pool under
        no ring span of its own, and the wait still explains that part
        of the tick. The ring's length does not change."""
        tracer = make_tracer(slow_tick_s=10.0)
        trace_id = tracer.new_trace()
        tracer.record("decode", 0.0, 0.010, trace_id)
        wait0 = span_totals("accumulate_wait")
        with tracer.bind(trace_id):
            tracer.observe("accumulate_wait", 0.025, covers=True)
            tracer.observe("pool_queue", 0.5)  # a plain aggregate covers nothing
        assert len(tracer.spans()) == 1
        wait1 = span_totals("accumulate_wait")
        assert (wait1[1], wait1[0] - wait0[0]) == (wait0[1] + 1, 0.025)
        before = span_totals("unspanned")
        tracer.finish_tick(trace_id, 0.040, tiled=True)
        after = span_totals("unspanned")
        assert after[1] == before[1] + 1
        assert abs(after[0] - before[0] - (0.040 - 0.010 - 0.025)) < 1e-12

    def test_an_overlapping_covering_aggregate_drives_unspanned_negative(self):
        """A covering aggregate laid over a ring span of the same thread
        covers that time twice: the remainder stays signed."""
        tracer = make_tracer(slow_tick_s=10.0)
        trace_id = tracer.new_trace()
        with tracer.bind(trace_id):
            tracer.record("fetch", 0.0, 0.030)
            tracer.observe("accumulate_wait", 0.020, covers=True)  # inside fetch
        before = span_totals("unspanned")
        tracer.finish_tick(trace_id, 0.035, tiled=True)
        assert abs(
            span_totals("unspanned")[0] - before[0] - (0.035 - 0.050)
        ) < 1e-12

    def test_the_aggregate_context_times_its_region_off_the_ring(self):
        tracer = make_tracer(slow_tick_s=10.0)
        trace_id = tracer.new_trace()
        release = threading.Event()
        before = span_totals("accumulate_wait")
        with tracer.bind(trace_id):
            with tracer.aggregate("accumulate_wait", covers=True):
                release.wait(0.02)
        elapsed = span_totals("accumulate_wait")[0] - before[0]
        assert span_totals("accumulate_wait")[1] == before[1] + 1
        assert 0.015 < elapsed < 5.0
        assert tracer.spans() == []
        unspanned0 = span_totals("unspanned")
        tracer.finish_tick(trace_id, elapsed + 0.004, tiled=True)
        assert abs(span_totals("unspanned")[0] - unspanned0[0] - 0.004) < 1e-9
        # Unbound, a covering aggregate has no tick to cover: histogram only.
        with tracer.aggregate("accumulate_wait", covers=True):
            pass
        assert span_totals("accumulate_wait")[1] == before[1] + 2

    def test_a_disabled_tracer_records_no_aggregate(self):
        tracer = TickTracer(enabled=False)
        trace_id = tracer.new_trace()
        names = ("accumulate_wait", "stage_wait", "unspanned", "tick")
        before = [span_totals(name) for name in names]
        with tracer.bind(trace_id):
            tracer.observe("accumulate_wait", 1.0, covers=True)
            with tracer.aggregate("stage_wait"):
                pass
            with tracer.annotated("h2d_copy"):
                pass
        tracer.finish_tick(trace_id, 2.0, tiled=True)
        assert [span_totals(name) for name in names] == before
        assert tracer.spans() == []

    def test_covered_sum_does_not_leak_into_the_next_tick(self):
        tracer = make_tracer(slow_tick_s=10.0)
        first, second = tracer.new_trace(), tracer.new_trace()
        tracer.record("decode", 0.0, 0.040, first)
        tracer.finish_tick(first, 0.040, tiled=True)
        tracer.record("decode", 0.0, 0.010, second)
        before = span_totals("unspanned")
        tracer.finish_tick(second, 0.030, tiled=True)
        assert abs(span_totals("unspanned")[0] - before[0] - 0.020) < 1e-12


class TestChromeExport:
    def test_chrome_trace_loads_and_groups_by_trace_id(self, tmp_path):
        tracer = make_tracer()
        t1, t2 = tracer.new_trace(), tracer.new_trace()
        for trace_id in (t1, t2):
            for name in ("decode", "prestage", "tick_execute", "fetch"):
                tracer.record(name, 0.001, 0.002, trace_id)
        path = tmp_path / "trace.json"
        tracer.dump(str(path))
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert len(events) == 8
        # Chrome trace_event contract: complete events with microsecond
        # timestamps, one pid per window so the viewer groups spans.
        for event in events:
            assert event["ph"] == "X"
            assert event["dur"] == 2000.0
            assert event["pid"] in (t1, t2)
        names_t1 = [e["name"] for e in events if e["pid"] == t1]
        assert names_t1 == ["decode", "prestage", "tick_execute", "fetch"]

    def test_dump_names_its_clock_and_carries_span_args(self, tmp_path):
        """``ts`` is on ``perf_counter``; the offset to the epoch is
        sampled in the process that recorded the spans, at dump time."""
        import time

        tracer = make_tracer()
        trace_id = tracer.new_trace()
        with tracer.span("h2d", trace_id, {"bytes": 16384}):
            pass
        path = tmp_path / "trace.json"
        lo = time.time_ns() - time.perf_counter_ns()
        tracer.dump(str(path))
        hi = time.time_ns() - time.perf_counter_ns()
        doc = json.loads(path.read_text())
        assert doc["clock"] == "perf_counter"
        # The two clocks tick together to well under a millisecond.
        assert lo - 1_000_000 <= doc["epoch_minus_clock_ns"] <= hi + 1_000_000
        (event,) = doc["traceEvents"]
        assert event["args"] == {"trace_id": trace_id, "bytes": 16384}
        at_ns = event["ts"] * 1e3 + doc["epoch_minus_clock_ns"]
        assert abs(at_ns - time.time_ns()) < 60e9


class TestWatchdog:
    def test_slow_tick_logs_breakdown_and_latches(self, caplog):
        tracer = make_tracer(slow_tick_s=0.1)
        trace_id = tracer.new_trace()
        tracer.record("fetch", 0.0, 0.19, trace_id)
        with caplog.at_level("WARNING", logger="esslivedata_tpu.telemetry.trace"):
            tracer.finish_tick(trace_id, 0.2)
        assert tracer.slow_ticks == 1
        assert "slow tick" in caplog.text
        assert "fetch" in caplog.text
        # Latched onto the triggering duration: an equally slow tick
        # does NOT re-log; a slower one does.
        tracer.finish_tick(tracer.new_trace(), 0.2)
        assert tracer.slow_ticks == 1
        tracer.finish_tick(tracer.new_trace(), 0.5)
        assert tracer.slow_ticks == 2

    def test_breakdown_sums_repeated_span_names(self, caplog):
        """A window records one tick_execute/fetch pair PER tick group
        (and per mesh slice): the watchdog breakdown must aggregate
        them, not keep only the last — otherwise a tick dominated by
        four 50 ms fetches logs 'fetch: 50'."""
        tracer = make_tracer(slow_tick_s=0.1)
        trace_id = tracer.new_trace()
        for _ in range(4):
            tracer.record("fetch", 0.0, 0.05, trace_id)
        with caplog.at_level(
            "WARNING", logger="esslivedata_tpu.telemetry.trace"
        ):
            tracer.finish_tick(trace_id, 0.21)
        assert "200.0ms/4x" in caplog.text

    def test_latch_decays_back_toward_floor(self):
        tracer = make_tracer(slow_tick_s=0.1)
        tracer.finish_tick(tracer.new_trace(), 10.0)
        assert tracer.slow_ticks == 1
        # Healthy ticks decay the latch (0.95^n); after enough of them
        # a 0.2 s tick trips again even though 10 s once latched.
        for _ in range(200):
            tracer.finish_tick(tracer.new_trace(), 0.01)
        tracer.finish_tick(tracer.new_trace(), 0.2)
        assert tracer.slow_ticks == 2


class TestCompileClassification:
    def test_trigger_taxonomy(self):
        rec = CompileEventRecorder()
        group = ("hist", ("pub",))
        base = dict(layout_digest="d1", staged_sig="s1")
        assert rec.classify("tick", group, **base) == "new_group"
        assert (
            rec.classify("tick", group, **{**base, "layout_digest": "d2"})
            == "layout_swap"
        )
        assert (
            rec.classify(
                "tick",
                group,
                layout_digest="d2",
                staged_sig="s2",
            )
            == "batch_shape"
        )
        assert (
            rec.classify(
                "tick",
                group,
                layout_digest="d2",
                staged_sig="s2",
                residual="tag-b",
            )
            == "regroup"
        )
        # Byte-identical key missing anyway = LRU eviction recompile.
        assert (
            rec.classify(
                "tick",
                group,
                layout_digest="d2",
                staged_sig="s2",
                residual="tag-b",
            )
            == "evicted"
        )
        # Sites are independent: the same group is new at another site.
        assert rec.classify("publish", group, **base) == "new_group"

    def test_memory_is_bounded(self):
        rec = CompileEventRecorder()
        for i in range(rec._MEMORY_MAX + 10):
            rec.classify("tick", f"group-{i}")
        assert len(rec._memory) == rec._MEMORY_MAX
        # The evicted earliest group classifies as new again.
        assert rec.classify("tick", "group-0") == "new_group"


class TestExportConsistency:
    """The ring-export contract (ADR 0120 satellite): every exporter
    reads ONE snapshot under the lock, so concurrent writers trimming
    the ring can never make an export drop spans it promised."""

    def test_export_is_one_consistent_snapshot(self, tmp_path):
        tracer = make_tracer(capacity=100_000)
        stop = threading.Event()
        recorded = []

        def writer(worker: int) -> None:
            trace_id = tracer.new_trace()
            n = 0
            while not stop.is_set():
                tracer.record(f"w{worker}", 0.0, 1e-6, trace_id)
                n += 1
            recorded.append(n)

        def exporter() -> None:
            last = 0
            while not stop.is_set():
                snapshot = tracer.export()
                doc = tracer.chrome_trace(snapshot)
                # Payload and snapshot describe the SAME ring state.
                assert len(doc["traceEvents"]) == len(snapshot)
                # While the ring is not full, exports only grow: a
                # shrink means a snapshot raced a concurrent trim.
                assert len(snapshot) >= last
                last = len(snapshot)

        writers = [
            threading.Thread(target=writer, args=(i,)) for i in range(4)
        ]
        export_threads = [
            threading.Thread(target=exporter) for _ in range(2)
        ]
        for t in writers + export_threads:
            t.start()
        import time as _time

        _time.sleep(0.3)
        stop.set()
        for t in writers + export_threads:
            t.join()
        # Hammer postcondition: nothing below capacity was lost — the
        # final export holds every span every writer recorded.
        assert sum(recorded) <= 100_000, "raise capacity for this test"
        assert len(tracer.export()) == sum(recorded)

    def test_spans_recorded_before_export_always_appear(self):
        tracer = make_tracer(capacity=4096)
        trace_id = tracer.new_trace()
        tracer.record("landed", 0.0, 1e-6, trace_id)
        names = {s.name for s in tracer.export()}
        assert "landed" in names

    def test_dump_count_matches_payload(self, tmp_path, caplog):
        import logging

        tracer = make_tracer()
        trace_id = tracer.new_trace()
        for _ in range(5):
            tracer.record("phase", 0.0, 1e-6, trace_id)
        path = tmp_path / "trace.json"
        with caplog.at_level(logging.INFO):
            tracer.dump(str(path))
        doc = json.loads(path.read_text())
        assert len(doc["traceEvents"]) == 5
        assert "5 spans" in caplog.text


class TestWatchdogLatchSignal:
    def test_latched_between_breach_and_decay(self):
        tracer = make_tracer(slow_tick_s=0.1)
        assert not tracer.watchdog_latched
        trace_id = tracer.new_trace()
        tracer.finish_tick(trace_id, 0.5)  # breach: latch to 0.5
        assert tracer.watchdog_latched
        # Healthy ticks decay the latch back toward the floor
        # (0.95^n); latched stays True until the floor is reached.
        for _ in range(50):
            tracer.finish_tick(tracer.new_trace(), 0.01)
        assert not tracer.watchdog_latched
