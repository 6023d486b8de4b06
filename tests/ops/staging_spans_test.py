"""The two leaf spans of a stage-cache miss (ADR 0116): ``flatten`` and
``h2d`` are recorded where the work happens, inside the ``stage()``
closures of ``ops/histogram.py`` and ``ops/event_batch.py``: once per
miss and never on a hit, into the ring on a thread with a bound trace
and into the histogram alone on one without; with them the count of
event slots shipped and of those that are padding."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from esslivedata_tpu.core.device_event_cache import DeviceEventCache
from esslivedata_tpu.ops import EventBatch, EventHistogrammer
from esslivedata_tpu.ops.event_batch import dispatch_safe, stage_for, stage_raw
from esslivedata_tpu.telemetry import REGISTRY, TRACER

N_EVENTS, BUCKET = 1000, 4096


@pytest.fixture()
def tracer():
    TRACER.enabled = True
    TRACER.clear()
    TRACER.set_current(None)
    yield TRACER
    TRACER.set_current(None)


def make_batch() -> EventBatch:
    rng = np.random.default_rng(5)
    return EventBatch.from_arrays(
        rng.integers(0, 16, N_EVENTS).astype(np.int32),
        rng.uniform(0, 7e7, N_EVENTS).astype(np.float32),
    )


def make_hist(method: str = "scatter") -> EventHistogrammer:
    return EventHistogrammer(
        toa_edges=np.linspace(0.0, 7.1e7, 11), n_screen=16, method=method
    )


def observed() -> dict[str, int]:
    spans = REGISTRY.get("livedata_tick_span_seconds")
    staged = REGISTRY.get("livedata_staged_events_total")
    return {
        "flatten": spans.count(span="flatten"),
        "h2d": spans.count(span="h2d"),
        "h2d_copy": spans.count(span="h2d_copy"),
        "staged": staged.value(kind="staged"),
        "pad": staged.value(kind="pad"),
    }


def added(before: dict[str, int]) -> dict[str, int]:
    return {name: value - before[name] for name, value in observed().items()}


def fresh_slot():
    cache = DeviceEventCache()
    cache.begin_window()
    return cache.slot("det")


class TestMissRecordsHitDoesNot:
    @pytest.mark.parametrize("method", ["scatter", "pallas2d"])
    def test_one_flatten_and_one_h2d_per_miss(self, tracer, method):
        hist, batch, slot = make_hist(method), make_batch(), fresh_slot()
        trace_id = tracer.new_trace()
        before = observed()
        with tracer.bind(trace_id):
            first = hist.tick_staging(batch, slot)
            again = hist.tick_staging(batch, slot)
        assert all(a is b for a, b in zip(first, again, strict=True))
        assert added(before) == {
            "flatten": 1, "h2d": 1, "h2d_copy": 1,
            "staged": BUCKET, "pad": BUCKET - N_EVENTS,
        }
        flatten, h2d = tracer.spans(trace_id)
        assert (flatten.name, h2d.name) == ("flatten", "h2d")
        assert flatten.args == {"events": N_EVENTS, "padded": BUCKET}
        assert h2d.args["bytes"] == sum(a.nbytes for a in first)
        # Leaf spans: the copy starts where the flatten ended.
        assert flatten.start_s + flatten.duration_s <= h2d.start_s

    def test_each_layout_of_one_stream_is_its_own_miss(self, tracer):
        """DREAM's mantle is flattened once per view: three layouts,
        three misses, each with its spans and its slots counted."""
        batch, slot = make_batch(), fresh_slot()
        coarse = EventHistogrammer(
            toa_edges=np.linspace(0.0, 7.1e7, 11),
            n_screen=4,
            pixel_lut=np.arange(16) // 4,
        )
        before = observed()
        with tracer.bind(tracer.new_trace()):
            make_hist().tick_staging(batch, slot)
            coarse.tick_staging(batch, slot)
        assert added(before) == {
            "flatten": 2, "h2d": 2, "h2d_copy": 2,
            "staged": 2 * BUCKET, "pad": 2 * (BUCKET - N_EVENTS),
        }

    def test_raw_wire_has_no_flatten(self, tracer):
        batch, slot = make_batch(), fresh_slot()
        trace_id = tracer.new_trace()
        before = observed()
        with tracer.bind(trace_id):
            stage_raw(batch, slot)
            stage_raw(batch, slot)
        assert added(before) == {
            "flatten": 0, "h2d": 1, "h2d_copy": 1,
            "staged": BUCKET, "pad": BUCKET - N_EVENTS,
        }
        (h2d,) = tracer.spans(trace_id)
        assert h2d.args == {"bytes": batch.pixel_id.nbytes + batch.toa.nbytes}


class TestHostCopyInsideH2d:
    """``h2d_copy``: the host copies of one ``ship`` call alone, an
    aggregate inside ``h2d``; what is left of ``h2d`` is the enqueue."""

    @staticmethod
    def seconds(name: str) -> float:
        return REGISTRY.get("livedata_tick_span_seconds").sum(span=name)

    @pytest.mark.parametrize("placed", [False, True])
    def test_one_observation_a_call_and_no_more_than_h2d(self, tracer, placed):
        import jax

        batch = make_batch()  # two arrays: two copies, one observation
        device = jax.devices()[0] if placed else None
        trace_id = tracer.new_trace()
        before = observed()
        copy0, h2d0 = self.seconds("h2d_copy"), self.seconds("h2d")
        with tracer.bind(trace_id):
            pid, toa = stage_raw(batch, device=device)
        assert (added(before)["h2d"], added(before)["h2d_copy"]) == (1, 1)
        copied, shipped = self.seconds("h2d_copy") - copy0, self.seconds("h2d") - h2d0
        assert 0.0 < copied <= shipped
        assert [s.name for s in tracer.spans(trace_id)] == ["h2d"]  # off the ring
        np.testing.assert_array_equal(np.asarray(pid), batch.pixel_id)
        np.testing.assert_array_equal(np.asarray(toa), batch.toa)

    def test_a_copy_outside_ship_is_not_observed(self, tracer):
        import jax

        batch = make_batch()
        before = observed()
        with tracer.bind(tracer.new_trace()):
            dispatch_safe(batch.pixel_id)
            stage_for(batch.toa, jax.devices()[0])
        assert added(before)["h2d_copy"] == 0


class TestEveryWireIsCopied:
    """ADR 0130: a flattened wire and the raw one are both copied, one
    count an array (``fresh`` on the CPU backend, which keeps no
    buffer) and one ``h2d_copy`` observation a ``ship``."""

    @staticmethod
    def copies() -> dict[str, float]:
        counter = REGISTRY.get("livedata_staging_copies_total")
        return {k: counter.value(kind=k) for k in ("kept", "fresh")}

    @pytest.mark.parametrize(
        ("method", "arrays"), [("scatter", 1), ("pallas2d", 2)]
    )
    def test_a_flattened_wire_is_copied(self, tracer, method, arrays):
        hist, batch, slot = make_hist(method), make_batch(), fresh_slot()
        before, seen = self.copies(), observed()
        hist.tick_staging(batch, slot)
        after = self.copies()
        assert {k: after[k] - before[k] for k in after} == {
            "kept": 0, "fresh": arrays,
        }
        assert added(seen)["h2d_copy"] == 1

    def test_the_raw_wire_is_copied(self, tracer):
        batch = make_batch()
        before = self.copies()
        pid, _ = stage_raw(batch, fresh_slot())
        after = self.copies()
        assert {k: after[k] - before[k] for k in after} == {
            "kept": 0, "fresh": 2,
        }
        assert not np.shares_memory(np.asarray(pid), batch.pixel_id)


class TestUnboundThreadReachesTheHistogramOnly:
    def test_worker_without_a_trace_skips_the_ring(self, tracer):
        """The pipelined stage worker and pool threads carry no bound
        trace: their staging aggregates on /metrics and ``prestage``
        stays that path's ring span."""
        hist, batch, slot = make_hist(), make_batch(), fresh_slot()
        before = observed()
        worker = threading.Thread(
            target=hist.stage_events, args=(batch, slot)
        )
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
        assert added(before)["flatten"] == added(before)["h2d"] == 1
        assert tracer.spans() == []
