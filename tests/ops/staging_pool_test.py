"""The staging copy's ownership contract (ADR 0130): the pool of kept
host buffers alone, with a put and a readiness that the test drives (a
transfer reads its buffer only when told to complete), then the two
branches of ``dispatch_safe`` / ``stage_for`` / ``ship`` over it: kept
(an accelerator, stood in for by a fake ``device_put``) and the fresh
copy of a CPU target."""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from esslivedata_tpu.ops import EventBatch, event_batch, staging_pool
from esslivedata_tpu.ops.event_batch import dispatch_safe, ship, stage_for
from esslivedata_tpu.ops.staging_pool import StagingPool, transfer_done
from esslivedata_tpu.telemetry import REGISTRY
from esslivedata_tpu.telemetry.registry import Gauge

N = 1024


class Transfer:
    """What the fake put returns: it keeps the host buffer it was given
    and reads it only at ``complete()``, as an asynchronous transfer
    reads it some time after ``device_put`` returned."""

    def __init__(self, buffer: np.ndarray, placement=None) -> None:
        self.buffer = buffer
        self.placement = placement
        self.data: np.ndarray | None = None

    def complete(self) -> np.ndarray:
        self.data = self.buffer.copy()
        return self.data


def done(transfer: Transfer) -> bool:
    return transfer.data is not None


def make_pool(**kwargs) -> StagingPool:
    return StagingPool(ready=done, **kwargs)


def copies() -> dict[str, float]:
    counter = REGISTRY.get("livedata_staging_copies_total")
    return {k: counter.value(kind=k) for k in ("kept", "fresh")}


def added(before: dict[str, float]) -> dict[str, float]:
    return {k: v - before[k] for k, v in copies().items()}


def values(seed: int, dtype=np.int32) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 1 << 20, N).astype(dtype)


class TestReadinessGate:
    def test_a_slot_in_flight_is_not_handed_out_again(self):
        pool = make_pool()
        first, second = values(1), values(2)
        before = copies()
        t1 = pool.stage(first, Transfer)
        t2 = pool.stage(second, Transfer)  # t1 has not read its buffer yet
        assert t2.buffer is not t1.buffer
        assert not np.shares_memory(t1.buffer, first)
        np.testing.assert_array_equal(t1.complete(), first)
        np.testing.assert_array_equal(t2.complete(), second)
        assert added(before) == {"kept": 0, "fresh": 2}
        assert pool.nbytes == first.nbytes + second.nbytes

    def test_a_ready_slot_is_reused(self):
        pool = make_pool()
        before = copies()
        t1 = pool.stage(values(1), Transfer)
        t1.complete()
        third = values(3)
        t3 = pool.stage(third, Transfer)
        assert t3.buffer is t1.buffer  # same memory, no allocation
        np.testing.assert_array_equal(t3.complete(), third)
        assert added(before) == {"kept": 1, "fresh": 1}
        assert pool.nbytes == third.nbytes

    def test_the_serial_loop_keeps_one_generation_and_overlap_two(self):
        pool = make_pool()
        nbytes = values(0).nbytes
        for window in range(4):  # the fetch precedes the next staging
            pool.stage(values(window), Transfer).complete()
        assert pool.nbytes == nbytes
        in_flight = pool.stage(values(4), Transfer)
        for window in range(5, 9):  # window i+1 staged while i is read
            staged = pool.stage(values(window), Transfer)
            in_flight.complete()
            in_flight = staged
        assert pool.nbytes == 2 * nbytes

    def test_the_device_array_is_let_go_once_seen_ready(self):
        import weakref

        pool = make_pool()
        transfer = pool.stage(values(1), Transfer)
        transfer.complete()
        seen = weakref.ref(transfer)
        del transfer
        assert seen() is not None  # the slot's hold, until it looks
        pool.stage(values(2, np.float32), Transfer)  # any staging looks
        assert seen() is None

    def test_a_put_that_raises_frees_its_slot(self):
        pool = make_pool()

        def refuse(buffer):
            raise MemoryError("device full")

        with pytest.raises(MemoryError):
            pool.stage(values(1), refuse)
        t = pool.stage(values(2), Transfer)
        assert pool.nbytes == t.buffer.nbytes  # the one slot, taken again

    def test_shapes_and_dtypes_do_not_share_slots(self):
        pool = make_pool()
        pool.stage(values(1), Transfer).complete()
        t_float = pool.stage(values(2, np.float32), Transfer)
        t_half = pool.stage(values(3)[: N // 2], Transfer)
        assert t_float.buffer.dtype == np.float32
        assert t_half.buffer.shape == (N // 2,)
        assert pool.nbytes == 4 * N + 4 * N + 2 * N


class TestThreads:
    def test_five_threads_at_once_get_five_distinct_slots(self):
        pool = make_pool()
        pool.stage(values(0), Transfer).complete()  # one ready slot for all
        barrier = threading.Barrier(5, timeout=30)
        transfers: list[Transfer] = []

        def put(buffer):  # every thread holds its slot here, none has put
            barrier.wait()
            return Transfer(buffer)

        def job(seed: int) -> None:
            transfers.append(pool.stage(values(seed), put))

        threads = [threading.Thread(target=job, args=(s,)) for s in range(5)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60)
        assert not any(thread.is_alive() for thread in threads)
        assert len({id(t.buffer) for t in transfers}) == 5
        assert pool.nbytes == 5 * 4 * N

    def test_no_transfer_reads_bytes_staged_for_another(self):
        """More threads than cores, a short switch interval, transfers
        completed late and out of order: each still reads what was
        staged for it, which a slot handed out early would break."""
        pool = make_pool()
        wrong: list[int] = []
        interval = sys.getswitchinterval()

        def job(worker: int) -> None:
            pending: list[tuple[int, Transfer]] = []
            for i in range(200):
                seed = worker * 1000 + i
                pending.append((seed, pool.stage(values(seed), Transfer)))
                if len(pending) > worker % 3:
                    expect, transfer = pending.pop(0)
                    if not np.array_equal(transfer.complete(), values(expect)):
                        wrong.append(expect)

        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=job, args=(w,)) for w in range(16)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(thread.is_alive() for thread in threads)
        assert wrong == []
        assert pool.nbytes <= 16 * 3 * 4 * N  # depth follows what is in flight


class TestBytesBounded:
    def test_a_length_no_longer_staged_gives_its_bytes_back(self):
        now = [0.0]
        gauge = Gauge("test_staging_pool_bytes", "the pool's bytes, in a test")
        pool = make_pool(clock=lambda: now[0], gauge=gauge)
        wide, narrow = np.zeros(4 * N, np.int32), np.zeros(N, np.int32)
        pool.stage(wide, Transfer).complete()  # the escalated bucket
        assert gauge.value() == pool.nbytes == wide.nbytes
        for second in range(1, 100):  # relaxed: the base bucket from now on
            now[0] = float(second)
            pool.stage(narrow, Transfer).complete()
            expected = narrow.nbytes + (
                wide.nbytes if second <= staging_pool.IDLE_SECONDS else 0
            )
            assert gauge.value() == pool.nbytes == expected

    def test_a_slot_in_flight_is_never_given_back(self):
        now = [0.0]
        pool = make_pool(clock=lambda: now[0])
        stuck = pool.stage(values(1), Transfer)
        now[0] = 10 * staging_pool.IDLE_SECONDS
        pool.stage(values(2, np.float32), Transfer)
        np.testing.assert_array_equal(stuck.complete(), values(1))
        assert pool.nbytes == 2 * 4 * N

    def test_a_sweep_alone_lets_go_of_what_a_stopped_stream_left(self):
        """Beam off: nothing is staged any more, and the service loop's
        metrics line is what sweeps."""
        import weakref

        now = [0.0]
        gauge = Gauge("test_stopped_pool_bytes", "the pool's bytes, in a test")
        pool = make_pool(clock=lambda: now[0], gauge=gauge)
        last = pool.stage(values(1), Transfer)
        seen = weakref.ref(last)
        pool.sweep()
        assert pool.nbytes == 4 * N  # in flight: kept, and held
        last.complete()
        del last
        now[0] = 30.0
        pool.sweep()
        assert seen() is None  # the last window's wire leaves the device
        assert gauge.value() == pool.nbytes == 4 * N
        now[0] = 30.0 + staging_pool.IDLE_SECONDS + 1
        pool.sweep()
        assert gauge.value() == pool.nbytes == 0


class TestOnePassCast:
    def test_dtype_is_cast_by_the_copy_itself(self, monkeypatch):
        passes = []
        copyto = np.copyto

        def spy(dst, src, **kwargs):
            passes.append((dst.dtype, src.dtype))
            return copyto(dst, src, **kwargs)

        monkeypatch.setattr(np, "copyto", spy)
        wide = np.array([1, -1, 2**31 + 5, 7], dtype=np.int64)
        transfer = make_pool().stage(wide, Transfer, dtype=np.int32)
        assert passes == [(np.dtype(np.int32), np.dtype(np.int64))]
        assert transfer.buffer.dtype == np.int32
        np.testing.assert_array_equal(transfer.complete(), wide.astype(np.int32))

    def test_array_likes_and_scalars_are_staged(self):
        pool = make_pool()
        scalar = pool.stage(3, Transfer, dtype=np.float32)
        listed = pool.stage([1.5, 2.5], Transfer)
        assert scalar.complete() == np.float32(3.0)
        np.testing.assert_array_equal(listed.complete(), [1.5, 2.5])


@pytest.fixture()
def accelerator(monkeypatch):
    """``event_batch`` as it runs where host memory is not aliased: its
    backend probe answers "not the CPU", its pool is the test's and
    ``jax.device_put`` is the fake transfer."""
    import jax

    pool = make_pool()
    monkeypatch.setattr(event_batch, "_CPU_BACKEND", False)
    monkeypatch.setattr(event_batch, "POOL", pool)
    monkeypatch.setattr(jax, "device_put", Transfer)
    return pool


def raw_batch() -> EventBatch:
    rng = np.random.default_rng(7)
    return EventBatch.from_arrays(
        rng.integers(0, 16, 1000).astype(np.int32),
        rng.uniform(0, 7e7, 1000).astype(np.float32),
    )


class TestOwnershipOnAnAccelerator:
    def test_a_view_of_reused_memory_goes_through_a_kept_buffer(self, accelerator):
        staging = np.arange(N, dtype=np.int32)
        before = copies()
        transfer = dispatch_safe(staging[: N // 2])
        staging[:] = -1  # release(): the next cycle overwrites the view
        np.testing.assert_array_equal(transfer.complete(), np.arange(N // 2))
        again = dispatch_safe(staging[: N // 2])
        assert again.buffer is transfer.buffer
        assert added(before) == {"kept": 1, "fresh": 1}

    def test_a_wire_that_asks_for_it_keeps_the_fresh_copy(self, accelerator):
        """The detector views' flat wires: copied as before the pool,
        into an array of their own that goes with its transfer."""
        flat = values(4)
        before = copies()
        transfer = dispatch_safe(flat, kept=False)
        assert not np.shares_memory(transfer.buffer, flat)
        placed = stage_for(flat, "slice 1", kept=False)
        assert not np.shares_memory(placed.buffer, flat)
        assert placed.placement == "slice 1"
        again = dispatch_safe(flat, kept=False)
        assert again.buffer is not transfer.buffer
        flat[:] = -1
        np.testing.assert_array_equal(transfer.complete(), values(4))
        assert added(before) == {"kept": 0, "fresh": 3}
        assert accelerator.nbytes == 0

    def test_stage_for_casts_into_the_slot_and_places_it(self, accelerator):
        wide = np.arange(N, dtype=np.int64)
        transfer = stage_for(wide, "mesh", dtype=np.int32)
        assert transfer.placement == "mesh"
        assert transfer.buffer.dtype == np.int32
        assert accelerator.nbytes == 4 * N  # no int64 or second int32 array kept
        np.testing.assert_array_equal(transfer.complete(), wide)

    @pytest.mark.parametrize("placed", [False, True])
    def test_ship_keeps_the_raw_wire_and_not_a_flattened_one(
        self, accelerator, placed
    ):
        batch, device = raw_batch(), ("slice 0" if placed else None)
        spans = REGISTRY.get("livedata_tick_span_seconds")
        before, observed = copies(), spans.count(span="h2d_copy")
        pid, toa = ship(batch, (batch.pixel_id, batch.toa), device)
        assert not np.shares_memory(pid.buffer, batch.pixel_id)
        assert not np.shares_memory(toa.buffer, batch.toa)
        held = accelerator.nbytes
        assert held == batch.pixel_id.nbytes + batch.toa.nbytes
        flat = values(5)
        (wire,) = ship(batch, (flat,), device, kept=False)
        assert not np.shares_memory(wire.buffer, flat)
        assert wire.placement == device
        assert accelerator.nbytes == held
        assert added(before) == {"kept": 0, "fresh": 3}
        assert spans.count(span="h2d_copy") - observed == 2  # one a call
        np.testing.assert_array_equal(pid.complete(), batch.pixel_id)
        np.testing.assert_array_equal(toa.complete(), batch.toa)
        np.testing.assert_array_equal(wire.complete(), flat)

    @pytest.mark.parametrize(
        ("method", "arrays"), [("scatter", 1), ("pallas2d", 2)]
    )
    def test_the_detector_views_flat_wires_stay_out_of_the_pool(
        self, accelerator, method, arrays
    ):
        from esslivedata_tpu.ops import EventHistogrammer

        hist = EventHistogrammer(
            toa_edges=np.linspace(0.0, 7.1e7, 11), n_screen=16, method=method
        )
        before = copies()
        hist.tick_staging(raw_batch(), None)
        assert added(before) == {"kept": 0, "fresh": arrays}
        assert accelerator.nbytes == 0

    def test_an_arena_leased_batch_is_still_copied(self, accelerator):
        """``owned`` says the lease keeps the arena from being re-issued
        while the batch lives, not while a transfer does."""
        batch = raw_batch()
        batch.owned = True
        pid, _ = ship(batch, (batch.pixel_id, batch.toa))
        assert not np.shares_memory(pid.buffer, batch.pixel_id)


class TestACpuTargetKeepsAFreshCopy:
    def test_dispatch_safe_returns_a_copy_of_its_own(self):
        source = values(6)
        before, held = copies(), staging_pool.POOL.nbytes
        staged = dispatch_safe(source)
        expected = source.copy()
        source[:] = -1
        assert isinstance(staged, np.ndarray)
        np.testing.assert_array_equal(staged, expected)
        assert added(before) == {"kept": 0, "fresh": 1}
        assert staging_pool.POOL.nbytes == held  # XLA:CPU aliases: none kept

    def test_stage_for_places_a_copy_of_its_own(self):
        import jax

        source = values(7)
        before = copies()
        staged = stage_for(source, jax.devices()[0], dtype=np.float32)
        expected = source.astype(np.float32)
        source[:] = -1
        np.testing.assert_array_equal(np.asarray(staged), expected)
        assert added(before) == {"kept": 0, "fresh": 1}

    @pytest.mark.parametrize("target", ["device", "single", "named"])
    def test_a_cpu_target_is_read_from_the_target_not_the_default_backend(
        self, monkeypatch, target
    ):
        """A process whose default backend is the chip can still place
        on a CPU device, and XLA:CPU aliases there all the same."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from jax.sharding import SingleDeviceSharding

        pool = make_pool()
        monkeypatch.setattr(event_batch, "_CPU_BACKEND", False)
        monkeypatch.setattr(event_batch, "POOL", pool)
        sharding = {
            "device": jax.devices("cpu")[0],
            "single": SingleDeviceSharding(jax.devices("cpu")[0]),
            "named": NamedSharding(
                Mesh(np.array(jax.devices("cpu")[:2]), ("x",)),
                PartitionSpec("x"),
            ),
        }[target]
        source = values(8)
        before = copies()
        staged = stage_for(source, sharding)
        expected = source.copy()
        source[:] = -1
        np.testing.assert_array_equal(np.asarray(staged), expected)
        assert added(before) == {"kept": 0, "fresh": 1}
        assert pool.nbytes == 0

    def test_device_arrays_pass_through_uncounted(self):
        import jax.numpy as jnp

        on_device = jnp.arange(8)
        before = copies()
        assert dispatch_safe(on_device) is on_device
        assert added(before) == {"kept": 0, "fresh": 0}


class TestTransferDone:
    def test_a_finished_and_a_deleted_array_both_read_done(self):
        import jax

        array = jax.device_put(np.arange(8))
        jax.block_until_ready(array)
        assert transfer_done(array)
        array.delete()
        assert transfer_done(array)
