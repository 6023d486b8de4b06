"""LinkMonitor: EWMA estimates, policy switching across a bandwidth
step (ADR 0111 acceptance: batch-size target AND wire format must both
flip), hysteresis, and cross-thread counter integrity (lock hammer)."""

from __future__ import annotations

import threading

from esslivedata_tpu.core.link_monitor import LinkMonitor, LinkPolicy

MB = 1_000_000


def feed(monitor: LinkMonitor, bps: float, n: int = 40) -> None:
    """Converge the EWMA onto ``bps`` with realistic 16 MB stagings."""
    nbytes = 16 * MB
    for _ in range(n):
        monitor.observe_staging(nbytes, nbytes / bps)


class TestPolicySwitching:
    def test_neutral_before_any_observation(self):
        policy = LinkMonitor().policy()
        assert policy == LinkPolicy(
            window_scale=1.0, compact_wire=None, depth=2
        )

    def test_bandwidth_step_switches_batch_target_and_wire(self):
        """The acceptance scenario: healthy -> degraded -> healthy, with
        injected timings, must flip the batch-size target AND the wire
        format (and back)."""
        monitor = LinkMonitor()
        # Healthy link: ~800 MB/s.
        feed(monitor, 8.0e8)
        healthy = monitor.policy()
        assert healthy.window_scale == 1.0
        # None = leave the construction-time wire default (ADR 0108
        # already prefers compact where it fits) — the policy forces
        # compact only on a degraded link, and never forces wide.
        assert healthy.compact_wire is None
        assert healthy.depth == 2

        # Bandwidth step down: ~40 MB/s (round-5 degraded regime).
        feed(monitor, 4.0e7)
        degraded = monitor.policy()
        assert degraded.window_scale > healthy.window_scale
        assert degraded.window_scale == 8.0  # target/bw capped at max
        assert degraded.compact_wire is True
        assert degraded.depth == 4

        # Step back up: both decisions recover.
        feed(monitor, 8.0e8)
        recovered = monitor.policy()
        assert recovered.window_scale == 1.0
        assert recovered.compact_wire is None
        assert recovered.depth == 2

    def test_hysteresis_dead_zone(self):
        """Between the degrade and recover thresholds the latch keeps
        its last state — no flapping across a noisy boundary."""
        monitor = LinkMonitor(
            degraded_bandwidth_bps=1.0e8, recover_factor=2.0
        )
        feed(monitor, 5.0e7)
        assert monitor.policy().compact_wire is True
        # Inside the dead zone (above degrade, below recover): stays on.
        feed(monitor, 1.5e8)
        assert monitor.policy().compact_wire is True
        # Past the recover threshold: releases.
        feed(monitor, 2.5e8)
        assert monitor.policy().compact_wire is None
        # And re-engages only below the degrade threshold again.
        feed(monitor, 1.2e8)
        assert monitor.policy().compact_wire is None
        feed(monitor, 5.0e7)
        assert monitor.policy().compact_wire is True

    def test_window_scale_quantized_and_bounded(self):
        monitor = LinkMonitor(target_bandwidth_bps=4.0e8)
        feed(monitor, 2.9e8)  # raw scale ~1.38 -> sqrt(2) step
        scale = monitor.policy().window_scale
        assert scale in (1.0, 2.0**0.5)
        feed(monitor, 1.0)  # absurdly degraded: capped
        assert monitor.policy().window_scale == 8.0

    def test_rtt_alone_deepens_pipeline(self):
        """A healthy-bandwidth but high-RTT link (a 78 ms round trip)
        still wants more windows in flight."""
        monitor = LinkMonitor()
        feed(monitor, 8.0e8)
        for _ in range(20):
            monitor.observe_publish(0.078)
        policy = monitor.policy()
        assert policy.depth == 4
        assert policy.compact_wire is None

    def test_degenerate_observations_ignored(self):
        monitor = LinkMonitor()
        monitor.observe_staging(0, 0.1)
        monitor.observe_staging(100, 0.0)
        monitor.observe_staging(-5, -1.0)
        monitor.observe_publish(0.0)
        assert monitor.bandwidth_bps() is None
        assert monitor.rtt_s() is None
        stats = monitor.stats()
        assert stats["n_staging"] == 0
        assert stats["n_publish"] == 0


class TestCrossThreadCounters:
    def test_lock_hammer(self):
        """Concurrent observers and policy readers: every observation
        must be counted (a lost increment means the RMW is racy) and
        the EWMA must stay inside the observed envelope."""
        monitor = LinkMonitor()
        n_threads, per_thread = 8, 500
        barrier = threading.Barrier(n_threads)

        def hammer(tid: int) -> None:
            barrier.wait()
            for i in range(per_thread):
                # Alternate two honest rates so the EWMA has a bounded
                # envelope to be checked against.
                bps = 1.0e8 if (i + tid) % 2 else 4.0e8
                monitor.observe_staging(1_000_000, 1_000_000 / bps)
                monitor.observe_publish(0.001 + 0.0005 * (i % 3))
                if i % 50 == 0:
                    monitor.policy()
                    monitor.stats()

        threads = [
            threading.Thread(target=hammer, args=(t,))
            for t in range(n_threads)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        stats = monitor.stats()
        assert stats["n_staging"] == n_threads * per_thread
        assert stats["n_publish"] == n_threads * per_thread
        assert stats["bytes_observed"] == n_threads * per_thread * 1_000_000
        assert 1.0e8 <= stats["bandwidth_bps"] <= 4.0e8
        assert 0.001 <= stats["rtt_s"] <= 0.0025

    def test_stats_snapshot_is_one_coherent_read(self):
        """The stats() bugfix pin: policy fields and latch state must
        come from ONE lock acquisition. Writers slam the bandwidth
        estimate across the degrade/recover thresholds while readers
        assert the pairing that is impossible under a coherent snapshot
        to break: ``compact_wire is True`` exactly when ``degraded``
        (policy() forces compact iff the degraded latch is set). The
        pre-fix two-acquisition snapshot let observations land between
        computing the policy and reading the latch, so the pairing
        could tear."""
        monitor = LinkMonitor()
        stop = threading.Event()
        torn: list[dict] = []

        def writer(tid: int) -> None:
            nbytes = 16 * MB
            while not stop.is_set():
                # Full block convergence at each extreme: the EWMA (and
                # with it the latch) crosses a threshold on every block.
                for bps in (4.0e7, 8.0e8):
                    for _ in range(30):
                        monitor.observe_staging(nbytes, nbytes / bps)

        def reader() -> None:
            while not stop.is_set():
                stats = monitor.stats()
                if stats["degraded"] != (stats["compact_wire"] is True):
                    torn.append(stats)
                    return

        writers = [
            threading.Thread(target=writer, args=(t,)) for t in range(4)
        ]
        readers = [threading.Thread(target=reader) for _ in range(3)]
        for thread in writers + readers:
            thread.start()
        try:
            deadline = threading.Event()
            deadline.wait(1.0)
        finally:
            stop.set()
        for thread in writers + readers:
            thread.join()
        assert not torn, f"stats snapshot tore: {torn[0]}"


class TestPerSliceRtt:
    def test_worst_slice_drives_coalescing(self):
        monitor = LinkMonitor()
        for _ in range(20):
            monitor.observe_publish(0.01, slice_key="cpu:0")
            monitor.observe_publish(0.2, slice_key="cpu:1")
        assert monitor.policy().publish_coalesce > 1
        assert monitor.rtt_s("cpu:0") < monitor.rtt_s("cpu:1")

    def test_retired_slice_entry_expires(self, monkeypatch):
        """ADR 0115's 60 s rule: a slice whose jobs stopped must stop
        gating the policy within the TTL — its final congested estimate
        would otherwise latch publish coalescing forever."""
        from esslivedata_tpu.core import link_monitor as lm

        now = [1000.0]
        monkeypatch.setattr(lm.time, "monotonic", lambda: now[0])
        monitor = LinkMonitor()
        for _ in range(20):
            monitor.observe_publish(0.2, slice_key="cpu:1")  # congested
            monitor.observe_publish(0.01, slice_key="cpu:0")  # healthy
        assert monitor.policy().publish_coalesce > 1
        # The congested slice retires; the healthy one keeps reporting.
        now[0] += LinkMonitor._SLICE_TTL_S / 2
        monitor.observe_publish(0.01, slice_key="cpu:0")
        assert monitor.policy().publish_coalesce > 1  # cpu:1 still live
        now[0] += LinkMonitor._SLICE_TTL_S / 2 + 1.0
        monitor.observe_publish(0.01, slice_key="cpu:0")
        # cpu:1's entry is past the TTL: pruned from the policy read AND
        # from later snapshots; the latch releases on the healthy RTT.
        for _ in range(20):
            monitor.observe_publish(0.01, slice_key="cpu:0")
        policy = monitor.policy()
        assert policy.publish_coalesce == 1
        assert "cpu:1" not in monitor.stats()["rtt_by_slice"]

    def test_sliceless_samples_keep_global_estimate(self):
        monitor = LinkMonitor()
        for _ in range(10):
            monitor.observe_publish(0.02)
        assert monitor.rtt_s() is not None
        assert monitor.stats()["rtt_by_slice"] == {}


class TestFanoutDemandAxis:
    """ADR 0117: the broadcast plane's subscriber count + queue
    pressure drive publish coalescing — back off when nobody watches,
    tighten the instant a viewer attaches, mild widening under
    sustained consumer pressure (dead-zoned)."""

    def _clocked(self, monkeypatch, **kwargs):
        from esslivedata_tpu.core import link_monitor as lm

        now = [1000.0]
        monkeypatch.setattr(lm.time, "monotonic", lambda: now[0])
        return LinkMonitor(**kwargs), now

    def test_neutral_until_a_plane_reports(self):
        monitor = LinkMonitor()
        policy = monitor.policy()
        assert policy.publish_coalesce == 1
        assert policy.fanout_coalesce == 1
        assert monitor.stats()["fanout_subscribers"] is None

    def test_idle_backoff_after_grace_not_before(self, monkeypatch):
        monitor, now = self._clocked(monkeypatch)
        monitor.observe_fanout(0, 0.0)
        # Inside the grace window: a reconnect blip must not widen.
        now[0] += 2.0
        assert monitor.policy().fanout_coalesce == 1
        # Grace elapsed with nobody watching: back off.
        now[0] += 9.0
        policy = monitor.policy()
        assert policy.fanout_coalesce == 4
        assert policy.publish_coalesce == 4

    def test_attach_tightens_instantly(self, monkeypatch):
        monitor, now = self._clocked(monkeypatch)
        monitor.observe_fanout(0, 0.0)
        now[0] += 60.0
        assert monitor.policy().publish_coalesce == 4
        # One subscriber attaches: no hysteresis wait for fresh data.
        monitor.observe_fanout(1, 0.0)
        policy = monitor.policy()
        assert policy.fanout_coalesce == 1
        assert policy.publish_coalesce == 1

    def test_idle_clock_restarts_after_every_attach(self, monkeypatch):
        monitor, now = self._clocked(monkeypatch)
        monitor.observe_fanout(0, 0.0)
        now[0] += 60.0
        monitor.observe_fanout(3, 0.0)
        monitor.observe_fanout(0, 0.0)  # viewers left again
        now[0] += 5.0
        assert monitor.policy().fanout_coalesce == 1  # grace restarted
        now[0] += 6.0
        assert monitor.policy().fanout_coalesce == 4

    def test_pressure_latch_with_dead_zone(self):
        monitor = LinkMonitor()
        monitor.observe_fanout(5, 0.9)  # over the high watermark
        assert monitor.policy().fanout_coalesce == 2
        # Inside the dead zone: latched.
        monitor.observe_fanout(5, 0.5)
        assert monitor.policy().fanout_coalesce == 2
        # Under the low watermark: released.
        monitor.observe_fanout(5, 0.1)
        assert monitor.policy().fanout_coalesce == 1

    def test_widest_axis_wins_and_cap_holds(self, monkeypatch):
        monitor, now = self._clocked(
            monkeypatch, fanout_idle_coalesce=16, max_publish_coalesce=8
        )
        # RTT latch engaged at width 4 (88 ms over the 50 ms threshold).
        for _ in range(40):
            monitor.observe_publish(0.088)
        assert monitor.policy().publish_coalesce == 4
        # Idle backoff wider than RTT: fanout wins, capped at max.
        monitor.observe_fanout(0, 0.0)
        now[0] += 60.0
        policy = monitor.policy()
        assert policy.fanout_coalesce == 8  # capped
        assert policy.publish_coalesce == 8
        # Viewer attaches: RTT width remains the binding axis.
        monitor.observe_fanout(2, 0.0)
        policy = monitor.policy()
        assert policy.fanout_coalesce == 1
        assert policy.publish_coalesce == 4

    def test_stats_surface_and_coherence(self):
        monitor = LinkMonitor()
        monitor.observe_fanout(7, 0.3)
        stats = monitor.stats()
        assert stats["fanout_subscribers"] == 7
        assert stats["fanout_pressure"] == 0.3
        assert stats["fanout_coalesce"] == stats["publish_coalesce"] == 1

    def test_stats_lock_hammer_includes_fanout_fields(self):
        """Extend the PR 9 stats-coherence contract: concurrent
        observe_fanout + stats() never tear (fanout_coalesce > 1 must
        imply the snapshot saw zero subscribers or high pressure)."""
        monitor = LinkMonitor(fanout_idle_grace_s=0.0)
        stop = threading.Event()
        errors: list[str] = []

        def feeder():
            i = 0
            while not stop.is_set():
                monitor.observe_fanout(i % 2, 0.0)
                i += 1

        def reader():
            while not stop.is_set():
                stats = monitor.stats()
                if (
                    stats["fanout_coalesce"] > 1
                    and stats["fanout_subscribers"] not in (0, None)
                ):
                    errors.append(str(stats))
                    return

        threads = [threading.Thread(target=feeder)] + [
            threading.Thread(target=reader) for _ in range(2)
        ]
        for t in threads:
            t.start()
        import time as _time

        _time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=5.0)
        assert not errors, errors[0]
