#!/usr/bin/env python
"""Headline benchmark: ev44 events/sec on the LOKI-style 2-D pixel x TOF
histogram (BASELINE.json config 2), single chip.

One process that runs on the device jax finds: it imports jax once,
starts no child, and exits non-zero when the platform is not ``tpu``
(unless ``--cpu`` asks for the XLA-CPU backend, or ``--smoke`` for the
structural CPU check) or when a section raises.

Times the kernel hot path on pre-made uniform-random batches:
host-staged padded event batches -> device transfer -> jitted scatter-add
step with donated HBM-resident state (no decode, no JobManager, no
publish — ``chip_smoke.py`` drives the served path). Prints ONE JSON
line naming the device it ran on:

    {"metric": ..., "value": ev_per_s, "unit": "events/s",
     "platform": ..., "device_kind": ..., "device_count": ..., ...}

``vs_baseline`` is the speedup over a single-threaded numpy scatter-add
(np.add.at) of the same workload measured in-process — the closest available
stand-in for the reference's CPU path (scipp is not installed here; its
threaded C++ hist is typically within ~2-5x of np.add.at for this access
pattern). The absolute target from BASELINE.json is >= 1e8 events/s/chip.

Usage: python bench.py [--events N] [--batches N] [--method scatter|sort]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np


def make_batch(n_events: int, n_pixel: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n_pixel, n_events).astype(np.int32)
    toa = rng.uniform(0.0, 71_000_000.0, n_events).astype(np.float32)
    return pid, toa


def telemetry_snapshot() -> dict:
    """Compact process-registry snapshot (ADR 0116) embedded in every
    scenario's JSON line: recorded lines then carry the
    dispatch/compile/RTT decomposition alongside throughput, not just
    the headline number. Empty dict if telemetry is unavailable (a
    bench must never fail on its own instrumentation)."""
    try:
        from esslivedata_tpu.telemetry import REGISTRY

        return REGISTRY.snapshot(compact=True)
    except Exception:
        return {}


def emit_line(line: dict) -> None:
    """Print one scenario metric line (stderr), with the registry
    snapshot attached under ``telemetry``."""
    line.setdefault("telemetry", telemetry_snapshot())
    print(json.dumps(line), file=sys.stderr)


def make_replay_batches(
    path: str, n_events: int, n_distinct: int, n_pixel: int
):
    """Batches drawn from a recorded NeXus event file (bench config 2
    with a REAL pixel/TOF distribution instead of uniform random —
    scripts/make_replay_nexus.py synthesizes one; any ESS recording with
    NXevent_data works)."""
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.services.fake_sources import load_nexus_events

    recordings = load_nexus_events(path)
    if not recordings:
        raise SystemExit(f"--replay {path}: no recorded NXevent_data found")
    rec = next(iter(recordings.values()))
    ids = rec.event_id.astype(np.int32) % n_pixel
    toa = rec.event_time_offset.astype(np.float32)
    need = n_events * n_distinct
    reps = -(-need // ids.size)
    ids = np.tile(ids, reps)[:need]
    toa = np.tile(toa, reps)[:need]
    return [
        EventBatch.from_arrays(
            ids[i * n_events : (i + 1) * n_events],
            toa[i * n_events : (i + 1) * n_events],
        )
        for i in range(n_distinct)
    ]


def measure_decode_ms(n_events: int) -> float | None:
    """Mean wall ms to decode one ev44 payload of ``n_events`` events —
    the stage the headline loop skips (its batches are pre-made). None
    when the wire codec is unavailable (minimal installs)."""
    try:
        from esslivedata_tpu.kafka import wire
    except Exception:
        return None
    rng = np.random.default_rng(5)
    payload = wire.encode_ev44(
        "bench",
        0,
        np.array([0]),
        np.array([0]),
        rng.uniform(0, 7.0e7, n_events).astype(np.int32),
        pixel_id=rng.integers(0, 1 << 20, n_events).astype(np.int32),
    )
    reps = 5
    wire.decode_ev44(payload)  # warm
    start = time.perf_counter()
    for _ in range(reps):
        wire.decode_ev44(payload)
    return 1e3 * (time.perf_counter() - start) / reps


def bench_numpy_baseline(
    pid: np.ndarray, toa: np.ndarray, n_pixel: int, n_toa: int, lo: float, hi: float
) -> float:
    """Events/s for a single-threaded numpy scatter-add of the same step."""
    hist = np.zeros((n_pixel, n_toa), dtype=np.float32)
    inv_w = n_toa / (hi - lo)
    # One warm-up + 3 timed reps on a slice to keep baseline wall time sane.
    n = min(len(pid), 2_000_000)
    p, t = pid[:n], toa[:n]
    reps = 3
    start = time.perf_counter()
    for _ in range(reps):
        tb = ((t - lo) * inv_w).astype(np.int32)
        ok = (t >= lo) & (t < hi) & (p >= 0) & (p < n_pixel)
        flat = p[ok].astype(np.int64) * n_toa + tb[ok]
        np.add.at(hist.reshape(-1), flat, 1.0)
    dt = time.perf_counter() - start
    return n * reps / dt


def bench_secondary_configs(args, edges, batches, method: str) -> None:
    # pallas2d tuning knobs apply to EVERY histogrammer built with the
    # swept method — otherwise a sweep silently measures defaults.
    p2 = {
        "pallas2d_budget": args.pallas2d_budget,
        "pallas2d_chunk": args.pallas2d_chunk,
        "pallas2d_precision": args.pallas2d_precision,
    }
    """BASELINE configs 1/3/4/5 (config 2 is the headline measurement).

    1: dummy 1-D TOF monitor histogram; 3: 9-bank multibank (sharded when
    >1 device, else bank-LUT single chip); 4: monitor-normalized output
    per step; 5: exponential-decay rolling window. Reported on stderr.
    """
    import jax
    import jax.numpy as jnp

    from esslivedata_tpu.ops import EventHistogrammer

    def timed(label: str, hist, step=None, post=None, **extra) -> None:
        """One warmed, timed loop; ``step(state, batch)`` defaults to the
        host-flattened fast path, ``post(state)`` optionally adds per-step
        work (e.g. monitor normalization) kept on device."""
        if step is None:
            step = lambda s, b: hist.step_flat(  # noqa: E731
                s, hist.flatten_host(b.pixel_id, b.toa)
            )
        state = hist.init_state()
        state = step(state, batches[0])
        state.window.block_until_ready()
        start = time.perf_counter()
        for i in range(args.batches):
            state = step(state, batches[i % len(batches)])
            if post is not None:
                last = post(state)
        state.window.block_until_ready()
        if post is not None:
            last.block_until_ready()
        dt = time.perf_counter() - start
        print(
            json.dumps(
                {
                    "metric": label,
                    "value": args.events * args.batches / dt,
                    "unit": "events/s",
                    **extra,
                }
            ),
            file=sys.stderr,
        )

    # Config 1: 1-D monitor histogram (single screen row, 1000 bins).
    edges_1d = np.linspace(0.0, 71_000_000.0, 1001)
    timed(
        "config1_monitor_1d_tof_histogram",
        EventHistogrammer(toa_edges=edges_1d, n_screen=1, method=method, **p2),
    )
    # The VMEM-sized bin space is where the pallas one-hot kernel can
    # beat the serial scatter: measure it alongside for the record
    # (interpret mode off-TPU is meaninglessly slow — TPU only).
    if jax.default_backend() == "tpu" and method != "pallas":
        try:
            timed(
                "config1_monitor_1d_pallas",
                EventHistogrammer(
                    toa_edges=edges_1d, n_screen=1, method="pallas"
                ),
            )
        except Exception:
            traceback.print_exc()

    # Headline-space pallas2d A/B (VERDICT r4 item 2): the MXU-tiled
    # kernel against the serial scatter on the SAME 1.5Mx100 bin space.
    # Device-resident rates (inputs pre-staged on device, donated state
    # stepped back-to-back) isolate the kernel from host flatten/
    # partition and link bandwidth; the e2e line includes them. TPU
    # only: interpret mode is meaninglessly slow.
    if jax.default_backend() == "tpu":
        try:
            reps = min(args.batches, 16)

            def timed_device(label, h, inputs, step, **extra):
                state = h.init_state()
                # Warm every distinct input SHAPE (chunk-bucket sizes
                # differ across batches): a compile inside the short
                # timed loop would skew the A/B.
                shapes = set()
                for inp in inputs:
                    key = jax.tree.map(lambda a: a.shape, inp)
                    if (k := str(key)) not in shapes:
                        shapes.add(k)
                        state = step(state, inp)
                state.window.block_until_ready()
                start = time.perf_counter()
                for i in range(reps):
                    state = step(state, inputs[i % len(inputs)])
                state.window.block_until_ready()
                dt = time.perf_counter() - start
                print(
                    json.dumps(
                        {
                            "metric": label,
                            "value": args.events * reps / dt,
                            "unit": "events/s",
                            **extra,
                        }
                    ),
                    file=sys.stderr,
                )

            h_sc = EventHistogrammer(
                toa_edges=edges, n_screen=args.pixels, method="scatter"
            )
            flats = [
                jax.device_put(
                    h_sc.flatten_host(b.pixel_id, b.toa)
                ).block_until_ready()
                for b in batches
            ]
            timed_device(
                "headline_scatter_device_resident",
                h_sc,
                flats,
                lambda s, f: h_sc._step_flat(s, f),
            )
            h_p2 = EventHistogrammer(
                toa_edges=edges,
                n_screen=args.pixels,
                method="pallas2d",
                pallas2d_budget=args.pallas2d_budget,
                pallas2d_chunk=args.pallas2d_chunk,
                pallas2d_precision=args.pallas2d_precision,
            )
            parts = []
            for b in batches:
                ev, cm = h_p2.flatten_partition_host(b.pixel_id, b.toa)
                parts.append(
                    (
                        jax.device_put(ev).block_until_ready(),
                        jax.device_put(cm).block_until_ready(),
                    )
                )
            timed_device(
                "headline_pallas2d_device_resident",
                h_p2,
                parts,
                lambda s, p: h_p2._step_part(s, *p),
                bpb=h_p2._bpb,
            )
            if method != "pallas2d":
                # End-to-end (host partition + link + kernel), only when
                # the graded headline didn't already measure it.
                timed(
                    "headline_pallas2d_e2e",
                    h_p2,
                    step=h_p2.step_batch,
                )
        except Exception:
            traceback.print_exc()

    # Config 3: 9-bank multibank view.
    n_banks, per_bank = 9, 1 + (args.pixels - 1) // 9
    bank_lut = (np.arange(args.pixels, dtype=np.int32) // per_bank).astype(
        np.int32
    )
    if len(jax.devices()) > 1:
        from esslivedata_tpu.parallel import ShardedHistogrammer, make_mesh

        n_dev = len(jax.devices())
        bank_axis = 3 if n_dev % 3 == 0 else 1
        mesh = make_mesh(n_dev, data=n_dev // bank_axis, bank=bank_axis)
        # Screen rows = banks, padded up to a multiple of the bank axis.
        n_screen = -(-n_banks // bank_axis) * bank_axis
        sharded = ShardedHistogrammer(
            toa_edges=edges,
            n_screen=n_screen,
            mesh=mesh,
            pixel_lut=bank_lut,
        )
        timed(
            "config3_multibank_sharded",
            sharded,
            step=lambda s, b: sharded.step(s, b.pixel_id, b.toa),
            devices=n_dev,
        )
    else:
        # Single chip: the REAL Q-E rebinning over BIFROST's 45-triplet
        # analyzer geometry (BASELINE wording: "multi-analyzer Q-E
        # rebinning across 9 detector banks": the nine channels) —
        # per-event physics rides the precompiled (pixel, toa-bin) ->
        # (Q, E) table, so the streaming cost is the same gather+scatter
        # as the histogram. A kernel loop on an unwrapped TOA axis of
        # its own; the deployment through its service, on the wire's
        # frame, is the benchmark's cell bifrost_qe.paced14.
        from esslivedata_tpu.config.instrument import instrument_registry

        instrument_registry["bifrost"].load_factories()
        from esslivedata_tpu.config.instruments.bifrost.specs import (
            analyzer_geometry,
        )
        from esslivedata_tpu.ops import EventBatch as _EB
        from esslivedata_tpu.ops.qhistogram import (
            QHistogrammer,
            build_qe_map,
        )

        geometry = analyzer_geometry()
        qe_toa = np.linspace(8.0e7, 4.0e8, 321)
        qe_map = build_qe_map(
            two_theta=geometry["two_theta"],
            ef_mev=geometry["ef_mev"],
            l2=geometry["l2"],
            pixel_ids=geometry["pixel_ids"],
            toa_edges=qe_toa,
            q_edges=np.linspace(0.2, 2.6, 81),
            e_edges=np.linspace(-3.0, 6.0, 61),
        )
        qe_hist = QHistogrammer(qmap=qe_map, toa_edges=qe_toa, n_q=80 * 60)
        rng = np.random.default_rng(7)
        id_lo = int(geometry["pixel_ids"].min())
        id_hi = int(geometry["pixel_ids"].max()) + 1
        qe_batches = [
            _EB.from_arrays(
                rng.integers(id_lo, id_hi, args.events).astype(np.int32),
                rng.uniform(8.0e7, 4.0e8, args.events).astype(np.float32),
            )
            for _ in range(4)
        ]
        def timed_qe(label: str, hist) -> None:
            state = hist.init_state()
            state = hist.step(state, qe_batches[0], 100.0)
            state.window.block_until_ready()
            start = time.perf_counter()
            for i in range(args.batches):
                state = hist.step(
                    state, qe_batches[i % len(qe_batches)], 100.0
                )
            state.window.block_until_ready()
            dt = time.perf_counter() - start
            print(
                json.dumps(
                    {
                        "metric": label,
                        "value": args.events * args.batches / dt,
                        "unit": "events/s",
                        "banks": 45,
                    }
                ),
                file=sys.stderr,
            )

        timed_qe("config3_bifrost_qe_rebinning", qe_hist)
        # The Q-E bin space (80x60) fits the pallas kernel: measure the
        # one-hot variant alongside on real hardware.
        if jax.default_backend() == "tpu":
            try:
                timed_qe(
                    "config3_bifrost_qe_pallas",
                    QHistogrammer(
                        qmap=qe_map,
                        toa_edges=qe_toa,
                        n_q=80 * 60,
                        method="pallas",
                    ),
                )
            except Exception:
                traceback.print_exc()

    # Config 4: monitor-normalized output computed per step (on device —
    # the normalized array is the job's published output, not a host read).
    monitor_total = jnp.asarray(1.0e4)
    timed(
        "config4_monitor_normalized",
        EventHistogrammer(
            toa_edges=edges, n_screen=args.pixels, method=method, **p2
        ),
        post=lambda s: s.window / monitor_total,
    )

    # Config 5: exponential-decay rolling window.
    timed(
        "config5_decay_window",
        EventHistogrammer(
            toa_edges=edges, n_screen=args.pixels, decay=0.95, method=method, **p2
        ),
    )


def bench_multijob(args) -> None:
    """K jobs, ONE detector stream: the stage-once + fused-stepping
    scenario (ADR 0110).

    Before the DeviceEventCache, K subscribed jobs each flattened and
    transferred identical batches — wire bytes and host ingest CPU scaled
    as K x. With stage-once the staging is per (stream, layout) and the
    fused stepping layer advances all K states in one dispatch, so
    wire_bytes_per_event must stay ~flat in K (acceptance: K=4 within
    1.1x of K=1) while aggregate events/s grows toward K x. Runs through
    the REAL job path — JobManager fan-out, fused dispatch, per-job
    fused publish — not a stripped kernel loop. Reported on stderr, one
    JSON line per K plus a summary line.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    # Smaller screen than the headline: each job owns a private state
    # pair, so K=4 at full LOKI scale would be ~5 GB of HBM just for
    # accumulators — the scenario measures staging amortization, which
    # is screen-size independent.
    side = int(np.sqrt(min(args.pixels, 1 << 16)))
    det = np.arange(side * side).reshape(side, side)
    n_events = args.events
    n_windows = max(4, args.batches // 4)
    n_distinct = 4
    staged = []
    for s in range(n_distinct):
        pid, toa = make_batch(n_events, side * side, seed=100 + s)
        staged.append(
            StagedEvents(
                batch=EventBatch.from_arrays(pid, toa),
                first_timestamp=None,
                last_timestamp=None,
                n_chunks=1,
            )
        )
    method = args.method if args.method in ("scatter", "sort") else "scatter"

    results = {}
    for k in (1, 4):
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench", name=f"dv_k{k}", source_names=["det0"]
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(job_factory=JobFactory(reg), job_threads=min(4, k))
        for _ in range(k):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        t0, t1 = Timestamp.from_ns(0), Timestamp.from_ns(1)
        mgr.process_jobs({"det0": staged[0]}, start=t0, end=t1)  # warm
        mgr.event_cache_stats()  # drain warm-up staging
        start = time.perf_counter()
        for i in range(n_windows):
            out = mgr.process_jobs(
                {"det0": staged[i % n_distinct]},
                start=t0,
                end=Timestamp.from_ns(2 + i),
            )
            assert len(out) == k, f"expected {k} results, got {len(out)}"
        dt = time.perf_counter() - start
        stats = mgr.event_cache_stats()
        total_events = n_events * n_windows
        line = {
            "metric": "multijob_shared_stream_ingest",
            "jobs": k,
            "value": k * total_events / dt,
            "unit": "events/s",
            "events_per_sec_aggregate": k * total_events / dt,
            "wire_bytes_per_event": stats["bytes_staged"] / total_events,
            "stage_hit_rate": stats["hit_rate"],
            "stage_misses": stats["misses"],
            "windows": n_windows,
            "events_per_window": n_events,
        }
        results[k] = line
        emit_line(line)
        mgr.shutdown()
    k1, k4 = results[1], results[4]
    print(
        json.dumps(
            {
                "metric": "multijob_stage_once_summary",
                "k4_vs_k1_aggregate_throughput": (
                    k4["events_per_sec_aggregate"]
                    / k1["events_per_sec_aggregate"]
                ),
                # ~1.0 = stage-once working (acceptance bound: <= 1.1)
                "k4_vs_k1_wire_bytes_ratio": (
                    k4["wire_bytes_per_event"]
                    / max(k1["wire_bytes_per_event"], 1e-12)
                ),
            }
        ),
        file=sys.stderr,
    )


def bench_publish(args) -> dict:
    """Cross-job publish combining through the REAL JobManager path
    (ADR 0113).

    K detector-view jobs on one stream, publishing every window: before
    the PublishCombiner each job paid its own publish execute + fetch
    (K device round trips per tick, overlapped but not combined); with
    combining every job due in a tick is served from ONE execute + ONE
    packed fetch per device, and layout-constant outputs (the zero ROI
    blocks here) are fetched once per layout digest instead of every
    tick. Reads the process-wide publish counters (ops/publish.METRICS)
    drained around the measured loop, so the reported executes/fetches
    are exactly the device round trips the publish path performed.

    Acceptance (asserted here AND in --smoke/CI): fetches per tick == 1
    at K=4 — the K=4/K=1 round-trip ratio is 1.0 — and steady-state
    static bytes == 0 (statics served from the host cache).
    One JSON line per K plus a summary line, on stderr.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.publish import METRICS
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 14)))
    det = np.arange(side * side).reshape(side, side)
    n_events = min(args.events, 1 << 18)
    n_windows = max(6, args.batches // 4)
    n_distinct = 4
    staged = []
    for s in range(n_distinct):
        pid, toa = make_batch(n_events, side * side, seed=300 + s)
        staged.append(
            StagedEvents(
                batch=EventBatch.from_arrays(pid, toa),
                first_timestamp=None,
                last_timestamp=None,
                n_chunks=1,
            )
        )
    method = args.method if args.method in ("scatter", "sort") else "scatter"

    results = {}
    for k in (1, 4):
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench", name=f"dv_pub_k{k}", source_names=["det0"]
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        # tick_program=False: this scenario measures the ADR 0113
        # PublishCombiner path (the tick program would otherwise route
        # around it and the publish_combining metric would silently
        # change meaning vs the PERF.md round-7 numbers); the ADR 0114
        # tick path has its own --tick scenario.
        mgr = JobManager(
            job_factory=JobFactory(reg),
            job_threads=min(4, k),
            tick_program=False,
        )
        for _ in range(k):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        t0 = Timestamp.from_ns(0)
        # Two warm windows: the first compiles the static-inclusive
        # publish (and fetches the layout's statics once), the second
        # the steady-state dynamic-only program.
        for w in range(2):
            out = mgr.process_jobs(
                {"det0": staged[w]}, start=t0, end=Timestamp.from_ns(1 + w)
            )
            assert len(out) == k
        METRICS.drain()
        start = time.perf_counter()
        for i in range(n_windows):
            out = mgr.process_jobs(
                {"det0": staged[i % n_distinct]},
                start=t0,
                end=Timestamp.from_ns(3 + i),
            )
            assert len(out) == k, f"expected {k} results, got {len(out)}"
        dt = time.perf_counter() - start
        m = METRICS.drain()
        mgr.shutdown()
        line = {
            "metric": "publish_combining",
            "jobs": k,
            "value": m["fetches"] / n_windows,
            "unit": "fetches/tick",
            "executes_per_tick": m["executes"] / n_windows,
            "fetches_per_tick": m["fetches"] / n_windows,
            "fetched_bytes_per_publish": (
                (m["dynamic_bytes"] + m["static_bytes"])
                / max(m["fetches"], 1)
            ),
            "dynamic_bytes_per_tick": m["dynamic_bytes"] / n_windows,
            "static_bytes_total": m["static_bytes"],
            "combined_jobs_per_publish": (
                m["combined_jobs"] / m["combined_publishes"]
                if m["combined_publishes"]
                else 1.0
            ),
            "events_per_sec_aggregate": k * n_events * n_windows / dt,
            "windows": n_windows,
            "events_per_window": n_events,
        }
        results[k] = line
        emit_line(line)
    k1, k4 = results[1], results[4]
    # The acceptance bound: K jobs due in one tick publish via exactly
    # one execute + one fetch; statics never refetch in steady state.
    assert k4["fetches_per_tick"] == 1.0, k4
    assert k4["executes_per_tick"] == 1.0, k4
    assert k1["fetches_per_tick"] == 1.0, k1
    assert k4["static_bytes_total"] == 0, k4
    summary = {
        "metric": "publish_combining_summary",
        # 1.0 = combining working: K=4 pays the same round trips per
        # tick as K=1 (the pre-combining ratio was 4.0).
        "k4_vs_k1_fetches_per_tick_ratio": (
            k4["fetches_per_tick"] / k1["fetches_per_tick"]
        ),
        "k4_vs_k1_fetched_bytes_ratio": (
            k4["fetched_bytes_per_publish"]
            / max(k1["fetched_bytes_per_publish"], 1e-12)
        ),
    }
    print(json.dumps(summary), file=sys.stderr)
    return results[4]


def bench_tick(args) -> dict:
    """One-dispatch tick programs through the REAL JobManager path
    (ADR 0114).

    K=4 same-layout detector-view jobs on one stream, publishing every
    window. Without the tick program a steady-state window pays up to
    three device dispatches: the staging transfer
    (stage-once cache miss — every window carries new events), the
    fused ``step_many`` dispatch, and the combined publish execute +
    fetch (ADR 0113). With it the step and publish fuse into ONE jitted
    tick program: one execute + one fetch per tick, with the staging
    transfer overlapped (async ``device_put``; prestaged entirely away
    under the pipelined ingest).

    Reads the process-wide publish counters (ops/publish.METRICS) and
    the stage-once cache stats drained around the measured loop, so the
    per-tick RTT decomposition (staging transfers / separate step
    dispatches / publish executes / fetches) is exactly the device
    traffic each path performed.

    Acceptance (asserted here AND in --smoke/CI): with the tick program
    a steady-state tick is exactly 1 execute + 1 fetch + 0 separate
    step dispatches at K=4 (the no-tick reference pays 1 fetch but >=2
    dispatches), steady-state static bytes == 0, every window actually
    rode a tick program, and the da00 wire output is byte-identical to
    the separate-dispatch path. One JSON line per mode plus a summary
    line, on stderr.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
    from esslivedata_tpu.kafka.wire import encode_da00
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.publish import METRICS
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 14)))
    det = np.arange(side * side).reshape(side, side)
    n_events = min(args.events, 1 << 18)
    n_windows = max(6, args.batches // 4)
    n_distinct = 4
    k = 4
    staged_batches = []
    for s in range(n_distinct):
        pid, toa = make_batch(n_events, side * side, seed=400 + s)
        staged_batches.append(EventBatch.from_arrays(pid, toa))

    def staged(i: int) -> StagedEvents:
        return StagedEvents(
            batch=staged_batches[i % n_distinct],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    method = args.method if args.method in ("scatter", "sort") else "scatter"

    def make_mgr(tick_program: bool) -> JobManager:
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench",
            name=f"dv_tick_{int(tick_program)}",
            source_names=["det0"],
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg),
            job_threads=min(4, k),
            tick_program=tick_program,
        )
        for _ in range(k):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        return mgr

    from esslivedata_tpu.telemetry import COMPILE_EVENTS

    t0 = Timestamp.from_ns(0)
    results = {}
    wire: dict[bool, list[list[bytes]]] = {}
    for tick_program in (False, True):
        compiles_before = COMPILE_EVENTS.total()
        mgr = make_mgr(tick_program)
        # Warm windows: the first compiles the static-inclusive program
        # variant (and fetches the layout's statics once), the second
        # the steady-state dynamic-only variant.
        for w in range(2):
            out = mgr.process_jobs(
                {"det0": staged(w)}, start=t0, end=Timestamp.from_ns(1 + w)
            )
            assert len(out) == k
        METRICS.drain()
        mgr.event_cache_stats()  # drain staging counters
        compiles_warm = COMPILE_EVENTS.total()
        wire[tick_program] = []
        start = time.perf_counter()
        for i in range(n_windows):
            out = mgr.process_jobs(
                {"det0": staged(i)}, start=t0, end=Timestamp.from_ns(3 + i)
            )
            assert len(out) == k, f"expected {k} results, got {len(out)}"
            wire[tick_program].append(
                [
                    encode_da00(name, 12345, dataarray_to_da00(da))
                    for res in out
                    for name, da in res.outputs.items()
                ]
            )
        dt = time.perf_counter() - start
        m = METRICS.drain()
        cache = mgr.event_cache_stats()
        compiles_steady = COMPILE_EVENTS.total() - compiles_warm
        mgr.shutdown()
        # The per-tick RTT decomposition: every class of device traffic
        # a steady-state window pays, per tick.
        decomposition = {
            "staging_transfers": cache["misses"] / n_windows,
            "staged_bytes": cache["bytes_staged"] / n_windows,
            "step_executes": m["step_executes"] / n_windows,
            "publish_executes": m["executes"] / n_windows,
            "fetches": m["fetches"] / n_windows,
        }
        line = {
            "metric": "tick_program",
            "tick_program": tick_program,
            "jobs": k,
            # Graded value: device dispatches per steady-state tick —
            # the quantity the tick program collapses to 1.
            "value": (m["executes"] + m["step_executes"]) / n_windows,
            "unit": "dispatches/tick",
            "executes_per_tick": m["executes"] / n_windows,
            "fetches_per_tick": m["fetches"] / n_windows,
            "step_executes_per_tick": m["step_executes"] / n_windows,
            "tick_publishes": m["tick_publishes"],
            "static_bytes_total": m["static_bytes"],
            "rtt_decomposition_per_tick": decomposition,
            "wall_ms_per_tick": 1e3 * dt / n_windows,
            "events_per_sec_aggregate": k * n_events * n_windows / dt,
            "windows": n_windows,
            "events_per_window": n_events,
            # Compile-event instrument (ADR 0116): warmup MUST compile
            # (the instrument sees the misses the RTT estimator only
            # excludes) and the measured steady state must not — a
            # steady-state compile means the jit key churns per window,
            # exactly the regression this field exists to catch.
            "compile_events_warmup": compiles_warm - compiles_before,
            "compile_events_steady": compiles_steady,
        }
        results[tick_program] = line
        emit_line(line)
        assert line["compile_events_warmup"] >= 1, line
        assert line["compile_events_steady"] == 0, line

    # Byte-identity: the tick program may not change a single da00 wire
    # byte vs the separate fused-step + combined-publish dispatches.
    for w, (ref, tick) in enumerate(zip(wire[False], wire[True])):
        assert ref == tick, f"window {w}: tick da00 wire != combined wire"

    ref, tick = results[False], results[True]
    # The acceptance bound: a steady-state tick is exactly ONE device
    # execute + ONE fetch with the tick program (vs >= 2 dispatches on
    # the separate path; >= 3 round trips counting the staging
    # transfer), every window actually ticked, and statics never
    # refetch in steady state.
    assert tick["executes_per_tick"] == 1.0, tick
    assert tick["fetches_per_tick"] == 1.0, tick
    assert tick["step_executes_per_tick"] == 0.0, tick
    assert tick["tick_publishes"] == n_windows, tick
    assert tick["static_bytes_total"] == 0, tick
    assert ref["value"] >= 2.0, ref
    summary = {
        "metric": "tick_program_summary",
        # >= 2.0 = the tick program halves (or better) the per-tick
        # dispatch count; the staging transfer overlap is on top.
        "dispatch_reduction": ref["value"] / tick["value"],
        "wire_byte_identical": True,
        "wall_ms_per_tick_ref": ref["wall_ms_per_tick"],
        "wall_ms_per_tick_tick": tick["wall_ms_per_tick"],
    }
    print(json.dumps(summary), file=sys.stderr)
    return tick


def bench_workloads(args) -> dict:
    """Workload plane through the REAL JobManager path (ADR 0122).

    Three families on one stream — powder focusing (calibration-LUT
    TOF->d, veto-filtered), a pass-all-filtered detector view, and the
    imaging view (flat-field at publish) — each a (stream, fuse-key)
    tick group of K=2 jobs.

    Acceptance (asserted here AND in --smoke/CI):

    - With per-event filters ACTIVE, a steady-state tick is still
      exactly 1 execute + 1 fetch per group and 0 separate step
      dispatches — filtering is a host batch transform, zero extra
      device round trips.
    - The pass-all-filtered detector view's da00 wire is BYTE-IDENTICAL
      to an unfiltered reference (predicates-pass-all identity).
    - A live calibration swap re-keys the tick program and the ADR 0116
      instrument classifies the resulting compile as ``layout_swap``;
      with the AOT warm-up attached (ADR 0118) the same swap's compile
      lands OFF the hot path — commit-time ``livedata_jit_compiles``
      delta 0.

    One JSON line on stderr.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.durability import CompileWarmupService
    from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
    from esslivedata_tpu.kafka.wire import encode_da00
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.publish import METRICS
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.telemetry import COMPILE_EVENTS
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewWorkflow,
        project_logical,
    )
    from esslivedata_tpu.workloads import (
        CalibrationTable,
        FilterChain,
        ImagingViewParams,
        ImagingViewWorkflow,
        PowderFocusParams,
        PowderFocusWorkflow,
        PulseVetoFilter,
        ToaRangeFilter,
    )

    n_pix = 1 << 10
    side = int(np.sqrt(n_pix))
    det = np.arange(n_pix).reshape(side, side)
    n_events = min(args.events, 1 << 16)
    n_windows = max(6, args.batches // 4)
    toa_hi = 71e6

    def make_calib(version=1, tzero=0.0) -> CalibrationTable:
        return CalibrationTable(
            name="bench_cal",
            version=version,
            columns={
                "difc": np.linspace(2.0e7, 3.0e7, n_pix),
                "tzero": np.full(n_pix, tzero),
            },
        )

    veto = FilterChain(
        [PulseVetoFilter(windows=((1e6, 4e6),), period_ns=toa_hi)]
    )
    passall = FilterChain([ToaRangeFilter(lo_ns=-1e18, hi_ns=1e18)])

    makes = {
        "powder": lambda: PowderFocusWorkflow(
            calibration=make_calib(),
            params=PowderFocusParams(d_bins=256),
            filters=veto,
        ),
        "detview": lambda: DetectorViewWorkflow(
            projection=project_logical(det), filters=passall
        ),
        "imaging": lambda: ImagingViewWorkflow(
            detector_number=det,
            params=ImagingViewParams(frames=4, toa_high=toa_hi),
            filters=veto,
        ),
    }

    def make_mgr(factories) -> JobManager:
        reg = WorkflowFactory()
        mgr = JobManager(job_factory=JobFactory(reg), job_threads=4)
        for name, make in factories.items():
            spec = WorkflowSpec(
                instrument="bench_wl", name=name, source_names=["det0"]
            )
            reg.register_spec(spec).attach_factory(
                lambda *, source_name, params, _m=make: _m()
            )
            for _ in range(2):
                mgr.schedule_job(
                    WorkflowConfig(
                        identifier=spec.identifier,
                        job_id=JobId(source_name="det0"),
                    )
                )
        return mgr

    def layout_swaps() -> float:
        return COMPILE_EVENTS.total(trigger="layout_swap")

    t0 = Timestamp.from_ns(0)
    rng = np.random.default_rng(4600)
    batches = [
        EventBatch.from_arrays(
            rng.integers(0, n_pix, n_events),
            rng.uniform(0, toa_hi, n_events).astype(np.float32),
        )
        for _ in range(4)
    ]

    def staged(i: int) -> StagedEvents:
        return StagedEvents(
            batch=batches[i % len(batches)],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    mgr = make_mgr(makes)
    # Unfiltered reference detector views for the pass-all identity.
    ref = make_mgr(
        {
            "detview": lambda: DetectorViewWorkflow(
                projection=project_logical(det)
            )
        }
    )
    n_groups, k = 3, 2
    for w in range(2):  # warm: program variants + static fetches
        out = mgr.process_jobs(
            {"det0": staged(w)}, start=t0, end=Timestamp.from_ns(1 + w)
        )
        assert len(out) == n_groups * k
        ref.process_jobs(
            {"det0": staged(w)}, start=t0, end=Timestamp.from_ns(1 + w)
        )
    from esslivedata_tpu.telemetry.instruments import EVENTS_FILTERED

    METRICS.drain()
    mgr.event_cache_stats()
    compiles_warm = COMPILE_EVENTS.total()
    filtered_before = EVENTS_FILTERED.total()
    dv_wire: list[list[bytes]] = []
    events_seen = 0
    start = time.perf_counter()
    # Measured loop: ONLY the workload manager (the unfiltered
    # reference runs after, outside the drained counters).
    for i in range(n_windows):
        out = mgr.process_jobs(
            {"det0": staged(i)}, start=t0, end=Timestamp.from_ns(3 + i)
        )
        assert len(out) == n_groups * k
        dv_wire.append(
            [
                encode_da00(name, 1, dataarray_to_da00(da))
                for r in out
                if "detview" in str(r.workflow_id)
                for name, da in r.outputs.items()
            ]
        )
        events_seen += int(batches[i % len(batches)].n_valid)
    dt = time.perf_counter() - start
    m = METRICS.drain()
    compiles_steady = COMPILE_EVENTS.total() - compiles_warm
    # Veto drop rate over the measured loop: powder + imaging both run
    # the chain, so normalize per consuming family pass.
    events_filtered = EVENTS_FILTERED.total() - filtered_before

    # Pass-all identity: the filtered detector view's wire == the
    # unfiltered reference's, byte for byte, every window.
    for i in range(n_windows):
        out_ref = ref.process_jobs(
            {"det0": staged(i)}, start=t0, end=Timestamp.from_ns(3 + i)
        )
        ref_wire = [
            encode_da00(name, 1, dataarray_to_da00(da))
            for r in out_ref
            for name, da in r.outputs.items()
        ]
        assert dv_wire[i] == ref_wire, (
            f"window {i}: pass-all filter changed the da00 wire"
        )

    # Live calibration swap, COLD: the next tick compiles on the hot
    # path and the instrument classifies it layout_swap.
    swaps_before = layout_swaps()
    cold_before = COMPILE_EVENTS.total()
    for rec in mgr._records.values():
        wf = rec.job.workflow
        if hasattr(wf, "set_calibration"):
            assert wf.set_calibration(make_calib(version=2, tzero=5e4))
    out = mgr.process_jobs(
        {"det0": staged(0)}, start=t0, end=Timestamp.from_ns(500)
    )
    assert len(out) == n_groups * k
    cold_swap_compiles = COMPILE_EVENTS.total() - cold_before
    swap_classified = layout_swaps() - swaps_before

    # The same swap WARMED (ADR 0118): request_warmup drains before the
    # next window, so the hot-path compile delta is 0.
    warmup = CompileWarmupService()
    mgr.set_warmup(warmup)
    try:
        for rec in mgr._records.values():
            wf = rec.job.workflow
            if hasattr(wf, "set_calibration"):
                assert wf.set_calibration(
                    make_calib(version=3, tzero=1e5)
                )
        mgr.request_warmup("layout_swap")
        assert warmup.quiesce(120), "warm-up never drained"
        warm_before = COMPILE_EVENTS.total()
        out = mgr.process_jobs(
            {"det0": staged(1)}, start=t0, end=Timestamp.from_ns(501)
        )
        assert len(out) == n_groups * k
        warmed_swap_compiles = COMPILE_EVENTS.total() - warm_before
    finally:
        warmup.close()
    mgr.shutdown()
    ref.shutdown()

    line = {
        "metric": "workload_plane",
        "families": ["powder_focus", "detector_view", "imaging_view"],
        "jobs": n_groups * k,
        # Graded value: device dispatches per steady-state FILTERED
        # tick, per group — the zero-extra-dispatch filtering claim.
        "value": (m["executes"] + m["step_executes"])
        / (n_windows * n_groups),
        "unit": "dispatches/tick/group",
        "executes_per_tick": m["executes"] / n_windows,
        "fetches_per_tick": m["fetches"] / n_windows,
        "step_executes_per_tick": m["step_executes"] / n_windows,
        "tick_publishes": m["tick_publishes"],
        "static_bytes_steady": m["static_bytes"],
        # One memoized chain pass per window (powder + imaging share
        # the chain digest), so the ratio is the per-event drop rate.
        "filtered_fraction": events_filtered / max(1, events_seen),
        "passall_wire_byte_identical": True,
        "compile_events_steady": compiles_steady,
        "cold_swap_compiles": cold_swap_compiles,
        "cold_swap_classified_layout_swap": swap_classified,
        "warmed_swap_compiles": warmed_swap_compiles,
        "wall_ms_per_tick": 1e3 * dt / n_windows,
        "windows": n_windows,
        "events_per_window": n_events,
        "telemetry": telemetry_snapshot(),
    }
    emit_line(line)
    # Acceptance: filters active, still one dispatch per group tick.
    assert line["value"] == 1.0, line
    assert m["fetches"] == n_windows * n_groups, line
    assert m["step_executes"] == 0, line
    assert m["static_bytes"] == 0, line
    assert compiles_steady == 0, line
    # The veto actually filtered (powder counts < raw events).
    assert 0.0 < line["filtered_fraction"] < 1.0, line
    # Cold swap: compiled on the hot path AND classified layout_swap.
    assert cold_swap_compiles >= 1, line
    assert swap_classified >= 1, line
    # Warmed swap: zero hot-path compiles (the ADR 0122 acceptance).
    assert warmed_swap_compiles == 0, line
    return line


def bench_fanout(args, n_values: tuple[int, ...] | None = None) -> dict:
    """Result fan-out tier through the REAL JobManager + ServingPlane
    (ADR 0117).

    K=4 detector-view jobs publish every window into the broadcast hub
    while N simulated SSE subscribers are attached — the same
    ``BroadcastServer.subscribe`` handles the real ``/streams/...``
    connections, minus the socket. One designated subscriber per stream
    drains and reconstructs every tick (DeltaDecoder) and its frames
    are asserted BYTE-IDENTICAL to the sink's da00 wire; the rest stay
    deliberately slow, so coalesce-on-overflow engages and their queues
    stay bounded.

    Acceptance (asserted here AND in --smoke/CI): publish-side device
    executes + fetches per tick are IDENTICAL at every N — the whole
    point of the tier is that subscribers cost the compute loop nothing
    — and a keeping-up subscriber's served bytes are well under the
    full-frame replay it would have paid without delta encoding. One
    JSON line per N plus a summary line, on stderr.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
    from esslivedata_tpu.kafka.wire import encode_da00
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.publish import METRICS
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.serving import DeltaDecoder, ServingPlane, stream_key
    from esslivedata_tpu.serving.broadcast import (
        SERVING_BYTES,
        SERVING_COALESCE_DROPS,
    )
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 14)))
    det = np.arange(side * side).reshape(side, side)
    # Modest per-window event counts keep the rolling histograms
    # SPARSE between ticks — the regime the delta codec exists for
    # (and the one the beam delivers at dashboard cadence): cap at
    # 1/8th of the bin space so the per-tick changed-bin fraction
    # stays representative regardless of --events.
    n_events = min(args.events, max(256, (side * side) // 8))
    n_windows = max(8, args.batches // 4)
    n_distinct = 4
    k = 4
    # Small enough that the deliberately-slow subscribers overflow
    # even at smoke sizes (n_windows >= 8), so the coalesce-on-overflow
    # path is ASSERTED to engage below — not merely recorded.
    queue_limit = 4
    if n_values is None:
        n_values = (1, 100, 2000)
    method = args.method if args.method in ("scatter", "sort") else "scatter"
    batches = []
    for s in range(500, 500 + n_distinct):
        pid, toa = make_batch(n_events, side * side, seed=s)
        batches.append(EventBatch.from_arrays(pid, toa))

    def staged(i: int) -> StagedEvents:
        return StagedEvents(
            batch=batches[i % n_distinct],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    t0 = Timestamp.from_ns(0)
    results_by_n = {}
    for n_subs in n_values:
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench",
            name=f"dv_fanout_{n_subs}",
            source_names=["det0"],
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=min(4, k)
        )
        for _ in range(k):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        plane = ServingPlane(port=None, queue_limit=queue_limit)
        # Warm windows: publish programs compile, statics fetch once,
        # and the hub learns every stream (so subscribers can attach).
        for w in range(2):
            out = mgr.process_jobs(
                {"det0": staged(w)}, start=t0, end=Timestamp.from_ns(1 + w)
            )
            assert len(out) == k
            plane.publish_results(out, Timestamp.from_ns(10 + w))
        streams = sorted(plane.cache.streams())
        assert streams, "no streams cached after warm windows"
        subs = [
            plane.server.subscribe(streams[i % len(streams)])
            for i in range(n_subs)
        ]
        # One keeping-up checker per stream (subscribers beyond the
        # stream count stay slow on purpose); drain attach keyframes.
        checkers: dict[str, tuple] = {}
        for sub in subs:
            blob = sub.next_blob(timeout=1.0)
            assert blob is not None, "attach keyframe missing"
            if sub.stream not in checkers:
                decoder = DeltaDecoder()
                decoder.apply(blob)
                checkers[sub.stream] = (sub, decoder)
        METRICS.drain()
        delta_bytes0 = SERVING_BYTES.value(kind="delta")
        key_bytes0 = SERVING_BYTES.value(kind="keyframe")
        drops0 = SERVING_COALESCE_DROPS.total()
        checker_bytes = 0
        full_bytes = 0
        last_reference: dict[str, bytes] = {}
        start = time.perf_counter()
        for i in range(n_windows):
            out = mgr.process_jobs(
                {"det0": staged(i)},
                start=t0,
                end=Timestamp.from_ns(3 + i),
            )
            assert len(out) == k
            ts = Timestamp.from_ns(100 + i)
            plane.publish_results(out, ts)
            # Reconstruction oracle: the sink serializer's exact bytes.
            for res in out:
                job = f"{res.job_id.source_name}:{res.job_id.job_number}"
                for key, da in zip(
                    res.keys(), res.outputs.values(), strict=True
                ):
                    stream = stream_key(job, key.output_name)
                    entry = checkers.get(stream)
                    if entry is None:
                        continue
                    sub, decoder = entry
                    reference = encode_da00(
                        key.to_string(), ts.ns, dataarray_to_da00(da)
                    )
                    last_reference[stream] = reference
                    full_bytes += len(reference)
                    got = None
                    while (blob := sub.next_blob(timeout=1.0)) is not None:
                        checker_bytes += len(blob)
                        got = decoder.apply(blob)
                        if decoder.seq is not None and got == reference:
                            break
                    assert got == reference, (
                        f"window {i}: subscriber reconstruction != "
                        f"sink da00 wire for {stream}"
                    )
        dt = time.perf_counter() - start
        m = METRICS.drain()
        slow_subs = [
            sub
            for sub in subs
            if checkers.get(sub.stream, (None,))[0] is not sub
        ]
        if slow_subs and n_windows > queue_limit:
            # The deliberately-slow subscribers MUST have overflowed:
            # the coalesce path is exercised here, not just recorded.
            assert SERVING_COALESCE_DROPS.total() > drops0, (
                "slow subscribers never coalesced"
            )
            # And a coalesced subscriber recovers the exact latest
            # frame from its resync keyframe on the next drain.
            probe = slow_subs[0]
            decoder = DeltaDecoder()
            got = None
            while (blob := probe.next_blob(timeout=1.0)) is not None:
                got = decoder.apply(blob)
            assert got == last_reference[probe.stream], (
                "coalesced subscriber did not recover the latest frame"
            )
        qos = plane.qos()
        drops = SERVING_COALESCE_DROPS.total() - drops0
        delta_bytes = SERVING_BYTES.value(kind="delta") - delta_bytes0
        key_bytes = SERVING_BYTES.value(kind="keyframe") - key_bytes0
        mgr.shutdown()
        plane.close()
        line = {
            "metric": "fanout",
            "subscribers": n_subs,
            "jobs": k,
            # Graded value: publish-side device round trips per tick —
            # must not move with N.
            "value": (m["executes"] + m["fetches"]) / n_windows,
            "unit": "publish_device_ops/tick",
            "executes_per_tick": m["executes"] / n_windows,
            "fetches_per_tick": m["fetches"] / n_windows,
            "streams": len(streams),
            "windows": n_windows,
            "events_per_window": n_events,
            "wall_ms_per_tick": 1e3 * dt / n_windows,
            # A keeping-up subscriber's wire cost vs replaying the full
            # frame every tick — the delta-encoding claim.
            "served_bytes_per_checker_tick": (
                checker_bytes / (n_windows * len(checkers))
            ),
            "full_frame_bytes_per_tick": (
                full_bytes / (n_windows * len(checkers))
            ),
            "delta_vs_replay_ratio": checker_bytes / max(full_bytes, 1),
            "enqueued_delta_bytes": delta_bytes,
            "enqueued_keyframe_bytes": key_bytes,
            "coalesce_drops": drops,
            "queue_pressure": qos["queue_pressure"],
        }
        results_by_n[n_subs] = line
        emit_line(line)
        # Keeping-up subscribers ride deltas: well under full replay.
        assert line["delta_vs_replay_ratio"] < 0.8, line
    ref = results_by_n[n_values[0]]
    for n_subs in n_values[1:]:
        cur = results_by_n[n_subs]
        # THE acceptance bound: device work per tick identical in N.
        assert cur["executes_per_tick"] == ref["executes_per_tick"], (
            ref,
            cur,
        )
        assert cur["fetches_per_tick"] == ref["fetches_per_tick"], (
            ref,
            cur,
        )
    summary = {
        "metric": "fanout_summary",
        "n_values": list(n_values),
        "publish_ops_flat_in_n": True,
        "executes_per_tick": ref["executes_per_tick"],
        "fetches_per_tick": ref["fetches_per_tick"],
        "delta_vs_replay_ratio": {
            n: results_by_n[n]["delta_vs_replay_ratio"] for n in n_values
        },
        "wall_ms_per_tick": {
            n: results_by_n[n]["wall_ms_per_tick"] for n in n_values
        },
    }
    print(json.dumps(summary), file=sys.stderr)
    return results_by_n[max(n_values)]


def bench_relay(args, r_values: tuple[int, ...] | None = None) -> dict:
    """Relay-tree fan-out edge through the REAL JobManager + ServingPlane
    + fleet relays (ADR 0121).

    K=4 detector-view jobs publish every window into the compute-tier
    hub; R in {1, 2, 4} relays (fleet/relay.py HubRelay — the same
    RelayChannel state machine the ``livedata-relay`` SSE service runs,
    driven through the hub API the SSE handler uses) each re-fan to
    their own N subscribers. Every subscriber drains every window — the
    capacity claim is that R relays serve R x N KEEPING-UP viewers —
    and one checker per (relay, stream) reconstructs frames asserted
    BYTE-IDENTICAL to a direct compute-hub subscription (and therefore
    to the sink's da00 wire, per the --fanout acceptance).

    Acceptance (asserted here AND in --smoke/CI):

    - compute-tier publish executes + fetches per tick == 1.0 at every
      R (subscriber/relay count costs the compute loop nothing);
    - the COMPUTE hub encodes exactly once per stream per tick at
      every R (``BroadcastServer.encodes`` — relays re-encode on their
      own hubs, the compute tier never pays for them);
    - downstream frames byte-identical to a direct subscription;
    - served-subscriber count strictly increases 1 -> 2 -> 4 relays
      with every subscriber fully served (monotone capacity in R).
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.fleet.relay import HubRelay
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.publish import METRICS
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.serving import DeltaDecoder, ServingPlane
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 14)))
    det = np.arange(side * side).reshape(side, side)
    n_events = min(args.events, max(256, (side * side) // 8))
    n_windows = max(8, args.batches // 4)
    n_distinct = 4
    k = 4
    subs_per_relay = 16
    if r_values is None:
        r_values = (1, 2, 4)
    method = args.method if args.method in ("scatter", "sort") else "scatter"
    batches = []
    for s in range(700, 700 + n_distinct):
        pid, toa = make_batch(n_events, side * side, seed=s)
        batches.append(EventBatch.from_arrays(pid, toa))

    def staged(i: int) -> StagedEvents:
        return StagedEvents(
            batch=batches[i % n_distinct],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    t0 = Timestamp.from_ns(0)
    results_by_r = {}
    for n_relays in r_values:
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench",
            name=f"dv_relay_{n_relays}",
            source_names=["det0"],
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=min(4, k)
        )
        for _ in range(k):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        plane = ServingPlane(port=None, queue_limit=32)
        relays = [
            HubRelay(plane.server, name=f"bench_relay_{n_relays}_{i}")
            for i in range(n_relays)
        ]
        for w in range(2):
            out = mgr.process_jobs(
                {"det0": staged(w)}, start=t0, end=Timestamp.from_ns(1 + w)
            )
            plane.publish_results(out, Timestamp.from_ns(10 + w))
            for relay in relays:
                relay.pump()
        streams = sorted(plane.cache.streams())
        assert streams, "no streams cached after warm windows"
        for relay in relays:
            assert sorted(relay.hub.cache.streams()) == streams, (
                "relay hub did not mirror the upstream stream set"
            )
        # Direct compute-hub checkers: the byte-identity oracle.
        direct = {}
        for stream in streams:
            sub = plane.server.subscribe(stream)
            decoder = DeltaDecoder()
            blob = sub.next_blob(timeout=1.0)
            assert blob is not None
            decoder.apply(blob)
            direct[stream] = (sub, decoder)
        # R x N downstream subscribers, one checker per (relay, stream).
        downstream = []  # (relay_idx, stream, sub, decoder-or-None)
        for r_i, relay in enumerate(relays):
            checked: set[str] = set()
            for i in range(subs_per_relay):
                stream = streams[i % len(streams)]
                sub = relay.hub.subscribe(stream)
                blob = sub.next_blob(timeout=1.0)
                assert blob is not None, "relay attach keyframe missing"
                decoder = None
                if stream not in checked:
                    checked.add(stream)
                    decoder = DeltaDecoder()
                    decoder.apply(blob)
                downstream.append((r_i, stream, sub, decoder))
        METRICS.drain()
        hub_encodes0 = plane.server.encodes
        delivered = 0
        start = time.perf_counter()
        for i in range(n_windows):
            out = mgr.process_jobs(
                {"det0": staged(i)}, start=t0, end=Timestamp.from_ns(3 + i)
            )
            plane.publish_results(out, Timestamp.from_ns(100 + i))
            for relay in relays:
                relay.pump()
            reference = {}
            for stream, (sub, decoder) in direct.items():
                got = None
                while (blob := sub.next_blob(timeout=1.0)) is not None:
                    got = decoder.apply(blob)
                    if sub.depth() == 0:
                        break
                assert got is not None, f"direct subscriber starved ({stream})"
                reference[stream] = got
            for _r_i, stream, sub, decoder in downstream:
                got = None
                while (blob := sub.next_blob(timeout=1.0)) is not None:
                    delivered += 1
                    if decoder is not None:
                        got = decoder.apply(blob)
                    if sub.depth() == 0:
                        break
                if decoder is not None:
                    assert got == reference[stream], (
                        f"window {i}: relay frame != direct frame for "
                        f"{stream}"
                    )
        dt = time.perf_counter() - start
        m = METRICS.drain()
        hub_encodes = plane.server.encodes - hub_encodes0
        relay_encode_total = sum(r.hub.encodes for r in relays)
        served = len(downstream)
        for relay in relays:
            relay.close()
        mgr.shutdown()
        plane.close()
        line = {
            "metric": "relay",
            "relays": n_relays,
            "jobs": k,
            # Graded value: compute-tier device round trips per tick —
            # must not move with relay count.
            "value": (m["executes"] + m["fetches"]) / n_windows,
            "unit": "publish_device_ops/tick",
            "executes_per_tick": m["executes"] / n_windows,
            "fetches_per_tick": m["fetches"] / n_windows,
            "hub_encodes_per_tick": hub_encodes / n_windows,
            "streams": len(streams),
            "served_subscribers": served,
            "frames_delivered": delivered,
            "frames_delivered_per_s": delivered / dt,
            "relay_hub_encodes": relay_encode_total,
            "windows": n_windows,
            "events_per_window": n_events,
            "wall_ms_per_tick": 1e3 * dt / n_windows,
        }
        results_by_r[n_relays] = line
        emit_line(line)
        # THE hub contract: one encode per stream per tick, whatever R.
        assert hub_encodes == n_windows * len(streams), line
    ref = results_by_r[r_values[0]]
    prev_served = 0
    for n_relays in r_values:
        cur = results_by_r[n_relays]
        # Compute-tier work flat in relay count.
        assert cur["executes_per_tick"] == ref["executes_per_tick"], (
            ref,
            cur,
        )
        assert cur["fetches_per_tick"] == ref["fetches_per_tick"], (
            ref,
            cur,
        )
        assert cur["hub_encodes_per_tick"] == ref["hub_encodes_per_tick"], (
            ref,
            cur,
        )
        # Monotone capacity: every downstream subscriber was fully
        # served at every R, and the served count strictly grows.
        assert cur["served_subscribers"] > prev_served, (prev_served, cur)
        prev_served = cur["served_subscribers"]
    summary = {
        "metric": "relay_summary",
        "r_values": list(r_values),
        "compute_ops_flat_in_r": True,
        "executes_per_tick": ref["executes_per_tick"],
        "hub_encodes_per_tick": ref["hub_encodes_per_tick"],
        "served_subscribers": {
            r: results_by_r[r]["served_subscribers"] for r in r_values
        },
        "frames_delivered_per_s": {
            r: results_by_r[r]["frames_delivered_per_s"] for r in r_values
        },
    }
    print(json.dumps(summary), file=sys.stderr)
    return results_by_r[max(r_values)]


def bench_churn(args) -> dict:
    """Durability plane under churn (ADR 0118): kill-and-restart with
    checkpoint/replay, and commit-time AOT warm-up.

    K=3 detector-view jobs (fixed job ids, so the restarted process
    serves the SAME streams) run through the real JobManager. A
    checkpoint is taken mid-run (state + the window-index bookmark),
    more windows flow, then the process "dies" — the manager is dropped
    with no shutdown dump, exactly a crash. A second manager restores
    from the checkpoint directory and replays from the bookmark through
    the normal ingest path.

    Acceptance (asserted here AND in --smoke/CI):

    - every replayed window's da00 wire — including the windows the
      doomed process had already published and the final one — is
      BYTE-IDENTICAL to an uninterrupted control's;
    - a subscriber reconnecting to the restarted serving hub gets a
      keyframe carrying the restored accumulation (== the control's
      cumulative at that window, byte-identical frame) — a gap, NOT a
      reset to zero;
    - committing a NEW job on the restarted manager with the warm-up
      service attached costs 0 hot-path jit compiles
      (``livedata_jit_compiles_total`` delta == 0 over the next
      windows), while the identical commit on the control without
      warm-up pays >= 1 — the instrument-verified half of ROADMAP
      item 1.

    One JSON line on stderr.
    """
    import tempfile
    import uuid as _uuid

    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.durability import (
        CheckpointPlane,
        CompileWarmupService,
    )
    from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
    from esslivedata_tpu.kafka.wire import decode_da00, encode_da00
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.serving import DeltaDecoder, ServingPlane, stream_key
    from esslivedata_tpu.telemetry import COMPILE_EVENTS
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 14)))
    det = np.arange(side * side).reshape(side, side)
    n_events = min(args.events, 1 << 14)
    n_windows = max(9, args.batches // 4)
    checkpoint_at = n_windows // 3  # bookmark = checkpoint_at + 1
    crash_at = 2 * n_windows // 3
    k = 3
    method = args.method if args.method in ("scatter", "sort") else "scatter"
    batches = []
    for s in range(n_windows):
        pid, toa = make_batch(n_events, side * side, seed=700 + s)
        batches.append(EventBatch.from_arrays(pid, toa))

    def staged(w: int) -> StagedEvents:
        return StagedEvents(
            batch=batches[w],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    def make_mgr(tag: str, durability=None) -> JobManager:
        # ONE spec name across control/doomed/restarted: the restarted
        # process schedules the same workflow identity, and checkpoint
        # entries match on (workflow_id, source, fingerprint). The
        # registries are per-manager, so the shared name cannot clash.
        del tag
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench", name="dv_churn", source_names=["det0"]
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg),
            job_threads=1,
            durability=durability,
        )
        # FIXED job numbers: the restarted process schedules the same
        # jobs (restart semantics), so checkpoint entries and serving
        # stream keys line up across the kill.
        for i in range(k):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(
                        source_name="det0", job_number=_uuid.UUID(int=i)
                    ),
                )
            )
        return mgr, spec

    def run(mgr, w: int):
        out = mgr.process_jobs(
            {"det0": staged(w)},
            start=Timestamp.from_ns(1 + w),
            end=Timestamp.from_ns(2 + w),
        )
        return out

    def wire_of(results, ts_ns: int) -> list[bytes]:
        frames = []
        for res in sorted(results, key=lambda r: str(r.job_id.job_number)):
            for key, da in zip(
                res.keys(), res.outputs.values(), strict=True
            ):
                frames.append(
                    encode_da00(key.to_string(), ts_ns, dataarray_to_da00(da))
                )
        return frames

    # ---- control: uninterrupted, plus the no-warm-up commit cost ----
    control, control_spec = make_mgr("ctrl")
    control_wire = []
    control_results = []
    for w in range(n_windows):
        out = run(control, w)
        assert len(out) == k
        control_results.append(out)
        control_wire.append(wire_of(out, 100 + w))
    compiles0 = COMPILE_EVENTS.total()
    control.schedule_job(
        WorkflowConfig(
            identifier=control_spec.identifier,
            job_id=JobId(source_name="det0", job_number=_uuid.UUID(int=50)),
        )
    )
    # One window after the cold commit: the re-keyed tick program
    # compiles ON the hot path — the spike class warm-up removes.
    assert len(run(control, n_windows - 1)) == k + 1
    commit_compiles_cold = COMPILE_EVENTS.total() - compiles0
    assert commit_compiles_cold >= 1, (
        "cold commit paid no compile — the warm-up claim below would "
        "be vacuous"
    )

    # ---- churn run: checkpoint, crash, restore, replay ----
    ckdir = tempfile.mkdtemp(prefix="bench-churn-ck-")
    plane_a = CheckpointPlane(ckdir, interval_s=0)
    doomed, _spec = make_mgr("a", durability=plane_a)
    for w in range(checkpoint_at + 1):
        assert len(run(doomed, w)) == k
    manifest = plane_a.checkpoint(
        doomed.checkpoint_snapshot(),
        offsets={"det0": checkpoint_at + 1},
        reset_seq=doomed.reset_seq,
    )
    checkpoint_bytes = sum(
        entry["nbytes"]
        for entry in json.loads(manifest.read_bytes())["jobs"]
    )
    for w in range(checkpoint_at + 1, crash_at + 1):
        assert len(run(doomed, w)) == k
    plane_a.close()
    del doomed  # crash: no shutdown dump, no final checkpoint

    plane_b = CheckpointPlane(ckdir, interval_s=0)
    t_restore = time.perf_counter()
    restored, spec_b = make_mgr("b", durability=plane_b)
    bookmark = plane_b.bookmarks()["det0"]
    assert bookmark == checkpoint_at + 1
    hub = ServingPlane(port=None)
    replay_identical = True
    for w in range(bookmark, n_windows):
        out = run(restored, w)
        assert len(out) == k
        if wire_of(out, 100 + w) != control_wire[w]:
            replay_identical = False
        hub.publish_results(out, Timestamp.from_ns(100 + w))
    replay_wall_s = time.perf_counter() - t_restore
    assert replay_identical, (
        "replayed da00 wire != uninterrupted control"
    )

    # ---- the reconnecting subscriber sees a gap, not a reset ----
    job0 = f"det0:{_uuid.UUID(int=0)}"
    sub = hub.server.subscribe(stream_key(job0, "image_cumulative"))
    blob = sub.next_blob(timeout=1.0)
    assert blob is not None, "reconnect keyframe missing"
    decoder = DeltaDecoder()
    frame = decoder.apply(blob)
    decoded = decode_da00(frame)
    cumulative = next(
        np.asarray(v.data)
        for v in decoded.variables
        if v.name == "signal"
    )
    # The keyframe carries the FULL restored + replayed accumulation:
    # n_windows x n_events counts. A reset would show only the
    # post-restart windows' counts.
    expected = n_windows * n_events
    subscriber_not_reset = float(cumulative.sum()) == float(expected)
    assert subscriber_not_reset, (
        f"subscriber keyframe shows {cumulative.sum()} counts, "
        f"expected the full {expected}: accumulation RESET across the "
        "restart"
    )
    hub.close()

    # ---- commit-time warm-up on the restarted manager ----
    warmup = CompileWarmupService()
    restored.set_warmup(warmup)
    restored.schedule_job(
        WorkflowConfig(
            identifier=spec_b.identifier,
            job_id=JobId(source_name="det0", job_number=_uuid.UUID(int=51)),
        )
    )
    assert warmup.quiesce(120), "warm-up never drained"
    compiles1 = COMPILE_EVENTS.total()
    assert len(run(restored, n_windows - 1)) == k + 1
    assert len(run(restored, n_windows - 2)) == k + 1
    commit_compiles_warm = COMPILE_EVENTS.total() - compiles1
    warmup.close()
    plane_b.close()
    restored.shutdown()
    control.shutdown()
    assert commit_compiles_warm == 0, (
        f"warm-up left {commit_compiles_warm} compile(s) on the hot "
        "path at commit time"
    )

    line = {
        "metric": "churn",
        # Graded value: hot-path jit compiles at commit time with
        # warm-up on — the quantity the durability plane zeroes.
        "value": commit_compiles_warm,
        "unit": "hot_path_compiles_at_commit",
        "jobs": k,
        "windows": n_windows,
        "events_per_window": n_events,
        "checkpoint_window": checkpoint_at,
        "crash_window": crash_at,
        "bookmark": bookmark,
        "replayed_windows": n_windows - bookmark,
        "replay_wall_ms": 1e3 * replay_wall_s,
        "checkpoint_bytes": checkpoint_bytes,
        "wire_byte_identical_after_replay": replay_identical,
        "subscriber_gap_not_reset": subscriber_not_reset,
        "commit_compiles_without_warmup": commit_compiles_cold,
        "commit_compiles_with_warmup": commit_compiles_warm,
    }
    emit_line(line)
    return line


def bench_slo(args, *, scale: float | None = None) -> dict:
    """SLO plane acceptance (ADR 0120): the load+chaos harness through
    the REAL JobManager + ServingPlane, gated by the declarative rule
    file ``scripts/slo_rules/smoke.json``.

    Reports the p99 consume->subscriber-delivered e2e latency
    DECOMPOSED BY STAGE (consume / decode / published / fanout_encoded
    / subscriber_delivered — ``livedata_e2e_latency_seconds``) over the
    gated phase, and asserts the chaos drill's containment contracts:
    injected post-donation state loss is SIGNALED (epoch bumps, zero
    unsignaled resets), wire parity holds byte-exactly at every checker
    subscriber, hot-path compiles stay 0 (the failover path is warmed),
    queues stay bounded at the limit, coalesced subscribers recover.
    Then the CONTROL: the same drill with the state-loss signal
    disabled must make the gate exit non-zero — proving the gate can
    catch the regression it exists for.
    """
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "slo_gate", Path(__file__).resolve().parent / "scripts/slo_gate.py"
    )
    slo_gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(slo_gate)
    from esslivedata_tpu.telemetry.e2e import E2E_STAGES

    if scale is None:
        # Rough size coupling to the headline knobs: --smoke budgets
        # (events 8192 / batches 6) land ~0.5, a full run ~1.0.
        scale = 0.5 if (args.events or 0) <= 65536 else 1.0
    # THE drill is slo_gate's own (chaos schedule, scaling, scrape
    # delta all included): the bench grades the exact scenario CI
    # gates — a schedule tweak there can never silently diverge from
    # what this scenario measures.
    report, delta = slo_gate._smoke_report(None, scale)
    rules = slo_gate._load_rules(
        Path(__file__).resolve().parent / "scripts/slo_rules/smoke.json"
    )
    gate_ok, gate_results = slo_gate.evaluate(rules, delta)
    e2e = delta.get("livedata_e2e_latency_seconds")
    p99_by_stage = {}
    if e2e is not None:
        for stage in E2E_STAGES:
            q = slo_gate.histogram_quantile(e2e, 0.99, {"stage": stage})
            if q is not None:
                p99_by_stage[stage] = None if q == float("inf") else q
    # The acceptance contracts, asserted here AND gated by the rules.
    assert report["chaos_injected"], "chaos schedule fired nothing"
    assert report["parity_violations"] == 0, report
    assert report["gap_violations"] == 0, report
    assert report["steady_compiles"] == 0, report
    assert report["coalesce_drops"] > 0, report
    assert report["coalesce_recoveries"] > 0, report
    assert report["peak_queue_depth"] <= report["queue_limit"], report
    assert gate_ok, gate_results
    assert "subscriber_delivered" in p99_by_stage, p99_by_stage
    # CONTROL: the same drill with the state-loss epoch signal
    # disabled; the gate MUST go red (unsignaled resets observed by
    # subscribers).
    control, control_delta = slo_gate._smoke_report(
        "state-lost-signal", min(scale, 0.25)
    )
    control_ok, control_results = slo_gate.evaluate(rules, control_delta)
    assert not control_ok, (
        "gate stayed green with state-loss containment disabled",
        control_results,
    )
    assert control["gap_violations"] > 0, control
    line = {
        "metric": "slo",
        # Graded value: the headline — p99 consume->subscriber e2e
        # freshness (seconds) under chaos, CPU-container scale.
        "value": p99_by_stage.get("subscriber_delivered"),
        "unit": "p99_e2e_seconds",
        "e2e_p99_by_stage": p99_by_stage,
        "windows": report["windows"],
        "subscribers": report["subscribers"],
        "jobs": report["jobs"],
        "wall_ms_per_window": report["wall_ms_per_window"],
        "chaos_injected": report["chaos_injected"],
        "parity_checks": report["parity_checks"],
        "parity_violations": report["parity_violations"],
        "gap_violations": report["gap_violations"],
        "steady_compiles": report["steady_compiles"],
        "coalesce_drops": report["coalesce_drops"],
        "coalesce_recoveries": report["coalesce_recoveries"],
        "peak_queue_depth": report["peak_queue_depth"],
        "healthz_after_chaos": report["healthz"],
        "gate_passed": gate_ok,
        "gate_rules": gate_results,
        "control_gate_breached": not control_ok,
        "control_gap_violations": control["gap_violations"],
    }
    emit_line(line)
    return line


def bench_telemetry(args, tick_wall_ms: float | None = None) -> dict:
    """Steady-state telemetry overhead guard (ADR 0116, PERF round 10).

    The flight recorder put instruments on the hot path: span records on
    every pipeline stage, a publish-metrics record and an RTT observe
    per tick, compile-event probes per fused dispatch. This scenario
    measures the microcost of each instrument op (counter inc, bound
    histogram observe, tracer span record, disabled-tracer no-op) and
    bounds the per-tick budget: a steady-state tick pays a fixed,
    countable number of instrument ops (~12: six spans, two registry
    records, stage-timer folds, compile probes), so

        overhead <= ops_per_tick * max_op_cost / tick_wall

    is a deterministic bound, robust where an A/B wall-clock diff of
    <1% would drown in CI noise. Asserted < 1% of tick wall time
    (``tick_wall_ms`` from the tick scenario when chained; a
    conservative 10 ms floor otherwise — the smoke tick measures ~25 ms
    on this container).
    Scrape-time cost (registry collect + render) is reported but not
    part of the hot-path bound: scrapes run on the HTTP thread.
    """
    from esslivedata_tpu.telemetry import REGISTRY, TRACER, TickTracer

    n = 50_000
    counter = REGISTRY.counter(
        "livedata_bench_overhead_ops",
        "telemetry-overhead bench scratch instrument",
        labelnames=("kind",),
    ).labels(kind="inc")
    hist = REGISTRY.histogram(
        "livedata_bench_overhead_seconds",
        "telemetry-overhead bench scratch instrument",
        labelnames=("kind",),
    ).labels(kind="observe")

    def per_op_ns(fn) -> float:
        fn()  # warm
        start = time.perf_counter()
        for _ in range(n):
            fn()
        return 1e9 * (time.perf_counter() - start) / n

    inc_ns = per_op_ns(counter.inc)
    observe_ns = per_op_ns(lambda: hist.observe(0.001))
    enabled_tracer = TickTracer(enabled=True)
    trace_id = enabled_tracer.new_trace()
    span_ns = per_op_ns(
        lambda: enabled_tracer.record("bench", 0.0, 1e-6, trace_id)
    )
    disabled_tracer = TickTracer(enabled=False)
    disabled_ns = per_op_ns(
        lambda: disabled_tracer.record("bench", 0.0, 1e-6, trace_id)
    )
    t0 = time.perf_counter()
    REGISTRY.collect()
    collect_ms = 1e3 * (time.perf_counter() - t0)

    #: Instrument ops a steady-state tick pays (six spans + publish
    #: metrics record + RTT observe/EWMA + two stage-timer folds +
    #: compile probes), with headroom.
    ops_per_tick = 16
    wall_ms = tick_wall_ms if tick_wall_ms else 10.0
    worst_op_ns = max(inc_ns, observe_ns, span_ns)
    overhead_fraction = ops_per_tick * worst_op_ns / (wall_ms * 1e6)
    line = {
        "metric": "telemetry_overhead",
        "value": overhead_fraction,
        "unit": "fraction_of_tick_wall",
        "counter_inc_ns": inc_ns,
        "histogram_observe_ns": observe_ns,
        "span_record_ns": span_ns,
        "disabled_tracer_ns": disabled_ns,
        "registry_collect_ms": collect_ms,
        "ops_per_tick_budget": ops_per_tick,
        "tick_wall_ms_reference": wall_ms,
    }
    emit_line(line)
    # The acceptance bound (PERF round 10): instruments must stay under
    # 1% of tick wall — they observe the serving path, never tax it.
    assert overhead_fraction < 0.01, line
    return line


def bench_mesh(args, *, strict_scaling: bool = False) -> dict:
    """Mesh serving tier through the REAL JobManager path (ADR 0115).

    Two sections, one JSON line each on stderr:

    - **mesh_tick** — K=2 bank-sharded multibank jobs on the 2x4
      data×bank mesh, placed by DevicePlacement: asserts the per-slice
      tick contract (ONE execute + ONE fetch per mesh slice per
      steady-state tick, zero separate step dispatches) and that the
      da00 wire output is byte-identical to the single-device tick
      program over identical windows.
    - **mesh_scaling** — the same workload compiled over 1→2→4→8-device
      data-sharded meshes: the recorded events/s curve must rise
      monotonically from 1→2 devices (the data axis splits the
      scatter's event work); 8 fake devices share one CPU host's cores,
      so the tail of the curve measures contention, not chips — noted
      in the line. ``strict_scaling`` (the direct ``--mesh`` acceptance
      run on a many-core host) turns the 1→2 rise into a hard assert;
      the CI smoke records it without gating — a 2-vCPU runner has
      fewer cores than virtual devices, so there the curve measures the
      runner, not the code (the per-slice dispatch/parity contract
      above stays hard everywhere).

    Skips (with a visible line) when the process sees fewer than 2
    devices: the mesh topology needs the virtual-device flag staged
    before backend init (``bench.py --mesh`` and the smoke path pin it;
    ``scripts/bench_multichip.py`` is the fresh-process driver).
    """
    import jax

    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
    from esslivedata_tpu.kafka.wire import encode_da00
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.publish import METRICS
    from esslivedata_tpu.parallel import make_mesh
    from esslivedata_tpu.parallel.mesh_tick import DevicePlacement
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.multibank import (
        MultiBankParams,
        MultiBankViewWorkflow,
    )

    n_devices = len(jax.devices())
    if n_devices < 2:
        line = {
            "metric": "mesh_tick",
            "skipped": True,
            "reason": (
                f"{n_devices} device(s) visible; the mesh scenario "
                "needs >=2 virtual devices pinned before backend init "
                "(run bench.py --mesh or scripts/bench_multichip.py)"
            ),
        }
        emit_line(line)
        return line

    n_banks = 8
    pixels_per_bank = 64
    n_pixels = n_banks * pixels_per_bank
    banks = {
        f"bank{i}": np.arange(i * pixels_per_bank, (i + 1) * pixels_per_bank)
        for i in range(n_banks)
    }
    n_events = min(args.events or (1 << 17), 1 << 18)
    n_windows = max(6, (args.batches or 32) // 4)
    k = 2
    batches = []
    for s in range(4):
        rng = np.random.default_rng(500 + s)
        batches.append(
            EventBatch.from_arrays(
                rng.integers(0, n_pixels, n_events).astype(np.int64),
                rng.uniform(0.0, 7.1e7, n_events).astype(np.float32),
            )
        )

    def staged(i: int) -> StagedEvents:
        return StagedEvents(
            batch=batches[i % 4],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    uniq = [0]

    def make_mgr(mesh, *, toa_bins=32, placement=None, k_jobs=k):
        uniq[0] += 1
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench",
            name=f"mesh{uniq[0]}",
            source_names=["det0"],
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: MultiBankViewWorkflow(
                bank_detector_numbers=banks,
                params=MultiBankParams(
                    toa_bins=toa_bins, use_mesh=mesh is not None
                ),
                mesh=mesh,
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg),
            job_threads=2,
            placement=placement,
        )
        for _ in range(k_jobs):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        return mgr

    from esslivedata_tpu.core.timestamp import Timestamp

    T = Timestamp.from_ns

    def run(mgr, n, k_jobs=k):
        for w in range(2):
            out = mgr.process_jobs(
                {"det0": staged(w)}, start=T(0), end=T(1 + w)
            )
            assert len(out) == k_jobs
        METRICS.drain()
        mgr.event_cache_stats()
        wires = []
        start = time.perf_counter()
        for i in range(n):
            out = mgr.process_jobs(
                {"det0": staged(i)}, start=T(0), end=T(10 + i)
            )
            assert len(out) == k_jobs
            wires.append(
                [
                    encode_da00(name, 12345, dataarray_to_da00(da))
                    for res in out
                    for name, da in res.outputs.items()
                ]
            )
        dt = time.perf_counter() - start
        m = METRICS.drain()
        mgr.shutdown()
        return wires, m, dt

    # -- section 1: per-slice tick contract + single-device parity ---------
    # Largest power-of-two device subset <= 8: the data axis is 2-way
    # and the bank axis always divides the 512-row screen, so an odd
    # visible count (3, 5, 7 devices) runs on its power-of-two subset
    # instead of failing mesh construction or bank sharding.
    n_mesh = 1 << (min(8, n_devices).bit_length() - 1)
    data_axis = 2
    mesh = make_mesh(n_mesh, data=data_axis, bank=n_mesh // data_axis)
    placement = DevicePlacement(mesh)
    wires_mesh, m_mesh, _ = run(make_mgr(mesh, placement=placement), n_windows)
    wires_single, _m, _ = run(make_mgr(None), n_windows)
    slices = m_mesh["slices"]
    mesh_labels = [key for key in slices if key.startswith("mesh:")]
    wire_identical = wires_mesh == wires_single
    line = {
        "metric": "mesh_tick",
        "jobs": k,
        "mesh": {"data": data_axis, "bank": n_mesh // data_axis},
        "value": (
            slices[mesh_labels[0]]["executes"] / n_windows
            if mesh_labels
            else float("nan")
        ),
        "unit": "executes/slice/tick",
        "executes_per_tick": m_mesh["executes"] / n_windows,
        "fetches_per_tick": m_mesh["fetches"] / n_windows,
        "step_executes_per_tick": m_mesh["step_executes"] / n_windows,
        "tick_publishes": m_mesh["tick_publishes"],
        "slices": slices,
        "wire_byte_identical_vs_single_device": wire_identical,
        "windows": n_windows,
        "events_per_window": n_events,
    }
    emit_line(line)
    # The acceptance bound (asserted here AND in --smoke/CI): ONE
    # execute + ONE fetch per mesh slice per steady-state tick, no
    # separate step dispatches, byte-identical wire vs single-device.
    assert mesh_labels, slices
    for label, counts in slices.items():
        assert counts["executes"] == n_windows, (label, counts)
        assert counts["fetches"] == n_windows, (label, counts)
    assert m_mesh["step_executes"] == 0, m_mesh
    assert wire_identical

    # -- section 2: 1 -> n_devices data-sharded scaling curve --------------
    curve = []
    scale_events = min(max(n_events, 1 << 18), 1 << 20)
    scale_windows = max(4, n_windows // 2)
    rng = np.random.default_rng(77)
    big_batches = [
        EventBatch.from_arrays(
            rng.integers(0, n_pixels, scale_events).astype(np.int64),
            rng.uniform(0.0, 7.1e7, scale_events).astype(np.float32),
        )
        for _ in range(4)
    ]

    def staged_big(i: int) -> StagedEvents:
        return StagedEvents(
            batch=big_batches[i % 4],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    counts = [n for n in (1, 2, 4, 8) if n <= n_devices]
    for n_dev in counts:
        mgr = make_mgr(
            make_mesh(n_dev, data=n_dev, bank=1), toa_bins=100, k_jobs=1
        )
        for w in range(2):
            mgr.process_jobs({"det0": staged_big(w)}, start=T(0), end=T(w + 1))
        # Best-of-2 windows per point, like the graded headline: a
        # shared-core CI runner's noisy-neighbor dip on one pass must
        # not flip the monotonicity gate below.
        dt = float("inf")
        for _attempt in range(2):
            start = time.perf_counter()
            for i in range(scale_windows):
                mgr.process_jobs(
                    {"det0": staged_big(i)}, start=T(0), end=T(10 + i)
                )
            dt = min(dt, time.perf_counter() - start)
        mgr.shutdown()
        curve.append(
            {
                "devices": n_dev,
                "events_per_sec": scale_events * scale_windows / dt,
                "wall_ms_per_window": 1e3 * dt / scale_windows,
            }
        )
    monotone = len(curve) < 2 or (
        curve[1]["events_per_sec"] > curve[0]["events_per_sec"]
    )
    scaling_line = {
        "metric": "mesh_scaling",
        "curve": curve,
        "monotone_1_to_2": monotone,
        "events_per_window": scale_events,
        "windows": scale_windows,
        "note": (
            "data axis splits the scatter's event work per device; "
            "virtual CPU devices share one host's cores, so the 8-way "
            "point measures host contention, not chips — the topology "
            "contract (per-slice dispatch counts, parity) is what CI "
            "grades"
        ),
    }
    print(json.dumps(scaling_line), file=sys.stderr)
    if strict_scaling:
        assert monotone, curve
    line["scaling_curve"] = curve
    line["monotone_1_to_2"] = monotone
    return line


def bench_pipeline(args) -> dict:
    """Pipelined vs serial ingest through the REAL JobManager path
    (ADR 0111).

    Feeds identical windows of staged events through (a) the serial
    loop — prestage+step+publish back to back, paying sum(stages) — and
    (b) the bounded IngestPipeline, where decode | prestage | step
    overlap across windows. Reports per-stage utilization (stage busy
    seconds / pipeline wall seconds), the slowest stage's mean, and
    ``e2e_vs_max_stage`` — steady-state wall per batch over the slowest
    single stage, the pipelining figure of merit (1.0 = perfect
    overlap; the serial loop sits at sum/max). Ordering and output
    parity of the two paths are asserted, so a regression in either is
    loud here AND in --smoke/CI. One JSON line on stderr.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.ingest_pipeline import IngestPipeline
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 16)))
    det = np.arange(side * side).reshape(side, side)
    n_events = args.events
    n_windows = max(8, args.batches)
    n_distinct = 4
    batches = []
    for s in range(n_distinct):
        pid, toa = make_batch(n_events, side * side, seed=200 + s)
        batches.append(EventBatch.from_arrays(pid, toa))

    def staged(i: int) -> StagedEvents:
        return StagedEvents(
            batch=batches[i % n_distinct],
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )

    method = args.method if args.method in ("scatter", "sort") else "scatter"

    def make_mgr() -> JobManager:
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench", name="dv_pipe", source_names=["det0"]
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(job_factory=JobFactory(reg), job_threads=2)
        for _ in range(2):  # K=2: exercises prestage + fused stepping
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        return mgr

    t0, results_serial = Timestamp.from_ns(0), []
    mgr_s = make_mgr()
    mgr_s.process_jobs(
        {"det0": staged(0)}, start=t0, end=Timestamp.from_ns(1)
    )  # warm/compile
    start = time.perf_counter()
    for i in range(n_windows):
        results_serial.append(
            mgr_s.process_jobs(
                {"det0": staged(i)}, start=t0, end=Timestamp.from_ns(2 + i)
            )
        )
    serial_wall = time.perf_counter() - start
    mgr_s.shutdown()

    mgr_p = make_mgr()
    published: list = []
    pipe = IngestPipeline(
        job_manager=mgr_p,
        decode=lambda payload: (payload, {}, None),
        publish=lambda results, end: published.append(results),
        depth=2,
        flatten_workers=2,
        name="bench",
    )
    pipe.submit(
        {"det0": staged(0)}, start=t0, end=Timestamp.from_ns(1)
    )  # warm
    assert pipe.flush(timeout=120), "pipeline warm-up did not drain"
    pipe.stats()  # reset timers: compile cost stays out of utilization
    published.clear()
    start = time.perf_counter()
    for i in range(n_windows):
        pipe.submit(
            {"det0": staged(i)}, start=t0, end=Timestamp.from_ns(2 + i)
        )
    assert pipe.flush(timeout=300), "pipeline did not drain"
    pipelined_wall = time.perf_counter() - start
    stats = pipe.stats()
    pipe.stop(drain=True)
    mgr_p.shutdown()

    assert len(published) == n_windows, (
        f"dropped batches: published {len(published)} of {n_windows}"
    )
    for w, (res_p, res_s) in enumerate(zip(published, results_serial)):
        assert len(res_p) == len(res_s), f"window {w}: result count differs"
        for rp, rs in zip(res_p, res_s):
            for (kp, vp), (ks, vs) in zip(
                rp.outputs.items(), rs.outputs.items()
            ):
                assert kp == ks
                if not np.array_equal(
                    np.asarray(vp.values), np.asarray(vs.values)
                ):
                    raise AssertionError(
                        f"window {w} output {kp!r}: pipelined != serial"
                    )

    stage_mean_ms = {
        name: entry["mean_ms"] for name, entry in stats["stages"].items()
    }
    max_stage_ms = max(stage_mean_ms.values()) if stage_mean_ms else 0.0
    per_batch_ms = 1e3 * pipelined_wall / n_windows
    line = {
        "metric": "pipeline_ingest",
        "unit": "events/s",
        "value": n_events * n_windows / pipelined_wall,
        "serial_events_per_sec": n_events * n_windows / serial_wall,
        "pipelined_vs_serial_speedup": serial_wall / pipelined_wall,
        "stage_mean_ms": {
            k: round(v, 3) for k, v in stage_mean_ms.items()
        },
        "stage_utilization": {
            k: round(v, 4) for k, v in stats["utilization"].items()
        },
        "per_batch_ms": round(per_batch_ms, 3),
        # Steady-state wall per batch over the slowest stage: 1.0 is a
        # perfect pipeline; the acceptance bound is <= 1.25 on the CPU
        # control (sum-of-stages sits well above it).
        "e2e_vs_max_stage": (
            round(per_batch_ms / max_stage_ms, 4) if max_stage_ms else None
        ),
        "windows": n_windows,
        "events_per_window": n_events,
        "jobs": 2,
        "parity": "bit-identical",
    }
    emit_line(line)
    return line


def bench_decode(args) -> dict:
    """Batch decode plane vs the per-message reference decoder (ADR 0125).

    Builds real ev44 wire polls and measures the decode STAGE both ways
    through the real adapter + accumulator path: (a) per message —
    ``adapt`` -> ``DetectorEvents`` ndarrays -> staging-buffer append
    per message; (b) batched — ``adapt_batch`` -> ``EventChunkRef``
    headers -> one arena landing at ``get()``. Asserts the da00 wire
    out of a real JobManager is byte-identical across the two decode
    modes (the rollout gate's non-negotiable), that the batch decoder
    clears the >= 3x decode-stage events/s floor, and — through a real
    IngestPipeline whose decode worker runs the batch decoder — that
    decode is no longer the max-utilization stage. One JSON line on
    stderr.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.ingest_pipeline import IngestPipeline
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.kafka import wire
    from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
    from esslivedata_tpu.kafka.message_adapter import (
        KafkaToDetectorEventsAdapter,
    )
    from esslivedata_tpu.kafka.source import FakeKafkaMessage
    from esslivedata_tpu.kafka.stream_mapping import (
        InputStreamKey,
        StreamMapping,
    )
    from esslivedata_tpu.kafka.wire import encode_da00
    from esslivedata_tpu.preprocessors.event_data import ToEventBatch
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import (
        DetectorViewParams,
        DetectorViewWorkflow,
        project_logical,
    )

    side = int(np.sqrt(min(args.pixels, 1 << 16)))
    n_pixel = side * side
    det = np.arange(n_pixel).reshape(side, side)
    # ~200 events/message is a representative ESS pulse chunk: small
    # enough that per-message Python+allocation overhead dominates the
    # reference path, exactly the regime the batch decoder targets.
    events_per_msg = 200
    n_msgs = int(max(128, min(1200, args.events // events_per_msg)))
    n_polls = max(4, args.batches)
    # Enough decoded messages per mode that the faster path still
    # accumulates a stable wall time on a noisy CI host.
    reps = max(1, -(-4000 // (n_msgs * n_polls)))

    mapping = StreamMapping(
        instrument="bench",
        detectors={
            InputStreamKey(topic="bench_det", source_name="panel_a"): "det0"
        },
    )

    rng = np.random.default_rng(125)
    polls: list[list] = []
    for p in range(n_polls):
        raws = []
        for m in range(n_msgs):
            tof = rng.uniform(0.0, 71e6, events_per_msg).astype(np.int32)
            pid = rng.integers(0, n_pixel, events_per_msg).astype(np.int32)
            buf = wire.encode_ev44(
                "panel_a",
                p * n_msgs + m,
                np.array([1_000_000 + p * n_msgs + m], dtype=np.int64),
                np.array([0], dtype=np.int32),
                tof,
                pixel_id=pid,
            )
            raws.append(FakeKafkaMessage(buf, "bench_det"))
        polls.append(raws)
    poll_bytes = sum(len(r.value()) for r in polls[0])

    adapters = {
        "per_message": KafkaToDetectorEventsAdapter(
            mapping, batch_wire=False
        ),
        "batch": KafkaToDetectorEventsAdapter(mapping, batch_wire=True),
    }

    def decode_poll(mode: str, acc: ToEventBatch, raws):
        adapter = adapters[mode]
        if mode == "batch":
            for msg in adapter.adapt_batch(raws):
                acc.add(msg.timestamp, msg.value)
        else:
            for raw in raws:
                msg = adapter.adapt(raw)
                acc.add(msg.timestamp, msg.value)
        return acc.get()

    events_per_sec: dict[str, float] = {}
    staged_n: dict[str, int] = {}
    for mode in ("per_message", "batch"):
        acc = ToEventBatch()
        staged = decode_poll(mode, acc, polls[0])  # warm pools/buffers
        staged_n[mode] = staged.n_events
        del staged
        acc.release_buffers()
        start = time.perf_counter()
        for _ in range(reps):
            for raws in polls:
                staged = decode_poll(mode, acc, raws)
                del staged  # returns the arena lease to the pool
                acc.release_buffers()
        dt = time.perf_counter() - start
        events_per_sec[mode] = (
            reps * n_polls * n_msgs * events_per_msg / dt
        )
    assert staged_n["per_message"] == staged_n["batch"], staged_n
    speedup = events_per_sec["batch"] / events_per_sec["per_message"]

    # Byte-identity: decode mode may not change a single da00 wire byte
    # out of the real JobManager path (same windows, same job sequence).
    method = args.method if args.method in ("scatter", "sort") else "scatter"

    def make_mgr() -> JobManager:
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="bench", name="dv_decode", source_names=["det0"]
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(histogram_method=method),
            )
        )
        mgr = JobManager(job_factory=JobFactory(reg), job_threads=2)
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name="det0")
            )
        )
        return mgr

    t0 = Timestamp.from_ns(0)
    n_windows = min(n_polls, 4)
    wire_out: dict[str, list[list[bytes]]] = {}
    for mode in ("per_message", "batch"):
        mgr = make_mgr()
        acc = ToEventBatch()
        staged = decode_poll(mode, acc, polls[0])
        mgr.process_jobs(
            {"det0": staged}, start=t0, end=Timestamp.from_ns(1)
        )  # warm/compile
        acc.release_buffers()
        wire_out[mode] = []
        for i in range(n_windows):
            staged = decode_poll(mode, acc, polls[i])
            out = mgr.process_jobs(
                {"det0": staged}, start=t0, end=Timestamp.from_ns(2 + i)
            )
            acc.release_buffers()
            wire_out[mode].append(
                [
                    encode_da00(name, 12345, dataarray_to_da00(da))
                    for res in out
                    for name, da in res.outputs.items()
                ]
            )
        mgr.shutdown()
    for w, (ref, bat) in enumerate(
        zip(wire_out["per_message"], wire_out["batch"])
    ):
        assert ref == bat, (
            f"window {w}: batch-decode da00 wire != per-message wire"
        )

    # Utilization: a real IngestPipeline whose decode worker runs the
    # batch decoder end to end (adapt_batch -> arena -> StagedEvents).
    # The acceptance claim is relative — decode is no longer the
    # bottleneck stage — so it holds at smoke scale too.
    mgr_p = make_mgr()
    published: list = []

    def pipe_decode(raws):
        acc = ToEventBatch()
        for msg in adapters["batch"].adapt_batch(raws):
            acc.add(msg.timestamp, msg.value)
        staged = acc.get().detach()
        acc.release_buffers()
        return {"det0": staged}, {}, None

    pipe = IngestPipeline(
        job_manager=mgr_p,
        decode=pipe_decode,
        publish=lambda results, end: published.append(results),
        depth=2,
        flatten_workers=2,
        name="bench-decode",
    )
    pipe.submit(polls[0], start=t0, end=Timestamp.from_ns(1))  # warm
    assert pipe.flush(timeout=120), "decode pipeline warm-up did not drain"
    pipe.stats()  # reset timers: compile cost stays out of utilization
    published.clear()
    for i in range(n_polls):
        pipe.submit(polls[i], start=t0, end=Timestamp.from_ns(2 + i))
    assert pipe.flush(timeout=300), "decode pipeline did not drain"
    stats = pipe.stats()
    pipe.stop(drain=True)
    mgr_p.shutdown()
    assert len(published) == n_polls, (
        f"dropped polls: published {len(published)} of {n_polls}"
    )
    util = stats["utilization"]
    max_stage = max(util, key=util.get) if util else None

    line = {
        "metric": "decode_plane",
        "unit": "events/s",
        # Graded value: decode-stage throughput with the batch decoder.
        "value": events_per_sec["batch"],
        "per_message_events_per_sec": events_per_sec["per_message"],
        "batch_vs_per_message_speedup": round(speedup, 2),
        "wire_mb_per_poll": round(poll_bytes / 1e6, 3),
        "messages_per_poll": n_msgs,
        "events_per_message": events_per_msg,
        "polls": n_polls,
        "wire_byte_identical": True,
        "pipeline_stage_utilization": {
            k: round(v, 4) for k, v in util.items()
        },
        "pipeline_max_stage": max_stage,
        "decode_not_max_stage": max_stage != "decode",
    }
    emit_line(line)
    # The acceptance floor (ADR 0125): batch decode >= 3x the
    # per-message reference on the decode stage, and decode off the
    # critical path of the pipelined ingest.
    assert speedup >= 3.0, line
    assert max_stage != "decode", line
    return line


def bench_latency(args) -> None:
    """p99 ingest->publish latency through a real detector service.

    The BASELINE latency target (p99 Kafka->dashboard < 100 ms) minus the
    broker hops, which this environment cannot include: per pulse, ev44
    bytes are injected into a real service (adapters -> batcher -> staging
    -> jitted step -> da00 serialization) and the wall time from inject to
    published output is recorded. Reported on stderr.

    A publish is one execute + one device->host fetch (the fused
    PackedPublisher path), i.e. ONE accelerator round trip. Alongside
    the totals this reports an interleaved round-trip probe
    (execute+fetch of a tiny fresh array) and the residual = latency -
    rtt, which is the framework's own cost.
    """
    from esslivedata_tpu.config import JobId, WorkflowConfig
    from esslivedata_tpu.config.instruments.dummy.specs import (
        DETECTOR_VIEW_HANDLE,
        INSTRUMENT,
    )
    from esslivedata_tpu.core.message_batcher import NaiveMessageBatcher
    from esslivedata_tpu.kafka import wire
    from esslivedata_tpu.kafka.sink import (
        FakeProducer,
        KafkaSink,
        make_default_serializer,
    )
    from esslivedata_tpu.kafka.source import FakeKafkaMessage
    from esslivedata_tpu.services.detector_data import (
        make_detector_service_builder,
    )

    from esslivedata_tpu.services.fake_sources import PulsedRawSource

    builder = make_detector_service_builder(
        instrument="dummy", batcher=NaiveMessageBatcher(), job_threads=1
    )
    raw = PulsedRawSource([])
    producer = FakeProducer()
    sink = KafkaSink(
        producer,
        make_default_serializer(builder.stream_mapping.livedata, "lat"),
    )
    service = builder.from_raw_source(raw, sink)
    config = WorkflowConfig(
        identifier=DETECTOR_VIEW_HANDLE.workflow_id,
        job_id=JobId(source_name="panel_0"),
        params={},
    )
    raw.inject(
        FakeKafkaMessage(
            json.dumps(
                {"kind": "start_job", "config": config.model_dump(mode="json")}
            ).encode(),
            "dummy_livedata_commands",
        )
    )
    service.step()

    import jax
    import jax.numpy as jnp

    probe = jax.jit(lambda x: x * 1.0000001)
    probe_x = jnp.arange(16, dtype=jnp.float32)

    def rtt_ms() -> float:
        t0 = time.perf_counter()
        np.asarray(probe(probe_x))
        return 1e3 * (time.perf_counter() - t0)

    rtt_ms()  # compile outside the timed region

    det = INSTRUMENT.detectors["panel_0"]
    ids_space = det.detector_number.reshape(-1)
    rng = np.random.default_rng(3)
    events_per_pulse = max(1, args.events // 16)
    pulse_period_ns = int(1e9 / 14)
    n_pulses = 100
    latencies = []
    rtts = []
    # Mirror the production worker's GC policy (core/service.py
    # _run_loop): the cycle collector runs BETWEEN pulses, never inside
    # the measured ingest->publish window.
    import gc

    gc_was_enabled = gc.isenabled()
    gc.disable()
    for pulse in range(n_pulses + 5):
        t_pulse = 1_700_000_000_000_000_000 + pulse * pulse_period_ns
        ids = rng.choice(ids_space, events_per_pulse).astype(np.int32)
        toa = rng.uniform(0, 7.0e7, events_per_pulse).astype(np.int32)
        payload = wire.encode_ev44(
            det.source_name, pulse, np.array([t_pulse]), np.array([0]),
            toa, pixel_id=ids,
        )
        n_before = len(producer.messages)
        start = time.perf_counter()
        raw.inject(FakeKafkaMessage(payload, "dummy_detector"))
        service.step()
        if len(producer.messages) > n_before and pulse >= 5:  # warmed
            latencies.append(1e3 * (time.perf_counter() - start))
        if pulse >= 5 and pulse % 10 == 0:
            rtts.append(rtt_ms())
        if pulse % 20 == 0:
            gc.collect()
    if gc_was_enabled:
        gc.enable()
    if not latencies:
        print(
            json.dumps(
                {
                    "metric": "ingest_to_publish_latency_ms",
                    "error": "no output published — check job errors / "
                    f"serialize drops (produced={len(producer.messages)})",
                }
            ),
            file=sys.stderr,
        )
        return
    latencies.sort()
    rtts.sort()
    p50 = latencies[len(latencies) // 2]
    # Nearest-rank p99 (ceil(0.99*n)-1), NOT the max sample.
    p99 = latencies[max(0, -(-99 * len(latencies) // 100) - 1)]
    rtt50 = rtts[len(rtts) // 2] if rtts else 0.0
    print(
        json.dumps(
            {
                "metric": "ingest_to_publish_latency_ms",
                "p50": p50,
                "p99": p99,
                "n": len(latencies),
                "events_per_pulse": events_per_pulse,
                "unit": "ms",
                # One publish = one accelerator round trip; the residual
                # is the framework's own cost once the link is removed.
                "device_roundtrip_p50": rtt50,
                "residual_p50": p50 - rtt50,
                "residual_p99": p99 - rtt50,
            }
        ),
        file=sys.stderr,
    )


def run_benchmark(args) -> dict:
    """The headline measurement; returns the graded JSON record, which
    names the device it ran on (platform, device_kind, device_count).

    The timed loop is the service hot path: per batch, the host flattens
    raw (pixel_id, toa) into int32 bin indices (4 bytes/event over the
    link instead of 8 — in production the native ingest shim does this
    during ev44 decode) and dispatches the jitted scatter. Dispatch is
    async, so the host flatten of batch i+1 overlaps the device scatter
    of batch i, exactly as the streaming service overlaps staging with
    compute.
    """
    from esslivedata_tpu.ops import EventBatch, EventHistogrammer
    from esslivedata_tpu.utils.runtime import device_identity

    device = device_identity()
    lo, hi = 0.0, 71_000_000.0
    edges = np.linspace(lo, hi, args.toa_bins + 1)

    # Pre-stage a few distinct batches so the device never sees cached inputs.
    n_distinct = 4
    if args.replay:
        batches = make_replay_batches(
            args.replay, args.events, n_distinct, args.pixels
        )
    else:
        batches = [
            EventBatch.from_arrays(*make_batch(args.events, args.pixels, seed=s))
            for s in range(n_distinct)
        ]

    def make_step(h, timer=None):
        """Per-batch ingest for the timed loops: pallas2d takes the
        fused flatten+partition path; everything else the host-flatten +
        flat-scatter path — each method's production ingest, not a common
        denominator. ``timer`` (utils.profiling.StageTimer) optionally
        splits each step into the flatten-partition / transfer / step
        stages for the structured breakdown in the metric line."""
        from contextlib import nullcontext

        from esslivedata_tpu.ops.event_batch import dispatch_safe

        stage = timer.stage if timer is not None else (lambda name: nullcontext())
        if h._method == "pallas2d":

            def step(s, b):
                with stage("flatten_partition"):
                    ev, cm = h.flatten_partition_host(b.pixel_id, b.toa)
                with stage("transfer"):
                    ev, cm = dispatch_safe(ev), dispatch_safe(cm)
                with stage("step"):
                    return h._step_part(s, ev, cm)

            return step

        def step(s, b):
            with stage("flatten_partition"):
                flat = h.flatten_host(b.pixel_id, b.toa)
            with stage("transfer"):
                flat = dispatch_safe(flat)
            with stage("step"):
                return h.step_flat(s, flat)

        return step

    def calibrate(method: str) -> float:
        """Short timed run; returns events/s for one method."""
        h = EventHistogrammer(
            toa_edges=edges,
            n_screen=args.pixels,
            method=method,
            pallas2d_budget=args.pallas2d_budget,
            pallas2d_chunk=args.pallas2d_chunk,
            pallas2d_precision=args.pallas2d_precision,
        )
        step = make_step(h)
        s = h.init_state()
        s = step(s, batches[0])
        s.window.block_until_ready()
        reps = 4
        t0 = time.perf_counter()
        for i in range(reps):
            s = step(s, batches[i % n_distinct])
        s.window.block_until_ready()
        return args.events * reps / (time.perf_counter() - t0)

    method = args.method
    if method == "pallas":
        # The headline 1.5Mx100 bin space is far beyond the pallas
        # kernel's VMEM bound: measure the headline on the scatter and
        # let the secondary configs (--all) measure pallas where it
        # fits (config1's 1-D monitor histogram).
        print(
            "--method pallas: headline uses scatter (bin space exceeds "
            "the pallas VMEM bound); config1 measures pallas under --all",
            file=sys.stderr,
        )
        method = "scatter"
    if method == "auto":
        # Scatter vs sort is hardware-dependent (random-index scatter is
        # memory-bound on TPU; sorted scatter trades an argsort for
        # locality), and pallas2d's compact uint16 wire halves the
        # host->device bytes (the binding constraint on degraded links)
        # — measure each briefly and keep the winner.
        rates = {m: calibrate(m) for m in ("scatter", "sort", "pallas2d")}
        method = max(rates, key=rates.get)
        if args.verbose:
            print(
                f"auto method: {rates} -> {method}",
                file=sys.stderr,
            )

    hist = EventHistogrammer(
        toa_edges=edges,
        n_screen=args.pixels,
        method=method,
        pallas2d_budget=args.pallas2d_budget,
        pallas2d_chunk=args.pallas2d_chunk,
        pallas2d_precision=args.pallas2d_precision,
    )
    from esslivedata_tpu.utils.profiling import StageTimer

    # Per-stage decomposition of every run's metric line (not only --all):
    # the recorded line then carries the breakdown for trend analysis. The
    # timed loop splits flatten-partition / transfer / step; decode and
    # publish are measured alongside at the same batch size.
    stage_timer = StageTimer()
    step_fn = make_step(hist, stage_timer)
    state = hist.init_state()

    # Warm-up: compile + first transfers, plus a few steps to let the
    # host->device link reach steady state before the timed window.
    for i in range(4):
        state = step_fn(state, batches[i % n_distinct])
    state.window.block_until_ready()
    stage_timer.drain()  # compile/first-transfer costs stay out of the stats

    from contextlib import nullcontext

    if args.profile:
        import jax

        trace = jax.profiler.trace(args.profile)
    else:
        trace = nullcontext()
    # Three timed windows, best one graded (all three are printed to
    # stderr under --verbose for the record).
    n_windows = 3
    per_window = max(1, args.batches // n_windows)
    window_rates = []
    with trace:
        step = 0
        for _ in range(n_windows):
            start = time.perf_counter()
            for _ in range(per_window):
                state = step_fn(state, batches[step % n_distinct])
                step += 1
            state.window.block_until_ready()
            dt = time.perf_counter() - start
            window_rates.append(args.events * per_window / dt)
    ev_per_s = max(window_rates)
    if args.verbose:
        print(
            "window rates: "
            + ", ".join(f"{r:.3e}" for r in window_rates),
            file=sys.stderr,
        )

    total = float(hist.read(state)[0].sum())
    # timed steps (3 windows x per_window) + 4 warm-up steps
    expected = args.events * (n_windows * per_window + 4)
    if not np.isclose(total, expected, rtol=1e-3):
        print(
            f"WARNING: histogram total {total} != expected {expected}",
            file=sys.stderr,
        )

    # Stage decomposition: the loop's host/dispatch stages, plus a decode
    # probe (ev44 codec at this batch size) and a production-shaped
    # publish (summaries + window fold = one execute + one packed fetch).
    stages = {
        name: {
            "mean_ms": round(s["mean_ms"], 3),
            "total_s": round(s["total_s"], 4),
        }
        for name, s in stage_timer.drain().items()
    }
    decode_ms = measure_decode_ms(args.events)
    stages["decode"] = (
        {"mean_ms": round(decode_ms, 3)} if decode_ms is not None else {}
    )
    try:
        from esslivedata_tpu.ops.publish import PackedPublisher

        def _pub_program(s):
            cum, win = hist.views_of(s)
            return (
                {"spectrum": win.sum(axis=0), "counts": win.sum()},
                hist.fold_window(s),
            )

        publisher = PackedPublisher(_pub_program)
        _, state = publisher(state)  # compile outside the timed reps
        pub_reps = 3
        t_pub = time.perf_counter()
        for _ in range(pub_reps):
            _, state = publisher(state)
        stages["publish"] = {
            "mean_ms": round(
                1e3 * (time.perf_counter() - t_pub) / pub_reps, 3
            )
        }
    except Exception:
        traceback.print_exc()
        stages["publish"] = {}

    pid, toa = make_batch(args.events, args.pixels, seed=99)
    fresh = bench_numpy_baseline(pid, toa, args.pixels, args.toa_bins, lo, hi)
    # vs_baseline uses the PINNED constant from BASELINE.json when present
    # so the ratio is comparable across rounds (the shared host's fresh
    # measurement swings ~40% run to run); the fresh number rides along.
    baseline = _pinned_baseline() or fresh

    if args.verbose:
        import jax

        print(
            f"device={jax.devices()[0]} events/batch={args.events} "
            f"batches={args.batches} wall={dt:.3f}s "
            f"{device['platform']}={ev_per_s:.3e} ev/s numpy={baseline:.3e} ev/s",
            file=sys.stderr,
        )

    result = {
        "metric": "loki_2d_pixel_tof_histogram_events_per_sec",
        "value": ev_per_s,
        "unit": "events/s",
        "vs_baseline": ev_per_s / baseline,
        "baseline_ev_s": baseline,
        "baseline_fresh_ev_s": fresh,
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["count"],
        "method": method,
        "window": "best-of-3",
        # Ingest bytes/event over the host->device link: 4 for the
        # flat-int32 wire, 2 when pallas2d's compact uint16 wire engages
        # (ADR 0108).
        "wire_bytes_per_event": (
            2 if method == "pallas2d" and getattr(hist, "_p2_compact", False)
            else 4
        ),
        # Per-stage decomposition (ms per batch) on EVERY run, so the
        # graded line carries the trend data without --all.
        "stages": stages,
    }
    if args.replay:
        result["distribution"] = f"replayed:{Path(args.replay).name}"
    # The graded line goes out BEFORE the optional secondary sections: a
    # failure in those must not discard a completed headline
    # measurement. The telemetry snapshot rides it (ADR 0116): the line
    # then carries the dispatch/compile/RTT decomposition, not just
    # throughput.
    result.setdefault("telemetry", telemetry_snapshot())
    print(json.dumps(result), flush=True)

    if args.all:
        for section in (
            lambda: bench_secondary_configs(args, edges, batches, method),
            lambda: bench_multijob(args),
            lambda: bench_publish(args),
            lambda: bench_tick(args),
            lambda: bench_workloads(args),
            lambda: bench_fanout(args),
            lambda: bench_relay(args),
            lambda: bench_churn(args),
            lambda: bench_slo(args),
            lambda: bench_telemetry(args),
            lambda: bench_mesh(args),
            lambda: bench_pipeline(args),
            lambda: bench_decode(args),
            lambda: bench_latency(args),
        ):
            section()  # a section that raises fails the run

    return result


def _headline_main(args) -> int:
    """The headline measurement, in this process, on the device jax finds.

    One process, one import of jax: nothing here starts a child, so
    nothing can hold the chip against the measurement. A platform other
    than ``tpu`` is refused unless ``--cpu`` asked for it — an XLA-CPU
    number is not a device metric and is never printed as one by
    accident.
    """
    if args.cpu:
        from esslivedata_tpu.utils.platform_pin import pin_cpu

        pin_cpu()
    from esslivedata_tpu.utils.runtime import device_identity

    platform = device_identity()["platform"]
    if platform != "tpu" and not args.cpu:
        print(
            f"bench.py: jax runs on platform {platform!r}, not a TPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). The "
            "headline is a device metric and is not measured on another "
            "backend; pass --cpu to time the XLA-CPU backend on purpose, "
            "or --smoke for the structural CPU check.",
            file=sys.stderr,
        )
        return 1
    # Batch sizing is backend-dependent: 4M events amortize the TPU
    # scatter's fixed cost, while on CPU smaller batches stay
    # cache-resident. None = "user left it unset": resolve per platform;
    # explicit values always win.
    if args.events is None:
        args.events = (1 << 18) if platform == "cpu" else (1 << 22)
    if args.batches is None:
        args.batches = 128 if platform == "cpu" else 32
    run_benchmark(args)  # prints the graded JSON line itself
    return 0


def _pinned_baseline() -> float | None:
    """The pinned single-threaded numpy baseline from BASELINE.json.

    Pinned (with provenance) so ``vs_baseline`` is comparable across
    rounds; the shared host's fresh measurement swings ~40%.
    """
    try:
        doc = json.loads(
            (Path(__file__).resolve().parent / "BASELINE.json").read_text()
        )
        return float(doc["pinned_baseline"]["events_per_sec"])
    except (OSError, KeyError, ValueError, TypeError):
        return None


def _parse_args():
    parser = argparse.ArgumentParser()
    # None = platform-resolved in the measurement child (TPU: 4M x 32,
    # CPU: 256k x 128 — see _child_main).
    parser.add_argument("--events", type=int, default=None)
    parser.add_argument("--batches", type=int, default=None)
    parser.add_argument("--pixels", type=int, default=1_500_000)  # LOKI scale
    parser.add_argument("--toa-bins", type=int, default=100)
    # pallas2d hardware-tuning knobs: block-size budget (bins/VMEM tile)
    # and events per grid step. Sweep on real TPU, e.g.
    #   for b in 32768 65536 131072; do
    #     python bench.py --method pallas2d --pallas2d-budget $b; done
    parser.add_argument("--pallas2d-budget", type=int, default=None)
    parser.add_argument("--pallas2d-chunk", type=int, default=None)
    parser.add_argument(
        "--pallas2d-precision", choices=["bf16", "int8"], default="bf16",
        help="one-hot MXU dtype; int8 doubles the v5e MXU rate, both exact"
    )
    parser.add_argument(
        "--method",
        default="scatter",
        choices=["auto", "scatter", "sort", "pallas", "pallas2d"],
        help="scatter wins on every TPU measured (sort adds an argsort "
        "for no scatter gain); 'auto' re-measures both with a short "
        "calibration. 'pallas' "
        "(ops/pallas_hist.py one-hot reduction) only fits VMEM-sized "
        "bin spaces — the headline 1.5Mx100 config rejects it, but "
        "config1's 1-D monitor histogram measures it (see --all). "
        "'pallas2d' (ops/pallas_hist2d.py MXU-tiled kernel) covers the "
        "full headline bin space; --all also reports its device-resident "
        "A/B against the scatter",
    )
    parser.add_argument(
        "--all",
        action="store_true",
        help="Also measure BASELINE configs 1/3/4/5 plus the K-jobs "
        "stage-once scenario (reported on stderr; stdout stays the "
        "single headline JSON line)",
    )
    parser.add_argument(
        "--multijob",
        action="store_true",
        help="Run ONLY the K-jobs-one-stream stage-once scenario on the "
        "ambient backend and exit (dev flag)",
    )
    parser.add_argument(
        "--pipeline",
        action="store_true",
        help="Run ONLY the pipelined-vs-serial ingest scenario "
        "(ADR 0111) on the ambient backend and exit: stage overlap, "
        "per-stage utilization, bit-identical parity (dev flag, like "
        "--multijob; also runs under --all and --smoke)",
    )
    parser.add_argument(
        "--decode",
        action="store_true",
        help="Run ONLY the batch-decode-plane scenario (ADR 0125) and "
        "exit: per-message vs batched ev44 wire decode through the real "
        "adapter + accumulator path — batch decoder >= 3x decode-stage "
        "events/s asserted, da00 wire byte-identical across decode "
        "modes, and decode no longer the max-utilization stage of a "
        "real IngestPipeline (dev flag, like --multijob; also runs "
        "under --all and --smoke)",
    )
    parser.add_argument(
        "--publish",
        action="store_true",
        help="Run ONLY the cross-job publish-combining scenario "
        "(ADR 0113) on the ambient backend and exit: executes + "
        "fetches per tick and fetched bytes per publish at K=1 vs K=4 "
        "through the real JobManager path, K=4 fetches/tick == 1 "
        "asserted (dev flag, like --multijob; also runs under --all "
        "and --smoke)",
    )
    parser.add_argument(
        "--tick",
        action="store_true",
        help="Run ONLY the one-dispatch tick-program scenario "
        "(ADR 0114) on the ambient backend and exit: K=4 same-layout "
        "jobs through the real JobManager, steady-state 1 execute + "
        "1 fetch per tick asserted with a per-tick RTT decomposition "
        "and combined-vs-tick da00 byte identity (dev flag, like "
        "--multijob; also runs under --all and --smoke)",
    )
    parser.add_argument(
        "--workloads",
        action="store_true",
        help="Run ONLY the workload-plane scenario (ADR 0122) and "
        "exit: powder-focus + filtered detector-view + imaging through "
        "the real JobManager — 1 execute + 1 fetch per FILTERED tick "
        "asserted, pass-all-filter da00 byte identity, calibration "
        "LUT-swap compile classified layout_swap (and 0 hot-path "
        "compiles with the AOT warm-up attached) (dev flag, like "
        "--multijob; also runs under --all and --smoke)",
    )
    parser.add_argument(
        "--mesh",
        action="store_true",
        help="Run ONLY the mesh serving-tier scenario (ADR 0115) on an "
        "8-virtual-device CPU mesh and exit: K=2 bank-sharded multibank "
        "jobs through the real JobManager with DevicePlacement — "
        "asserts 1 execute + 1 fetch per mesh slice per steady-state "
        "tick and da00 byte identity vs the single-device tick "
        "program, then records the 1->2->4->8-device data-sharded "
        "scaling curve (dev flag, like --multijob; also runs under "
        "--all and --smoke; scripts/bench_multichip.py is the "
        "fresh-process driver)",
    )
    parser.add_argument(
        "--fanout",
        action="store_true",
        help="Run ONLY the result fan-out tier scenario (ADR 0117) on "
        "the ambient backend and exit: K=4 jobs publish through the "
        "real JobManager + ServingPlane while N in {1, 100, 2000} "
        "simulated SSE subscribers attach — asserts publish-side "
        "device executes+fetches per tick are IDENTICAL across N, "
        "subscriber reconstruction byte-identical to the sink da00 "
        "wire, and delta bytes well under full-frame replay (dev "
        "flag, like --multijob; also runs under --all and --smoke, "
        "which uses N=50)",
    )
    parser.add_argument(
        "--relay",
        action="store_true",
        help="Run ONLY the relay-tree fan-out edge scenario (ADR 0121) "
        "on the ambient backend and exit: K=4 jobs publish through the "
        "real JobManager + ServingPlane while R in {1, 2, 4} fleet "
        "relays each re-fan to their own subscribers — asserts "
        "compute-tier publish executes/tick == 1.0 and hub encodes == "
        "one per stream per tick at every R, downstream frames "
        "byte-identical to a direct subscription, and served-"
        "subscriber capacity monotone in R (dev flag, like --multijob; "
        "also runs under --all and --smoke, which uses R in {1, 2})",
    )
    parser.add_argument(
        "--churn",
        action="store_true",
        help="Run ONLY the durability-plane churn scenario (ADR 0118) "
        "and exit: checkpoint mid-run, kill, restore + replay from "
        "the bookmark — asserts the replayed da00 wire byte-identical "
        "to an uninterrupted control, a reconnecting subscriber sees "
        "the restored accumulation (a gap, not a reset), and a job "
        "commit with AOT warm-up costs 0 hot-path jit compiles where "
        "the cold commit pays >= 1 (dev flag, like --multijob; also "
        "runs under --all and --smoke)",
    )
    parser.add_argument(
        "--slo",
        action="store_true",
        help="Run ONLY the SLO-plane scenario (ADR 0120) and exit: the "
        "load+chaos harness through the real JobManager + ServingPlane "
        "— p99 consume->subscriber e2e latency decomposed by stage, "
        "injected state-loss/wedged-subscriber/slow-tick/consumer-"
        "restart chaos with containment asserted (signaled resets, "
        "wire parity, 0 hot-path compiles, bounded queues, coalesce "
        "recovery), the scripts/slo_gate.py rule gate green, and a "
        "containment-disabled control proving the gate goes red (dev "
        "flag, like --multijob; also runs under --all and --smoke)",
    )
    parser.add_argument(
        "--telemetry",
        action="store_true",
        help="Run ONLY the telemetry-overhead guard (ADR 0116) and "
        "exit: microcosts of the registry/tracer instrument ops and "
        "the per-tick overhead bound, asserted < 1%% of tick wall "
        "(dev flag; also runs under --all and --smoke)",
    )
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="CI smoke: tiny CPU-pinned headline run; asserts the graded "
        "JSON line parses and carries the per-stage breakdown fields, "
        "then exits. Catches hot-path breakage before a TPU round.",
    )
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument(
        "--profile",
        default=None,
        metavar="DIR",
        help="write a JAX device trace of the timed headline loop to DIR",
    )
    parser.add_argument(
        "--replay",
        default=None,
        metavar="NEXUS_FILE",
        help="draw headline batches from a recorded NeXus event file "
        "(pixel ids wrapped into --pixels) instead of uniform random",
    )
    parser.add_argument(
        "--cpu",
        action="store_true",
        help="pin JAX to the CPU and time the XLA-CPU backend on "
        "purpose; without it a run that finds no TPU exits non-zero",
    )
    return parser.parse_args()


def _smoke_main(args) -> int:
    """CI smoke: tiny CPU run, assert the metric line's structure.

    Pins 8 virtual devices so the mesh serving-tier control (ADR 0115)
    runs its per-slice assertions; the headline smoke line is
    structural, not a perf gate, so the thread-pool split is harmless.
    """
    from esslivedata_tpu.utils.platform_pin import pin_cpu

    pin_cpu(8)
    args.events = args.events or 8192
    args.batches = args.batches or 6
    args.pixels = min(args.pixels, 1 << 16)
    result = run_benchmark(args)
    line = json.dumps(result)
    parsed = json.loads(line)
    problems = []
    for field in ("metric", "value", "unit", "vs_baseline", "stages"):
        if field not in parsed:
            problems.append(f"missing field {field!r}")
    if not (isinstance(parsed.get("value"), (int, float)) and parsed["value"] > 0):
        problems.append(f"non-positive value: {parsed.get('value')!r}")
    stages = parsed.get("stages", {})
    for name in ("decode", "flatten_partition", "transfer", "step", "publish"):
        if name not in stages:
            problems.append(f"missing stage {name!r}")
    # Publish-combining control (ADR 0113): tiny run through the real
    # JobManager; the scenario itself asserts the 1-fetch-per-tick
    # bound at K=4 and the static-cache steady state, and this guards
    # the report's structure.
    try:
        pub_line = bench_publish(args)
    except Exception:
        traceback.print_exc()
        problems.append("publish scenario raised")
    else:
        for field in (
            "fetches_per_tick",
            "executes_per_tick",
            "fetched_bytes_per_publish",
            "combined_jobs_per_publish",
        ):
            if pub_line.get(field) is None:
                problems.append(f"publish line missing {field!r}")
        if pub_line.get("fetches_per_tick") != 1.0:
            problems.append("publish combining not at 1 fetch/tick")
    # Tick-program control (ADR 0114): tiny run through the real
    # JobManager; the scenario itself asserts the 1-execute-1-fetch
    # steady state at K=4 and the combined-vs-tick da00 byte identity,
    # and this guards the report's structure.
    tick_line = None
    try:
        tick_line = bench_tick(args)
    except Exception:
        traceback.print_exc()
        problems.append("tick scenario raised")
    else:
        for field in (
            "value",
            "executes_per_tick",
            "fetches_per_tick",
            "step_executes_per_tick",
            "rtt_decomposition_per_tick",
        ):
            if tick_line.get(field) is None:
                problems.append(f"tick line missing {field!r}")
        if tick_line.get("value") != 1.0:
            problems.append("tick program not at 1 dispatch/tick")
        # Compile-event instrument (ADR 0116): warmup must MISS (>= 1
        # recorded compile) and the measured steady state must not —
        # the scenario asserts it too; this guards the report fields.
        if not tick_line.get("compile_events_warmup", 0) >= 1:
            problems.append("compile-event instrument saw no warmup miss")
        if tick_line.get("compile_events_steady") != 0:
            problems.append(
                "compile events in steady state (jit key churn?)"
            )
        if "telemetry" not in tick_line:
            problems.append("tick line missing telemetry snapshot")
    # Workload-plane control (ADR 0122): tiny run through the real
    # JobManager; the scenario itself asserts 1-dispatch filtered
    # ticks, pass-all byte identity, layout_swap classification and
    # the warmed 0-compile swap, and this guards the report structure.
    try:
        wl_line = bench_workloads(args)
    except Exception:
        traceback.print_exc()
        problems.append("workloads scenario raised")
    else:
        for field in (
            "value",
            "executes_per_tick",
            "fetches_per_tick",
            "filtered_fraction",
            "cold_swap_classified_layout_swap",
            "warmed_swap_compiles",
        ):
            if wl_line.get(field) is None:
                problems.append(f"workloads line missing {field!r}")
        if wl_line.get("value") != 1.0:
            problems.append(
                "filtered workload tick not at 1 dispatch/group"
            )
        if wl_line.get("warmed_swap_compiles") != 0:
            problems.append(
                "warmed calibration swap still compiled on the hot path"
            )
    # Result fan-out control (ADR 0117): tiny run through the real
    # JobManager + ServingPlane at N=1 and N=50 simulated subscribers;
    # the scenario itself asserts publish-side device ops identical
    # across N, byte-identical subscriber reconstruction and bounded
    # slow-consumer queues, and this guards the report's structure.
    try:
        fanout_line = bench_fanout(args, n_values=(1, 50))
    except Exception:
        traceback.print_exc()
        problems.append("fanout scenario raised")
    else:
        for field in (
            "value",
            "executes_per_tick",
            "fetches_per_tick",
            "delta_vs_replay_ratio",
            "served_bytes_per_checker_tick",
            "coalesce_drops",
        ):
            if fanout_line.get(field) is None:
                problems.append(f"fanout line missing {field!r}")
        if not fanout_line.get("delta_vs_replay_ratio", 1.0) < 0.8:
            problems.append(
                "fanout delta encoding not under full-frame replay"
            )
    # Relay-tree control (ADR 0121): tiny run through the real
    # JobManager + ServingPlane + fleet relays at R=1 and R=2; the
    # scenario itself asserts compute-tier device ops and hub encodes
    # flat in R, byte-identical downstream frames and monotone served-
    # subscriber capacity, and this guards the report's structure.
    try:
        relay_line = bench_relay(args, r_values=(1, 2))
    except Exception:
        traceback.print_exc()
        problems.append("relay scenario raised")
    else:
        for field in (
            "value",
            "executes_per_tick",
            "hub_encodes_per_tick",
            "served_subscribers",
            "frames_delivered_per_s",
        ):
            if relay_line.get(field) is None:
                problems.append(f"relay line missing {field!r}")
        if relay_line.get("value") != 2.0:
            problems.append(
                "relay: compute publish ops/tick not at 1 execute + "
                "1 fetch"
            )
    # Durability-plane churn control (ADR 0118): tiny kill-and-restart
    # through the real JobManager + CheckpointPlane; the scenario
    # itself asserts replay byte identity, the subscriber gap-not-
    # reset, and the 0-compile warmed commit vs >= 1 cold, and this
    # guards the report's structure.
    try:
        churn_line = bench_churn(args)
    except Exception:
        traceback.print_exc()
        problems.append("churn scenario raised")
    else:
        for field in (
            "value",
            "replayed_windows",
            "wire_byte_identical_after_replay",
            "subscriber_gap_not_reset",
            "commit_compiles_without_warmup",
        ):
            if churn_line.get(field) is None:
                problems.append(f"churn line missing {field!r}")
        if churn_line.get("value") != 0:
            problems.append(
                "warmed commit paid hot-path compiles (warm-up broken?)"
            )
        if not churn_line.get("wire_byte_identical_after_replay"):
            problems.append("replay wire not byte-identical to control")
    # SLO-plane control (ADR 0120): the load+chaos drill at smoke
    # scale; the scenario itself asserts containment (signaled resets,
    # wire parity, 0 hot-path compiles, bounded queues, coalesce
    # recovery), the rule gate green and the containment-disabled
    # control red, and this guards the report's structure.
    try:
        slo_line = bench_slo(args, scale=0.25)
    except Exception:
        traceback.print_exc()
        problems.append("slo scenario raised")
    else:
        for field in (
            "value",
            "e2e_p99_by_stage",
            "gate_passed",
            "control_gate_breached",
            "chaos_injected",
        ):
            if slo_line.get(field) is None:
                problems.append(f"slo line missing {field!r}")
        if not slo_line.get("gate_passed"):
            problems.append("slo gate breached on the contained run")
        if not slo_line.get("control_gate_breached"):
            problems.append(
                "slo gate stayed green with containment disabled"
            )
        stages = slo_line.get("e2e_p99_by_stage", {})
        if "subscriber_delivered" not in stages:
            problems.append("slo line missing subscriber_delivered p99")
    # Telemetry-overhead guard (ADR 0116): instrument microcosts
    # bounded against the tick wall this very smoke just measured.
    try:
        telem_line = bench_telemetry(
            args,
            tick_wall_ms=(
                tick_line.get("wall_ms_per_tick") if tick_line else None
            ),
        )
    except Exception:
        traceback.print_exc()
        problems.append("telemetry-overhead scenario raised")
    else:
        if not telem_line.get("value", 1.0) < 0.01:
            problems.append("telemetry overhead >= 1% of tick wall")
    # Mesh serving-tier control (ADR 0115): tiny run through the real
    # JobManager on the 8-virtual-device mesh; the scenario itself
    # asserts 1 execute + 1 fetch per mesh slice per tick, the
    # single-device da00 byte identity and the 1->2 scaling rise, and
    # this guards the report's structure.
    try:
        mesh_line = bench_mesh(args)
    except Exception:
        traceback.print_exc()
        problems.append("mesh scenario raised")
    else:
        if mesh_line.get("skipped"):
            problems.append(
                f"mesh scenario skipped: {mesh_line.get('reason')}"
            )
        else:
            for field in (
                "value",
                "slices",
                "wire_byte_identical_vs_single_device",
                "scaling_curve",
            ):
                if mesh_line.get(field) is None:
                    problems.append(f"mesh line missing {field!r}")
            if mesh_line.get("value") != 1.0:
                problems.append(
                    "mesh tick not at 1 execute/slice/tick"
                )
    # Pipelined-ingest control (ADR 0111): tiny run through the real
    # JobManager + IngestPipeline; the scenario itself asserts parity,
    # ordering and drain, and this guards the report's structure — a
    # hot-path regression in the pipeline fails CI loudly.
    try:
        pipe_line = bench_pipeline(args)
    except Exception:
        traceback.print_exc()
        problems.append("pipeline scenario raised")
    else:
        for field in (
            "value",
            "pipelined_vs_serial_speedup",
            "stage_utilization",
            "e2e_vs_max_stage",
        ):
            if pipe_line.get(field) is None:
                problems.append(f"pipeline line missing {field!r}")
        if not pipe_line.get("value", 0) > 0:
            problems.append("pipeline throughput non-positive")
    # Batch-decode-plane control (ADR 0125): real ev44 wire through the
    # real adapter + accumulator + JobManager path in both decode
    # modes; the scenario itself asserts the >= 3x decode-stage floor,
    # the cross-mode da00 byte identity and decode off the pipeline's
    # critical path, and this guards the report's structure.
    try:
        dec_line = bench_decode(args)
    except Exception:
        traceback.print_exc()
        problems.append("decode scenario raised")
    else:
        for field in (
            "value",
            "per_message_events_per_sec",
            "batch_vs_per_message_speedup",
            "wire_byte_identical",
            "pipeline_stage_utilization",
            "decode_not_max_stage",
        ):
            if dec_line.get(field) is None:
                problems.append(f"decode line missing {field!r}")
        if not dec_line.get("batch_vs_per_message_speedup", 0.0) >= 3.0:
            problems.append(
                "batch decoder under the 3x decode-stage floor"
            )
        if not dec_line.get("wire_byte_identical"):
            problems.append("decode modes not da00 byte-identical")
        if not dec_line.get("decode_not_max_stage"):
            problems.append(
                "decode still the max-utilization pipeline stage"
            )
    if problems:
        print("SMOKE FAIL: " + "; ".join(problems), file=sys.stderr)
        return 1
    print(
        "SMOKE OK: metric line parses, stage breakdown present, "
        "publish combining at 1 fetch/tick, tick program at 1 "
        "dispatch/tick with wire parity, compile instrument saw the "
        "warmup miss and a clean steady state, telemetry overhead "
        "under 1% of tick wall, fan-out tier flat in subscribers with "
        "byte-identical reconstruction, churn kill-and-restart "
        "replayed byte-identical with a 0-compile warmed commit, mesh "
        "tier at 1 execute/slice/tick with single-device parity, "
        "pipelined ingest drained with parity, batch decode plane over "
        "the 3x floor with cross-mode da00 parity and decode off the "
        "critical path, SLO chaos drill contained with the rule gate "
        "green and the control red",
        file=sys.stderr,
    )
    return 0


def main() -> None:
    args = _parse_args()
    from esslivedata_tpu.utils.runtime import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()  # every mode, before any compile
    if args.smoke:
        sys.exit(_smoke_main(args))
    if args.multijob:
        if args.events is None:
            args.events = 1 << 18
        if args.batches is None:
            args.batches = 16
        bench_multijob(args)
        sys.exit(0)
    if args.pipeline:
        if args.events is None:
            args.events = 1 << 18
        if args.batches is None:
            args.batches = 16
        bench_pipeline(args)
        sys.exit(0)
    if args.decode:
        if args.events is None:
            args.events = 1 << 17
        if args.batches is None:
            args.batches = 8
        bench_decode(args)
        sys.exit(0)
    if args.publish:
        if args.events is None:
            args.events = 1 << 17
        if args.batches is None:
            args.batches = 32
        bench_publish(args)
        sys.exit(0)
    if args.tick:
        if args.events is None:
            args.events = 1 << 17
        if args.batches is None:
            args.batches = 32
        bench_tick(args)
        sys.exit(0)
    if args.workloads:
        if args.events is None:
            args.events = 1 << 15
        if args.batches is None:
            args.batches = 32
        bench_workloads(args)
        sys.exit(0)
    if args.fanout:
        if args.events is None:
            args.events = 1 << 12
        if args.batches is None:
            args.batches = 48
        bench_fanout(args)
        sys.exit(0)
    if args.relay:
        if args.events is None:
            args.events = 1 << 12
        if args.batches is None:
            args.batches = 48
        bench_relay(args)
        sys.exit(0)
    if args.churn:
        if args.events is None:
            args.events = 1 << 13
        if args.batches is None:
            args.batches = 48
        bench_churn(args)
        sys.exit(0)
    if args.telemetry:
        bench_telemetry(args)
        sys.exit(0)
    if args.slo:
        bench_slo(args, scale=0.5)
        sys.exit(0)
    if args.mesh:
        # The virtual-device topology must be pinned BEFORE backend
        # init; the scenario itself asserts the per-slice contract.
        from esslivedata_tpu.utils.platform_pin import pin_cpu

        pin_cpu(8)
        if args.events is None:
            args.events = 1 << 17
        if args.batches is None:
            args.batches = 32
        # The acceptance run asserts the 1->2 scaling rise; a driver on
        # a core-starved CI host may relax it (the per-slice contract
        # stays hard): scripts/bench_multichip.py --smoke sets this.
        bench_mesh(
            args,
            strict_scaling=(
                os.environ.get("BENCH_MESH_LENIENT_SCALING") != "1"
            ),
        )
        sys.exit(0)

    sys.exit(_headline_main(args))


if __name__ == "__main__":
    main()
