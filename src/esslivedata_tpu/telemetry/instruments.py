"""Shared serving-path instruments, registered at import.

Instruments defined here exist on the FIRST scrape of any process that
imports telemetry at all — not only once their producer module happens
to load: a service that hosts no workload family must still EXPOSE
``livedata_calibration_swaps`` (an absent name reads as 'not
instrumented', the wrong answer) with zero samples.
Span and compile-event instruments live with their single producers
(telemetry/trace.py, telemetry/compile.py), which this package's
``__init__`` imports for the same always-registered guarantee.
"""

from __future__ import annotations

from .registry import REGISTRY

__all__ = [
    "BATCHER_SCALE_CHANGES",
    "BATCH_HOLD_SECONDS",
    "CALIBRATION_SWAPS",
    "CAMERA_FRAMES",
    "CAMERA_FRAME_BYTES",
    "DECODE_BATCH_SIZE",
    "DECODE_BYTES",
    "DECODE_ERRORS",
    "EVENTS_FILTERED",
    "JOB_PUBLISHES",
    "JOB_WINDOWS",
    "Q_BINCOUNT_STEPS",
    "Q_LOOKUP_STEPS",
    "SCATTER_UPDATES",
    "SINK_BYTES",
    "SINK_SECONDS",
    "SINK_SERIALIZE_SECONDS",
    "STAGED_EVENTS",
    "STAGING_COPIES",
    "STAGING_POOL_BYTES",
    "TABLE_BUILD_SECONDS",
    "TABLE_BYTES",
    "TICK_GROUPS",
    "VIEW_STEPS",
    "VIEW_WIRES",
]

#: Calibration-plane swaps (workloads/calibration.py, ADR 0122): every
#: live table replacement that re-keyed staged wires/tick programs,
#: labeled by table kind (tof_dspacing/flatfield/...). Registered here
#: so a service that hosts no workload family still EXPOSES the family
#: with zero samples (scripts/metrics_smoke.py gates its presence).
CALIBRATION_SWAPS = REGISTRY.counter(
    "livedata_calibration_swaps",
    "Live calibration-table swaps adopted by workload kernels "
    "(each re-keys staging + tick programs under the new digest)",
    labelnames=("kind",),
)

#: Per-event filter drops (workloads/filters.py, ADR 0122): events a
#: composable predicate chain rejected before histogramming, labeled by
#: filter kind. Counted at the host filter pass — the device sees zero
#: extra dispatches, so this is the only place the drop rate exists.
EVENTS_FILTERED = REGISTRY.counter(
    "livedata_events_filtered",
    "Events rejected by per-event filter chains before histogramming",
    labelnames=("kind",),
)

#: Messages per consume poll reaching the adapter layer (ADR 0125): the
#: batch decode plane amortizes per-poll overhead across this count, so
#: its distribution IS the amortization factor — a mode stuck at 1-2
#: messages/poll means batching buys nothing and the broker fetch
#: configuration is the lever, not the decoder.
DECODE_BATCH_SIZE = REGISTRY.histogram(
    "livedata_decode_batch_size",
    "Raw messages per consume poll handed to the adapter layer",
    buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
             512.0, 1024.0),
)

#: Wire bytes entering decode. With the PERF.md ~4 B/event wire cost
#: this is the decode plane's throughput denominator (bytes/s scraped
#: against `livedata_e2e_latency{stage="decode"}`).
DECODE_BYTES = REGISTRY.counter(
    "livedata_decode_bytes_total",
    "Raw wire bytes handed to the decode plane",
)

#: Quarantined messages (ADR 0125): malformed wire contained per
#: message — a bad buffer raises WireError and is skipped (batch mode:
#: without poisoning the rest of its poll). Labeled by schema so a
#: producer-side corruption shows WHICH codec is affected.
DECODE_ERRORS = REGISTRY.counter(
    "livedata_decode_errors_total",
    "Messages dropped by the decode plane as malformed wire",
    labelnames=("schema",),
)

#: How long an emitted batch's newest message sat in the batcher: from
#: the return of the poll that delivered it to the start of the batch's
#: processing (``core/message_batcher.BatchHold``). A window closes only
#: when a message of a LATER window arrives, so at 14 Hz this is one
#: pulse period (71 ms) plus the phase of the consumer's polls: the
#: part of a picture's age that no span of the tick covers.
BATCH_HOLD_SECONDS = REGISTRY.histogram(
    "livedata_batch_hold_seconds",
    "Poll that delivered a batch's last message -> start of its "
    "processing (one observation per emitted batch)",
)

#: The adaptive batchers' window scale moving (``LoadGovernor``): an
#: escalation re-shapes every staged batch, so each one is followed by
#: a recompile of every tick program (``up`` is the benchmark's
#: ``escalations_in_window``). The scale itself is the gauge
#: ``livedata_batcher_window_scale`` in the processor's collector,
#: which ``scripts/slo_rules/default.json`` holds to 1.
BATCHER_SCALE_CHANGES = REGISTRY.counter(
    "livedata_batcher_scale_changes_total",
    "Window-scale changes of the load governor (up = escalation)",
    labelnames=("direction",),
)

#: Event slots shipped to the device per stage-cache miss, beside the
#: ``flatten`` / ``h2d`` spans: ``staged`` = slots of the bucket,
#: ``pad`` = those of them that hold no decoded event (the bucket's
#: padding, which the scatter pays for all the same).
STAGED_EVENTS = REGISTRY.counter(
    "livedata_staged_events_total",
    "Event slots staged for the device; kind=pad is the share of them "
    "that is bucket padding",
    labelnames=("kind",),
)

#: Which wire each stage-cache miss of an event histogrammer
#: (``ops/histogram.EventHistogrammer``: the detector views, the monitor
#: histograms) shipped, one count per miss beside the ``h2d`` span:
#: ``flat`` = indices flattened on the host (4 B an event; the
#: partitioned wire of ``method="pallas2d"`` too), ``raw`` = the
#: (pixel id, TOA) pair (8 B an event) of a view that projects on the
#: device: a replica LUT or per-pixel weights. Both have a sample from
#: the first histogrammer on; a service that builds none shows none.
#: raw / both is the benchmark's ``view_raw_staging_share``.
VIEW_WIRES = REGISTRY.counter(
    "livedata_view_wires_total",
    "Wires an event histogrammer staged for the device, by kind "
    "(flat = host-flattened indices, raw = pixel id and TOA)",
    labelnames=("staging",),
)

#: What the histogrammers' scatters were handed: the staged bucket's
#: slots times the LUT's replicas, one count per dispatch of a step
#: (a tick program, a fused or a private step; the members of one group
#: share the dispatch and count once). Over
#: ``livedata_staged_events_total{kind="staged"}`` it is the benchmark's
#: ``scatter_updates_per_event``: 1 where every wire is scattered once,
#: R under R position-noise replicas.
SCATTER_UPDATES = REGISTRY.counter(
    "livedata_scatter_updates_total",
    "Updates handed to the histogram scatter: staged slots times LUT "
    "replicas, per step dispatch",
)

#: Steps of the histogrammers (``ops/histogram.EventHistogrammer``: the
#: detector views, the monitor histograms), one count per dispatch (the
#: members of one group share it and count once, where
#: ``livedata_scatter_updates_total`` counts), by the kernel the step's
#: program was traced with: ``mxu`` = the factorised one-hot on the MXU
#: over a partition by bin block (``method="mxu"``, which
#: ``method="auto"`` takes on a TPU wherever the update is a scalar: the
#: chip sorts the indices; and ``"pallas2d"``, whose partition the host
#: makes), ``scatter`` = XLA's scatter-add (per-pixel weights, other
#: backends, ``"scatter"`` / ``"sort"``, and pallas2d's (pixel_id, toa)
#: device path). Both labels have a sample from the first histogrammer
#: on. mxu / all is the benchmark's ``view_mxu_share``.
VIEW_STEPS = REGISTRY.counter(
    "livedata_view_steps_total",
    "Event-histogrammer step dispatches, by the kernel that counts the "
    "bins (mxu or scatter)",
    labelnames=("kernel",),
)

#: How each host array shipped to the device got its staging copy
#: (``ops/event_batch.py`` over ``ops/staging_pool.py``, ADR 0130), one
#: count per array: ``kept`` = copied into a kept host buffer whose last
#: transfer was done, ``fresh`` = a buffer had to be allocated (first
#: use, a second generation in flight, a new bucket; every copy on the
#: CPU backend, which aliases host memory and so keeps none; and the
#: detector views' flat wires, ``kept=False``). Both have a sample from
#: import on. ``kept`` over both is the benchmark's
#: ``staging_kept_share``.
STAGING_COPIES = REGISTRY.counter(
    "livedata_staging_copies_total",
    "Host arrays shipped to the device, by how the staging copy was "
    "made (kept buffer reused, or fresh allocation)",
    labelnames=("kind",),
)
for _kind in ("kept", "fresh"):
    STAGING_COPIES.inc(0.0, kind=_kind)

#: Host bytes the process's staging pool holds (``ops/staging_pool.py``):
#: one generation of a window's staged arrays in the serial loop, two
#: where windows overlap; falls when a bucket is left behind or the
#: stream stops.
STAGING_POOL_BYTES = REGISTRY.gauge(
    "livedata_staging_pool_bytes",
    "Host bytes held by the staging pool's kept buffers",
)
STAGING_POOL_BYTES.set(0.0)

#: Where the ``sink`` span's time goes (``kafka/sink.py``): summed over
#: a publish's messages, two clock reads a message, not a span each.
SINK_SECONDS = REGISTRY.counter(
    "livedata_sink_seconds_total",
    "Cumulative seconds of publish_messages by phase "
    "(serialize = da00/f144/... encode, produce, flush)",
    labelnames=("phase",),
)

#: The ``serialize`` phase of a da00 message apart (``DefaultSerializer``,
#: one clock read between the two), by ``step``: ``da00`` builds the message's
#: variables from the job's result (``dataarray_to_da00``; a value still
#: on the device would be pulled here), ``wire`` is the native encoder
#: (``wire.encode_da00``). ``serialize`` above still holds both plus the
#: publish loop's own overhead and every other message kind.
SINK_SERIALIZE_SECONDS = REGISTRY.counter(
    "livedata_sink_serialize_seconds_total",
    "Cumulative seconds of da00 serialization by step "
    "(da00 = build the variables, wire = encode them)",
    labelnames=("step",),
)

#: What that time is paid for: the benchmark's ``sink_mb`` per tick, an
#: operator's output bandwidth (broker and retention sizing) as a rate.
SINK_BYTES = REGISTRY.counter(
    "livedata_sink_bytes_total",
    "Serialized payload bytes handed to the producer",
)

#: How a tick's (stream, fuse-key) groups met the chip
#: (``JobManager._run_tick_programs``), one count per group at its
#: dispatch: ``ahead`` = an earlier group of the same tick was still
#: uncollected, so this group's staging ran beside that one's program;
#: ``alone`` = the first group of a tick, a one-group tick, a compile
#: round. ahead / (ahead + alone) is the benchmark's
#: ``groups_ahead_share``: (n - 1) / n of a warm n-group service, 0 of
#: a one-group one.
TICK_GROUPS = REGISTRY.counter(
    "livedata_tick_groups_total",
    "Tick-program groups dispatched, by whether an earlier group of "
    "the same tick was still in flight (ahead) or not (alone)",
    labelnames=("dispatched",),
)

#: When a window's job results left the manager
#: (``JobManager.process_jobs``), one count per job result handed to the
#: window's publisher: ``ahead`` = handed over right after its tick
#: group's collect, while a later group of the same tick was still
#: uncollected, so its finalize, encode and write ran beside the chip's
#: work; ``end`` = returned at the end of the window (the last group of
#: a tick, a one-group tick, a compile round, a member that fell back,
#: every job outside a tick group). ahead / (ahead + end) is the
#: benchmark's ``publishes_ahead_share``: (n - 1) / n of a warm service
#: of n one-job groups, 0 of one with no tick group.
JOB_PUBLISHES = REGISTRY.counter(
    "livedata_job_publishes_total",
    "Job results handed to the window's publisher, by whether a tick "
    "group of the same tick was still uncollected (ahead) or not (end)",
    labelnames=("when",),
)

#: Which of the manager's three ways to step a job each window took
#: (``JobManager.process_jobs``), one count per job per window that
#: carried data for it: ``tick`` = stepped and published by its group's
#: tick program, ``fused`` = stepped with its group in one dispatch and
#: published apart, ``private`` = the workflow's own ``accumulate`` (a
#: window that carries more than the job's one primary stream, such as
#: the monitor events of every LOKI window). private / all is the
#: benchmark's ``private_windows_share``.
JOB_WINDOWS = REGISTRY.counter(
    "livedata_job_windows_total",
    "Job-windows stepped, by the path that stepped them "
    "(tick, fused or private)",
    labelnames=("path",),
)

#: The (pixel, TOA bin) -> bin tables resident beside the Q family's
#: states (``ops/qhistogram.QHistogrammer``), by family: bytes of the
#: array the kernel keeps (the host's int table, or the packed layout
#: of ``ops/pallas_lookup.py``: one bfloat16 byte plane up to 255 bins,
#: two up to 65 535, with its padding; the device's tiling may pad
#: further), summed over live tables. Set-up has no span, so
#: what building them cost is the counter below: both change at
#: construction and at ``swap_table`` only, and neither can be read by a
#: windowed metric. Family ``projection`` is the detector views'
#: pixel -> screen-bin LUT (``workflows/detector_view/projectors.py``):
#: int32 ``[replicas, id space]`` as built at job start, read on the
#: device by a view that projects there (replicas, weights) and on the
#: host by one that flattens there.
TABLE_BYTES = REGISTRY.gauge(
    "livedata_table_bytes",
    "Bytes of precompiled event->bin tables resident on the device "
    "(the int table, or its packed byte planes with their padding), "
    "by kernel family",
    labelnames=("family",),
)

TABLE_BUILD_SECONDS = REGISTRY.counter(
    "livedata_table_build_seconds_total",
    "Seconds spent building event->bin tables on the host and placing "
    "them on the device (job start, swap_table), by kernel family",
    labelnames=("family",),
)

#: How each Q step looked its events up in the table
#: (``ops/qhistogram.QHistogrammer``), one count per step at its
#: dispatch: ``windowed`` = sort + dense windows on the MXU
#: (``ops/pallas_lookup.py``: a table packed in one or two byte planes
#: and a batch at or above its crossover), ``gather`` = XLA's element
#: gather (everything else: the CPU, a bin space past 65 535, a batch
#: under the crossover). windowed / both is the benchmark's
#: ``q_lookup_windowed_share``, gather / both its
#: ``q_lookup_gather_share``.
Q_LOOKUP_STEPS = REGISTRY.counter(
    "livedata_q_lookup_steps_total",
    "Q-histogram steps dispatched, by how the (pixel, TOA bin) table "
    "was read (windowed: sorted, dense windows of one or two byte "
    "planes; or gather)",
    labelnames=("lookup",),
)

#: How each Q step counted its looked-up bins, the kernel's other
#: choice (``QHistogrammer(method="auto")``), counted beside the one
#: above, three labels: ``onehot`` = the flat VMEM one-hot kernel of
#: ``ops/pallas_hist.py`` (a TPU backend and a bin space under
#: ``MXU_LANE_GROUPS`` groups of 128 bins: LOKI's 100), ``mxu`` = that
#: module's factorised one-hot on the MXU (a TPU backend, from there to
#: ``MAX_MXU_BINS`` = 65 536 bins: the powder reduction's 2000 x 17,
#: BIFROST's 80 x 60 and 100 x 100), ``scatter`` = XLA's scatter-add
#: (every other backend, and bin spaces past one VMEM tile). All three
#: have a sample from the first Q kernel on. scatter / all is the
#: benchmark's ``q_bincount_scatter_share``, mxu / all its
#: ``q_bincount_mxu_share``.
Q_BINCOUNT_STEPS = REGISTRY.counter(
    "livedata_q_bincount_steps_total",
    "Q-histogram steps dispatched, by how the looked-up bins were "
    "counted (onehot, mxu or scatter)",
    labelnames=("method",),
)

#: Camera frames summed by the area-detector views
#: (``workflows/area_detector_view.py`` over ``ops/frames.py``, ADR
#: 0132), one count a frame, by where its sum is taken: ``device`` = the
#: uint32 window state on the device (an unsigned frame of at most 16
#: bits: every ad00 camera of the package), ``host`` = a frame of
#: another type, summed in a wider type on the host. Both labels have a
#: sample from import on. device / both is the benchmark's
#: ``frames_device_share``.
CAMERA_FRAMES = REGISTRY.counter(
    "livedata_camera_frames_total",
    "Camera frames summed by the area-detector views, by where the sum "
    "is taken (device or host)",
    labelnames=("path",),
)
for _path in ("device", "host"):
    CAMERA_FRAMES.inc(0.0, path=_path)

#: The bytes of those frames as they came off the wire (the frame's own
#: type), summed over both paths.
CAMERA_FRAME_BYTES = REGISTRY.counter(
    "livedata_camera_frame_bytes_total",
    "Bytes of the camera frames summed by the area-detector views, in "
    "the frames' own type",
)
