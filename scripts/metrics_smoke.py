#!/usr/bin/env python
"""Metrics-plane smoke: fake-kafka service + --metrics-port -> scrape.

CI's counterpart to the /metrics acceptance (ADR 0116): bring up a REAL
detector service over the file-backed broker (the fake Kafka, ADR 0104)
with ``--metrics-port``, feed it a start command and a few ev44 pulses,
then

1. ``GET /healthz`` answers 200 ``{"status": "ok"}``;
2. ``GET /metrics`` answers Prometheus text exposition that the IN-TREE
   promtext parser (telemetry/exposition.py — no prometheus_client
   dependency) accepts: labels unescape, histogram bucket series are
   monotone and closed at +Inf;
3. the payload exposes the migrated producer families — publish
   dispatch counters, pipeline/stage surfaces, stream counts, compile
   histograms, span decomposition, HBM gauges — and, once data flowed,
   nonzero publish executes;
4. (ADR 0117) with ``--serve-port`` the result fan-out tier answers:
   ``GET /results`` lists the job's streams, the first SSE event on
   ``/streams/<job>/<output>`` is a valid keyframe whose payload
   decodes as da00, and the ``livedata_serving_*`` families appear in
   ``/metrics`` after the subscriber attached;
5. (ADR 0118) with ``--checkpoint-dir`` + ``--warmup`` the durability
   plane's families scrape — snapshot age/bytes/epoch, checkpoint and
   restore counters, replay lag, warm-up compiles — and once data
   flowed, a checkpoint generation was actually written (snapshot age
   sample >= 0, a ``manifest-*.json`` on disk).

Exit 0 on success, 1 with a diagnostic otherwise.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

TIMEOUT_S = float(os.environ.get("METRICS_SMOKE_TIMEOUT_S", "90"))
PORT = int(os.environ.get("METRICS_SMOKE_PORT", "18917"))
SERVE_PORT = int(os.environ.get("METRICS_SMOKE_SERVE_PORT", PORT + 1))
RELAY_PORT = int(os.environ.get("METRICS_SMOKE_RELAY_PORT", PORT + 2))
RELAY_METRICS_PORT = int(
    os.environ.get("METRICS_SMOKE_RELAY_METRICS_PORT", PORT + 3)
)

#: Families one scrape of a running service must expose (the /metrics
#: acceptance list; livedata_hbm_bytes may be sample-less on CPU but
#: its HELP/TYPE header must still be there).
REQUIRED_FAMILIES = (
    "livedata_publish_events",
    "livedata_publish_slice_events",
    "livedata_jit_compiles_total",
    "livedata_jit_compile_seconds",
    "livedata_tick_span_seconds",
    "livedata_stream_messages",
    "livedata_kafka_sink_events",
    "livedata_hbm_bytes",
    "livedata_device_info",
    # SLO plane (ADR 0120): the e2e freshness histogram and the
    # state-loss counter are always-registered instruments.
    "livedata_e2e_latency_seconds",
    "livedata_state_lost",
    # Workload plane (ADR 0122): calibration-swap and filter-drop
    # counters are always-registered — a service hosting no workload
    # family still exposes them with zero samples.
    "livedata_calibration_swaps",
    "livedata_events_filtered",
    # Batch decode plane (ADR 0125): poll-size histogram, wire-byte
    # counter and the quarantine counter are always-registered.
    "livedata_decode_batch_size",
    "livedata_decode_bytes_total",
    "livedata_decode_errors_total",
)


def fetch(path: str, timeout: float = 5.0) -> tuple[int, bytes]:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{PORT}{path}", timeout=timeout
    ) as response:
        return response.status, response.read()


def main() -> int:
    import uuid

    import numpy as np

    from esslivedata_tpu.config import JobId, WorkflowConfig
    from esslivedata_tpu.config.instruments.dummy.specs import (
        DETECTOR_VIEW_HANDLE,
        INSTRUMENT,
    )
    from esslivedata_tpu.kafka import wire
    from esslivedata_tpu.kafka.file_broker import (
        FileBrokerProducer,
        ensure_topics,
    )
    from esslivedata_tpu.telemetry import parse_prometheus_text

    deadline = time.time() + TIMEOUT_S
    broker_dir = tempfile.mkdtemp(prefix="metrics-smoke-broker-")
    checkpoint_dir = tempfile.mkdtemp(prefix="metrics-smoke-ck-")
    ensure_topics(
        broker_dir, ["dummy_detector", "dummy_livedata_commands"]
    )
    env = {
        **os.environ,
        "LIVEDATA_FORCE_CPU": "1",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(Path(__file__).resolve().parent.parent / "src"),
        # The smoke exercises the batch decode plane (ADR 0125): the
        # gated rollout path must keep the whole metrics/serving/
        # checkpoint surface green, not just the per-message default.
        "LIVEDATA_BATCH_DECODE": "1",
    }
    service = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "esslivedata_tpu.services.detector_data",
            "--instrument",
            "dummy",
            "--batcher",
            "naive",
            "--broker-dir",
            broker_dir,
            "--metrics-port",
            str(PORT),
            "--serve-port",
            str(SERVE_PORT),
            "--checkpoint-dir",
            checkpoint_dir,
            # Tight cadence so the smoke window reliably contains a
            # written generation (prod default is 30 s).
            "--checkpoint-interval",
            "2",
            "--warmup",
        ],
        env=env,
    )
    try:
        producer = FileBrokerProducer(broker_dir)
        config = WorkflowConfig(
            identifier=DETECTOR_VIEW_HANDLE.workflow_id,
            job_id=JobId(
                source_name="panel_0", job_number=uuid.uuid4()
            ),
            params={},
        )
        command = json.dumps(
            {"kind": "start_job", "config": config.model_dump(mode="json")}
        ).encode()
        det = INSTRUMENT.detectors["panel_0"]
        ids_space = np.asarray(det.detector_number).reshape(-1)
        rng = np.random.default_rng(7)

        # 1. liveness first: the endpoint must come up with the service.
        health = None
        while time.time() < deadline:
            if service.poll() is not None:
                print(f"service died rc={service.returncode}")
                return 1
            try:
                status, body = fetch("/healthz")
                health = json.loads(body)
                break
            except Exception:
                time.sleep(1.0)
        # 'ok' normally; 'degraded' (with a reason, still 200) is a
        # valid payload too — a starved CI runner can latch the
        # slow-tick watchdog on the very first windows (ADR 0120).
        if health.get("status") not in ("ok", "degraded") or (
            health["status"] == "degraded" and not health.get("reason")
        ):
            print(f"/healthz wrong or never up: {health!r}")
            return 1
        print(f"healthz OK ({health['status']})")

        # 2. drive data so the publish/compile/span producers fire.
        publishes = 0.0
        parsed = None
        pulse = 0
        period_ns = int(1e9 / 14)
        while time.time() < deadline and publishes < 1:
            if service.poll() is not None:
                print(f"service died rc={service.returncode}")
                return 1
            producer.produce("dummy_livedata_commands", command)
            for _ in range(5):
                t_pulse = 1_700_000_000_000_000_000 + pulse * period_ns
                payload = wire.encode_ev44(
                    det.source_name,
                    pulse,
                    np.array([t_pulse]),
                    np.array([0]),
                    rng.uniform(0, 7.0e7, 256).astype(np.int32),
                    pixel_id=rng.choice(ids_space, 256).astype(np.int32),
                )
                producer.produce("dummy_detector", payload)
                pulse += 1
            time.sleep(2.0)
            status, body = fetch("/metrics")
            if status != 200:
                print(f"/metrics HTTP {status}")
                return 1
            # 3. the payload must PARSE (in-tree promtext parser:
            # escapes, bucket monotonicity) on every scrape, data or no.
            parsed = parse_prometheus_text(body.decode())
            publishes = sum(
                value
                for _n, labels, value in parsed[
                    "livedata_publish_events"
                ].samples
                if labels.get("kind") == "executes"
            ) if "livedata_publish_events" in parsed else 0.0
        if parsed is None or publishes < 1:
            print(
                f"no publish executes after {TIMEOUT_S}s "
                f"(families: {sorted(parsed) if parsed else None})"
            )
            return 1
        missing = [f for f in REQUIRED_FAMILIES if f not in parsed]
        if missing:
            print(f"scrape missing families: {missing}")
            return 1
        compiles = sum(
            value
            for _n, _l, value in parsed["livedata_jit_compiles_total"].samples
        )
        if compiles < 1:
            print("compile-event instrument saw no compiles")
            return 1
        # E2E freshness (ADR 0120): the decode and published boundaries
        # must have observed the driven windows.
        e2e_counts = {
            labels.get("stage"): value
            for name, labels, value in parsed[
                "livedata_e2e_latency_seconds"
            ].samples
            if name.endswith("_count")
        }
        for stage in ("decode", "published"):
            if e2e_counts.get(stage, 0.0) < 1:
                print(f"e2e latency stage {stage!r} never observed: {e2e_counts}")
                return 1
        print("e2e latency boundaries OK")

        # 4. result fan-out tier (ADR 0117): index, first SSE event a
        # valid keyframe decoding as da00, serving families scraped.
        import base64

        from esslivedata_tpu.serving.delta import HEADER_SIZE, decode_header
        from esslivedata_tpu.kafka.wire import decode_da00

        def fetch_serve(path: str, timeout: float = 5.0):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{SERVE_PORT}{path}", timeout=timeout
            ) as response:
                return response.status, response.read()

        index = None
        while time.time() < deadline:
            status, body = fetch_serve("/results")
            if status != 200:
                print(f"/results HTTP {status}")
                return 1
            index = json.loads(body)
            if index.get("streams"):
                break
            time.sleep(1.0)
        if not index or not index.get("streams"):
            print(f"/results never listed a stream: {index!r}")
            return 1
        entry = index["streams"][0]
        print(
            f"serving index OK: {len(index['streams'])} streams, "
            f"first={entry['stream']}"
        )
        sse = urllib.request.urlopen(
            f"http://127.0.0.1:{SERVE_PORT}{entry['path']}", timeout=15
        )
        event_kind = blob = None
        for raw in sse:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                event_kind = line[len("event: "):]
            elif line.startswith("data: "):
                blob = base64.b64decode(line[len("data: "):])
                break
        sse.close()
        if blob is None or event_kind != "keyframe":
            print(f"first SSE event not a keyframe: {event_kind!r}")
            return 1
        header = decode_header(blob)
        if not header.keyframe:
            print("SSE keyframe event carries a non-keyframe blob")
            return 1
        frame = blob[HEADER_SIZE:]
        decoded = decode_da00(frame)
        if not decoded.variables:
            print("keyframe decoded as da00 but carries no variables")
            return 1
        print(
            f"SSE keyframe OK: epoch={header.epoch} seq={header.seq} "
            f"{len(frame)}B, {len(decoded.variables)} da00 variables"
        )
        status, body = fetch("/metrics")
        parsed = parse_prometheus_text(body.decode())
        serving_missing = [
            family
            for family in (
                "livedata_serving_subscribers",
                "livedata_serving_frames",
                "livedata_serving_bytes",
            )
            if family not in parsed
        ]
        if serving_missing:
            print(f"scrape missing serving families: {serving_missing}")
            return 1
        # 5. durability plane (ADR 0118): families scrape and a real
        # checkpoint generation landed on disk within the window.
        durability_missing = [
            family
            for family in (
                "livedata_durability_snapshot_age_seconds",
                "livedata_durability_snapshot_bytes",
                "livedata_durability_checkpoint_epoch",
                "livedata_durability_checkpoints_total",
                "livedata_durability_restores_total",
                "livedata_durability_replay_lag",
                "livedata_durability_warmup_compiles_total",
                "livedata_durability_warmup_seconds",
            )
            if family not in parsed
        ]
        if durability_missing:
            print(f"scrape missing durability families: {durability_missing}")
            return 1
        manifest = None
        age = None
        while time.time() < deadline:
            manifests = sorted(
                Path(checkpoint_dir).glob("manifest-*.json")
            )
            status, body = fetch("/metrics")
            parsed = parse_prometheus_text(body.decode())
            samples = parsed[
                "livedata_durability_snapshot_age_seconds"
            ].samples
            age = samples[0][2] if samples else None
            if manifests and age is not None and age >= 0:
                manifest = manifests[-1]
                break
            time.sleep(1.0)
        if manifest is None:
            print(
                "durability plane never wrote a checkpoint "
                f"(age={age!r}, dir={checkpoint_dir})"
            )
            return 1
        entries = json.loads(manifest.read_bytes())
        if not entries.get("jobs"):
            print(f"checkpoint manifest carries no job states: {manifest}")
            return 1
        print(
            f"durability OK: generation {entries['epoch']} with "
            f"{len(entries['jobs'])} job state(s), "
            f"{len(entries.get('offsets', {}))} bookmarked topic(s), "
            f"snapshot age {age:.1f}s"
        )
        # 6. fleet plane (ADR 0121): boot a REAL relay against the
        # service's fan-out endpoint; its federated /results must list
        # the upstream streams, its SSE must serve a valid da00
        # keyframe at hop >= 1, and the livedata_relay_* families must
        # scrape from ITS /metrics.
        relay = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "esslivedata_tpu.fleet.service",
                "--upstream",
                f"http://127.0.0.1:{SERVE_PORT}",
                "--serve-port",
                str(RELAY_PORT),
                "--metrics-port",
                str(RELAY_METRICS_PORT),
                "--poll-interval",
                "0.5",
                "--name",
                "smoke-relay",
            ],
            env=env,
        )
        try:

            def fetch_relay(path: str, port: int = RELAY_PORT, timeout=5.0):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=timeout
                ) as response:
                    return response.status, response.read()

            relay_rows = None
            while time.time() < deadline:
                if relay.poll() is not None:
                    print(f"relay died rc={relay.returncode}")
                    return 1
                try:
                    status, body = fetch_relay("/results")
                except Exception:
                    time.sleep(0.5)
                    continue
                rows = json.loads(body).get("streams", [])
                local = [
                    row
                    for row in rows
                    if row.get("node") == "smoke-relay"
                ]
                if local:
                    relay_rows = local
                    break
                time.sleep(0.5)
            if not relay_rows:
                print("relay /results never listed a relayed stream")
                return 1
            row = relay_rows[0]
            if row.get("hop", 0) < 1:
                print(f"relay row carries hop {row.get('hop')!r} (< 1)")
                return 1
            print(
                f"relay index OK: {len(relay_rows)} relayed stream(s), "
                f"hop={row['hop']}"
            )
            sse = urllib.request.urlopen(
                f"http://127.0.0.1:{RELAY_PORT}{row['path']}", timeout=15
            )
            event_kind = blob = None
            for raw in sse:
                line = raw.decode().rstrip("\n")
                if line.startswith("event: "):
                    event_kind = line[len("event: "):]
                elif line.startswith("data: "):
                    blob = base64.b64decode(line[len("data: "):])
                    break
            sse.close()
            if blob is None or event_kind != "keyframe":
                print(f"relay SSE first event not a keyframe: {event_kind!r}")
                return 1
            header = decode_header(blob)
            decoded = decode_da00(blob[HEADER_SIZE:])
            if not decoded.variables:
                print("relay keyframe decoded as da00 but carries nothing")
                return 1
            print(
                f"relay SSE keyframe OK: epoch={header.epoch} "
                f"seq={header.seq}, {len(decoded.variables)} da00 variables"
            )
            status, body = fetch_relay(
                "/metrics", port=RELAY_METRICS_PORT
            )
            relay_parsed = parse_prometheus_text(body.decode())
            relay_missing = [
                family
                for family in (
                    "livedata_relay_frames",
                    "livedata_relay_streams",
                    "livedata_relay_hop",
                    "livedata_relay_upstream_lag_seconds",
                    "livedata_serving_encodes",
                )
                if family not in relay_parsed
            ]
            if relay_missing:
                print(f"relay scrape missing families: {relay_missing}")
                return 1
            relayed_frames = sum(
                value
                for _n, _l, value in relay_parsed[
                    "livedata_relay_frames"
                ].samples
            )
            if relayed_frames < 1:
                print("relay scraped but relayed no frames")
                return 1
            print(
                f"relay metrics OK: {relayed_frames:.0f} frames relayed"
            )
        finally:
            relay.terminate()
            try:
                relay.wait(timeout=15)
            except subprocess.TimeoutExpired:
                relay.kill()
        print(
            f"metrics smoke PASSED: {len(parsed)} families, "
            f"publish executes={publishes:.0f}, compiles={compiles:.0f}, "
            f"serving plane live, durability plane checkpointing, "
            f"relay plane relaying"
        )
        return 0
    finally:
        service.terminate()
        try:
            service.wait(timeout=15)
        except subprocess.TimeoutExpired:
            service.kill()


if __name__ == "__main__":
    raise SystemExit(main())
