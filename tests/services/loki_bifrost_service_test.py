"""End-to-end tests for LOKI (SANS I(Q) with aux monitor binding) and
BIFROST (merged multi-bank stream) services — broker-less, bytes to bytes."""

import json

import numpy as np
import pytest

from esslivedata_tpu.config import JobId, WorkflowConfig
from esslivedata_tpu.core.message_batcher import NaiveMessageBatcher
from esslivedata_tpu.kafka import wire
from esslivedata_tpu.kafka.sink import FakeProducer, KafkaSink, make_default_serializer
from esslivedata_tpu.kafka.source import FakeKafkaMessage
from esslivedata_tpu.services.data_reduction import make_reduction_service_builder
from esslivedata_tpu.services.detector_data import make_detector_service_builder
from esslivedata_tpu.services.fake_sources import (
    FakeDetectorStream,
    FakeMonitorStream,
    PulsedRawSource,
)


def start_command(workflow_id, source_name, topic, aux=None):
    config = WorkflowConfig(
        identifier=workflow_id,
        job_id=JobId(source_name=source_name),
        aux_source_names=aux or {},
    )
    return FakeKafkaMessage(
        json.dumps(
            {"kind": "start_job", "config": config.model_dump(mode="json")}
        ).encode(),
        topic,
    )


def decoded_outputs(producer, topic):
    out = {}
    for m in producer.messages:
        if m.topic != topic:
            continue
        da00 = wire.decode_da00(m.value)
        out[da00.source_name.split("|")[-1]] = da00
    return out


class TestLokiReduction:
    def test_sans_iq_with_monitor_normalization(self):
        from esslivedata_tpu.config.instruments.loki import INSTRUMENT
        from esslivedata_tpu.config.instruments.loki.specs import SANS_IQ_HANDLE

        det = INSTRUMENT.detectors["larmor_detector"]
        det_stream = FakeDetectorStream(
            topic="loki_detector",
            source_name="loki_rear_detector",
            detector_ids=det.pixel_ids,
            events_per_pulse=1000,
        )
        mon_stream = FakeMonitorStream(
            topic="loki_monitor", source_name="loki_mon_1", events_per_pulse=100
        )
        builder = make_reduction_service_builder(
            instrument="loki", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([det_stream, mon_stream])
        producer = FakeProducer()
        sink = KafkaSink(
            producer, make_default_serializer(builder.stream_mapping.livedata, "r")
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                SANS_IQ_HANDLE.workflow_id,
                "larmor_detector",
                "loki_livedata_commands",
                aux={"monitor": "monitor_1"},
            )
        )
        for _ in range(4):
            service.step()
        outputs = decoded_outputs(producer, "loki_livedata_data")
        assert "iq_cumulative" in outputs
        iq = next(v for v in outputs["iq_cumulative"].variables if v.name == "signal")
        assert iq.data.shape == (100,)
        assert iq.data.sum() > 0
        mon = next(
            v
            for v in outputs["monitor_counts_current"].variables
            if v.name == "signal"
        )
        assert mon.data.shape == ()  # scalar survived the wire

    def test_detector_view_with_noise_replicas(self):
        from esslivedata_tpu.config.instruments.loki import INSTRUMENT
        from esslivedata_tpu.config.instruments.loki.specs import DETECTOR_VIEW_HANDLE

        det = INSTRUMENT.detectors["larmor_detector"]
        det_stream = FakeDetectorStream(
            topic="loki_detector",
            source_name="loki_rear_detector",
            detector_ids=det.pixel_ids,
            events_per_pulse=500,
        )
        builder = make_detector_service_builder(
            instrument="loki", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([det_stream])
        producer = FakeProducer()
        sink = KafkaSink(
            producer, make_default_serializer(builder.stream_mapping.livedata, "d")
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                DETECTOR_VIEW_HANDLE.workflow_id,
                "larmor_detector",
                "loki_livedata_commands",
            )
        )
        for _ in range(3):
            service.step()
        outputs = decoded_outputs(producer, "loki_livedata_data")
        img = next(
            v for v in outputs["image_cumulative"].variables if v.name == "signal"
        )
        assert img.data.shape == (256, 256)
        # replica weighting conserves counts up to edge losses: replicas
        # jittered off the screen edge drop their 1/R weight share
        assert 0.99 * 3 * 500 <= img.data.sum() <= 3 * 500


def bifrost_service(make_builder, tag):
    builder = make_builder(
        instrument="bifrost", batcher=NaiveMessageBatcher(), job_threads=1
    )
    producer = FakeProducer()
    sink = KafkaSink(
        producer, make_default_serializer(builder.stream_mapping.livedata, tag)
    )
    return builder, producer, sink


def bank_overview_on_45_triplets():
    """The detector service: every one of the 45 declared sources lands
    on the merged stream and in its own bank of the overview."""
    from esslivedata_tpu.config.instruments.bifrost.specs import (
        BANK_DETECTOR_NUMBERS,
        MULTIBANK_HANDLE,
        N_TRIPLETS,
    )

    streams = [
        FakeDetectorStream(
            topic="bifrost_detector",
            source_name=f"bifrost_{name}",
            detector_ids=det,
            events_per_pulse=100,
            seed=b,
        )
        for b, (name, det) in enumerate(BANK_DETECTOR_NUMBERS.items())
    ]
    assert len(streams) == N_TRIPLETS == 45
    builder, producer, sink = bifrost_service(make_detector_service_builder, "b")
    raw = PulsedRawSource(streams)
    service = builder.from_raw_source(raw, sink)
    raw.inject(
        start_command(
            MULTIBANK_HANDLE.workflow_id, "detector", "bifrost_livedata_commands"
        )
    )
    for _ in range(3):
        service.step()
    outputs = decoded_outputs(producer, "bifrost_livedata_data")
    counts = next(
        v for v in outputs["bank_counts_current"].variables if v.name == "signal"
    )
    assert counts.data.shape == (45,)
    # every triplet produced events on the merged stream
    assert (counts.data > 0).all()
    total = next(
        v for v in outputs["counts_cumulative"].variables if v.name == "signal"
    )
    assert float(total.data) == 45 * 100 * 3


def qe_map_with_default_parameters_bins_the_elastic_line_off_the_wire():
    """The reduction service applies the merged-detector adaptation
    (once it did not: jobs at 'detector' saw no events), and a job
    started with its default parameters, as a dashboard starts it, bins
    TOAs as ev44 carries them: relative to their own pulse, below
    1/14 s, the flight time being the TOA plus the frame offset."""
    from esslivedata_tpu.config.instruments.bifrost.specs import (
        ARC_EF_MEV,
        ARC_L2_M,
        BANK_DETECTOR_NUMBERS,
        MERGED_STREAM,
        QE_HANDLE,
    )
    from esslivedata_tpu.config.models import PULSE_PERIOD_NS
    from esslivedata_tpu.ops.qhistogram import E_FROM_V2
    from esslivedata_tpu.workflows.qe_spectroscopy import QESpectroscopyParams

    builder, producer, sink = bifrost_service(make_reduction_service_builder, "qe")
    raw = PulsedRawSource([])
    service = builder.from_raw_source(raw, sink)
    raw.inject(
        start_command(
            QE_HANDLE.workflow_id,
            MERGED_STREAM,
            "bifrost_livedata_commands",
            aux={"monitor": "monitor_1"},
        )
    )
    service.step()
    # Elastic arrivals on the second arc (Ef 3.2 meV; the first arc's
    # elastic line, 2.7 meV, lies outside the frame the offset selects).
    arc = 1
    v = np.sqrt(ARC_EF_MEV[arc] / E_FROM_V2)
    flight_ns = (162.0 + ARC_L2_M[0] + ARC_L2_M[1] * arc) / v * 1e9
    t_wire = flight_ns - QESpectroscopyParams().toa_offset_ns
    assert 0 <= t_wire < PULSE_PERIOD_NS  # what a wire can carry
    ids_of = BANK_DETECTOR_NUMBERS["triplet_1_0"].reshape(-1)
    rng = np.random.default_rng(0)
    for pulse in range(3):
        t_pulse = 1_700_000_000_000_000_000 + pulse * int(1e9 / 14)
        ids = rng.choice(ids_of, 1000).astype(np.int32)
        toa = np.full(1000, t_wire, dtype=np.int32)
        raw.inject(
            FakeKafkaMessage(
                wire.encode_ev44(
                    "bifrost_triplet_1_0",
                    pulse,
                    np.array([t_pulse]),
                    np.array([0]),
                    toa,
                    pixel_id=ids,
                ),
                "bifrost_detector",
            )
        )
        service.step()
    outputs = decoded_outputs(producer, "bifrost_livedata_data")
    sqw = next(
        var for var in outputs["sqw_cumulative"].variables if var.name == "signal"
    )
    assert float(np.asarray(sqw.data, np.float64).sum()) == 3000.0
    # Elastic events concentrate in few (Q, E) bins around dE=0.
    assert (np.asarray(sqw.data) > 0).sum() < 40


class TestBifrostMergedStream:
    @pytest.mark.parametrize(
        "case",
        [
            bank_overview_on_45_triplets,
            qe_map_with_default_parameters_bins_the_elastic_line_off_the_wire,
        ],
        ids=lambda case: case.__name__,
    )
    def test_45_triplets_one_stream(self, case):
        case()


class TestLokiParsedCatalogTimeseries:
    """A motion stream from the *generated* registry (ADR 0009) flows
    through the timeseries service end-to-end: f144 bytes on the catalog
    topic -> route derivation -> timeseries job -> republished da00."""

    def test_parsed_motion_stream_republishes(self):
        from esslivedata_tpu.config.instruments.loki import INSTRUMENT
        from esslivedata_tpu.config.instruments.loki.specs import (
            TIMESERIES_HANDLE,
        )
        from esslivedata_tpu.services.timeseries import (
            make_timeseries_service_builder,
        )

        # Pick a parsed catalog stream that no device claims (device
        # substreams are merged away by the DeviceSynthesizer and are
        # exercised by the device test below).
        name, stream = next(
            (n, s)
            for n, s in INSTRUMENT.streams.items()
            if s.source == "LOKI-SE:Tmp-TIC-101"
        )
        builder = make_timeseries_service_builder(
            instrument="loki", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "ts"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                TIMESERIES_HANDLE.workflow_id, name, "loki_livedata_commands"
            )
        )
        service.step()
        t0 = 1_700_000_000_000_000_000
        for i in range(3):
            payload = wire.encode_f144(
                stream.source, 1.5 + i, t0 + i * 1_000_000_000
            )
            raw.inject(FakeKafkaMessage(payload, stream.topic))
            service.step()
        out = decoded_outputs(producer, "loki_livedata_data")
        assert any(name in key for key in out), sorted(out)

    def test_parsed_device_stream_merges_and_republishes(self):
        """RBV+DMOV substreams from the generated catalog merge into one
        synthesised Device stream which a timeseries job republishes."""
        from esslivedata_tpu.config.instruments.loki import INSTRUMENT
        from esslivedata_tpu.config.instruments.loki.specs import (
            TIMESERIES_HANDLE,
        )
        from esslivedata_tpu.config.stream import Device
        from esslivedata_tpu.services.timeseries import (
            make_timeseries_service_builder,
        )

        name, dev = next(
            (n, s)
            for n, s in INSTRUMENT.streams.items()
            if isinstance(s, Device)
            and INSTRUMENT.streams[s.value].source
            == "LOKI-Smpl:MC-LinX-01:Mtr.RBV"
        )
        rbv = INSTRUMENT.streams[dev.value]
        builder = make_timeseries_service_builder(
            instrument="loki", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "ts"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                TIMESERIES_HANDLE.workflow_id, name, "loki_livedata_commands"
            )
        )
        service.step()
        t0 = 1_700_000_000_000_000_000
        # Bootstrap every declared role (emission starts once the device
        # has been seen on all substreams), then move the axis.
        val = INSTRUMENT.streams[dev.target]
        idle = INSTRUMENT.streams[dev.idle]
        raw.inject(
            FakeKafkaMessage(
                wire.encode_f144(val.source, 12.0, t0), val.topic
            )
        )
        raw.inject(
            FakeKafkaMessage(
                wire.encode_f144(idle.source, 1.0, t0), idle.topic
            )
        )
        for i in range(3):
            raw.inject(
                FakeKafkaMessage(
                    wire.encode_f144(rbv.source, 10.0 + i, t0 + (i + 1) * 10**9),
                    rbv.topic,
                )
            )
            service.step()
        out = decoded_outputs(producer, "loki_livedata_data")
        assert any(name in key for key in out), sorted(out)



class TestDreamLiveEmissionOffset:
    def test_f144_wfm_offset_swaps_the_running_bragg_table(self):
        # Optional context end to end: the WFM T0 arrives as a real f144
        # log, the job is NOT gated on it, and identical arrivals bin to
        # a shifted d-spacing afterwards (table swapped, no restart).
        import numpy as np

        from esslivedata_tpu.config.instrument import instrument_registry

        instrument_registry["dream"].load_factories()
        from esslivedata_tpu.config.instruments.dream.specs import (
            POWDER_HANDLE,
        )

        builder = make_reduction_service_builder(
            instrument="dream", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "wfm"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                POWDER_HANDLE.workflow_id,
                "mantle_detector",
                "dream_livedata_commands",
                aux={"monitor": "monitor_bunker"},
            )
        )
        service.step()

        h_over_mn = 3956.034
        t_ns = 2.0 * 77.7 / h_over_mn * 1e9
        t0 = 1_700_000_000_000_000_000
        rng = np.random.default_rng(0)

        def inject(pulse):
            ids = rng.integers(1, 491521, 1000).astype(np.int32)
            toa = np.full(1000, t_ns, dtype=np.int32)
            raw.inject(
                FakeKafkaMessage(
                    wire.encode_ev44(
                        "dream_mantle_detector",
                        pulse,
                        np.array([t0 + pulse * int(1e9 / 14)]),
                        np.array([0]),
                        toa,
                        pixel_id=ids,
                    ),
                    "dream_detector",
                )
            )
            service.step()

        def peak():
            for m in reversed(producer.messages):
                if m.topic != "dream_livedata_data":
                    continue
                da = wire.decode_da00(m.value)
                if "dspacing_current" in da.source_name:
                    for var in da.variables:
                        if var.name == "signal" and np.asarray(var.data).sum():
                            return int(np.asarray(var.data).argmax())
            return None

        inject(0)
        inject(1)
        p_before = peak()
        assert p_before is not None  # not gated: optional context
        raw.inject(
            FakeKafkaMessage(
                wire.encode_f144(
                    "dream_wfm_t0", -3.0e6, t0 + int(1.5e9 / 14)
                ),
                "dream_motion",
            )
        )
        service.step()
        inject(2)
        inject(3)
        p_after = peak()
        assert p_after is not None and p_after < p_before
