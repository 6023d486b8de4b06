"""What a service started with no flag and no ``LIVEDATA_*`` variable
runs: the serial loop, the tick program, the adaptive batcher, no
warm-up, per-message decode. It is what ``docker-compose.yml`` starts
and what ``benchmark/`` measures (PERF.md section 4), pinned here so
that flipping a default is a diff of this file; and the set of flags
that select a code path (ROADMAP D3, D10), so that a new one cannot
arrive unnoticed."""

from __future__ import annotations

import importlib
import os

import pytest

from esslivedata_tpu.core.message_batcher import AdaptiveMessageBatcher
from esslivedata_tpu.core.service import _ServiceArgumentParser
from esslivedata_tpu.kafka.message_adapter import _env_batch_decode
from esslivedata_tpu.kafka.sink import (
    FakeProducer,
    KafkaSink,
    make_default_serializer,
)
from esslivedata_tpu.services.fake_sources import PulsedRawSource
from esslivedata_tpu.services.service_factory import DataServiceRunner

#: service -> (module, builder function).
SERVICES = {
    "detector_data": ("detector_data", "make_detector_service_builder"),
    "monitor_data": ("monitor_data", "make_monitor_service_builder"),
    "data_reduction": ("data_reduction", "make_reduction_service_builder"),
    "timeseries": ("timeseries", "make_timeseries_service_builder"),
}

#: Flags that choose between two implementations of the same result.
PATH_SELECTING = {
    "--pipeline",
    "--pipeline-depth",
    "--flatten-threads",
    "--batch-decode",
    "--warmup",
    "--no-tick-program",
    "--batcher",
}
#: Flags that say where the service runs, what it talks to and what it
#: reports: a deployment sets them, no result depends on them.
DEPLOYMENT = {
    "--help",
    "--instrument",
    "--dev",
    "--cpu",
    "--log-level",
    "--log-json-file",
    "--metrics-port",
    "--serve-port",
    "--checkpoint-dir",
    "--checkpoint-interval",
    "--trace-dump",
    "--job-threads",
    "--mesh",
    "--fleet-replicas",
    "--fleet-self",
    "--kafka-bootstrap",
    "--profile",
    "--profile-seconds",
    "--broker-dir",
    "--check",
}


@pytest.fixture
def bare_environment(monkeypatch):
    for name in list(os.environ):
        if name.startswith("LIVEDATA_"):
            monkeypatch.delenv(name)
    # The runner reconfigures the root logger; this test's is pytest's.
    monkeypatch.setattr(
        "esslivedata_tpu.logging_config.configure_logging",
        lambda **_kwargs: None,
    )


def run_check(service: str, monkeypatch, argv=()):
    """The runner's own path from argv to a configured builder
    (``--check`` returns before any broker is touched). Returns the
    parser it built and the builder it configured."""
    module_name, make_name = SERVICES[service]
    module = importlib.import_module(f"esslivedata_tpu.services.{module_name}")
    seen = {}

    def make_builder(**kwargs):
        seen["builder"] = getattr(module, make_name)(**kwargs)
        return seen["builder"]

    parse_args = _ServiceArgumentParser.parse_args

    def recording_parse_args(self, *args, **kwargs):
        seen["parser"] = self
        return parse_args(self, *args, **kwargs)

    monkeypatch.setattr(
        _ServiceArgumentParser, "parse_args", recording_parse_args
    )
    runner = DataServiceRunner(service_name=service, make_builder=make_builder)
    assert runner.run(["--instrument", "dummy", "--check", *argv]) == 0
    return seen["parser"], seen["builder"]


def ev44_adapters(adapter):
    """Every leaf of the route tree that decodes ev44."""
    routes = getattr(adapter, "_routes", None)
    if routes is None:
        return [adapter] if hasattr(adapter, "_batch") else []
    return [
        leaf for child in routes.values() for leaf in ev44_adapters(child)
    ]


@pytest.mark.parametrize("service", list(SERVICES))
def test_a_service_started_with_no_flag(
    service, bare_environment, monkeypatch
):
    _parser, builder = run_check(service, monkeypatch)
    sink = KafkaSink(
        FakeProducer(),
        make_default_serializer(builder.stream_mapping.livedata, "defaults"),
    )
    processor = builder.from_raw_source(PulsedRawSource([]), sink).processor
    manager = processor._job_manager
    try:
        assert processor._pipeline is None  # the serial loop
        assert manager._tick_combiner is not None  # the tick program
        assert manager._publish_combiner is not None
        assert type(processor._batcher) is AdaptiveMessageBatcher
        assert manager._warmup is None
        assert manager._placement is None and manager._fleet is None
        assert processor._result_fanout is None
        assert processor._durability is None
        assert not _env_batch_decode()  # per-message decode
        adapters = ev44_adapters(
            builder._route_builder(builder.stream_mapping)
        )
        assert all(adapter._batch is False for adapter in adapters)
        assert bool(adapters) == (service != "timeseries")
    finally:
        manager.shutdown()


def test_path_selecting_flags_are_these(bare_environment, monkeypatch):
    parser, _builder = run_check("detector_data", monkeypatch)
    flags = {
        option
        for action in parser._actions
        for option in action.option_strings
        if option.startswith("--")
    }
    assert flags - DEPLOYMENT == PATH_SELECTING
    assert flags >= DEPLOYMENT
    (batcher,) = (a for a in parser._actions if a.dest == "batcher")
    assert list(batcher.choices) == [
        "naive", "simple", "adaptive", "rate_aware"
    ]
    assert batcher.default == "adaptive"
