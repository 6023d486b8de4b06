"""Service assembly: adapter + preprocessors + processor + sink -> Service.

Parity with reference ``service_factory.py`` (DataServiceBuilder:58,
DataServiceRunner:271): builders wire the full stack from an instrument
name; the runner adds the CLI surface (--instrument --dev --batcher
--job-threads --check, LIVEDATA_* env overrides) and broker config. The
broker path needs confluent_kafka (optional dependency); everything else
runs against in-memory fakes, which is also the test rig.
"""

from __future__ import annotations

import logging
from collections.abc import Callable

from ..core.job_manager import JobFactory, JobManager
from ..core.message_batcher import (
    AdaptiveMessageBatcher,
    MessageBatcher,
    NaiveMessageBatcher,
    SimpleMessageBatcher,
)
from ..core.nicos_devices import DeviceExtractor
from ..core.orchestrating_processor import OrchestratingProcessor
from ..core.service import Service, get_env_defaults, setup_arg_parser
from ..config.device_contract import DeviceContract
from ..config.instrument import instrument_registry
from ..config.streams import get_stream_mapping
from ..kafka.message_adapter import AdaptingMessageSource, RouteByTopicAdapter
from ..kafka.sink import KafkaSink, UnrollingSinkAdapter, make_default_serializer
from ..kafka.source import BackgroundMessageSource
from ..core.rate_aware_batcher import RateAwareMessageBatcher
from ..kafka.stream_counter import StreamCounter
from ..kafka.stream_mapping import StreamMapping
from ..workflows.workflow_factory import workflow_registry

__all__ = ["DataServiceBuilder", "DataServiceRunner", "make_batcher"]

logger = logging.getLogger(__name__)


def make_batcher(name: str) -> MessageBatcher:
    if name == "naive":
        return NaiveMessageBatcher()
    if name == "simple":
        return SimpleMessageBatcher()
    if name == "adaptive":
        return AdaptiveMessageBatcher()
    if name == "rate_aware":
        return RateAwareMessageBatcher()
    raise ValueError(f"Unknown batcher {name!r}")


class DataServiceBuilder:
    """Builds one backend service for one instrument."""

    def __init__(
        self,
        *,
        instrument: str,
        service_name: str,
        preprocessor_factory,
        route_builder: Callable[[StreamMapping], RouteByTopicAdapter],
        batcher: MessageBatcher | None = None,
        job_threads: int = 5,
        dev: bool = False,
        heartbeat_interval_s: float = 2.0,
        source_decorator: Callable | None = None,
        snapshot_dir: str | None = None,
    ) -> None:
        self.instrument_name = instrument
        self.service_name = service_name
        self._preprocessor_factory = preprocessor_factory
        self._route_builder = route_builder
        self._batcher = batcher or AdaptiveMessageBatcher()
        self._job_threads = job_threads
        self._dev = dev
        self._heartbeat_interval_s = heartbeat_interval_s
        self._source_decorator = source_decorator
        # Histogram-state snapshots at run boundaries/shutdown (SURVEY §5):
        # explicit argument wins; LIVEDATA_SNAPSHOT_DIR enables it for
        # deployed services; unset = disabled.
        import os as _os

        self._snapshot_dir = (
            snapshot_dir
            if snapshot_dir is not None
            else _os.environ.get("LIVEDATA_SNAPSHOT_DIR")
        )
        # Pipelined ingest (ADR 0111). Precedence: kafka config
        # namespace (the consume->ingest tier's app-tuning keys,
        # kafka/consumer.py) < LIVEDATA_* env < the runner's
        # --pipeline/--pipeline-depth/--flatten-threads flags, which
        # override by assigning these public attributes after build.
        tuning = self._ingest_tuning()
        self.pipelined = (
            _os.environ["LIVEDATA_PIPELINE"].lower() in ("1", "true", "yes")
            if "LIVEDATA_PIPELINE" in _os.environ
            else bool(tuning.get("pipeline", False))
        )
        self.pipeline_depth = int(
            _os.environ.get(
                "LIVEDATA_PIPELINE_DEPTH", tuning.get("pipeline_depth", 2)
            )
        )
        self.flatten_threads = int(
            _os.environ.get(
                "LIVEDATA_FLATTEN_THREADS", tuning.get("flatten_threads", 0)
            )
        )
        # One-dispatch tick programs (ADR 0114): on by default — a
        # steady-state window steps AND publishes in one device round
        # trip. LIVEDATA_TICK_PROGRAM=0 (or --no-tick-program) keeps the
        # separate fused-step + combined-publish dispatches, the
        # triage/parity escape hatch.
        self.tick_program = _os.environ.get(
            "LIVEDATA_TICK_PROGRAM", "1"
        ).lower() not in ("0", "false", "no")
        # Mesh serving tier (parallel/mesh_tick.py, ADR 0115):
        # "data,bank" (e.g. "2,4"), a device count, or "auto" = all
        # visible devices on the bank axis. Empty/unset = single-
        # placement serving (the classic path). The runner's --mesh
        # flag overrides by assigning this attribute after build.
        self.mesh_spec: str | None = (
            _os.environ.get("LIVEDATA_MESH") or None
        )
        # Result fan-out tier (serving/, ADR 0117): when a port is
        # configured the processor feeds every publish tick's da00
        # outputs into a delta-encoded SSE broadcast plane. None =
        # disabled. The runner's --serve-port overrides after build.
        _serve_env = _os.environ.get("LIVEDATA_SERVE_PORT")
        self.serve_port: int | None = (
            int(_serve_env) if _serve_env else None
        )
        # Fleet partitioning (fleet/assignment.py, ADR 0121): the full
        # replica-id set plus this replica's id — both required
        # together; the JobManager then processes only the
        # (stream, fuse-key) groups rendezvous-hashed here. The
        # runner's --fleet-replicas/--fleet-self override after build.
        self.fleet_replicas: str | None = (
            _os.environ.get("LIVEDATA_FLEET_REPLICAS") or None
        )
        self.fleet_self: str | None = (
            _os.environ.get("LIVEDATA_FLEET_SELF") or None
        )
        # Durability plane (durability/, ADR 0118): periodic state +
        # offset checkpoints under --checkpoint-dir, AOT tick-program
        # warm-up under --warmup. The runner's flags override after
        # build, like every other axis here.
        self.checkpoint_dir: str | None = (
            _os.environ.get("LIVEDATA_CHECKPOINT_DIR") or None
        )
        # Empty-but-set env degrades to the default (the serve-port
        # rule): a deployment template that exports the var
        # unconditionally must not crash every service at build time.
        _interval_env = _os.environ.get("LIVEDATA_CHECKPOINT_INTERVAL")
        self.checkpoint_interval = (
            float(_interval_env) if _interval_env else 30.0
        )
        self.warmup = _os.environ.get(
            "LIVEDATA_WARMUP", ""
        ).lower() in ("1", "true", "yes")
        # Built lazily (durability_plane()) so the runner's restore
        # path and from_raw_source share ONE plane — and therefore one
        # sha256-verified manifest load — instead of each scanning the
        # directory independently.
        self._durability_plane = None
        self._instrument = instrument_registry[instrument]
        self._instrument.load_factories()
        # Subscribe only to streams the hosted specs consume (reference
        # route_derivation.scope_stream_mapping:109).
        from ..config.route_derivation import scope_stream_mapping

        self.stream_mapping = scope_stream_mapping(
            self._instrument, get_stream_mapping(self._instrument, dev), service_name
        )

    @staticmethod
    def _ingest_tuning() -> dict:
        """The kafka config namespace's ingest hand-off keys (see
        kafka/consumer.py _APP_TUNING_KEYS); empty without a config."""
        try:
            from ..config.config_loader import load_config

            conf = load_config(namespace="kafka") or {}
        except Exception:
            # Config files are optional (tests, fakes-only deployments);
            # the env/CLI surface still configures the pipeline.
            logger.debug("kafka config namespace unavailable", exc_info=True)
            return {}
        return {
            key: conf[key]
            for key in ("pipeline", "pipeline_depth", "flatten_threads")
            if key in conf
        }

    def durability_plane(self):
        """The (lazily built, cached) CheckpointPlane for
        ``checkpoint_dir`` — None when durability is off. Shared by the
        runner's seek-to-bookmark path and the service build, so the
        manifest is loaded and digest-verified exactly once."""
        if self.checkpoint_dir and self._durability_plane is None:
            from ..durability import CheckpointPlane

            self._durability_plane = CheckpointPlane(
                self.checkpoint_dir,
                interval_s=self.checkpoint_interval,
            )
            logger.info(
                "durability plane: checkpoints every %.0f s into %s",
                self.checkpoint_interval,
                self.checkpoint_dir,
            )
        return self._durability_plane

    @property
    def topics(self) -> list[str]:
        """The service's actual subscription = the topics its route tree
        handles (reference derives this by scoping the stream mapping to the
        service, route_derivation.py:109)."""
        return self._route_builder(self.stream_mapping).topics

    def from_raw_source(self, raw_source, sink) -> Service:
        """Assemble from anything yielding KafkaMessages + a MessageSink —
        used by tests (fakes) and by the broker path alike."""
        adapter = self._route_builder(self.stream_mapping)
        counter = StreamCounter()
        source = AdaptingMessageSource(raw_source, adapter, stream_counter=counter)
        if self._source_decorator is not None:
            # In-process stream synthesis (ADR 0001): device merge, chopper
            # cascade — wraps the already-adapted source.
            source = self._source_decorator(source, self._instrument)
        snapshot_store = None
        if self._snapshot_dir:
            from ..core.state_snapshot import SnapshotStore

            snapshot_store = SnapshotStore(self._snapshot_dir)
        placement = None
        if self.mesh_spec:
            # A bad mesh spec is a deployment configuration error: fail
            # the build loudly rather than silently serving single-
            # placement (the operator asked for a topology).
            from ..parallel.mesh import mesh_from_spec
            from ..parallel.mesh_tick import DevicePlacement

            mesh = mesh_from_spec(self.mesh_spec)
            placement = DevicePlacement(mesh)
            logger.info(
                "mesh serving: %s over devices %s",
                dict(mesh.shape),
                [int(d.id) for d in mesh.devices.flat],
            )
        durability = self.durability_plane()
        job_manager = JobManager(
            job_factory=JobFactory(),
            job_threads=self._job_threads,
            snapshot_store=snapshot_store,
            tick_program=self.tick_program,
            placement=placement,
            durability=durability,
        )
        if bool(self.fleet_replicas) != bool(self.fleet_self):
            raise ValueError(
                "--fleet-replicas and --fleet-self must be set "
                "together (a replica that doesn't know the set, or a "
                "set without an identity, would silently own the "
                "wrong groups)"
            )
        if self.fleet_replicas and self.fleet_self:
            from ..fleet import FleetAssignment

            replica_ids = [
                r.strip()
                for r in self.fleet_replicas.split(",")
                if r.strip()
            ]
            assignment = FleetAssignment(
                replica_ids,
                self.fleet_self,
                name=f"{self.instrument_name}_{self.service_name}",
            )
            job_manager.set_fleet(assignment)
            logger.info(
                "fleet partitioning: replica %r of %s",
                self.fleet_self,
                replica_ids,
            )
        if self.warmup:
            from ..durability import CompileWarmupService

            job_manager.set_warmup(CompileWarmupService())
            logger.info("AOT tick-program warm-up enabled")
        # Contract derived from this instrument's registered specs: outputs
        # listed in ``device_outputs`` ride the stable NICOS device stream.
        contract = DeviceContract.from_specs(
            workflow_registry.specs_for_instrument(self.instrument_name)
        )
        result_fanout = None
        if self.serve_port is not None:
            # Keyed by requested port so repeated builds in one process
            # (tests driving main()) reuse the listener — the
            # core/service.py metrics-server rule. A bind failure
            # raises loudly: an operator who asked for a serve port
            # must not silently run without the fan-out tier.
            from ..serving import get_or_create_plane

            result_fanout = get_or_create_plane(
                int(self.serve_port),
                name=f"{self.instrument_name}_{self.service_name}",
            )
            logger.info(
                "result fan-out tier on port %s (/results, /streams/...)",
                result_fanout.port,
            )
        processor = OrchestratingProcessor(
            source=source,
            sink=sink,
            preprocessor_factory=self._preprocessor_factory,
            job_manager=job_manager,
            batcher=self._batcher,
            instrument=self.instrument_name,
            service_name=self.service_name,
            device_extractor=DeviceExtractor(device_contract=contract),
            stream_counter=counter,
            heartbeat_interval_s=self._heartbeat_interval_s,
            pipelined=self.pipelined,
            pipeline_depth=self.pipeline_depth,
            flatten_threads=self.flatten_threads,
            result_fanout=result_fanout,
            durability=durability,
        )
        return Service(
            processor=processor,
            name=f"{self.instrument_name}_{self.service_name}",
        )

    def from_consumer(self, consumer, producer) -> Service:
        """Assemble over a real (or fake) Kafka consumer/producer pair."""
        raw_source = BackgroundMessageSource(consumer)
        raw_source.start()
        sink = UnrollingSinkAdapter(
            KafkaSink(
                producer,
                make_default_serializer(
                    self.stream_mapping.livedata,
                    f"{self.instrument_name}_{self.service_name}",
                ),
            )
        )
        return self.from_raw_source(raw_source, sink)


class DataServiceRunner:
    """CLI entry point shared by the four services."""

    def __init__(self, *, service_name: str, make_builder) -> None:
        self._service_name = service_name
        self._make_builder = make_builder

    def run(self, argv: list[str] | None = None) -> int:
        parser = setup_arg_parser(f"esslivedata-tpu {self._service_name} service")
        parser.add_argument(
            "--batcher",
            default="adaptive",
            choices=["naive", "simple", "adaptive", "rate_aware"],
            help="how messages are cut into windows: adaptive and "
            "rate_aware widen the window under load (LoadGovernor); "
            "rate_aware also closes a window when every gated "
            "stream's last expected pulse has arrived",
        )
        parser.add_argument("--job-threads", type=int, default=5)
        parser.add_argument(
            "--pipeline",
            action="store_true",
            default=False,
            help="pipelined ingest (ADR 0111): decode | prestage | "
            "step/publish overlap across windows with bounded "
            "backpressure (LIVEDATA_PIPELINE=1 equivalently)",
        )
        parser.add_argument(
            "--pipeline-depth",
            type=int,
            default=None,
            help="in-flight window bound of --pipeline",
        )
        parser.add_argument(
            "--flatten-threads",
            type=int,
            default=None,
            help="chunk the host flatten across this many threads "
            "during prestaging (multicore ingest hosts; 0/1 = off)",
        )
        parser.add_argument(
            "--mesh",
            default=None,
            metavar="DATA,BANK",
            help="mesh serving tier (ADR 0115): place tick groups on a "
            "data x bank device mesh — '2,4' = 2-way event sharding x "
            "4-way bank sharding, '8' or 'auto' = all devices on the "
            "bank axis. Single-device jobs spread round-robin over the "
            "mesh; bank-sharded jobs get the whole mesh "
            "(LIVEDATA_MESH equivalently)",
        )
        parser.add_argument(
            "--no-tick-program",
            action="store_true",
            default=False,
            help="disable the one-dispatch tick program (ADR 0114) and "
            "keep the separate fused-step + combined-publish dispatches "
            "(LIVEDATA_TICK_PROGRAM=0 equivalently; parity/triage)",
        )
        parser.add_argument(
            "--fleet-replicas",
            default=None,
            metavar="ID,ID,...",
            help="fleet partitioning (ADR 0121): the full replica-id "
            "set this service belongs to; each (stream, fuse-key) "
            "group is rendezvous-hashed onto exactly one replica "
            "(LIVEDATA_FLEET_REPLICAS equivalently; requires "
            "--fleet-self)",
        )
        parser.add_argument(
            "--fleet-self",
            default=None,
            metavar="ID",
            help="this replica's id within --fleet-replicas "
            "(LIVEDATA_FLEET_SELF equivalently)",
        )
        parser.add_argument(
            "--kafka-bootstrap",
            default=None,
            help="override the broker from the kafka config namespace",
        )
        parser.add_argument(
            "--profile",
            default=None,
            metavar="DIR",
            help="capture a JAX device trace of the first "
            "--profile-seconds into DIR (TensorBoard/Perfetto readable)",
        )
        parser.add_argument(
            "--profile-seconds", type=float, default=30.0
        )
        parser.add_argument(
            "--broker-dir",
            default=None,
            help="use the file-backed broker rooted at this directory "
            "instead of Kafka (multi-process integration/dev runs)",
        )
        parser.add_argument(
            "--check",
            action="store_true",
            help="build everything, print topics, exit",
        )
        parser.set_defaults(**get_env_defaults(parser))
        args = parser.parse_args(argv)
        from ..logging_config import configure_logging

        configure_logging(level=args.log_level, json_file=args.log_json_file)

        from ..config.instrument import instrument_registry as registry

        if args.instrument not in registry:
            parser.error(
                f"Unknown instrument {args.instrument!r}; "
                f"known: {', '.join(registry.names()) or '(none)'}"
            )
        builder = self._make_builder(
            instrument=args.instrument,
            dev=args.dev,
            batcher=make_batcher(args.batcher),
            job_threads=args.job_threads,
        )
        # CLI overrides win over the builder's LIVEDATA_* env defaults.
        if args.pipeline:
            builder.pipelined = True
        if args.pipeline_depth is not None:
            builder.pipeline_depth = args.pipeline_depth
        if args.flatten_threads is not None:
            builder.flatten_threads = args.flatten_threads
        if args.no_tick_program:
            builder.tick_program = False
        if args.mesh is not None:
            builder.mesh_spec = args.mesh or None
        if args.serve_port is not None:
            builder.serve_port = args.serve_port
        if args.fleet_replicas is not None:
            builder.fleet_replicas = args.fleet_replicas or None
        if args.fleet_self is not None:
            builder.fleet_self = args.fleet_self or None
        if args.checkpoint_dir is not None:
            builder.checkpoint_dir = args.checkpoint_dir or None
        if args.checkpoint_interval is not None:
            builder.checkpoint_interval = args.checkpoint_interval
        if args.warmup:
            builder.warmup = True
        if args.batch_decode:
            # The ev44 adapters resolve the gate from the environment at
            # construction (inside from_raw_source's route build, after
            # this point) — env-as-plumbing, same convention the
            # LIVEDATA_* builder defaults use (ADR 0125).
            import os

            os.environ["LIVEDATA_BATCH_DECODE"] = "1"
        if args.check:
            print(
                f"{self._service_name}: instrument={args.instrument} "
                f"topics={builder.topics}"
            )
            return 0
        from ..kafka.consumer import assign_all_partitions
        from ..utils.runtime import (
            enable_persistent_compilation_cache,
            keep_freed_host_memory,
            log_device_identity,
        )

        # Before the first large array: what a window frees is faulted
        # in once, not again every window (ADR 0133).
        keep_freed_host_memory()
        # Before anything compiles: the AOT warm-up path and the live
        # jits share one on-disk compilation cache, so restarts skip XLA.
        enable_persistent_compilation_cache()
        log_device_identity()
        if args.broker_dir:
            from ..kafka.file_broker import (
                FileBrokerConsumer,
                FileBrokerProducer,
                ensure_topics,
            )

            # Create this service's input topics (the admin op a Kafka
            # deployment does out of band) so launch order doesn't matter.
            ensure_topics(args.broker_dir, builder.topics)
            consumer = FileBrokerConsumer(args.broker_dir)
            producer = FileBrokerProducer(args.broker_dir)
        else:
            try:
                from confluent_kafka import Consumer, Producer
            except ImportError:
                logger.error(
                    "confluent_kafka not installed; install extra [kafka] "
                    "or use the fake transport (tests/demos)"
                )
                return 2
            from ..kafka.consumer import kafka_client_config

            # Full client config (incl. SASL/SSL in prod) from the kafka
            # config namespace; --kafka-bootstrap overrides the broker.
            client_conf = kafka_client_config(
                bootstrap_override=args.kafka_bootstrap
            )
            consumer = Consumer(
                {
                    **client_conf,
                    "group.id": f"{args.instrument}_{self._service_name}",
                    "auto.offset.reset": "latest",
                    "enable.auto.commit": False,
                }
            )
            producer = Producer(client_conf)
        # Manual assignment — never subscribe: no group rebalancing, no
        # offset commits (kafka/consumer.py, reference consumer.py:31).
        # Without a checkpoint, offsets pin at the high watermark (the
        # documented resume-at-live-data gap); WITH one, each bookmarked
        # topic seeks to its bookmark and the normal ingest path replays
        # the gap into the restored states (durability/replay.py,
        # ADR 0118).
        offsets: dict[str, int] = {}
        plane = builder.durability_plane()
        if plane is not None:
            from ..durability.replay import record_replay_lag

            offsets = plane.bookmarks()
            if offsets:
                lag = record_replay_lag(consumer, builder.topics, offsets)
                logger.info(
                    "seeking %d bookmarked topic(s); replay backlog %d",
                    len(offsets),
                    lag,
                )
        assign_all_partitions(
            consumer, builder.topics, start_offsets=offsets or None
        )
        service = builder.from_consumer(consumer, producer)
        if args.profile:
            from ..utils.profiling import bounded_device_trace

            bounded_device_trace(args.profile, args.profile_seconds)
        service.start(blocking=True)
        return service.exit_code
