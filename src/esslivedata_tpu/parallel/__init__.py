"""Device-mesh serving tier: sharded kernels, mesh tick programs, placement.

The reference scales out with OS processes partitioned by Kafka topic
(SURVEY.md section 2.10) and has no collective backend at all;
compute-level scale-out here is TPU-native instead: a
``jax.sharding.Mesh`` with a ``data`` axis (event-stream shards, the DP
analog) and a ``bank`` axis (bin-space shards over detector banks/screen
rows — the TP/SP analog for a histogramming workload), with XLA
collectives riding ICI for cross-shard merges and monitor/detector
normalization. Kafka over DCN remains the inter-host system bus,
unchanged.

This package is the production serving topology, not a demo (ADR 0115):
the sharded kernels expose the same stage-once / fused-step / tick
contract as the single-device ``EventHistogrammer``, so mesh-backed
jobs ride the JobManager's one-dispatch tick program
(:mod:`.mesh_tick` ``MeshTickCombiner`` — one collective execute + one
replicated fetch per tick), and ``DevicePlacement`` assigns every
(stream, fuse-key) tick group a sticky mesh slice: single-device jobs
spread round-robin across chips, bank-sharded LOKI-scale jobs take the
whole mesh. Service surface: ``--mesh data,bank`` / ``LIVEDATA_MESH``
(services/service_factory.py); per-slice dispatch counts report
through ``ops/publish.METRICS``.
"""

from .mesh import make_mesh, mesh_from_spec
from .mesh_tick import DevicePlacement, MeshTickCombiner, TickSlice
from .sharded_hist import ShardedHistogrammer
from .sharded_qhist import ShardedQHistogrammer

__all__ = [
    "DevicePlacement",
    "MeshTickCombiner",
    "ShardedHistogrammer",
    "ShardedQHistogrammer",
    "TickSlice",
    "make_mesh",
    "mesh_from_spec",
]
