"""Multi-device Q-family histogrammer: the TABLE is what gets sharded.

The precompiled (pixel, toa-bin) -> bin tables of the reduction
families (ops/qhistogram.py) dominate device memory at scale — DREAM's
mantle Bragg table is ~0.5 GB int16 — while the OUTPUT bin space is
tiny (10^2-10^4 bins). So the scaling shape is the inverse of the
detector-view histogrammer (sharded_hist.py, which shards screen rows):

- table rows shard over the mesh's ``bank`` axis (each device holds
  ``n_rows / n_bank`` contiguous pixel rows);
- the event batch is replicated (its P() sharding broadcasts it);
- each device scatters only the events landing in its row range — the
  bank-local id shift routes them for free, everything else drops via
  the OOB bin;
- one ``psum('bank')`` over the small [n_bins] delta merges the
  partials, keeping the replicated QState identical on all devices.

Per-step ICI traffic is O(n_bins) — independent of both table size and
event count — so the table can grow with instrument cardinality while
collectives stay constant. The table rides the shard_mapped step as an
ARGUMENT (ADR 0105): a live recalibration (emission offset, sample
angle) re-shards a rebuilt table with one host->device transfer per
shard and zero recompiles.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.event_batch import sanitize_pixel_id, stage_for
from ..ops.qhistogram import PixelBinMap, QState, table_scatter_delta

__all__ = ["ShardedQHistogrammer"]


def _pad_to_shards(table: np.ndarray, n_shards: int) -> np.ndarray:
    """Pad rows to the shard boundary with drop rows (-1): padded pixels
    can never be hit (ids beyond the bank range shift OOB)."""
    pad = (-table.shape[0]) % n_shards
    if pad:
        table = np.concatenate(
            [table, np.full((pad, table.shape[1]), -1, dtype=table.dtype)]
        )
    return table


class ShardedQHistogrammer:
    """Table-row-sharded scatter-add into a replicated Q-bin state.

    Single-device equivalent: ``ops.qhistogram.QHistogrammer`` — same
    logical inputs (global pixel ids, toa, monitor count), same QState
    semantics (window folds, cumulative monotone, monitor channel).
    """

    def __init__(
        self,
        *,
        qmap: PixelBinMap,
        toa_edges: np.ndarray,
        n_q: int,
        mesh: Mesh,
        axis: str = "bank",
        dtype=jnp.float32,
        method: str = "scatter",
    ) -> None:
        if method not in ("auto", "scatter", "pallas"):
            raise ValueError(f"Unknown method {method!r}")
        if method == "auto":
            # Same resolution as the single-device QHistogrammer: the
            # per-shard delta is a full [n_q] vector either way, so the
            # VMEM bound is the global one.
            from ..ops.pallas_hist import MAX_PALLAS_BINS

            method = (
                "pallas"
                if (
                    n_q + 1 <= MAX_PALLAS_BINS
                    and jax.default_backend() == "tpu"
                )
                else "scatter"
            )
        self._method = method
        table, id_base = qmap.table, int(qmap.id_base)
        toa_edges = np.asarray(toa_edges, dtype=np.float64)
        if table.shape[1] != toa_edges.size - 1:
            raise ValueError("qmap toa axis must match toa_edges")
        if table.max(initial=-1) >= n_q:
            raise ValueError("qmap entries must be < n_q")
        self._mesh = mesh
        self._axis = axis
        n_shards = mesh.shape[axis]
        table = _pad_to_shards(table, n_shards)
        self._rows_per_shard = table.shape[0] // n_shards
        self._id_base = id_base
        self._n_q = int(n_q)
        self._lo = float(toa_edges[0])
        self._hi = float(toa_edges[-1])
        n_toa = toa_edges.size - 1
        self._n_toa = n_toa
        self._inv_width = float(n_toa / (self._hi - self._lo))
        self._dtype = dtype
        self._table_sharding = NamedSharding(mesh, P(axis, None))
        self._table = jax.device_put(table, self._table_sharding)

        rows = self._rows_per_shard

        def _step(state, table_shard, pixel_id, toa, monitor_count):
            # Rows are contiguous: shard i covers
            # [id_base + i*rows, id_base + (i+1)*rows). Same traceable
            # core as the single-device kernel, with the shard-local base.
            shard = jax.lax.axis_index(axis)
            delta = table_scatter_delta(
                table_shard,
                pixel_id,
                toa,
                id_base=self._id_base + shard * rows,
                lo=self._lo,
                hi=self._hi,
                inv_width=self._inv_width,
                n_bins=self._n_q,
                dtype=dtype,
                method=self._method,
            )
            # The ONLY collective: O(n_q) regardless of table size.
            delta = jax.lax.psum(delta, axis)
            mc = jnp.asarray(monitor_count, dtype=dtype)
            return QState(
                cumulative=state.cumulative + delta,
                window=state.window + delta,
                monitor_cumulative=state.monitor_cumulative + mc,
                monitor_window=state.monitor_window + mc,
            )

        state_specs = QState(
            cumulative=P(), window=P(), monitor_cumulative=P(),
            monitor_window=P(),
        )
        self._step = jax.jit(
            jax.shard_map(
                _step,
                mesh=mesh,
                in_specs=(state_specs, P(axis, None), P(), P(), P()),
                out_specs=state_specs,
                # Interpret-mode pallas inside shard_map trips a JAX vma
                # propagation gap (dynamic_slice with mixed varying
                # axes); the error message itself prescribes this
                # workaround. Scatter keeps full vma checking.
                check_vma=(method != "pallas"),
            ),
            donate_argnums=(0,),
        )
        self._replicated_sharding = NamedSharding(mesh, P())
        self._replicate = lambda x: jax.device_put(
            x, self._replicated_sharding
        )

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def n_q(self) -> int:
        return self._n_q

    @property
    def rows_per_shard(self) -> int:
        return self._rows_per_shard

    def init_state(self) -> QState:
        zeros = self._replicate(jnp.zeros((self._n_q,), dtype=self._dtype))
        scalar = self._replicate(jnp.zeros((), dtype=self._dtype))
        return QState(
            cumulative=zeros,
            window=jnp.array(zeros),
            monitor_cumulative=scalar,
            monitor_window=jnp.array(scalar),
        )

    def step(
        self, state: QState, pixel_id, toa, monitor_count: float = 0.0
    ) -> QState:
        # Same ingest-boundary guards as every other path: wide dtypes
        # sanitize (no int32 wrap) and staging copies decouple reused
        # host buffers from the async dispatch (event_batch.py). Device
        # arrays pass through untouched (already int32/float32, no sync).
        if not isinstance(pixel_id, jax.Array):
            pixel_id = sanitize_pixel_id(np.asarray(pixel_id))

        # One hop host->mesh (stage_for): dispatch_safe would commit the
        # batch to the DEFAULT device and pay a second device->device
        # copy on the replicated placement.
        sharding = self._replicated_sharding
        return self._step(
            state,
            self._table,
            stage_for(pixel_id, sharding, dtype=jnp.int32),
            stage_for(toa, sharding, dtype=jnp.float32),
            stage_for(monitor_count, sharding, dtype=self._dtype),
        )

    def swap_table(self, qmap: PixelBinMap) -> None:
        """Re-shard a rebuilt table (live recalibration) — one transfer
        per shard, no recompile (the table is a step argument)."""
        table, id_base = qmap.table, int(qmap.id_base)
        if id_base != self._id_base:
            raise ValueError(
                f"swap_table id_base {id_base} != compiled {self._id_base}"
            )
        if table.max(initial=-1) >= self._n_q:
            raise ValueError("qmap entries must be < n_q")
        if table.shape[1] != self._n_toa:
            raise ValueError(
                "swap_table must keep the toa binning: the step's TOA "
                f"projection compiled against {self._n_toa} bins"
            )
        n_shards = self._mesh.shape[self._axis]
        table = _pad_to_shards(table, n_shards)
        if table.shape[0] // n_shards != self._rows_per_shard:
            raise ValueError("swap_table must keep the row count")
        self._table = jax.device_put(table, self._table_sharding)

    def clear_window(self, state: QState) -> QState:
        return QState(
            cumulative=state.cumulative,
            window=jnp.zeros_like(state.window),
            monitor_cumulative=state.monitor_cumulative,
            monitor_window=jnp.zeros_like(state.monitor_window),
        )

    def read(self, state: QState) -> tuple[np.ndarray, np.ndarray, float, float]:
        """(cumulative, window, monitor_cumulative, monitor_window)."""
        return (
            np.asarray(state.cumulative),
            np.asarray(state.window),
            float(state.monitor_cumulative),
            float(state.monitor_window),
        )
