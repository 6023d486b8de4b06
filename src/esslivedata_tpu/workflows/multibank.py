"""Multi-bank detector workflow: one kernel over all banks, mesh-shardable.

BIFROST-style instruments have many detector banks (9 analyzer triplets)
merged into one logical stream (reference: Ev44ToDetectorEventsAdapter
merge-detectors, message_adapter.py:416). TPU-native shape: the screen
space is the *concatenation of all banks* — one [n_banks*rows, toa] state,
one scatter per window — and when the process owns a multi-device mesh the
same workflow shards that bank axis over devices via ShardedHistogrammer
(BASELINE config 3). Per-bank outputs are slices of the global state.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any, Literal

import numpy as np
from pydantic import BaseModel, ConfigDict, Field

import jax

from ..config.models import TOARange
from ..ops.histogram import EventHistogrammer
from ..parallel.mesh import make_mesh
from ..parallel.sharded_hist import ShardedHistogrammer
from ..preprocessors.event_data import StagedEvents
from ..utils.labeled import DataArray, Variable

__all__ = ["MultiBankParams", "MultiBankViewWorkflow"]




class MultiBankParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    toa_bins: int = 100
    toa_range: TOARange = Field(default_factory=TOARange)
    use_mesh: bool = True
    """Shard the bank axis over all visible devices when more than one."""
    mesh_exchange: Literal["auto", "delta_psum", "event_gather"] = "auto"
    """Data-shard merge strategy for the sharded kernel; 'auto' compares
    actual delta vs gather bytes (parallel/sharded_hist.py)."""
    mesh_batch_hint: int | None = None
    """Expected events per padded batch for the 'auto' crossover."""


class MultiBankViewWorkflow:
    """Per-bank TOA histograms from a merged multi-bank event stream."""

    def __init__(
        self,
        *,
        bank_detector_numbers: Mapping[str, np.ndarray],
        params: MultiBankParams | None = None,
        mesh=None,
    ) -> None:
        params = params or MultiBankParams()
        self._params = params
        self._bank_names = list(bank_detector_numbers)
        n_banks = len(self._bank_names)
        sizes = [np.asarray(d).size for d in bank_detector_numbers.values()]
        if len(set(sizes)) != 1:
            raise ValueError("All banks must have equal pixel counts")
        self._pixels_per_bank = sizes[0]
        n_screen = n_banks * self._pixels_per_bank

        # Global LUT: detector_number -> bank*pixels_per_bank + local index
        max_id = max(int(np.asarray(d).max()) for d in bank_detector_numbers.values())
        lut = np.full(max_id + 1, -1, dtype=np.int32)
        for b, det in enumerate(bank_detector_numbers.values()):
            ids = np.asarray(det).reshape(-1)
            lut[ids] = b * self._pixels_per_bank + np.arange(ids.size)

        edges = np.linspace(
            params.toa_range.low, params.toa_range.high, params.toa_bins + 1
        )
        n_devices = len(jax.devices())
        # The bank axis shards only in whole banks; use the largest device
        # count that divides n_screen bank-wise. An explicit ``mesh``
        # (service placement, bench, tests) wins — the mesh serving tier
        # (parallel/mesh_tick.py, ADR 0115) hands LOKI-scale jobs the
        # whole serving mesh this way.
        self._sharded = None
        if mesh is None and params.use_mesh and n_devices > 1:
            bank_axis = n_devices
            while bank_axis > 1 and n_banks % bank_axis:
                bank_axis -= 1
            if bank_axis > 1:
                mesh = make_mesh(bank_axis, bank=bank_axis)
        if mesh is not None and params.use_mesh:
            self._sharded = ShardedHistogrammer(
                toa_edges=edges,
                n_screen=n_screen,
                mesh=mesh,
                pixel_lut=lut,
                exchange=params.mesh_exchange,
                batch_hint=params.mesh_batch_hint,
            )
        if self._sharded is not None:
            self._hist = self._sharded
        else:
            self._hist = EventHistogrammer(
                toa_edges=edges, n_screen=n_screen, pixel_lut=lut
            )
        self._state = self._hist.init_state()
        self._edges_var = Variable(edges, ("toa",), "ns")
        self._n_banks = n_banks
        self._publish = None
        self._prefetched_publish: dict | None = None

    @property
    def is_sharded(self) -> bool:
        return self._sharded is not None

    def accumulate(self, data: Mapping[str, Any]) -> None:
        for value in data.values():
            if isinstance(value, StagedEvents):
                # Single-chip and mesh-sharded kernels share the contract:
                # stage through the window stream-cache (K jobs place the
                # batch once — onto the default device or onto the mesh's
                # P('data') event sharding) and advance the donated state
                # in one dispatch.
                self._state = self._hist.step_batch(
                    self._state, value.batch, cache=value.cache
                )

    def event_ingest(self, stream: str, staged: StagedEvents):
        """Fused-stepping offer — BOTH kernels (core/job_manager.py).
        Feeds the tick program too (ops/tick.py, ADR 0114/0115): the
        bank reductions in the publish program below then ride the
        step's dispatch, one round trip for the whole window. On the
        mesh, that one dispatch IS the collective step (shard_map body)
        plus the replicated publish reductions — the whole serving mesh
        turns over in one execute + one fetch per tick."""
        from ..core.device_event_cache import EventIngest

        def set_state(state) -> None:
            self._state = state

        return EventIngest(
            key=self._hist.fuse_key + ("",),
            hist=self._hist,
            batch=staged.batch,
            batch_tag="",
            get_state=lambda: self._state,
            set_state=set_state,
        )

    def _publisher(self):
        """Lazy fused publish program, both kernels: bank reductions on
        device, one execute + one packed fetch, window fold included
        (ops/publish.py). ``views_of`` is the kernel-portable seam —
        the single-chip kernel slices its flat state, the mesh kernel
        gathers the window to a replicated value (so the reductions
        below and the packed vector replicate, one fetch serves the
        mesh, and the reduction HLO matches the single-device program:
        the byte-parity contract of ADR 0115)."""
        if self._publish is None:
            from ..ops.publish import PackedPublisher

            def program(state):
                cum, win = self._hist.views_of(state)
                shape = (self._n_banks, self._pixels_per_bank, -1)
                win3 = win.reshape(shape)
                cum3 = cum.reshape(shape)
                outputs = {
                    "bank_spectra_current": win3.sum(axis=1),
                    "bank_spectra_cumulative": cum3.sum(axis=1),
                    "bank_counts_current": win3.sum(axis=(1, 2)),
                    "bank_counts_cumulative": cum3.sum(axis=(1, 2)),
                    "counts_current": win3.sum(),
                    "counts_cumulative": cum3.sum(),
                }
                return outputs, self._hist.fold_window(state)

            self._publish = PackedPublisher(program, name="multibank")
        return self._publish

    def publish_offer(self):
        """Combined-publish offer (ADR 0113), both kernels. Tick-capable
        (ADR 0114/0115): args[0] is the pre-step state and the carry is
        exactly ``(new_state,)``, the make_publish_offer contract the
        tick program's donation layout relies on. Mesh-sharded states
        group by their device SET (ops/publish.publish_device), so a
        combined program never mixes mesh and single-device members."""
        from ..ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publisher(),
            (self._state,),
            fresh_state=self._hist.init_state,
        )

    def finalize(self) -> dict[str, DataArray]:
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publisher()(self._state)
        win_spectra = out["bank_spectra_current"]
        cum_spectra = out["bank_spectra_cumulative"]
        win_counts = out["bank_counts_current"]
        cum_counts = out["bank_counts_cumulative"]
        total_win = out["counts_current"]
        total_cum = out["counts_cumulative"]
        bank_coord = Variable(
            np.arange(self._n_banks), ("bank",), ""
        )
        coords = {"toa": self._edges_var, "bank": bank_coord}
        return {
            "bank_spectra_current": DataArray(
                Variable(win_spectra, ("bank", "toa"), "counts"),
                coords=coords,
                name="bank_spectra_current",
            ),
            "bank_spectra_cumulative": DataArray(
                Variable(cum_spectra, ("bank", "toa"), "counts"),
                coords=coords,
                name="bank_spectra_cumulative",
            ),
            "bank_counts_current": DataArray(
                Variable(win_counts, ("bank",), "counts"),
                coords={"bank": bank_coord},
                name="bank_counts_current",
            ),
            "bank_counts_cumulative": DataArray(
                Variable(cum_counts, ("bank",), "counts"),
                coords={"bank": bank_coord},
                name="bank_counts_cumulative",
            ),
            "counts_current": DataArray(
                Variable(np.asarray(total_win), (), "counts"),
                name="counts_current",
            ),
            "counts_cumulative": DataArray(
                Variable(np.asarray(total_cum), (), "counts"),
                name="counts_cumulative",
            ),
        }

    def clear(self) -> None:
        self._state = self._hist.clear(self._state)
        self._prefetched_publish = None
