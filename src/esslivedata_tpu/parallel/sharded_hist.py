"""Multi-device sharded event histogrammer.

The multi-bank / long-axis scale-out path (BASELINE configs 3-4): screen
rows (detector banks) are sharded over the mesh's ``bank`` axis so a
histogram too large for one chip's HBM splits across chips, and the event
stream is sharded over the ``data`` axis. Parity with the single-device
``EventHistogrammer``: replica LUTs, per-pixel weights, decay, and the
fold semantics (steps touch only the window; the cumulative total folds at
publish rate).

Two exchange strategies merge the data shards (all XLA collectives over
ICI, no NCCL analog):

- ``delta_psum``: every data shard scatters into its own dense copy of
  its bank rows, then ``psum('data')`` merges. Per-step traffic is
  O(rows_per_bank * n_toa) per device regardless of how sparse the batch
  is — fine for small bin spaces (DREAM-size banks), ruinous at LOKI
  scale (1.5M x 100 bins: ~150 MB per shard per step).
- ``event_gather``: ``all_gather('data')`` the *event* shards instead —
  every device then scatters the full batch into its own bank rows, and
  the data-replicated window copies stay identical with no dense
  reduction at all. Per-step traffic is O(n_events * (data-1)/data),
  independent of bin-space size.

``exchange='auto'`` compares the two strategies' ACTUAL per-step wire
bytes — the dense delta each device psums (rows_per_bank x n_toa x
dtype itemsize) against the event bytes each device gathers from the
other data shards (n_events x 8 B x (data-1)/data) — and picks the
cheaper one. ``batch_hint`` (expected events per padded batch; default
the 4M headline batch) supplies the event count the crossover needs at
construction time. Events are also replicated across the ``bank`` axis
by their P('data') sharding, so each bank shard routes gather-free: it
scatters the events landing in its rows and drops the rest via the dump
bin.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.histogram import EventProjection, HistogramState

__all__ = ["ShardedHistogrammer"]

#: Default expected events per padded batch for the 'auto' exchange
#: crossover when the caller gives no hint: the 4M-event headline batch
#: the bench and the LOKI-scale ingest budget are sized around (PERF.md).
_DEFAULT_BATCH_HINT = 1 << 22

#: Wire bytes per event crossing the gather: int32 pixel_id + float32 toa.
_EVENT_WIRE_BYTES = 8


class ShardedHistogrammer:
    """Scatter-add histogrammer with screen rows sharded over ``bank`` and
    events sharded over ``data`` mesh axes.

    The single-device equivalent is ``ops.histogram.EventHistogrammer``;
    this class accepts the same logical inputs (global pixel ids, toa) and
    produces the same global histogram, distributed.
    """

    def __init__(
        self,
        *,
        toa_edges: np.ndarray,
        n_screen: int,
        mesh: Mesh,
        pixel_lut: np.ndarray | None = None,
        pixel_weights: np.ndarray | None = None,
        decay: float | None = None,
        exchange: str = "auto",
        dtype=jnp.float32,
        batch_hint: int | None = None,
    ) -> None:
        if exchange not in ("auto", "delta_psum", "event_gather"):
            raise ValueError(f"Unknown exchange {exchange!r}")
        self._mesh = mesh
        self._n_bank = mesh.shape["bank"]
        self._n_data = mesh.shape["data"]
        if n_screen % self._n_bank:
            raise ValueError(
                f"n_screen={n_screen} must divide over bank axis {self._n_bank}"
            )
        # One projection kernel shared with EventHistogrammer: identical
        # TOA binning (incl. non-uniform edges), LUT/replica routing and
        # weight semantics; only the row window differs per bank shard.
        self._proj = EventProjection(
            toa_edges=toa_edges,
            pixel_lut=pixel_lut,
            pixel_weights=pixel_weights,
            n_screen=n_screen,
        )
        # Weights replicated on every device: gathers stay local. The
        # LUT rides the jitted step as an ARGUMENT (ADR 0105) so a
        # live-geometry rebuild swaps tables without recompiling; it is
        # replicated explicitly below.
        self._has_lut = self._proj.lut_host is not None
        self._replicate = lambda x: jax.device_put(
            x, NamedSharding(mesh, P())
        )
        # place_constants replicates the LUT straight from its HOST copy
        # (one placement, no default-device staging hop) and re-places
        # the weights; the replicated LUT then rides the jitted step as
        # an argument (ADR 0105).
        self._proj.place_constants(self._replicate)
        self._lut_rep = self._proj.lut if self._has_lut else None
        # graft: key-derived=_rows_per_bank,_n_toa pure functions of
        # keyed configuration: fuse_key carries n_bank and the layout
        # digest, which hashes the screen size and the edges these
        # unpack from.
        self._rows_per_bank = n_screen // self._n_bank
        self._n_screen = n_screen
        self._n_toa = self._proj.n_toa
        self._edges = self._proj.edges
        self._decay = decay
        self._dtype = dtype
        self._batch_hint = int(
            _DEFAULT_BATCH_HINT if batch_hint is None else batch_hint
        )
        if exchange == "auto":
            exchange = self._resolve_exchange(
                rows_per_bank=self._rows_per_bank,
                n_toa=self._n_toa,
                n_data=self._n_data,
                dtype=dtype,
                batch_hint=self._batch_hint,
            )
        self._exchange = exchange

        self._state_sharding = NamedSharding(mesh, P("bank", None))
        self._event_sharding = NamedSharding(mesh, P("data"))
        self._scalar_sharding = NamedSharding(mesh, P())
        # The no-decay step's unit update magnitude, staged once: building
        # it per step would dispatch a host->device scalar transfer on
        # every batch (graftlint JGL006).
        self._unit_scale = jax.device_put(
            jnp.asarray(1.0, self._dtype), self._scalar_sharding
        )

        lut_specs = (P(),) if self._has_lut else ()  # replicated LUT arg
        shard = partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(
                P("bank", None),  # window
                *lut_specs,
                P("data"),  # pixel_id
                P("data"),  # toa
                P(),  # inv_scale (replicated lazy-decay magnitude)
            ),
            out_specs=P("bank", None),
            # event_gather keeps the window replicated over 'data' by
            # construction (identical full-batch scatter on every copy
            # after the all_gather); the static varying-mesh-axes check
            # cannot infer that through the scatter, so only that mode
            # disables it — delta_psum keeps the safety net.
            check_vma=(self._exchange != "event_gather"),
        )
        if self._has_lut:

            def _local(win, lut, pid, toa, inv_scale):
                return self._step_local(win, pid, toa, inv_scale, lut=lut)

        else:

            def _local(win, pid, toa, inv_scale):
                return self._step_local(win, pid, toa, inv_scale)

        sharded_step = shard(_local)
        # The traceable (un-jitted) step body: the mesh tick program
        # (parallel/mesh_tick.py, ADR 0115) composes it with the packed
        # publish bodies under ONE outer jit via ``tick_step``.
        self._step_body = sharded_step
        self._decay_body = None
        self._step = jax.jit(sharded_step, donate_argnums=(0,))

        if decay is not None:
            from ..ops.histogram import EventHistogrammer as _EH

            def _step_decay(win, *args):
                # Lazy decay fused into the one jitted program (the
                # single-device kernel does the same inside _advance):
                # scale shrinks, updates grow by 1/scale, renormalize on
                # underflow — no per-batch eager dispatches.
                *rest, scale = args
                scale = scale * decay
                win = sharded_step(win, *rest, 1.0 / scale)
                return jax.lax.cond(
                    scale < _EH._SCALE_FLOOR,
                    lambda w, sc: (w * sc, jnp.ones_like(sc)),
                    lambda w, sc: (w, sc),
                    win,
                    scale,
                )

            self._decay_body = _step_decay
            self._step_decay = jax.jit(_step_decay, donate_argnums=(0,))

        # Fused K-state variant (one dispatch advances K donated states
        # from ONE staged batch; the jit caches one program per K) — the
        # mesh counterpart of EventHistogrammer._step_fused, feeding the
        # fused-stepping layer and the mesh tick program (ADR 0115).
        self._fused = jax.jit(self._tick_step_impl, donate_argnums=(0,))

        norm = partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P("bank", None), P("data")),
            out_specs=P("bank", None),
        )
        self._normalize = jax.jit(norm(self._normalize_local))
        # Fold semantics as in EventHistogrammer: steps touch only the
        # window; the cumulative total is folded at publish rate.
        def _physical(win, scale):
            return win if scale is None else win * scale

        self._clear_window = jax.jit(
            lambda cum, win, scale: (
                cum + _physical(win, scale),
                jnp.zeros_like(win),
            ),
            donate_argnums=(0, 1),
        )
        self._views = jax.jit(
            lambda cum, win, scale: (
                cum + _physical(win, scale),
                _physical(win, scale),
            )
        )

    @staticmethod
    def _resolve_exchange(
        *, rows_per_bank: int, n_toa: int, n_data: int, dtype, batch_hint: int
    ) -> str:
        """The cheaper data-shard merge for this configuration, by ACTUAL
        per-step bytes moved per device.

        - delta_psum: every device reduces a dense copy of its bank rows
          — ``rows_per_bank * n_toa * itemsize`` bytes, batch-size
          independent.
        - event_gather: every device receives the other data shards'
          events — ``n_events * 8 B * (data-1)/data`` bytes, bin-space
          independent (and zero when data == 1: the all_gather is the
          identity, so gather always wins a single-data-shard mesh).

        The old heuristic compared bins against a hard-coded 1<<20
        constant regardless of batch size or dtype, which mispicks on
        both sides of the crossover: a small-batch service on mid-size
        banks paid dense deltas that a cheap gather would beat, and a
        64M-event burst on just-over-threshold banks gathered more
        bytes than the delta it avoided (pinned both ways in
        tests/parallel/sharded_hist_test.py).
        """
        delta_bytes = rows_per_bank * n_toa * np.dtype(dtype).itemsize
        gather_bytes = batch_hint * _EVENT_WIRE_BYTES * (n_data - 1) / n_data
        return "event_gather" if gather_bytes < delta_bytes else "delta_psum"

    # -- local (per-shard) kernels ---------------------------------------
    def _step_local(self, win, pixel_id, toa, inv_scale, lut=None):
        """One shard's step. ``inv_scale`` is the lazy-decay update
        magnitude (1.0 without decay): the dense ``win * decay`` multiply
        the naive formulation would pay per step is folded into the
        scatter updates instead, exactly as in EventHistogrammer."""
        bank = jax.lax.axis_index("bank")
        row0 = bank * self._rows_per_bank
        n_local = self._rows_per_bank * self._n_toa

        if self._exchange == "event_gather":
            # Merge data shards by gathering the (small) event arrays;
            # every data-replicated window copy then applies the identical
            # full-batch scatter — no dense reduction. The dump index
            # (n_local) is out of bounds of the window and dropped.
            pixel_id = jax.lax.all_gather(
                pixel_id, "data", axis=0, tiled=True
            )
            toa = jax.lax.all_gather(toa, "data", axis=0, tiled=True)
            flat, w = self._proj.flat_and_weights(
                pixel_id, toa, row0=row0, n_rows=self._rows_per_bank, lut=lut
            )
            updates = (
                inv_scale if w is None else w.astype(self._dtype) * inv_scale
            )
            return (
                win.reshape(-1)
                .at[flat]
                .add(updates, mode="drop")
                .reshape(win.shape)
            )

        # delta_psum: scatter into a fresh local delta, merge over 'data'.
        flat, w = self._proj.flat_and_weights(
            pixel_id, toa, row0=row0, n_rows=self._rows_per_bank, lut=lut
        )
        updates = inv_scale if w is None else w.astype(self._dtype) * inv_scale
        delta = jnp.zeros((n_local + 1,), dtype=self._dtype)
        delta = delta.at[flat].add(updates, mode="drop")[:n_local]
        delta = delta.reshape(self._rows_per_bank, self._n_toa)
        delta = jax.lax.psum(delta, "data")
        return win + delta

    def _normalize_local(self, hist, monitor_counts):
        # monitor_counts: per-event-shard scalar counts; global total via psum.
        total = jax.lax.psum(jnp.sum(monitor_counts), "data")
        return hist / jnp.maximum(total, 1.0)

    # -- public API -------------------------------------------------------
    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def exchange(self) -> str:
        return self._exchange

    @property
    def shape(self) -> tuple[int, int]:
        return (self._n_screen, self._n_toa)

    def init_state(self) -> HistogramState:
        zeros = jax.device_put(
            jnp.zeros((self._n_screen, self._n_toa), dtype=self._dtype),
            self._state_sharding,
        )
        scale = (
            jax.device_put(
                jnp.ones((), dtype=self._dtype), self._scalar_sharding
            )
            if self._decay is not None
            else None
        )
        return HistogramState(
            folded=zeros, window=jnp.array(zeros), scale=scale
        )

    def _shard_events(self, pixel_id, toa):
        n = pixel_id.shape[0]
        if n % self._n_data:
            raise ValueError(
                f"padded event count {n} must divide over data axis {self._n_data}"
            )
        from ..ops.event_batch import stage_for

        # One hop host->mesh (stage_for): dispatch_safe would commit the
        # batch to the DEFAULT device first and pay a second copy on the
        # resharded placement.
        return (
            stage_for(pixel_id, self._event_sharding),
            stage_for(toa, self._event_sharding),
        )

    @property
    def stage_key(self) -> tuple:
        """Cache key for pre-staged event shards (stage-once, ADR 0110):
        the placement depends only on the event sharding — mesh devices
        and data-axis extent — never on the projection layout, so every
        kernel sharing the mesh shares the staged shards."""
        devices = tuple(int(d.id) for d in self._mesh.devices.flat)
        return ("shard1", devices, self._n_data)

    # -- serving-tier surface (ADR 0110/0114/0115) -------------------------
    # The same duck-typed contract EventHistogrammer exposes, so mesh-
    # backed workflows ride the stage-once cache, the fused-stepping
    # layer, the combined publish and the one-dispatch tick program
    # exactly like single-device ones — the mesh stops being a
    # standalone demo and becomes a serving topology.

    @property
    def n_toa(self) -> int:
        return self._n_toa

    @property
    def n_screen(self) -> int:
        return self._n_screen

    @property
    def toa_edges(self) -> np.ndarray:
        return self._edges

    @property
    def decay(self) -> float | None:
        return self._decay

    @property
    def layout_digest(self) -> str:
        """The projection layout's content fingerprint (the static-
        publish cache token, ADR 0113) — a LUT/edge swap re-keys it."""
        return self._proj.layout_digest

    @property
    def supports_host_flatten(self) -> bool:
        """The mesh kernel projects on DEVICE (each bank shard routes its
        own rows); the host-flatten fast path does not apply."""
        return False

    @property
    def fuse_key(self) -> tuple:
        """Grouping key for fused stepping and tick programs
        (core/job_manager.py): equal keys promise identical staged wire
        AND an identical sharded step program — mesh devices, both axis
        extents, the exchange strategy, accumulation semantics, and the
        projection layout all participate."""
        devices = tuple(int(d.id) for d in self._mesh.devices.flat)
        return (
            "fuse-mesh",
            devices,
            self._n_data,
            self._n_bank,
            self._exchange,
            self._decay,
            np.dtype(self._dtype).str,
            self._proj.layout_digest,
        )

    def tick_staging(
        self,
        batch,
        cache,
        *,
        batch_tag: str = "",
        pool=None,
        device=None,
    ) -> tuple:
        """The staged mesh wire as a flat tuple ``(lut, pixel_id, toa)``
        shaped for ``tick_step``'s trailing arguments (ops/tick.py).

        The (pixel_id, toa) pair is placed onto the P('data') event
        sharding ONCE per window per (stream, tag, mesh) through the
        stream cache — the staged shards are layout-independent, so
        every kernel sharing the mesh shares them; the replicated LUT
        rides as an argument (ADR 0105: swaps stay transfers, never
        retraces). ``pool`` (host-flatten chunking) and ``device``
        (single-device slice placement, parallel/mesh_tick.py) do not
        apply to the mesh wire — the mesh IS the placement.
        """
        del pool, device  # single-device staging knobs; the mesh places
        pid, toa = batch.pixel_id, batch.toa

        def stage():
            return self._shard_events(pid, toa)

        if cache is None:
            staged = stage()
        else:
            staged = cache.get_or_stage(
                (batch_tag,) + self.stage_key, stage
            )
        return (self._lut_rep,) + tuple(staged)

    def _tick_step_impl(self, states, lut, pixel_id, toa):
        # graft: key-derived=_has_lut,_step_body,_decay_body,_unit_scale
        # pure functions of keyed configuration: fuse_key carries the
        # layout digest (which fingerprints the LUT _has_lut reflects),
        # the exchange/decay/dtype the step bodies were compiled from,
        # and the dtype the staged unit scale was built with.
        states = tuple(states)
        lut_args = (lut,) if self._has_lut else ()
        if self._decay is None:
            return tuple(
                HistogramState(
                    folded=s.folded,
                    window=self._step_body(
                        s.window, *lut_args, pixel_id, toa, self._unit_scale
                    ),
                    scale=None,
                )
                for s in states
            )

        def stepped(s: HistogramState) -> HistogramState:
            win, scale = self._decay_body(
                s.window, *lut_args, pixel_id, toa, s.scale
            )
            return HistogramState(folded=s.folded, window=win, scale=scale)

        # Trace-unrolled over the (small, stable-K) states tuple — the
        # same shape as EventHistogrammer's fused impls.
        return tuple(stepped(s) for s in states)

    def tick_step(self, states, *staged):
        """TRACEABLE fused step over ``tick_staging``'s arrays — the tick
        program (ops/tick.py / parallel/mesh_tick.py) composes this with
        the members' packed publish bodies so the collective step and
        the publish reductions ride ONE dispatch. Applies the exact
        per-state program ``step`` runs (same shard_map body, same lazy
        decay protocol), so tick results are identical to separate
        stepping."""
        return self._tick_step_impl(tuple(states), *staged)

    def step_many(
        self, states, batch, *, cache=None, batch_tag=""
    ) -> tuple[HistogramState, ...]:
        """Advance K independent mesh-sharded states from ONE staged
        batch in ONE jitted dispatch (the fused-stepping layer's kernel
        entry, core/job_manager.py). All states are donated."""
        states = tuple(states)
        if not states:
            return ()
        staged = self.tick_staging(batch, cache, batch_tag=batch_tag)
        return self._fused(states, *staged)

    def step_batch(
        self, state: HistogramState, batch, *, cache=None, batch_tag=""
    ) -> HistogramState:
        """Accumulate one staged ``EventBatch`` through the stream cache
        (the workflow-private path's entry; same keys as ``step_many``
        and the tick program, so whichever consumer stages first, the
        rest share the placed shards by reference)."""
        staged = self.tick_staging(batch, cache, batch_tag=batch_tag)
        (new,) = self._fused((state,), *staged)
        return new

    def stage_events(
        self, batch, cache, *, batch_tag: str = "", pool=None
    ) -> None:
        """Warm the window stream-cache with this mesh's staged wire —
        the pipelined ingest's prestage entry (ADR 0111), same contract
        as ``EventHistogrammer.stage_events``: exactly the staging the
        step/tick paths run, so a prestaged window is a guaranteed hit."""
        if cache is None:
            return
        self.tick_staging(batch, cache, batch_tag=batch_tag, pool=pool)

    def views_of(
        self, state: HistogramState
    ) -> tuple[jax.Array, jax.Array]:
        """Traceable (cumulative, window) views, ``[n_screen, n_toa]``,
        REPLICATED over the mesh — the composition surface the packed
        publish programs consume (ops/publish.py).

        The replication constraint is the publish-rate gather that keeps
        readback O(1): downstream reductions run on a replicated value,
        so the packed output vector is replicated by construction and
        one ``device_get`` serves the whole mesh (and the reduction HLO
        matches the single-device program's — the mesh↔single-device
        parity contract, tests/parallel/mesh_tick_test.py). Per-step
        collectives stay O(delta/gather); only the ~1 Hz publish pays
        the window gather."""
        replicated = NamedSharding(self._mesh, P())
        win = self.physical_window(state)
        win = jax.lax.with_sharding_constraint(win, replicated)
        cum = win + jax.lax.with_sharding_constraint(
            state.folded, replicated
        )
        return cum, win

    def physical_window(self, state: HistogramState) -> jax.Array:
        """The window in physical counts (applies the lazy decay scale);
        traceable, sharding-preserving."""
        if state.scale is None:
            return state.window
        return state.window * state.scale

    def fold_window(self, state: HistogramState) -> HistogramState:
        """Traceable window fold (the publish-program composition
        counterpart of ``clear_window``): the cumulative absorbs the
        physical window in place — both leaves keep their P('bank')
        sharding, so the fold is collective-free."""
        with jax.named_scope("fold"):
            return HistogramState(
                folded=state.folded + self.physical_window(state),
                window=jnp.zeros_like(state.window),
                scale=(
                    None
                    if state.scale is None
                    else jnp.ones_like(state.scale)
                ),
            )

    def clear(self, state: HistogramState) -> HistogramState:
        """Zero the full accumulation (run-transition reset), keeping
        every leaf's mesh sharding."""
        return HistogramState(
            folded=jnp.zeros_like(state.folded),
            window=jnp.zeros_like(state.window),
            scale=(
                None if state.scale is None else jnp.ones_like(state.scale)
            ),
        )

    def step(self, state: HistogramState, pixel_id, toa) -> HistogramState:
        """Accumulate one padded global batch (host or pre-staged device
        arrays — see ``stage_events``)."""
        pid, t = self._shard_events(pixel_id, toa)
        lut_args = (self._lut_rep,) if self._has_lut else ()
        if self._decay is None:
            win = self._step(
                state.window, *lut_args, pid, t, self._unit_scale
            )
            return HistogramState(folded=state.folded, window=win)
        win, scale = self._step_decay(
            state.window, *lut_args, pid, t, state.scale
        )
        return HistogramState(folded=state.folded, window=win, scale=scale)

    def swap_projection(self, pixel_lut) -> bool:
        """Replace the pixel LUT on the running mesh without recompiling
        (ADR 0105): the table is a replicated jit argument, so a
        same-shape swap is one broadcast placement. Returns False for
        shape changes or LUT-less configurations (full rebuild); this is
        the sharded kernel's validity gate, mirroring the single-device
        ``EventHistogrammer.swap_projection``."""
        new = np.atleast_2d(np.asarray(pixel_lut, np.int32))
        if (
            self._proj.lut_host is None
            or new.shape != self._proj.lut_host.shape
        ):
            return False
        old = self._proj
        self._proj = EventProjection(
            toa_edges=self._edges,
            pixel_lut=new,
            n_screen=self._n_screen,
        )
        # Carry the replicated device array over: round-tripping it
        # through numpy would block on a d2h copy and lose the mesh
        # placement established in __init__. The new LUT is placed from
        # the host array directly — this is the per-swap live-geometry
        # path, so the default-device staging hop a jnp.asarray would add
        # is paid on every swap, not once. The HOST weights copy rides
        # along so the rebuilt layout_digest — the key every staging/
        # fusion/static-publish cache hangs off (ADR 0110/0113) — still
        # fingerprints the weights.
        self._proj.weights = old.weights
        self._proj._weights_host = old._weights_host
        self._lut_rep = self._replicate(new)
        return True

    def clear_window(self, state: HistogramState) -> HistogramState:
        cum, win = self._clear_window(
            state.folded, state.window, state.scale
        )
        scale = (
            None if state.scale is None else jnp.ones_like(state.scale)
        )
        return HistogramState(folded=cum, window=win, scale=scale)

    def normalized(self, hist: jax.Array, monitor_counts) -> jax.Array:
        """hist / global monitor total — the monitor-normalized I(Q)-style
        output (BASELINE config 4). One-hop staging (stage_for), as in
        ``_shard_events``."""
        from ..ops.event_batch import stage_for

        return self._normalize(
            hist, stage_for(monitor_counts, self._event_sharding, dtype=self._dtype)
        )

    def read(self, state: HistogramState) -> tuple[np.ndarray, np.ndarray]:
        """Host copies of the (cumulative, window) views — same contract as
        ``EventHistogrammer.read`` (applies the lazy decay scale)."""
        cum, win = jax.device_get(
            self._views(state.folded, state.window, state.scale)
        )
        return np.asarray(cum), np.asarray(win)

    # -- state snapshot codec (ADR 0107, multichip shape) ------------------
    def dump_state_arrays(self, state: HistogramState) -> dict[str, np.ndarray]:
        """Gathered host copy of the sharded accumulation: snapshots are
        mesh-layout-independent, so a state dumped on one mesh restores
        onto a service with a different device count."""
        out = {
            "folded": np.asarray(jax.device_get(state.folded)),
            "window": np.asarray(jax.device_get(state.window)),
        }
        if state.scale is not None:
            out["scale"] = np.asarray(jax.device_get(state.scale))
        return out

    def restore_state_arrays(
        self, current: HistogramState, arrays: dict
    ) -> HistogramState | None:
        """Re-place dumped host arrays over THIS mesh's shardings, or
        None if they don't fit (shape-checked, never partially adopts)."""
        folded = np.asarray(arrays.get("folded"))
        window = np.asarray(arrays.get("window"))
        want = (self._n_screen, self._n_toa)
        if folded.shape != want or window.shape != want:
            return None
        has_scale = self._decay is not None
        if has_scale != ("scale" in arrays):
            return None
        return HistogramState(
            folded=jax.device_put(
                jnp.asarray(folded, dtype=self._dtype), self._state_sharding
            ),
            window=jax.device_put(
                jnp.asarray(window, dtype=self._dtype), self._state_sharding
            ),
            scale=(
                jax.device_put(
                    jnp.asarray(arrays["scale"], dtype=self._dtype),
                    self._scalar_sharding,
                )
                if has_scale
                else None
            ),
        )

    # Backwards-compatible alias.
    to_host = read
