"""Device-resident event histogrammer — the framework's hot kernel.

Replaces scipp's C++ ``bin``/``hist``/``group`` CPU path (reference:
preprocessors/to_nxevent_data.py, group_by_pixel.py:17, workflows/
detector_view/providers.py:169) with one jitted scatter-add program:

    events (pixel_id, toa) --gather--> screen bin --scatter_add--> hist HBM

Key properties:

- **State lives in HBM, flat, with a dump bin.** ``HistogramState`` holds a
  (folded, window) pair of flat ``[n_screen*n_toa + 1]`` arrays; the extra
  trailing *dump bin* swallows padded/invalid events, so the scatter needs
  no per-event select. ``step`` donates the state so XLA updates it in
  place — the rolling histogram never round-trips to host (the reference's
  NoCopyAccumulator exists to avoid a 30 ms deepcopy of a 500 MB histogram,
  accumulators.py:96; here the histogram is never copied).
- **One count per step.** XLA's TPU scatter is serial (~9.7 ns a slot
  on a v5e whatever the bin space, padding included), so on a TPU a
  step whose update is a scalar counts on the MXU instead
  (``method="auto"`` -> ``"mxu"``, ADR 0131: the flat indices sorted on
  the chip, then counted block by block in place; 0.8-2.2 ns a slot).
  Events are counted *only* into ``window``; ``clear_window`` folds the
  window into ``folded`` with a dense add (~1.5 ms at LOKI scale, paid at
  the ~1 Hz publish rate, not per batch). The cumulative view is
  ``folded + window``, fused into whatever jitted read consumes it. This
  halves per-step work vs scattering into both accumulators.
- **Grouping disappears.** The reference groups events by pixel once per
  batch (GroupByPixel) so workflows can histogram per-pixel; here grouping
  *is* the scatter — one kernel does project+bin+accumulate.
- Projection (physical pixel -> screen bin, with optional position-noise
  replicas and per-pixel weights) is a precomputed int32 gather table, the
  TPU-native form of GeometricProjector (projectors.py:47-100).
- **Host pre-flattening fast path**: ``flatten_host`` + ``step_flat`` move
  the (multiply-add) bin computation to the host and ship 4 bytes/event
  (one int32 flat index) instead of 8 — host->device bandwidth is the
  other half of the ingest budget, and this halves it.

``toa`` is float32: at the 71 ms ESS frame, float32 resolution is ~8 ns,
three orders of magnitude below realistic bin widths — fine for binning,
and it keeps the kernel off the slow float64 path on TPU.

Measured on TPU v5e (1.5M pixels x 100 TOA bins, 4M-event batches):
two-scatter design 26.8M ev/s -> single-scatter flat design 93M ev/s
device-resident; sort/``indices_are_sorted``/``unique_indices``/dtype
make no measurable difference (the scatter is scalar-core serial either
way), so where the scatter is used it is the simple unsorted one; the
sort that pays is the key sort in front of the MXU count (4 Mi slots
into 6.55 M bins: 3.28 ms against the scatter's 40.56; my chip run,
PR 38, ``ops/pallas_hist2d.py``).
"""

from __future__ import annotations

import logging
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.instruments import SCATTER_UPDATES, VIEW_STEPS, VIEW_WIRES
from ..telemetry.trace import TRACER
from .event_batch import (
    EventBatch,
    device_token,
    dispatch_safe,
    leaf_device_set,
    sanitize_pixel_id,
    ship,
    stage_raw,
)

__all__ = ["EventHistogrammer", "EventProjection", "HistogramState"]

logger = logging.getLogger(__name__)


_FLAT_WIRES = VIEW_WIRES.labels(staging="flat")
_RAW_WIRES = VIEW_WIRES.labels(staging="raw")
_METHODS = ("auto", "scatter", "sort", "mxu", "pallas2d")


def _flatten_args(batch: EventBatch) -> dict[str, int]:
    """The ``flatten`` span's counts: decoded events, and the bucket
    they were padded to (what flatten, H2D and the scatter cost)."""
    return {"events": int(batch.n_valid), "padded": batch.padded_size}


class EventProjection:
    """The traceable event -> flat-bin projection, shared by the single-
    device and sharded histogrammers (one masking kernel, one set of
    semantics: TOA binning incl. non-uniform edges, LUT routing with
    replicas at 1/R weight, per-pixel weights, dump-bin for invalid).

    ``row0``/``n_rows`` select a row window so a bank shard projects into
    its local rows; the dump index is ``n_rows * n_toa``.
    """

    def __init__(
        self,
        *,
        toa_edges: np.ndarray,
        pixel_lut=None,
        pixel_weights=None,
        n_screen: int,
    ) -> None:
        toa_edges = np.asarray(toa_edges, dtype=np.float64)
        if toa_edges.ndim != 1 or toa_edges.size < 2:
            raise ValueError("toa_edges must be 1-D with at least 2 entries")
        if not np.all(np.diff(toa_edges) > 0):
            raise ValueError("toa_edges must be strictly increasing")
        self.edges = toa_edges
        self.n_toa = toa_edges.size - 1
        self.n_screen = int(n_screen)
        widths = np.diff(toa_edges)
        self.uniform = bool(np.allclose(widths, widths[0], rtol=1e-9))
        self.lo = float(toa_edges[0])
        self.hi = float(toa_edges[-1])
        self.inv_width = float(self.n_toa / (self.hi - self.lo))
        self.nonuniform_edges = (
            None if self.uniform else jnp.asarray(toa_edges, dtype=jnp.float32)
        )
        if pixel_lut is not None:
            pixel_lut = np.asarray(pixel_lut, dtype=np.int32)
            if pixel_lut.ndim == 1:
                pixel_lut = pixel_lut[None, :]
            if pixel_lut.ndim != 2:
                raise ValueError("pixel_lut must be 1-D or 2-D")
            if pixel_lut.max(initial=-1) >= n_screen:
                raise ValueError("pixel_lut entries must be < n_screen")
            self.lut_host = pixel_lut
            self._lut_dev = None  # device copy materializes on first use
        else:
            self.lut_host = None
            self._lut_dev = None
        if pixel_weights is not None:
            self._weights_host = np.asarray(pixel_weights, dtype=np.float32)
            self.weights = jnp.asarray(self._weights_host)
        else:
            self._weights_host = None
            self.weights = None
        self._layout_digest: str | None = None

    @property
    def layout_digest(self) -> str:
        """Content fingerprint of everything that determines where an
        event lands: bin edges, screen size, LUT and weight tables. Two
        projections with equal digests flatten identically, so staged
        flat/partitioned arrays may be shared across their consumers
        (core/device_event_cache.py keys on this). Computed lazily and
        cached per projection object — a live LUT swap builds a new
        projection, so the swapped layout re-fingerprints by
        construction (the cache-invalidation rule of ADR 0110)."""
        if self._layout_digest is None:
            import hashlib

            h = hashlib.sha1()
            h.update(self.edges.tobytes())
            h.update(np.int64(self.n_screen).tobytes())
            if self.lut_host is not None:
                h.update(np.ascontiguousarray(self.lut_host).tobytes())
            if self._weights_host is not None:
                h.update(np.ascontiguousarray(self._weights_host).tobytes())
            self._layout_digest = h.hexdigest()
        return self._layout_digest

    @property
    def lut(self):
        """Device LUT, materialized lazily: host-flatten configurations
        never read it, so swaps/construction stay host-only there."""
        if self._lut_dev is None and self.lut_host is not None:
            self._lut_dev = jnp.asarray(self.lut_host)
        return self._lut_dev

    def place_constants(self, device_put) -> None:
        """Re-place the LUT/weights (e.g. replicated over a mesh).

        Places from the HOST copy: going through the ``lut`` property
        would first materialize the table on the default device and pay
        an extra device->device copy on the re-placement (the same
        double-staging hazard fixed in ShardedHistogrammer._shard_events).
        """
        if self.lut_host is not None:
            self._lut_dev = device_put(self.lut_host)
        if self.weights is not None:
            self.weights = device_put(self.weights)

    def toa_bin(self, toa: jax.Array) -> tuple[jax.Array, jax.Array]:
        if self.uniform:
            tb = jnp.floor((toa - self.lo) * self.inv_width).astype(jnp.int32)
            t_ok = (toa >= self.lo) & (toa < self.hi)
        else:
            tb = (
                jnp.searchsorted(
                    self.nonuniform_edges, toa, side="right"
                ).astype(jnp.int32)
                - 1
            )
            t_ok = (tb >= 0) & (tb < self.n_toa)
        return jnp.clip(tb, 0, self.n_toa - 1), t_ok

    def flat_and_weights(
        self,
        pixel_id: jax.Array,
        toa: jax.Array,
        *,
        row0=0,
        n_rows: int | None = None,
        lut=None,
    ) -> tuple[jax.Array, jax.Array | None]:
        """Flat local bin index per event (dump = n_rows*n_toa = dropped)
        and the event weight (None = unit weights); replicas folded in:
        without per-pixel weights a replica LUT's weight is the scalar
        1/R, with them the per-slot array of weight / R.

        ``lut`` optionally overrides the captured device LUT so callers
        can thread it through jit as an ARGUMENT (ADR 0105: live
        LUT swaps without recompiles)."""
        n_rows = self.n_screen if n_rows is None else n_rows
        n_local = n_rows * self.n_toa
        tb, t_ok = self.toa_bin(toa)
        lut = lut if lut is not None else self.lut

        if self.weights is not None:
            n_pix = self.weights.shape[0]
            p_in = (pixel_id >= 0) & (pixel_id < n_pix)
            w = jnp.where(
                p_in, self.weights[jnp.clip(pixel_id, 0, n_pix - 1)], 0.0
            )
        else:
            w = None

        if lut is not None:
            n_rep, n_pix = lut.shape
            p_ok = (pixel_id >= 0) & (pixel_id < n_pix)
            pid = jnp.clip(pixel_id, 0, n_pix - 1)
            with jax.named_scope("replica_gather"):
                screen = lut[:, pid]  # [R, N]
            local_row = screen - row0
            ok = (
                p_ok[None, :]
                & t_ok[None, :]
                & (screen >= 0)
                & (local_row >= 0)
                & (local_row < n_rows)
            )
            flat = jnp.where(
                ok, local_row * self.n_toa + tb[None, :], n_local
            ).reshape(-1)
            if w is None and n_rep > 1:
                # one weight for every slot: a scalar, so that the count
                # scales it once and no per-slot float array is built
                w = jnp.asarray(1.0 / n_rep, dtype=jnp.float32)
            elif w is not None:
                w = jnp.broadcast_to(w[None, :] / n_rep, screen.shape).reshape(-1)
        else:
            local_row = pixel_id - row0
            ok = (
                (pixel_id >= 0)
                & (pixel_id < self.n_screen)
                & t_ok
                & (local_row >= 0)
                & (local_row < n_rows)
            )
            flat = jnp.where(ok, local_row * self.n_toa + tb, n_local)
            if w is not None:
                w = jnp.where(ok, w, 0.0)
        return flat, w


class HistogramState(NamedTuple):
    """Device-resident accumulator pair, flat ``[n_screen*n_toa + 1]``
    (``method='pallas2d'`` pads further, to whole bin blocks — the owning
    histogrammer knows the layout; views always slice padding away).

    ``window`` receives the scatters; ``folded`` holds counts folded out of
    the window by ``clear_window``. The trailing element of each array is
    the dump bin for padded/invalid events and is excluded from all views.
    The *cumulative* histogram is ``folded + window`` (see
    ``EventHistogrammer.read`` / ``views``).

    ``scale`` (decay mode only, else None): the physical rolling window is
    ``window * scale``. Instead of multiplying the dense window by the
    decay factor every step (a full HBM read+write of the state per batch
    — measured 50x slower than the scatter at LOKI scale), the decay is
    folded into the *scatter updates*: each step shrinks ``scale`` by the
    decay factor and scatters ``1/scale``-sized updates, so older counts
    decay relatively without ever being touched. ``scale`` is renormalized
    back to 1 (one dense multiply) only when it underflows toward float32
    tiny values — every ~500 steps at decay=0.95.
    """

    folded: jax.Array
    window: jax.Array
    scale: jax.Array | None = None


class EventHistogrammer:
    """Configurable jitted histogrammer over screen x TOA bins.

    Parameters
    ----------
    toa_edges:
        Bin edges along the time-of-arrival (or wavelength) axis. Uniform
        edges compile to a multiply+floor; non-uniform to a searchsorted.
    n_screen:
        Number of screen bins (rows). 1 for plain 1-D monitors.
    pixel_lut:
        Optional int32 map raw pixel_id -> screen bin, shape [n_pixel] or
        [n_replica, n_pixel] for position-noise replicas (each replica
        contributes weight 1/R). Entries < 0 drop the event. Without a LUT,
        pixel_id is used directly as the screen bin.
    pixel_weights:
        Optional float32 per-pixel weight, applied by raw pixel_id
        (reference: detector_view pixel weighting, providers.py:98).
    decay:
        Optional per-step multiplier for the window accumulator: the
        on-device exponential-decay rolling window. None = plain window.
        With decay, the ``folded + window`` cumulative view intentionally
        reflects the decayed window (the decayed EMA is the product; a
        raw-count cumulative alongside it would need a second scatter).
    method:
        How a batch's flat bin indices are counted into the window.
        'auto' resolves at construction from what the histogrammer
        observes: 'mxu' on a TPU backend for every configuration whose
        update is a scalar (counts, a replica LUT's 1/R, the decay's
        1/scale), whatever the bin space; 'scatter' for per-pixel
        weights (a float per event) and on other backends.
        'mxu' counts on the chip without XLA's serial scatter
        (ops/pallas_hist2d.py, ADR 0131): the indices are sorted by key
        alone, listed as (chunk, bin block) work items, and each item's
        counts are the factorised one-hot on the MXU, scaled once by
        the update and added in place; dropped slots sort past every
        block and cost no item. A one-block bin space skips the sort.
        The dump slot is never counted. Per-event weights fall back to
        the scatter.
        'scatter' (the default) is XLA's scatter-add: serial on a TPU's
        scalar core (6-10 ns a slot, padding included, whatever the bin
        space; PERF.md section 6), and the only kernel per-event weights
        take. 'sort' sorts before it (no faster on a v5e).
        'pallas2d' is 'mxu''s kernel over a partition made on the host
        (native ``ld_partition`` or numpy) and shipped as the wire: the
        flat-index fast path (``step_flat`` / ``step_batch``) feeds it;
        it requires a host-flattenable configuration (no per-pixel
        weights, no replica LUTs). 'mxu' and 'pallas2d' pad the state
        arrays to whole blocks; all views slice the padding (and the
        dump bin) away.
    """

    def __init__(
        self,
        *,
        toa_edges: np.ndarray,
        n_screen: int = 1,
        pixel_lut: np.ndarray | None = None,
        pixel_weights: np.ndarray | None = None,
        decay: float | None = None,
        method: str = "scatter",
        dtype=jnp.float32,
        pallas2d_budget: int | None = None,
        pallas2d_chunk: int | None = None,
        pallas2d_precision: str = "bf16",
    ) -> None:
        if method not in _METHODS:
            raise ValueError(f"Unknown method {method!r}")
        self._proj = EventProjection(
            toa_edges=toa_edges,
            pixel_lut=pixel_lut,
            pixel_weights=pixel_weights,
            n_screen=n_screen,
        )
        if method == "auto":
            # Resolve at construction, from what is observed: the MXU
            # count wherever the update is a scalar on a TPU (and the
            # padded state stays inside int32 keys); per-pixel weights
            # and other backends, where the kernel would run in
            # interpret mode, keep XLA's scatter.
            from .pallas_hist2d import count_layout

            method = (
                "mxu"
                if (
                    pixel_weights is None
                    and count_layout(self._proj.n_screen * self._proj.n_toa)[1]
                    < np.iinfo(np.int32).max
                    and jax.default_backend() == "tpu"
                )
                else "scatter"
            )
        # Both wire kinds and every kernel have a sample from the first
        # histogrammer on, so that a share of any reads 0, not "no
        # sample".
        _FLAT_WIRES.inc(0.0)
        _RAW_WIRES.inc(0.0)
        for kind in ("mxu", "scatter"):
            VIEW_STEPS.inc(0.0, kernel=kind)
        # The kernel of the unpartitioned paths (``_counter``); a batch
        # that pallas2d partitions on the host counts on the MXU.
        self._kernel = (
            "mxu" if method == "mxu" and pixel_weights is None else "scatter"
        )
        self._edges = self._proj.edges
        self._edges_f32 = self._edges.astype(np.float32)
        # graft: key-derived=_n_toa,_n_screen,_n_bins pure functions of
        # the projection layout: layout_digest (in every key tuple)
        # hashes the edges and LUT geometry these unpack from, so they
        # cannot change without re-keying staging and fusion.
        self._n_toa = self._proj.n_toa
        self._n_screen = self._proj.n_screen
        self._n_bins = self._n_screen * self._n_toa
        self._dtype = dtype
        self._method = method
        self._decay = decay
        self._n_state = self._n_bins + 1
        self._ppb_shift = None
        if method == "mxu":
            from .pallas_hist2d import count_layout

            self._bpb, self._n_state = count_layout(self._n_bins)
        if method == "pallas2d":
            from .pallas_hist2d import DEFAULT_BPB, padded_bins

            if not self.supports_host_flatten:
                raise ValueError(
                    "method='pallas2d' requires a host-flattenable "
                    "configuration (no per-pixel weights or replica "
                    "LUTs): the tiled kernel consumes host-partitioned "
                    "flat indices"
                )
            # Prefer pixel-aligned blocks (bpb = 2**k * n_toa): the fused
            # native ingest derives the block from the screen pixel with
            # one shift. Falls back to generic power-of-two blocks when
            # no 2**k * n_toa fits the VMEM budget as a lane multiple.
            # ``pallas2d_budget``/``pallas2d_chunk`` are hardware-tuning
            # knobs (bench.py --pallas2d-budget/--pallas2d-chunk): block
            # size trades MXU FLOPs/event against partition padding and
            # grid-step count.
            from .pallas_hist2d import DEFAULT_CHUNK

            budget = pallas2d_budget or DEFAULT_BPB
            self._p2_chunk = (
                DEFAULT_CHUNK if pallas2d_chunk is None else pallas2d_chunk
            )
            if self._p2_chunk <= 0 or self._p2_chunk % 128:
                raise ValueError(
                    "pallas2d_chunk must be a positive multiple of 128 "
                    "(the event-row block's lane dimension)"
                )
            if pallas2d_precision not in ("bf16", "int8"):
                raise ValueError(
                    "pallas2d_precision must be 'bf16' or 'int8'"
                )
            self._p2_precision = pallas2d_precision
            for k in range(16, -1, -1):
                bpb = (1 << k) * self._n_toa
                if bpb <= budget and bpb % 128 == 0:
                    self._ppb_shift = k
                    self._bpb = bpb
                    break
            if self._ppb_shift is None:
                self._bpb = budget
                if self._bpb % 128 or (self._bpb & (self._bpb - 1)):
                    raise ValueError(
                        "pallas2d_budget must be a power-of-two multiple "
                        "of 128 when no pixel-aligned block fits"
                    )
            self._n_state = padded_bins(self._n_bins + 1, self._bpb)
            # Compact uint16 wire whenever block-local offsets fit: same
            # partition, half the host->device bytes per event.
            self._p2_compact = self._bpb <= 0xFFFF
            self._step_part = jax.jit(
                self._step_part_impl, donate_argnums=(0,)
            )
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))
        self._step_flat = jax.jit(self._step_flat_impl, donate_argnums=(0,))
        self._clear_window = jax.jit(self._clear_window_impl, donate_argnums=(0,))
        self._clear_all = jax.jit(self._clear_all_impl, donate_argnums=(0,))
        self._views = jax.jit(self._views_impl)
        # Fused K-job variants (one dispatch advances K independent donated
        # states from ONE staged batch; jit caches one program per K). The
        # per-state ops match the single-state programs exactly, so fused
        # and private stepping are bit-identical (asserted in tests).
        self._step_fused = jax.jit(self._step_fused_impl, donate_argnums=(0,))
        self._step_flat_fused = jax.jit(
            self._step_flat_fused_impl, donate_argnums=(0,)
        )
        if method == "pallas2d":
            self._step_part_fused = jax.jit(
                self._step_part_fused_impl, donate_argnums=(0,)
            )

    # -- properties -------------------------------------------------------
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
    def shape(self) -> tuple[int, int]:
        return (self._n_screen, self._n_toa)

    @property
    def layout_digest(self) -> str:
        """The projection layout's content fingerprint (see
        ``EventProjection.layout_digest``) — the static-publish cache
        token (ops/publish.py, ADR 0113): a LUT/edge swap re-keys it."""
        return self._proj.layout_digest

    # -- state ------------------------------------------------------------
    def init_state(self, device=None) -> HistogramState:
        zeros = jnp.zeros(self._n_state, dtype=self._dtype)
        if device is not None:
            zeros = jax.device_put(zeros, device)
        scale = (
            jnp.ones((), dtype=self._dtype) if self._decay is not None else None
        )
        return HistogramState(folded=zeros, window=jnp.array(zeros), scale=scale)

    # -- kernel -----------------------------------------------------------
    # Renormalize the lazy decay scale well before float32 underflow
    # (tiny floats start at ~1e-38; 1e-12 leaves update magnitudes 1/scale
    # no larger than 1e12, far inside float32 range).
    _SCALE_FLOOR = 1e-12

    def _scatter_into(
        self, window: jax.Array, flat: jax.Array, updates
    ) -> jax.Array:
        sorted_ = self._method == "sort"
        if sorted_:
            if isinstance(updates, jax.Array) and updates.ndim:
                order = jnp.argsort(flat)
                flat, updates = flat[order], updates[order]
            else:
                flat = jnp.sort(flat)
        # mode='drop' (not promise_in_bounds): indices are in-bounds by
        # construction on the device path, but step_flat trusts host/native
        # flattening — drop keeps a buggy producer memory-safe at zero
        # measured cost.
        return window.at[flat].add(
            updates, mode="drop", indices_are_sorted=sorted_
        )

    def _counter(self, flat: jax.Array):
        """``(window, updates) -> window`` for one batch of flat indices.
        The MXU count partitions the batch on the chip here, once, so
        that the K states of a fused step share its sort and work
        items."""
        if self._method == "mxu" and self._proj.weights is None:
            from .pallas_hist2d import count_partitioned, partition_on_device

            part = partition_on_device(flat, self._n_bins, bpb=self._bpb)
            return lambda win, upd: count_partitioned(
                win, *part, bpb=self._bpb, upd=upd
            )
        return lambda win, upd: self._scatter_into(win, flat, upd)

    def _advance(
        self, state: HistogramState, flat: jax.Array, w
    ) -> HistogramState:
        """One count into the window; decay handled via the lazy scale."""
        return self._advance_core(state, self._counter(flat), w)

    def _advance_core(
        self, state: HistogramState, apply_updates, w
    ) -> HistogramState:
        """The ONE copy of the lazy-decay protocol, shared by every
        kernel variant: ``apply_updates(window, updates) -> window``
        accumulates the batch (scatter or pallas2d), ``updates`` being a
        scalar magnitude or a per-event weight array scaled by
        ``1/scale`` in decay mode."""
        if self._decay is None:
            updates = (
                jnp.asarray(1.0, self._dtype) if w is None else w.astype(self._dtype)
            )
            return HistogramState(
                folded=state.folded,
                window=apply_updates(state.window, updates),
                scale=None,
            )
        scale = state.scale * self._decay
        inv = 1.0 / scale
        updates = inv if w is None else w.astype(self._dtype) * inv
        window = apply_updates(state.window, updates)
        window, scale = jax.lax.cond(
            scale < self._SCALE_FLOOR,
            lambda win, s: (win * s, jnp.ones_like(s)),
            lambda win, s: (win, s),
            window,
            scale,
        )
        return HistogramState(folded=state.folded, window=window, scale=scale)

    def _step_impl(
        self,
        state: HistogramState,
        lut: jax.Array | None,
        pixel_id: jax.Array,
        toa: jax.Array,
    ) -> HistogramState:
        # The LUT rides as an ARGUMENT (ADR 0105, same mechanism as the
        # Q-table kernels): a live-geometry swap is one device transfer,
        # never a retrace. ``None`` (LUT-less configurations) is an empty
        # pytree leaf — its cache entry projects without a LUT.
        flat, w = self._proj.flat_and_weights(pixel_id, toa, lut=lut)
        return self._advance(state, flat, w)

    def _step_flat_impl(
        self, state: HistogramState, flat: jax.Array
    ) -> HistogramState:
        # Externally produced indices: scatter mode='drop' bounds-checks
        # AFTER one negative wrap, so -1 is dropped but -2..-n_bins would
        # wrap into real bins. Route all negatives to the dump bin first.
        # (pallas2d state is block-padded: indices in the padding tail
        # would be memory-safe but miscounted as real bins — dump them.)
        flat = jnp.where(
            (flat < 0) | (flat > self._n_bins), self._n_bins, flat
        )
        return self._advance(state, flat, None)

    def _step_part_impl(
        self, state: HistogramState, events: jax.Array, chunk_map: jax.Array
    ) -> HistogramState:
        """pallas2d step over host-partitioned events (ops/pallas_hist2d)."""
        from .pallas_hist2d import scatter_add_pallas2d

        return self._advance_core(
            state,
            lambda win, upd: scatter_add_pallas2d(
                win,
                events,
                chunk_map,
                bpb=self._bpb,
                upd=upd,
                precision=self._p2_precision,
            ),
            None,
        )

    # -- fused K-job variants (one dispatch, K donated states) -------------
    # Each fused impl applies the SAME per-state program as its single
    # counterpart, trace-unrolled over the states tuple: the shared
    # routing/one-hot work folds into one program, the K scatters ride
    # one dispatch instead of K, and per-state float op order is
    # unchanged — fused
    # results are bit-identical to K private steps.
    def _step_fused_impl(self, states, lut, pixel_id, toa):
        flat, w = self._proj.flat_and_weights(pixel_id, toa, lut=lut)
        count = self._counter(flat)
        return tuple(self._advance_core(s, count, w) for s in states)

    def _step_flat_fused_impl(self, states, flat):
        flat = jnp.where(
            (flat < 0) | (flat > self._n_bins), self._n_bins, flat
        )
        count = self._counter(flat)
        return tuple(self._advance_core(s, count, None) for s in states)

    def _step_part_fused_impl(self, states, events, chunk_map):
        from .pallas_hist2d import scatter_add_pallas2d

        return tuple(
            self._advance_core(
                s,
                lambda win, upd: scatter_add_pallas2d(
                    win,
                    events,
                    chunk_map,
                    bpb=self._bpb,
                    upd=upd,
                    precision=self._p2_precision,
                ),
                None,
            )
            for s in states
        )

    def physical_window(self, state: HistogramState) -> jax.Array:
        """The window in physical counts, flat incl. dump bin — applies the
        lazy decay scale. Traceable: workflows compose this inside their
        own jitted finalize programs instead of re-deriving state layout."""
        if state.scale is None:
            return state.window
        return state.window * state.scale

    def swap_projection(self, pixel_lut) -> bool:
        """Replace the pixel LUT without touching the compiled hot path.

        Returns True when the new LUT is drop-in compatible (same shape
        after replica normalization): the host-flatten fast path
        (``step_flat``) reads the LUT on the host per batch, so the swap
        costs nothing on device, and the device path threads the LUT
        through jit as an argument (ADR 0105) so it keeps its compiled
        step too. Returns False — caller does a full rebuild —
        for shape changes or LUT-less configurations — each kernel owns
        its own gate (the sharded twin mirrors this one).
        """
        old = self._proj
        new_lut = np.atleast_2d(np.asarray(pixel_lut))
        old_lut = old.lut_host
        if old_lut is None or new_lut.shape != old_lut.shape:
            return False
        self._proj = EventProjection(
            toa_edges=old.edges,
            pixel_lut=new_lut,
            pixel_weights=None,  # carried over below
            n_screen=old.n_screen,
        )
        # Carry the DEVICE weights array over directly: re-threading it
        # through __init__ would round-trip device->host->device on every
        # swap (the sharded twin documents the same hazard). The host
        # copy rides along so the layout fingerprint still covers it.
        self._proj.weights = old.weights
        self._proj._weights_host = old._weights_host
        # No re-jit: the device path takes the LUT as a jit argument
        # (ADR 0105), so the swap costs one lazy device transfer on the
        # next step — never a retrace, even for per-batch geometry flaps.
        # TOA binning constants captured at trace time are unchanged by
        # construction (same edges object, shape-gated LUT).
        return True

    def fold_window(self, state: HistogramState) -> HistogramState:
        """Traceable window fold: the cumulative absorbs the window, which
        zeroes. Workflows compose this into their fused publish programs
        (ops/publish.py) so summaries and the fold ride one execute call;
        ``clear_window`` is the standalone jitted equivalent."""
        with jax.named_scope("fold"):
            return self._clear_window_impl(state)

    # -- state snapshot codec (core/state_snapshot.py, ADR 0107) -----------
    # The ONE place that knows how a HistogramState serializes; workflow
    # dump_state/restore_state implementations layer their extras on top
    # instead of hand-rolling (and drifting) per-workflow copies.
    @staticmethod
    def dump_state_arrays(state: HistogramState) -> dict[str, np.ndarray]:
        out = {
            "folded": np.asarray(state.folded),
            "window": np.asarray(state.window),
        }
        if state.scale is not None:
            out["scale"] = np.asarray(state.scale)
        return out

    def _fit_flat(self, arr: np.ndarray, want: int) -> np.ndarray | None:
        """Adapt a flat accumulator across block-padding layouts.

        The scatter layout is ``[n_bins + 1]``; pallas2d pads to whole
        blocks with a zero tail. Under the snapshot fingerprint gate
        (same workflow config = same logical bins) the layouts differ
        only by that padding, so: an array covering the logical prefix
        (``n_bins + 1``) adapts — a longer tail must be all zeros
        (counts there would mean it was not padding), a shorter array
        is rejected (wrong configuration, not a layout).
        """
        n = arr.shape[0]
        logical = self._n_bins + 1
        if n == want:
            return arr
        if n < logical or np.any(arr[logical:]):
            return None
        if n >= want:
            return arr[:want]
        out = np.zeros(want, dtype=arr.dtype)
        out[:n] = arr
        return out

    def restore_state_arrays(
        self, current: HistogramState, arrays: dict
    ) -> HistogramState | None:
        """A restored state shaped like ``current``, or None if the
        arrays don't fit (never partially adopts). Arrays from the other
        histogram method's layout (block padding, ``method='pallas2d'``)
        adapt — an operator switching kernels between runs must not lose
        a recovery snapshot."""
        folded = np.asarray(arrays.get("folded"))
        window = np.asarray(arrays.get("window"))
        want_shape = current.folded.shape
        if folded.ndim != 1 or window.ndim != 1 or len(want_shape) != 1:
            return None
        want = want_shape[0]
        folded = self._fit_flat(folded, want)
        window = self._fit_flat(window, want)
        if folded is None or window is None:
            return None
        has_scale = current.scale is not None
        if has_scale != ("scale" in arrays):
            return None
        if has_scale and np.asarray(arrays["scale"]).shape != (
            current.scale.shape
        ):
            return None
        return HistogramState(
            folded=jnp.asarray(folded, dtype=current.folded.dtype),
            window=jnp.asarray(window, dtype=current.window.dtype),
            scale=(
                jnp.asarray(arrays["scale"], dtype=current.scale.dtype)
                if has_scale
                else None
            ),
        )

    def views_of(self, state: HistogramState) -> tuple[jax.Array, jax.Array]:
        """Traceable (cumulative, window) views, ``[n_screen, n_toa]`` —
        the composition counterpart of the jitted ``views``."""
        return self._views_impl(state)

    def _clear_window_impl(self, state: HistogramState) -> HistogramState:
        return HistogramState(
            folded=state.folded + self.physical_window(state),
            window=jnp.zeros_like(state.window),
            scale=None if state.scale is None else jnp.ones_like(state.scale),
        )

    @staticmethod
    def _clear_all_impl(state: HistogramState) -> HistogramState:
        return HistogramState(
            folded=jnp.zeros_like(state.folded),
            window=jnp.zeros_like(state.window),
            scale=None if state.scale is None else jnp.ones_like(state.scale),
        )

    def _views_impl(
        self, state: HistogramState
    ) -> tuple[jax.Array, jax.Array]:
        shape = (self._n_screen, self._n_toa)
        win = self.physical_window(state)[: self._n_bins].reshape(shape)
        cum = win + state.folded[: self._n_bins].reshape(shape)
        return cum, win

    # -- stage-once staging (core/device_event_cache.py) -------------------
    @property
    def stage_key(self) -> tuple:
        """Cache key for this configuration's host-flattened wire: flat
        indices depend only on the projection layout, so any two
        histogrammers with equal keys may share one staged array."""
        return ("flat", self._proj.layout_digest)

    @property
    def partition_key(self) -> tuple:
        """Cache key for the pallas2d partitioned wire: the partition
        additionally depends on the block/chunk geometry and compaction."""
        return (
            "part",
            self._proj.layout_digest,
            self._bpb,
            self._p2_chunk,
            self._p2_compact,
        )

    @property
    def fuse_key(self) -> tuple:
        """Grouping key for fused stepping (core/job_manager.py): two
        histogrammers with equal fuse keys run the same step program
        over the same staged input, so their jobs' states may advance in
        one fused dispatch. Strictly finer than the stage keys — it adds
        the accumulation semantics (method, decay, dtype, state size)."""
        base = (
            "fuse1",
            self._method,
            self._decay,
            np.dtype(self._dtype).str,
            self._proj.layout_digest,
            self._n_state,
        )
        if self._method == "pallas2d":
            base += (self._bpb, self._p2_chunk, self._p2_compact,
                     self._p2_precision)
        return base

    def _staged_flat(
        self, batch: EventBatch, cache, tag: str, pool=None, device=None
    ):
        """Host-flattened indices staged for dispatch — once per window
        per (stream, tag, layout, slice) when a cache slot is provided.
        ``pool`` (pipelined prestage only) chunks the flatten across a
        thread pool; the result is bit-identical either way. ``device``
        (mesh-slice placement, parallel/mesh_tick.py) commits the wire
        to that slice and keys the cache by it, so each batch stages
        once per slice. A miss records the ``flatten`` and ``h2d`` leaf
        spans (ADR 0116); a hit records nothing."""

        def stage():
            _FLAT_WIRES.inc()
            with TRACER.span("flatten", args=_flatten_args(batch)):
                if pool is not None:
                    flat = self.flatten_host_chunked(
                        batch.pixel_id, batch.toa, pool
                    )
                else:
                    flat = self.flatten_host(batch.pixel_id, batch.toa)
            return ship(batch, (flat,), device, kept=False)[0]

        if cache is None:
            return stage()
        return cache.get_or_stage(
            (tag,) + self.stage_key + (device_token(device),), stage
        )

    def _staged_partition(
        self, batch: EventBatch, cache, tag: str, device=None
    ):
        """Block-partitioned (events, chunk_map) staged for the pallas2d
        kernel — once per window per (stream, tag, partition layout,
        slice); the same two leaf spans on a miss."""

        def stage():
            _FLAT_WIRES.inc()
            with TRACER.span("flatten", args=_flatten_args(batch)):
                wire = self.flatten_partition_host(batch.pixel_id, batch.toa)
            return ship(batch, wire, device, kept=False)

        if cache is None:
            return stage()
        return cache.get_or_stage(
            (tag,) + self.partition_key + (device_token(device),), stage
        )

    @staticmethod
    def _staged_raw(batch: EventBatch, cache, tag: str, device=None):
        """The raw (pixel id, TOA) pair staged for the device path
        (``stage_raw``: 8 B an event, shared by (stream, tag) whatever
        the layout), a miss counted as this family's ``raw`` wire."""
        return stage_raw(
            batch, cache, tag, device=device, on_miss=_RAW_WIRES.inc
        )

    def _count_scatter(
        self, slots: int, *, partitioned: bool = False
    ) -> None:
        """One step dispatch: ``slots`` staged slots, each counted
        once per LUT replica, and one step of the kernel it runs: the
        MXU's for a batch partitioned on the host, else that of the
        unpartitioned path."""
        lut = self._proj.lut_host
        SCATTER_UPDATES.inc(slots * (1 if lut is None else lut.shape[0]))
        VIEW_STEPS.inc(kernel="mxu" if partitioned else self._kernel)

    def stage_events(
        self,
        batch: EventBatch,
        cache,
        *,
        batch_tag: str = "",
        pool=None,
        device=None,
    ) -> None:
        """Warm the window stream-cache with this configuration's wire.

        The pipelined ingest's prestage entry (core/ingest_pipeline.py,
        ADR 0111): runs exactly the staging — same keys, same functions —
        that ``step_batch``/``step_many`` would run at step time, so the
        host flatten/partition and the async device transfer happen on a
        stage worker while the previous window's step executes. Step-time
        consumers then hit the warm slot. ``pool`` optionally chunks the
        flat-wire flatten across a thread pool (the native shim releases
        the GIL per chunk); the pallas2d fused flatten+partition always
        runs as the single native pass the step path would take, keeping
        the staged value identical across paths. A miss here is never an
        error amplifier: a staging failure poisons nothing — the slot
        drops the entry and step time retries privately.
        """
        if cache is None:
            return
        if self._method == "pallas2d":
            self._staged_partition(batch, cache, batch_tag, device=device)
        elif self.supports_host_flatten:
            self._staged_flat(
                batch, cache, batch_tag, pool=pool, device=device
            )
        else:
            self._staged_raw(batch, cache, batch_tag, device=device)

    #: Below this many events per chunk the pool dispatch overhead beats
    #: the parallel flatten; chunks are sized to keep every worker fed.
    _FLATTEN_CHUNK_MIN = 1 << 17

    def flatten_host_chunked(
        self, pixel_id: np.ndarray, toa: np.ndarray, pool
    ) -> np.ndarray:
        """``flatten_host`` split over a thread pool in contiguous
        chunks, writing each chunk's result straight into one output
        array. The projection is elementwise, so the result is
        bit-identical to the unchunked pass; the native shim (and
        numpy's ufunc cores) release the GIL, so chunks genuinely
        overlap on multicore ingest hosts."""
        n = int(np.asarray(pixel_id).shape[0])
        workers = getattr(pool, "_max_workers", 1) if pool is not None else 1
        if workers < 2 or n < 2 * self._FLATTEN_CHUNK_MIN:
            return self.flatten_host(pixel_id, toa)
        n_chunks = min(workers, -(-n // self._FLATTEN_CHUNK_MIN))
        bounds = np.linspace(0, n, n_chunks + 1, dtype=np.int64)
        out = np.empty(n, dtype=np.int32)

        def run(lo: int, hi: int) -> None:
            self.flatten_host(pixel_id[lo:hi], toa[lo:hi], out=out[lo:hi])

        futures = [
            pool.submit(run, int(lo), int(hi))
            for lo, hi in zip(bounds[:-1], bounds[1:])
            if hi > lo
        ]
        for future in futures:
            future.result()
        return out

    # -- public API -------------------------------------------------------
    def step(self, state: HistogramState, batch: EventBatch) -> HistogramState:
        """Accumulate one padded batch. Donates ``state``: the caller's
        handle is invalidated, use the returned state."""
        self._count_scatter(batch.padded_size)
        return self._step(
            state,
            self._proj.lut,
            dispatch_safe(batch.pixel_id),
            dispatch_safe(batch.toa),
        )

    def step_arrays(
        self, state: HistogramState, pixel_id, toa
    ) -> HistogramState:
        """Accumulate from already-device-resident (or padded host) arrays."""
        if isinstance(pixel_id, np.ndarray):
            # Host arrays may carry wire dtypes (int64 ev44 ids); device
            # arrays are already int32 by construction.
            pixel_id = sanitize_pixel_id(pixel_id)
        self._count_scatter(int(pixel_id.shape[0]))
        return self._step(
            state,
            self._proj.lut,
            dispatch_safe(pixel_id),
            dispatch_safe(toa),
        )

    @staticmethod
    def _state_slice_device(state: HistogramState):
        """The device a slice-placed state is COMMITTED to (mesh-slice
        placement, parallel/mesh_tick.py), else None.

        The private/fallback step paths resolve their staging placement
        from the STATE: a slice-placed group that drops to the private
        path (tick ineligibility, a contained tick failure) must stage
        onto its slice — default-device staging would hand the jitted
        step arguments committed to two devices, which jax rejects on
        real multi-chip hardware (the CPU backend masks it:
        ``dispatch_safe`` returns uncommitted numpy there).
        Committedness is the discriminator, not device identity: a
        group PLACED on the default device still returns it (so the
        staging cache key matches the tick path's slice token — no
        double staging for the 1/N of groups landing on device 0),
        while un-placed states are uncommitted and return None, keeping
        placement-less deployments' cache keys byte-identical.
        """
        for leaf in state:
            ds = leaf_device_set(leaf, committed_only=True)
            if ds is None:
                continue
            if len(ds) != 1:
                return None  # mesh-sharded or replicated: not a slice
            return next(iter(ds))
        return None

    def step_batch(
        self,
        state: HistogramState,
        batch: EventBatch,
        *,
        cache=None,
        batch_tag: str = "",
        device=None,
    ) -> HistogramState:
        """One staged batch, taking the 4-byte/event ingest fast path
        (host flatten + flat scatter) whenever the configuration allows it
        — half the host->device bytes of the (pixel_id, toa) path
        (PERF.md); replica/weighted configurations use the device path.
        ``method='pallas2d'`` fuses flatten + block partition into one
        native pass feeding the MXU-tiled kernel.

        ``cache`` (a ``StreamStageSlot`` from core/device_event_cache.py)
        makes the host flatten/partition and the device transfer run once
        per window per (stream, layout) no matter how many jobs step from
        the same batch; ``batch_tag`` marks pre-staging content
        transforms so transformed batches never collide with the raw
        stream under the same layout key. ``device`` defaults to the
        state's own slice (``_state_slice_device``) so a placed group's
        private path stages where its state lives — under the same
        slice-keyed cache entry the tick path uses."""
        if device is None:
            device = self._state_slice_device(state)
        self._count_scatter(
            batch.padded_size, partitioned=self._method == "pallas2d"
        )
        if self._method == "pallas2d":
            events, chunk_map = self._staged_partition(
                batch, cache, batch_tag, device=device
            )
            return self._step_part(state, events, chunk_map)
        if self.supports_host_flatten:
            return self._step_flat(
                state,
                self._staged_flat(batch, cache, batch_tag, device=device),
            )
        pid, toa = self._staged_raw(batch, cache, batch_tag, device=device)
        return self._step(state, self._proj.lut, pid, toa)

    def step_many(
        self,
        states,
        batch: EventBatch,
        *,
        cache=None,
        batch_tag: str = "",
        device=None,
    ) -> tuple[HistogramState, ...]:
        """Advance K independent states from ONE staged batch in ONE
        jitted dispatch (the fused-stepping layer's kernel entry,
        core/job_manager.py). All states are donated; per-state results
        are bit-identical to K private ``step_batch`` calls. The jit
        cache holds one program per K — group sizes are expected to be
        few and stable (the number of co-subscribed jobs). ``device``
        (mesh-slice placement) stages the wire onto the group's slice —
        the states were committed there at assignment time; when not
        given it resolves from the first state's placement, so callers
        outside the placement-aware manager cannot mix devices."""
        states = tuple(states)
        if not states:
            return ()
        if device is None:
            device = self._state_slice_device(states[0])
        self._count_scatter(
            batch.padded_size, partitioned=self._method == "pallas2d"
        )
        if self._method == "pallas2d":
            events, chunk_map = self._staged_partition(
                batch, cache, batch_tag, device=device
            )
            return self._dispatch_fused(
                self._step_part_fused, states, events, chunk_map
            )
        if self.supports_host_flatten:
            return self._dispatch_fused(
                self._step_flat_fused,
                states,
                self._staged_flat(batch, cache, batch_tag, device=device),
            )
        pid, toa = self._staged_raw(batch, cache, batch_tag, device=device)
        return self._dispatch_fused(
            self._step_fused, states, self._proj.lut, pid, toa
        )

    def _dispatch_fused(self, fn, states, *staged):
        """Dispatch one fused-step jit with compile-event detection
        (telemetry, ADR 0116): a cache miss on the jitted ``fn`` — a
        new K, a layout swap re-keying the staged wire — records its
        wall time into the labeled compile
        histogram. The probe is jax's jit cache size (guarded: absent
        on exotic wrappers), read before and after the call; compile is
        synchronous at first call, so the unblocked wall time is the
        stall the serving path actually saw. NOT traced code — this is
        the host-side dispatch wrapper (JGL018 boundary)."""
        probe = getattr(fn, "_cache_size", None)
        if probe is None:
            return fn(states, *staged)
        try:
            before = probe()
        except Exception:  # pragma: no cover - probe API drift
            return fn(states, *staged)
        t0 = time.perf_counter()
        out = fn(states, *staged)
        try:
            if probe() > before:
                from ..telemetry.compile import COMPILE_EVENTS

                COMPILE_EVENTS.classify_and_record(
                    "step_many",
                    (id(self), len(states)),
                    time.perf_counter() - t0,
                    layout_digest=self.layout_digest,
                    staged_sig=tuple(
                        (tuple(getattr(a, "shape", ())),
                         str(getattr(a, "dtype", "")))
                        for a in staged
                    ),
                )
        except Exception:  # pragma: no cover - telemetry is advisory
            logger.debug("compile-event recording failed", exc_info=True)
        return out

    # -- one-dispatch tick program (ops/tick.py, ADR 0114) -----------------
    def tick_staging(
        self,
        batch: EventBatch,
        cache,
        *,
        batch_tag: str = "",
        pool=None,
        device=None,
    ) -> tuple:
        """This configuration's staged wire as a flat tuple of device
        arrays, shaped for ``tick_step``'s trailing arguments.

        Runs exactly the staging ``step_batch``/``step_many`` would run
        — same cache keys, same functions — so a window prestaged by the
        pipelined ingest is a guaranteed hit (zero transfers at tick
        time) and any other same-layout consumer shares the arrays by
        reference. The device-path tuple leads with the LUT so a live
        swap stays an argument change (ADR 0105), never a retrace of the
        step body itself. The tick program that takes the tuple is this
        window's one dispatch of the group, so its scatter's updates
        are counted here."""
        self._count_scatter(
            batch.padded_size, partitioned=self._method == "pallas2d"
        )
        if self._method == "pallas2d":
            return self._staged_partition(
                batch, cache, batch_tag, device=device
            )
        if self.supports_host_flatten:
            return (
                self._staged_flat(
                    batch, cache, batch_tag, pool=pool, device=device
                ),
            )
        pid, toa = self._staged_raw(batch, cache, batch_tag, device=device)
        return (self._proj.lut, pid, toa)

    def tick_step(self, states, *staged):
        """TRACEABLE fused step over ``tick_staging``'s arrays — the tick
        program (ops/tick.py) composes this with the members' packed
        publish bodies so step + publish ride ONE dispatch. Applies the
        exact per-state program the standalone fused ``step_many`` jits
        run, so tick results are bit-identical to separate stepping."""
        states = tuple(states)
        if self._method == "pallas2d":
            return self._step_part_fused_impl(states, *staged)
        if self.supports_host_flatten:
            return self._step_flat_fused_impl(states, *staged)
        return self._step_fused_impl(states, *staged)

    def flatten_partition_host(
        self, pixel_id: np.ndarray, toa: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host ingest for ``method='pallas2d'``: raw (pixel_id, toa) to
        block-partitioned ``(events, chunk_map)`` for the tiled kernel.

        One fused native pass (``ld_flatten_partition``) when the
        configuration is uniform-edged and pixel-block-aligned; otherwise
        ``flatten_host`` + ``partition_events_host``.
        """
        from .pallas_hist2d import (
            bucketed_chunks,
            chunk_capacity,
            partition_events_host,
        )

        if self._ppb_shift is not None and self._proj.uniform:
            try:
                from ..native import flatten_partition
            except ImportError:
                flatten_partition = None
            if flatten_partition is not None:
                pixel_id = sanitize_pixel_id(pixel_id)
                chunk = self._p2_chunk
                n_blocks = self._n_state // self._bpb
                cap = chunk_capacity(pixel_id.shape[0], n_blocks, chunk)
                lut_host = self._proj.lut_host
                res = flatten_partition(
                    pixel_id,
                    toa,
                    lut=None if lut_host is None else lut_host[0],
                    n_screen=self._n_screen,
                    n_toa=self._n_toa,
                    lo=self._proj.lo,
                    hi=self._proj.hi,
                    inv_width=self._proj.inv_width,
                    ppb_shift=self._ppb_shift,
                    chunk=chunk,
                    cap_chunks=cap,
                    compact=self._p2_compact,
                )
                if res is not None:
                    events, chunk_map, used = res
                    n_padded = bucketed_chunks(used)
                    return events[: n_padded * chunk], chunk_map[:n_padded]
        flat = self.flatten_host(pixel_id, toa)
        return partition_events_host(
            flat,
            self._n_bins + 1,
            bpb=self._bpb,
            chunk=self._p2_chunk,
            compact=self._p2_compact,
        )

    def step_flat(self, state: HistogramState, flat) -> HistogramState:
        """Accumulate host-pre-flattened int32 bin indices (see
        ``flatten_host``): 4 bytes/event over the host->device link instead
        of 8. Out-of-range indices are dropped by the scatter.

        With ``method='pallas2d'`` the indices are partitioned by bin
        block on the host (native ``ld_partition`` when available) and
        fed to the MXU-tiled kernel instead of the serial scatter."""
        self._count_scatter(
            int(np.shape(flat)[0]), partitioned=self._method == "pallas2d"
        )
        if self._method == "pallas2d":
            from .pallas_hist2d import partition_events_host

            events, chunk_map = partition_events_host(
                np.asarray(flat),
                self._n_bins + 1,
                bpb=self._bpb,
                chunk=self._p2_chunk,
                compact=self._p2_compact,
            )
            return self._step_part(
                state, dispatch_safe(events), dispatch_safe(chunk_map)
            )
        return self._step_flat(state, dispatch_safe(flat))

    @property
    def supports_host_flatten(self) -> bool:
        """True when this configuration can use the 4-byte/event ingest
        fast path (``flatten_host`` + ``step_flat``): replica LUTs multiply
        events and weighted configurations need float updates, so both
        stay on the device path."""
        return (
            self._proj.weights is None
            and (self._proj.lut_host is None or self._proj.lut_host.shape[0] == 1)
            and self._n_bins < np.iinfo(np.int32).max
        )

    def flatten_host(
        self,
        pixel_id: np.ndarray,
        toa: np.ndarray,
        *,
        out: np.ndarray | None = None,
    ) -> np.ndarray:
        """Host-side flat-index computation for ``step_flat``.

        Supports the no-LUT and single-replica-LUT configurations (the
        replica path multiplies events and must stay on device). Weighted
        configurations also stay on the device path.

        The native shim (ingest.cpp ld_flatten) does this in one C pass
        when available; the numpy fallback is kept to a handful of
        int32/float32 passes — this runs on the host ingest thread per
        batch, so every extra temporary costs real pipeline time.

        ``out`` optionally receives the result in place (int32, same
        length) — the chunked parallel flatten writes worker slices
        straight into one array instead of concatenating copies.
        """
        if self._proj.weights is not None:
            raise ValueError("flatten_host does not support pixel_weights")
        lut_host = self._proj.lut_host
        if lut_host is not None and lut_host.shape[0] != 1:
            raise ValueError("flatten_host does not support replica LUTs")
        if self._n_bins >= np.iinfo(np.int32).max:
            raise ValueError("bin space exceeds int32 flat indexing")
        pixel_id = sanitize_pixel_id(pixel_id)
        toa = np.asarray(toa, dtype=np.float32)
        try:
            from ..native import flatten_events
        except ImportError:
            flatten_events = None
        if flatten_events is not None:
            native_out = flatten_events(
                pixel_id,
                toa,
                lut=None if lut_host is None else lut_host[0],
                n_screen=self._n_screen,
                n_toa=self._n_toa,
                lo=self._proj.lo,
                hi=self._proj.hi,
                inv_width=self._proj.inv_width,
                dump=self._n_bins,
                edges=None if self._proj.uniform else self._edges_f32,
                out=out,
            )
            if native_out is not None:
                return native_out
        proj = self._proj
        if proj.uniform:
            tb = (toa - np.float32(proj.lo)) * np.float32(proj.inv_width)
            tb = tb.astype(np.int32)
            # Range checks on toa itself (not tb): int32 truncation rounds
            # toward zero, so toa slightly below lo yields tb == 0.
            t_ok = (toa >= np.float32(proj.lo)) & (toa < np.float32(proj.hi))
            np.clip(tb, 0, self._n_toa - 1, out=tb)
        else:
            # float32 edges, matching the device path's dtype exactly —
            # boundary-adjacent events must land in the same bin whichever
            # ingest path (host flatten vs device projection) a config takes.
            tb = np.searchsorted(
                self._edges_f32, toa, side="right"
            ).astype(np.int32) - 1
            t_ok = (tb >= 0) & (tb < self._n_toa)
            np.clip(tb, 0, self._n_toa - 1, out=tb)
        if lut_host is not None:
            lut = lut_host[0]
            p_ok = (pixel_id >= 0) & (pixel_id < lut.shape[0])
            screen = lut.take(pixel_id, mode="clip")
            ok = p_ok & t_ok & (screen >= 0)
        else:
            screen = pixel_id
            ok = (pixel_id >= 0) & (pixel_id < self._n_screen) & t_ok
        # int32 multiply-add is safe: n_bins < 2**31 checked above; invalid
        # rows may wrap but are overwritten with the dump bin right after.
        if out is not None:
            np.copyto(out, screen, casting="unsafe")
            flat = out
        else:
            flat = screen.astype(np.int32, copy=True)
        flat *= np.int32(self._n_toa)
        flat += tb
        flat[~ok] = self._n_bins
        return flat

    def clear_window(self, state: HistogramState) -> HistogramState:
        """Fold the window into the cumulative total and zero it (one dense
        add, paid at publish rate rather than per batch)."""
        return self._clear_window(state)

    def clear(self, state: HistogramState) -> HistogramState:
        return self._clear_all(state)

    def views(self, state: HistogramState) -> tuple[jax.Array, jax.Array]:
        """Device-resident (cumulative, window) views, shape
        ``[n_screen, n_toa]`` — the dump bin is dropped and the window is
        folded into the cumulative on the fly."""
        return self._views(state)

    def read(self, state: HistogramState) -> tuple[np.ndarray, np.ndarray]:
        """Host copies of the (cumulative, window) views — one bulk
        device->host fetch, not one per array."""
        return jax.device_get(self._views(state))
