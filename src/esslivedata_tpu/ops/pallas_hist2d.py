"""Pallas tiled 2-D histogram kernel — MXU accumulation for bin spaces
far beyond VMEM (the LOKI-scale 1.5M-pixel x 100-TOA headline space).

Why
---
XLA's TPU ``scatter_add`` runs on the scalar core, serially: ~11 ns/event
measured at LOKI scale, which pins the device-resident histogram step at
~93M events/s (PERF.md "Where the time goes"). ``ops/pallas_hist.py``
breaks that ceiling only for bin spaces that fit VMEM in one tile. This
kernel handles the other regime: the output lives in HBM, tiled into
VMEM-sized *blocks* of ``bpb`` bins, and events are pre-partitioned by
block on the host so each output block is visited exactly once, by a
consecutive run of grid steps.

How
---
1. **Host partition** (``partition_events_host`` / native
   ``ld_partition``): a counting sort groups flat bin indices by
   ``block = flat >> log2(bpb)`` and pads each used block's events up to a
   multiple of the chunk size ``C`` with ``-1``. Emits the padded event
   array plus a non-decreasing int32 ``chunk -> block`` map.
2. **Pallas grid over chunks** with the map scalar-prefetched: the output
   BlockSpec indexes ``window[map[j]]``, so consecutive chunks of one
   block accumulate in VMEM and the block is flushed to HBM once when the
   map advances (TPU revisiting semantics). ``input_output_aliases``
   makes the kernel accumulate **in place** into the donated window
   state: blocks with no events are never touched.
3. **MXU accumulation** (``pallas_hist.factorised_counts``, the body
   ``bincount_mxu`` runs for bin spaces of one block): within a chunk
   the local offset decomposes as ``local = hi * 128 + lo``; one-hot
   matrices over ``hi`` ([bpb/128, C]) and ``lo`` ([128, C]) are built
   with two VPU compares, the events on the lanes, and contracted over
   the chunk axis on the MXU (bf16 one-hots — 0/1 are exact — with
   float32 accumulation): ``counts[hi, lo] += onehot_hi @ onehot_lo^T``.
   The serial 11 ns/event scatter becomes ~2*bpb MXU FLOPs/event, which
   at bpb=65536 is ~1.3e5 FLOPs — well under 1 ns/event at v5e bf16
   rates, leaving the host partition and HBM traffic as the new bounds.
   A 4 Mi batch into 150 M bins takes 8.2 ms on a v5e (5.2 from the
   compact wire; 15.3 / 13.4 with the events on the sublanes, as up to
   PR 33; my chip run, PR 34, parity with XLA's scatter exact).

Out-of-range/padded events (``flat = -1`` after block-local shift) have a
negative ``hi`` and match no one-hot row, so they are dropped for free —
the same semantics as the scatter path's dump-bin routing.

The partition on the chip (``partition_on_device`` +
``count_partitioned``; ``EventHistogrammer(method="mxu")``, ADR 0131)
------------------------------------------------------------------------
The detector views' indices are made on the device (a replica LUT's
projection) or arrive unsorted on the flat wire, so the same kernel takes
a partition the chip makes: dropped slots keyed ``INT32_MAX``, the keys
sorted alone, cut into chunks of ``COUNT_CHUNK``, and the ``(chunk,
block)`` work items listed by ``pallas_lookup.work_items``; the grid
walks the items with both indices scalar-prefetched and skips the steps
past the last. A state of one block (up to ``MAX_MXU_BINS``) needs no
sort. 4 Mi slots, 23.4 % of them dropped, every count exact against
``np.bincount`` at updates of 1 and 1/4 (``scripts/tpu_kernel_check.py
--view``; my chip run, PR 38), ms (ns a slot):

============ ============ ===================== ================ ================
 bins         XLA scatter  blocks of 16 Ki,      blocks of 64 Ki   one block
 (dump incl.) weighted /   chunk 512 / 2 048 /   chunk 512 /       (up to 65 536)
              unit         8 192                 2 048 / 8 192
============ ============ ===================== ================ ================
  6 553 601  40.56 / 40.57 4.68 / **3.28** / 3.51  7.82 / 4.85 / 5.06        -
163 840 001  41.77 / 41.79 9.88 / 9.08 / 18.69   10.39 / **8.77** / 18.73    -
 15 769 601  40.64 / 40.63 4.93 / **3.63** / 4.44  8.00 / 5.13 / 5.91        -
     25 601  25.59 / 25.61 4.48 / 3.05 / 2.83          -             3.73 / **1.59** / 1.34
============ ============ ===================== ================ ================

(the 6.55 M row is 0.78 ns a slot against the scatter's 9.67; of it the
key sort alone reads 3.4 ms and the work items 0.5 ms at 4 Mi keys.) So
``COUNT_BPB`` 16 Ki and ``COUNT_CHUNK`` 2 048: the fastest at 6.55 M and
15.8 M, 4 % over 64 Ki blocks at NMX's 10 001 blocks, and one block for
DREAM's 25 601 bins at 1.59 (8 192 keys would give 1.34 there and 18.7
at 164 M).

The state arrays for ``method='pallas2d'`` are padded to ``n_blocks*bpb``
(the dump bin and the padding tail are excluded from all views, exactly
like the existing dump-bin slot).

Reference parity: this replaces the same scipp CPU ``hist`` call as the
scatter path (reference preprocessors/to_nxevent_data.py:180-199); it is
a pure performance variant with bit-identical counts (asserted against
the scatter in tests/ops/pallas_hist2d_test.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_hist import MAX_MXU_BINS, factorised_counts

__all__ = [
    "COUNT_BPB",
    "COUNT_CHUNK",
    "DEFAULT_BPB",
    "DEFAULT_CHUNK",
    "bucketed_chunks",
    "chunk_capacity",
    "count_layout",
    "count_partitioned",
    "partition_on_device",
    "partition_events_host",
    "scatter_add_pallas2d",
    "padded_bins",
]

#: Default bins-per-block: 64Ki f32 = 256 KiB VMEM per output tile.
DEFAULT_BPB = 65536
#: Default events per grid step (chunk).
DEFAULT_CHUNK = 512
#: The device-partitioned count's block past one tile (``count_layout``)
#: and the keys of one of its work items (``partition_on_device``): the
#: chip sweep's pick (``scripts/tpu_kernel_check.py --view``; the table
#: is in the module docstring): 16 Ki bins and 2 048 keys are the
#: fastest or within 4 % of it at every bin space measured.
COUNT_BPB = 16384
COUNT_CHUNK = 2048
#: Chunk-count bucket: the padded chunk count rounds up to a multiple of
#: this so the jit cache sees a handful of shapes, not one per batch.
_CHUNK_BUCKET = 512

_LANES = 128
_SENTINEL = np.iinfo(np.int32).max


def padded_bins(n_bins_incl_dump: int, bpb: int = DEFAULT_BPB) -> int:
    """State size for pallas2d: bins (incl. dump) padded to whole blocks."""
    n_blocks = -(-n_bins_incl_dump // bpb)
    return n_blocks * bpb


def count_layout(n_bins: int) -> tuple[int, int]:
    """``(bpb, n_state)`` of the device-partitioned count over
    ``n_bins`` bins and the dump slot behind them: one block of whole
    (8, 128) float32 tiles up to ``MAX_MXU_BINS`` (``bincount_mxu``'s
    one VMEM tile: no sort), else whole blocks of ``COUNT_BPB``."""
    n = n_bins + 1
    if n <= max(MAX_MXU_BINS, COUNT_BPB):
        bpb = -(-n // (8 * _LANES)) * 8 * _LANES
        return bpb, bpb
    return COUNT_BPB, padded_bins(n, COUNT_BPB)


def partition_on_device(flat: jax.Array, n_bins: int, *, bpb: int):
    """The chip's twin of ``partition_events_host``, traceable: the
    batch's keys and its ``(chunk, block)`` work items, the trailing
    arguments of ``count_partitioned``. ``flat`` is int32 ``[n]``; an
    index outside ``[0, n_bins)`` (the dump slot, a bucket's padding) is
    keyed ``INT32_MAX``, so that it sorts past every block and makes no
    item: a dropped slot costs its share of the sort and nothing else,
    and the dump slot is never counted. The keys are sorted alone
    (unstable: equal keys are one bin, and nothing rides along) and cut
    into chunks of ``COUNT_CHUNK``; ``pallas_lookup.work_items`` lists
    the blocks each chunk's keys fall in. A state of one block skips the
    sort: every chunk is an item of that block, up to the last one that
    holds a key."""
    return _lowered_once(
        _partition, (flat,), n_bins=n_bins, bpb=bpb, chunk=COUNT_CHUNK
    )


def _partition(flat, *, n_bins: int, bpb: int, chunk: int):
    from .pallas_lookup import work_items

    keys = jnp.where((flat >= 0) & (flat < n_bins), flat, _SENTINEL)
    pad = max(-(-keys.shape[0] // chunk), 1) * chunk - keys.shape[0]
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), _SENTINEL, jnp.int32)])
    n_chunks = keys.shape[0] // chunk
    n_blocks = -(-(n_bins + 1) // bpb)
    if n_blocks == 1:
        held = jnp.any(keys.reshape(n_chunks, chunk) != _SENTINEL, axis=1)
        steps = jnp.arange(n_chunks, dtype=jnp.int32)
        n_items = jnp.max(jnp.where(held, steps + 1, 0))
        return (
            keys,
            jnp.minimum(steps, jnp.maximum(n_items - 1, 0)),
            jnp.zeros((n_chunks,), jnp.int32),
            n_items.reshape(1),
        )
    with jax.named_scope("count_sort"):
        keys = jax.lax.sort(keys, is_stable=False)
    chunk_of, block_of, n_items = work_items(
        keys, group=chunk, span=bpb, n_targets=n_blocks
    )
    return keys, chunk_of, block_of, n_items


def count_partitioned(
    window: jax.Array,
    keys: jax.Array,
    chunk_of: jax.Array,
    block_of: jax.Array,
    n_items: jax.Array,
    *,
    bpb: int,
    upd=1.0,
    interpret: bool | None = None,
) -> jax.Array:
    """Count ``partition_on_device``'s keys into the block-padded flat
    ``window`` in place (``count_layout``'s ``n_state`` elements),
    every count times the scalar ``upd``. Traceable."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _lowered_once(
        _pallas2d_call,
        (window, keys, chunk_of, block_of, n_items, jnp.asarray(upd, jnp.float32)),
        bpb=bpb,
        chunk=COUNT_CHUNK,
        interpret=bool(interpret),
    )


def _lowered_once(fn, args: tuple, **static):
    """``fn(*args, **static)`` inside the caller's trace, lowered once a
    process per shape and kept as a ``jax.export`` module, which a
    program that calls it again inlines without lowering the Mosaic
    kernel again. A view's two tick programs (the first tick's, with its
    statics, and the steady one) and every view of one shape then pay
    the kernel's lowering once: lowering it is ~0.13 s of Python on the
    chip's host, where the whole scatter program took ~0.013 (my chip
    run, PR 38), and a compile round pays it per program."""
    avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
    exported = _export(
        fn, avals, tuple(sorted(static.items())), jax.default_backend()
    )
    return exported.call(*args)


@functools.lru_cache(maxsize=64)
def _export(fn, avals: tuple, static: tuple, platform: str):
    from jax import export

    jitted = jax.jit(functools.partial(fn, **dict(static)))
    return export.export(jitted, platforms=[platform])(*avals)


def chunk_capacity(
    n_items: int, n_blocks: int, chunk: int = DEFAULT_CHUNK
) -> int:
    """Worst-case chunk count for a partition of ``n_items`` events
    (every used block ends in a partial chunk), bucket-rounded — the ONE
    bound both native partition entry points allocate against."""
    cap = n_items // chunk + n_blocks + 1
    return max(_CHUNK_BUCKET, -(-cap // _CHUNK_BUCKET) * _CHUNK_BUCKET)


def bucketed_chunks(used: int) -> int:
    """Round a used-chunk count up to the jit-cache shape bucket."""
    return max(_CHUNK_BUCKET, -(-used // _CHUNK_BUCKET) * _CHUNK_BUCKET)


def partition_events_host(
    flat: np.ndarray,
    n_bins_incl_dump: int,
    *,
    bpb: int = DEFAULT_BPB,
    chunk: int = DEFAULT_CHUNK,
    compact: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Group flat indices by bin block, pad each block to whole chunks.

    Returns ``(events, chunk_map)``: ``events`` is int32
    ``[n_chunks * chunk]`` with ``-1`` padding, ``chunk_map`` is int32
    ``[n_chunks]``, non-decreasing. Out-of-range indices (negative or
    ``>= n_bins_incl_dump``) are routed to the dump bin
    (``n_bins_incl_dump - 1``) first — same policy as ``step_flat``.

    ``compact=True`` (requires ``bpb <= 0xFFFF``) emits ``events`` as
    uint16 block-LOCAL offsets with ``0xFFFF`` padding — the same
    partition at half the host->device wire bytes. The sentinel can
    never collide with a real offset (``0xFFFF >= bpb``), and the
    kernel drops it exactly like the int32 path's ``-1``.

    The native shim (``ld_partition``/``ld_partition_u16``) does the
    counting sort in two C passes — for power-of-two ``bpb`` it derives
    blocks with a shift; otherwise numpy vectorizes the division and the
    C pass takes the precomputed block ids. The pure-numpy fallback (no
    compiler) is a CHUNKED counting sort: per-block destination cursors
    from one global bincount, then cache-resident input chunks are
    stably grouped and their block runs memcpy'd to the cursors — the
    same stable order as the native pass, without the former global
    argsort + full-array gather (~80 ms at 4M events; measured ~2.5×
    slower than native — see PERF.md).
    """
    if bpb % _LANES:
        raise ValueError("bpb must be a multiple of 128")
    if compact and bpb > 0xFFFF:
        raise ValueError("compact partition requires bpb <= 0xFFFF")
    flat = np.asarray(flat, np.int32)
    n_blocks = -(-n_bins_incl_dump // bpb)

    try:
        from ..native import partition_events
    except ImportError:
        partition_events = None
    if partition_events is not None:
        cap = chunk_capacity(flat.shape[0], n_blocks, chunk)
        compact_bpb = bpb if compact else 0
        if not (bpb & (bpb - 1)):
            res = partition_events(
                flat,
                n_bins_incl_dump,
                shift=bpb.bit_length() - 1,
                chunk=chunk,
                cap_chunks=cap,
                compact_bpb=compact_bpb,
            )
        else:
            dump = n_bins_incl_dump - 1
            bad = (flat < 0) | (flat >= n_bins_incl_dump)
            routed = np.where(bad, np.int32(dump), flat) if bad.any() else flat
            res = partition_events(
                routed,
                n_bins_incl_dump,
                chunk=chunk,
                cap_chunks=cap,
                blk=routed // np.int32(bpb),
                n_blocks=n_blocks,
                compact_bpb=compact_bpb,
            )
        if res is not None:
            events, chunk_map, used = res
            n_padded = bucketed_chunks(used)
            return events[: n_padded * chunk], chunk_map[:n_padded]

    dump = n_bins_incl_dump - 1
    bad = (flat < 0) | (flat >= n_bins_incl_dump)
    if bad.any():
        flat = np.where(bad, np.int32(dump), flat)
    if bpb & (bpb - 1):
        blk = flat // np.int32(bpb)
    else:
        # All indices are >= 0 after the dump routing above, so the
        # shift is the division (the fused native pass does the same).
        blk = flat >> np.int32(bpb.bit_length() - 1)
    counts = np.bincount(blk, minlength=n_blocks)
    chunks_per_block = -(-counts // chunk)  # 0 for empty blocks
    n_chunks = int(chunks_per_block.sum())
    n_padded = bucketed_chunks(n_chunks)
    if compact:
        events = np.full(n_padded * chunk, 0xFFFF, np.uint16)
        vals = (flat - blk * np.int32(bpb)).astype(np.uint16)
    else:
        events = np.full(n_padded * chunk, -1, np.int32)
        vals = flat
    chunk_map = np.full(n_padded, n_blocks - 1, np.int32)
    # Per-block destinations in the padded events array (each block's
    # region starts on a chunk boundary), then one pass of the chunk map.
    first_chunk = np.concatenate(
        ([0], np.cumsum(chunks_per_block[:-1]))
    ).astype(np.int64)
    dst = 0
    for b in np.nonzero(counts)[0]:
        k = int(chunks_per_block[b])
        chunk_map[dst : dst + k] = b
        dst += k
    cursor = first_chunk * chunk  # running write position per block
    # Chunked counting sort: group each cache-resident input slice
    # stably by block (numpy's stable sort on int32 is a radix pass,
    # O(c)), then memcpy each block run to its cursor. Input order is
    # preserved within every block — slices are processed in order and
    # the within-slice grouping is stable — so the result is identical
    # to the native two-pass counting sort (and to the old argsort
    # path), while touching the 21 MB output with sequential run writes
    # instead of a full-array random gather.
    # Narrow sort keys: numpy's stable argsort is a radix pass for
    # 16-bit keys (~10x the int32 sort on this access pattern), and the
    # block id fits uint16 for every realistic configuration (LOKI's
    # 1.5M x 100 space at bpb=64Ki is ~2.3k blocks).
    keys = blk.astype(np.uint16) if n_blocks <= 0xFFFF else blk
    span = 1 << 17
    for lo in range(0, flat.shape[0], span):
        b_slice = keys[lo : lo + span]
        v_slice = vals[lo : lo + span]
        order = np.argsort(b_slice, kind="stable")
        b_sorted = b_slice[order]
        v_sorted = v_slice[order]
        run_starts = np.flatnonzero(
            np.r_[True, b_sorted[1:] != b_sorted[:-1]]
        )
        run_lens = np.diff(np.r_[run_starts, b_sorted.size])
        run_blocks = b_sorted[run_starts]
        # dest[i] = cursor[block of i] + rank of i within its run —
        # one vectorized grouped scatter per slice.
        within = np.arange(b_sorted.size, dtype=np.int64) - np.repeat(
            run_starts, run_lens
        )
        events[np.repeat(cursor[run_blocks], run_lens) + within] = v_sorted
        cursor[run_blocks] += run_lens
    return events, chunk_map


@functools.partial(
    jax.jit, static_argnums=(6, 7, 8, 9, 10), donate_argnums=(0,)
)
def _pallas2d_call(
    window: jax.Array,  # [n_blocks * bpb] float32, donated
    events: jax.Array,  # [n_chunks * chunk]: int32 flat (-1 or INT32_MAX
    #                     padded) or uint16 block-local (0xFFFF, `local`)
    chunk_of,  # [n_steps] int32: each work item's chunk (None: step's)
    block_of: jax.Array,  # [n_steps] int32, non-decreasing: its block
    n_items,  # [1] int32: steps from here on are skipped (None: none)
    upd,  # traced float32 scalar (1.0 for counts; 1/R; 1/scale for decay)
    bpb: int,
    chunk: int,
    interpret: bool,
    precision: str = "bf16",
    local: bool = False,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_chunks = events.shape[0] // chunk
    n_blocks = window.shape[0] // bpb
    if chunk_of is None:
        chunk_of = jnp.arange(block_of.shape[0], dtype=jnp.int32)
        n_items = jnp.full((1,), block_of.shape[0], jnp.int32)
    h = bpb // _LANES
    win3 = window.reshape(n_blocks, h, _LANES)
    if local:
        # Compact uint16 wire (2 B/event over the link): widen on device
        # — one cheap HBM pass — so the kernel never needs 16-bit tiles.
        # The 0xFFFF sentinel widens to 65535 >= bpb and drops in the
        # one-hot exactly like the int32 path's -1.
        events = events.astype(jnp.int32)
    # (n_chunks, 8, chunk/8): Mosaic needs the last two block dims
    # divisible by (8, 128) or equal to the array dims — a (1, chunk)
    # block over (n_chunks, chunk) breaks the sublane rule, while the
    # (8, cw) tail here covers the full trailing dims and is always
    # legal.
    cw = chunk // 8
    rows = events.reshape(n_chunks, 8, cw)
    upd_arr = jnp.full((1,), upd, jnp.float32)
    # One-hot operand dtype for the MXU contraction. 0/1 are exact in
    # both; int8 runs at ~2x the bf16 MXU rate on v5e with exact int32
    # accumulation (a chunk sums at most `chunk` ones per bin, far
    # inside int32).
    oh_dtype = jnp.int8 if precision == "int8" else jnp.bfloat16

    def kernel(chunk_ref, block_ref, n_ref, upd_ref, win_ref, rows_ref, out_ref):
        j = pl.program_id(0)
        blk = block_ref[j]

        # A block's first visit loads it, counted or not: the output
        # tile is written back whatever the step did, so a skipped
        # first step (an empty batch) must still hold the window.
        @pl.when((j == 0) | (blk != block_ref[jnp.maximum(j - 1, 0)]))
        def _load():
            out_ref[...] = win_ref[...]

        @pl.when(j < n_ref[0])
        def _count():
            # `local` events arrive block-local already; flat events
            # subtract the block base (padding, -1 or INT32_MAX, and
            # another block's bins land outside the tile's rows).
            contrib = factorised_counts(
                rows_ref,
                h,
                base=None if local else blk * bpb,
                oh_dtype=oh_dtype,
            )
            # the update scales the item's counts once, not each event
            out_ref[0, :, :] += contrib.astype(jnp.float32) * upd_ref[0]

    def at_block(j, c, b, n, u):
        return (b[j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(block_of.shape[0],),
        in_specs=[
            pl.BlockSpec((1, h, _LANES), at_block),
            pl.BlockSpec((1, 8, cw), lambda j, c, b, n, u: (c[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, _LANES), at_block),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(win3.shape, jnp.float32),
        input_output_aliases={4: 0},  # window (after the 4 scalar args)
        interpret=interpret,
        name="count_blocks_mxu",
    )(chunk_of, block_of, n_items, upd_arr, win3, rows)
    return out.reshape(n_blocks * bpb)


def scatter_add_pallas2d(
    window: jax.Array,
    events,
    chunk_map,
    *,
    bpb: int = DEFAULT_BPB,
    upd: float = 1.0,
    interpret: bool | None = None,
    precision: str = "bf16",
) -> jax.Array:
    """Accumulate partitioned events into the padded flat window in place.

    ``window`` must have ``padded_bins(...)`` elements and is donated.
    ``events``/``chunk_map`` come from ``partition_events_host`` (or the
    native ``ld_partition``). uint16 ``events`` are the compact wire:
    block-LOCAL offsets, 0xFFFF padding (``partition_events_host(...,
    compact=True)``). ``upd`` scales every hit (1.0 for counts; the
    lazy-decay path passes ``1/scale``). ``precision`` selects the
    one-hot MXU dtype: 'bf16' or 'int8' (both exact for counts; int8
    doubles the v5e MXU rate).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if bpb % _LANES:
        raise ValueError("bpb must be a multiple of 128")
    n_chunks = len(chunk_map)
    if n_chunks and (events.shape[0] // n_chunks) % 8:
        raise ValueError("chunk must be a multiple of 8 (sublane staging)")
    if window.shape[0] % bpb:
        raise ValueError(
            f"window size {window.shape[0]} is not a multiple of bpb={bpb}"
        )
    if precision not in ("bf16", "int8"):
        raise ValueError("precision must be 'bf16' or 'int8'")
    local = np.dtype(getattr(events, "dtype", np.int32)) == np.uint16
    if local and bpb > 0xFFFF:
        raise ValueError("uint16 compact events require bpb <= 0xFFFF")
    return _pallas2d_call(
        window,
        jnp.asarray(events) if local else jnp.asarray(events, jnp.int32),
        None,  # chunk j is step j's, and every step counts
        jnp.asarray(chunk_map, jnp.int32),
        None,
        jnp.asarray(upd, jnp.float32),
        bpb,
        events.shape[0] // max(n_chunks, 1),
        bool(interpret),
        precision,
        local,
    )
