"""Pallas TPU table lookup without a random access per event.

``qb = table[pixel, toa_bin]`` as an XLA gather costs ~12.4 ns an entry
on a v5e wherever the entry lies (the per-entry issue of a random
access, not HBM bytes): 52 ms for LOKI's 4 Mi-event step, 95 % of the
chip's work there (PERF.md section 5). Dense work is 20 times cheaper
per event and a key sort is cheap, so the lookup here is sort + dense
windows:

1. **Sort** one packed key per event, ``pixel << toa_bits | toa_bin``,
   dropped events keyed to ``INT32_MAX`` so that they sort to the end.
   Key only: the step's result is a histogram, so nothing is scattered
   back.
2. **Work items.** The sorted events are cut into blocks of ``BLOCK``
   and the table's pixels into windows of ``WINDOW``. A block's first
   and last valid key give the windows it touches; the list of
   ``(block, window)`` items, at most ``n / BLOCK + n_pix / WINDOW``
   long, is built by dense XLA ops in the same program and
   scalar-prefetched (the ``chunk -> block`` map of ``pallas_hist2d``).
3. **Per item, on the MXU**: ``table_window [n_toa, WINDOW]`` (bf16) ``@
   onehot(pixel - window * WINDOW) [WINDOW, events]`` with float32
   accumulation gives each event its table row; a one-hot over the TOA
   bin selects the entry. A block's output accumulates across the items
   that revisit it (an event's pixel lies in exactly one of them).
   Items past the last valid event are skipped: padding costs nothing.

The table lives on the device **packed** for this kernel: byte planes
``[planes, n_toa, n_pix]``, transposed, bfloat16, TOA axis padded to the
bf16 sublane tile and the pixel axis to whole windows. bfloat16 holds
the integers -1..256 and no more, so a bin space of up to 255 bins is
one plane (the value itself; LOKI's I(Q): 2 B an entry, what the int16
table was) and one of up to 65 535 bins is two, ``hi = v >> 8``
(arithmetic: -1 for the builders' -1) and ``lo = v & 255``, each exact
in bfloat16, with ``v = hi * 256 + lo`` (-256 + 255 for -1): DREAM's
powder tables, 34 000 bins, 4 B an entry, what the int32 table was. The
same one-hot product reads each plane (a float32 sum over a one-hot has
one non-zero term: exact) and the planes recombine in float32, where
every integer below 2**24 is exact: the result is the gather's, entry
for entry. One resident copy: ``lookup`` reads that layout on both of
its paths (a batch under the crossover gathers from it), so the choice
is made per compiled shape from what the trace observes and the table
never exists twice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BLOCK",
    "EVENTS_PER_WINDOW",
    "MAX_VALUE",
    "MIN_EVENTS",
    "WINDOW",
    "lookup",
    "lookup_kind",
    "pack_table",
    "packable",
    "work_items",
]

#: Events per block: 8 sublane rows of BLOCK / 8 lanes, so that the
#: sorted keys and the output are dense (8, 128) tiles in HBM.
BLOCK = 1024
#: Pixels per table window (the one-hot's contraction length).
WINDOW = 128
#: What one plane of the packed layout counts to: bfloat16 (8 bits of
#: mantissa) holds the integers -1..256 exactly, so a plane is a byte
#: and the radix of the split is 256. A table whose bin count stays
#: under it takes one plane, one under ``MAX_VALUE ** 2`` = 65 536 two.
MAX_VALUE = 256
_MAX_PLANES = 2
#: The crossover against the XLA gather, measured on a v5e with LOKI's
#: tables and id distribution (scripts/tpu_kernel_check.py --lookup; my
#: chip run, PR 28, PERF.md section 6): the gather costs 13 ns an event,
#: the windows 0.5 us an item (most of a table's windows are touched
#: whatever the batch) plus ~1.4 ns an event of sort and blocks. For the
#: 802 816-pixel bank (6 272 windows) the two meet at 2**18 events (3.40
#: against 3.32 ms), for a 172 032-pixel bank (1 344) at 2**16 (0.75
#: against 0.77): 40-50 events a window. Under 2**16 nothing was
#: measured ahead, so the gather stays.
EVENTS_PER_WINDOW = 48
MIN_EVENTS = 1 << 16
#: ``EVENTS_PER_WINDOW`` holds for LOKI's window of 208 rows (one plane
#: x 208 padded TOA bins). An item's cost is its MXU rows, and the
#: gather pays once a plane, so the crossover moves with the window's
#: rows, and slowly: DREAM's powder tables (two planes x 512 = 1 024
#: rows, 1.5 us an item against 28.5 ns an event for one element gather
#: a plane) cross at 58 events a window for the mantle (3 840 windows:
#: 2**18 events 7.47 against 6.47 ms, 2**17 3.78 against 6.24) and under
#: 68 for the SANS bank (240: a tie at 2**14); my chip run, PR 32. So a
#: third more at 1 024 rows, 64, on the line through the two windows
#: measured: one 2 448th of the constant a row.
_ROWS_MEASURED = 208
_ROWS_PER_CONSTANT = 2448

_SENTINEL = np.iinfo(np.int32).max
_SUBLANES = 8
_BF16_ROWS = 16


def _toa_bits(n_toa_padded: int) -> int:
    """Bits of a packed key that hold the TOA bin."""
    return (n_toa_padded - 1).bit_length()


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def packable(table: np.ndarray, n_bins: int) -> int:
    """How many byte planes ``table`` takes in the packed layout, 0
    where it cannot live packed: an int16 or int32 table (the builders'
    two choices) whose values and bin count are exact in that many
    bfloat16 planes, with a packed key per event inside int32."""
    n_pix, n_toa = table.shape
    if table.dtype not in (np.int16, np.int32) or (
        _padded(n_pix, WINDOW) << _toa_bits(_padded(n_toa, _BF16_ROWS))
        >= _SENTINEL
    ):
        return 0
    for planes in range(1, _MAX_PLANES + 1):
        if n_bins + 1 <= MAX_VALUE**planes:
            return planes
    return 0


@functools.partial(jax.jit, static_argnames="planes")
def pack_table(table: jax.Array, *, planes: int) -> jax.Array:
    """int16 or int32 ``[n_pix, n_toa]`` -> the packed layout, bfloat16
    ``[planes, n_toa padded to 16, n_pix padded to WINDOW]``: the top
    plane holds ``table >> 8 * (planes - 1)`` (arithmetic, so it keeps
    the sign: -1 stays -1), every plane under it one byte, most
    significant first. Padding holds 0 and is never selected (a valid
    event's pixel and TOA bin lie inside the table); it must be finite,
    since the MXU multiplies it by 0."""
    n_pix, n_toa = table.shape
    wide = table.T.astype(jnp.int32)
    top = 8 * (planes - 1)
    split = [wide >> top] + [
        (wide >> shift) & (MAX_VALUE - 1) for shift in range(top - 8, -1, -8)
    ]
    return jnp.pad(
        jnp.stack(split).astype(jnp.bfloat16),
        (
            (0, 0),
            (0, _padded(n_toa, _BF16_ROWS) - n_toa),
            (0, _padded(n_pix, WINDOW) - n_pix),
        ),
    )


def _join_planes(planes):
    """The table value from its planes, most significant first (any
    arithmetic type in which the value is exact)."""
    return functools.reduce(lambda high, low: high * MAX_VALUE + low, planes)


def lookup_kind(n_events: int, packed_shape: tuple[int, int, int]) -> str:
    """The path ``lookup`` takes for a batch of ``n_events`` on a packed
    table of shape ``packed_shape``: ``'windowed'`` or ``'gather'`` (the
    label of ``livedata_q_lookup_steps_total``)."""
    planes, n_toa_p, n_pix_p = packed_shape
    rows = planes * n_toa_p
    per_window = EVENTS_PER_WINDOW * (
        1 + (rows - _ROWS_MEASURED) / _ROWS_PER_CONSTANT
    )
    dense = n_events >= max(MIN_EVENTS, per_window * (n_pix_p // WINDOW))
    return "windowed" if dense else "gather"


def work_items(keys: jax.Array, *, group: int, span: int, n_targets: int):
    """``(group, target, n_items)`` of the sorted ``keys``, cut into
    groups of ``group`` keys, the key space into targets of ``span``
    keys (target = key // span): per item a group and a target that
    holds one of its keys, int32 ``[n / group + n_targets]``, groups and
    targets both non-decreasing; entries from ``n_items`` on repeat the
    last item, so that a skipped grid step moves no block. A dropped key
    (``INT32_MAX``) sorts to the end and makes no item. Dense ops only:
    a gather here would pay the per-entry price the kernels exist to
    avoid. The lookup's items are (event block, table window); the
    device-partitioned count's (``pallas_hist2d.partition_on_device``)
    are (event chunk, bin block)."""
    n_groups = keys.shape[0] // group
    by_group = keys.reshape(n_groups, group)
    first = by_group[:, 0]
    last = jnp.max(jnp.where(by_group == _SENTINEL, -1, by_group), axis=1)
    t_lo = first // span
    t_hi = last // span
    count = jnp.where(last >= 0, t_hi - t_lo + 1, 0)
    ends = jnp.cumsum(count)
    n_items = ends[-1]
    j = jnp.minimum(
        jnp.arange(n_groups + n_targets, dtype=jnp.int32),
        jnp.maximum(n_items - 1, 0),
    )
    # group(j) = how many groups end at or before item j; target(j) =
    # j + g(group(j)) with g(b) = t_lo[b] - starts[b], summed from its
    # differences under the same comparison
    before = ends[None, :] <= j[:, None]
    g = t_lo - (ends - count)
    dg = jnp.diff(g, append=g[-1:])
    of_group = jnp.sum(before, axis=1, dtype=jnp.int32)
    target = j + g[0] + jnp.sum(jnp.where(before, dg[None, :], 0), axis=1)
    return (
        jnp.minimum(of_group, n_groups - 1),
        jnp.clip(target, 0, n_targets - 1).astype(jnp.int32),
        n_items.astype(jnp.int32).reshape(1),
    )


def _lookup_sorted(packed, keys, shift: int, interpret: bool):
    """Table value per sorted key, float32 ``[n]``; what a dropped key
    (``INT32_MAX``) reads is undefined."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    planes, n_toa_p, n_pix_p = packed.shape
    n_blocks = keys.shape[0] // BLOCK
    lanes = BLOCK // _SUBLANES
    block, window, n_items = work_items(
        keys, group=BLOCK, span=WINDOW << shift, n_targets=n_pix_p // WINDOW
    )
    toa_mask = (1 << shift) - 1

    def kernel(block_ref, window_ref, n_ref, keys_ref, table_ref, out_ref):
        j = pl.program_id(0)

        @pl.when(j < n_ref[0])
        def _item():
            b = block_ref[j]

            @pl.when((j == 0) | (b != block_ref[jnp.maximum(j - 1, 0)]))
            def _first_visit():
                out_ref[...] = jnp.zeros_like(out_ref)

            base = window_ref[j] * WINDOW
            tables = [table_ref[p] for p in range(planes)]
            pixels = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, lanes), 0)
            toas = jax.lax.broadcasted_iota(jnp.int32, (n_toa_p, lanes), 0)
            # Static unroll over the 8 sublane rows, each loaded
            # straight from the ref (as in pallas_hist): events lie
            # along the lanes, so both one-hots are sublane broadcasts
            # and the selected entry is a sum over sublanes.
            for s in range(_SUBLANES):
                key = keys_ref[0, s : s + 1, :]  # [1, lanes]
                in_window = (pixels == (key >> shift) - base).astype(
                    jnp.bfloat16
                )
                rows = _join_planes(
                    jnp.dot(
                        table, in_window, preferred_element_type=jnp.float32
                    )
                    for table in tables
                )  # [n_toa_p, lanes]: each event's table row
                out_ref[0, s : s + 1, :] += jnp.sum(
                    jnp.where(toas == (key & toa_mask), rows, 0.0),
                    axis=0,
                    keepdims=True,
                )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(block.shape[0],),
            in_specs=[
                pl.BlockSpec(
                    (1, _SUBLANES, lanes), lambda j, b, w, n: (b[j], 0, 0)
                ),
                pl.BlockSpec(
                    (planes, n_toa_p, WINDOW), lambda j, b, w, n: (0, 0, w[j])
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, _SUBLANES, lanes), lambda j, b, w, n: (b[j], 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_blocks, _SUBLANES, lanes), jnp.float32
        ),
        interpret=interpret,
        name="lookup_windowed",
    )(block, window, n_items, keys.reshape(n_blocks, _SUBLANES, lanes), packed)
    return out.reshape(-1)


def lookup(
    packed: jax.Array,
    pid: jax.Array,
    tb: jax.Array,
    ok: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``table[pid, tb]`` as int32 ``[n]`` from the packed table, -1
    where ``ok`` is false. Traceable. ``pid`` and ``tb`` lie inside the
    table (the caller clips them). **On the windowed path the values
    come in sorted-key order, not the events'**: the consumer is a
    histogram."""
    n = pid.shape[0]
    if lookup_kind(n, packed.shape) == "gather":
        # one element gather a plane: a slice over the planes (or of one)
        # would have XLA copy the whole table into another layout first
        planes = (
            packed[p, tb, pid].astype(jnp.int32) for p in range(packed.shape[0])
        )
        return jnp.where(ok, _join_planes(planes), -1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shift = _toa_bits(packed.shape[1])
    keys = jnp.where(ok, (pid << shift) | tb, _SENTINEL)
    pad = (-n) % BLOCK
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), _SENTINEL, jnp.int32)])
    with jax.named_scope("qmap_sort"):
        # equal keys are one entry: a stable sort would carry an index
        # along (five times the compile, twice the code on a v5e)
        keys = jax.lax.sort(keys, is_stable=False)
    values = _lookup_sorted(packed, keys, shift, bool(interpret))
    # select before the cast: an unvisited block holds whatever was there
    return jnp.where(keys != _SENTINEL, values, -1.0).astype(jnp.int32)[:n]
