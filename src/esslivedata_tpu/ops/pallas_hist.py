"""Pallas TPU bincount kernels for VMEM-sized bin spaces.

XLA's TPU ``scatter_add`` executes on the scalar core, serially: 6.6-8.8
ns an event slot whatever the bin count (below), which makes the scatter
THE cost of a histogram step. For bin spaces that fit VMEM this module
has two kernels that do dense work instead, and a histogram step has
three regimes:

- ``bincount_pallas`` (``bincount_onehot`` in a trace), the flat
  one-hot: the grid walks event blocks sequentially (TPU grid
  semantics), each step reduces a ``[block, n_bins]`` equality matrix on
  the VPU and accumulates into the VMEM-resident output block. Its cost
  goes with the bin space's groups of 128 lanes: 0.60 ns an event at one
  to four groups, 0.81 at eight, 6.3 at 38 (4 800 bins: 0.95 of the
  scatter), and past ``MAX_PALLAS_BINS`` the tile does not fit.
- ``bincount_mxu``, the factorised one-hot: bin = 128 * hi + lo,
  ``onehot(hi) @ onehot(lo)^T`` contracted over the events on the MXU
  into one ``[n_bins / 128, 128]`` count tile that stays in VMEM for the
  whole grid, for up to ``MAX_MXU_BINS``. Two compares an event whatever
  the bin count: 0.12-0.16 ns an event up to 10 000 bins, 0.39 at
  34 000. ``ops/pallas_hist2d.py`` runs the same body
  (``factorised_counts``) block by block for bin spaces far past VMEM.
- XLA's scatter: every other backend, and bin spaces past one tile.

Out-of-range indices (negative padding, the dump overflow) match no
column and are dropped for free, the same semantics as scatter's
``mode='drop'`` with negatives pre-routed.

The big 2-D pixel x TOF spaces (1.5M x 100 bins) do NOT fit VMEM; those
take ``pallas_hist2d`` (``EventHistogrammer``'s counts of every size,
ADR 0131).

On non-TPU backends the kernels run in interpret mode (slow, for
tests); ``QHistogrammer(method='auto')`` takes one of the two on a TPU
by the bin count (``MXU_LANE_GROUPS``).

Readings on a v5e (``scripts/tpu_kernel_check.py --bincount``; my chip
run, PR 34, PERF.md section 6; ms for 4 Mi / 16 Mi event slots, 23 % of
them routed to the drop slot as a step's padding is, every count exact
against ``np.bincount``; scatter / flat one-hot / ``bincount_mxu``):

====== ============ ============== ============== ==============
 bins  lane groups  scatter        flat one-hot   ``bincount_mxu``
====== ============ ============== ============== ==============
   100       1      36.75 / 146.92   2.51 /   9.86  0.54 / 1.97
   256       2      36.71 / 146.92   2.52 /   9.83  0.54 / 1.96
   512       4      36.73 / 146.93   2.55 /   9.91  0.55 / 1.96
  1024       8      30.04 / 120.17   3.41 /  13.44  0.54 / 1.96
  4800      38      27.89 / 111.50  26.51 / 105.79  0.58 / 2.11
 10000      79      27.89 / 111.53        -         0.68 / 2.50
 34000     266      27.90 / 111.51        -         1.73 / 6.70
====== ============ ============== ============== ==============

(``bincount_mxu`` at the 4 096-event chunk of that run; at the 8 192
kept here 0.49, 0.50, 0.60, 1.65 ms for 4 Mi slots into 100, 4 800,
10 000, 34 000 bins.) In LOKI's Q step the flat kernel compares 4 Mi
events against 128 lanes in 2.44 ms (PR 27). The table lookup in front
of either was 52.0 ms of that step as a gather and is
ops/pallas_lookup.py's since PR 28 (a 3.4 ms key sort + 4.9 ms of dense
windows on the MXU for the 802 816-pixel bank): the same trade, dense
work for random accesses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "MAX_MXU_BINS",
    "MAX_PALLAS_BINS",
    "bincount_mxu",
    "bincount_pallas",
    "tpu_bincount",
]

#: Upper bound on the bin space (incl. dump bin) the kernel accepts: the
#: [block, n_bins] one-hot tile must fit VMEM alongside the output
#: (block=512 x 8192 floats = 16 MB is already the ceiling; the default
#: block shrinks as bins grow).
MAX_PALLAS_BINS = 8192


def _pick_block(n_bins_padded: int) -> int:
    """Largest event block whose one-hot tile stays ~4 MB of VMEM."""
    budget = 4 * 1024 * 1024 // 4  # floats
    block = budget // n_bins_padded
    # Power-of-two, within [128, 2048], multiple of 128 (lane width).
    block = max(128, min(2048, 1 << (block.bit_length() - 1)))
    return block


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _bincount_call(flat, n_bins_padded: int, block: int, interpret: bool):
    from jax.experimental import pallas as pl

    n = flat.shape[0]
    grid = n // block
    # Mosaic requires the last two dims of a block shape to be divisible
    # by (8, 128) or equal the array dims: a flat (grid, block) layout
    # with (1, block) blocks violates the sublane rule, so the event
    # stream is staged as (grid, 8, w) — the (8, w) tail covers the full
    # trailing dims and is always legal.
    w = block // 8
    rows = flat.reshape(grid, 8, w)

    def kernel(flat_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        bins = jax.lax.broadcasted_iota(
            jnp.int32, (w, n_bins_padded), 1
        )
        # Static unroll over the 8 sublane rows keeps every one-hot tile
        # 2-D (w x bins) — shapes Mosaic lowers well — instead of one
        # (block x bins) tile. Rows are loaded straight from the ref
        # (vector loads); slicing the loaded (8, w) value would lower to
        # a gather Mosaic rejects.
        acc = jnp.zeros((1, n_bins_padded), jnp.float32)
        for s in range(8):
            idx = flat_ref[0, s, :]  # [w] int32
            hits = (idx[:, None] == bins).astype(jnp.float32)
            acc = acc + hits.sum(axis=0, keepdims=True)
        out_ref[...] += acc

    # vma propagation: inside shard_map (the sharded Q kernels) the
    # per-shard delta varies over the mesh axes the events vary over;
    # check_vma requires the out_shape to say so.
    vma = jax.typeof(flat).vma
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 8, w), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_bins_padded), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (1, n_bins_padded), jnp.float32, vma=vma
        ),
        interpret=interpret,
        name="bincount_onehot",
    )(rows)[0]


def bincount_pallas(
    flat: jax.Array,
    n_bins: int,
    *,
    block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``[n]`` int32 flat bin indices -> ``[n_bins]`` float32 counts.

    Indices outside ``[0, n_bins)`` are dropped. ``interpret`` defaults
    to True off-TPU (tests) and False on TPU.
    """
    if n_bins > MAX_PALLAS_BINS:
        raise ValueError(
            f"bincount_pallas: {n_bins} bins exceed the VMEM bound "
            f"({MAX_PALLAS_BINS}); use the XLA scatter path"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if flat.shape[0] == 0:
        return jnp.zeros((n_bins,), jnp.float32)
    n_bins_padded = -(-n_bins // 128) * 128
    if block is None:
        block = _pick_block(n_bins_padded)
    if block % 8:
        raise ValueError("block must be a multiple of 8 (sublane staging)")
    flat = jnp.asarray(flat, jnp.int32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), -1, jnp.int32)]
        )
    counts = _bincount_call(flat, n_bins_padded, block, bool(interpret))
    return counts[:n_bins]


#: Upper bound on the bin space of ``bincount_mxu``: a [512, 128] float32
#: count tile, 256 KiB, which stays in VMEM for the whole grid.
MAX_MXU_BINS = 65536
#: Events a grid step of ``bincount_mxu``. 4 Mi slots into 34 000 /
#: 10 000 / 4 800 bins, ms by chunk (my chip run, PR 34): 2 048: 1.89 /
#: 0.82 / 0.71; 4 096: 1.73 / 0.68 / 0.58; 8 192: 1.65 / 0.60 / 0.50;
#: 16 384: 1.62 / 0.56 / 0.47. Past 8 192 little is left, and the
#: unrolled one-hots of 65 536 bins still compile inside VMEM's default.
MXU_CHUNK = 8192
#: Groups of 128 bins from which ``QHistogrammer(method="auto")`` takes
#: ``bincount_mxu`` and not the flat one-hot, on a TPU. By the table in
#: the module docstring the factorised kernel is the faster at every
#: group count measured, one included (4 Mi slots into 100 bins: 0.54
#: against 2.51 ms; ``scripts/tpu_kernel_check.py --bincount``, my chip
#: run, PR 34), so the crossover is no higher than 1; it stands at 2
#: because ISSUE 34 holds LOKI's one-group I(Q) on the flat kernel, so
#: that ``loki_iq.paced14`` stays the unchanged side of its before and
#: after (PERF.md section 7: what 1 would take there).
MXU_LANE_GROUPS = 2

_LANES = 128


def tpu_bincount(n_bins: int) -> str:
    """The ``method`` that counts ``n_bins`` bins fastest on a TPU: the
    flat one-hot (``"pallas"``) under ``MXU_LANE_GROUPS`` lane groups,
    ``"mxu"`` from there to one VMEM tile, XLA's ``"scatter"`` past it."""
    if n_bins > MAX_MXU_BINS:
        return "scatter"
    return "pallas" if -(-n_bins // _LANES) < MXU_LANE_GROUPS else "mxu"


def factorised_counts(rows_ref, h: int, *, base=None, oh_dtype=jnp.bfloat16):
    """Inside a kernel: the counts of one chunk of bin offsets as an
    ``[h, 128]`` tile, bin = 128 * hi + lo. ``rows_ref`` is the chunk's
    ``(1, 8, cw)`` block of int32 offsets (less ``base``, where given);
    one outside ``[0, 128 * h)`` has a ``hi`` that matches no row and
    counts nowhere. Both one-hots are built with the events on the
    lanes, as a row of the block arrives (``onehot(hi)`` ``[h, cw]``,
    ``onehot(lo)`` ``[128, cw]``: two VPU compares), and contracted
    over them on the MXU, the ``q @ k^T`` form. 0/1 operands are exact
    in bfloat16 (float32 accumulation) and in int8 (int32). The other
    orientation, events on the sublanes (``hi[:, None]``), has to move
    every event to a sublane first and took 3.3-10 times as long (4 Mi
    slots into 34 000 / 4 800 bins: 6.46 / 5.12 against 1.74 / 0.57 ms;
    my chip run, PR 34)."""
    cw = rows_ref.shape[2]
    acc_dtype = jnp.int32 if oh_dtype == jnp.int8 else jnp.float32
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (h, cw), 0)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (_LANES, cw), 0)
    counts = jnp.zeros((h, _LANES), acc_dtype)
    # Static unroll over the 8 sublane rows: each is loaded straight
    # from the ref (slicing a loaded (8, cw) value lowers to a gather
    # Mosaic rejects).
    for s in range(8):
        off = rows_ref[0, s : s + 1, :]  # [1, cw]
        if base is not None:
            off = off - base
        hi = off >> 7  # arithmetic shift: negatives stay < 0
        lo = off & (_LANES - 1)
        counts = counts + jax.lax.dot_general(
            (hi == iota_h).astype(oh_dtype),
            (lo == iota_l).astype(oh_dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dtype,
        )
    return counts


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _bincount_mxu_call(flat, h: int, chunk: int, interpret: bool):
    from jax.experimental import pallas as pl

    grid = flat.shape[0] // chunk
    # (grid, 8, chunk/8) for the same reason as ``_bincount_call``'s
    rows = flat.reshape(grid, 8, chunk // 8)

    def kernel(rows_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += factorised_counts(rows_ref, h)

    # the output's block index is constant, so the count tile stays in
    # VMEM for the whole grid and is written back once
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 8, chunk // 8), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((h, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (h, _LANES), jnp.float32, vma=jax.typeof(flat).vma
        ),
        interpret=interpret,
        name="bincount_mxu",
    )(rows).reshape(h * _LANES)


def bincount_mxu(
    flat: jax.Array, n_bins: int, *, interpret: bool | None = None
) -> jax.Array:
    """``[n]`` int32 flat bin indices -> ``[n_bins]`` float32 counts for
    ``n_bins <= MAX_MXU_BINS``, on the MXU (``factorised_counts``).

    Indices outside ``[0, n_bins)`` are dropped: they land in the count
    tile's padded tail, which is sliced off, or on no row of it. Exact
    while no bin passes 2**24. ``interpret`` as ``bincount_pallas``'s.

    The one-hots are bfloat16: int8 operands read 1.27 against 1.74 ms
    for 4 Mi slots into 34 000 bins and 0.72 / 0.62 against 0.67 / 0.57
    into 10 000 / 4 800 (my chip run, PR 34), under 2.5 ms of a tick
    either way, and bfloat16 accumulates straight into the float32 tile.
    """
    if n_bins > MAX_MXU_BINS:
        raise ValueError(
            f"bincount_mxu: {n_bins} bins exceed one VMEM tile "
            f"({MAX_MXU_BINS}); use the XLA scatter path"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if flat.shape[0] == 0:
        return jnp.zeros((n_bins,), jnp.float32)
    h = -(-n_bins // (8 * _LANES)) * 8  # whole (8, 128) float32 tiles
    flat = jnp.asarray(flat, jnp.int32)
    pad = (-flat.shape[0]) % MXU_CHUNK
    if pad:
        flat = jnp.concatenate([flat, jnp.full((pad,), -1, jnp.int32)])
    return _bincount_mxu_call(flat, h, MXU_CHUNK, bool(interpret))[:n_bins]
