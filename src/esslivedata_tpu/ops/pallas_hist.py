"""Pallas TPU bincount kernel for VMEM-sized bin spaces.

XLA's TPU ``scatter_add`` executes on the scalar core, serially —
~11 ns/event measured at LOKI scale (see ops/histogram.py) — which makes
the scatter THE cost of a histogram step. For bin spaces that fit VMEM
(1-D monitor spectra ~1000 bins, SANS I(Q) ~100, powder composite
~3200), this kernel replaces the serial scatter with a vectorized
one-hot compare + reduction over event blocks: the grid walks event
blocks sequentially (TPU grid semantics), each step reduces a
``[block, n_bins]`` equality matrix on the VPU and accumulates into the
VMEM-resident output block, so throughput scales with vector width
instead of one event per cycle.

Out-of-range indices (negative padding, the dump overflow) match no
column and are dropped for free — same semantics as scatter's
``mode='drop'`` with negatives pre-routed.

The big 2-D pixel×TOF spaces (1.5M × 100 bins) do NOT fit VMEM; those
stay on the XLA scatter (``EventHistogrammer`` enforces the bound).

On non-TPU backends the kernel runs in interpret mode (slow, for
tests); ``EventHistogrammer(method='pallas')`` is the integration
point, and ``QHistogrammer(method='auto')`` takes it on a TPU.

Readings on a v5e (PERF.md sections 5 and 6): in LOKI's Q step this
kernel (``bincount_onehot`` in a trace) compares 4 Mi events against
128 lanes in 2.44 ms, 0.58 ns an event (PR 27), where XLA's scatter and
gather pay ~12 ns an entry. The table lookup in front of it was 52.0 ms
of that step as a gather and is ops/pallas_lookup.py's since PR 28
(a 3.4 ms key sort + 4.9 ms of dense windows on the MXU for the
802 816-pixel bank): the same trade, dense work for random accesses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["MAX_PALLAS_BINS", "bincount_pallas"]

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
