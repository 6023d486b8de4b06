"""Device decode prologue: wire sanitize/validation as a staged device op.

The per-message decode path sanitizes pixel ids on the host
(``event_batch.sanitize_pixel_id``) while flattening chunk lists — a
pass the batch decode plane (ADR 0125) deliberately skips: payloads
land straight off the wire into the decode arena with no per-message
host work. The validation still has to happen SOMEWHERE before the
tick kernels index with the ids, so it moves here, onto the device,
fused into staging: ``stage_raw`` applies :func:`decode_prologue` to
the staged ``(pixel_id, toa)`` pair once per (stream, tag) window key.

Semantics match the host pass exactly where it matters: any id a
kernel would treat as out-of-range (negative — wire ids are int32, so
unrepresentable-width clamping does not arise) canonicalizes to -1,
the universal drop/padding marker, and the time-of-arrival lane is
normalized to float32. Every downstream kernel (scatter ``mode='drop'``,
the pallas one-hot bincount, the partitioned shard kernels) drops -1
and any other out-of-range id identically, which is why the prologue
can canonicalize without changing a single published da00 byte — the
byte-identity contract batch decode is pinned to.

The elementwise pass runs as a pallas VPU kernel on TPU (same staging
shape discipline as ops/pallas_hist.py: ``(grid, 8, w)`` blocks for the
Mosaic sublane rule) and as plain ``jnp`` everywhere else — including
shapes the pallas tiling does not cover. Both are jitted; the jnp
fallback fuses into two elementwise kernels on any backend, so the
pallas path is an on-TPU locality optimization, not a requirement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["decode_prologue"]

#: Event block per pallas grid step: 8 sublanes x 128 lanes x 4 rows.
_BLOCK = 4096


@functools.partial(jax.jit, static_argnums=(2,))
def _prologue_jnp(pixel_id, toa, _interpret=False):
    pid = jnp.asarray(pixel_id, jnp.int32)
    # Weak-typed -1 folds into the int32 where() at trace time.
    pid = jnp.where(pid < 0, -1, pid)
    return pid, jnp.asarray(toa, jnp.float32)


@functools.partial(jax.jit, static_argnums=(2,))
def _prologue_pallas(pixel_id, toa, interpret: bool):
    from jax.experimental import pallas as pl

    n = pixel_id.shape[0]
    grid = n // _BLOCK
    w = _BLOCK // 8
    pid_rows = jnp.asarray(pixel_id, jnp.int32).reshape(grid, 8, w)
    toa_rows = jnp.asarray(toa, jnp.float32).reshape(grid, 8, w)

    def kernel(pid_ref, toa_ref, pid_out, toa_out):
        pid = pid_ref[...]
        pid_out[...] = jnp.where(pid < 0, -1, pid)
        toa_out[...] = toa_ref[...]

    spec = pl.BlockSpec((1, 8, w), lambda i: (i, 0, 0))
    pid_o, toa_o = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((grid, 8, w), jnp.int32),
            jax.ShapeDtypeStruct((grid, 8, w), jnp.float32),
        ],
        interpret=interpret,
        name="decode_prologue",
    )(pid_rows, toa_rows)
    return pid_o.reshape(n), toa_o.reshape(n)


def decode_prologue(pixel_id, toa, *, interpret: bool | None = None):
    """Sanitize a staged wire pair on device: ``(int32 ids with
    negatives canonicalized to -1, float32 times of arrival)``.

    Batch sizes are already power-of-two bucketed (>= 4096,
    ``event_batch.bucket_size``), so the pallas tiling always divides
    evenly on the staged path; any other shape — callers outside the
    staging contract, zero-length probes — takes the jnp kernel, which
    is semantically identical. Off-TPU the jnp kernel is also the
    DEFAULT (interpret-mode pallas is a test vehicle, not a fast path);
    pass ``interpret=True`` explicitly to exercise the pallas kernel
    without hardware. On TPU a kernel that fails to lower RAISES: a
    Mosaic refusal must surface, not hide behind the jnp kernel.
    """
    n = int(pixel_id.shape[0])
    if n == 0 or n % _BLOCK:
        return _prologue_jnp(pixel_id, toa, False)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _prologue_jnp(pixel_id, toa, False)
        interpret = False
    return _prologue_pallas(pixel_id, toa, bool(interpret))
