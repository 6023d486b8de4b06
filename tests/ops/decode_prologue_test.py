"""Device decode prologue (ADR 0125): parity with the host sanitize pass.

The batch decode plane skips the per-message host ``sanitize_pixel_id``
and defers validation to one jitted device op fused into staging. These
tests pin the three contracts that make that safe: the jnp kernel and
the pallas kernel (interpret mode off-TPU) compute the same result, the
result matches what the host pass would have produced for wire-int32
inputs, and ``stage_raw`` actually applies the prologue to batches that
carry ``prologue=True`` — and only to those.
"""

import numpy as np
import pytest

from esslivedata_tpu.ops.decode_prologue import _BLOCK, decode_prologue
from esslivedata_tpu.ops.event_batch import (
    EventBatch,
    sanitize_pixel_id,
    stage_raw,
)


def _wire_pair(n, seed=0):
    """A staged-shape (pixel_id, toa) pair as the decode arena holds it:
    int32 ids (negatives = padding/hostile), float32 times."""
    rng = np.random.default_rng(seed)
    pid = rng.integers(-5, 100, n).astype(np.int32)
    toa = rng.uniform(0, 7e7, n).astype(np.float32)
    return pid, toa


class TestSemantics:
    @pytest.mark.parametrize("n", [0, 1, 17, 4096, 8192])
    def test_matches_host_sanitize(self, n):
        pid, toa = _wire_pair(n)
        out_pid, out_toa = decode_prologue(pid, toa)
        out_pid, out_toa = np.asarray(out_pid), np.asarray(out_toa)
        assert out_pid.dtype == np.int32
        assert out_toa.dtype == np.float32
        # Wire int32 passes the host sanitize unchanged; the prologue
        # additionally canonicalizes negatives to the -1 drop marker —
        # indistinguishable downstream (every kernel drops any id < 0).
        ref = np.asarray(sanitize_pixel_id(pid))
        np.testing.assert_array_equal(out_pid >= 0, ref >= 0)
        np.testing.assert_array_equal(out_pid[out_pid >= 0], ref[ref >= 0])
        assert (out_pid[pid < 0] == -1).all()
        np.testing.assert_array_equal(out_toa, toa)

    def test_float64_toa_normalized(self):
        pid = np.array([1, 2, 3], dtype=np.int32)
        toa = np.array([1.5, 2.5, 3.5], dtype=np.float64)
        _, out_toa = decode_prologue(pid, toa)
        assert np.asarray(out_toa).dtype == np.float32

    def test_empty_batch(self):
        pid, toa = decode_prologue(
            np.empty(0, dtype=np.int32), np.empty(0, dtype=np.float32)
        )
        assert np.asarray(pid).shape == (0,)
        assert np.asarray(toa).shape == (0,)


class TestKernelParity:
    """The pallas kernel (interpret mode) and the jnp fallback agree."""

    @pytest.mark.parametrize("n", [_BLOCK, 4 * _BLOCK])
    def test_interpret_matches_jnp(self, n):
        pid, toa = _wire_pair(n, seed=n)
        jnp_pid, jnp_toa = decode_prologue(pid, toa)
        pal_pid, pal_toa = decode_prologue(pid, toa, interpret=True)
        np.testing.assert_array_equal(np.asarray(pal_pid), np.asarray(jnp_pid))
        np.testing.assert_array_equal(np.asarray(pal_toa), np.asarray(jnp_toa))

    def test_off_block_shapes_take_jnp_kernel(self):
        # Shapes the pallas tiling does not cover must still work even
        # when interpret is requested — the dispatcher falls back.
        pid, toa = _wire_pair(_BLOCK + 1)
        out_pid, _ = decode_prologue(pid, toa, interpret=True)
        assert np.asarray(out_pid).shape == (_BLOCK + 1,)


class TestNoSilentFallback:
    def test_raising_pallas_call_is_not_swallowed(self, monkeypatch):
        """A kernel that fails to lower must surface (on TPU: a Mosaic
        refusal), never quietly become the jnp kernel."""
        from esslivedata_tpu.ops import decode_prologue as module

        def refuse(*_args):
            raise RuntimeError("Mosaic refused the kernel")

        monkeypatch.setattr(module, "_prologue_pallas", refuse)
        pid, toa = _wire_pair(_BLOCK)
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            decode_prologue(pid, toa, interpret=True)


class TestStageRawFusion:
    def _batch(self, prologue):
        pid = np.full(4096, -1, dtype=np.int32)
        toa = np.zeros(4096, dtype=np.float32)
        pid[:4] = np.array([3, -7, 0, 99], dtype=np.int32)
        toa[:4] = np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32)
        return EventBatch(pixel_id=pid, toa=toa, n_valid=4, prologue=prologue)

    def test_prologue_batch_sanitized_on_stage(self):
        staged_pid, staged_toa = stage_raw(self._batch(prologue=True))
        out = np.asarray(staged_pid)
        np.testing.assert_array_equal(out[:4], [3, -1, 0, 99])
        assert (out[4:] == -1).all()
        np.testing.assert_array_equal(
            np.asarray(staged_toa)[:4], [1.0, 2.0, 3.0, 4.0]
        )

    def test_plain_batch_staged_verbatim(self):
        staged_pid, _ = stage_raw(self._batch(prologue=False))
        # No prologue flag: the pair stages as-is (the eager path already
        # sanitized on the host) — -7 rides through untouched.
        np.testing.assert_array_equal(
            np.asarray(staged_pid)[:4], [3, -7, 0, 99]
        )

    def test_cached_staging_applies_prologue_once(self):
        class _Cache:
            def __init__(self):
                self.calls = {}

            def get_or_stage(self, key, fn):
                if key not in self.calls:
                    self.calls[key] = fn()
                return self.calls[key]

        cache = _Cache()
        batch = self._batch(prologue=True)
        first = stage_raw(batch, cache, tag="t")
        second = stage_raw(batch, cache, tag="t")
        assert first is second
        assert len(cache.calls) == 1
        np.testing.assert_array_equal(
            np.asarray(first[0])[:4], [3, -1, 0, 99]
        )
