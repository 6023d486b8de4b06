"""A camera's frames summed exactly, on the device (ADR 0132).

An ad00 camera sends a dense frame a pulse: the ORCA-Flash4.0's is
2048 x 2048 uint16, 8 MiB. A window's frames summed in the frame's own
type wrap past 65 535 counts a pixel, so here they are summed in uint32,
in two device-resident states: the window, to which every frame is added
by one jitted program as it comes (the state donated, the frame staged
through the kept host buffers of ``ops/staging_pool.py``), and the
cumulative, into which the window is folded at publish. The publish
program also shows both images as the view's parameters say
(transposed, then flipped in y, then in x), so the two images it returns
are contiguous, and both come back in one fetch.

**How long uint32 holds.** A pixel at the top of a 16-bit frame every
frame reaches 2**32 - 1 after (2**32 - 1) // 65 535 = 65 537 frames: 78
minutes at 14 frames a second. Past it a sum does not wrap: every add
saturates at 2**32 - 1, so a pixel that got there reads 4 294 967 295
from then on, and the first publish that finds one logs a warning. A
restart (a new run, a reset, a new ROI) starts both sums at 0.

**What the device does not take.** A frame of another type than an
unsigned integer of at most 16 bits is summed on the host, in a type no
narrower than its own that does not wrap: int64 for the other integers
(uint64 for uint64), float64 for a float frame, whose sum is a float
sum in any type. No camera of the package sends one.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from .event_batch import dispatch_safe

__all__ = [
    "FrameSums",
    "UINT32_MAX",
    "host_sum_dtype",
    "on_device",
]

logger = logging.getLogger(__name__)

UINT32_MAX = int(np.iinfo(np.uint32).max)
_SATURATED = np.uint32(UINT32_MAX)


def on_device(dtype) -> bool:
    """Whether a frame of ``dtype`` is summed on the device: an unsigned
    integer of at most 16 bits, whose sums uint32 holds exactly."""
    dtype = np.dtype(dtype)
    return dtype.kind == "u" and dtype.itemsize <= 2


def host_sum_dtype(dtype) -> np.dtype:
    """The type a frame the device does not take is summed in."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f":
        return np.dtype(np.float64)
    if dtype == np.uint64:
        return np.dtype(np.uint64)
    return np.dtype(np.int64)


def _saturating_add(total, part):
    """``total + part`` in uint32, a wrapped sum read as 2**32 - 1: the
    sum is smaller than ``total`` exactly where it wrapped."""
    summed = total + part.astype(jnp.uint32)
    return jnp.where(summed < total, _SATURATED, summed)


def _shown(image, transpose: bool, flip_y: bool, flip_x: bool):
    if transpose:
        image = image.T
    if flip_y:
        image = image[::-1, :]
    if flip_x:
        image = image[:, ::-1]
    return image


def _add_impl(window, frame):
    return _saturating_add(window, frame)


def _publish_impl(window, cumulative, *, transpose, flip_y, flip_x):
    """Fold the window into the cumulative; return both states (the
    window started again) and the two images as shown, with the
    cumulative's largest pixel."""
    cumulative = _saturating_add(cumulative, window)
    return (
        jnp.zeros_like(window),
        cumulative,
        _shown(window, transpose, flip_y, flip_x),
        _shown(cumulative, transpose, flip_y, flip_x),
        jnp.max(cumulative),
    )


_add = jax.jit(_add_impl, donate_argnums=(0,))
_publish = jax.jit(
    _publish_impl,
    donate_argnums=(0, 1),
    static_argnames=("transpose", "flip_y", "flip_x"),
)
_zeros = jax.jit(
    lambda shape: jnp.zeros(shape, jnp.uint32), static_argnums=(0,)
)


class FrameSums:
    """The window and cumulative sums of one camera's frames of one
    shape, unit and path: on the device in uint32 where ``on_device``
    takes the frame's type, on the host in ``host_sum_dtype`` where it
    does not. A frame that does not ``fit`` asks its owner for new sums
    (the restart on a structure change)."""

    def __init__(
        self,
        frame: np.ndarray,
        unit: str,
        *,
        transpose: bool = False,
        flip_y: bool = False,
        flip_x: bool = False,
    ) -> None:
        self.shape = tuple(frame.shape)
        self.unit = unit
        self.device = on_device(frame.dtype)
        self._sum_dtype = (
            np.dtype(np.uint32) if self.device else host_sum_dtype(frame.dtype)
        )
        self._transform = {
            "transpose": transpose, "flip_y": flip_y, "flip_x": flip_x
        }
        self._saturated = False
        if self.device:
            self._window = _zeros(self.shape)
            self._cumulative = _zeros(self.shape)
        else:
            self._window = np.zeros(self.shape, self._sum_dtype)
            self._cumulative = np.zeros(self.shape, self._sum_dtype)

    def fits(self, frame: np.ndarray, unit: str) -> bool:
        """Whether ``frame`` adds into these sums: the same shape and
        unit, and a type summed on the same path in the same type."""
        if frame.shape != self.shape or unit != self.unit:
            return False
        if self.device:
            return on_device(frame.dtype)
        return not on_device(frame.dtype) and (
            host_sum_dtype(frame.dtype) == self._sum_dtype
        )

    def add(self, frame: np.ndarray) -> None:
        """Add one frame to the window: on the device, the frame staged
        through a kept host buffer, then one asynchronous dispatch."""
        if self.device:
            self._window = _add(self._window, dispatch_safe(frame))
        else:
            self._window += frame

    def publish(self) -> tuple[np.ndarray, np.ndarray]:
        """Fold the window into the cumulative and start it again;
        return the two images (window, cumulative) as shown."""
        if not self.device:
            self._cumulative += self._window
            window, self._window = self._window, np.zeros_like(self._window)
            return (
                np.ascontiguousarray(_shown(window, **self._transform)),
                np.array(_shown(self._cumulative, **self._transform)),
            )
        self._window, self._cumulative, window, cumulative, peak = _publish(
            self._window, self._cumulative, **self._transform
        )
        window, cumulative, peak = jax.device_get((window, cumulative, peak))
        if int(peak) == UINT32_MAX and not self._saturated:
            self._saturated = True
            logger.warning(
                "camera view saturated: a pixel of the %s cumulative "
                "reached %d counts and holds there until the next "
                "restart",
                "x".join(map(str, self.shape)),
                UINT32_MAX,
            )
        return window, cumulative
