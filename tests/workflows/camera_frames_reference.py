"""What a camera view should publish, in plain numpy, for the tests.

Independent of the package: the frames are summed in float64, which
holds every sum of uint16 counts these tests reach exactly, and shown
as the view's parameters say: transposed, then flipped in y, then in x.
``current`` is the sum of the frames since the previous publish,
``cumulative`` that of every frame since the last restart; a frame of
another shape than the one before it restarts both.
"""

from __future__ import annotations

import numpy as np


def shown(image, transpose=False, flip_y=False, flip_x=False):
    if transpose:
        image = image.T
    if flip_y:
        image = image[::-1, :]
    if flip_x:
        image = image[:, ::-1]
    return image


def publishes(windows, **params):
    """``windows`` is a list of windows, each a list of 2-D frames; the
    (current, cumulative) pair each window's publish should show."""
    out = []
    current = cumulative = None
    for frames in windows:
        for frame in frames:
            frame = np.asarray(frame, np.float64)
            if cumulative is None or frame.shape != cumulative.shape:
                current = np.zeros(frame.shape)
                cumulative = np.zeros(frame.shape)
            current += frame
            cumulative += frame
        out.append((shown(current, **params), shown(cumulative.copy(), **params)))
        current = np.zeros(cumulative.shape)
    return out


def camera_frames(rng, n, shape, mean_counts, dtype=np.uint16):
    """``n`` frames of Poisson counts of ``mean_counts`` over a flat
    field and eight times that in a disc at the centre (the beam spot),
    clipped to ``dtype``."""
    ny, nx = shape
    y, x = np.ogrid[:ny, :nx]
    spot = np.hypot(y - (ny - 1) / 2, x - (nx - 1) / 2) <= 0.2 * min(ny, nx)
    mean = mean_counts * np.where(spot, 8.0, 1.0)
    top = np.iinfo(dtype).max
    return [np.minimum(rng.poisson(mean), top).astype(dtype) for _ in range(n)]
