"""LOKI bank geometry, loaded from the NeXus geometry artifact.

The positions and pixel ids come from the date-resolved geometry file
(``config/geometry_store.py`` — reference parity:
preprocessors/detector_data.py:66-127, where real deployments fetch the
artifact with pooch and ``LIVEDATA_DATA_DIR`` overrides the cache). The
synthesized artifact carries the nine straw-tube banks
``loki_detector_0`` .. ``_8`` at their deployed size (3 211 264 pixels;
the plan, by formula: ``config/nexus_plans.py``) and, beside them, the
toy ``larmor_detector``: a 256x256 pixel plane, 1 m x 1 m, 5 m
downstream of the sample, on which the detector view and the package's
own tests run. A real ESS file dropped into the cache is picked up with
no code change.

Nothing is read at import: ``bank_geometry`` is what a
``DetectorConfig.geometry_loader`` calls when a job on the bank starts.
"""

from __future__ import annotations

import math

import numpy as np

from ...nexus_plans import plan_for

#: Pixels of each straw-tube bank, by the plan's shapes (no file is read).
BANK_PIXELS = {
    bank.name: math.prod(bank.shape)
    for bank in plan_for("loki").banks
    if bank.panel is not None
}


def bank_geometry(bank: str) -> tuple[np.ndarray, np.ndarray]:
    """Returns ([n, 3] positions in m, [n] pixel ids) of one bank of the
    geometry file valid today."""
    from ...geometry_store import geometry_path, load_detector_geometry

    return load_detector_geometry(geometry_path("loki"), bank)
