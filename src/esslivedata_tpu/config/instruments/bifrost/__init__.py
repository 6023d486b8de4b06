"""BIFROST (indirect-geometry spectrometer): 45 analyzer triplets (5 arcs
x 9 channels) on 45 ev44 sources with a merged detector stream, S(Q, E)
and elastic Q maps over it, and a mesh-shardable multi-bank overview
(reference: config/instruments/bifrost; BASELINE config 3)."""

from . import specs  # noqa: F401
from .specs import INSTRUMENT

__all__ = ["INSTRUMENT"]
