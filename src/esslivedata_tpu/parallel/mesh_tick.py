"""Mesh-native tick serving: compile the ADR 0114 tick program onto a
data×bank mesh, and place every tick group on a mesh slice.

The single-device hot path runs a steady-state tick as ONE jitted
dispatch + ONE fetch (ops/tick.py). This module is the scale-out tier
that turns the standalone mesh demo (`__graft_entry__.py`'s dryrun) into
the real serving topology (ADR 0115, ROADMAP item 1):

- :class:`MeshTickCombiner` compiles the SAME tick program under a
  ``Mesh`` + ``PartitionSpec``: the staged event wire enters sharded
  ``P('data')``, each member's rolling histogram state ``P('bank',
  None)``, the collective step is the sharded kernel's shard_map body
  (delta_psum / event_gather exchange, parallel/sharded_hist.py), and
  the publish bodies run over mesh-replicated views — so the packed
  output vector is replicated and ONE ``device_get`` serves the whole
  mesh. Donation is preserved straight through the outer jit
  (SNIPPETS.md [1]–[2]: donation composes with pjit-style explicit
  shardings).

- :class:`DevicePlacement` makes the JobManager placement-aware: each
  (stream, fuse-key) tick group is assigned a mesh *slice* — a single
  device, round-robin over the mesh, for single-device histogrammers
  (K independent instrument streams spread across chips), or the WHOLE
  mesh for bank-sharded LOKI-scale jobs (whose state already spans it).
  The assignment is sticky for the group's lifetime, so staged wires,
  donated states and compiled programs never migrate between ticks;
  ``DeviceEventCache`` keys carry the slice, so each batch stages once
  per slice with the right placement (ADR 0110 extended per-slice).

Readback stays O(1) fetch per slice per tick: single-device slices fetch
their own packed vector; the mesh slice fetches one replicated vector.
Per-slice execute/fetch counts land in ``ops/publish.METRICS`` under
``slices``, so the bench (``bench.py --mesh``) asserts the contract
directly.
"""

from __future__ import annotations

import inspect
import logging
import threading
from dataclasses import dataclass
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.tick import TickCombiner

__all__ = ["DevicePlacement", "MeshTickCombiner", "TickSlice"]

logger = logging.getLogger(__name__)


class MeshTickCombiner(TickCombiner):
    """One execute + one replicated fetch for a whole mesh tick group.

    The program body is TickCombiner's verbatim — staged wire in, the
    group histogrammer's ``tick_step`` (here: the shard_map'ed
    collective step), each member's packed publish body over its
    stepped state — with one addition at the output seam: the packed
    vector and any static leaves are pinned to the replicated sharding,
    so GSPMD cannot leave them partially placed and the host-side
    ``device_get`` is a single-shard read however many devices the
    group spans. Per-member plan/unpack/containment machinery is shared
    with the base class (ADR 0113/0114), so the mesh path cannot
    diverge in spec handling or failure semantics.
    """

    #: Compile-event site label (telemetry, ADR 0116): mesh-program
    #: compiles are the expensive tier (GSPMD partitioning on top of
    #: XLA) and must decompose separately from single-device ticks.
    compile_site = "mesh_tick"

    def __init__(self, mesh: Mesh, max_programs: int = 16) -> None:
        super().__init__(max_programs)
        self._mesh = mesh
        self._replicated = NamedSharding(mesh, P())

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    def _finish_outputs(self, packed, statics):
        constrain = lambda x: jax.lax.with_sharding_constraint(  # noqa: E731
            x, self._replicated
        )
        packed = constrain(packed)
        statics = tuple(
            tuple(constrain(leaf) for leaf in member) for member in statics
        )
        return packed, statics


@dataclass(frozen=True)
class TickSlice:
    """One tick group's placement on the serving mesh.

    ``device`` is set for single-device slices (the group's staged wire
    and donated states are committed there); ``mesh``/``combiner`` are
    set for whole-mesh groups. ``label`` keys the per-slice METRICS
    breakdown.
    """

    label: str
    device: Any | None = None
    mesh: Mesh | None = None
    combiner: MeshTickCombiner | None = None


class DevicePlacement:
    """Sticky (stream, fuse-key) → mesh-slice assignment policy.

    Single-device tick groups land round-robin over the mesh's devices
    in first-seen order — the cheapest policy that spreads independent
    instrument streams across chips while keeping every group's
    placement stable (a migrating group would re-stage its wire,
    re-commit its donated states and recompile its tick program for
    nothing). Mesh-sharded groups (the histogrammer carries a ``mesh``)
    get the whole mesh and the shared :class:`MeshTickCombiner`.

    Thread-safety: ``assign`` is called from the JobManager's window
    path under load; the table mutates under a lock and entries are
    immutable after insertion.
    """

    def __init__(self, mesh: Mesh) -> None:
        self._mesh = mesh
        self._devices = list(mesh.devices.flat)
        self._lock = threading.Lock()
        self._slices: dict[tuple, TickSlice] = {}
        self._next = 0
        self._mesh_combiners: dict[tuple, MeshTickCombiner] = {}

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @staticmethod
    def _supports_device_staging(hist) -> bool:
        """True when the histogrammer's staging surface accepts the
        slice ``device=`` kwarg. Bespoke duck-typed histogrammers
        predating slice placement don't — forwarding the kwarg would
        TypeError every window — so their groups pin to the default
        placement instead of a device slice."""
        stage = getattr(hist, "tick_staging", None)
        if stage is None:
            return False
        try:
            return "device" in inspect.signature(stage).parameters
        except (TypeError, ValueError):  # builtins/partials: unknown
            return False

    def assign(self, stream: str, group_key, hist) -> TickSlice:
        """The (sticky) slice for one tick/fused group."""
        key = (stream, group_key)
        with self._lock:
            s = self._slices.get(key)
            if s is not None:
                return s
            group_mesh = getattr(hist, "mesh", None)
            if not isinstance(group_mesh, Mesh) and (
                not self._supports_device_staging(hist)
            ):
                # Sticky, labeled, but UN-placed: the group serves from
                # the default device exactly as without a placement.
                s = TickSlice(label="default")
                self._slices[key] = s
                return s
            if isinstance(group_mesh, Mesh):
                ids = tuple(
                    int(d.id) for d in group_mesh.devices.flat
                )
                combiner = self._mesh_combiners.get(ids)
                if combiner is None:
                    combiner = self._mesh_combiners[ids] = (
                        MeshTickCombiner(group_mesh)
                    )
                s = TickSlice(
                    label="mesh:" + ",".join(str(i) for i in ids),
                    mesh=group_mesh,
                    combiner=combiner,
                )
            else:
                dev = self._devices[self._next % len(self._devices)]
                self._next += 1
                s = TickSlice(label=f"device:{int(dev.id)}", device=dev)
            self._slices[key] = s
            logger.info(
                "placed tick group %r/%r on %s", stream, group_key, s.label
            )
            return s

    def slices(self) -> dict[tuple, TickSlice]:
        with self._lock:
            return dict(self._slices)

    @staticmethod
    def state_on(state, device) -> bool:
        """True when every array leaf of ``state`` already lives on
        ``device`` (metadata probe, no sync). Uncommitted leaves count
        as elsewhere on purpose: placement commits them (one transfer)
        so every later probe — including the private path's
        ``_state_slice_device``, which reads committedness — sees the
        slice."""
        from ..ops.event_batch import leaf_device_set

        for leaf in jax.tree_util.tree_leaves(state):
            ds = leaf_device_set(leaf)
            if ds is None:
                continue
            if ds != {device} or not getattr(leaf, "committed", True):
                return False
        return True

    @staticmethod
    def place_state(state, device):
        """``state`` with every array leaf committed to ``device`` —
        the one-off migration when a group is first assigned its slice
        (or recovers from a reset on the default device). One async
        transfer per leaf; steady-state ticks never pay it because the
        returned (donated) carries stay on the slice."""
        return jax.tree_util.tree_map(
            lambda leaf: (
                jax.device_put(leaf, device)
                if isinstance(leaf, jax.Array)
                else leaf
            ),
            state,
        )

    def ensure_state_on(self, ingest, device) -> None:
        """Move one ingest offer's state to ``device`` if it is not
        already committed there (sticky slices make this a no-op on
        every tick after the first)."""
        state = ingest.get_state()
        if self.state_on(state, device):
            return
        ingest.set_state(self.place_state(state, device))
