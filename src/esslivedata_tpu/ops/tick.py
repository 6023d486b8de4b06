"""One dispatch per tick: the fused stage→step→publish device program.

A steady-state ingest tick used to pay up to three device dispatches:
the staging transfer on a ``DeviceEventCache`` miss, the fused
``step_many`` dispatch, and the combined publish execute + fetch
(ADR 0113). The step and publish halves were already each one dispatch
— but they were *separate* dispatches.

:class:`TickCombiner` closes the gap (ADR 0114): for each (stream,
fuse-key) group of same-layout jobs due in a publish tick it builds ONE
jitted **tick program** that

- consumes the group's staged event arrays exactly as ``step_many``
  would (``EventHistogrammer.tick_staging`` — same cache keys, so a
  prestaged window is a guaranteed hit and the wire stages once however
  many jobs subscribe),
- advances every member's donated rolling state with the SAME traceable
  fused-step body the standalone ``step_many`` jit runs
  (``EventHistogrammer.tick_step`` — per-state op order unchanged, so
  tick results are bit-identical to the three-dispatch path), and
- feeds each stepped state straight into that member's packed publish
  body (``PackedPublisher._packed_impl``), concatenating the per-member
  packed vectors into one fetch with the ADR 0113 static/dynamic output
  split carried through verbatim.

A steady-state tick is then ONE execute + ONE ``device_get``. Donation
is shifted like the combiner's: each member's pre-step state enters at
its flat position and is donated there (the step consumes it; the
publish fold reuses the buffers), plus any further donated argnums the
member's publisher declares. Staged event arrays are never donated —
other consumers of the window (private-path fallbacks, parity paths)
share them by reference.

Keying: the jitted-program LRU is keyed on (histogrammer identity, the
group's fuse key + batch tag, the staged wire's signature, the exact
member tuple). The fuse key already folds in the projection layout
digest and — for ``method='pallas2d'`` — the wire format, so a live LUT
swap or a link-policy int32↔uint16 flip re-keys cleanly: the next tick
compiles (marked via ``last_compiled`` so RTT observers skip it, the
ADR 0113 mechanism) and staged payloads can never meet a program traced
for the other wire. The staged signature is in the key so a batch-shape
change is also visible as a compile, not silently folded into the RTT
estimate.

Containment mirrors ADR 0113 exactly (the plan/unpack machinery is
shared with :class:`~.publish.PublishCombiner`): a member whose plan
fails at abstract evaluation drops out before the dispatch; a member
whose unpack fails still adopts its folded carry; a dispatch failure
after donation reports ``state_lost`` per member so the caller can
rebuild exactly the states that were consumed, leaving every other
member intact.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp

from ..telemetry.trace import TRACER
from .publish import (
    METRICS,
    CombinedPublish,
    PackedPublisher,
    PublishRequest,
    fetch_outputs,
    member_signature,
    program_name,
    plan_members,
    publish_args_consumed,
    signature_fingerprint,
    unpack_members,
)

__all__ = ["TickCombiner"]

logger = logging.getLogger(__name__)


class TickCombiner:
    """One execute + one packed fetch for a whole (step + publish) tick.

    Builds (and LRU-caches) a jitted tick program per exact
    (histogrammer, group key, staged signature, member tuple): the
    group's fused step runs first, then each member's packed publish
    body over its stepped state, all under one ``jax.jit``. Member
    composition changes at command time and layouts/wire formats flip
    rarely (hysteresis-latched), so recompiles are rare; the cache bound
    caps how many retired programs (and the publishers/histogrammers
    they close over) stay alive.
    """

    def __init__(self, max_programs: int = 16) -> None:
        self._programs: OrderedDict[tuple, Callable] = OrderedDict()
        self._max_programs = int(max_programs)
        # The LRU is touched from TWO threads since ADR 0118: the step
        # worker's publish() and the warm-up thread's warm() (insert +
        # eviction). An unlocked move_to_end racing a concurrent
        # eviction is a KeyError in the middle of a live tick; the
        # lock covers only dict operations (never a build/compile), so
        # it costs nanoseconds against a millisecond tick.
        self._programs_lock = threading.Lock()
        #: True when the last ``publish`` compiled its program (cache
        #: miss). RTT observers must skip those rounds — same contract
        #: as ``PublishCombiner.last_compiled`` (ADR 0113): a tick
        #: compile is one-off XLA work, and folding it into the EWMA
        #: publish RTT would latch the coalescing policy on every
        #: startup, layout swap or wire flip.
        self.last_compiled = False

    def publish(
        self,
        hist,
        group_key,
        staged: tuple,
        requests: Sequence[PublishRequest],
        *,
        slice_key: str | None = None,
    ) -> list[CombinedPublish]:
        """Run one tick program: step every member's state (``args[0]``
        of its request, the ``make_publish_offer`` contract) from the
        shared ``staged`` arrays, then serve every member's publish from
        the one packed fetch.

        ``hist`` is the group's (shared-configuration) histogrammer —
        its ``tick_step`` is the traceable fused step; ``group_key`` is
        the fused-stepping group key (fuse key + batch tag);
        ``staged`` is ``tick_staging``'s flat tuple of device arrays.
        ``slice_key`` (mesh serving, ADR 0115) labels the mesh slice
        this group executes on for the per-slice METRICS breakdown.
        """
        plan, planned_errors = plan_members(requests)
        if not plan:
            return [
                CombinedPublish(None, (), error=planned_errors.get(i))
                for i in range(len(requests))
            ]
        key = self._program_key(hist, group_key, staged, plan)
        with self._programs_lock:
            fn = self._programs.get(key)
            self.last_compiled = fn is None
            if fn is not None:
                # LRU touch: the steady-state program runs every tick
                # and must never be the eviction victim of key churn
                # (layout swaps, wire flips) — eviction means a
                # surprise whole-tick recompile in the hot path.
                self._programs.move_to_end(key)
        if fn is None:
            fn = self._build(
                hist,
                len(staged),
                [
                    (req.publisher, len(req.args), skeys, include_static)
                    for _i, req, skeys, _spec, _names, include_static, _c, _s
                    in plan
                ],
            )
            with self._programs_lock:
                self._programs[key] = fn
                self._programs.move_to_end(key)
                while len(self._programs) > self._max_programs:
                    self._programs.popitem(last=False)
        flat_args = tuple(staged) + tuple(
            a for _i, req, *_ in plan for a in req.args
        )
        by_index: dict[int, CombinedPublish] = {
            i: CombinedPublish(None, (), error=err)
            for i, err in planned_errors.items()
        }
        try:
            if self.last_compiled:
                # Compile-event instrument (ADR 0116): the first call of
                # a fresh program pays trace + XLA compile + execute —
                # the stall PERF round 7 could only EXCLUDE from RTT
                # estimates. Time it and label WHY the key missed
                # (layout swap / wire flip / batch shape / new group) so
                # compile spikes decompose on the scrape. The execute is
                # async-dispatched; the device_get inside the timed
                # region bounds the compile+first-round wall time. No
                # tick/fetch spans on compile rounds — they would put a
                # compile stall in the steady-state span histograms,
                # the exact confusion the compile instrument exists to
                # prevent.
                t0 = time.perf_counter()
                packed, statics, carries = fn(*flat_args)
                flat, static_fetched = jax.device_get((packed, statics))
                self._record_compile(
                    hist, group_key, key, plan, time.perf_counter() - t0
                )
            else:
                # Per-tick tracer spans (ADR 0116), against the step
                # worker's thread-bound trace id: the dispatch (host
                # Python + ASYNC submit: it returns before the program
                # has run) and the fetch (the wait for the chip, then
                # the copy back: what a steady-state tick actually
                # waits on) decompose separately in the slow-tick
                # breakdown.
                with TRACER.span("tick_execute"):
                    packed, statics, carries = fn(*flat_args)
                flat, static_fetched = fetch_outputs((packed, statics))
        except Exception as err:
            # Dispatch-level failure: per-member containment happens at
            # the caller, which needs to know whose donated state the
            # failed dispatch already consumed (state_lost — the step
            # donates every member state, so a runtime failure may have
            # invalidated all of them). The cached program is evicted:
            # a poisoned entry (an AOT-warmed executable whose input
            # placement drifted, a backend error pinned to this
            # compilation) must not fail every later tick — the next
            # tick recompiles fresh instead.
            with self._programs_lock:
                self._programs.pop(key, None)
            logger.exception(
                "tick program dispatch failed (%d jobs)", len(plan)
            )
            for _i, req, *_ in plan:
                by_index[_i] = CombinedPublish(
                    None,
                    (),
                    error=err,
                    state_lost=publish_args_consumed(req.args),
                )
            return [by_index[i] for i in range(len(requests))]
        static_total = unpack_members(
            plan, flat, static_fetched, carries, by_index
        )
        METRICS.record(
            executes=1,
            fetches=1,
            dynamic_bytes=int(flat.nbytes),
            static_bytes=static_total,
            combined_jobs=len(plan),
            tick=True,
            slice_key=slice_key,
        )
        return [by_index[i] for i in range(len(requests))]

    @staticmethod
    def _program_key(hist, group_key, staged: tuple, plan: list) -> tuple:
        """The program-LRU key for one planned tick — shared by the
        live path and the AOT warm-up (durability/warmup.py) so the two
        can never compute different keys for the same program."""
        return (
            hist,
            group_key,
            PackedPublisher._signature(staged),
            member_signature(plan),
        )

    def warm(
        self,
        hist,
        group_key,
        staged: tuple,
        requests: Sequence[PublishRequest],
    ) -> int:
        """AOT-compile the tick program(s) for this group and seed the
        program LRU, so the group's next LIVE tick is a cache hit — no
        compile stall on the hot path, no ``livedata_jit_compiles_total``
        event at commit time (the durability plane's warm-up contract,
        ADR 0118).

        ``staged`` may be synthetic (a zero-filled batch staged to the
        group's device): only its signature reaches the key, and
        lowering reads avals, never values. Member ``requests`` may
        carry :class:`jax.ShapeDtypeStruct` trees in place of the live
        state arrays — ``member_signature`` is shape/dtype-based, so
        the warmed key equals the live key exactly, and nothing here
        can touch (or donate) a live buffer.

        Both program variants a fresh member set needs are warmed: the
        plan as it stands now (static-inclusive for members whose
        static token has not been fetched yet — the first post-commit
        tick) and the all-static-excluded steady-state variant. Returns
        the number of programs actually compiled (0 = already warm).
        Failures raise to the caller (the warm-up service contains and
        counts them); nothing is inserted on failure, so the live path
        compiles honestly — the instrument then reports the miss
        instead of a warmed lie.
        """
        plan, _planned_errors = plan_members(requests)
        if not plan:
            return 0
        variants = [plan]
        steady = [
            (i, req, skeys, dyn_spec, static_names, False, cached, size)
            for i, req, skeys, dyn_spec, static_names, _inc, cached, size
            in plan
        ]
        if member_signature(steady) != member_signature(plan):
            variants.append(steady)
        compiled = 0
        for variant in variants:
            key = self._program_key(hist, group_key, staged, variant)
            with self._programs_lock:
                if key in self._programs:
                    continue
            fn = self._build(
                hist,
                len(staged),
                [
                    (req.publisher, len(req.args), skeys, include_static)
                    for _i, req, skeys, _spec, _names, include_static, _c,
                    _s in variant
                ],
            )
            flat_args = tuple(staged) + tuple(
                a for _i, req, *_ in variant for a in req.args
            )
            # The stored entry is the AOT EXECUTABLE, not the jit
            # wrapper: a jit fn seeded here would still trace+compile on
            # its first live call, making the warmed 0-compile claim a
            # lie. ``Compiled`` validates avals at call time, so a
            # signature drift surfaces as a contained dispatch error
            # (and the eviction above recompiles fresh), never a wrong
            # result.
            executable = fn.lower(*flat_args).compile()
            with self._programs_lock:
                # A live tick may have compiled the same key while we
                # lowered: its program is serving, never clobber it.
                if key not in self._programs:
                    self._programs[key] = executable
                    self._programs.move_to_end(key)
                    while len(self._programs) > self._max_programs:
                        self._programs.popitem(last=False)
            compiled += 1
        return compiled

    #: Compile-site label for the instrument; the mesh subclass
    #: (parallel/mesh_tick.py) overrides to "mesh_tick".
    compile_site = "tick"

    def _record_compile(
        self, hist, group_key, key, plan, seconds: float
    ) -> None:
        """Classify + record one tick-program compile (best-effort: the
        instrument must never take a tick down)."""
        try:
            from ..telemetry.compile import COMPILE_EVENTS

            COMPILE_EVENTS.classify_and_record(
                self.compile_site,
                # WHO is compiling: this histogrammer serving this
                # publisher set. The key dimensions that churn (layout,
                # wire, staged shape, residual key material) are passed
                # separately for trigger classification.
                (id(hist), tuple(id(req.publisher) for _i, req, *_ in plan)),
                seconds,
                layout_digest=getattr(hist, "layout_digest", None),
                wire=getattr(hist, "wire_format", None),
                staged_sig=key[2],
                # Object-free residual: the raw member signature holds
                # live publishers, which must not be pinned in the
                # recorder's memory past their program's LRU life.
                residual=(group_key, signature_fingerprint(key[3])),
            )
        except Exception:  # pragma: no cover - telemetry is advisory
            logger.debug("compile-event recording failed", exc_info=True)

    def _finish_outputs(self, packed, statics):
        """Hook between the traced publish bodies and the program's
        outputs. The base combiner passes through; the mesh combiner
        (parallel/mesh_tick.py) pins a replicated sharding here so one
        ``device_get`` serves the whole mesh (ADR 0115)."""
        return packed, statics

    def _build(
        self,
        hist,
        n_staged: int,
        members: list[tuple[PackedPublisher, int, frozenset, bool]],
    ) -> Callable:
        # Flat argument layout: [staged wire..., member0 args...,
        # member1 args..., ...] with each member's state at local
        # position 0 (the make_publish_offer contract).
        state_offsets: list[int] = []
        offset = 0
        for _pub, n_args, _skeys, _inc in members:
            state_offsets.append(offset)
            offset += n_args

        def tick(*args):
            staged = args[:n_staged]
            flat = args[n_staged:]
            states = tuple(flat[o] for o in state_offsets)
            with jax.named_scope("scatter"):
                new_states = hist.tick_step(states, *staged)
            parts, statics, carries = [], [], []
            for j, (pub, n_args, skeys, include_static) in enumerate(
                members
            ):
                o = state_offsets[j]
                packed, stat, *carry = pub._packed_impl(
                    skeys,
                    include_static,
                    new_states[j],
                    *flat[o + 1 : o + n_args],
                )
                parts.append(packed)
                statics.append(stat)
                carries.append(tuple(carry))
            with jax.named_scope("pack"):
                packed_all = (
                    jnp.concatenate(parts)
                    if parts
                    else jnp.zeros((0,), jnp.float32)
                )
            packed_all, statics = self._finish_outputs(
                packed_all, statics
            )
            return packed_all, tuple(statics), tuple(carries)

        # A stable name per workflow family (``tick_detector_view``):
        # what the device trace and the compile log call the program.
        tick.__name__ = program_name("tick", [m[0] for m in members])

        # Shifted donation: member states (and any further publisher
        # donations) keep their donated positions behind the staged
        # prefix. The staged arrays are shared with other window
        # consumers and are NEVER donated.
        donate: list[int] = []
        offset = n_staged
        for pub, n_args, _skeys, _inc in members:
            donate.extend(offset + d for d in pub._donate if d < n_args)
            offset += n_args
        return jax.jit(tick, donate_argnums=tuple(donate))
