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

A steady-state tick is then ONE execute + ONE ``device_get`` per group.
The two are separate calls, :meth:`TickCombiner.dispatch` (asynchronous)
and :meth:`TickCombiner.collect`, so that the JobManager can dispatch
every group of a tick before it collects the first: the host stages
group i+1 while the chip runs group i. Nothing in the contract of one
group changes by that. Donation
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
swap re-keys cleanly: the next tick compiles (``compiled`` on its
handle) and staged payloads can never meet a program traced for another
layout or wire. The staged signature is in the key so a batch-shape
change is also visible as a compile.

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
from dataclasses import dataclass

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

__all__ = ["PendingTick", "TickCombiner"]

logger = logging.getLogger(__name__)


@dataclass
class PendingTick:
    """One group's tick program between :meth:`TickCombiner.dispatch`
    and :meth:`TickCombiner.collect`: the plan, the program's LRU key
    and what it returned, not yet fetched. Opaque to the caller but for
    ``compiled``."""

    n_requests: int
    plan: list[tuple]
    #: Results so far: plan-time errors, then whatever ``collect``
    #: unpacks or a failure fills in.
    by_index: dict[int, CombinedPublish]
    slice_key: str | None = None
    key: tuple | None = None
    #: The dispatch missed the program LRU and ran the compile round
    #: to its end.
    compiled: bool = False
    #: ``(packed, statics)`` on the device, their copies enqueued.
    outputs: tuple | None = None
    #: The same on the host: a compile round's, fetched at dispatch.
    fetched: tuple | None = None
    carries: tuple = ()


class TickCombiner:
    """One execute + one packed fetch for a whole (step + publish) tick.

    Builds (and LRU-caches) a jitted tick program per exact
    (histogrammer, group key, staged signature, member tuple): the
    group's fused step runs first, then each member's packed publish
    body over its stepped state, all under one ``jax.jit``. Member
    composition changes at command time and layouts swap rarely, so
    recompiles are rare; the cache bound
    caps how many retired programs (and the publishers/histogrammers
    they close over) stay alive.
    """

    def __init__(self, max_programs: int = 16) -> None:
        self._programs: OrderedDict[tuple, Callable] = OrderedDict()
        self._max_programs = int(max_programs)
        # The LRU is touched from TWO threads since ADR 0118: the step
        # worker's dispatch() and the warm-up thread's warm() (insert +
        # eviction). An unlocked move_to_end racing a concurrent
        # eviction is a KeyError in the middle of a live tick; the
        # lock covers only dict operations (never a build/compile), so
        # it costs nanoseconds against a millisecond tick.
        self._programs_lock = threading.Lock()

    def publish(
        self,
        hist,
        group_key,
        staged: tuple,
        requests: Sequence[PublishRequest],
        *,
        slice_key: str | None = None,
    ) -> list[CombinedPublish]:
        """Run one tick program to its end: ``collect(dispatch(...))``,
        for the callers that have one group (tests, parity paths). The
        JobManager calls the two halves itself, every group's dispatch
        before the first collect."""
        return self.collect(
            self.dispatch(
                hist, group_key, staged, requests, slice_key=slice_key
            )
        )

    def dispatch(
        self,
        hist,
        group_key,
        staged: tuple,
        requests: Sequence[PublishRequest],
        *,
        slice_key: str | None = None,
    ) -> PendingTick:
        """Submit one tick program and return without waiting for it:
        step every member's state (``args[0]`` of its request, the
        ``make_publish_offer`` contract) from the shared ``staged``
        arrays, then pack every member's publish into one vector whose
        copy to the host is enqueued here, so that it starts the moment
        the program ends. :meth:`collect` waits for it and serves the
        members.

        ``hist`` is the group's (shared-configuration) histogrammer —
        its ``tick_step`` is the traceable fused step; ``group_key`` is
        the fused-stepping group key (fuse key + batch tag);
        ``staged`` is ``tick_staging``'s flat tuple of device arrays.
        ``slice_key`` (mesh serving, ADR 0115) labels the mesh slice
        this group executes on for the per-slice METRICS breakdown.

        A group whose program misses the LRU runs to its end in here
        (``compiled`` on the handle): the compile instrument times a
        synchronous first call. So does a group with no plan or whose
        dispatch raised: :meth:`collect` then only hands the results
        over.
        """
        plan, planned_errors = plan_members(requests)
        pending = PendingTick(
            n_requests=len(requests),
            plan=plan,
            by_index={
                i: CombinedPublish(None, (), error=err)
                for i, err in planned_errors.items()
            },
            slice_key=slice_key,
        )
        if not plan:
            return pending
        key = pending.key = self._program_key(hist, group_key, staged, plan)
        with self._programs_lock:
            fn = self._programs.get(key)
            pending.compiled = fn is None
            if fn is not None:
                # LRU touch: the steady-state program runs every tick
                # and must never be the eviction victim of key churn
                # (layout swaps) — eviction means a surprise whole-tick
                # recompile in the hot path.
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
        try:
            if pending.compiled:
                # Compile-event instrument (ADR 0116): the first call of
                # a fresh program pays trace + XLA compile + execute.
                # Time it and label WHY the key missed
                # (layout swap / batch shape / new group) so
                # compile spikes decompose on the scrape. The execute is
                # async-dispatched; the device_get inside the timed
                # region bounds the compile+first-round wall time. No
                # tick/fetch spans on compile rounds — they would put a
                # compile stall in the steady-state span histograms,
                # the exact confusion the compile instrument exists to
                # prevent.
                t0 = time.perf_counter()
                packed, statics, pending.carries = fn(*flat_args)
                pending.fetched = jax.device_get((packed, statics))
                self._record_compile(
                    hist, group_key, key, plan, time.perf_counter() - t0
                )
            else:
                # Per-tick tracer span (ADR 0116), against the step
                # worker's thread-bound trace id: the dispatch (host
                # Python + ASYNC submit: it returns before the program
                # has run). The wait for the chip is ``collect``'s
                # ``fetch`` span, so the two decompose separately in
                # the slow-tick breakdown.
                with TRACER.span("tick_execute"):
                    packed, statics, pending.carries = fn(*flat_args)
                    for leaf in jax.tree_util.tree_leaves((packed, statics)):
                        leaf.copy_to_host_async()
                pending.outputs = (packed, statics)
        except Exception as err:
            self._dispatch_failed(pending, err)
        return pending

    def collect(self, pending: PendingTick) -> list[CombinedPublish]:
        """Wait for a dispatched tick program (the ``fetch`` span) and
        serve every member's publish from the one packed fetch.

        A failure of the program itself is asynchronous and surfaces
        here: it is contained as a dispatch failure is, ``state_lost``
        for exactly the members whose arguments the program consumed,
        and no other pending handle is touched."""
        plan = pending.plan
        if pending.outputs is not None:
            try:
                pending.fetched = fetch_outputs(pending.outputs)
            except Exception as err:
                self._dispatch_failed(pending, err)
        if pending.fetched is not None:
            flat, static_fetched = pending.fetched
            static_total = unpack_members(
                plan, flat, static_fetched, pending.carries, pending.by_index
            )
            METRICS.record(
                executes=1,
                fetches=1,
                dynamic_bytes=int(flat.nbytes),
                static_bytes=static_total,
                combined_jobs=len(plan),
                tick=True,
                slice_key=pending.slice_key,
            )
        return [pending.by_index[i] for i in range(pending.n_requests)]

    def _dispatch_failed(self, pending: PendingTick, err: Exception) -> None:
        """Dispatch-level failure, synchronous or surfacing at the
        fetch: per-member containment happens at the caller, which
        needs to know whose donated state the failed dispatch already
        consumed (state_lost — the step donates every member state, so
        a runtime failure may have invalidated all of them). The cached
        program is evicted: a poisoned entry (an AOT-warmed executable
        whose input placement drifted, a backend error pinned to this
        compilation) must not fail every later tick — the next tick
        recompiles fresh instead."""
        with self._programs_lock:
            self._programs.pop(pending.key, None)
        logger.exception(
            "tick program dispatch failed (%d jobs)", len(pending.plan)
        )
        for _i, req, *_ in pending.plan:
            pending.by_index[_i] = CombinedPublish(
                None,
                (),
                error=err,
                state_lost=publish_args_consumed(req.args),
            )

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
                # staged shape, residual key material) are passed
                # separately for trigger classification.
                (id(hist), tuple(id(req.publisher) for _i, req, *_ in plan)),
                seconds,
                layout_digest=getattr(hist, "layout_digest", None),
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
