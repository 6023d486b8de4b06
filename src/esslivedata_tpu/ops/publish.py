"""Single-round-trip publish programs + the cross-job publish combiner.

A workflow's finalize used to cost three device dispatches: the summary
program, the fetch of its output tree (one transfer per leaf), then the
window fold.

:class:`PackedPublisher` compiles the whole publish step into ONE jitted
program that returns the new (donated) state plus every output flattened
into a single float32 vector, so a publish is exactly one execute call
and one single-array device->host fetch. The host unpacks by precomputed
offsets; output keys, shapes and order are derived by abstract
evaluation per input signature.

A K-job service paying K publish round trips per tick (overlapped by
the job pool, but still K executes + K fetches) is K-1 round trips too
many. Two further layers close that gap (ADR 0113):

- **Static/dynamic split.** A publisher may declare ``static_keys``:
  outputs whose values depend only on the layout (coords, edges, zero
  ROI blocks). Dynamic outputs pack into the per-tick float32 vector as
  before; static outputs ride a separate native-dtype channel that is
  included in the fetch ONLY when the caller's ``static_token`` (a
  layout digest) misses the host-side cache — once per (publisher,
  token), re-fetched only when the token changes (layout swap). Per-tick
  fetch bytes then carry only the data that changed.

- **Cross-job combining.** :class:`PublishCombiner` concatenates the
  packed publish programs of every job due in a publish tick (grouped
  by device by the caller) into ONE jitted mega-publish with per-job
  offsets: one execute + one packed fetch serves every job, and the
  host-side unpack fans the per-job output trees back out with per-job
  error containment. The jit cache is keyed on the exact (publisher,
  signature, static-inclusion) tuple per member, so a job-set change
  compiles a new program (rare: job sets change at command time, not in
  the data path).

Every publish — private or combined — records into :data:`METRICS`
(executes, fetches, dynamic/static fetched bytes), which the ``--publish``
bench scenario and the parity tests read.

The third layer lives in :mod:`.tick` (ADR 0114): the per-device
**tick program** composes the fused event step with the combined packed
publish under ONE jit, so a steady-state tick is one execute + one
fetch instead of the stage/step/publish triple. The per-member planning
and unpack machinery is shared verbatim (:func:`plan_members` /
:func:`unpack_members`), so tick and combined publishes cannot diverge
in spec handling, static caching, or containment.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from collections.abc import Callable, Hashable, Sequence
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.trace import TRACER

__all__ = [
    "METRICS",
    "CombinedPublish",
    "PackedPublisher",
    "PublishCombiner",
    "PublishMetrics",
    "PublishOffer",
    "PublishRequest",
    "make_publish_offer",
    "member_signature",
    "plan_members",
    "publish_args_consumed",
    "publish_device",
    "signature_fingerprint",
    "unpack_members",
]

logger = logging.getLogger(__name__)


def program_name(kind: str, publishers) -> str:
    """``<kind>_<family>[_<family>...]``: the name a jitted publish or
    tick program carries into the device trace and the compile log, from
    the families (``PackedPublisher.name``) of its members."""
    return "_".join([kind, *sorted({pub.name for pub in publishers})])


def fetch_outputs(outputs):
    """The ``fetch`` span of a steady-state round (ADR 0116): the wait
    for the chip, then the copy back, on the caller's bound trace.

    The dispatch before it is asynchronous, so this is where the host
    meets the program's run time: ``block_until_ready`` first, then
    ``device_get``. The second part alone is observed as the aggregate
    ``d2h`` (no ring entry: it lies inside ``fetch``, and the ring
    stays flat), which tells the wait from the copy.

    The copies are ENQUEUED before the wait, as ``device_get`` alone
    would: they then start on the device the moment the program ends.
    Enqueued only after the wait, each costs a host wake-up and a
    submit with the chip idle: 20 ms of every picture's age at seven
    fetches a tick (PERF.md section 6). ``d2h`` is therefore what of the
    copy is left to wait for once the program has run.

    The tick path enqueues them earlier still, at the dispatch
    (``ops/tick.TickCombiner.dispatch``; asking again here costs
    nothing), and dispatches every group of a tick before the first
    fetch. A group's program and copy have then mostly run under the
    staging of the groups after it: the ``fetch`` spans of a tick add
    up to what of the chip's work is left to wait for after the last
    dispatch, and ``d2h`` to little more than the last group's copy."""
    with TRACER.span("fetch"):
        for leaf in jax.tree_util.tree_leaves(outputs):
            leaf.copy_to_host_async()
        jax.block_until_ready(outputs)
        t0 = time.perf_counter()
        fetched = jax.device_get(outputs)
        TRACER.observe("d2h", time.perf_counter() - t0)
    return fetched


class PublishMetrics:
    """Process-wide publish round-trip counters.

    One ``record`` per publish execute+fetch pair, whether private
    (``PackedPublisher.__call__``), combined (``PublishCombiner``) or a
    whole-tick program (``ops/tick.TickCombiner``, which sets ``tick``).
    ``dynamic_bytes`` is the packed per-tick vector; ``static_bytes``
    counts only the tokens that actually missed the static cache — at
    most once per (publisher, layout digest) by construction.

    ``step_executes`` counts SEPARATE fused-step dispatches (the
    stage→step→publish triple's middle round trip): the JobManager
    records one per ``step_many`` group it runs outside a tick program,
    so the bench ``--tick`` decomposition can show the dispatch count a
    tick actually pays — 1 with the tick program, ≥2 without.

    ``slice_key`` (mesh serving, ADR 0115) attributes a record to the
    mesh slice — a device label or the whole-mesh label — that executed
    it; the ``slices`` sub-dict lets the ``--mesh`` bench assert the
    per-slice contract (ONE execute + ONE fetch per slice per tick)
    instead of only the process-wide aggregate.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._executes = 0
        self._fetches = 0
        self._dynamic_bytes = 0
        self._static_bytes = 0
        self._combined_publishes = 0
        self._combined_jobs = 0
        self._step_executes = 0
        self._tick_publishes = 0
        self._tick_jobs = 0
        self._slices: dict[str, dict[str, int]] = {}

    def record(
        self,
        *,
        executes: int = 0,
        fetches: int = 0,
        dynamic_bytes: int = 0,
        static_bytes: int = 0,
        combined_jobs: int = 0,
        step_executes: int = 0,
        tick: bool = False,
        slice_key: str | None = None,
    ) -> None:
        with self._lock:
            self._executes += executes
            self._fetches += fetches
            self._dynamic_bytes += dynamic_bytes
            self._static_bytes += static_bytes
            self._step_executes += step_executes
            if combined_jobs:
                self._combined_publishes += 1
                self._combined_jobs += combined_jobs
            if tick:
                self._tick_publishes += 1
                self._tick_jobs += combined_jobs
            if slice_key is not None:
                per = self._slices.setdefault(
                    slice_key,
                    {"executes": 0, "fetches": 0, "tick_publishes": 0,
                     "jobs": 0},
                )
                per["executes"] += executes
                per["fetches"] += fetches
                per["jobs"] += combined_jobs
                if tick:
                    per["tick_publishes"] += 1

    def _dict(self) -> dict:
        return {
            "executes": self._executes,
            "fetches": self._fetches,
            "dynamic_bytes": self._dynamic_bytes,
            "static_bytes": self._static_bytes,
            "combined_publishes": self._combined_publishes,
            "combined_jobs": self._combined_jobs,
            "step_executes": self._step_executes,
            "tick_publishes": self._tick_publishes,
            "tick_jobs": self._tick_jobs,
            "slices": {k: dict(v) for k, v in self._slices.items()},
        }

    def snapshot(self) -> dict:
        with self._lock:
            return self._dict()

    def drain(self) -> dict:
        with self._lock:
            out = self._dict()
            self._executes = 0
            self._fetches = 0
            self._dynamic_bytes = 0
            self._static_bytes = 0
            self._combined_publishes = 0
            self._combined_jobs = 0
            self._step_executes = 0
            self._tick_publishes = 0
            self._tick_jobs = 0
            self._slices = {}
        return out


#: The process-wide publish counters (bench ``--publish``, tests).
METRICS = PublishMetrics()


def _publish_metric_families():
    """Telemetry collector (ADR 0116): the publish counters — including
    the per-slice breakdown (ADR 0115) — as scrape families. Pull-time
    only: the hot path keeps paying exactly the one ``record`` it
    already paid. NOTE: benches/tests ``drain()`` these around measured
    loops, so a scrape across a drain can observe a reset; the
    operator-facing monotone signals are the direct telemetry
    instruments (compile events, span histograms)."""
    from ..telemetry.registry import MetricFamily, Sample

    snap = METRICS.snapshot()
    plain = MetricFamily(
        "livedata_publish_events",
        "gauge",
        "Publish-path dispatch counters since process start (or the "
        "last explicit drain): executes/fetches are device round "
        "trips, step_executes are SEPARATE fused-step dispatches, "
        "tick_publishes rode the one-dispatch tick program (ADR 0114)",
    )
    for key in (
        "executes",
        "fetches",
        "dynamic_bytes",
        "static_bytes",
        "combined_publishes",
        "combined_jobs",
        "step_executes",
        "tick_publishes",
        "tick_jobs",
    ):
        plain.samples.append(Sample("", (("kind", key),), float(snap[key])))
    per_slice = MetricFamily(
        "livedata_publish_slice_events",
        "gauge",
        "Per-mesh-slice publish dispatch counters (ADR 0115): one "
        "execute + one fetch per slice per steady-state tick is the "
        "serving contract",
    )
    per_slice.samples = [
        Sample(
            "",
            (("slice", str(slice_key)), ("kind", kind)),
            float(value),
        )
        for slice_key, counts in sorted(snap["slices"].items())
        for kind, value in sorted(counts.items())
    ]
    return [plain, per_slice]


def _register_telemetry() -> None:
    from ..telemetry.registry import REGISTRY

    REGISTRY.register_collector("ops.publish.METRICS", _publish_metric_families)


_register_telemetry()


def _unpack_segment(
    flat: np.ndarray, spec: list[tuple[str, tuple[int, ...], int]]
) -> dict[str, np.ndarray]:
    """Fan one packed float32 segment back out by precomputed offsets."""
    outputs: dict[str, np.ndarray] = {}
    offset = 0
    for key, shape, size in spec:
        view = flat[offset : offset + size]
        outputs[key] = view.reshape(shape) if shape else view[0]
        offset += size
    return outputs


def publish_device(args):
    """The placement key of the first array leaf of ``args`` (None for
    host-only args): the device for single-device arrays, the sorted
    device-id tuple for mesh-sharded ones. The JobManager groups publish
    offers by this so a combined program never spans placements — two
    single-device jobs on different slices stay separate dispatches, K
    jobs sharing one mesh combine, and a mesh member can never be fused
    with a default-device one (jit would reject the device mix at
    dispatch time, costing the whole group its combine)."""
    from .event_batch import leaf_device_set

    for leaf in jax.tree_util.tree_leaves(args):
        ds = leaf_device_set(leaf)
        if ds is None:
            continue
        if len(ds) == 1:
            return next(iter(ds))
        if len(ds) > 1:
            return tuple(sorted(d.id for d in ds))
    return None


def publish_args_consumed(args) -> bool:
    """True when any array leaf of ``args`` was invalidated by a donated
    dispatch that subsequently failed (the caller's state is gone)."""
    for leaf in jax.tree_util.tree_leaves(args):
        deleted = getattr(leaf, "is_deleted", None)
        try:
            if deleted is not None and deleted():
                return True
        except Exception:  # pragma: no cover - defensive
            return True
    return False


class PackedPublisher:
    """Wrap ``program(*args) -> (outputs, *carry)`` for one-fetch publish.

    ``program`` must be traceable; ``outputs`` is a dict of arrays (any
    shapes/dtypes — dynamic outputs are packed as float32) and ``carry``
    is whatever device state flows to the next cycle (e.g. the cleared
    histogram state). Calling the publisher returns
    ``(outputs_on_host, *carry)`` where outputs are numpy arrays of the
    traced shapes.

    ``donate`` names positional args whose buffers the program may reuse
    (pass the old state's index; defaults to arg 0).

    ``static_keys`` names outputs whose values are layout-constant: they
    are fetched (in their native traced dtype, not the float32 pack)
    only when the per-call ``static_token`` misses the host-side cache,
    and served from that cache on every later publish until the token
    changes. A call without a token treats every output as dynamic.

    ``name`` is the workflow family's, for the device trace: the jitted
    programs this publisher is part of are called ``publish_<name>`` and
    ``tick_<name>`` (``program_name``), stable across refactors, where
    the wrapped closure's own name says nothing.
    """

    #: Static cache entries kept per publisher; tokens are layout
    #: digests, so churn means live geometry flaps — keep a few.
    _STATIC_CACHE_MAX = 8

    def __init__(
        self,
        program: Callable,
        *,
        donate: tuple[int, ...] = (0,),
        static_keys: Sequence[str] = (),
        name: str = "publish",
    ) -> None:
        self._program = program
        self.name = name
        self._donate = tuple(donate)
        self._static_keys = frozenset(static_keys)
        # (signature, static-key split) -> (dynamic spec, static names).
        # A jit cache can hold several entries (state rebuilt with
        # different bins, a new batch shape) and a cached entry executes
        # without retracing, so the unpack spec must be resolved per
        # signature — abstract evaluation (no compile), cached forever.
        # Spec entries are (key, shape, size) with the element count
        # precomputed: the unpack runs once per publish per output key.
        self._spec_by_sig: dict[
            tuple, tuple[list[tuple[str, tuple[int, ...], int]], tuple[str, ...]]
        ] = {}
        # One jitted variant per (static split, statics included): the
        # first publish under a fresh token includes the static leaves,
        # every later publish runs the dynamic-only variant.
        self._jits: dict[tuple[frozenset, bool], Callable] = {}
        self._static_cache: OrderedDict[Hashable, dict[str, np.ndarray]] = (
            OrderedDict()
        )

    # -- static split ------------------------------------------------------
    @property
    def static_keys(self) -> frozenset:
        return self._static_keys

    def set_static_keys(self, keys: Sequence[str]) -> None:
        """Re-declare the static output set (e.g. detector-view flips
        its ROI blocks dynamic once real masks are installed). Flushes
        the static cache — cached entries were split under the old set."""
        keys = frozenset(keys)
        if keys == self._static_keys:
            return
        self._static_keys = keys
        self._static_cache.clear()

    def invalidate_static(self, token: Hashable | None = None) -> None:
        """Drop one cached static entry (or all): the next publish under
        that token re-fetches. Layout swaps normally invalidate by
        *token change* (a new digest misses); this is the explicit hook."""
        if token is None:
            self._static_cache.clear()
        else:
            self._static_cache.pop(token, None)

    def _store_static(
        self, token: Hashable, values: dict[str, np.ndarray]
    ) -> None:
        cache = self._static_cache
        cache[token] = values
        cache.move_to_end(token)
        while len(cache) > self._STATIC_CACHE_MAX:
            cache.popitem(last=False)

    # -- specs -------------------------------------------------------------
    @staticmethod
    def _signature(args) -> tuple:
        # Leaves AND treedef: jit keys its cache on both, so two arg
        # structures with identical flattened leaves must not share a
        # spec entry.
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (
            treedef,
            tuple(
                (tuple(getattr(leaf, "shape", ())),
                 str(getattr(leaf, "dtype", type(leaf).__name__)))
                for leaf in leaves
            ),
        )

    @staticmethod
    def _spec_of(outputs) -> list[tuple[str, tuple[int, ...], int]]:
        # SORTED key order — the one canonical pack order, matching the
        # dict-key sorting jax's pytree flattening applies, so specs
        # derived abstractly and packs built in the traced program can
        # never disagree about which bytes belong to which key.
        return [
            (k, shape := tuple(v.shape), int(np.prod(shape)) if shape else 1)
            for k, v in sorted(outputs.items())
        ]

    def _spec_for(
        self, args, skeys: frozenset
    ) -> tuple[list[tuple[str, tuple[int, ...], int]], tuple[str, ...]]:
        """(dynamic spec, static names) for ``args`` under ``skeys`` via
        abstract evaluation (no compile); cached per signature."""
        key = (self._signature(args), skeys)
        spec = self._spec_by_sig.get(key)
        if spec is None:
            out = jax.eval_shape(lambda *a: self._program(*a)[0], *args)
            dynamic = {k: v for k, v in out.items() if k not in skeys}
            static_names = tuple(sorted(k for k in out if k in skeys))
            spec = self._spec_by_sig[key] = (
                self._spec_of(dynamic),
                static_names,
            )
        return spec

    # -- traced body -------------------------------------------------------
    def _packed_impl(
        self, skeys: frozenset, include_static: bool, *args
    ):
        """The traceable publish body: ``(packed_dynamic, static_leaves,
        *carry)``. The combiner inlines this per member, so private and
        combined publishes run the exact same per-job ops."""
        # Scopes name the phases in the device trace (op metadata
        # only): the member's reductions (the fold inside them is
        # ``fold``, ops/histogram.py) and the pack into one vector.
        with jax.named_scope("publish_reduce"):
            outputs, *carry = self._program(*args)
        dynamic = sorted(
            (k, v) for k, v in outputs.items() if k not in skeys
        )
        with jax.named_scope("pack"):
            if dynamic:
                packed = jnp.concatenate(
                    [jnp.ravel(v).astype(jnp.float32) for _, v in dynamic]
                )
            else:
                packed = jnp.zeros((0,), jnp.float32)
        statics = (
            tuple(
                outputs[k] for k in sorted(k for k in outputs if k in skeys)
            )
            if include_static
            else ()
        )
        return (packed, statics, *carry)

    def _jit_for(self, skeys: frozenset, include_static: bool) -> Callable:
        key = (skeys, include_static)
        fn = self._jits.get(key)
        if fn is None:

            def run(*args, _sk=skeys, _inc=include_static):
                return self._packed_impl(_sk, _inc, *args)

            run.__name__ = program_name("publish", [self])
            fn = self._jits[key] = jax.jit(
                run, donate_argnums=self._donate
            )
        return fn

    def _static_plan(self, args, static_token: Hashable | None):
        """(skeys, dynamic spec, static names, cached statics,
        include_static) for one publish — the ONE place the cache-hit /
        fetch-statics decision lives, shared verbatim by the private
        path and the combiner so the two can never diverge."""
        skeys = self._static_keys if static_token is not None else frozenset()
        dyn_spec, static_names = self._spec_for(args, skeys)
        cached = None
        if static_names and static_token in self._static_cache:
            cached = self._static_cache[static_token]
            self._static_cache.move_to_end(static_token)  # LRU touch
        include_static = bool(static_names) and cached is None
        return skeys, dyn_spec, static_names, cached, include_static

    def _static_adopt(
        self, token: Hashable, names: tuple[str, ...], arrays
    ) -> tuple[dict[str, np.ndarray], int]:
        """Store freshly fetched static leaves under ``token``; returns
        (cached dict, fetched bytes) — the counterpart of _static_plan."""
        cached = {
            name: np.asarray(a) for name, a in zip(names, arrays)
        }
        self._store_static(token, cached)
        return cached, sum(a.nbytes for a in cached.values())

    # -- publish -----------------------------------------------------------
    def __call__(self, *args, static_token: Hashable | None = None):
        skeys, dyn_spec, static_names, cached, include_static = (
            self._static_plan(args, static_token)
        )
        packed, statics, *carry = self._jit_for(skeys, include_static)(*args)
        # device_get already lands numpy arrays: one bulk fetch (the
        # statics, when included, ride the same call), no second host
        # copy.
        flat, static_arrays = jax.device_get((packed, statics))
        outputs = _unpack_segment(flat, dyn_spec)
        static_bytes = 0
        if static_names:
            if include_static:
                cached, static_bytes = self._static_adopt(
                    static_token, static_names, static_arrays
                )
            outputs.update(cached)
        METRICS.record(
            executes=1,
            fetches=1,
            dynamic_bytes=int(flat.nbytes),
            static_bytes=static_bytes,
        )
        return (outputs, *carry)


@dataclass(frozen=True)
class PublishOffer:
    """A workflow's offer to have its publish combined across jobs.

    Workflows owning a :class:`PackedPublisher` expose
    ``publish_offer() -> PublishOffer | None`` (duck-typed, like
    ``event_ingest``). The JobManager collects offers from every job due
    in a publish tick, groups them by device, and serves each group from
    one combined execute + fetch; ``consume(outputs, carry)`` then hands
    the job its unpacked output tree and new device state, after which
    the job's ``finalize`` must use them instead of dispatching
    privately. ``reset`` (optional) rebuilds a fresh state when a failed
    combined dispatch consumed the donated buffers — mirror of the fused
    stepping layer's donation-loss recovery.
    """

    publisher: PackedPublisher
    args: tuple
    consume: Callable[[dict, tuple], None]
    static_token: Hashable | None = None
    reset: Callable[[], None] | None = None


def make_publish_offer(
    owner,
    publisher: PackedPublisher,
    args: tuple,
    *,
    static_token: Hashable | None = None,
    fresh_state: Callable[[], Any] | None = None,
) -> PublishOffer:
    """The one shared PublishOffer wiring for state-carrying workflows.

    Contract (every offering workflow follows it): device state lives in
    ``owner._state``, the prefetched output tree in
    ``owner._prefetched_publish`` (consumed-and-cleared by finalize,
    dropped by ``clear``), and the publish program's carry is exactly
    ``(new_state,)``. ``fresh_state`` rebuilds a zeroed state after a
    donation-losing dispatch failure. Centralized so a behavior fix
    (carry handling, recovery) cannot silently diverge between the four
    workflow families.
    """

    def consume(outputs, carry) -> None:
        (owner._state,) = carry
        owner._prefetched_publish = outputs

    reset = None
    if fresh_state is not None:

        def reset() -> None:
            owner._state = fresh_state()

    return PublishOffer(
        publisher=publisher,
        args=args,
        consume=consume,
        static_token=static_token,
        reset=reset,
    )


@dataclass(frozen=True)
class PublishRequest:
    """One member of a combined publish (offer minus the callbacks)."""

    publisher: PackedPublisher
    args: tuple
    static_token: Hashable | None = None


@dataclass
class CombinedPublish:
    """Per-member result of a combined publish.

    ``error`` is set (and ``outputs`` None) when this member's unpack
    failed or the whole dispatch did; ``state_lost`` additionally marks
    a failed dispatch that had already consumed the member's donated
    buffers — the caller must rebuild that state, the other members are
    unaffected.
    """

    outputs: dict[str, np.ndarray] | None
    carry: tuple = ()
    error: BaseException | None = None
    state_lost: bool = False


def plan_members(
    requests: Sequence[PublishRequest],
) -> tuple[list[tuple], dict[int, BaseException]]:
    """Per-member publish plans for one combined/tick dispatch.

    Each plan entry is ``(index, request, skeys, dyn_spec, static_names,
    include_static, cached_statics, packed_size)`` — the resolved
    ``PackedPublisher._static_plan`` for that member. Containment: a
    member whose plan raises (bad restored state, workflow bug surfacing
    at abstract-evaluation time) lands in the error dict and drops out
    of the dispatch; the rest of the tick proceeds. Shared by
    :class:`PublishCombiner` and :class:`~.tick.TickCombiner` so the two
    cannot diverge in static-cache or spec handling.
    """
    plan: list[tuple] = []
    planned_errors: dict[int, BaseException] = {}
    for i, req in enumerate(requests):
        try:
            skeys, dyn_spec, static_names, cached, include_static = (
                req.publisher._static_plan(req.args, req.static_token)
            )
        except Exception as err:
            logger.exception("combined publish plan failed (member %d)", i)
            planned_errors[i] = err
            continue
        size = sum(s for _, _, s in dyn_spec)
        plan.append(
            (i, req, skeys, dyn_spec, static_names, include_static,
             cached, size)
        )
    return plan, planned_errors


def signature_fingerprint(msig: tuple) -> tuple:
    """An object-free echo of :func:`member_signature` for the
    compile-event memory (telemetry, ADR 0116): the signature itself
    holds live ``PackedPublisher`` references, and parking those in the
    recorder's bounded memory (capacity 64, wider than the 16-program
    LRUs) would pin retired publishers — and the static caches they
    close over — long after their programs evicted. Publisher identity
    degrades to ``id()``; the shape/dtype leaf info, static split and
    inclusion flag carry the classification signal."""
    return tuple(
        (id(pub), sig[1], tuple(sorted(skeys)), include_static)
        for pub, sig, skeys, include_static in msig
    )


def member_signature(plan: list[tuple]) -> tuple:
    """The jit-cache key fragment for a planned member set: publisher
    identity, args signature, static split and static inclusion per
    member — exactly what determines the compiled program."""
    return tuple(
        (req.publisher, req.publisher._signature(req.args), skeys,
         include_static)
        for _i, req, skeys, _spec, _names, include_static, _c, _s in plan
    )


def unpack_members(
    plan: list[tuple],
    flat: np.ndarray,
    static_fetched,
    carries,
    by_index: dict[int, CombinedPublish],
) -> int:
    """Fan one packed fetch back out per planned member; returns the
    static bytes adopted. Per-member unpack containment: one bad
    spec/shape cannot poison the other members' trees (their offsets are
    fixed), and an unpack-failed member still carries its (valid) folded
    carry for adoption."""
    offset = 0
    static_total = 0
    for k, (
        _i, req, _skeys, dyn_spec, static_names, include_static, cached,
        size,
    ) in enumerate(plan):
        carry = tuple(carries[k])
        try:
            outputs = _unpack_segment(flat[offset : offset + size], dyn_spec)
            if static_names:
                if include_static:
                    cached, nbytes = req.publisher._static_adopt(
                        req.static_token, static_names, static_fetched[k]
                    )
                    static_total += nbytes
                outputs.update(cached)
            by_index[_i] = CombinedPublish(outputs, carry)
        except Exception as err:
            logger.exception(
                "combined publish unpack failed (member %d)", _i
            )
            by_index[_i] = CombinedPublish(None, carry, error=err)
        offset += size
    return static_total


class PublishCombiner:
    """One execute + one packed fetch for K jobs' publish programs.

    Builds (and LRU-caches) a jitted mega-program per exact member
    tuple: each member's :meth:`PackedPublisher._packed_impl` is inlined
    in order, the per-member packed vectors concatenate into one fetch,
    and every member's donated args keep their donation at the shifted
    position. Member composition changes at command time (jobs
    scheduled/removed), so recompiles are rare; the cache bound caps
    how many retired job-set programs (and the publishers they close
    over) stay alive.
    """

    def __init__(self, max_programs: int = 16) -> None:
        self._programs: OrderedDict[tuple, Callable] = OrderedDict()
        self._max_programs = int(max_programs)

    def publish(
        self, requests: Sequence[PublishRequest]
    ) -> list[CombinedPublish]:
        # Per-member plan containment (plan_members): a publish program
        # that raises at abstract-evaluation time (bad restored state,
        # workflow bug surfacing on first publish) drops ONLY that
        # member — it gets an error result (caller falls back to its
        # private path, where the same trace error lands in per-job
        # containment) while the rest of the tick combines normally.
        plan, planned_errors = plan_members(requests)
        if not plan:
            return [
                CombinedPublish(None, (), error=planned_errors.get(i))
                for i in range(len(requests))
            ]
        key = member_signature(plan)
        fn = self._programs.get(key)
        compiled = fn is None
        if fn is not None:
            # LRU touch: the steady-state program runs every tick and
            # must never be the eviction victim of key churn (layout
            # swaps, ROI flips) — eviction means a surprise mega-publish
            # recompile in the hot path.
            self._programs.move_to_end(key)
        else:
            fn = self._build(
                [
                    (req.publisher, len(req.args), skeys, include_static)
                    for _i, req, skeys, _spec, _names, include_static, _c, _s
                    in plan
                ]
            )
            self._programs[key] = fn
            self._programs.move_to_end(key)
            while len(self._programs) > self._max_programs:
                self._programs.popitem(last=False)
        flat_args = tuple(a for _i, req, *_ in plan for a in req.args)
        by_index: dict[int, CombinedPublish] = {
            i: CombinedPublish(None, (), error=err)
            for i, err in planned_errors.items()
        }
        try:
            if compiled:
                # Compile-event instrument (ADR 0116): the miss round's
                # wall time (trace + XLA + first execute+fetch) becomes
                # a labeled histogram sample. Job-set changes are command-
                # time events, so the expected trigger here is
                # new_group/regroup; per-member signature churn (batch
                # shape, static inclusion) classifies via residual. No
                # execute/fetch spans on compile rounds (same rule as
                # the tick combiner's).
                t0 = time.perf_counter()
                packed, statics, carries = fn(*flat_args)
                flat, static_fetched = jax.device_get((packed, statics))
                self._record_compile(plan, key, time.perf_counter() - t0)
            else:
                with TRACER.span("publish_execute"):
                    packed, statics, carries = fn(*flat_args)
                flat, static_fetched = fetch_outputs((packed, statics))
        except Exception as err:
            # Dispatch-level failure: per-member containment happens at
            # the caller, which needs to know whose donated state the
            # failed dispatch already consumed.
            logger.exception(
                "combined publish dispatch failed (%d jobs)", len(plan)
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
        )
        return [by_index[i] for i in range(len(requests))]

    @staticmethod
    def _record_compile(plan, key, seconds: float) -> None:
        """Best-effort compile-event recording (telemetry, ADR 0116)."""
        try:
            from ..telemetry.compile import COMPILE_EVENTS

            COMPILE_EVENTS.classify_and_record(
                "publish",
                tuple(id(req.publisher) for _i, req, *_ in plan),
                seconds,
                residual=signature_fingerprint(key),
            )
        except Exception:  # pragma: no cover - telemetry is advisory
            logger.debug("compile-event recording failed", exc_info=True)

    @staticmethod
    def _build(
        members: list[tuple[PackedPublisher, int, frozenset, bool]]
    ) -> Callable:
        def mega(*flat_args):
            parts, statics, carries = [], [], []
            offset = 0
            for pub, n_args, skeys, include_static in members:
                args = flat_args[offset : offset + n_args]
                offset += n_args
                packed, stat, *carry = pub._packed_impl(
                    skeys, include_static, *args
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
            return packed_all, tuple(statics), tuple(carries)

        mega.__name__ = program_name("publish", [m[0] for m in members])
        donate: list[int] = []
        offset = 0
        for pub, n_args, _skeys, _inc in members:
            donate.extend(offset + d for d in pub._donate if d < n_args)
            offset += n_args
        return jax.jit(mega, donate_argnums=tuple(donate))
