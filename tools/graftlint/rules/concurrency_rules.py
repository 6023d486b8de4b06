"""Thread-safety hazards: JGL004 (unlocked shared mutation), JGL005
(blocking calls in async bodies), JGL010 (unbounded/untimeboxed
queue hand-offs between threads that drive the device pipeline) and
JGL019 (broadcast fan-out state: unlocked subscriber-registry mutation,
unbounded list fan-out buffers).

JGL004 is a lightweight race detector scoped to modules that import
``threading`` (the Kafka consume thread / service worker split is this
codebase's thread boundary): it flags read-modify-write updates
(``self.x += 1``, writes to ``global`` names) reachable from more than
one method when the write is not lexically under a ``with <lock>:``
block. Plain stores (``self._broken = True``) are not flagged — a GIL
store is atomic; it is the lost-update pattern that corrupts counters.
"""

from __future__ import annotations

import ast
import re
from collections import defaultdict

from typing import TYPE_CHECKING

from ..context import FileContext
from ..findings import Finding
from ..registry import project_rule, rule

if TYPE_CHECKING:
    from ..project import ProjectContext

#: Call names that block the event loop when not awaited.
_BLOCKING_ATTRS = frozenset({"poll", "consume"})


@rule("JGL004", "unlocked shared-state mutation in a threaded module")
def unlocked_shared_mutation(ctx: FileContext):
    if not ctx.is_threaded_module:
        return

    # Writes to module-level names declared `global` inside functions.
    for fn in ctx.functions:
        if isinstance(fn, ast.Lambda):
            continue
        global_names: set[str] = set()
        for node in ctx.walk_shallow(fn):
            if isinstance(node, ast.Global):
                global_names.update(node.names)
        if not global_names:
            continue
        for node in ctx.walk_shallow(fn):
            targets: list[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for target in targets:
                if (
                    isinstance(target, ast.Name)
                    and target.id in global_names
                    and not ctx.under_lock(node)
                ):
                    yield Finding(
                        ctx.path,
                        node.lineno,
                        "JGL004",
                        f"write to module-global '{target.id}' in "
                        f"'{fn.name}' without holding a lock, in a "
                        "module that runs threads; guard it or make it "
                        "thread-local",
                    )

    # self.<attr> read-modify-write shared across methods of one class.
    for cls in ctx.nodes(ast.ClassDef):
        access: dict[str, set[str]] = defaultdict(set)
        methods = [
            n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for method in methods:
            for node in ast.walk(method):
                if (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                ):
                    access[node.attr].add(method.name)
        for method in methods:
            if method.name == "__init__":
                continue
            for node in ast.walk(method):
                if not isinstance(node, ast.AugAssign):
                    continue
                target = node.target
                if not (
                    isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "self"
                ):
                    continue
                sharers = access[target.attr] - {"__init__"}
                if len(sharers) < 2 or ctx.under_lock(node):
                    continue
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL004",
                    f"read-modify-write of self.{target.attr} in "
                    f"'{cls.name}.{method.name}' without holding a "
                    "lock; the attribute is also touched by "
                    f"{sorted(sharers - {method.name}) or '[other threads]'}"
                    " — a concurrent update loses increments",
                )


@rule("JGL005", "blocking call inside an async function body")
def blocking_in_async(ctx: FileContext):
    for fn in ctx.nodes(ast.AsyncFunctionDef):
        for node in ctx.walk_shallow(fn):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qualname(node.func)
            awaited = isinstance(ctx.parent(node), ast.Await)
            if qual == "time.sleep":
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL005",
                    f"time.sleep() inside 'async def {fn.name}' stalls "
                    "the whole event loop (every dashboard session, not "
                    "one); use 'await asyncio.sleep(...)'",
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _BLOCKING_ATTRS
                and not awaited
            ):
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL005",
                    f"sync '.{node.func.attr}()' inside 'async def "
                    f"{fn.name}' blocks the event loop on broker I/O; "
                    "run it in an executor (loop.run_in_executor) or "
                    "use the async client",
                )


#: stdlib queue constructors that accept a maxsize bound.
_BOUNDABLE_QUEUES = frozenset(
    {"queue.Queue", "queue.LifoQueue", "queue.PriorityQueue"}
)


def _maxsize_arg(call: ast.Call) -> ast.AST | None:
    """The maxsize argument expression of a queue constructor, or None."""
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg == "maxsize":
            return kw.value
    return None


def _const_false(expr: ast.AST | None) -> bool:
    return isinstance(expr, ast.Constant) and expr.value is False


def _queue_target_names(node: ast.Assign | ast.AnnAssign) -> set[str]:
    """Plain and ``self.<attr>`` names a queue construction binds to."""
    targets = (
        node.targets if isinstance(node, ast.Assign) else [node.target]
    )
    names: set[str] = set()
    for target in targets:
        if isinstance(target, ast.Name):
            names.add(target.id)
        elif isinstance(target, ast.Attribute) and isinstance(
            target.value, ast.Name
        ):
            names.add(target.attr)
    return names


@rule(
    "JGL010",
    "unbounded queue / timeout-less blocking hand-off on a "
    "device-pipeline thread",
)
def unbounded_queue_handoff(ctx: FileContext):
    """Scope: modules that import both ``threading`` and ``queue`` — the
    cross-thread hand-off tier of a pipelined ingest. Two hazards:

    - ``queue.Queue()`` with no (or non-positive) ``maxsize``: a slow
      consumer turns backpressure into unbounded memory growth instead
      of throttling the producer (the whole point of a bounded stage
      hand-off, ADR 0111);
    - blocking ``.put()``/``.get()`` with no ``timeout`` on such a
      queue: a thread that also dispatches jitted computations can
      never observe shutdown (or a peer stage's failure) while parked
      in an untimeboxed wait — the service hangs instead of stopping.
    """
    imports = set(ctx._names.values())  # noqa: SLF001 - registry-internal
    if not ctx.is_threaded_module or not any(
        q == "queue" or q.startswith("queue.") for q in imports
    ):
        return

    tracked: set[str] = set()
    for node in ctx.nodes(ast.Assign, ast.AnnAssign):
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        qual = ctx.qualname(call.func)
        if qual == "queue.SimpleQueue":
            tracked |= _queue_target_names(node)
            yield Finding(
                ctx.path,
                node.lineno,
                "JGL010",
                "queue.SimpleQueue has no capacity bound; use "
                "queue.Queue(maxsize=...) so a slow stage throttles "
                "its producer instead of growing memory",
            )
            continue
        if qual not in _BOUNDABLE_QUEUES:
            continue
        tracked |= _queue_target_names(node)
        maxsize = _maxsize_arg(call)
        unbounded = maxsize is None or (
            isinstance(maxsize, ast.Constant)
            and isinstance(maxsize.value, int)
            and maxsize.value <= 0
        )
        if unbounded:
            yield Finding(
                ctx.path,
                node.lineno,
                "JGL010",
                f"unbounded {qual}() hand-off in a threaded module; "
                "pass maxsize so a slow consumer throttles the "
                "producer (bounded backpressure) instead of growing "
                "memory without limit",
            )

    if not tracked:
        return
    for node in ctx.nodes(ast.Call):
        func = node.func
        if not (
            isinstance(func, ast.Attribute) and func.attr in ("put", "get")
        ):
            continue
        base = func.value
        base_name = None
        if isinstance(base, ast.Name):
            base_name = base.id
        elif isinstance(base, ast.Attribute):
            base_name = base.attr
        if base_name not in tracked:
            continue
        # Signatures: get(block=True, timeout=None) / put(item,
        # block=True, timeout=None) — block and timeout may arrive
        # positionally, and a positional timeout is just as timeboxed
        # as a keyword one.
        block_pos, timeout_pos = (0, 1) if func.attr == "get" else (1, 2)
        has_timeout = any(
            kw.arg == "timeout" for kw in node.keywords
        ) or len(node.args) > timeout_pos
        nonblocking = any(
            _const_false(kw.value)
            for kw in node.keywords
            if kw.arg == "block"
        ) or (
            len(node.args) > block_pos
            and _const_false(node.args[block_pos])
        )
        if has_timeout or nonblocking:
            continue
        yield Finding(
            ctx.path,
            node.lineno,
            "JGL010",
            f"blocking '.{func.attr}()' without a timeout on queue "
            f"'{base_name}': a pipeline thread parked here can never "
            "observe shutdown or a peer stage's failure; loop on "
            f"'.{func.attr}(timeout=...)' and re-check the stop flag",
        )


# -- JGL019: broadcast fan-out state --------------------------------------

#: Attribute names that read as a per-subscriber registry: the mapping a
#: broadcast accept thread mutates on attach/detach while the publish
#: thread iterates it to fan out.
_SUBSCRIBER_ATTR = re.compile(
    r"subscriber|client|session|listener|watcher|viewer", re.IGNORECASE
)
#: Mutating calls on dict/set registries.
_REGISTRY_MUTATORS = frozenset(
    {"add", "append", "clear", "discard", "pop", "popitem", "remove",
     "setdefault", "update"}
)
#: List attributes that read as per-message fan-out buffers (frames,
#: backlogs...) — registration lists (listeners, plotters) grow per
#: registration, not per message, and stay out of scope.
_FANOUT_BUFFER_ATTR = re.compile(
    r"buffer|backlog|pending|frame|blob|event|message|payload|queue",
    re.IGNORECASE,
)
#: Test doubles intentionally record everything they are given.
_DOUBLE_CLASS = re.compile(r"^(Fake|Stub|Mock|Recording)")
#: Calls that bound a list (a class using any of these on the buffer is
#: managing its growth).
_LIST_BOUNDERS = frozenset({"pop", "clear", "remove"})


def _self_attr_name(node: ast.AST) -> str | None:
    """'x' for a ``self.x`` expression, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _flat_targets(targets: list[ast.AST]) -> list[ast.AST]:
    """Assignment targets with tuple/list unpacking flattened — the
    swap-drain idiom ``frames, self._buf = self._buf, []`` reassigns
    ``self._buf`` just as surely as a plain store."""
    out: list[ast.AST] = []
    for target in targets:
        if isinstance(target, (ast.Tuple, ast.List)):
            out.extend(_flat_targets(list(target.elts)))
        else:
            out.append(target)
    return out


def _init_container_attrs(
    cls: ast.ClassDef,
) -> tuple[set[str], set[str]]:
    """(registry attrs, list attrs) assigned empty in ``__init__``:
    ``self.x = {}`` / ``dict()`` / ``set()`` and ``self.y = []`` /
    ``list()``."""
    registries: set[str] = set()
    lists: set[str] = set()
    for method in cls.body:
        if (
            not isinstance(method, ast.FunctionDef)
            or method.name != "__init__"
        ):
            continue
        for node in ast.walk(method):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            value = node.value
            is_registry = isinstance(value, (ast.Dict, ast.Set)) or (
                isinstance(value, ast.Call)
                and isinstance(value.func, ast.Name)
                and value.func.id in ("dict", "set")
            )
            is_list = isinstance(value, ast.List) or (
                isinstance(value, ast.Call)
                and isinstance(value.func, ast.Name)
                and value.func.id == "list"
            )
            if not (is_registry or is_list):
                continue
            for target in targets:
                name = _self_attr_name(target)
                if name is None:
                    continue
                if is_registry:
                    registries.add(name)
                else:
                    lists.add(name)
    return registries, lists


@rule(
    "JGL019",
    "broadcast fan-out state: unlocked subscriber-registry mutation / "
    "unbounded list fan-out buffer",
)
def broadcast_fanout_state(ctx: FileContext):
    """Scope: threaded modules (the broadcast tier's accept threads vs
    publish thread split, serving/broadcast.py). Two hazards:

    - **Unlocked subscriber-registry mutation**: a dict/set attribute
      whose name reads as a per-subscriber registry (``subscribers``,
      ``_clients``, ``sessions``...) initialized empty in ``__init__``
      and mutated outside a ``with <lock>:`` block. The HTTP accept
      thread registers/removes subscribers while the service's publish
      thread iterates the same mapping to fan a frame out — an unlocked
      attach can vanish mid-iteration or never receive its keyframe.

    - **Unbounded ``list.append`` fan-out buffer**: a buffer-named list
      attribute (``_frames``, ``backlog``, ``pending``...) initialized
      empty in ``__init__`` and only ever appended to from methods
      (never popped/cleared/reassigned/length-gated). A slow consumer
      turns such a buffer into unbounded memory — the exact failure
      bounded queues with coalesce-on-overflow exist to prevent
      (extends the JGL010 queue discipline to ad-hoc list buffers).
      Registration lists (listeners, plotters) and test doubles
      (``Fake*``/``Stub*``...) stay out of scope.

    Methods named ``*_locked`` are exempt from the registry hazard —
    the codebase's caller-holds-the-lock convention (see
    ``CheckpointPlane._gc_locked``); the lock discipline is checked at
    their call sites.
    """
    if not ctx.is_threaded_module:
        return
    for cls in ctx.nodes(ast.ClassDef):
        if _DOUBLE_CLASS.match(cls.name):
            continue
        registries, lists = _init_container_attrs(cls)
        registries = {n for n in registries if _SUBSCRIBER_ATTR.search(n)}
        lists = {n for n in lists if _FANOUT_BUFFER_ATTR.search(n)}
        if not registries and not lists:
            continue
        methods = [
            n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name != "__init__"
        ]
        # A list is "managed" when any method bounds or replaces it:
        # .pop/.clear/.remove, `del self.y[...]`, slice/index stores,
        # reassignment, or an append lexically inside an `if` whose
        # test reads len(...) (an explicit growth gate).
        managed_lists: set[str] = set()
        appends: list[tuple[str, ast.Call, str]] = []
        for method in methods:
            for node in ast.walk(method):
                if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute
                ):
                    owner = _self_attr_name(node.func.value)
                    if owner in lists:
                        if node.func.attr in _LIST_BOUNDERS:
                            managed_lists.add(owner)
                        elif node.func.attr == "append":
                            appends.append((owner, node, method.name))
                elif isinstance(node, ast.Delete):
                    for target in node.targets:
                        if isinstance(target, ast.Subscript):
                            owner = _self_attr_name(target.value)
                            if owner in lists:
                                managed_lists.add(owner)
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = _flat_targets(
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for target in targets:
                        owner = _self_attr_name(target)
                        if owner in lists:
                            # Reassignment (e.g. `self.buf = []` drain)
                            managed_lists.add(owner)
                        elif isinstance(target, ast.Subscript):
                            owner = _self_attr_name(target.value)
                            if owner in lists:
                                managed_lists.add(owner)
        # Hazard 1: registry mutation outside the lock.
        for method in methods:
            if method.name.endswith("_locked"):
                continue
            for node in ast.walk(method):
                finding_attr = None
                if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute
                ):
                    owner = _self_attr_name(node.func.value)
                    if (
                        owner in registries
                        and node.func.attr in _REGISTRY_MUTATORS
                    ):
                        finding_attr = owner
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = _flat_targets(
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for target in targets:
                        if isinstance(target, ast.Subscript):
                            owner = _self_attr_name(target.value)
                            if owner in registries:
                                finding_attr = owner
                        else:
                            owner = _self_attr_name(target)
                            if owner in registries:
                                # Wholesale replacement races iteration
                                # the same way item stores do.
                                finding_attr = owner
                elif isinstance(node, ast.Delete):
                    for target in node.targets:
                        if isinstance(target, ast.Subscript):
                            owner = _self_attr_name(target.value)
                            if owner in registries:
                                finding_attr = owner
                if finding_attr is not None and not ctx.under_lock(node):
                    yield Finding(
                        ctx.path,
                        node.lineno,
                        "JGL019",
                        f"subscriber registry self.{finding_attr} "
                        f"mutated in '{cls.name}.{method.name}' without "
                        "holding the registry lock: the accept thread "
                        "races the publish thread's fan-out iteration "
                        "— take the lock that guards the fan-out",
                    )
        # Hazard 2: append-only fan-out buffers.
        for owner, node, method_name in appends:
            if owner in managed_lists:
                continue
            # An append under `if len(...)` (or any test naming len) is
            # an explicit growth gate.
            gated = False
            parent = ctx.parent(node)
            while parent is not None and not isinstance(
                parent, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                if isinstance(parent, ast.If):
                    for sub in ast.walk(parent.test):
                        if (
                            isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Name)
                            and sub.func.id == "len"
                        ):
                            gated = True
                parent = ctx.parent(parent)
            if gated:
                continue
            yield Finding(
                ctx.path,
                node.lineno,
                "JGL019",
                f"append-only fan-out buffer self.{owner} in "
                f"'{cls.name}.{method_name}': nothing in the class "
                "bounds, drains or replaces it, so a slow consumer "
                "grows it without limit — use a bounded queue.Queue "
                "with coalesce-on-overflow (the JGL010 discipline), "
                "or drain/cap the list",
            )


# -- JGL023: blocking call while a lock is held -----------------------------


@project_rule(
    "JGL023",
    "blocking operation (fsync/device fetch/compile/serialize/queue "
    "wait) executed while a lock is held",
)
def blocking_while_locked(project: "ProjectContext"):
    """A lock that guards the hot path must never be held across a
    wall-clock wait: a checkpoint fsync inside the plane lock stalls
    every publisher behind disk latency; a ``device_get`` under the
    registry lock serializes the service behind a device round trip;
    ``.compile()`` under a lock turns the first tick after a layout
    swap into a global pause (exactly the class PR 11's review caught
    by eye). Two halves, both on the dataflow lock-region analysis
    (``with`` blocks plus ``acquire()``/``release()`` pairing):

    - **direct** — a blocking call at a statement whose lock-region
      set is non-empty;
    - **interprocedural** — a call made while holding a lock into a
      function that may (transitively, over resolved call-graph edges
      only) reach a blocking call; reported at the lock-holding call
      site and naming the operation it bottoms out in.

    The ``*_locked`` caller-holds-the-lock convention (JGL019) is
    honored: a blocking call inside a ``foo_locked()`` body with no
    lexical lock is NOT flagged there — the lock belongs to the
    caller, and the interprocedural half flags the call site where
    that lock is visible. Move the wait outside the critical section:
    snapshot under the lock, block after releasing it."""
    direct_sites: set[tuple[str, int]] = set()
    for ff in project.facts:
        for bf in ff.blocking:
            if not bf.held:
                continue
            direct_sites.add((bf.path, bf.lineno))
            yield Finding(
                bf.path,
                bf.lineno,
                "JGL023",
                f"blocking {bf.op} while holding "
                f"{sorted(bf.held)} — every thread contending on the "
                "lock stalls behind this wait; snapshot under the "
                "lock and do the blocking work after releasing it",
            )
    for call in project.all_calls:
        if not call.held:
            continue
        for target in project.resolve_call(call):
            got = project.may_block.get(target)
            if got is None:
                continue
            op, site = got
            fn = project.functions.get(target)
            callee = (
                f"{fn.cls + '.' if fn and fn.cls else ''}"
                f"{fn.name if fn else call.callee}"
            )
            caller = project.functions.get(call.caller)
            # Caller quals are "<path>::qualname" by construction.
            path = caller.path if caller else call.caller.split("::")[0]
            if (path, call.lineno) in direct_sites:
                # A name-classified blocking call (serialize/compile/
                # ...) that ALSO resolves to a may-block function is
                # one hazard, already reported by the direct half.
                continue
            yield Finding(
                path,
                call.lineno,
                "JGL023",
                f"call to '{callee}()' while holding "
                f"{sorted(call.held)} reaches blocking {op} "
                f"(at {site}) — the lock is held across a wall-clock "
                "wait; hoist the blocking work out of the critical "
                "section (or snapshot under the lock and flush "
                "outside it)",
            )
