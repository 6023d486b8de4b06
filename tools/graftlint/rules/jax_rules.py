"""JAX jit-boundary hazards: JGL001/002/003/006/008/009/015/016/017/027.

Most of these erase TPU throughput without failing a test — host syncs
serialize the pipeline behind a device round trip, retraces recompile
the hot kernel mid-stream, a missing donation doubles rolling-state HBM
traffic, per-scalar ``jnp`` dispatch pays a device transfer per event
batch, and re-staging a shared batch inside a per-job loop multiplies
wire traffic by the job count. JGL016 is the correctness twin: reading
a state/staged array AFTER it was passed to a donated argnum of a
tick/step/publish dispatch touches buffers XLA already reused (a
deleted-array error at best, donation aliasing at worst). Rationale and
bad/good pairs: docs/graftlint.md.
"""

from __future__ import annotations

import ast
from pathlib import Path

from ..context import FileContext
from ..dataflow import walk_own
from ..findings import Finding
from ..registry import rule

#: Calls that force a device->host sync (or host compute on a traced
#: value) when they appear inside a traced region.
_HOST_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})
_HOST_SYNC_BUILTINS = frozenset({"float", "int", "bool"})

#: First-parameter names that mark a jitted program as a rolling-state
#: update (the donate_argnums audience).
_STATE_PARAMS = frozenset({"state", "hist", "carry", "window", "win", "acc"})


def _is_constant(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(
        node.op, (ast.USub, ast.UAdd)
    ):
        return _is_constant(node.operand)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_constant(e) for e in node.elts)
    return False


def _jit_label(ctx: FileContext, fn) -> str:
    name = getattr(fn, "name", "<lambda>")
    return f"in jit-traced function '{name}'"


@rule("JGL001", "host-sync call inside a jit-traced region")
def host_sync_in_jit(ctx: FileContext):
    for fn in ctx.jit_regions:
        params = ctx.params(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qualname(node.func)
            hit = None
            if qual == "jax.device_get":
                # Never legitimate under trace, traced operand or not.
                hit = "jax.device_get"
            elif qual is not None and qual.startswith("numpy.") and any(
                ctx.mentions_any(arg, params) for arg in node.args
            ):
                hit = qual.replace("numpy.", "np.", 1)
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _HOST_SYNC_METHODS
                and ctx.mentions_any(node.func.value, params)
            ):
                hit = f".{node.func.attr}()"
            elif (
                isinstance(node.func, ast.Name)
                and node.func.id in _HOST_SYNC_BUILTINS
                and node.func.id not in ctx._names
                and len(node.args) == 1
                and not _is_constant(node.args[0])
                and ctx.mentions_any(node.args[0], params)
            ):
                hit = f"{node.func.id}()"
            if hit:
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL001",
                    f"{hit} on a traced value {_jit_label(ctx, fn)} forces "
                    "a host round trip per dispatch (or a trace-time "
                    "ConcretizationError); keep the value on device or "
                    "hoist the conversion outside the jit boundary",
                )


@rule("JGL002", "Python loop over traced values inside a jit region")
def python_loop_in_jit(ctx: FileContext):
    for fn in ctx.jit_regions:
        if isinstance(fn, ast.Lambda):
            continue
        params = ctx.params(fn)
        for node in ctx.walk_shallow(fn):
            if isinstance(node, ast.For) and ctx.mentions_any(
                node.iter, params
            ):
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL002",
                    f"Python 'for' over argument-derived data "
                    f"{_jit_label(ctx, fn)} unrolls at trace time and "
                    "retraces when lengths change; use jax.lax.scan / "
                    "fori_loop or vectorize",
                )
            elif isinstance(node, ast.While) and ctx.mentions_any(
                node.test, params
            ):
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL002",
                    f"Python 'while' conditioned on an argument "
                    f"{_jit_label(ctx, fn)} cannot trace (or unrolls "
                    "unboundedly); use jax.lax.while_loop",
                )


def _returns_state(fn: ast.AST, first_param: str) -> bool:
    """Does the wrapped program hand back a new version of its state?

    Returning a ``*State`` constructor call is the strong signal; a bare
    ``return state`` counts only when the body reassigns the name (a
    pass-through read like a views program does not want donation — the
    caller keeps using its handle).
    """
    reassigned = False
    if not isinstance(fn, ast.Lambda):
        for node in FileContext.walk_shallow(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                if any(
                    isinstance(t, ast.Name) and t.id == first_param
                    for t in targets
                ):
                    reassigned = True
                    break

    def state_expr(expr: ast.AST | None) -> bool:
        if expr is None:
            return False
        if isinstance(expr, ast.Call):
            name = None
            if isinstance(expr.func, ast.Name):
                name = expr.func.id
            elif isinstance(expr.func, ast.Attribute):
                name = expr.func.attr
            if name is not None and name.endswith("State"):
                return True
        if isinstance(expr, ast.Name) and expr.id == first_param:
            return reassigned
        if isinstance(expr, ast.Tuple):
            return any(state_expr(e) for e in expr.elts)
        return False

    if isinstance(fn, ast.Lambda):
        return state_expr(fn.body)
    return any(
        state_expr(node.value)
        for node in FileContext.walk_shallow(fn)
        if isinstance(node, ast.Return)
    )


@rule("JGL003", "rolling-state jit without buffer donation")
def missing_donation(ctx: FileContext):
    for call in ctx.jit_calls:
        if ctx.qualname(call.func) not in ("jax.jit", "jax.pjit"):
            continue
        if any(
            kw.arg in ("donate_argnums", "donate_argnames")
            for kw in call.keywords
        ):
            continue
        if not call.args:
            continue
        target = call.args[0]
        fns: list[ast.AST] = []
        if isinstance(target, ast.Lambda):
            fns = [target]
        elif isinstance(target, ast.Name):
            fns = list(ctx.defs_by_name.get(target.id, ()))
        elif isinstance(target, ast.Attribute):
            fns = list(ctx.defs_by_name.get(target.attr, ()))
        for fn in fns:
            args = fn.args
            names = [
                a.arg
                for a in (*args.posonlyargs, *args.args)
                if a.arg not in ("self", "cls")
            ]
            if not names:
                continue
            first = names[0]
            annotated_state = False
            for a in (*args.posonlyargs, *args.args):
                if a.arg == first and a.annotation is not None:
                    ann = a.annotation
                    ann_name = getattr(ann, "id", getattr(ann, "attr", ""))
                    annotated_state = str(ann_name).endswith("State")
                    break
            if (
                first in _STATE_PARAMS or annotated_state
            ) and _returns_state(fn, first):
                yield Finding(
                    ctx.path,
                    call.lineno,
                    "JGL003",
                    f"jax.jit of rolling-state update "
                    f"'{getattr(fn, 'name', '<lambda>')}' without "
                    "donate_argnums: XLA must copy the state buffer in "
                    "HBM every step instead of updating it in place "
                    "(donate_argnums=(0,) makes the update zero-copy)",
                )
                break


@rule("JGL006", "per-call jnp dispatch of a Python scalar constant")
def scalar_jnp_dispatch(ctx: FileContext):
    exempt = ("__init__", "init_state")
    for node in ctx.nodes(ast.Call):
        qual = ctx.qualname(node.func)
        if qual is None or not qual.startswith("jax.numpy."):
            continue
        if not node.args or not _is_constant(node.args[0]):
            continue
        fn = ctx.enclosing_function(node)
        if fn is None or fn in ctx.jit_regions:
            # Module level / __init__-time: one-off. Inside jit: the
            # constant folds into the trace. Both fine.
            continue
        name = getattr(fn, "name", "<lambda>")
        if name in exempt or name.startswith(
            # Construction-time staging is one-off; test bodies are not
            # per-message paths (keeps runs over tests/ usable).
            ("build", "_build", "make_", "test")
        ):
            continue
        yield Finding(
            ctx.path,
            node.lineno,
            "JGL006",
            f"{qual.replace('jax.numpy.', 'jnp.', 1)} of a Python scalar "
            f"constant in '{name}' dispatches a device transfer on every "
            "call; hoist the constant to construction time (or let the "
            "jitted callee fold it)",
        )


@rule("JGL008", "unhashable argument baked into a jitted partial")
def unhashable_partial_arg(ctx: FileContext):
    for node in ctx.nodes(ast.Call):
        if ctx.qualname(node.func) != "functools.partial":
            continue
        if not node.args:
            continue
        target = node.args[0]
        target_fns: set[ast.AST] = set()
        wrapped_in_jit = ctx.qualname(target) in ("jax.jit", "jax.pjit")
        if isinstance(target, ast.Name):
            target_fns = set(ctx.defs_by_name.get(target.id, ()))
        elif isinstance(target, ast.Attribute):
            target_fns = set(ctx.defs_by_name.get(target.attr, ()))
        if not wrapped_in_jit and not (target_fns & ctx.jit_regions):
            continue
        bad = [
            arg
            for arg in (*node.args[1:], *(kw.value for kw in node.keywords))
            if isinstance(arg, (ast.List, ast.Dict, ast.Set))
        ]
        for arg in bad:
            kind = type(arg).__name__.lower()
            yield Finding(
                ctx.path,
                arg.lineno,
                "JGL008",
                f"{kind} literal baked into a partial of a jitted "
                "function: unhashable static args defeat the jit cache "
                "(TypeError under static_argnums, silent retrace storm "
                "otherwise); pass a tuple or hoist to a hashable "
                "constant",
            )


#: Host->device staging entry points whose output is identical for an
#: identical input: re-invoking one per loop iteration on a value the
#: loop never changes re-transfers the same bytes each pass.
_STAGING_QUALNAMES = frozenset({"jax.device_put"})
_STAGING_NAMES = frozenset({"dispatch_safe", "stage_for"})


def _loop_varying_names(ctx, loop: ast.For) -> frozenset[str]:
    """Names that (may) change per iteration: the loop target plus
    anything assigned inside the body — a staged value derived from
    either is genuinely per-iteration data, not a duplicate."""
    names: set[str] = set()

    def add_target(target: ast.AST) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                names.add(n.id)

    add_target(loop.target)
    for sub in ctx.walk_shallow(loop):
        if isinstance(sub, ast.Assign):
            for t in sub.targets:
                add_target(t)
        elif isinstance(sub, (ast.AugAssign, ast.AnnAssign, ast.NamedExpr)):
            add_target(sub.target)
        elif isinstance(sub, (ast.For, ast.AsyncFor)):
            # A nested loop's target varies per (inner) iteration too —
            # without this, `for job in jobs: for b in batches:
            # device_put(b)` would flag b as invariant of the outer loop.
            add_target(sub.target)
        elif isinstance(sub, ast.comprehension):
            add_target(sub.target)
        elif isinstance(sub, ast.withitem) and sub.optional_vars is not None:
            add_target(sub.optional_vars)
    return frozenset(names)


@rule("JGL009", "loop-invariant batch re-staged inside a per-job loop")
def duplicate_staging_in_loop(ctx: FileContext):
    """``device_put``/``dispatch_safe``/``stage_for`` of a value the loop
    never changes — the K-jobs duplicate-staging hazard: every iteration
    (typically one per subscribed job) re-flattens/re-transfers identical
    bytes over the host->device link, scaling the measured ingest
    bottleneck by K. Stage once before the loop, or route consumers
    through the per-stream DeviceEventCache (ADR 0110)."""
    for loop in ctx.nodes(ast.For):
        varying = None  # computed lazily: most loops stage nothing
        for node in ctx.walk_shallow(loop):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            qual = ctx.qualname(node.func)
            name = (
                node.func.id
                if isinstance(node.func, ast.Name)
                else getattr(node.func, "attr", None)
            )
            if qual not in _STAGING_QUALNAMES and name not in _STAGING_NAMES:
                continue
            if varying is None:
                varying = _loop_varying_names(ctx, loop)
            staged = node.args[0]
            if _is_constant(staged) or ctx.mentions_any(staged, varying):
                continue
            label = qual or name
            yield Finding(
                ctx.path,
                node.lineno,
                "JGL009",
                f"{label}() of a loop-invariant value inside a 'for' "
                "loop re-stages identical bytes every iteration (K "
                "subscribed jobs = K transfers of one batch); hoist the "
                "staging above the loop or share it through the "
                "per-stream DeviceEventCache (ADR 0110)",
            )


#: Loop target/iterable name TOKENS that mark a per-job fan-out: the
#: loop body runs once per subscribed job, so any device->host fetch in
#: it pays one device round trip PER JOB per tick. Matched as whole
#: underscore-separated identifier tokens — substring matching would
#: have 'rec' flag loops over 'precomputed' or 'recent_batches'
#: (precision over recall, the ADR 0112 contract).
_JOBISH_TOKENS = frozenset(
    {
        "job", "jobs",
        "rec", "recs", "record", "records",
        "offer", "offers",
        "member", "members",
        "workflow", "workflows",
    }
)

#: Method-call names whose results are (or may be) traced/device
#: values: a ``np.asarray`` of one inside the loop is a disguised
#: device->host fetch.
_TRACED_PRODUCERS = frozenset(
    {
        "step",
        "step_batch",
        "step_flat",
        "step_many",
        "finalize",
        "views",
        "views_of",
        "physical_window",
        "fold_window",
        "clear_window",
    }
)


def _mentions_jobish(node: ast.AST) -> bool:
    for n in ast.walk(node):
        name = None
        if isinstance(n, ast.Name):
            name = n.id
        elif isinstance(n, ast.Attribute):
            name = n.attr
        if name is not None and any(
            tok in _JOBISH_TOKENS for tok in name.lower().split("_")
        ):
            return True
    return False


@rule("JGL015", "device->host fetch inside a per-job loop")
def fetch_in_per_job_loop(ctx: FileContext):
    """``jax.device_get`` / ``.block_until_ready()`` / ``np.asarray`` of
    a traced result inside a loop over jobs — the K-round-trips publish
    hazard (ADR 0113): each iteration forces its own device->host sync,
    so K subscribed jobs pay K round trips per tick where one combined
    fetch would do. Batch device reads across the loop (pack outputs
    into one array and fetch once — ops/publish.py), or let the
    PublishCombiner serve the whole group from a single round trip."""
    for loop in ctx.nodes(ast.For):
        if not (
            _mentions_jobish(loop.target) or _mentions_jobish(loop.iter)
        ):
            continue
        # Names assigned in this loop from calls that produce traced
        # values: np.asarray of one is a fetch in disguise.
        traced_names: set[str] = set()
        for sub in ctx.walk_shallow(loop):
            if not isinstance(sub, ast.Assign):
                continue
            value = sub.value
            call = value
            if isinstance(call, ast.Call) and (
                (
                    isinstance(call.func, ast.Attribute)
                    and call.func.attr in _TRACED_PRODUCERS
                )
                or (
                    isinstance(call.func, ast.Name)
                    and call.func.id in _TRACED_PRODUCERS
                )
            ):
                for t in sub.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            traced_names.add(n.id)
        for node in ctx.walk_shallow(loop):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qualname(node.func)
            hit = None
            if qual == "jax.device_get":
                hit = "jax.device_get()"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            ):
                hit = ".block_until_ready()"
            elif (
                qual in ("numpy.asarray", "numpy.array")
                and node.args
                and traced_names
                and ctx.mentions_any(node.args[0], frozenset(traced_names))
            ):
                hit = f"{qual.replace('numpy.', 'np.', 1)}() of a traced result"
            if hit:
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL015",
                    f"{hit} inside a per-job loop forces one device->host "
                    "round trip per job per tick; pack the per-job "
                    "outputs into one fetch (ops/publish.py "
                    "PackedPublisher/PublishCombiner, ADR 0113) or hoist "
                    "the fetch below the loop",
                )


#: Dispatch names that donate their first positional argument — the
#: state (or states tuple) contract shared by ops/histogram's step
#: family, ``clear_window``, and the tick/publish combiners (the state
#: is local arg 0 per the make_publish_offer contract). Matched by
#: method/function NAME; the private jit handles (``_step_flat`` etc.)
#: intentionally do not match — they live inside the owning class,
#: where the wrapper methods are the audited surface.
_DONATING_DISPATCHES = frozenset(
    {
        "step",
        "step_batch",
        "step_flat",
        "step_arrays",
        "step_many",
        "tick_step",
        "clear_window",
    }
)

#: Names that donate only when the receiver names itself a
#: publisher/combiner: ``combiner.publish(requests)`` donates the
#: member states inside ``requests``; ``sink.publish(messages)`` is a
#: Kafka call and must stay quiet (precision over recall, ADR 0112).
_DONATING_GATED = frozenset({"publish", "tick"})
_PUBLISHER_RECEIVER_TOKENS = frozenset({"publisher", "combiner"})

#: Probe calls allowed on a consumed handle: they read buffer METADATA
#: (deletion flags), never values — the documented failure-path idiom.
_CONSUMED_PROBES = frozenset(
    {
        "is_deleted",
        "publish_args_consumed",
        "state_consumed",
        "_state_consumed",
    }
)

_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for a Name/Attribute chain of plain names, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return None if base is None else f"{base}.{node.attr}"
    return None


def _call_name(call: ast.Call) -> str | None:
    if isinstance(call.func, ast.Name):
        return call.func.id
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return None


def _donated_names(call: ast.Call) -> list[str]:
    """Dotted names whose buffers this call donates ([] = not a
    donating dispatch, or the donated operand is not a plain name)."""
    name = _call_name(call)
    if name is None or not call.args:
        return []
    if name in _DONATING_GATED:
        recv = (
            _dotted(call.func.value)
            if isinstance(call.func, ast.Attribute)
            else None
        )
        tokens = set((recv or "").lower().replace(".", "_").split("_"))
        if not tokens & _PUBLISHER_RECEIVER_TOKENS:
            return []
    elif name not in _DONATING_DISPATCHES:
        return []
    arg0 = call.args[0]
    elts = arg0.elts if isinstance(arg0, (ast.Tuple, ast.List)) else [arg0]
    return [d for e in elts if (d := _dotted(e)) is not None]


def _clear_name(tainted: dict[str, tuple[int, str]], name: str) -> None:
    """Rebinding ``name`` kills its taint (and any dotted extension)."""
    for key in list(tainted):
        if key == name or key.startswith(name + "."):
            del tainted[key]


def _clear_target(tgt: ast.AST, tainted: dict[str, tuple[int, str]]) -> None:
    if isinstance(tgt, (ast.Tuple, ast.List)):
        for elt in tgt.elts:
            _clear_target(elt, tainted)
        return
    if isinstance(tgt, ast.Starred):
        _clear_target(tgt.value, tainted)
        return
    name = _dotted(tgt)
    if name is not None:
        _clear_name(tainted, name)


def _walk_skipping(node: ast.AST, skip: set):
    """Child walk that descends into neither ``skip`` subtrees (donation
    arg sites, probe calls) nor nested callables (their execution
    context differs), nor compound-statement bodies (the block scanner
    recurses into those itself)."""
    for child in ast.iter_child_nodes(node):
        if child in skip or isinstance(child, (*_SCOPE_NODES, ast.stmt)):
            continue
        yield child
        yield from _walk_skipping(child, skip)


class _DonationScan:
    """Lexical post-donation-reuse scan over one function body.

    Over-approximation contract (ADR 0112, precision over recall):
    statements are processed in source order; loop bodies get a second
    pass so a donation feeding back into the next iteration is seen;
    ``except`` handlers are read-exempt (probing/rebuilding a consumed
    state there is the documented recovery idiom) but their assignments
    still clear taints.
    """

    def __init__(self, ctx: FileContext) -> None:
        self.ctx = ctx
        self.findings: list[Finding] = []

    def run(self, fn) -> None:
        self._block(fn.body, {}, report=True)

    # -- statement dispatch -----------------------------------------------
    def _block(self, stmts, tainted, *, report: bool) -> None:
        for stmt in stmts:
            self._stmt(stmt, tainted, report=report)

    def _stmt(self, stmt, tainted, *, report: bool) -> None:
        if isinstance(stmt, (*_SCOPE_NODES, ast.ClassDef)):
            return  # nested scope: runs later, under other bindings
        if isinstance(stmt, ast.Try):
            self._block(stmt.body, tainted, report=report)
            for handler in stmt.handlers:
                self._block(handler.body, tainted, report=False)
            self._block(stmt.orelse, tainted, report=report)
            self._block(stmt.finalbody, tainted, report=report)
            return
        if isinstance(stmt, ast.If):
            self._expr(stmt.test, tainted, report=report)
            self._block(stmt.body, tainted, report=report)
            self._block(stmt.orelse, tainted, report=report)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr(stmt.iter, tainted, report=report)
            _clear_target(stmt.target, tainted)
            # Two passes: a donation late in the body reaches the reads
            # at its top on the next iteration.
            self._block(stmt.body, tainted, report=report)
            _clear_target(stmt.target, tainted)
            self._block(stmt.body, tainted, report=report)
            self._block(stmt.orelse, tainted, report=report)
            return
        if isinstance(stmt, ast.While):
            self._expr(stmt.test, tainted, report=report)
            self._block(stmt.body, tainted, report=report)
            self._expr(stmt.test, tainted, report=report)
            self._block(stmt.body, tainted, report=report)
            self._block(stmt.orelse, tainted, report=report)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr, tainted, report=report)
                if item.optional_vars is not None:
                    _clear_target(item.optional_vars, tainted)
            self._block(stmt.body, tainted, report=report)
            return
        # Simple statement: reads, donations, then target clears.
        self._expr(stmt, tainted, report=report)
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                _clear_target(tgt, tainted)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            _clear_target(stmt.target, tainted)
        elif isinstance(stmt, (ast.Delete,)):
            for tgt in stmt.targets:
                _clear_target(tgt, tainted)

    # -- expression-level reads + donations -------------------------------
    def _expr(self, node, tainted, *, report: bool) -> None:
        donations: list[tuple[list[str], ast.Call]] = []
        skip: set = set()
        for sub in ast.walk(node):
            if isinstance(sub, _SCOPE_NODES):
                continue
            if not isinstance(sub, ast.Call):
                continue
            name = _call_name(sub)
            if name in _CONSUMED_PROBES:
                skip.add(sub)
                continue
            donated = _donated_names(sub)
            if donated:
                donations.append((donated, sub))
                skip.add(sub.args[0])
        if report:
            for sub in _walk_skipping(node, skip):
                if not isinstance(sub, (ast.Name, ast.Attribute)):
                    continue
                if not isinstance(getattr(sub, "ctx", None), ast.Load):
                    continue
                name = _dotted(sub)
                hit = tainted.get(name) if name is not None else None
                if hit is not None:
                    line, label = hit
                    self.findings.append(
                        Finding(
                            self.ctx.path,
                            sub.lineno,
                            "JGL016",
                            f"'{name}' is read after being donated to "
                            f"{label}() on line {line}: the dispatch "
                            "consumed its buffers (donate_argnums — XLA "
                            "may already have reused them), so this "
                            "reads a deleted array. Use the returned "
                            "state, rebuild via init_state(), or probe "
                            "only is_deleted()/publish_args_consumed() "
                            "in the failure path (ADR 0114)",
                        )
                    )
            for donated, call in donations:
                label = _call_name(call)
                for name in donated:
                    hit = tainted.get(name)
                    if hit is not None:
                        self.findings.append(
                            Finding(
                                self.ctx.path,
                                call.lineno,
                                "JGL016",
                                f"'{name}' is dispatched again via "
                                f"{label}() after being donated to "
                                f"{hit[1]}() on line {hit[0]}: the "
                                "first dispatch consumed its buffers — "
                                "re-stepping a consumed state reuses "
                                "freed memory; thread the returned "
                                "state through instead (ADR 0114)",
                            )
                        )
        for donated, call in donations:
            label = _call_name(call)
            for name in donated:
                tainted[name] = (call.lineno, label)


@rule("JGL016", "read of a donated state after a tick/step/publish dispatch")
def post_donation_reuse(ctx: FileContext):
    """A tick/step/publish dispatch donates its state argument
    (``donate_argnums``): after the call, the caller's handle points at
    buffers XLA has already reused for the outputs. Reading it again —
    or passing it to a second dispatch — is the post-donation-reuse
    hazard the one-dispatch tick program (ops/tick.py, ADR 0114) makes
    easy to write: the state now flows ``offer -> tick program ->
    carry``, and any code still holding the pre-tick handle is reading
    freed memory (a deleted-array error on JAX's slow path, silent
    aliasing on fast ones). Rebinding the handle from the dispatch's
    return clears the taint; ``except`` handlers may probe consumed-ness
    (``is_deleted``/``publish_args_consumed``) and rebuild."""
    for fn in ctx.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        scan = _DonationScan(ctx)
        scan.run(fn)
        yield from scan.findings


#: Import-value markers of MESH-SCOPED code: modules that name jax
#: sharding types or the repo's mesh layer in their imports. Detection
#: is import-based (never docstrings/comments), the ADR 0112 precision
#: contract.
_MESH_IMPORT_MARKERS = (
    "jax.sharding.",
    "shard_map",
    "sharded_hist",
    "sharded_qhist",
    "mesh_tick",
    "make_mesh",
    "mesh_from_spec",
)

#: Dispatch method names that consume staged arrays on a mesh-sharded
#: receiver (receiver tokens below): feeding a default-placed array in
#: forces an implicit reshard per call.
_MESH_DISPATCH_NAMES = frozenset(
    {"step", "step_batch", "step_many", "tick_step", "normalized"}
)
_MESH_RECEIVER_TOKENS = frozenset({"sharded", "mesh"})

#: Calls whose result is committed to (or destined for) the DEFAULT
#: placement: dispatch_safe by name, jnp.asarray/array by qualname, and
#: single-argument jax.device_put (no device/sharding).
_DEFAULT_STAGE_QUALNAMES = frozenset(
    {"jax.numpy.asarray", "jax.numpy.array"}
)


def _is_mesh_scoped(ctx: FileContext) -> bool:
    for qual in ctx._names.values():
        if any(marker in qual for marker in _MESH_IMPORT_MARKERS):
            return True
    return False


def _is_default_placed_stage(ctx: FileContext, call: ast.Call) -> bool:
    qual = ctx.qualname(call.func)
    if qual in _DEFAULT_STAGE_QUALNAMES:
        return True
    if qual == "jax.device_put":
        return len(call.args) < 2 and not call.keywords
    name = (
        call.func.id
        if isinstance(call.func, ast.Name)
        else getattr(call.func, "attr", None)
    )
    return name == "dispatch_safe"


@rule("JGL017", "implicit resharding in mesh-scoped code")
def implicit_resharding(ctx: FileContext):
    """Two shapes of the same hazard (ADR 0115): an array placed on the
    DEFAULT device meeting a mesh-compiled dispatch. (a) ``jax.device_put``
    without an explicit device/sharding inside mesh-scoped code — the
    array commits to the default device, so the mesh program that
    consumes it pays a second device->device copy per call (or rejects
    the device mix outright, degrading the whole group). (b) a value
    staged by ``dispatch_safe``/``jnp.asarray``/placement-less
    ``device_put`` inside a per-job loop and fed to a mesh-sharded
    receiver's dispatch — the K-jobs variant: one implicit reshard per
    job per window. Stage onto the target NamedSharding in ONE hop
    (``stage_for``) or through the slice-keyed stream cache instead."""
    if not _is_mesh_scoped(ctx):
        return
    for node in ctx.nodes(ast.Call):
        if ctx.qualname(node.func) != "jax.device_put":
            continue
        placed = len(node.args) >= 2 or bool(node.keywords)
        if not placed:
            yield Finding(
                ctx.path,
                node.lineno,
                "JGL017",
                "jax.device_put without an explicit device/sharding in "
                "mesh-scoped code commits the array to the DEFAULT "
                "device; a mesh-compiled dispatch consuming it must "
                "implicitly reshard (a second device->device copy per "
                "call) or reject the device mix. Place onto the target "
                "NamedSharding/slice in one hop (stage_for, ADR 0115)",
            )
    for loop in ctx.nodes(ast.For):
        if not (
            _mentions_jobish(loop.target) or _mentions_jobish(loop.iter)
        ):
            continue
        default_placed: set[str] = set()
        for sub in ctx.walk_shallow(loop):
            if not isinstance(sub, ast.Assign):
                continue
            value = sub.value
            if isinstance(value, ast.Call) and _is_default_placed_stage(
                ctx, value
            ):
                for t in sub.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            default_placed.add(n.id)
        if not default_placed:
            continue
        frozen = frozenset(default_placed)
        for node in ctx.walk_shallow(loop):
            if not isinstance(node, ast.Call) or not isinstance(
                node.func, ast.Attribute
            ):
                continue
            if node.func.attr not in _MESH_DISPATCH_NAMES:
                continue
            recv = _dotted(node.func.value)
            tokens = set((recv or "").lower().replace(".", "_").split("_"))
            if not tokens & _MESH_RECEIVER_TOKENS:
                continue
            if any(ctx.mentions_any(arg, frozen) for arg in node.args):
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL017",
                    f"default-placed staged value fed to mesh-sharded "
                    f"dispatch '{node.func.attr}' inside a per-job loop: "
                    "each call implicitly reshards the same bytes onto "
                    "the mesh (K jobs = K redundant copies of one "
                    "batch). Stage once onto the event NamedSharding "
                    "(stage_for / the slice-keyed stream cache, "
                    "ADR 0110/0115) before the loop",
                )


#: Host clock reads: under trace these run ONCE, at trace time — the
#: jitted program replays without them, so the "measurement" is the
#: tracer's wall clock, not the execution's.
_TIMING_QUALNAMES = frozenset(
    {
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.time",
        "time.time_ns",
    }
)

#: Telemetry mutation methods; gated on a telemetry-ish receiver below
#: (``.set`` alone is far too common to flag bare).
_TELEMETRY_METHODS = frozenset(
    {
        "inc", "dec", "observe", "record", "set", "span", "stage",
        "aggregate", "annotated",
    }
)

#: Receiver-name tokens that mark a telemetry/timing object: the
#: process registry's instruments and children (counter/gauge/
#: histogram), the tick tracer, StageTimer, and the conventional
#: METRICS/metrics singletons.
_TELEMETRY_RECEIVER_TOKENS = frozenset(
    {
        "metrics",
        "metric",
        "counter",
        "counters",
        "gauge",
        "gauges",
        "histogram",
        "tracer",
        "telemetry",
        "timer",
        "registry",
        "instrument",
    }
)


def _telemetry_receiver(node: ast.AST) -> bool:
    recv = _dotted(node)
    if recv is None:
        return False
    tokens = set(recv.lower().replace(".", "_").split("_"))
    return bool(tokens & _TELEMETRY_RECEIVER_TOKENS)


@rule("JGL018", "telemetry/timing call inside jit-traced code")
def telemetry_in_jit(ctx: FileContext):
    """Instrumentation that never measures what it claims (ADR 0116):
    inside a jit-traced region, ``time.perf_counter()`` (and friends)
    executes ONCE at trace time — the compiled program replays without
    it, so the recorded 'duration' is trace overhead on the first call
    and a stale constant forever after. The same applies to registry
    increments (``counter.inc``, ``histogram.observe``,
    ``METRICS.record``) and tracer span enter/exit: they fire per
    TRACE, not per execution, silently under-counting by the cache hit
    rate. Time and count around the dispatch on the host side
    (ops/tick.py's combiner, EventHistogrammer._dispatch_fused are the
    worked examples); keep traced bodies pure."""
    for fn in ctx.jit_regions:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qualname(node.func)
            if qual in _TIMING_QUALNAMES:
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL018",
                    f"{qual}() {_jit_label(ctx, fn)} runs at TRACE time "
                    "only: the compiled program replays without it, so "
                    "it measures tracing, not execution (and reads as a "
                    "frozen constant on cache hits). Time around the "
                    "dispatch on the host side instead",
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _TELEMETRY_METHODS
                and _telemetry_receiver(node.func.value)
            ):
                yield Finding(
                    ctx.path,
                    node.lineno,
                    "JGL018",
                    f"telemetry call '.{node.func.attr}()' on "
                    f"'{_dotted(node.func.value)}' {_jit_label(ctx, fn)} "
                    "fires once per TRACE, not per execution — counters "
                    "silently under-count by the jit cache hit rate and "
                    "span timings measure trace overhead. Record on the "
                    "host side, outside the jit boundary",
                )


# -- JGL021: traced-value escape --------------------------------------------

#: Calls whose result is a traced array when they run under trace.
_TRACED_PRODUCER_PREFIXES = (
    "jax.numpy.",
    "jax.lax.",
    "jax.nn.",
    "jax.scipy.",
    "jax.random.",
    "jax.ops.",
)

#: Container-mutating method calls through which a traced value can
#: escape into state that outlives the traced call.
_ESCAPE_MUTATORS = frozenset(
    {"append", "add", "update", "extend", "insert", "setdefault",
     "appendleft", "put", "put_nowait"}
)


def _store_roots(target: ast.AST):
    """Flattened assignment-target leaves (tuple unpacking expanded)."""
    if isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from _store_roots(elt)
    else:
        yield target


class _TaintState:
    """Reaching-defs-based taint for one traced function: a definition
    site is tainted when its RHS derives from a parameter or from a
    traced-producer call; taint queries are then per-(statement,
    expression), so a name rebound to a host constant after a traced
    use stays clean from there on."""

    def __init__(self, ctx: FileContext, fn) -> None:
        self.ctx = ctx
        self.fn = fn
        self.cfg = ctx.cfg(fn)
        self.reaching = ctx.reaching(fn)
        self.tainted_defs: set[tuple[str, int]] = {
            (p, self.cfg.ENTRY) for p in ctx.params(fn)
        }
        self._solve()

    def _producer_call(self, expr: ast.AST) -> bool:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                qual = self.ctx.qualname(sub.func)
                if qual is not None and qual.startswith(
                    _TRACED_PRODUCER_PREFIXES
                ):
                    return True
        return False

    def name_tainted(self, node: int, name: str) -> bool:
        """Is any definition of ``name`` reaching ``node`` tainted?"""
        for n, def_node in self.reaching.get(node, frozenset()):
            if n == name and (n, def_node) in self.tainted_defs:
                return True
        return False

    def expr_tainted(self, node: int, expr: ast.AST) -> bool:
        """Is ``expr``, evaluated at CFG node ``node``, traced-derived?"""
        if self._producer_call(expr):
            return True
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Name) and isinstance(
                sub.ctx, ast.Load
            ):
                if self.name_tainted(node, sub.id):
                    return True
        return False

    def _solve(self) -> None:
        binds: list[tuple[int, ast.AST, list[str]]] = []
        #: (node, name) pairs where an AugAssign target also READS the
        #: name — taint flows through even though the Name is a Store.
        aug_reads: list[tuple[int, str]] = []
        for node, stmt in self.cfg.statements():
            value = None
            names: list[str] = []
            if isinstance(stmt, ast.Assign):
                value = stmt.value
                for t in stmt.targets:
                    for leaf in _store_roots(t):
                        if isinstance(leaf, ast.Name):
                            names.append(leaf.id)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                value = stmt.value
                if isinstance(stmt.target, ast.Name):
                    names.append(stmt.target.id)
            elif isinstance(stmt, ast.AugAssign):
                # x += traced taints x; x += 1 KEEPS x's own taint —
                # the target reads itself, but its Name is in Store
                # context, so the taint query must name it explicitly
                # (an expr-only check would wash x on every no-op
                # augment).
                value = stmt.value
                if isinstance(stmt.target, ast.Name):
                    names.append(stmt.target.id)
                    aug_reads.append((node, stmt.target.id))
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                value = stmt.iter
                for leaf in _store_roots(stmt.target):
                    if isinstance(leaf, ast.Name):
                        names.append(leaf.id)
            if names and value is not None:
                binds.append((node, value, names))
        aug_by_node: dict[int, set[str]] = {}
        for node, name in aug_reads:
            aug_by_node.setdefault(node, set()).add(name)
        changed = True
        while changed:
            changed = False
            for node, value, names in binds:
                hit = self.expr_tainted(node, value) or any(
                    self.name_tainted(node, n)
                    for n in aug_by_node.get(node, ())
                )
                if hit:
                    for name in names:
                        if (name, node) not in self.tainted_defs:
                            self.tainted_defs.add((name, node))
                            changed = True


def _outer_scope_receiver(
    ctx: FileContext, fn, expr: ast.AST, module_names: frozenset[str]
) -> str | None:
    """A receiver that outlives the traced call: ``self.<attr>``, a
    module-level container, or a closure name from an enclosing def.
    Returns a display name, or None for locals/params."""
    if (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
    ):
        return f"self.{expr.attr}"
    if isinstance(expr, ast.Name):
        local = {
            name
            for name, _def in ctx.reaching(fn).get(
                ctx.cfg(fn).EXIT, frozenset()
            )
        }
        # Collect every name the function binds anywhere (reaching defs
        # at EXIT can miss names bound only on abandoned paths).
        bound: set[str] = set(ctx.params(fn))
        for _node, stmt in ctx.cfg(fn).statements():
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, ast.Store
                ):
                    bound.add(sub.id)
        bound |= local
        if expr.id in bound:
            return None
        if expr.id in module_names:
            return expr.id
        # Name from an enclosing function scope (closure).
        for anc in ctx.ancestors(fn):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return expr.id
        return None
    return None


@rule("JGL021", "traced value escaping the jit boundary into host state")
def traced_value_escape(ctx: FileContext):
    """The leaked-tracer class. A jit-traced body executes ONCE per
    trace; any value it binds is a Tracer, and storing one into
    ``self.*``, a module global, or a container that outlives the call
    leaks it: the next host-side read raises
    ``UnexpectedTracerError`` — or worse, silently holds a stale
    trace-time constant that never updates again. Dataflow-precise:
    taint starts at the traced parameters and jnp/lax producer calls
    and follows reaching definitions, so binding a host constant to
    ``self`` under trace (config captured at trace time, legal if
    intentional) is not flagged — only traced data escaping is."""
    module_names = frozenset(
        t.id
        for node in ast.iter_child_nodes(ctx.tree)
        if isinstance(node, (ast.Assign, ast.AnnAssign))
        for t in (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        if isinstance(t, ast.Name)
    )
    for fn in ctx.jit_regions:
        if isinstance(fn, ast.Lambda):
            continue
        taint = _TaintState(ctx, fn)
        for node, stmt in taint.cfg.statements():
            if isinstance(stmt, ast.ExceptHandler):
                continue
            # Stores: self.x = traced / GLOBAL[k] = traced / outer = ...
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = (
                    stmt.value
                    if not isinstance(stmt, ast.AugAssign)
                    else stmt
                )
                if value is None or not taint.expr_tainted(node, value):
                    continue
                targets = (
                    stmt.targets
                    if isinstance(stmt, ast.Assign)
                    else [stmt.target]
                )
                for t in targets:
                    for leaf in _store_roots(t):
                        base = leaf
                        via = "assigned to"
                        if isinstance(leaf, ast.Subscript):
                            base = leaf.value
                            via = "stored into"
                        dest = _outer_scope_receiver(
                            ctx, fn, base, module_names
                        )
                        if dest is None and isinstance(
                            base, ast.Attribute
                        ):
                            dest = _outer_scope_receiver(
                                ctx, fn, base.value, module_names
                            )
                        if dest is not None:
                            yield Finding(
                                ctx.path,
                                stmt.lineno,
                                "JGL021",
                                f"traced value {via} '{dest}' "
                                f"{_jit_label(ctx, fn)} escapes the jit "
                                "boundary: the store runs once at TRACE "
                                "time and leaks a Tracer into host "
                                "state (UnexpectedTracerError on the "
                                "next host read, or a frozen stale "
                                "constant). Return the value instead "
                                "and store it outside the traced call",
                            )
            # Mutator calls: self._hist.append(traced) etc.
            for sub in walk_own(stmt):
                if not (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _ESCAPE_MUTATORS
                ):
                    continue
                args_tainted = any(
                    taint.expr_tainted(node, a) for a in sub.args
                ) or any(
                    taint.expr_tainted(node, kw.value)
                    for kw in sub.keywords
                )
                if not args_tainted:
                    continue
                dest = _outer_scope_receiver(
                    ctx, fn, sub.func.value, module_names
                )
                if dest is not None:
                    yield Finding(
                        ctx.path,
                        sub.lineno,
                        "JGL021",
                        f"traced value passed to "
                        f"'{dest}.{sub.func.attr}()' "
                        f"{_jit_label(ctx, fn)} escapes into a "
                        "container that outlives the trace — the "
                        "mutation happens once at TRACE time and the "
                        "container keeps a leaked Tracer. Return the "
                        "value and collect it on the host side",
                    )


# -- JGL027: static-table mutation without digest invalidation ---------------

#: Self-attribute stems that read as a device-resident constant/LUT —
#: the data every staging/fusion/static-publish key fingerprints.
_TABLE_STEMS = ("lut", "qmap", "table", "calib", "flatfield")
#: Substrings that mark an attr as table METADATA, not the table
#: (shape/sharding descriptors, names, the invalidation fields
#: themselves, and content-neutral residence caches — per-device
#: copies of already-digested bytes).
_TABLE_META = (
    "shape", "sharding", "name", "digest", "version", "token", "epoch",
    "cache", "by_device",
)
#: Methods whose writes are the sanctioned mutation paths: construction,
#: the swap_*/set_* re-fingerprinting surface, placement re-staging, and
#: the adopt/install/build helpers those route through.
_SANCTIONED_PREFIXES = (
    "swap_", "set_", "place_", "load_", "restore_", "_build", "_adopt",
    "_install",
)
_SANCTIONED_EXACT = frozenset({"__init__", "__post_init__", "clear"})
#: Attr-write (or callee-name) evidence that the method feeds the
#: invalidation path itself.
_INVALIDATION_HINTS = ("digest", "version", "token", "epoch", "invalidate")
#: Class methods/properties whose presence marks the class as carrying a
#: key surface (ADR 0110/0113): only these classes are in scope — a
#: plain cache dict named `_table` in an unrelated class is not a
#: staged-wire hazard.
_KEY_SURFACE = frozenset(
    {"layout_digest", "stage_key", "partition_key", "fuse_key"}
)


def _table_attr(name: str) -> bool:
    lowered = name.lower()
    if any(meta in lowered for meta in _TABLE_META):
        return False
    return any(stem in lowered for stem in _TABLE_STEMS)


def _self_attr_targets(stmt: ast.AST):
    """Attribute targets on ``self`` of one assignment statement,
    including tuple-unpacking targets AND subscript stores
    (``self._lut[:] = new`` mutates the table in place without even
    changing the object identity — the sneakiest instance of the
    staleness class, since cached digests AND staged device copies
    keep pointing at the mutated buffer)."""
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    else:
        return
    for target in targets:
        stack = [target]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.Tuple, ast.List)):
                stack.extend(node.elts)
            elif isinstance(node, ast.Subscript):
                stack.append(node.value)
            elif (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            ):
                yield node


def _method_invalidates(fn: ast.FunctionDef) -> bool:
    """True when the method also touches the invalidation surface: a
    self-attr write whose name carries digest/version/token/epoch, or a
    call to an invalidate/re-digest helper."""
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            for attr in _self_attr_targets(node):
                lowered = attr.attr.lower()
                if any(h in lowered for h in _INVALIDATION_HINTS):
                    return True
        if isinstance(node, ast.Call):
            callee = (
                node.func.attr
                if isinstance(node.func, ast.Attribute)
                else node.func.id if isinstance(node.func, ast.Name) else ""
            )
            if any(h in callee.lower() for h in _INVALIDATION_HINTS):
                return True
    return False


def _rhs_reads_host_twin(stmt: ast.AST) -> bool:
    """True when the assignment's value reads a ``self.*host*`` attr —
    the lazy device materialization of a content-equal host copy
    (``self._lut_dev = jnp.asarray(self.lut_host)``): the content (and
    so the digest) is unchanged, only the residence moves."""
    value = getattr(stmt, "value", None)
    if value is None:
        return False
    for node in ast.walk(value):
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
            and "host" in node.attr.lower()
        ):
            return True
    return False


@rule(
    "JGL027",
    "device-resident table mutated outside a digest-invalidating path",
)
def table_mutation_without_invalidation(ctx: FileContext):
    """Scope: classes exposing a staging-key surface (``layout_digest``
    / ``stage_key`` / ``partition_key`` / ``fuse_key`` — the ADR 0110
    fingerprint methods), plus any module whose filename says
    calibration. In scope, a write to a self-attr that reads as a
    static table (``*lut*``/``*qmap*``/``*table*``/``*calib*``/
    ``*flatfield*``, metadata names excluded) must happen on a
    sanctioned path: ``__init__``/``__post_init__``/``clear``, a
    ``swap_*``/``set_*``/``place_*``/``load_*``/``restore_*`` method,
    an ``_adopt*``/``_install*``/``_build*`` helper, a method that also
    writes a digest/version/token/epoch attr (or calls an
    ``invalidate``/re-digest helper), or a lazy device materialization
    reading the ``*host*`` twin.

    Anything else is the silent-staleness bug class ADR 0110/0113 key
    discipline exists to prevent: the staged wire, the jitted tick
    program and the static-publish cache are all keyed on the table's
    fingerprint — a bare ``self._lut = new`` keeps serving results
    computed under the OLD table for as long as those keys survive,
    with no error and no metric. Route the write through a
    ``swap_*``/``set_*`` method that re-fingerprints (see
    workloads/calibration.py for the pattern).
    """
    module_scope = "calib" in Path(ctx.path).stem.lower()
    for cls in ctx.nodes(ast.ClassDef):
        methods = [
            node
            for node in cls.body
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        in_scope = module_scope or any(
            m.name in _KEY_SURFACE or m.name.endswith("static_token")
            for m in methods
        )
        if not in_scope:
            continue
        for fn in methods:
            name = fn.name
            if name in _SANCTIONED_EXACT or name.startswith(
                _SANCTIONED_PREFIXES
            ):
                continue
            hits = [
                (stmt, attr)
                for stmt in ast.walk(fn)
                for attr in _self_attr_targets(stmt)
                if _table_attr(attr.attr)
                and not _rhs_reads_host_twin(stmt)
            ]
            if not hits or _method_invalidates(fn):
                continue
            stmt, attr = hits[0]
            yield Finding(
                ctx.path,
                stmt.lineno,
                "JGL027",
                f"'{cls.name}.{name}' writes static-table attr "
                f"'self.{attr.attr}' outside a swap_*/set_* path and "
                "without bumping a digest/version/token — staged wires, "
                "tick programs and static-publish caches keyed on the "
                "old fingerprint will keep serving results computed "
                "under the OLD table (ADR 0110/0113 invalidation rule). "
                "Route the write through a swap_*/set_* method that "
                "re-fingerprints",
            )
