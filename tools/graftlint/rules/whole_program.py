"""Whole-program rules: JGL011 (lock-order inversion), JGL012
(cross-thread-role unlocked writes), JGL013 (mutable hand-off through a
queue without detach), JGL014 (jit key coherence).

All four run on :class:`~..project.ProjectContext` — they see every
analyzed file at once, which is the point: the hazards they catch are
invisible per-file (a lock pair ordered one way in the batcher and the
other way in the pipeline; a counter written from two thread entry
points defined modules apart; a ``stage_key`` that silently drops an
attribute its jitted kernel reads). Precision model and known
imprecision: docs/adr/0112 and docs/graftlint.md "Analysis limitations".
"""

from __future__ import annotations

from collections import defaultdict

from ..findings import Finding
from ..project import _PRE_THREAD_METHODS, ProjectContext
from ..registry import meta_rule, project_rule


@project_rule(
    "JGL011", "lock-order inversion across the project lock graph"
)
def lock_order_inversion(project: ProjectContext):
    """Cycle detection over the cross-module lock-acquisition graph:
    an edge A→B means some thread acquires B while holding A (lexically
    nested ``with``, or a call made under A into code that may acquire
    B — transitively, across modules). Any cycle is a deadlock waiting
    for the right interleaving."""
    edges = project.lock_edges()
    adj: dict[str, set[str]] = defaultdict(set)
    for a, b in edges:
        adj[a].add(b)

    # Iterative Tarjan SCC.
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    sccs: list[set[str]] = []
    counter = 0
    for root in sorted(adj):
        if root in index:
            continue
        work = [(root, iter(sorted(adj.get(root, ()))))]
        index[root] = low[root] = counter
        counter += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter
                    counter += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(sorted(adj.get(nxt, ())))))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc: set[str] = set()
                while True:
                    member = stack.pop()
                    on_stack.discard(member)
                    scc.add(member)
                    if member == node:
                        break
                if len(scc) > 1:
                    sccs.append(scc)

    for scc in sccs:
        cycle_edges = sorted(
            (a, b) for (a, b) in edges if a in scc and b in scc
        )
        for a, b in cycle_edges:
            path, line, how = edges[(a, b)]
            # Name one counter-edge so the report shows both halves of
            # the inversion without the reader re-deriving the cycle.
            # Path only, no line number: baseline matching is
            # line-insensitive by contract, and a line here would let
            # unrelated edits resurrect baselined findings.
            counter_site = next(
                (
                    f"in {edges[(x, y)][0]}"
                    for (x, y) in cycle_edges
                    if x == b
                ),
                "elsewhere in the cycle",
            )
            yield Finding(
                path,
                line,
                "JGL011",
                f"lock-order inversion: '{b}' is acquired while holding "
                f"'{a}' here ({how}), but the opposite order is taken at "
                f"{counter_site} — two threads interleaving these paths "
                "deadlock; pick one global order (or drop one lock scope)",
            )


@project_rule(
    "JGL012",
    "attribute written from multiple thread roles without a common lock",
)
def cross_role_unlocked_write(project: ProjectContext):
    """The interprocedural successor of lexical JGL004: collect every
    ``self.<attr>`` write per class, infer which thread roles reach each
    writing method through the call graph, and require writes reachable
    from ≥2 roles to share one guarding lock. ``__init__``-time writes
    happen before threads exist and are exempt."""
    groups: dict[tuple[str, str, str], list] = defaultdict(list)
    for ff in project.facts:
        for w in ff.writes:
            if w.method in _PRE_THREAD_METHODS:
                continue
            groups[(w.path, w.cls, w.attr)].append(w)
    for (path, cls, attr), sites in sorted(groups.items()):
        roles: set[str] = set()
        for site in sites:
            roles.update(project.roles_of(site.func))
        if len(roles) < 2:
            continue
        writers = sorted({s.method for s in sites})
        unguarded = [s for s in sites if not s.held]
        if unguarded:
            # One finding PER unguarded site (not just the first):
            # every site is individually hazardous, and each needs its
            # own suppression to stay visible in the ledger — a single
            # collapsed finding would make the siblings' suppressions
            # read as stale to the JGL024 audit.
            for site in sorted(unguarded, key=lambda s: s.lineno):
                yield Finding(
                    site.path,
                    site.lineno,
                    "JGL012",
                    f"self.{attr} is written from thread roles "
                    f"{sorted(roles)} (writers: {writers}) but this "
                    f"write in '{cls}.{site.method}' holds no lock — "
                    "concurrent writes interleave; guard every write "
                    "with one shared lock",
                )
            continue
        common = set(sites[0].held)
        for site in sites[1:]:
            common &= set(site.held)
        if not common:
            site = min(sites, key=lambda s: s.lineno)
            yield Finding(
                site.path,
                site.lineno,
                "JGL012",
                f"self.{attr} is written from thread roles "
                f"{sorted(roles)} under DIFFERENT locks "
                f"({sorted({h for s in sites for h in s.held})}) — "
                "disjoint locks serialize nothing; guard every write "
                "with one shared lock",
            )


@project_rule(
    "JGL013",
    "mutable staged value escaping through queue.put without detach/copy",
)
def mutable_queue_escape(project: ProjectContext):
    """A mutable event carrier (EventBatch / StagedEvents / DataArray)
    handed to another thread through ``queue.put`` without ``.detach()``
    or ``.copy()`` aliases live buffers across the boundary: the
    producer's next window mutates arrays the consumer is still reading
    (ADR 0111's detach-before-hand-off discipline). Direct puts are
    flagged where they happen; puts through a forwarding helper
    (``self._put(q, item)``) are flagged at the call site that supplied
    the un-detached value."""
    for ff in project.facts:
        for put in ff.puts:
            yield Finding(
                put.path,
                put.lineno,
                "JGL013",
                f"'{put.value}' ({put.type_name}) crosses a queue.put "
                "thread boundary without .detach()/copy — the producer "
                "mutates buffers the consumer still reads; hand off an "
                "owned copy",
            )
    forwarders: dict[str, set[int]] = defaultdict(set)
    for ff in project.facts:
        for fwd in ff.forwards:
            forwarders[fwd.func].add(fwd.index)
    if not forwarders:
        return
    for ff in project.facts:
        for ta in ff.typed_args:
            for target in project._resolve_name(
                ta.callee, ta.receiver_cls, ta.plain, ta.module, ta.hint
            ):
                if ta.index in forwarders.get(target, ()):
                    fn = project.functions.get(target)
                    where = (
                        f"{fn.cls + '.' if fn and fn.cls else ''}"
                        f"{fn.name if fn else ta.callee}"
                    )
                    yield Finding(
                        ta.path,
                        ta.lineno,
                        "JGL013",
                        f"'{ta.value}' ({ta.type_name}) flows into a "
                        f"queue.put inside '{where}()' without "
                        ".detach()/copy — the hand-off aliases live "
                        "buffers across threads; detach before passing",
                    )


@project_rule(
    "JGL014",
    "trace-relevant attribute read in a jitted kernel missing from its "
    "staging/fusion key",
)
def jit_key_coherence(project: ProjectContext):
    """Attributes read inside a jitted/fused function are baked into the
    compiled program at trace time, and the stage-once cache + fused
    stepping reuse staged arrays and grouped dispatches by the class's
    ``stage_key``/``partition_key``/``fuse_key`` tuples (ADR 0110/0111).
    An attribute the kernel reads but no key mentions is a re-keying
    bug: change the attribute and the cache keeps serving bytes staged
    under the old value.
    Coverage is by attribute root (``self._proj.layout_digest`` in a key
    covers every ``self._proj.*`` read); attributes that are pure
    functions of keyed ones are declared once per class with
    ``# graft: key-derived=...``."""
    for ff in project.facts:
        for kc in ff.key_classes:
            covered = set(kc.covered) | set(kc.derived)
            seen: set[str] = set()
            for attr, lineno, fname in kc.jit_reads:
                if attr in covered or attr in seen:
                    continue
                seen.add(attr)
                yield Finding(
                    kc.path,
                    lineno,
                    "JGL014",
                    f"self.{attr} is read inside jitted '{fname}' but "
                    f"appears in none of {kc.cls}'s key tuples "
                    f"({', '.join(kc.key_funcs)}) — a change to it would "
                    "reuse stale staged arrays/fused programs under an "
                    "unchanged key; add it to the key, or declare "
                    f"'# graft: key-derived={attr} <why>' if it is a "
                    "pure function of keyed attributes",
                )


@meta_rule(
    "JGL024",
    "suppression comment whose rule no longer fires on that line",
)
def stale_suppression(path, suppressions, findings, select):
    """The suppression ledger's rot guard. A ``# graftlint:
    disable=JGLxxx`` earns its keep only while the named rule actually
    fires on the suppressed line — after a refactor removes the hazard
    (or moves it), the comment lingers and silently masks the NEXT
    genuine finding someone introduces there. This audit runs after
    both analysis passes over the pre-suppression findings: a line
    directive is live when its rule fires on the directive's line or
    the one below it (the two placements the suppression layer
    honors); a ``disable-file=`` is live when the rule fires anywhere
    in the file. Stale ones are reported at the directive.

    Directives naming rules excluded by ``--select`` are not judged
    (their rule did not run, so absence of findings proves nothing);
    ``disable=all`` (generated files) is exempt — it cannot be
    enumerated; ``JGL024`` entries are likewise skipped (a directive
    suppressing this audit is self-referential). A directive naming a
    rule id that does not exist at all is always stale."""
    from ..registry import RULES

    def audit(names, live):
        stale: list[str] = []
        for r in sorted(names):
            if r in ("all", "JGL024"):
                continue
            if r not in RULES:
                stale.append(f"{r} (no such rule)")
                continue
            if select is not None and r not in select:
                continue
            if not live(r):
                stale.append(r)
        return stale

    for lineno, names in sorted(suppressions.by_line.items()):
        stale = audit(
            names,
            lambda r: any(
                f.rule == r and f.line in (lineno, lineno + 1)
                for f in findings
            ),
        )
        if stale:
            yield Finding(
                path,
                lineno,
                "JGL024",
                f"stale suppression: {', '.join(stale)} no longer "
                "fire(s) on this line — the comment now only masks "
                "the next genuine finding here; delete it (or fix the "
                "rule id)",
            )
    if suppressions.file_wide:
        stale = audit(
            suppressions.file_wide,
            lambda r: any(f.rule == r for f in findings),
        )
        if stale:
            lineno = min(
                suppressions.file_wide_lines.get(
                    s.split(" ")[0], 1
                )
                for s in stale
            )
            yield Finding(
                path,
                lineno,
                "JGL024",
                f"stale file-wide suppression: {', '.join(stale)} "
                "fire(s) nowhere in this file — delete the "
                "disable-file directive (or fix the rule id)",
            )
