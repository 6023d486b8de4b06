"""The trace pass: AOT-lower every registered tick program and prove
the performance contract statically (ADR 0123).

"Static" here means *abstract lowering*: each family's builder
(``esslivedata_tpu.harness.tick_contract``) assembles the exact jitted
program the live JobManager would dispatch, and this engine calls
``fn.lower(*args)`` under ``JAX_PLATFORMS=cpu`` — tracing plus
StableHLO emission, never an XLA compile, never a device. The five
JGL10x checks then read the lowering:

- JGL101 — executable count per tick == 1 (registry-level: a family
  whose tick needs a second program is the pre-ADR-0114 regression).
- JGL102 — every rolling-state leaf is donated in ``args_info`` (the
  lowered computation's own donation record, not the call site), and
  no shared staged-wire leaf is (other window consumers hold them).
- JGL103 — rebuilding the family with a swapped digest-keyed table
  re-lowers to identical key material AND byte-identical StableHLO:
  the swap costs zero XLA recompilation, proven with no device.
- JGL104 — no callback/host-transfer primitive anywhere in the traced
  jaxpr (recursively, through nested jaxprs).
- JGL105 — publish output avals match the family's declared wire
  schema (``TICK_WIRE_SCHEMA``) and every dtype maps into the da00
  enum (schemas/da00_dataarray.fbs) the delta codec can carry.

Findings anchor at the owning workflow's defining file, so inline
suppressions, the findings baseline and the JGL024 ledger audit all
apply unchanged. Fingerprints (executables, donation set, output
avals, swap stability) feed the tickcontract baseline for drift
detection (JGL100).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from ..findings import Finding
from .contract_baseline import diff_fingerprint

#: Primitives that smuggle host work into the traced program. Any of
#: these inside a tick body is a per-tick host round trip — exactly
#: what the one-dispatch contract exists to forbid.
_HOST_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",  # what jax.debug.print traces to since jax 0.9
        "callback",
        "infeed",
        "outfeed",
    }
)

#: Where trace findings about the baseline itself anchor.
_BASELINE_PATH = "tickcontract-baseline.json"


@dataclass
class TraceReport:
    findings: list["Finding"] = field(default_factory=list)
    errors: list[str] = field(default_factory=list)
    #: Set (with a human reason) when the pass could not run at all —
    #: the CLI prints it as a visible notice, never a silent pass.
    skipped: str | None = None
    #: family -> contract fingerprint (baseline material).
    fingerprints: dict[str, dict] = field(default_factory=dict)
    #: True when the results were replayed from the lowering cache
    #: (no jax import, no lowering — see ``lowering_cache``).
    cache_hit: bool = False


def _import_jax():
    """Import jax for lowering-only use. ``JAX_PLATFORMS`` defaults to
    cpu BEFORE the first import so the pass needs no accelerator; an
    explicit setting (a TPU-attached CI lane) is respected."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax  # noqa: F401 — availability probe + return value

    return jax


def _load_specs():
    """The real program registry, importable from a source checkout
    even when ``src/`` is not on ``sys.path`` (the CLI case)."""
    import sys
    from pathlib import Path

    try:
        from esslivedata_tpu.harness import tick_contract
    except ImportError:
        src = Path("src").resolve()
        if not (src / "esslivedata_tpu").is_dir():
            raise
        sys.path.insert(0, str(src))
        from esslivedata_tpu.harness import tick_contract
    return tick_contract.load_registry()


def _leaf_spans(jax, args) -> list[tuple[int, int]]:
    """Per-argument [start, stop) ranges into the flattened leaf order
    — ``Lowered.args_info`` is a pytree over the SAME structure, so
    donation flags come back per leaf, not per argument."""
    spans = []
    offset = 0
    for arg in args:
        n = len(jax.tree_util.tree_leaves(arg))
        spans.append((offset, offset + n))
        offset += n
    return spans


def _donated_leaves(jax, lowered) -> tuple[bool, ...]:
    return tuple(
        bool(getattr(info, "donated", False))
        for info in jax.tree_util.tree_leaves(lowered.args_info)
    )


def _iter_subjaxprs(value):
    """Nested jaxprs hiding in an eqn's params (pjit bodies, scan/cond
    branches, custom-call subcomputations), whatever the container."""
    if hasattr(value, "jaxpr"):  # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):  # Jaxpr
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _iter_subjaxprs(item)


def _host_primitives(jaxpr, hits: set[str]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _HOST_PRIMS:
            hits.add(name)
        for param in eqn.params.values():
            for sub in _iter_subjaxprs(param):
                _host_primitives(sub, hits)


def _check_program(jax, spec, program, path: str, line: int):
    """JGL102/104/105 over one lowered program; returns (findings,
    fingerprint fragment, lowered)."""
    findings: list[Finding] = []
    lowered = program.fn.lower(*program.args)
    flags = _donated_leaves(jax, lowered)
    spans = _leaf_spans(jax, program.args)

    # JGL102 — donation proven from the lowering, both directions.
    for pos in program.state_positions:
        start, stop = spans[pos]
        missing = [i for i in range(start, stop) if not flags[i]]
        if missing:
            findings.append(
                Finding(
                    path,
                    line,
                    "JGL102",
                    f"{spec.family}: rolling-state argument {pos} of the "
                    f"{program.label} program has undonated leaves "
                    f"{missing} in the lowered computation — every tick "
                    "allocates a fresh state copy instead of reusing "
                    "the buffers; donate the state (args[0] of the "
                    "publish offer) in the program's donate_argnums",
                )
            )
    for pos in program.staged_positions:
        start, stop = spans[pos]
        donated = [i for i in range(start, stop) if flags[i]]
        if donated:
            findings.append(
                Finding(
                    path,
                    line,
                    "JGL102",
                    f"{spec.family}: staged-wire argument {pos} of the "
                    f"{program.label} program is DONATED (leaves "
                    f"{donated}) — the staged window is shared with "
                    "other consumers (fallback paths, parity checks) "
                    "and must never be consumed by one member",
                )
            )

    # JGL104 — host callbacks anywhere in the traced body.
    hits: set[str] = set()
    closed = jax.make_jaxpr(program.fn)(*program.args)
    _host_primitives(closed.jaxpr, hits)
    if hits:
        findings.append(
            Finding(
                path,
                line,
                "JGL104",
                f"{spec.family}: host callback primitive(s) "
                f"{sorted(hits)} inside the traced {program.label} "
                "program — each one is a per-tick host round trip; "
                "move the host work off the tick (publish "
                "channel, telemetry thread)",
            )
        )

    fingerprint = {
        "n_args": len(program.args),
        "donated": [i for i, d in enumerate(flags) if d],
        "outputs": {
            name: {
                "shape": [int(d) for d in aval.shape],
                "dtype": str(aval.dtype),
            }
            for name, aval in sorted(program.outputs.items())
        },
    }
    return findings, fingerprint, lowered


def _check_schema(spec, program, path: str, line: int, encodable):
    """JGL105 — declared wire schema vs traced output avals."""
    findings: list[Finding] = []
    declared = dict(spec.wire_schema)
    actual = {
        name: (len(aval.shape), str(aval.dtype))
        for name, aval in program.outputs.items()
    }
    for name in sorted(set(declared) - set(actual)):
        findings.append(
            Finding(
                path,
                line,
                "JGL105",
                f"{spec.family}: declared wire output {name!r} "
                f"{declared[name]!r} is not produced by the publish "
                "program — downstream consumers of the delta stream "
                "lose the channel; emit it or drop the schema entry",
            )
        )
    for name in sorted(set(actual) - set(declared)):
        findings.append(
            Finding(
                path,
                line,
                "JGL105",
                f"{spec.family}: publish output {name!r} "
                f"{actual[name]!r} is missing from TICK_WIRE_SCHEMA — "
                "an undeclared channel reaches the wire unreviewed; "
                "pin it in the family's schema",
            )
        )
    for name in sorted(set(actual) & set(declared)):
        if actual[name] != tuple(declared[name]):
            findings.append(
                Finding(
                    path,
                    line,
                    "JGL105",
                    f"{spec.family}: output {name!r} traced as "
                    f"{actual[name]!r} but the wire schema pins "
                    f"{tuple(declared[name])!r} — a silent (ndim, "
                    "dtype) drift breaks the delta codec's keyframe "
                    "contract; fix the program or update the schema "
                    "deliberately",
                )
            )
    for name, aval in sorted(program.outputs.items()):
        if not encodable(aval.dtype):
            findings.append(
                Finding(
                    path,
                    line,
                    "JGL105",
                    f"{spec.family}: output {name!r} dtype "
                    f"{aval.dtype!s} has no da00 wire dtype "
                    "(schemas/da00_dataarray.fbs) — the serializer "
                    "cannot encode it; cast to a wire dtype in the "
                    "publish program",
                )
            )
    return findings


def check_spec(jax, spec, encodable) -> tuple[list["Finding"], dict | None]:
    """All JGL101–JGL105 checks for one registered family."""
    findings: list[Finding] = []
    path, line = spec.source_location()
    base = spec.build("base")

    # JGL101 — one executable per tick.
    if len(base.programs) != 1:
        findings.append(
            Finding(
                path,
                line,
                "JGL101",
                f"{spec.family}: tick comprises {len(base.programs)} "
                "executables "
                f"({[p.label for p in base.programs]}) — every extra "
                "program is one more dispatch per tick; fuse "
                "into the one tick program (ADR 0114)",
            )
        )

    fingerprint: dict = {"executables": len(base.programs)}
    lowered_by_label: dict[str, str] = {}
    for program in base.programs:
        prog_findings, frag, lowered = _check_program(
            jax, spec, program, path, line
        )
        findings.extend(prog_findings)
        findings.extend(_check_schema(spec, program, path, line, encodable))
        if len(base.programs) == 1:
            fingerprint.update(frag)
        lowered_by_label[program.label] = lowered.as_text()

    # JGL103 — swap-stability, proven by re-lowering the swapped epoch.
    fingerprint["swap_stable"] = None
    if spec.swap_variant is not None:
        swap = spec.build("swap")
        stable = swap.key_material == base.key_material and len(
            swap.programs
        ) == len(base.programs)
        if stable:
            for program in swap.programs:
                text = program.fn.lower(*program.args).as_text()
                if text != lowered_by_label.get(program.label):
                    stable = False
                    break
        fingerprint["swap_stable"] = bool(stable)
        if not stable:
            findings.append(
                Finding(
                    path,
                    line,
                    "JGL103",
                    f"{spec.family}: swapped table "
                    f"({spec.swap_variant}) re-lowers to a DIFFERENT "
                    "program — the table is baked into the trace "
                    "instead of riding as an argument/staged wire, so "
                    "every live swap recompiles on the hot path; keep "
                    "table content out of the closure (ADR 0122)",
                )
            )
    return findings, fingerprint


def run_trace(
    *,
    specs=None,
    select: frozenset[str] | None = None,
    baseline: dict[str, dict] | None = None,
    cache_path: str | None = None,
) -> TraceReport:
    """Run the trace pass; never raises for environment gaps — a
    missing jax (or registry) sets ``skipped`` so callers surface a
    visible notice instead of a silent green.

    ``cache_path`` enables the lowering cache: when the source digest
    matches, the raw results replay from disk with no jax import.
    Baseline drift and ``select`` apply AFTER either path, so a cache
    hit behaves identically to a fresh run. Explicit ``specs`` bypass
    the cache (the digest only describes the on-disk tree)."""
    report = TraceReport()

    digest: str | None = None
    if cache_path is not None and specs is None:
        from ..lowering_cache import load_cache, source_digest

        digest = source_digest()
        cached = load_cache(cache_path, digest)
        if cached is not None:
            report.findings = [
                Finding(
                    entry["path"],
                    int(entry["line"]),
                    entry["rule"],
                    entry["message"],
                )
                for entry in cached["findings"]
            ]
            report.errors = list(cached["errors"])
            report.fingerprints = dict(cached["fingerprints"])
            report.cache_hit = True
            return _post_process(report, select, baseline)

    try:
        jax = _import_jax()
    except ImportError as exc:
        report.skipped = f"jax unavailable ({exc})"
        return report
    try:
        explicit = specs is not None
        if specs is None:
            specs = _load_specs()
    except Exception as exc:
        report.skipped = f"program registry unavailable ({exc})"
        return report
    try:
        from esslivedata_tpu.kafka.wire import da00_encodable as encodable
    except Exception:  # registry loaded but wire module gated out
        def encodable(_dtype) -> bool:
            return True

    for spec in specs:
        try:
            findings, fingerprint = check_spec(jax, spec, encodable)
        except Exception as exc:
            path, line = spec.source_location()
            report.errors.append(
                f"{path}: trace build failed for family "
                f"{spec.family!r}: {exc!r}"
            )
            continue
        report.findings.extend(findings)
        if fingerprint is not None:
            report.fingerprints[spec.family] = fingerprint

    if (
        cache_path is not None
        and not explicit
        and digest is not None
        and not report.errors
    ):
        # Only clean, complete sweeps are worth replaying: an errored
        # run must re-lower next time so the error stays visible.
        from ..lowering_cache import store_cache

        store_cache(
            cache_path,
            digest,
            findings=report.findings,
            errors=report.errors,
            fingerprints=report.fingerprints,
        )

    return _post_process(report, select, baseline)


def _post_process(
    report: TraceReport,
    select: frozenset[str] | None,
    baseline: dict[str, dict] | None,
) -> TraceReport:
    """The shared tail of fresh and cached runs: baseline drift, then
    the select filter, then deterministic ordering."""
    if baseline is not None:
        report.findings.extend(
            _baseline_drift(report.fingerprints, baseline)
        )
    if select is not None:
        report.findings = [
            f for f in report.findings if f.rule in select
        ]
    report.findings.sort()
    return report


def _baseline_drift(
    fingerprints: dict[str, dict], baseline: dict[str, dict]
) -> list["Finding"]:
    """JGL100 — fingerprints vs the committed pins. Drift in either
    direction fires: a changed contract AND a family that vanished
    from (or never entered) the baseline both need a reviewed diff."""
    out: list[Finding] = []
    for family in sorted(set(fingerprints) | set(baseline)):
        if family not in baseline:
            out.append(
                Finding(
                    _BASELINE_PATH,
                    1,
                    "JGL100",
                    f"{family}: no pinned contract fingerprint — "
                    "regenerate with --trace-write-baseline and commit "
                    "the reviewed diff",
                )
            )
            continue
        if family not in fingerprints:
            out.append(
                Finding(
                    _BASELINE_PATH,
                    1,
                    "JGL100",
                    f"{family}: pinned in the baseline but no longer "
                    "registered — prune the entry (or restore the "
                    "family's registration)",
                )
            )
            continue
        drift = diff_fingerprint(
            family, fingerprints[family], baseline[family]
        )
        if drift:
            out.append(
                Finding(
                    _BASELINE_PATH,
                    1,
                    "JGL100",
                    f"{family}: contract drifted from the pinned "
                    f"fingerprint: {'; '.join(drift)} — review the "
                    "change and regenerate with --trace-write-baseline",
                )
            )
    return out
