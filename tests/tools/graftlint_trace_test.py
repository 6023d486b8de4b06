"""Trace-pass fixture suite (JGL100–JGL105, ADR 0123): one seeded
contract violation per rule, fed to ``run_trace`` as a synthetic
``TickProgramSpec``, plus the tier-1 guard that lowers the REAL
program registry and keeps the shipped tree contract-clean.

The seeded specs are the rules' contract the same way the AST
snippets in ``graftlint_test.py`` are: each builds a tiny jitted
program that violates exactly one clause (a second dispatch, an
undonated state leaf, a baked table, a host callback, a schema
drift), and the test pins which JGL1xx code must fire.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from esslivedata_tpu.harness.tick_contract import (
    TickProgram,
    TickProgramBuild,
    TickProgramSpec,
)
from tools.graftlint.trace import run_trace
from tools.graftlint.trace.contract_baseline import (
    load_contract_baseline,
    write_contract_baseline,
)

# -- seeded-spec scaffolding -----------------------------------------------


def _args():
    """(rolling state, staged wire) — the minimal tick shape."""
    return (
        jnp.zeros(8, jnp.float32),
        jnp.ones(8, jnp.float32),
    )


def _program(fn, *, label="tick", outputs=None, args=None):
    args = _args() if args is None else args
    if outputs is None:
        outputs = {"counts": jax.eval_shape(fn, *args)}
    return TickProgram(
        label=label,
        fn=fn,
        args=args,
        state_positions=(0,),
        staged_positions=(1,),
        outputs=outputs,
    )


def _spec(build, *, family="fixture", schema=None, swap=None):
    return TickProgramSpec(
        family=family,
        build=build,
        wire_schema=schema if schema is not None else {"counts": (1, "float32")},
        # An unresolvable anchor falls back to the registry file — the
        # fixtures only care about rule codes, not anchoring.
        anchor="nonexistent.module:Nope",
        swap_variant=swap,
    )


def _rules(report):
    return sorted({f.rule for f in report.findings})


def _good_build(variant):
    fn = jax.jit(lambda state, staged: state + staged, donate_argnums=(0,))
    return TickProgramBuild(
        programs=(_program(fn),),
        key_material=("staged-sig", ("member-sig",)),
    )


# -- the clean fixture is clean --------------------------------------------


def test_seeded_clean_spec_has_no_findings():
    report = run_trace(specs=[_spec(_good_build)])
    assert report.skipped is None
    assert report.errors == []
    assert report.findings == []
    fp = report.fingerprints["fixture"]
    assert fp["executables"] == 1
    assert fp["donated"] == [0]  # the state leaf, nothing else
    assert fp["outputs"]["counts"] == {"shape": [8], "dtype": "float32"}


# -- JGL101: second dispatch ------------------------------------------------


def test_jgl101_second_executable_fires():
    def build(variant):
        hist = jax.jit(lambda s, w: s + w, donate_argnums=(0,))
        roi = jax.jit(lambda s, w: s * w, donate_argnums=(0,))
        return TickProgramBuild(
            programs=(
                _program(hist, label="hist"),
                _program(roi, label="roi"),
            ),
            key_material=("sig",),
        )

    report = run_trace(specs=[_spec(build)])
    assert "JGL101" in _rules(report)
    [f] = [f for f in report.findings if f.rule == "JGL101"]
    assert "2 executables" in f.message


# -- JGL102: donation gaps, both directions --------------------------------


def test_jgl102_undonated_state_fires():
    def build(variant):
        fn = jax.jit(lambda state, staged: state + staged)  # no donation
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    report = run_trace(specs=[_spec(build)])
    assert _rules(report) == ["JGL102"]
    [f] = report.findings
    assert "undonated" in f.message


def test_jgl102_donated_staged_wire_fires():
    def build(variant):
        # Donating the SHARED staged wire is the opposite hazard.
        fn = jax.jit(lambda state, staged: state + staged, donate_argnums=(0, 1))
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    report = run_trace(specs=[_spec(build)])
    assert _rules(report) == ["JGL102"]
    [f] = report.findings
    assert "DONATED" in f.message


# -- JGL103: baked table vs table-as-argument ------------------------------


def test_jgl103_baked_table_fires():
    def build(variant):
        # The anti-pattern: table CONTENT closed over, so the swap
        # epoch lowers to a different constant — a recompile per swap.
        table = np.full(8, 1.25 if variant == "swap" else 1.0, np.float32)
        fn = jax.jit(
            lambda state, staged: state + staged * table, donate_argnums=(0,)
        )
        # Identical key material: the staging keys would NOT move, so
        # the recompile would also be invisible to the cache metrics.
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    report = run_trace(specs=[_spec(build, swap="calibration")])
    assert _rules(report) == ["JGL103"]
    assert report.fingerprints["fixture"]["swap_stable"] is False


def test_jgl103_table_as_argument_is_stable():
    def build(variant):
        # The sanctioned shape: the table rides as an argument, so both
        # epochs lower byte-identically (only the VALUE differs).
        table = jnp.full(8, 1.25 if variant == "swap" else 1.0, jnp.float32)
        fn = jax.jit(
            lambda state, staged, tab: state + staged * tab,
            donate_argnums=(0,),
        )
        args = (*_args(), table)
        prog = TickProgram(
            label="tick",
            fn=fn,
            args=args,
            state_positions=(0,),
            staged_positions=(1,),
            outputs={"counts": jax.eval_shape(fn, *args)},
        )
        return TickProgramBuild(programs=(prog,), key_material=("s",))

    report = run_trace(specs=[_spec(build, swap="calibration")])
    assert report.findings == []
    assert report.fingerprints["fixture"]["swap_stable"] is True


# -- JGL104: host callback in the traced body ------------------------------


def test_jgl104_debug_callback_fires():
    def build(variant):
        def step(state, staged):
            jax.debug.print("tick {}", state[0])
            return state + staged

        fn = jax.jit(step, donate_argnums=(0,))
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    report = run_trace(specs=[_spec(build)])
    assert _rules(report) == ["JGL104"]
    [f] = report.findings
    # jax 0.9 traces jax.debug.print to the primitive ``debug_print``.
    assert "debug_print" in f.message


def test_jgl104_pure_callback_fires():
    def build(variant):
        def step(state, staged):
            extra = jax.pure_callback(
                lambda x: np.asarray(x),
                jax.ShapeDtypeStruct((8,), jnp.float32),
                staged,
            )
            return state + extra

        fn = jax.jit(step, donate_argnums=(0,))
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    report = run_trace(specs=[_spec(build)])
    assert _rules(report) == ["JGL104"]
    [f] = report.findings
    assert "pure_callback" in f.message


# -- JGL105: wire-schema drift ---------------------------------------------


def test_jgl105_dtype_drift_fires():
    def build(variant):
        fn = jax.jit(
            lambda state, staged: (state + staged).astype(jnp.int32),
            donate_argnums=(0,),
        )
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    # Schema pins float32; the program now produces int32.
    report = run_trace(specs=[_spec(build, schema={"counts": (1, "float32")})])
    assert _rules(report) == ["JGL105"]
    [f] = report.findings
    assert "int32" in f.message and "float32" in f.message


def test_jgl105_both_membership_directions_fire():
    report = run_trace(
        specs=[
            _spec(
                _good_build,
                schema={"image": (2, "float32")},  # declared, not produced
            )
        ]
    )
    messages = [f.message for f in report.findings]
    assert all(f.rule == "JGL105" for f in report.findings)
    assert any("'image'" in m and "not produced" in m for m in messages)
    assert any("'counts'" in m and "missing from" in m for m in messages)


def test_jgl105_non_da00_dtype_fires():
    def build(variant):
        fn = jax.jit(
            lambda state, staged: (state + staged).astype(jnp.complex64),
            donate_argnums=(0,),
        )
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    # Schema agrees on complex64, so the only failure left is that the
    # da00 enum (schemas/da00_dataarray.fbs) cannot carry it.
    report = run_trace(specs=[_spec(build, schema={"counts": (1, "complex64")})])
    assert _rules(report) == ["JGL105"]
    [f] = report.findings
    assert "da00" in f.message


# -- JGL100: baseline drift, all three directions --------------------------


def test_jgl100_baseline_roundtrip_and_drift(tmp_path):
    clean = run_trace(specs=[_spec(_good_build)])
    path = tmp_path / "tickcontract-baseline.json"
    write_contract_baseline(path, clean.fingerprints)
    baseline = load_contract_baseline(path)

    # In sync: no drift findings.
    report = run_trace(specs=[_spec(_good_build)], baseline=baseline)
    assert report.findings == []

    # Changed contract (a dtype drift in the pin) fires and names it.
    drifted = load_contract_baseline(path)
    drifted["fixture"]["outputs"]["counts"]["dtype"] = "float64"
    report = run_trace(specs=[_spec(_good_build)], baseline=drifted)
    assert _rules(report) == ["JGL100"]
    [f] = report.findings
    assert "counts" in f.message and f.path == "tickcontract-baseline.json"


def test_jgl100_unpinned_and_vanished_families_fire():
    baseline = {"ghost": {"executables": 1}}
    report = run_trace(specs=[_spec(_good_build)], baseline=baseline)
    rules = _rules(report)
    assert rules == ["JGL100"]
    messages = sorted(f.message for f in report.findings)
    assert any("no pinned contract" in m for m in messages)  # fixture
    assert any("no longer registered" in m for m in messages)  # ghost


# -- engine plumbing --------------------------------------------------------


def test_select_filters_trace_findings():
    def build(variant):
        fn = jax.jit(lambda state, staged: state + staged)
        return TickProgramBuild(programs=(_program(fn),), key_material=("s",))

    report = run_trace(specs=[_spec(build)], select=frozenset({"JGL104"}))
    assert report.findings == []  # the JGL102 finding is deselected


def test_build_exception_is_an_error_not_a_crash():
    def build(variant):
        raise RuntimeError("geometry unavailable")

    report = run_trace(specs=[_spec(build, family="broken")])
    assert report.findings == []
    assert len(report.errors) == 1
    assert "broken" in report.errors[0]
    assert "geometry unavailable" in report.errors[0]
    assert "broken" not in report.fingerprints


def test_missing_jax_is_a_visible_skip(monkeypatch):
    from tools.graftlint.trace import engine

    def boom():
        raise ImportError("No module named 'jax'")

    monkeypatch.setattr(engine, "_import_jax", boom)
    report = engine.run_trace()
    assert report.skipped is not None
    assert "jax unavailable" in report.skipped
    assert report.findings == [] and report.errors == []


def test_bad_contract_baseline_rejected(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"version": 99, "programs": {}}')
    with pytest.raises(ValueError):
        load_contract_baseline(path)


# -- the tier-1 guard: the shipped tree is contract-clean -------------------


def test_real_registry_is_contract_clean():
    """Every registered family lowers, and the contract holds: this is
    the in-suite twin of ``make lint``'s ``--trace`` gate — a donation
    gap, baked table, host callback or schema drift in the shipped
    workflows fails HERE, device-free, before any runtime counter
    could see it."""
    report = run_trace()
    assert report.skipped is None
    assert report.errors == []
    assert report.findings == []
    # Coverage floor: the six shipped families all fingerprinted.
    assert {
        "detector_view",
        "monitor",
        "q_sans",
        "powder_focus",
        "imaging",
        "correlation",
    } <= set(report.fingerprints)
    for family, fp in report.fingerprints.items():
        assert fp["executables"] == 1, family
        assert fp["donated"], family  # at least the state leaves


def test_real_registry_matches_committed_baseline():
    """The committed pin is exactly in sync — contract drift must ship
    with its reviewed baseline hunk (JGL100's whole point)."""
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent.parent
    baseline = load_contract_baseline(repo / "tickcontract-baseline.json")
    report = run_trace(baseline=baseline)
    assert report.skipped is None
    assert report.errors == []
    assert report.findings == []
