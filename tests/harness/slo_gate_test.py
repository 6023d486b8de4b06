"""SLO checker semantics (scripts/slo_gate.py, ADR 0120): rule
evaluation (quantiles, aggregates, allow_missing, absent-family
breach), scrape-delta algebra, and the load harness + gate round trip
with the containment-disabled control going red."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from esslivedata_tpu.telemetry.exposition import parse_prometheus_text

REPO = Path(__file__).resolve().parent.parent.parent


@pytest.fixture(scope="module")
def slo_gate():
    spec = importlib.util.spec_from_file_location(
        "slo_gate_under_test", REPO / "scripts" / "slo_gate.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


HIST = """\
# HELP lat_seconds latency
# TYPE lat_seconds histogram
lat_seconds_bucket{stage="deliver",le="0.1"} 90
lat_seconds_bucket{stage="deliver",le="0.5"} 99
lat_seconds_bucket{stage="deliver",le="+Inf"} 100
lat_seconds_sum{stage="deliver"} 12.5
lat_seconds_count{stage="deliver"} 100
"""

COUNTERS = """\
# HELP errors errs
# TYPE errors counter
errors_total{kind="a"} 3
errors_total{kind="b"} 5
# HELP quiet quiet counter
# TYPE quiet counter
# HELP depth depth
# TYPE depth gauge
depth{sub="1"} 4
depth{sub="2"} 9
"""


class TestEvaluation:
    def test_histogram_quantile_interpolates(self, slo_gate):
        fam = parse_prometheus_text(HIST)["lat_seconds"]
        p50 = slo_gate.histogram_quantile(fam, 0.5, {"stage": "deliver"})
        # 50th of 100 falls in the first bucket: 50/90 of [0, 0.1].
        assert p50 == pytest.approx(0.1 * 50 / 90)
        p99 = slo_gate.histogram_quantile(fam, 0.99, {"stage": "deliver"})
        assert 0.1 < p99 <= 0.5
        # The tail sample lands in +Inf: p100 reads as infinity.
        assert slo_gate.histogram_quantile(
            fam, 1.0, {"stage": "deliver"}
        ) == float("inf")

    def test_quantile_rule_breaches_on_budget(self, slo_gate):
        fams = parse_prometheus_text(HIST)
        rule = {
            "metric": "lat_seconds",
            "labels": {"stage": "deliver"},
            "agg": "p99",
            "op": "<=",
            "value": 0.05,
        }
        passed, observed, _ = slo_gate.evaluate_rule(rule, fams)
        assert not passed and observed > 0.05
        rule["value"] = 1.0
        assert slo_gate.evaluate_rule(rule, fams)[0]

    def test_sum_max_and_label_filter(self, slo_gate):
        fams = parse_prometheus_text(COUNTERS)
        assert slo_gate.evaluate_rule(
            {"metric": "errors", "agg": "sum", "op": "==", "value": 8},
            fams,
        )[0]
        assert slo_gate.evaluate_rule(
            {
                "metric": "errors",
                "labels": {"kind": "a"},
                "agg": "sum",
                "op": "==",
                "value": 3,
            },
            fams,
        )[0]
        assert slo_gate.evaluate_rule(
            {"metric": "depth", "agg": "max", "op": "<=", "value": 9},
            fams,
        )[0]

    def test_exposed_but_empty_counter_reads_zero(self, slo_gate):
        """A family with a HELP/TYPE header and no series is an
        instrument that never fired — 0, not a breach."""
        fams = parse_prometheus_text(COUNTERS)
        passed, observed, _ = slo_gate.evaluate_rule(
            {"metric": "quiet", "agg": "sum", "op": "==", "value": 0},
            fams,
        )
        assert passed and observed == 0.0

    def test_absent_family_breaches_unless_allowed(self, slo_gate):
        fams = parse_prometheus_text(COUNTERS)
        rule = {"metric": "nope", "agg": "sum", "op": "==", "value": 0}
        passed, observed, detail = slo_gate.evaluate_rule(rule, fams)
        assert not passed and observed is None and "absent" in detail
        rule["allow_missing"] = True
        assert slo_gate.evaluate_rule(rule, fams)[0]

    @pytest.mark.parametrize(("scale", "green"), [(1, True), (2, False)])
    def test_default_rules_hold_the_batcher_to_its_base_window(
        self, slo_gate, scale, green
    ):
        """``livedata_batcher_window_scale`` above 1 is a service past
        the rate it sustains: the shipped default gate goes red on it,
        and passes a scrape of a batcher that has no governor."""
        import json

        rules = json.loads(
            (REPO / "scripts" / "slo_rules" / "default.json").read_text()
        )["rules"]
        (rule,) = [r for r in rules if r["name"] == "batcher_at_base_window"]
        fams = parse_prometheus_text(
            "# HELP livedata_batcher_window_scale scale\n"
            "# TYPE livedata_batcher_window_scale gauge\n"
            f'livedata_batcher_window_scale{{service="detector_data"}} {scale}\n'
        )
        assert slo_gate.evaluate_rule(rule, fams)[0] is green
        assert slo_gate.evaluate_rule(rule, parse_prometheus_text(COUNTERS))[0]

    @pytest.mark.parametrize(
        ("seconds", "green"), [((13.7, 41.5), True), ((80.0, 60.5), False)]
    )
    def test_default_rules_bound_the_table_builds(self, slo_gate, seconds, green):
        """``livedata_table_build_seconds_total`` is the only reader of
        what a Q job's tables cost at job start (set-up has no span):
        the shipped gate sums the families and goes red past 120 s; a
        service that hosts no Q job has no such family and passes."""
        import json

        rules = json.loads(
            (REPO / "scripts" / "slo_rules" / "default.json").read_text()
        )["rules"]
        (rule,) = [r for r in rules if r["name"] == "table_build_bounded"]
        assert rule["metric"] == "livedata_table_build_seconds_total"
        fams = parse_prometheus_text(
            "# HELP livedata_table_build_seconds_total build\n"
            "# TYPE livedata_table_build_seconds_total counter\n"
            f'livedata_table_build_seconds_total{{family="sans_iq"}} {seconds[0]}\n'
            f'livedata_table_build_seconds_total{{family="qe"}} {seconds[1]}\n'
        )
        assert slo_gate.evaluate_rule(rule, fams)[0] is green
        assert slo_gate.evaluate_rule(rule, parse_prometheus_text(COUNTERS))[0]
        # A start-up gate (the rule's comment): the counter also grows
        # at every swap_table and restart, so a long-lived service is
        # gated over the increase since a baseline; bare, it reads red.
        assert "--baseline" in rule["comment"]

        def lifetime(sans_iq, qe):
            return parse_prometheus_text(
                "# TYPE livedata_table_build_seconds_total counter\n"
                f'livedata_table_build_seconds_total{{family="sans_iq"}} {sans_iq}\n'
                f'livedata_table_build_seconds_total{{family="qe"}} {qe}\n'
            )

        before = lifetime(400.0, 90.0)
        after = lifetime(400.0 + seconds[0], 90.0 + seconds[1])
        assert not slo_gate.evaluate_rule(rule, after)[0]
        passed, observed, _ = slo_gate.evaluate_rule(rule, slo_gate.subtract(after, before))
        assert passed is green and observed == pytest.approx(sum(seconds))

    def test_subtract_deltas_counters_keeps_gauges(self, slo_gate):
        before = parse_prometheus_text(COUNTERS)
        after_text = COUNTERS.replace(
            'errors_total{kind="a"} 3', 'errors_total{kind="a"} 10'
        ).replace('depth{sub="1"} 4', 'depth{sub="1"} 2')
        delta = slo_gate.subtract(parse_prometheus_text(after_text), before)
        errors = {
            labels["kind"]: value
            for _n, labels, value in delta["errors"].samples
        }
        assert errors == {"a": 7.0, "b": 0.0}
        depth = {
            labels["sub"]: value
            for _n, labels, value in delta["depth"].samples
        }
        assert depth["1"] == 2.0  # gauge: level, not rate


def _tiny_config(**overrides):
    from esslivedata_tpu.harness import LoadConfig

    cfg = LoadConfig(
        streams=2,
        jobs_per_stream=1,
        subscribers=12,
        windows=10,
        warm_windows=2,
        events_per_window=256,
        pixels=1 << 10,
        queue_limit=4,
        wedge_every=5,
    )
    for key, value in overrides.items():
        setattr(cfg, key, value)
    return cfg


class TestHarnessRoundTrip:
    def test_clean_run_is_green(self, slo_gate):
        from esslivedata_tpu.harness import LoadHarness

        report = LoadHarness(_tiny_config()).run()
        assert report["parity_checks"] > 0
        assert report["parity_violations"] == 0
        assert report["gap_violations"] == 0
        assert report["coalesce_drops"] > 0  # wedged subs overflowed
        assert report["coalesce_recoveries"] > 0
        assert report["peak_queue_depth"] <= report["queue_limit"]

    def test_chaos_contained_and_control_goes_red(self, slo_gate):
        """One round trip at test scale: injected state loss is
        signaled (gate green on the invariants), and the SAME drill
        with the epoch signal disabled produces unsignaled resets the
        gate catches (exit-path semantics of scripts/slo_gate.py)."""
        from esslivedata_tpu.harness import ChaosSpec, LoadHarness

        chaos = ChaosSpec(
            seed=11, at={"tick_dispatch": frozenset({1, 7})}
        )
        report = LoadHarness(
            _tiny_config(chaos=chaos)
        ).run()
        assert report["chaos_injected"].get("tick_dispatch", 0) >= 1
        assert report["gap_violations"] == 0
        assert report["parity_violations"] == 0
        assert report["steady_compiles"] == 0
        assert report["healthz"]["status"] == "degraded"

        control = LoadHarness(
            _tiny_config(
                chaos=chaos, disable_containment="state_lost_signal"
            )
        ).run()
        assert control["gap_violations"] > 0
        # And the rule file translates that into a red gate.
        rules = slo_gate._load_rules(
            REPO / "scripts" / "slo_rules" / "smoke.json"
        )
        delta = slo_gate.subtract(
            parse_prometheus_text(control["scrape_after"]),
            parse_prometheus_text(control["scrape_before"]),
        )
        ok, results = slo_gate.evaluate(rules, delta)
        assert not ok
        breached = {r["name"] for r in results if not r["passed"]}
        assert "unsignaled_resets_zero" in breached
