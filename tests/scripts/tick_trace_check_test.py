"""``scripts/tick_trace_check.py`` on the CPU: the reader of a profiler
trace beside a span dump. A tiny trace made here stands for the chip's;
the device plane's metadata is a few bytes of protobuf wire written by
hand, since the CPU backend has no such plane."""

import importlib.util
import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def check():
    spec = importlib.util.spec_from_file_location(
        "tick_trace_check_under_test", REPO / "scripts" / "tick_trace_check.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def varint(value: int) -> bytes:
    out = bytearray()
    while True:
        value, low = value >> 7, value & 0x7F
        out.append(low | (0x80 if value else 0))
        if not value:
            return bytes(out)


def field(number: int, value) -> bytes:
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def map_entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


def plane(name: str, op: str, stat: bytes) -> bytes:
    """An XPlane with one stat name (id 1 = ``tf_op``), one interned
    string (id 2) and one event metadata that carries ``stat``."""
    return (
        field(2, name)
        + field(5, map_entry(1, field(1, 1) + field(2, "tf_op")))
        + field(5, map_entry(2, field(1, 2) + field(2, "jit(tick_x)/pack/concatenate:")))
        + field(4, map_entry(7, field(1, 7) + field(2, op) + field(5, stat)))
    )


OP = "%fusion.3 = f32[163840001]{0:T(1024)} fusion(f32[8]{0} %p0), kind=kLoop"


class TestOpMetadata:
    def test_a_string_stat_gives_the_scope(self, check, tmp_path):
        stat = field(1, 1) + field(5, "jit(tick_detector_view)/scatter/add:")
        space = field(1, plane("/device:TPU:0", OP, stat)) + field(
            1, plane("/host:CPU", "%other = f32[] add()", stat)
        )
        path = tmp_path / "t.xplane.pb"
        path.write_bytes(space)
        found = check.op_metadata(path, check.DEVICE_PLANE)
        assert found == {OP: {"tf_op": "jit(tick_detector_view)/scatter/add:"}}
        assert check.scope_of(found[OP].values()) == (
            "scatter", "tick_detector_view"
        )

    def test_a_ref_stat_is_looked_up_among_the_plane_s_strings(
        self, check, tmp_path
    ):
        stat = field(1, 1) + field(7, 2)
        path = tmp_path / "t.xplane.pb"
        path.write_bytes(field(1, plane("/device:TPU:0", OP, stat)))
        (stats,) = check.op_metadata(path, check.DEVICE_PLANE).values()
        assert check.scope_of(stats.values()) == ("pack", "tick_x")


@pytest.mark.parametrize(
    ("text", "scope", "program"),
    [
        ("jit(tick_detector_view)/publish_reduce/fold/add:",
         "publish_reduce/fold", "tick_detector_view"),
        ("jit(tick_detector_view)/jit(_where)/select_n:",
         "(no scope)", "tick_detector_view"),
        ("loop fusion", "(no metadata)", "?"),
    ],
)
def test_scope_of(check, text, scope, program):
    assert check.scope_of([text]) == (scope, program)


def test_short_op_drops_layouts_and_operands(check):
    assert check.short_op(OP) == "%fusion.3 = f32[163840001] fusion"
    sort = (
        "%sort = (s32[4194304]{0:T(1024)S(1)}, f32[4194304]{0}) "
        "sort(s32[4194304]{0} %a, f32[4194304]{0} %b), dimensions={0}"
    )
    assert check.short_op(sort) == "%sort = (s32[4194304], f32[4194304]) sort"
    assert check.short_op("no instruction") == "no instruction"


def test_clock_check_pairs_in_order_and_counts_what_it_cannot_pair(check):
    ring = {("decode", 1): [1000.0], ("fetch", 1): [5000.0, 3000.0],
            ("sink", 2): [9000.0]}
    twins = {("decode", 1): [520], ("fetch", 1): [2500, 4490]}
    got = check.clock_check(ring, twins, start_ns=1_000_500, offset_ns=1_000_000)
    # decode: 1000 + 1e6 - (1000500 + 520) = -20; fetch: 0 and +10
    assert got["paired"] == 3 and got["unpaired_ring_spans"] == 1
    assert got["ticks"] == 2
    assert got["largest_abs_diff_us"] == pytest.approx(0.020)
    assert got["mean_diff_us"] == pytest.approx(-0.010 / 3)
    assert got["largest_abs_diff_us_by_span"] == {
        "decode": pytest.approx(0.020), "fetch": pytest.approx(0.010)
    }


def test_a_cpu_trace_beside_its_dump(check, tmp_path):
    """Spans recorded under a profiler session, dumped, and read back:
    every ring span finds its twin, on one clock."""
    import jax
    import jax.numpy as jnp

    from esslivedata_tpu.telemetry.trace import TickTracer

    tracer = TickTracer(enabled=True, slow_tick_s=10.0)
    step = jax.jit(lambda x: x + 1)
    step(jnp.zeros(8)).block_until_ready()
    profile = tmp_path / "profile"
    jax.profiler.start_trace(str(profile))
    try:
        for hold in (53_000, 106_000, 53_000):
            tick = tracer.new_trace()
            with tracer.bind(tick):
                with tracer.span("decode", args={"hold_us": hold}):
                    pass
                with tracer.span("tick_execute"):
                    out = step(jnp.zeros(8))
                with tracer.span("fetch"):
                    out.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    dump = tmp_path / "ticks.json"
    tracer.dump(str(dump))
    report = check.analyse(profile, dump)
    assert report["dump_clock"] == "perf_counter"
    assert report["ring_spans"] == 9
    assert report["hold_us_by_tick"] == [53_000, 106_000, 53_000]
    if not report.get("twins_by_name"):
        pytest.skip("the CPU backend's trace has no host plane")
    assert report["twins_by_name"] == {
        "decode": 3, "fetch": 3, "tick_execute": 3
    }
    clock = report["clock_check"]
    assert (clock["paired"], clock["unpaired_ring_spans"]) == (9, 0)
    assert clock["ticks"] == 3
    assert clock["largest_abs_diff_us"] < 5_000
    assert abs(report["this_process_offset_minus_dump_us"]) < 5_000
    # No TPU plane on the CPU: no device numbers, and no error.
    assert "device_s_by_scope" not in report and "error" not in report
    # The same trace without a dump: the twins alone.
    alone = check.analyse(profile)
    assert alone["twins_by_name"] == report["twins_by_name"]
    assert "clock_check" not in alone


def test_the_pool_phase_by_thread_from_a_hand_made_dump(check, tmp_path, capsys):
    """Two ticks of three jobs on two job threads (times in us): which
    thread staged what, when each started and ended, which finished
    last, and the phase's wall time beside the loop thread's wait."""

    def span(name, tick, tid, ts, dur):
        return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": tick, "tid": tid,
                "args": {"trace_id": tick}}

    events = [
        span("decode", 1, "loop", 0, 1_000),
        span("h2d", 1, "job_0", 1_100, 40_000), span("q_step", 1, "job_0", 41_100, 1_000),
        span("h2d", 1, "job_1", 1_200, 30_000), span("q_step", 1, "job_1", 31_200, 900),
        span("h2d", 1, "job_1", 32_200, 35_000), span("q_step", 1, "job_1", 67_200, 800),
        span("fetch", 1, "loop", 68_100, 20_000),
        span("decode", 2, "loop", 1_000_000, 1_000),
        span("h2d", 2, "loop", 1_001_000, 5_000),  # the loop thread's own staging is not the pool's
        span("h2d", 2, "job_0", 1_006_100, 50_000), span("q_step", 2, "job_0", 1_056_100, 1_000),
        span("h2d", 2, "job_1", 1_006_300, 20_000), span("q_step", 2, "job_1", 1_026_300, 700),
    ]
    pool = check.pool_phase(events, {1: 67_500_000})
    assert (pool["ticks"], pool["finished_last"]) == (2, {"job_0": 1, "job_1": 1})
    first, second = pool["by_tick"]
    assert (first["trace_id"], first["finished_last"]) == (1, "job_1")
    assert first["wall_ms"] == pytest.approx(66.9) and first["accumulate_wait_ms"] == 67.5
    assert first["threads"] == {
        "job_0": {"h2d_ms": 40.0, "q_step_ms": 1.0, "first_start_ms": 0.0, "last_end_ms": pytest.approx(41.0)},
        "job_1": {"h2d_ms": 65.0, "q_step_ms": pytest.approx(1.7), "first_start_ms": pytest.approx(0.1),
                  "last_end_ms": pytest.approx(66.9)},
    }
    assert (second["finished_last"], second["wall_ms"]) == ("job_0", pytest.approx(51.0))
    assert set(second["threads"]) == {"job_0", "job_1"} and "accumulate_wait_ms" not in second
    assert pool["wall_ms_median"] == pytest.approx((66.9 + 51.0) / 2)
    assert pool["accumulate_wait_ms_median"] == 67.5
    assert check.pool_phase([e for e in events if e["tid"] == "loop"]) is None  # a service without a pool
    # from the command line, a dump beside a directory without a trace still gives the table
    dump = tmp_path / "ticks.json"
    dump.write_text(json.dumps({"clock": "perf_counter", "epoch_minus_clock_ns": 0, "traceEvents": events}))
    assert check.main([str(tmp_path), str(dump)]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["error"] == "no xplane" and report["pool_phase"]["ticks"] == 2
    assert "accumulate_wait_ms_median" not in report["pool_phase"]


def test_the_command_line(check, tmp_path, capsys):
    assert check.main([]) == 2
    assert check.main(["--workload", "x"]) == 2
    assert check.main([str(tmp_path)]) == 1
    assert json.loads(capsys.readouterr().out)["error"] == "no xplane"
