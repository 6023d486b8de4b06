"""File-backed broker unit coverage: framing, watermarks, rotation,
partial-frame tolerance."""

import threading

import pytest

from esslivedata_tpu.kafka.consumer import assign_all_partitions
from esslivedata_tpu.kafka.file_broker import (
    FileBrokerConsumer,
    FileBrokerProducer,
    ensure_topics,
)


@pytest.fixture
def broker(tmp_path):
    ensure_topics(tmp_path, ["alpha", "beta"])
    return tmp_path


def test_round_trip_with_keys(broker):
    prod = FileBrokerProducer(broker)
    prod.produce("alpha", b"v1", key=b"k1")
    prod.produce("alpha", b"v2")
    cons = FileBrokerConsumer(broker)
    assign_all_partitions(cons, ["alpha"])  # at high watermark: sees nothing
    assert cons.consume(10, 0.0) == []
    prod.produce("alpha", b"v3", key="str-key")
    msgs = cons.consume(10, 0.0)
    assert [(m.value(), m.key()) for m in msgs] == [(b"v3", b"str-key")]
    assert msgs[0].topic() == "alpha" and msgs[0].error() is None


def test_assign_from_zero_reads_backlog(broker):
    prod = FileBrokerProducer(broker)
    for i in range(5):
        prod.produce("beta", f"m{i}".encode())
    cons = FileBrokerConsumer(broker)
    cons.assign([type("TP", (), {"topic": "beta", "offset": 0})()])
    assert [m.value() for m in cons.consume(10, 0.0)] == [
        b"m0", b"m1", b"m2", b"m3", b"m4"
    ]


def test_missing_topic_fails_assignment(broker):
    cons = FileBrokerConsumer(broker)
    with pytest.raises(ValueError, match="not found"):
        assign_all_partitions(cons, ["gamma"])


def test_partial_frame_not_surfaced(broker):
    prod = FileBrokerProducer(broker)
    prod.produce("alpha", b"complete")
    # Simulate a writer mid-append: torn frame at the tail.
    with open(broker / "alpha.log", "ab") as f:
        f.write(b"\x05\x00\x00\x00")  # half a header
    cons = FileBrokerConsumer(broker)
    cons.assign([type("TP", (), {"topic": "alpha", "offset": 0})()])
    assert [m.value() for m in cons.consume(10, 0.0)] == [b"complete"]
    # The torn tail stays pending; completing it surfaces the frame.


def test_round_robin_prevents_topic_starvation(broker):
    prod = FileBrokerProducer(broker)
    for _ in range(300):
        prod.produce("alpha", b"bulk")
    prod.produce("beta", b"control")
    cons = FileBrokerConsumer(broker)
    cons.assign(
        [
            type("TP", (), {"topic": "alpha", "offset": 0})(),
            type("TP", (), {"topic": "beta", "offset": 0})(),
        ]
    )
    seen_beta = False
    for _ in range(4):  # alpha alone needs 3 calls at budget 100
        for m in cons.consume(100, 0.0):
            seen_beta = seen_beta or m.topic() == "beta"
        if seen_beta:
            break
    assert seen_beta, "control topic starved behind bulk topic"


def test_concurrent_producers_interleave_at_frame_boundaries(broker):
    def writer(tag):
        prod = FileBrokerProducer(broker)
        for i in range(200):
            prod.produce("alpha", f"{tag}-{i}".encode())

    threads = [threading.Thread(target=writer, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cons = FileBrokerConsumer(broker)
    cons.assign([type("TP", (), {"topic": "alpha", "offset": 0})()])
    seen = []
    while batch := cons.consume(100, 0.0):
        seen.extend(m.value().decode() for m in batch)
    assert len(seen) == 400
    # per-producer order preserved
    for tag in "ab":
        mine = [s for s in seen if s.startswith(tag)]
        assert mine == [f"{tag}-{i}" for i in range(200)]


def test_bookmark_round_trip_through_broker(broker):
    """The durability plane's bookmark contract (ADR 0118): a consumer
    reads part of a topic, its transport ``positions()`` become the
    checkpoint bookmark, and a FRESH consumer assigned with
    ``start_offsets`` at that bookmark consumes exactly the remainder —
    no message lost, none replayed twice."""
    from esslivedata_tpu.kafka.source import BackgroundMessageSource

    prod = FileBrokerProducer(broker)
    first = FileBrokerConsumer(broker)
    assign_all_partitions(first, ["alpha"])
    source = BackgroundMessageSource(first)
    try:
        source.start()
        for i in range(6):
            prod.produce("alpha", f"m{i}".encode())
        seen: list[bytes] = []
        deadline = threading.Event()
        for _ in range(200):
            seen.extend(m.value() for m in source.get_messages())
            if len(seen) >= 3:
                break
            deadline.wait(0.02)
        assert len(seen) >= 3
        # The bookmark covers exactly what was HANDED to the worker.
        bookmark = source.positions()["alpha"]
        assert bookmark > 0
    finally:
        source.stop()
    # Restarted process: seek to the bookmark, consume the remainder.
    second = FileBrokerConsumer(broker)
    assign_all_partitions(
        second, ["alpha"], start_offsets={"alpha": bookmark}
    )
    rest: list[bytes] = []
    for _ in range(50):
        batch = second.consume(10, 0.0)
        if not batch and len(rest) + len(seen) >= 6:
            break
        rest.extend(m.value() for m in batch)
    assert seen + rest == [f"m{i}".encode() for i in range(6)]


def test_bookmark_beyond_high_watermark_clamps_to_live(broker):
    prod = FileBrokerProducer(broker)
    prod.produce("alpha", b"old")
    cons = FileBrokerConsumer(broker)
    # A bookmark from before the topic file was truncated/recreated:
    # way past the current high watermark; the seek clamps to live
    # instead of surfacing torn frames from a bogus mid-file offset.
    assign_all_partitions(
        cons, ["alpha"], start_offsets={"alpha": 10_000_000}
    )
    assert cons.consume(10, 0.0) == []
    prod.produce("alpha", b"new")
    assert [m.value() for m in cons.consume(10, 0.0)] == [b"new"]


def test_a_large_value_round_trips_byte_for_byte(broker):
    """A value of many MiB (a camera image's message) is appended from
    where it is, behind its header, and read back whole."""
    value = bytes(range(256)) * (3 * 2**14 + 7)
    prod = FileBrokerProducer(broker)
    prod.produce("alpha", b"before")
    prod.produce("alpha", value, key=b"orca")
    prod.produce("alpha", b"after")
    cons = FileBrokerConsumer(broker)
    cons.assign([type("TP", (), {"topic": "alpha", "offset": 0})()])
    got = [(m.value(), m.key()) for m in cons.consume(10, 0.0)]
    assert got == [(b"before", None), (value, b"orca"), (b"after", None)]


def test_a_short_write_is_carried_on_to_the_frame_end(broker, monkeypatch):
    """``writev`` may write fewer bytes than asked: the producer goes on
    from where it stopped, also inside a part, until the frame is out."""
    import os

    real_writev = os.writev
    calls = []

    def short_writev(fd, buffers):
        calls.append(sum(len(b) for b in buffers))
        return real_writev(fd, [bytes(memoryview(buffers[0])[:5])])

    monkeypatch.setattr(os, "writev", short_writev)
    FileBrokerProducer(broker).produce("beta", b"0123456789abcdef", key=b"key")
    monkeypatch.setattr(os, "writev", real_writev)
    cons = FileBrokerConsumer(broker)
    cons.assign([type("TP", (), {"topic": "beta", "offset": 0})()])
    got = [(m.value(), m.key()) for m in cons.consume(10, 0.0)]
    assert got == [(b"0123456789abcdef", b"key")]
    assert len(calls) > 2 and calls == sorted(calls, reverse=True)
