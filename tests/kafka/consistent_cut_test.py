"""A poll is one cut through the topics, and the worker is handed whole
cuts: a service that batches by data time across two topics (LOKI's
detector and monitor events) must not see one topic's later messages
without the other's earlier ones."""

import os
import threading

import pytest

from esslivedata_tpu.kafka.consumer import assign_all_partitions
from esslivedata_tpu.kafka.file_broker import (
    FileBrokerConsumer,
    FileBrokerProducer,
    ensure_topics,
)
from esslivedata_tpu.kafka.source import (
    _HELD_BATCHES,
    BackgroundMessageSource,
    FakeKafkaMessage,
)


@pytest.fixture
def broker(tmp_path):
    ensure_topics(tmp_path, ["detector", "monitor"])
    return tmp_path


@pytest.mark.parametrize("first", ["detector", "monitor"])
def test_a_poll_reads_no_topic_past_the_moment_it_began(broker, first, monkeypatch):
    """A producer that keeps writing pulse after pulse while the first
    topic is being read: what it appends meanwhile is the next poll's,
    on every topic, whichever is read first."""
    producer = FileBrokerProducer(broker)
    consumer = FileBrokerConsumer(broker)
    assign_all_partitions(consumer, ["detector", "monitor"])
    while list(consumer._offsets)[consumer._rr % 2] != first:
        consumer.consume(10, 0.0)  # the rotation decides the order
    for pulse in range(3):
        producer.produce("detector", b"d%d" % pulse)
        producer.produce("monitor", b"m%d" % pulse)
    read_topic = consumer._read_topic
    reads = []

    def read_while_the_producer_writes(topic, limit, size):
        if not reads:  # pulse 3 lands during the first topic's read
            producer.produce("detector", b"d3")
            producer.produce("monitor", b"m3")
        reads.append(topic)
        return read_topic(topic, limit, size)

    monkeypatch.setattr(consumer, "_read_topic", read_while_the_producer_writes)
    values = {m.value() for m in consumer.consume(100, 0.0)}
    assert reads[0] == first
    assert values == {b"d0", b"d1", b"d2", b"m0", b"m1", b"m2"}
    assert {m.value() for m in consumer.consume(100, 0.0)} == {b"d3", b"m3"}


def test_nothing_is_appended_to_any_topic_while_the_cut_is_taken(broker, monkeypatch):
    """Sizes read one after another are a cut only if nothing grows in
    between: a producer that writes pulse 3 (monitor, then the next
    detector message) while the sizes are read waits for its lock."""
    from esslivedata_tpu.kafka import file_broker

    producer = FileBrokerProducer(broker)
    consumer = FileBrokerConsumer(broker)
    assign_all_partitions(consumer, ["monitor", "detector"])
    for pulse in range(3):
        producer.produce("detector", b"d%d" % pulse)
        producer.produce("monitor", b"m%d" % pulse)
    writers = []
    fstat = os.fstat

    def fstat_while_a_producer_tries(fd):
        if not writers:

            def write():
                producer.produce("monitor", b"m3")
                producer.produce("detector", b"d4")

            writers.append(threading.Thread(target=write))
            writers[0].start()
            writers[0].join(timeout=0.3)
            assert writers[0].is_alive(), "an append went through under the cut"
        return fstat(fd)

    monkeypatch.setattr(file_broker.os, "fstat", fstat_while_a_producer_tries)
    values = {m.value() for m in consumer.consume(100, 0.0)}
    writers[0].join(timeout=5.0)
    assert values == {b"d0", b"d1", b"d2", b"m0", b"m1", b"m2"}
    assert {m.value() for m in consumer.consume(100, 0.0)} == {b"m3", b"d4"}


def test_a_frame_that_ends_after_the_cut_waits_for_the_next_poll(broker):
    producer = FileBrokerProducer(broker)
    consumer = FileBrokerConsumer(broker)
    assign_all_partitions(consumer, ["detector"])
    producer.produce("detector", b"whole")
    size = consumer._cut(["detector"])["detector"]
    producer.produce("detector", b"later")
    assert [m.value() for m in consumer._read_topic("detector", 10, size)] == [b"whole"]
    assert [m.value() for m in consumer._read_topic("detector", 10, size + 3)] == []
    assert [m.value() for m in consumer.consume(10, 0.0)] == [b"later"]
    assert consumer._cut(["no_such_topic"]) == {"no_such_topic": 0}


class ScriptedConsumer:
    """Hands out one scripted poll per ``step()`` and blocks between
    them, as a consumer does in ``consume`` (an idle empty poll would
    say "caught up")."""

    def __init__(self, polls):
        self.polls = list(polls)
        self.allowed = threading.Semaphore(0)
        self.taken = threading.Semaphore(0)
        self.closed = False

    def consume(self, num_messages, timeout):
        while not self.allowed.acquire(timeout=0.02):
            if self.closed:
                return []
        batch = self.polls.pop(0) if self.polls else []
        self.taken.release()
        return batch

    def step(self):
        self.allowed.release()
        assert self.taken.acquire(timeout=5.0)
        threading.Event().wait(0.05)  # the loop's bookkeeping after the poll


def messages(n, topic="t"):
    return [FakeKafkaMessage(b"%d" % i, topic) for i in range(n)]


@pytest.fixture
def stepped():
    started = []

    def start(polls, budget, **options):
        consumer = ScriptedConsumer(polls)
        source = BackgroundMessageSource(
            consumer, max_messages=budget, timeout_s=0.001, **options
        )
        source.start()
        started.append((consumer, source))
        return consumer, source

    yield start
    for consumer, source in started:
        consumer.closed = True
        source.stop()


def test_a_poll_that_filled_its_budget_is_held_until_the_consumer_has_caught_up(stepped):
    full, rest = messages(4, "detector"), messages(2, "monitor")
    consumer, source = stepped([full, rest], 4)
    consumer.step()  # four of four: the budget, so more may wait unread
    assert source.get_messages() == []
    consumer.step()  # two of four: caught up
    assert source.get_messages() == full + rest
    consumer.step()  # an empty poll also says so
    assert source.get_messages() == []


def test_a_backlog_that_never_ends_is_handed_over_all_the_same(stepped):
    consumer, source = stepped([messages(2) for _ in range(_HELD_BATCHES)], 2)
    for _ in range(_HELD_BATCHES - 1):
        consumer.step()
        assert source.get_messages() == []  # every poll at its budget: held
    consumer.step()
    assert len(source.get_messages()) == 2 * _HELD_BATCHES  # progress before order


def test_a_queue_shorter_than_the_hold_hands_over_when_it_is_full(stepped):
    """The hold never outlasts the queue: a full drop-oldest queue that
    went on waiting would discard what it holds."""
    consumer, source = stepped([messages(2) for _ in range(4)], 2, max_queued_batches=3)
    for _ in range(2):
        consumer.step()
        assert source.get_messages() == []
    consumer.step()
    assert len(source.get_messages()) == 6 and source._dropped_batches == 0


class Raising(ScriptedConsumer):
    def consume(self, num_messages, timeout):
        batch = super().consume(num_messages, timeout)
        if batch == "raise":
            raise OSError("broker away")
        return batch


@pytest.mark.parametrize("ending", ["consume_raises", "stop"])
def test_what_is_queued_is_not_stranded_when_the_reading_ends(ending):
    """A full poll is held for the next one; where that one raises, or
    the source is stopped, nothing more comes and the held poll goes out."""
    full = messages(2)
    consumer = Raising([full, "raise"])
    source = BackgroundMessageSource(consumer, max_messages=2, timeout_s=0.001)
    source.start()
    try:
        consumer.step()
        assert source.get_messages() == []
        if ending == "consume_raises":
            consumer.step()
        else:
            consumer.closed = True
            source.stop()
        assert source.get_messages() == full
    finally:
        consumer.closed = True
        source.stop()
