"""The load generator: a process of its own, so that its work shares
nothing with the client that times the results.

Started as ``python -m harness.generator <spec.json>`` with
``benchmark/`` on the path. It encodes the pool once, prints ``ready``,
then obeys lines on stdin and answers each on stdout with one JSON line:

``send N``   N pulses back to back (warm-up).
``mark``     answered with the pulses sent so far (also while running).
``run T``    pulses on the ``pulse_hz`` schedule until a ``stop``
             line: pulse k of the run is due at T + ``Traffic.due_ns(k)``
             (T: the client's window opening), a time that does not slip
             when sending is late.
``quit``     writes the pulse log and exits.

The pulse log is int64 rows (pulse, due_ns, sent_ns) on CLOCK_MONOTONIC,
which every process of the machine shares.
"""

from __future__ import annotations

import json
import select
import sys
import time
from pathlib import Path

import numpy as np

from .broker import Producer
from .traffic import Traffic, pulse_time_ns, stream_events, stream_pool
from .wire import Ad00Template, Ev44Template


class Generator:
    def __init__(self, spec: dict) -> None:
        self.traffic = Traffic.from_dict(spec["traffic"])
        self.producer = Producer(Path(spec["broker_dir"]))
        m = self.traffic.messages_per_pulse
        self.templates = []  # [pool entry][message of the pulse]: (topic, template)
        for entry in range(self.traffic.pool_pulses):
            self.templates.append([])
        cameras = []
        for index, stream in enumerate(spec["streams"]):
            pool = stream_pool(spec["seed"], index, stream, self.traffic)
            if stream.get("kind") == "camera":
                cameras.append((stream, pool))
                continue
            chunk = stream_events(stream, self.traffic) // m
            for entry, (ids, toa) in enumerate(pool):
                for part in range(m):
                    sel = slice(part * chunk, (part + 1) * chunk)
                    self.templates[entry].append(
                        (stream["topic"], Ev44Template(stream["wire_source"], toa[sel], ids[sel]))
                    )
        # a pulse's camera frames go after its ev44 messages, with its stamp
        for stream, pool in cameras:
            for entry in range(len(pool)):
                self.templates[entry] += [
                    (stream["topic"], Ad00Template(stream["wire_source"], frame))
                    for frame in pool[entry]
                ]
        self.base_index = int(time.time_ns() * 14 // 10**9)
        self.next_pulse = 0
        self.message_id = 0
        self.log: list[tuple[int, int, int]] = []

    def send_pulse(self, due_ns: int | None) -> None:
        pulse = self.next_pulse
        stamp = pulse_time_ns(self.base_index + pulse)
        for topic, template in self.templates[pulse % len(self.templates)]:
            self.producer.produce(topic, template.stamp(self.message_id, stamp))
            self.message_id += 1
        sent = time.monotonic_ns()
        self.log.append((pulse, sent if due_ns is None else due_ns, sent))
        self.next_pulse += 1

    def stop_requested(self, timeout_s: float) -> bool:
        """Waits up to ``timeout_s`` for a line: ``stop`` ends the phase,
        ``mark`` is answered with the pulses sent so far."""
        ready, _, _ = select.select([sys.stdin], [], [], max(timeout_s, 0.0))
        if not ready:
            return False
        word = sys.stdin.readline().strip()
        if word == "mark":
            print(json.dumps({"mark": self.next_pulse}), flush=True)
        return word in ("stop", "")

    def run_paced(self, start: int) -> None:
        k = 0
        while True:
            due = start + self.traffic.due_ns(k)
            if self.stop_requested((due - time.monotonic_ns()) / 1e9):
                return
            self.send_pulse(due)
            k += 1


def main(argv) -> int:
    spec = json.loads(Path(argv[1]).read_text())
    generator = Generator(spec)

    def say(**doc) -> None:
        print(json.dumps(doc), flush=True)

    say(ready=True, base_index=generator.base_index,
        messages_per_entry=[len(messages) for messages in generator.templates])
    while line := sys.stdin.readline():
        words = line.split()
        if not words:
            continue
        if words[0] == "send":
            for _ in range(int(words[1])):
                generator.send_pulse(None)
        elif words[0] == "mark":
            say(mark=generator.next_pulse)
            continue
        elif words[0] == "run":
            generator.run_paced(int(words[1]))
        elif words[0] == "quit":
            break
        say(sent=generator.next_pulse, bytes=generator.producer.bytes_written)
    np.asarray(generator.log, np.int64).reshape(-1, 3).tofile(spec["log_path"])
    generator.producer.close()
    say(sent=generator.next_pulse, bytes=generator.producer.bytes_written, done=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
