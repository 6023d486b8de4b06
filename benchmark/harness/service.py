"""The system under test as a child process, and the client side of it:
commands in, results out, ``/metrics`` beside.

The service is started as ``docker-compose.yml`` starts it, with no
path-selecting flag; only plumbing is passed.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
import uuid
from pathlib import Path

from . import prom
from .broker import Consumer, Producer, ensure_topics


class BenchFailure(Exception):
    """The run cannot produce a result (not: the result is wrong)."""


def child_env(repo: Path, data_dir: Path) -> dict[str, str]:
    """The child's environment: the caller's, minus every CPU pin (the
    child lands on whatever jax finds and names it), plus the geometry
    directory inside the checkout. The compile cache is the program's
    own rule: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    ``<checkout>/.jax_cache``."""
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS", "").lower() == "cpu":
        del env["JAX_PLATFORMS"]
    env.pop("LIVEDATA_FORCE_CPU", None)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    ).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    src = str(repo / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env["LIVEDATA_DATA_DIR"] = str(data_dir)
    return env


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def start_command(instrument: str, job: dict, number: str) -> bytes:
    """The ``start_job`` command of one configured job, as it goes on
    the commands topic: the job's workflow on its source, with its
    parameters and the auxiliary streams it binds (none by default)."""
    namespace, name = job["workflow"]
    return json.dumps({
        "kind": "start_job",
        "config": {
            "identifier": {
                "instrument": instrument, "namespace": namespace, "name": name, "version": 1,
            },
            "job_id": {"source_name": job["job_source"], "job_number": number},
            "params": job.get("params", {}),
            "aux_source_names": job.get("aux_source_names", {}),
            "schedule": {"start_time_ns": None, "end_time_ns": None},
        },
    }).encode()


class ServiceChild:
    """One ``python -m <service module>`` over a private file broker."""

    def __init__(self, config: dict, repo: Path, work: Path, extra_flags, deadline: float, allow_cpu: bool = False) -> None:
        self.instrument = config["instrument"]
        self.deadline = deadline
        self.broker = work / "broker"
        self.topic = {
            name: f"{self.instrument}_livedata_{name}"
            for name in ("data", "status", "commands", "responses")
        }
        ensure_topics(
            self.broker,
            [*self.topic.values(), config["detector_topic"],
             *(s["topic"] for s in config["streams"] if "topic" in s)],
        )
        self.producer = Producer(self.broker)
        self._consumers = {
            name: Consumer(self.broker, self.topic[name])
            for name in ("data", "status", "responses")
        }
        self.port = free_port()
        self.log_path = work / "service.log"
        self._log = open(self.log_path, "wb")
        data_dir = repo / ".bench_cache" / "geometry"
        data_dir.mkdir(parents=True, exist_ok=True)
        env = child_env(repo, data_dir)
        if allow_cpu:
            env["JAX_PLATFORMS"] = "cpu"
        module = config["service"]
        if "." not in module:
            module = f"esslivedata_tpu.services.{module}"
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", module,
                "--instrument", self.instrument,
                "--broker-dir", str(self.broker),
                "--metrics-port", str(self.port),
                *config.get("service_flags", []),
                *extra_flags,
            ],
            env=env, cwd=repo, stdout=self._log, stderr=subprocess.STDOUT,
        )

    @property
    def metrics_url(self) -> str:
        return f"http://127.0.0.1:{self.port}/metrics"

    def wait_for(self, what: str, probe, interval_s: float = 0.05):
        while True:
            got = probe()
            if got:
                return got
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"service exited rc={self.proc.returncode} while waiting for {what}"
                )
            if time.monotonic() > self.deadline:
                raise BenchFailure(f"timed out waiting for {what}")
            time.sleep(interval_s)

    def poll(self, name: str, limit: int = 64) -> list[bytes]:
        return self._consumers[name].poll(limit)

    def scrape(self):
        with urllib.request.urlopen(self.metrics_url, timeout=30) as response:
            return prom.parse(response.read().decode())

    def try_scrape(self):
        try:
            return self.scrape()
        except OSError:
            return None

    def start_jobs(self, config: dict) -> dict[str, str]:
        """Send one start_job per configured job; job_number -> job name
        once every one is acknowledged."""
        numbers = {}
        for job in config["jobs"]:
            number = str(uuid.uuid4())
            numbers[number] = job["name"]
            self.producer.produce(
                self.topic["commands"], start_command(self.instrument, job, number)
            )
        waiting = set(numbers)

        def probe():
            for raw in self.poll("responses"):
                doc = json.loads(raw)
                if doc.get("job_number") in waiting:
                    if doc.get("status") != "ack":
                        raise BenchFailure(f"start_job refused: {doc}")
                    waiting.discard(doc["job_number"])
            return not waiting

        self.wait_for("start_job acknowledgements", probe)
        return numbers

    def await_device(self) -> dict:
        """The service is up (first heartbeat) and names its device."""
        self.wait_for("the first service heartbeat", lambda: self.poll("status"))
        samples = self.wait_for("/metrics", self.try_scrape)
        infos = [labels for name, labels, _ in samples if name == "livedata_device_info"]
        if len(infos) != 1:
            raise BenchFailure(f"livedata_device_info has {len(infos)} samples")
        return {
            "platform": infos[0]["platform"],
            "kind": infos[0]["device_kind"],
            "count": int(infos[0]["count"]),
        }

    def stop(self) -> int:
        """SIGTERM, wait, reap (kill on a hang). Returns the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        self.producer.close()
        return self.proc.returncode

    def log_tail(self, n_bytes: int = 4000) -> str:
        try:
            return self.log_path.read_bytes()[-n_bytes:].decode(errors="replace")
        except OSError:
            return "<no log>"


class GeneratorChild:
    """The generator process and its line protocol."""

    def __init__(self, spec: dict, bench_dir: Path, work: Path) -> None:
        spec_path = work / "generator.json"
        spec_path.write_text(json.dumps(spec))
        env = dict(os.environ)
        env["PYTHONPATH"] = str(bench_dir)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "harness.generator", str(spec_path)],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, bufsize=1,
        )
        self.hello = self._reply()

    def _reply(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise BenchFailure(f"the generator exited rc={self.proc.wait()}")
        return json.loads(line)

    def tell(self, command: str) -> None:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()

    def ask(self, command: str) -> dict:
        self.tell(command)
        return self._reply()

    def close(self) -> dict | None:
        last = None
        if self.proc.poll() is None:
            try:
                last = self.ask("quit")
                self.proc.wait(timeout=30)
            except (BenchFailure, OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        return last
