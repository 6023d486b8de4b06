"""The /metrics plane: scrape + liveness over real HTTP, validated with
the in-tree promtext parser (what CI's metrics smoke runs against a
live service)."""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import pytest

from esslivedata_tpu.telemetry import (
    MetricsRegistry,
    MetricsServer,
    parse_prometheus_text,
    start_metrics_server,
)


@pytest.fixture()
def server():
    registry = MetricsRegistry()
    c = registry.counter("livedata_test_ticks", "ticks", labelnames=("site",))
    c.inc(3, site="tick")
    srv = MetricsServer(0, host="127.0.0.1", registry=registry)
    try:
        yield srv
    finally:
        srv.close()


def fetch(server: MetricsServer, path: str):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}{path}", timeout=5
    )


class TestMetricsPlane:
    def test_metrics_scrape_parses(self, server):
        response = fetch(server, "/metrics")
        assert response.status == 200
        assert response.headers["Content-Type"].startswith("text/plain")
        parsed = parse_prometheus_text(response.read().decode())
        family = parsed["livedata_test_ticks"]
        assert family.kind == "counter"
        assert family.samples == [
            ("livedata_test_ticks_total", {"site": "tick"}, 3.0)
        ]

    def test_healthz(self, server):
        response = fetch(server, "/healthz")
        assert response.status == 200
        assert json.loads(response.read()) == {"status": "ok"}

    def test_unknown_path_404(self, server):
        with pytest.raises(urllib.error.HTTPError) as err:
            fetch(server, "/nope")
        assert err.value.code == 404

    def test_start_metrics_server_none_port_is_noop(self):
        assert start_metrics_server(None) is None

    def test_concurrent_scrapes(self, server):
        import threading

        payloads = []
        lock = threading.Lock()

        def scrape():
            body = fetch(server, "/metrics").read().decode()
            with lock:
                payloads.append(body)

        threads = [threading.Thread(target=scrape) for _ in range(8)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        assert len(payloads) == 8
        for body in payloads:
            parse_prometheus_text(body)


def post(server: MetricsServer, path: str):
    request = urllib.request.Request(
        f"http://127.0.0.1:{server.port}{path}", method="POST"
    )
    return urllib.request.urlopen(request, timeout=30)


class TestProfilerOnCommand:
    """``POST /profile?seconds=N``: a bounded ``jax.profiler`` session
    into a directory of the server's choosing."""

    @pytest.fixture(autouse=True)
    def no_cooldown_left_over(self, monkeypatch):
        """Each test starts with the endpoint's cool-down spent, and
        (but for the test of it) asks for none."""
        from esslivedata_tpu.telemetry import http

        import jax  # noqa: F401  (the endpoint refuses without it)

        monkeypatch.setattr(http._PROFILE, "_not_before", 0.0)
        monkeypatch.setattr(http, "PROFILE_COOLDOWN_S", 0.0)

    @staticmethod
    def wait_for_the_session_to_end():
        import time

        from esslivedata_tpu.utils import profiling

        deadline = time.monotonic() + 30
        while profiling._SESSION.locked() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not profiling._SESSION.locked()

    def test_starts_a_session_in_a_fresh_directory_under_tmpdir(
        self, server, tmp_path, monkeypatch
    ):
        import tempfile
        from pathlib import Path

        monkeypatch.setenv("TMPDIR", str(tmp_path))
        monkeypatch.setattr(tempfile, "tempdir", None)  # re-read TMPDIR
        try:
            response = post(server, "/profile?seconds=0.2")
            assert response.status == 202
            body = json.loads(response.read())
            assert body["seconds"] == 0.2
            log_dir = Path(body["dir"])
            assert log_dir.parent == tmp_path and log_dir.is_dir()
            # One session per process: a second ask while it runs is
            # refused, whoever started the first (--profile included).
            with pytest.raises(urllib.error.HTTPError) as err:
                post(server, "/profile?seconds=0.2")
            assert err.value.code == 409
        finally:
            self.wait_for_the_session_to_end()
            monkeypatch.setattr(tempfile, "tempdir", None)
        assert list(log_dir.glob("plugins/profile/*/*.xplane.pb"))
        # ... and once it is over the next one starts.
        assert post(server, "/profile?seconds=0.1").status == 202
        self.wait_for_the_session_to_end()

    @pytest.mark.parametrize(
        "query", ["seconds=0", "seconds=-1", "seconds=61", "seconds=nan",
                  "seconds=abc"]
    )
    def test_bad_or_uncapped_seconds_are_refused(self, server, query):
        from esslivedata_tpu.utils import profiling

        with pytest.raises(urllib.error.HTTPError) as err:
            post(server, f"/profile?{query}")
        assert err.value.code == 400
        assert not profiling._SESSION.locked()

    def test_no_path_is_taken_from_the_client(self, server):
        with pytest.raises(urllib.error.HTTPError) as err:
            post(server, "/profile/etc/cron.d?seconds=1")
        assert err.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as err:
            post(server, "/metrics")
        assert err.value.code == 404

    def test_a_session_is_followed_by_a_cool_down(self, server, monkeypatch):
        from esslivedata_tpu.telemetry import http

        monkeypatch.setattr(http, "PROFILE_COOLDOWN_S", 120.0)
        try:
            assert post(server, "/profile?seconds=0.1").status == 202
        finally:
            self.wait_for_the_session_to_end()
        with pytest.raises(urllib.error.HTTPError) as err:
            post(server, "/profile?seconds=0.1")
        assert err.value.code == 429
        assert 0 < int(err.value.headers["Retry-After"]) <= 121
        # Once it has passed the next session starts.
        monkeypatch.setattr(http._PROFILE, "_not_before", 0.0)
        assert post(server, "/profile?seconds=0.1").status == 202
        self.wait_for_the_session_to_end()

    def test_only_the_newest_trace_directories_are_kept(
        self, server, tmp_path, monkeypatch
    ):
        import os
        import tempfile

        from esslivedata_tpu.telemetry import http

        monkeypatch.setenv("TMPDIR", str(tmp_path))
        monkeypatch.setattr(tempfile, "tempdir", None)  # re-read TMPDIR
        try:
            for age in range(6):  # what earlier sessions and runs left
                old = tmp_path / f"livedata-profile-old{age}"
                old.mkdir()
                (old / "trace.xplane.pb").write_bytes(b"x")
                os.utime(old, (1000 + age, 1000 + age))
            other = tmp_path / "someone-elses"
            other.mkdir()
            body = json.loads(post(server, "/profile?seconds=0.1").read())
        finally:
            self.wait_for_the_session_to_end()
            monkeypatch.setattr(tempfile, "tempdir", None)
        kept = sorted(p.name for p in tmp_path.glob("livedata-profile-*"))
        assert len(kept) == http.PROFILE_KEEP_DIRS
        assert os.path.basename(body["dir"]) in kept
        assert {"livedata-profile-old3", "livedata-profile-old4",
                "livedata-profile-old5"} < set(kept)
        assert other.is_dir()

    def test_a_process_without_jax_is_refused(self, server, monkeypatch):
        """The relay and the fakes never import jax, and this endpoint
        must not be what does."""
        import sys

        from esslivedata_tpu.utils import profiling

        monkeypatch.delitem(sys.modules, "jax")
        with pytest.raises(urllib.error.HTTPError) as err:
            post(server, "/profile?seconds=1")
        assert err.value.code == 503
        assert "jax" not in sys.modules
        assert not profiling._SESSION.locked()

    def test_a_launch_time_session_blocks_the_endpoint(self, server, tmp_path):
        from esslivedata_tpu.utils.profiling import bounded_device_trace

        assert bounded_device_trace(str(tmp_path / "launch"), 0.3)
        try:
            assert not bounded_device_trace(str(tmp_path / "second"), 0.3)
            with pytest.raises(urllib.error.HTTPError) as err:
                post(server, "/profile?seconds=1")
            assert err.value.code == 409
        finally:
            self.wait_for_the_session_to_end()
        assert not (tmp_path / "second").exists()


class TestServiceRunnerFlag:
    def test_setup_arg_parser_starts_endpoint_on_metrics_port(self):
        """--metrics-port 0 on the shared parser (every service runner's
        surface) must bring up a live /metrics + /healthz endpoint."""
        from esslivedata_tpu.core import service as service_mod

        parser = service_mod.setup_arg_parser("test")
        parser.parse_args(["--metrics-port", "0"])
        # The table keys by REQUESTED port (0 = ephemeral ask); the
        # bound port lives on the server. A second parse with the same
        # request must REUSE the listener, not leak another one.
        server = service_mod._metrics_servers.get(0)
        assert server is not None, "no metrics server started"
        parser.parse_args(["--metrics-port", "0"])
        assert service_mod._metrics_servers[0] is server
        port = server.port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        parse_prometheus_text(body)
        health = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5
            ).read()
        )
        assert health == {"status": "ok"}
        service_mod._metrics_servers.pop(0)
        server.close()

    def test_trace_dump_flag_registers_exit_dump(self, tmp_path):
        from esslivedata_tpu.core import service as service_mod
        from esslivedata_tpu.telemetry import TRACER

        path = tmp_path / "trace.json"
        parser = service_mod.setup_arg_parser("test")
        parser.parse_args(["--trace-dump", str(path)])
        assert str(path) in service_mod._trace_dump_paths
        # The atexit hook is registered; dump directly to verify the
        # ring serializes (exit-time behavior minus the interpreter
        # teardown).
        TRACER.dump(str(path))
        doc = json.loads(path.read_text())
        assert "traceEvents" in doc
        service_mod._trace_dump_paths.discard(str(path))
