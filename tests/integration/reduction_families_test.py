"""Multi-process reduction-family scenario: a DREAM powder job started
from the dashboard, reduced by a real data_reduction subprocess over the
file broker, with the I(d) pattern arriving back — the physics-workflow
analog of the detector-view end-to-end scenario."""

import time

import numpy as np
import pytest

from .backend import (
    IntegrationBackend,
    http_json,
    wait_for_http,
)

pytestmark = pytest.mark.integration

PORT = 8951  # its own: lifecycle_scenarios_test.py holds 8941 and may run beside it (xdist)
H_OVER_MN = 3956.034


@pytest.fixture(scope="module")
def backend(tmp_path_factory):
    b = IntegrationBackend(
        tmp_path_factory.mktemp("broker-dream"), instrument="dream"
    )
    yield b
    b.shutdown()


class TestPowderReduction:
    def test_dspacing_pattern_reaches_dashboard(self, backend):
        reduction = backend.spawn_service("data_reduction")
        dash = backend.spawn_dashboard(PORT)
        base = f"http://localhost:{PORT}"
        try:
            backend.wait_for_heartbeat(timeout_s=120)
            wait_for_http(f"{base}/api/state", timeout_s=120)

            state = http_json(f"{base}/api/state")
            wid = next(
                w["workflow_id"]
                for w in state["workflows"]
                if "powder/dspacing" in w["workflow_id"]
            )
            out = http_json(
                f"{base}/api/workflow/start",
                {
                    "workflow_id": wid,
                    "source_name": "mantle_detector",
                    "params": {"d_bins": 100},
                },
            )
            job_number = out["job_number"]

            def job_known():
                s = http_json(f"{base}/api/state")
                return any(
                    j["job_number"] == job_number for j in s["jobs"]
                )

            backend.wait_for(job_known, 60)

            # Monochromatic Bragg arrivals into the mantle: every event
            # at the flight time of lambda = 2 A for L ~ 77.7 m.
            t_ns = 2.0 * 77.7 / H_OVER_MN * 1e9
            t0 = time.time_ns()
            rng = np.random.default_rng(0)
            from esslivedata_tpu.kafka import wire

            for pulse in range(8):
                ids = rng.integers(1, 491521, 800).astype(np.int32)
                toa = np.full(800, t_ns, dtype=np.int32)
                payload = wire.encode_ev44(
                    "dream_mantle_detector",
                    pulse,
                    np.array([t0 + pulse * (10**9 // 14)]),
                    np.array([0]),
                    toa,
                    pixel_id=ids,
                )
                backend.producer.produce("dream_detector", payload)
                backend.producer.flush()
                time.sleep(0.1)

            def has_pattern():
                s = http_json(f"{base}/api/state")
                return [
                    k
                    for k in s["keys"]
                    if k["output"] == "dspacing_cumulative"
                ]

            keys = backend.wait_for(has_pattern, 90)
            assert keys, "I(d) never reached the dashboard"
            # And it renders.
            import urllib.request

            png = urllib.request.urlopen(
                f"{base}/plot/{keys[0]['id']}.png", timeout=30
            ).read()
            assert png[:4] == b"\x89PNG"
        except (AssertionError, TimeoutError) as err:
            backend.kill(dash)
            raise AssertionError(
                backend.dump_output(reduction, "reduction")
                + backend.dump_output(dash, "dashboard")
            ) from err
        finally:
            backend.kill(dash)
            backend.kill(reduction)
