"""Plumbing of ``chip_smoke.py`` on the CPU, against the file broker.

Runs the script as the driver would (``python chip_smoke.py``) in its
``--instrument dummy --allow-cpu`` mode. This proves the harness — the
child's environment, the numpy reference, the exit codes — and nothing
about the chip.
"""

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
SCRIPT = REPO / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses resolve their module
    try:
        spec.loader.exec_module(module)
    finally:
        del sys.modules[spec.name]
    return module


def _run(tmp_path, *flags):
    return subprocess.run(
        [
            sys.executable,
            str(SCRIPT),
            "--instrument",
            "dummy",
            "--phase-timeout",
            "120",
            "--log-dir",
            str(tmp_path),
            *flags,
        ],
        capture_output=True,
        text=True,
        timeout=420,
    )


def test_child_env_is_stripped_of_every_cpu_pin():
    env = _load().child_env(
        {
            "JAX_PLATFORMS": "cpu",
            "LIVEDATA_FORCE_CPU": "1",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8 --xla_foo=1",
            "JAX_COMPILATION_CACHE_DIR": "/somewhere",
            "PYTHONPATH": "/elsewhere",
        }
    )
    assert "JAX_PLATFORMS" not in env
    assert "LIVEDATA_FORCE_CPU" not in env
    assert env["XLA_FLAGS"] == "--xla_foo=1"
    # What places the compile cache from outside is inherited.
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere"
    assert env["PYTHONPATH"].split(":") == [str(REPO / "src"), "/elsewhere"]
    # A platform someone chose other than the CPU pin is left alone.
    assert _load().child_env({"JAX_PLATFORMS": "tpu"})["JAX_PLATFORMS"] == "tpu"


def test_deployment_tables_mirror_the_instrument_packages():
    """The script cannot import the instrument packages (they import
    jax), so its wire-level table is pinned against them here."""
    import numpy as np

    import esslivedata_tpu.config.instruments  # noqa: F401 - registers
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.streams import get_stream_mapping
    from esslivedata_tpu.config.workflow_spec import WorkflowId
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    for name, dep in _load().DEPLOYMENTS.items():
        instrument = instrument_registry[name]
        mapping = get_stream_mapping(instrument, False)
        wire_names = {
            stream: (key.topic, key.source_name)
            for key, stream in {**mapping.detectors, **mapping.monitors}.items()
        }
        assert wire_names[dep.detector_job_source] == (
            dep.detector_topic,
            dep.detector_source,
        )
        assert wire_names[dep.monitor_job_source] == (
            dep.monitor_topic,
            dep.monitor_source,
        )
        numbers = np.asarray(
            instrument.detectors[dep.detector_job_source].detector_number
        )
        assert numbers.shape == dep.shape
        assert numbers.min() == dep.first_id
        assert numbers.max() == dep.first_id + numbers.size - 1
        namespace, workflow = dep.detector_workflow
        for wid in (
            WorkflowId(instrument=name, namespace=namespace, name=workflow),
            WorkflowId(instrument=name, namespace="monitor_data", name="histogram"),
        ):
            assert wid in workflow_registry


def test_script_never_imports_jax():
    done = subprocess.run(
        [
            sys.executable,
            "-c",
            "import runpy, sys; runpy.run_path(sys.argv[1]); "
            "import esslivedata_tpu.kafka.wire, esslivedata_tpu.telemetry, "
            "esslivedata_tpu.config.workflow_spec, esslivedata_tpu.native; "
            "sys.exit('jax' in sys.modules)",
            str(SCRIPT),
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert done.returncode == 0, done.stderr


def test_all_phases_match_the_numpy_reference(tmp_path):
    done = _run(tmp_path, "--allow-cpu")
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert "proves NOTHING about the chip" in done.stdout
    reports = [
        json.loads(line.removeprefix("chip_smoke: "))
        for line in lines
        if line.startswith('chip_smoke: {"phase"')
    ]
    assert [r["phase"] for r in reports] == ["detector", "detector-fast", "monitor"]
    for report in reports:
        assert report["windows_published"] >= 4
        assert report["tick_publishes"] >= report["windows_published"]
    # The fast phase reached the AOT warm-up (Lowered.compile).
    assert reports[1]["warmup_compiles"] >= 6  # 3 jobs x 2 variants
    # The child's own log says jax landed on the CPU without being asked:
    # the CPU pins of this test process did not reach it.
    assert "without being asked" in (tmp_path / "detector.log").read_text()


def test_cpu_device_fails_without_allow_cpu(tmp_path):
    done = _run(tmp_path)
    assert done.returncode == 1
    assert "not a TPU" in done.stdout
    assert '"ok"' not in done.stdout


def test_wrong_reference_fails_the_phase(tmp_path):
    done = _run(tmp_path, "--allow-cpu", "--inject-wrong-reference")
    assert done.returncode == 1
    assert "differ from the numpy reference" in done.stdout
    assert '"ok"' not in done.stdout
