"""Test configuration: CPU backend with 8 virtual devices.

The reference cannot test multi-GPU without a physical cluster
(``MPIDeviceCheck`` exits with < 2 GPUs, ``Util.cu:43-61``). Here the
distributed runtime is validated on a simulated 8-device CPU mesh
(SURVEY §4 implication (c)). Env vars must be set before jax imports.
"""

import os

# The suite always runs on virtual CPU devices, so sharding is exercised
# without hardware and no test process ever holds a chip. Set before jax
# is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import tempfile

# the tuner must never read or write the user-level decision cache from
# tests (bench entry points under test enable measurement process-wide),
# and any in-test measurement runs at smoke-grade cost
os.environ.setdefault(
    "TPUCFD_TUNING_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="tpucfd_test_tuning_"),
                 "tuning.json"),
)
os.environ.setdefault("TPUCFD_TUNE_ITERS", "2")
# entry points point JAX's persistent compilation cache at
# <repo>/.jax_cache; tests (and the CLI children they start) compile
# fresh, so no test reads what another wrote
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
os.environ.setdefault("TPUCFD_TUNE_REPS", "1")

# measured-peak calibration must never read or write the user-level
# record from tests. The per-test fixture below gives each in-process
# test a fresh store; this session-level default covers SUBPROCESSES
# whose env is snapshotted at module-import time (test_examples._ENV),
# before any fixture runs.
os.environ.setdefault(
    "TPUCFD_CALIBRATION_PATH",
    os.path.join(tempfile.mkdtemp(prefix="tpucfd_test_calib_"),
                 "calibration.json"),
)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _isolate_calibration(tmp_path, monkeypatch):
    """Measured-peak calibration (telemetry/calibration.py) takes
    precedence over the env-assumed peaks in costmodel.peak_rates; a
    record written by one test (any run_solver call observes one) must
    never leak into another test's rooflines or tuner pruning — each
    test gets a fresh, empty store. Also zero the watermark tracker so
    one test's device-memory peak cannot bleed into the next."""
    monkeypatch.setenv(
        "TPUCFD_CALIBRATION_PATH", str(tmp_path / "calibration.json")
    )
    from multigpu_advectiondiffusion_tpu.telemetry import xprof

    xprof.reset_watermarks()
    yield
    xprof.reset_watermarks()


@pytest.fixture(autouse=True)
def _isolate_tuner_state():
    """bench/matrix entry points call tuning.configure (process-global);
    restore the knobs after every test so one test's enablement cannot
    change another's dispatch."""
    from multigpu_advectiondiffusion_tpu import tuning

    saved = dict(tuning._state)
    yield
    tuning._state.clear()
    tuning._state.update(saved)
