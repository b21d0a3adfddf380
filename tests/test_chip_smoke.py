"""The chip smoke's phases and checks, run small on the CPU.

``chip_smoke.py`` refuses any first device that is not a TPU, so here
its phase functions are called directly at tiny sizes, with the Pallas
kernels interpreted; the checks are also fed faked results to show they
fail where they must.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402
from chip_smoke import SmokeFailure  # noqa: E402


@pytest.fixture(autouse=True)
def _x64_off():
    """The smoke runs with x64 off; the suite's default is on."""
    with jax.enable_x64(False):
        yield


def test_main_refuses_a_cpu_process(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_phase_diffusion_small(tmp_path):
    line = chip_smoke.phase_diffusion(str(tmp_path), n=(24, 16, 20),
                                      lengths=(1.2, 0.8, 1.0), iters=4)
    assert line["rung"] in chip_smoke.FUSED_RUNGS
    assert line["max_err"] < 1e-5 and line["error_l1"] < 1.0
    # interpreted kernels compile to plain fusions: the chip check
    # main() applies fails here, as it must off the chip
    with pytest.raises(SmokeFailure, match="tpu_custom_call"):
        chip_smoke.check_kernels(line, "diffusion3d")


def test_phase_diffusion_fails_on_a_degraded_ladder(tmp_path):
    """A Mosaic rejection makes impl='pallas' fall down the kernel
    ladder; the run completes, and the phase must fail on it."""
    from multigpu_advectiondiffusion_tpu.resilience import faults

    with faults.mosaic_failure(), pytest.raises(SmokeFailure):
        chip_smoke.phase_diffusion(str(tmp_path), n=(24, 16, 20),
                                   lengths=(1.2, 0.8, 1.0), iters=2)


def test_phase_burgers_small(tmp_path):
    line = chip_smoke.phase_burgers(str(tmp_path), n=(24, 16, 16), iters=2)
    assert line["rung"] in chip_smoke.FUSED_RUNGS
    assert line["max_err"] < 1e-5


def test_phase_serving_small(tmp_path):
    line = chip_smoke.phase_serving(str(tmp_path), n=(16, 16, 16))
    assert line["requests"] == 4
    assert line["rung"] == "ensemble-vmap[generic-xla]"
    assert line["max_err"] < 1e-5


def test_phase_sharded_small(devices):
    lines = chip_smoke.phase_sharded(
        4, diff_n=(16, 16, 240), burg_n=(16, 16, 144), iters=2,
    )
    assert [ln["phase"] for ln in lines] == [
        "diffusion3d-dz4-split", "diffusion3d-dz4-dma",
        "burgers3d-dz4-split",
    ]
    assert all(ln["max_err"] < 1e-5 for ln in lines)


@pytest.mark.parametrize("engaged,match", [
    ({"stepper": "generic-xla", "fallback": None}, "engaged"),
    ({"stepper": "fused-stage", "fallback": "ineligible"}, "fallback"),
    ({"stepper": "fused-stage", "fallback": None,
      "degraded": [{"from": "fused-stage", "to": "xla"}]}, "degraded"),
    ({"stepper": "fused-stage", "fallback": None,
      "overlap": "serialized-refresh"}, "overlap"),
])
def test_check_engaged_fails(engaged, match):
    with pytest.raises(SmokeFailure, match=match):
        chip_smoke.check_engaged(engaged, overlap="split")


def test_check_close_fails_outside_tolerance():
    ref = np.linspace(0.0, 1.0, 64, dtype=np.float32)
    assert chip_smoke.check_close(ref, ref, 1e-4, 1e-6, "same") == 0.0
    with pytest.raises(SmokeFailure, match="outside"):
        chip_smoke.check_close(ref + 1e-3, ref, 1e-4, 1e-6, "shifted")
    bad = ref.copy()
    bad[3] = np.nan
    with pytest.raises(SmokeFailure, match="non-finite"):
        chip_smoke.check_close(bad, ref, 1e-4, 1e-6, "nan")


def test_check_devices_fails_on_one_device(devices):
    x = jax.device_put(np.zeros((8, 4), np.float32), devices[0])
    with pytest.raises(SmokeFailure, match="1 device"):
        chip_smoke.check_devices(x, 4, "unsharded")
