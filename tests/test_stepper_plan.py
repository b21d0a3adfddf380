"""What a sharded fused stepper declares of its plan, against hand counts
and against what one step does (interpret mode, CPU): the bytes its
stage kernels DMA from and to HBM (``hbm_bytes_per_step``), the bytes a
shard sends through the halo exchange (``halo_bytes_per_step``), and the
kernel block that runs (``block``), as ``engaged_path()`` reports them
and as stats of the ``tpucfd.run[<stepper>]`` span."""

from __future__ import annotations

import json

import jax
from jax.experimental.pallas import tpu as pltpu

from multigpu_advectiondiffusion_tpu import (
    BurgersConfig,
    BurgersSolver,
    DiffusionConfig,
    DiffusionSolver,
    Grid,
    telemetry,
)
from multigpu_advectiondiffusion_tpu.parallel.mesh import (
    Decomposition,
    make_mesh,
)

from host_trace import host_events

F32 = 4


def _dz4(devices):
    return {"mesh": make_mesh({"dz": 4}, devices=devices[:4]),
            "decomp": Decomposition.slab("dz")}


def _counted_dmas(monkeypatch) -> list:
    """Bytes of every DMA a stage kernel starts, appended as it runs."""
    moved = []
    real = pltpu.make_async_copy

    def counted(src, dst, sem):
        copy = real(src, dst, sem)
        n = src.size * src.dtype.itemsize

        class Counted:
            def start(self):
                jax.debug.callback(lambda: moved.append(n))
                copy.start()

            def wait(self):
                copy.wait()

        return Counted()

    monkeypatch.setattr(pltpu, "make_async_copy", counted)
    return moved


def _one_step(tmp_path, solver):
    """One step with the halo counters and the profiler on: ``(run span
    stats, halo bytes the exchange sites recorded)``."""
    path = tmp_path / "events.jsonl"
    state = solver.initial_state()
    with telemetry.capture(str(path)):
        events = host_events(
            tmp_path / "trace",
            lambda: solver.run(state, 1).u.block_until_ready())
    halo = sum(e.get("inc", 0) for e in map(json.loads, open(path))
               if e.get("name") == "halo.bytes_per_execution")
    (span,) = [e for e in events if e.name == "tpucfd.run[fused-stage]"]
    return span.stats, halo


def test_sharded_burgers_plan(tmp_path, monkeypatch, devices):
    """The four-chip Burgers cell's schedule at a tiny shape: 24-plane
    shards of 24x16 planes take 8x24 blocks (one y block, 24 rows wide)
    in three z-blocks, so the split-overlap schedule runs."""
    moved = _counted_dmas(monkeypatch)
    grid = Grid.make(16, 24, 96, lengths=(2.0, 3.0, 12.0))
    solver = BurgersSolver(
        BurgersConfig(grid=grid, nu=0.0, cfl=0.3, dtype="float32",
                      adaptive_dt=False, impl="pallas", overlap="split"),
        **_dz4(devices))
    eng = solver.engaged_path()
    assert eng["stepper"] == "fused-stage" and eng["overlap"] == "split"
    fused = solver._fused_stepper()
    assert fused.padded_shape == (30, 40, 128)
    assert fused.block == (8, 24) and eng["block"] == "8x24"
    # per call: the block's v box of (8 + 2x3) x (24 + 2x8) rows, its u
    # core (stages 2 and 3) and core write of 8x24 rows; the margin
    # writes of the first and last y-blocks (here the one block), 2 x
    # 8x8 rows per z-block. Three calls per stage (interior, bottom,
    # top), one z-block and one y-block each; every row is 128 lanes of
    # f32
    box, core, margins = 14 * 40, 8 * 24, 2 * 8 * 8
    stage1 = 3 * (box + core + margins)
    stage23 = 3 * (box + 2 * core + margins)
    plan = (stage1 + 2 * stage23) * 128 * F32
    assert eng["hbm_bytes_per_step"] == fused.hbm_bytes_per_step == plan
    # each stage sends 3 planes of 40x128 to each z-neighbour
    halo = 3 * 2 * 3 * 40 * 128 * F32
    assert eng["halo_bytes_per_step"] == fused.halo_bytes_per_step == halo

    stats, recorded = _one_step(tmp_path, solver)
    assert sum(moved) == 4 * plan  # what the four shards' kernels moved
    assert recorded == halo  # the exchange sites' own counter
    assert eng["y_dead_rows"] == 0
    assert stats == {"hbm_bytes_per_step": plan,
                     "halo_bytes_per_step": halo, "block": "8x24",
                     "y_dead_rows": 0}


def test_sharded_diffusion_plan(tmp_path, devices):
    """The serialized-refresh diffusion schedule on a dz=4 mesh: its
    block, its planes, and the exchange of every stage plus the one
    refresh of the freshly embedded buffer each run makes."""
    grid = Grid.make(24, 16, 32, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_stage"),
        **_dz4(devices))
    eng = solver.engaged_path()
    assert eng["overlap"] == "serialized-refresh"
    fused = solver._fused_stepper()
    (bz,) = fused.block
    assert eng["block"] == str(bz) and 8 % bz == 0
    assert fused.padded_shape == (12, 24, 128)  # R = 2 on each side
    plane = 24 * 128 * F32
    # v: 8 + 2R planes a stage; u: 8 in stages 2, 3; writes: 8 a stage
    assert eng["hbm_bytes_per_step"] == (3 * 12 + 2 * 8 + 3 * 8) * plane
    halo = 3 * 2 * 2 * plane
    assert eng["halo_bytes_per_step"] == fused.halo_bytes_per_step == halo

    stats, recorded = _one_step(tmp_path, solver)
    assert recorded == halo + halo // 3
    # the profiler reads a numeric string stat back as a number
    assert stats == {"hbm_bytes_per_step": eng["hbm_bytes_per_step"],
                     "halo_bytes_per_step": halo, "block": bz}


def test_unsharded_steppers_send_no_halo():
    grid = Grid.make(16, 16, 16, lengths=2.0)
    solver = BurgersSolver(BurgersConfig(grid=grid, nu=0.0, dtype="float32",
                                         adaptive_dt=False,
                                         impl="pallas_stage"))
    eng = solver.engaged_path()
    assert eng["stepper"] == "fused-stage"
    assert "halo_bytes_per_step" not in eng
    assert solver._fused_stepper().halo_bytes_per_step is None
    bz, by = solver._fused_stepper().block
    assert eng["block"] == f"{bz}x{by}"
