"""The four-chip Burgers cell (``burgers3d_weno5_dz4.march``) at tiny
grids on the CPU: its entries keep the contract, its plain reference
agrees with the program and partitions over a z-slab mesh, and the
harness runs it through the chip cell's schedule and block with
``correct`` true."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench_testlib import ROOT, run_cell, tiny_tree

from benchmark import compare, inputs
from benchmark.spec import Benchmark, load_plugin

CELL = "burgers3d_weno5_dz4.march"
CONFIG = "burgers3d_weno5_dz4"
# four 24-plane shards of 8x16 planes: three z-blocks of 8 planes (the
# split-overlap schedule) and 8-row y blocks, as 1000-row planes get
TINY = [16, 8, 96]


def tiny_config(**fields) -> dict:
    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{CONFIG}.json")
                     .read_text())
    cfg.update({"grid_xyz": TINY, **fields})
    return cfg


def tiny_cell_tree(tmp_path):
    """The benchmark under ``tmp_path`` at tiny grids, this cell's too,
    with one step a chunk."""
    root = tiny_tree(tmp_path, chunk_steps=1)
    path = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(grid_xyz=TINY, chunk_steps=1)
    path.write_text(json.dumps(cfg))
    return root


def test_the_cell_keeps_the_contract():
    bench = Benchmark(str(ROOT))
    bench.validate()
    cell = bench.cell(CELL)
    cfg = cell.config
    assert cell.chips == 4 and cfg["mesh"] == {"dz": 4}
    assert cfg["reduced"] == [] and cfg["grid_xyz"] == [1000, 1000, 800]
    assert cfg["nu"] == 0 and cfg["cfl"] == 0.3 and cfg["fixed_dt"]
    assert {"physics", "lengths_xyz", "chunk_steps", "ic"} <= set(
        cfg["assumed"])
    # halo_ici_roofline is left out: under the split schedule a
    # permute's in-flight span covers the interior stage call
    assert {m["name"] for m in cell.per_layer} == {
        "step_hbm_roofline", "step_gflops", "device_idle_pct",
        "halo_exposed_pct", "compile_s", "dispatch_host_us",
        "dispatch_to_device_us"}


def test_reference_matches_the_generic_path():
    """Same input, 6 inviscid steps at CFL 0.3 on one device: the
    program's plain XLA rung and the reference agree to float32
    rounding, both moved the field, and the reference in bfloat16 fails
    the limit."""
    from multigpu_advectiondiffusion_tpu.models.state import SolverState

    cfg = tiny_config(grid_xyz=[16, 16, 16], impl="xla", mesh=None)
    system = load_plugin(str(ROOT), "systems", cfg["family"]).build(
        cfg, jax.devices()[:1])
    assert system.engaged_path()["stepper"] == "generic-xla"
    ref = load_plugin(str(ROOT), "reference", cfg["family"])
    u0 = inputs.initial_field(cfg, 2**40 + 5)
    got = system.run(SolverState.create(u0, 0.0), 6).u
    want = ref.march(u0, cfg, 6)
    limit = cfg["limits"]["max_rel_err"]
    assert compare.max_rel_err(got, want) < limit / 10
    assert compare.max_rel_err(u0, want) > 10 * limit
    low = ref.march(u0.astype(jnp.bfloat16), cfg, 6)
    assert compare.max_rel_err(low, want) > 3 * limit


def test_sharded_reference_equals_unsharded():
    cfg = tiny_config()
    ref = load_plugin(str(ROOT), "reference", cfg["family"])
    mesh = jax.make_mesh((4,), ("dz",), devices=jax.devices()[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    u0 = inputs.initial_field(cfg, 2**33 + 1)
    u0s = inputs.initial_field(cfg, 2**33 + 1, NamedSharding(mesh, P("dz")))
    assert len(u0s.sharding.device_set) == 4
    assert compare.max_rel_err(ref.march(u0s, cfg, 3),
                               ref.march(u0, cfg, 3)) < 1e-6


def test_the_sharded_fused_stepper_is_correct(tmp_path, monkeypatch,
                                              capsys):
    """The harness's run of the cell in interpret mode: the fused stage
    kernels under the split-overlap schedule with 8x8 blocks, as on the
    chip, and the result agrees with the reference."""
    from benchmark import harness

    engaged = []
    real_say = harness.Run.say
    monkeypatch.setattr(harness.Run, "say", staticmethod(
        lambda kind, **f: (engaged.append(f) if kind == "engaged" else None,
                           real_say(kind, **f))))
    rc, line, err = run_cell(tiny_cell_tree(tmp_path), CELL, monkeypatch,
                             capsys, seed=2**35 + 3, seconds=0.01)
    assert rc == 0, err
    (eng,) = engaged
    assert eng["stepper"] == "fused-stage" and eng["overlap"] == "split"
    assert eng["block"] == "8x8" and eng["fallback"] is None
    assert eng["halo_bytes_per_step"] > 0 and eng["hbm_bytes_per_step"] > 0
    assert line["correct"] is True and line["device"]["count"] == 4
    check = line["checks"]["max_rel_err"]
    assert 0 <= check["value"] <= check["limit"]
