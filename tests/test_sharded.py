"""Distributed-runtime tests on the simulated 8-device CPU mesh.

The key property the reference could never test (SURVEY §4: no fake
backend, ``MPIDeviceCheck`` exits without >= 2 physical GPUs): a sharded
run must be **bit-identical** to the unsharded run — the halo exchange
(``lax.ppermute``), global-edge BC fix-up, and ``pmax`` CFL reduction may
not change a single ulp.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from multigpu_advectiondiffusion_tpu import (
    BurgersConfig,
    BurgersSolver,
    DiffusionConfig,
    DiffusionSolver,
    Grid,
)
from multigpu_advectiondiffusion_tpu.parallel.mesh import Decomposition, make_mesh

from f32_bound import assert_f32_close


def _max_abs_diff(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# WENO sharded-vs-unsharded bound: the single-division weight form
# (ops/weno.py _weno5_alphas_unnormalized) is a chain of multiplies whose
# FMA contraction XLA chooses per program shape, so shard-local and
# global compilations may round differently by a few ulps per step, and
# the nonlinear weights compound that over the 5-step runs below
# (measured: ~11 ulps at step 5). Diffusion stays exactly bit-identical
# (its linear stencil leaves XLA no such freedom). float64 eps because
# every WENO config below runs dtype="float64"; the float32 analog lives
# in test_multihost.py.
_WENO_ULPS = 32 * np.finfo(np.float64).eps


@pytest.mark.parametrize(
    "mesh_axes,decomp_map",
    [
        ({"dz": 4}, {0: "dz"}),  # reference-style slab over z
        ({"dz": 2, "dy": 2}, {0: "dz", 1: "dy"}),  # 2-D pencils
        ({"dz": 2, "dy": 2, "dx": 2}, {0: "dz", 1: "dy", 2: "dx"}),  # 3-D blocks
    ],
)
def test_diffusion3d_sharded_bit_identical(devices, mesh_axes, decomp_map):
    grid = Grid.make(24, 24, 24, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float64")
    mesh = make_mesh(mesh_axes)
    ref = DiffusionSolver(cfg).run(DiffusionSolver(cfg).initial_state(), 10)
    solver = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.of(decomp_map))
    out = solver.run(solver.initial_state(), 10)
    assert _max_abs_diff(ref.u, out.u) == 0.0


@pytest.mark.parametrize("variant", ["js", "z"])
def test_burgers3d_sharded_bit_identical(devices, variant):
    """Adaptive dt: the global max|u| reduction must also agree (pmax)."""
    grid = Grid.make(16, 16, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, weno_variant=variant, nu=1e-5, dtype="float64")
    mesh = make_mesh({"dz": 2, "dy": 2})
    ref = BurgersSolver(cfg).run(BurgersSolver(cfg).initial_state(), 5)
    solver = BurgersSolver(
        cfg, mesh=mesh, decomp=Decomposition.of({0: "dz", 1: "dy"})
    )
    out = solver.run(solver.initial_state(), 5)
    assert _max_abs_diff(ref.u, out.u) <= _WENO_ULPS
    # adaptive dt inherits the state's few-ulp freedom through the CFL
    # max, so the accumulated t may differ in the last ulp or two —
    # demand ulp-level, not bit-level, agreement
    assert abs(float(ref.t) - float(out.t)) <= (
        8 * np.finfo(np.float64).eps * max(1.0, abs(float(ref.t)))
    )


def test_burgers3d_weno7_sharded(devices):
    """3-D WENO7 (halo 4) under a pencil mesh on the generic path: the
    4-deep ppermute exchange must reproduce the unsharded trajectory
    (adaptive dt, so the pmax reduction is exercised too)."""
    grid = Grid.make(16, 16, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, weno_order=7, nu=1e-5, dtype="float64")
    mesh = make_mesh({"dz": 2, "dy": 2})
    ref = BurgersSolver(cfg).run(BurgersSolver(cfg).initial_state(), 5)
    solver = BurgersSolver(
        cfg, mesh=mesh, decomp=Decomposition.of({0: "dz", 1: "dy"})
    )
    out = solver.run(solver.initial_state(), 5)
    assert _max_abs_diff(ref.u, out.u) <= _WENO_ULPS
    # adaptive dt inherits the state's few-ulp freedom through the CFL
    # max, so the accumulated t may differ in the last ulp or two —
    # demand ulp-level, not bit-level, agreement
    assert abs(float(ref.t) - float(out.t)) <= (
        8 * np.finfo(np.float64).eps * max(1.0, abs(float(ref.t)))
    )


def test_burgers2d_sharded_innermost_axis(devices):
    """Sharding the x (innermost/lane) axis exercises the awkward sweep."""
    grid = Grid.make(32, 32, lengths=2.0)
    cfg = BurgersConfig(grid=grid, dtype="float64")
    mesh = make_mesh({"dx": 4})
    ref = BurgersSolver(cfg).run(BurgersSolver(cfg).initial_state(), 5)
    solver = BurgersSolver(cfg, mesh=mesh, decomp=Decomposition.of({1: "dx"}))
    out = solver.run(solver.initial_state(), 5)
    assert _max_abs_diff(ref.u, out.u) <= _WENO_ULPS


def test_periodic_sharded(devices):
    grid = Grid.make(32, 32, lengths=2.0)
    cfg = BurgersConfig(grid=grid, bc="periodic", dtype="float64")
    mesh = make_mesh({"dy": 4})
    ref = BurgersSolver(cfg).run(BurgersSolver(cfg).initial_state(), 5)
    solver = BurgersSolver(cfg, mesh=mesh, decomp=Decomposition.of({0: "dy"}))
    out = solver.run(solver.initial_state(), 5)
    assert _max_abs_diff(ref.u, out.u) <= _WENO_ULPS


def test_sharded_output_sharding_preserved(devices):
    grid = Grid.make(24, 24, 24, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32")
    mesh = make_mesh({"dz": 8})
    solver = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.slab("dz"))
    out = solver.run(solver.initial_state(), 3)
    assert out.u.sharding.is_equivalent_to(solver.sharding(), grid.ndim)


def test_axisymmetric_sharded_r_axis(devices):
    """Sharding r exercises the 1/r local-window slice (diffusion.py)."""
    grid = Grid.make(32, 32, bounds=[(-4.0, 4.0), (-4.0, 4.0)])
    cfg = DiffusionConfig(
        grid=grid, geometry="axisymmetric", diffusivity=0.5, t0=1.0,
        bc=("edge", "dirichlet"), dtype="float64",
    )
    mesh = make_mesh({"dr": 4})
    ref = DiffusionSolver(cfg).run(DiffusionSolver(cfg).initial_state(), 5)
    solver = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.of({1: "dr"}))
    out = solver.run(solver.initial_state(), 5)
    assert _max_abs_diff(ref.u, out.u) == 0.0


def test_hybrid_mesh_single_slice_runs_sharded_step():
    """hybrid_mesh with a trivial DCN extent must build a usable mesh on
    platforms without slice topology (the virtual-CPU rig) and drive the
    sharded solver exactly like make_mesh."""
    from multigpu_advectiondiffusion_tpu.parallel.multihost import hybrid_mesh

    mesh = hybrid_mesh({"dz": 8}, {"dz_dcn": 1})
    assert mesh.axis_names == ("dz_dcn", "dz")
    assert dict(mesh.shape) == {"dz_dcn": 1, "dz": 8}

    grid = Grid.make(16, 16, 16, lengths=4.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32")
    ref = DiffusionSolver(cfg).run(DiffusionSolver(cfg).initial_state(), 3)
    sharded = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.slab("dz"))
    out = sharded.run(sharded.initial_state(), 3)
    # f32 + 2-cell shards: compiled-program FMA fusion may differ at ulp
    np.testing.assert_allclose(np.asarray(out.u), np.asarray(ref.u),
                               rtol=1e-6, atol=1e-7)


def test_hybrid_mesh_rejects_wrong_device_count():
    """A size mismatch must stay a loud error, not a silent
    subset-of-devices mesh."""
    from multigpu_advectiondiffusion_tpu.parallel.multihost import hybrid_mesh

    with pytest.raises(ValueError):
        hybrid_mesh({"dz": 4}, {"dz_dcn": 1})  # 4 != the rig's 8 devices


def test_hybrid_mesh_multi_slice_unavailable_raises_cleanly():
    """With a real DCN extent on a platform without slice/process
    topology the failure must be a ValueError, not an attribute crash."""
    from multigpu_advectiondiffusion_tpu.parallel.multihost import hybrid_mesh

    with pytest.raises(ValueError):
        hybrid_mesh({"dz": 4}, {"dz_dcn": 2})


@pytest.mark.parametrize(
    "mesh_axes,decomp_map",
    [
        ({"dz": 4}, {0: "dz"}),
        ({"dz": 2, "dy": 2}, {0: "dz", 1: "dy"}),
    ],
)
def test_diffusion3d_split_overlap_bit_identical(devices, mesh_axes,
                                                 decomp_map):
    """overlap='split' (interior concurrent with in-flight ghost
    collectives, bands patched after) must be bitwise equal to the
    padded schedule AND to the unsharded run at ulp level — same
    stencil over the same values; only FMA-fusion choices may differ
    between the two compiled programs."""
    grid = Grid.make(24, 24, 24, lengths=10.0)
    mesh = make_mesh(mesh_axes)
    ref = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float64")
    )
    ref_out = ref.run(ref.initial_state(), 10)
    split = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float64", overlap="split"),
        mesh=mesh, decomp=Decomposition.of(decomp_map),
    )
    out = split.run(split.initial_state(), 10)
    scale = float(jnp.max(jnp.abs(ref_out.u)))
    assert _max_abs_diff(ref_out.u, out.u) <= 4 * np.finfo(np.float64).eps * scale


def test_burgers3d_split_overlap_matches_padded(devices):
    """Split schedule for the WENO sweeps + viscous Laplacian under an
    adaptive-dt sharded run (pmax reduction in the loop)."""
    grid = Grid.make(16, 16, 16, lengths=4.0)
    mesh = make_mesh({"dz": 4})
    outs = {}
    for overlap in ("padded", "split"):
        cfg = BurgersConfig(grid=grid, nu=1e-4, dtype="float64",
                            ic="gaussian", overlap=overlap)
        s = BurgersSolver(cfg, mesh=mesh, decomp=Decomposition.slab("dz"))
        outs[overlap] = s.run(s.initial_state(), 6)
    scale = float(jnp.max(jnp.abs(outs["padded"].u)))
    assert _max_abs_diff(outs["padded"].u, outs["split"].u) <= (
        16 * np.finfo(np.float64).eps * scale
    )
    np.testing.assert_allclose(float(outs["padded"].t),
                               float(outs["split"].t), rtol=1e-14)


def test_split_overlap_tiny_shard_falls_back(devices):
    """Shards narrower than 2 x halo take the unsplit path inside
    split_axis_apply and still match the padded schedule."""
    # 8 cells over 4 shards -> 2 cells/shard < 2*r for the O4 Laplacian
    # halo of 2? (2*2=4 > 2) -> fallback branch exercised
    grid = Grid.make(12, 12, 8, lengths=4.0)
    mesh = make_mesh({"dz": 4})
    outs = {}
    for overlap in ("padded", "split"):
        cfg = DiffusionConfig(grid=grid, dtype="float64", overlap=overlap)
        s = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.slab("dz"))
        outs[overlap] = s.run(s.initial_state(), 4)
    scale = float(jnp.max(jnp.abs(outs["padded"].u)))
    assert _max_abs_diff(outs["padded"].u, outs["split"].u) <= (
        4 * np.finfo(np.float64).eps * scale
    )


def test_sharded_pallas_impl_matches_xla(devices):
    """Sharded runs with impl='pallas' (per-axis VMEM kernels fed by
    ppermute halos inside shard_map) must match the sharded XLA path."""
    grid = Grid.make(24, 16, 16, lengths=4.0)
    mesh = make_mesh({"dz": 4})
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl=impl)
        s = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.slab("dz"))
        outs[impl] = np.asarray(s.run(s.initial_state(), 4).u)
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "mesh_axes,decomp_map",
    [
        ({"dz": 4}, {0: "dz"}),  # reference-style z slabs
        ({"dz": 2, "dy": 2}, {0: "dz", 1: "dy"}),  # pencils
        ({"dz": 2, "dy": 2, "dx": 2}, {0: "dz", 1: "dy", 2: "dx"}),  # blocks
    ],
)
def test_fused_diffusion_sharded_bit_identical_to_unsharded_fused(
    devices, mesh_axes, decomp_map
):
    """The fused per-stage Pallas stepper running shard-local inside
    shard_map (ppermute ghost refresh between stages, global wall masks
    via the offsets operand) must reproduce the single-device fused run:
    the same per-cell arithmetic over the same values, up to XLA-CPU's
    per-program FMA contraction (``f32_bound``)."""
    grid = Grid.make(24, 16, 16, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    ref_solver = DiffusionSolver(cfg)
    assert ref_solver._fused_stepper() is not None
    ref = ref_solver.run(ref_solver.initial_state(), 8)

    mesh = make_mesh(mesh_axes)
    solver = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.of(decomp_map))
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded
    out = solver.run(solver.initial_state(), 8)
    assert_f32_close(out.u, ref.u, what="sharded vs unsharded fused")


def test_fused_diffusion_sharded_minimal_shards(devices):
    """2-cell shards: every shard is the minimum that can serve the O4
    halo, and the edge shards lie entirely inside the frozen boundary
    band — the offsets operand must keep those global-index decisions
    right."""
    grid = Grid.make(16, 16, 16, lengths=4.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    ref_solver = DiffusionSolver(cfg)
    ref = ref_solver.run(ref_solver.initial_state(), 4)
    mesh = make_mesh({"dz": 8})
    solver = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.slab("dz"))
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded
    out = solver.run(solver.initial_state(), 4)
    assert_f32_close(out.u, ref.u, what="minimal shards vs unsharded")


def test_cli_style_pallas_step_on_burgers_falls_back():
    """A global --impl pallas_step applied to Burgers must run the
    per-axis pallas kernels, not crash in the WENO dispatcher."""
    grid = Grid.make(16, 12, 12, lengths=4.0)
    cfg = BurgersConfig(grid=grid, ic="gaussian", impl="pallas_step",
                        adaptive_dt=True)
    s = BurgersSolver(cfg)
    out = s.run(s.initial_state(), 2)
    assert np.isfinite(np.asarray(out.u)).all()
