"""Pallas-vs-XLA kernel equality.

The gate from SURVEY §7 step 2: the Pallas slab-pipelined kernels must
agree with the XLA shifted-slice reference implementation. On CPU the
kernels run in interpret mode; the same code path compiles via Mosaic on
TPU.
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
from multigpu_advectiondiffusion_tpu.core.bc import Boundary, pad_axis
from multigpu_advectiondiffusion_tpu.ops import flux as flux_lib
from multigpu_advectiondiffusion_tpu.ops.laplacian import laplacian
from multigpu_advectiondiffusion_tpu.ops.weno import flux_divergence


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("shape", [(16, 24), (8, 12, 32)])
def test_laplacian_pallas_matches_xla(shape):
    u = _field(shape)
    spacing = [0.1] * len(shape)
    bcs = [Boundary("dirichlet")] * len(shape)
    ref = laplacian(u, spacing, diffusivity=0.7, bcs=bcs, impl="xla")
    out = laplacian(u, spacing, diffusivity=0.7, bcs=bcs, impl="pallas")
    # f32 tolerance scaled to the field magnitude: the interpret-mode
    # kernel and the fused XLA loop associate/fuse differently.
    scale = float(np.max(np.abs(np.asarray(ref))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-6 * scale)


@pytest.mark.parametrize("ndim,axis", [(2, 0), (2, 1), (3, 0), (3, 1), (3, 2)])
@pytest.mark.parametrize("variant", ["js", "z"])
def test_weno_pallas_matches_xla(ndim, axis, variant):
    shape = {2: (16, 24), 3: (8, 12, 32)}[ndim]
    u = _field(shape, seed=axis)
    fx = flux_lib.burgers()
    bc = Boundary("edge")
    ref = flux_divergence(u, axis, 0.05, fx, variant=variant, bc=bc,
                          impl="xla")
    out = flux_divergence(u, axis, 0.05, fx, variant=variant, bc=bc,
                          impl="pallas")
    scale = float(np.max(np.abs(np.asarray(ref))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-6 * scale)


@pytest.mark.parametrize("ndim,axis", [(2, 0), (2, 1), (3, 0), (3, 1), (3, 2)])
def test_weno7_pallas_matches_xla(ndim, axis):
    """The per-axis Pallas rung now covers WENO7 (halo-4 sweeps — the
    deepest stress of the roll-based tiled-axis construction); every
    sweep axis must match the XLA WENO7 path."""
    shape = {2: (16, 24), 3: (10, 12, 32)}[ndim]
    u = _field(shape, seed=20 + axis)
    fx = flux_lib.burgers()
    bc = Boundary("edge")
    ref = flux_divergence(u, axis, 0.05, fx, order=7, bc=bc, impl="xla")
    out = flux_divergence(u, axis, 0.05, fx, order=7, bc=bc, impl="pallas")
    scale = float(np.max(np.abs(np.asarray(ref))))
    # WENO7 betas carry ~1e5-scale integer coefficients, so f32
    # cancellation noise between the roll- and slice-order evaluations
    # is a few ulp of the *field* scale at near-zero-divergence cells
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5 * scale)


def test_weno7_pallas_solver_end_to_end():
    """A WENO7 solver with impl='pallas_axis' pins the per-axis WENO7
    kernels (explicitly opting out of the fused stepper) and matches the
    XLA solver; impl='pallas' engages the fused WENO7 stepper in BOTH
    dimensions (3-D per-stage, 2-D whole-run — round 5); and a 2-D
    order-7 config too large for the whole-run VMEM budget declines to
    the per-op ladder with XLA winning (the per-axis WENO7 kernel
    measures ~2x slower at 512^3 — 'pallas' promises best-available)."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    outs = {}
    for impl in ("xla", "pallas_axis"):
        cfg = BurgersConfig(grid=grid, weno_order=7, cfl=0.3,
                            adaptive_dt=False, dtype="float32",
                            ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        assert solver._fused_stepper() is None
        st = solver.run(solver.initial_state(), 4)
        outs[impl] = np.asarray(st.u)
    scale = float(np.max(np.abs(outs["xla"])))
    np.testing.assert_allclose(outs["pallas_axis"], outs["xla"],
                               rtol=1e-4, atol=1e-6 * scale)

    auto = BurgersSolver(BurgersConfig(
        grid=grid, weno_order=7, dtype="float32", impl="pallas"))
    assert auto.engaged_path()["stepper"] == "fused-stage"

    flat = BurgersSolver(BurgersConfig(
        grid=Grid.make(32, 32, lengths=4.0), weno_order=7,
        dtype="float32", impl="pallas"))
    assert flat.engaged_path()["stepper"] == "fused-whole-run"

    big = BurgersSolver(BurgersConfig(
        grid=Grid.make(8192, 8192, lengths=4.0), weno_order=7,
        dtype="float32", impl="pallas"))
    path = big.engaged_path()
    assert path["stepper"] == "generic-xla"
    assert "pallas_axis" in path["fallback"]


def test_weno7_pallas_supported_gates():
    """WENO7 support: JS only (like the XLA path and the reference's
    MATLAB-only WENO7), 2-D/3-D, VMEM-gated with the larger live set."""
    from multigpu_advectiondiffusion_tpu.ops.pallas import weno as pw

    assert pw.supported(3, 7, "js", shape=(512, 512, 512))
    assert not pw.supported(3, 7, "z", shape=(64, 64, 64))
    assert pw.supported(2, 7, "js", shape=(400, 406))
    assert not pw.supported(1, 7, "js", shape=(1000,))


def test_pallas_impls_gate_non_f32_dtypes_to_xla():
    """Non-f32 dtypes under any pallas flavor dispatch the per-op path
    to XLA (the per-axis DMA/roll kernels are f32-calibrated and Mosaic
    has no f64 vector path — on TPU the kernel would fail in the
    compiler, not fall back), and the engaged path says so. The ONE
    exception since the slab-run round: 3-D diffusion f64 rides the
    fused f32 kernels through the f64-storage/f32-compute convention
    instead of losing the whole ladder."""
    grid = Grid.make(16, 12, 12, lengths=4.0)
    d = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float64", impl="pallas"))
    assert d._op_impl() == "xla"  # per-axis kernels stay f32-only
    p = d.engaged_path()
    assert p["stepper"] in ("fused-whole-run-slab", "fused-stage")
    # 2-D diffusion f64 has no storage rung: generic path, reason given
    d2 = DiffusionSolver(
        DiffusionConfig(grid=Grid.make(16, 12, lengths=4.0),
                        dtype="float64", impl="pallas"))
    p2 = d2.engaged_path()
    assert p2["stepper"] == "generic-xla" and "f64 storage" in p2["fallback"]
    b = BurgersSolver(
        BurgersConfig(grid=grid, dtype="float64", impl="pallas_axis"))
    assert b._op_impl() == "xla"
    assert "float32-only" in b.engaged_path()["fallback"]
    # f32 keeps the per-axis kernels
    b32 = BurgersSolver(
        BurgersConfig(grid=grid, dtype="float32", impl="pallas_axis"))
    assert b32._op_impl() == "pallas"


def test_laplacian_pallas_gates_vmem_exceeding_rows():
    """The 3-D block picker must size the z-block against VMEM, not a
    fixed 8: the reference's 1601x986x35 slab workload (6.6 MB rows)
    OOM'd the compiler at the old divisor-only default (bz=7) and is
    viable only at bz=1; rows too wide for even a 1-row block must be
    rejected to the XLA path."""
    from multigpu_advectiondiffusion_tpu.ops.pallas import laplacian as pl_lap

    row = pl_lap._aligned_row_bytes_3d((35, 986, 1601), 4)
    assert pl_lap.pick_vmem_block_3d(35, row) == 1
    assert pl_lap.supported((35, 986, 1601), 4, 4)
    # ~33 MB rows: no viable block at all -> XLA fallback
    assert not pl_lap.supported((35, 2000, 4000), 4, 4)
    # 512^2 trailing: bz=8 measured 105.1 MB (over the 100 MiB scope);
    # the picker must stop at 4
    row512 = pl_lap._aligned_row_bytes_3d((512, 512, 512), 4)
    assert pl_lap.pick_vmem_block_3d(512, row512) == 4
    assert pl_lap.supported((512, 512, 512), 4, 4)
    assert pl_lap.supported((160, 204, 508), 4, 4)


def test_weno_pallas_supported_at_flagship_grid():
    """The per-axis Pallas WENO kernel must accept the 512^3 benchmark
    grid (the one Burgers config with a published reference number,
    SingleGPU/Burgers3d_WENO5/Run.m:15-25) — the z-block shrinks against
    VMEM rather than rejecting large rows."""
    from multigpu_advectiondiffusion_tpu.ops.pallas import weno as pw

    for variant in ("js", "z"):
        assert pw.supported(3, 5, variant, shape=(512, 512, 512),
                            dtype=jnp.float32)
    # the flagship row size forces a small (but viable) z-block
    b = pw._pick_vmem_block(
        512, 6, pw._row_bytes((518, 512, 512), jnp.float32)
    )
    assert b is not None and 512 % b == 0


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_weno_pallas_explicit_multi_block(axis):
    """Force multiple leading-axis blocks (the flagship-grid regime) and
    check the blocked DMA path against XLA for every sweep axis —
    including the blocked axis itself (in-block halo)."""
    from multigpu_advectiondiffusion_tpu.core.bc import pad_axis
    from multigpu_advectiondiffusion_tpu.ops.pallas.weno import (
        flux_divergence_pallas,
    )

    shape = (12, 16, 32)
    u = _field(shape, seed=10 + axis)
    fx = flux_lib.burgers()
    bc = Boundary("edge")
    ref = flux_divergence(u, axis, 0.05, fx, bc=bc, impl="xla")
    up = pad_axis(u, axis, 3, bc)
    out = flux_divergence_pallas(up, axis, 0.05, fx, block=2)
    scale = float(np.max(np.abs(np.asarray(ref))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-6 * scale)


def test_impl_pallas_axis_pins_per_axis_kernels():
    """impl='pallas_axis' is the explicit per-axis-kernel rung: the fused
    steppers must NOT engage, and the physics must match XLA."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    outs = {}
    for impl in ("xla", "pallas_axis"):
        cfg = BurgersConfig(grid=grid, cfl=0.3, adaptive_dt=False,
                            dtype="float32", ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        assert solver._fused_stepper() is None
        st = solver.run(solver.initial_state(), 4)
        outs[impl] = np.asarray(st.u)
    scale = float(np.max(np.abs(outs["xla"])))
    np.testing.assert_allclose(outs["pallas_axis"], outs["xla"],
                               rtol=1e-4, atol=1e-6 * scale)

    dcfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas_axis")
    assert DiffusionSolver(dcfg)._fused_stepper() is None


def test_fused_diffusion_run_matches_xla():
    """The fused single-kernel-per-stage fast path (run() with
    impl='pallas' on an eligible config) must agree with the generic XLA
    path to f32 rounding across a multi-step run."""
    grid = Grid.make(24, 28, 36, lengths=10.0)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl=impl)
        solver = DiffusionSolver(cfg)
        if impl == "pallas":
            assert solver._fused_stepper() is not None, "fast path not taken"
        st = solver.run(solver.initial_state(), 9)
        outs[impl] = (np.asarray(st.u), float(st.t))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=1e-5, atol=1e-6)
    assert outs["pallas"][1] == outs["xla"][1]


@pytest.mark.parametrize("nz,block_z", [(23, None), (14, 4)])
def test_fused_diffusion_non_multiple_nz_pads_dead_rows(nz, block_z):
    """Unsharded fused diffusion pads z to a block multiple instead of
    shrinking the block to a divisor (a prime-ish nz like the literal
    reference grid's 206 would otherwise force a tiny block). The dead
    tail rows hold the Dirichlet value and stay frozen, so results match
    the XLA path exactly as for multiple sizes. nz=23 (prime, above no
    viable same-size block) and an explicit non-divisor block both force
    real dead rows — asserted, so the padding path cannot silently stop
    being exercised."""
    grid = Grid.make(24, 16, nz, lengths=2.0)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl=impl)
        solver = DiffusionSolver(cfg)
        if impl == "pallas":
            fused = solver._fused_stepper()
            assert fused is not None
            # the iters-mode selection is the slab whole-run stepper; the
            # cache key follows the rung
            key = (
                "fused_slab"
                if fused.engaged_label == "fused-whole-run-slab"
                else "fused"
            )
            if block_z is not None:
                fused = type(fused)(
                    grid.shape, solver.dtype, grid.spacing, [1.0] * 3,
                    solver.dt, 2, 0.0, block_z=block_z,
                )
                solver._cache[key] = fused
            # dead tail rows beyond the interior (halo is the stepper's
            # own fused-step/stage ghost depth)
            dead = fused.padded_shape[0] - 2 * fused.halo - nz
            assert dead > 0, "test must exercise the dead-row path"
        st = solver.run(solver.initial_state(), 6)
        outs[impl] = np.asarray(st.u)
    assert outs["pallas"].shape == outs["xla"].shape
    scale = float(np.max(np.abs(outs["xla"])))
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-5, atol=2e-6 * scale)


def test_fused_diffusion_advance_to_matches_xla():
    """Diffusion advance_to (the MATLAB heat drivers' native
    `while t < t_end` loop, heat3d.m:48-77) must engage the fused
    stepper's run_to — dt rides a runtime SMEM scalar so the same
    compiled stages serve the trimmed last step — and reproduce the
    generic path's trajectory, landing time, and step count."""
    grid = Grid.make(24, 28, 36, lengths=10.0)
    outs = {}
    t_end = None
    for impl in ("xla", "pallas"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl=impl)
        solver = DiffusionSolver(cfg)
        st0 = solver.initial_state()
        if t_end is None:
            t_end = float(st0.t) + 4.5 * solver.dt  # trimmed 5th step
        st = solver.advance_to(st0, t_end)
        if impl == "pallas":
            assert "fused_adv" in solver._cache, "fused t_end path not taken"
        outs[impl] = (np.asarray(st.u), float(st.t), int(st.it))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs["pallas"][1], t_end, rtol=1e-6)
    assert outs["pallas"][2] == outs["xla"][2] == 5


def _stage_stepper(solver, shape, block_z, dtype="float32", **kw):
    """A fused-stage stepper with a forced z-block, in the solver's
    cache slot, so ``solver.run`` marches with it."""
    from multigpu_advectiondiffusion_tpu.ops.pallas.fused_diffusion import (
        FusedDiffusionStepper,
    )

    assert solver._fused_stepper().engaged_label == "fused-stage"
    fused = FusedDiffusionStepper(
        shape, dtype, solver.grid.spacing, [1.0] * 3, solver.dt, 2, 0.0,
        block_z=block_z, **kw,
    )
    solver._cache["fused"] = fused
    return fused


def _xla_run(grid, steps, mesh=None, decomp=None):
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="xla"),
        mesh=mesh, decomp=decomp,
    )
    return np.asarray(solver.run(solver.initial_state(), steps).u)


@pytest.mark.parametrize("nz,block_z,dtype", [
    (9, 1, "float32"),  # one plane per block: the ring's 6 planes
    (14, 3, "float32"),  # 2R % bz != 0: shifted ring, one dead row
    (8, 8, "float32"),  # one block: the slab alone, no ring
    (14, 3, "bfloat16"),  # bf16 storage through the shifted ring
], ids=["bz1", "bz3-dead-row", "one-block", "bf16-bz3"])
def test_fused_diffusion_ring_blocks_match_xla(nz, block_z, dtype):
    """The stage kernel streams each ``v`` plane through its VMEM ring
    once, whatever the block: a plane per block, a block that does not
    divide 2R (shifted ring, dead tail row) and a single block all march
    as the generic XLA path does; bf16 storage stays within its storage
    price."""
    grid = Grid.make(24, 16, nz, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype=dtype, impl="pallas_stage")
    )
    fused = _stage_stepper(solver, grid.shape, block_z, dtype)
    nz_eff = fused.padded_shape[0] - 2 * fused.halo
    assert nz_eff == -(-nz // block_z) * block_z
    got = np.asarray(solver.run(solver.initial_state(), 6).u, np.float32)
    ref = _xla_run(grid, 6)
    scale = float(np.max(np.abs(ref)))
    if dtype == "bfloat16":
        # the bound of test_fused_diffusion_bf16_storage_rung
        assert float(np.max(np.abs(got - ref))) <= 0.05 * scale
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-6 * scale)


@pytest.mark.parametrize("overlap", ["padded", "split"])
def test_fused_diffusion_sharded_ring_blocks_match_xla(devices, overlap):
    """dz=2 shards of 30 planes in 5-plane blocks (a shifted ring): the
    serialized-refresh calls ring over all six blocks, the split
    schedule's interior call over its four, and its edge calls read
    their ghost planes from the exchanged slabs."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 60, lengths=2.0)
    mesh, decomp = make_mesh({"dz": 2}), Decomposition.slab("dz")
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_stage",
                        overlap=overlap),
        mesh=mesh, decomp=decomp,
    )
    fused = _stage_stepper(solver, (30, 16, 24), 5,
                           global_shape=grid.shape,
                           overlap_split=overlap == "split")
    assert fused.sharded and fused.overlap_split == (overlap == "split")
    got = np.asarray(solver.run(solver.initial_state(), 5).u)
    ref = _xla_run(grid, 5)
    scale = float(np.max(np.abs(ref)))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-6 * scale)


@pytest.mark.parametrize("nz,block_z,split", [
    (9, 1, False), (14, 3, False), (8, 8, False), (30, 5, True),
], ids=["bz1", "bz3", "one-block", "split-bz5"])
def test_fused_diffusion_stage_fetches_each_plane_once(
    devices, monkeypatch, nz, block_z, split
):
    """Counted DMA by DMA while one step runs (interpret mode): every
    stage call fetches each plane of its ``v`` slab range from HBM once
    — ``nz_eff + 2R`` planes for a whole-range call, plus the 2R ghost
    planes each edge call of the split schedule takes from the exchanged
    slabs — and the stepper's ``hbm_bytes_per_step`` is the sum of every
    DMA to or from HBM, ``u`` reads and output writes included."""
    import collections

    import jax
    from jax.experimental.pallas import tpu as pltpu

    planes = collections.Counter()
    real = pltpu.make_async_copy

    def space(ref):
        return str(getattr(ref, "ref", ref).aval.memory_space)

    def counted(src, dst, sem):
        copy = real(src, dst, sem)
        root = getattr(dst, "ref", dst)
        kind = ("write" if space(dst) == "any"
                else "v" if root.ndim == 3 else "u")
        n = src.shape[0]

        class Counted:
            def start(self):
                jax.debug.callback(lambda: planes.update({kind: n}))
                copy.start()

            def wait(self):
                copy.wait()

        return Counted()

    monkeypatch.setattr(pltpu, "make_async_copy", counted)
    shards = 2 if split else 1
    shape = (nz, 16, 24)
    kw = {}
    if split:
        from multigpu_advectiondiffusion_tpu.parallel.mesh import (
            Decomposition,
            make_mesh,
        )

        grid = Grid.make(24, 16, nz * shards, lengths=2.0)
        kw = {"mesh": make_mesh({"dz": shards}),
              "decomp": Decomposition.slab("dz")}
    else:
        grid = Grid.make(24, 16, nz, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_stage",
                        overlap="split" if split else "padded"),
        **kw,
    )
    fused = _stage_stepper(
        solver, shape, block_z,
        **({"global_shape": grid.shape, "overlap_split": True}
           if split else {}),
    )
    assert fused.overlap_split == split
    solver.run(solver.initial_state(), 1).u.block_until_ready()

    R = fused.halo
    nz_eff = fused.padded_shape[0] - 2 * R
    ghost = 4 * R if split else 0  # two edge calls' own 2R slabs
    assert planes["v"] == shards * 3 * (nz_eff + 2 * R + ghost)
    assert planes["u"] == shards * 2 * nz_eff
    assert planes["write"] == shards * 3 * nz_eff
    plane_bytes = (fused.padded_shape[1] * fused.padded_shape[2]
                   * fused.dtype.itemsize)
    assert fused.hbm_bytes_per_step == (
        sum(planes.values()) // shards * plane_bytes)


def test_fused_diffusion_split_overlap_matches_serialized(devices):
    """overlap='split' diffusion on a z-slab mesh runs the three-call
    overlapped schedule (interior blocks concurrent with the z-halo
    ppermute) — matching both the serialized-refresh fused path and the
    generic XLA path, in run() and the fused run_to. Match: the
    reference's five-stream choreography around its tuned kernel
    (MultiGPU/Diffusion3d_Baseline/main.c:203-260, Kernels.cu:207-261)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 120, lengths=2.0)  # local lz=60 -> 3 blocks
    outs = {}
    for overlap in ("split", "padded"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                              overlap=overlap)
        solver = DiffusionSolver(
            cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded
        assert fused.overlap_split == (overlap == "split")
        st = solver.run(solver.initial_state(), 5)
        outs[overlap] = np.asarray(st.u)
    scale = float(np.max(np.abs(outs["padded"])))
    np.testing.assert_allclose(outs["split"], outs["padded"],
                               rtol=1e-6, atol=1e-7 * scale)

    # run_to on the split path: step count + trajectory vs unsharded
    scfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                           overlap="split")
    ss = DiffusionSolver(
        scfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
    )
    st0 = ss.initial_state()
    t_end = float(st0.t) + 3.4 * ss.dt
    out = ss.advance_to(st0, t_end)
    assert "fused_adv" in ss._cache
    ref_solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    )
    ref = ref_solver.advance_to(ref_solver.initial_state(), t_end)
    assert int(out.it) == int(ref.it) == 4
    np.testing.assert_allclose(
        np.asarray(out.u), np.asarray(ref.u), rtol=1e-6, atol=1e-7 * scale
    )


def test_fused_diffusion_advance_to_sharded_pencil(devices):
    """Diffusion run_to on a (dz, dy) pencil mesh exercises the
    serialized-refresh sharded path (pencils can't use split-overlap,
    which is z-slab-only) with the offsets operand — bit-identical to
    the unsharded fused advance_to with the same step count."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 48, lengths=2.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                          overlap="split")  # split requested, pencil denies
    ref_solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    )
    st0 = ref_solver.initial_state()
    t_end = float(st0.t) + 3.4 * ref_solver.dt
    ref = ref_solver.advance_to(st0, t_end)
    solver = DiffusionSolver(
        cfg, mesh=make_mesh({"dz": 2, "dy": 2}),
        decomp=Decomposition.of({0: "dz", 1: "dy"}),
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded and not fused.overlap_split
    out = solver.advance_to(solver.initial_state(), t_end)
    assert "fused_adv" in solver._cache
    assert int(out.it) == int(ref.it) == 4
    np.testing.assert_array_equal(np.asarray(out.u), np.asarray(ref.u))


def test_fused_diffusion_ineligible_configs_fall_back():
    """Configs outside the fused kernel's assumptions must quietly use
    the generic path (and still run)."""
    grid = Grid.make(16, 16, 16, lengths=10.0)
    for kw in (
        {"integrator": "ssp_rk2"},
        {"bc": "periodic", "ic": "gaussian"},
        {"reference_parity": False},
        {"order": 2},
        {"boundary_band": 0},
    ):
        cfg = DiffusionConfig(grid=grid, impl="pallas", **kw)
        solver = DiffusionSolver(cfg)
        assert solver._fused_stepper() is None, kw
        solver.run(solver.initial_state(), 2)


def test_diffusion_solver_pallas_impl():
    grid = Grid.make(32, 24, 16, lengths=10.0)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl=impl)
        solver = DiffusionSolver(cfg)
        outs[impl] = np.asarray(solver.run(solver.initial_state(), 5).u)
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-5, atol=1e-6)


def test_burgers_solver_pallas_impl():
    grid = Grid.make(32, 16, lengths=2.0)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32", impl=impl)
        solver = BurgersSolver(cfg)
        outs[impl] = np.asarray(solver.run(solver.initial_state(), 5).u)
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "kw",
    [
        {},
        {"weno_variant": "z"},
        {"nu": 1e-3},
        {"flux": "linear"},
        {"flux": "buckley"},
        {"weno_order": 7},
        {"weno_order": 7, "nu": 1e-3},
    ],
    ids=["js", "z", "viscous", "linear", "buckley", "weno7",
         "weno7-viscous"],
)
def test_fused_burgers_run_matches_xla(kw):
    """The fused single-kernel-per-stage Burgers fast path (run() with
    impl='pallas' on an eligible 3-D fixed-dt config) must agree with the
    generic XLA path to f32 rounding across a multi-step run."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, cfl=0.3, adaptive_dt=False,
                            dtype="float32", ic="gaussian", impl=impl, **kw)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            assert solver._fused_stepper() is not None, "fast path not taken"
        st = solver.run(solver.initial_state(), 5)
        outs[impl] = (np.asarray(st.u), float(st.t))
    scale = float(np.max(np.abs(outs["xla"][0])))
    # WENO7's ~1e5-scale beta coefficients amplify f32 reassociation
    # noise between the e-form kernel and the q-form XLA path (same
    # reasoning as test_weno7_pallas_matches_xla), so order 7 carries a
    # wider — still rounding-level — band
    atol = (2e-6 if kw.get("weno_order", 5) == 5 else 3e-5) * scale
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5, atol=atol)
    assert outs["pallas"][1] == outs["xla"][1]


def test_fused_burgers_adaptive_dt_matches_xla():
    """Adaptive dt on the fused path: the runtime SMEM dt scalar (global
    max|f'(u)| reduction between fused steps) must reproduce the generic
    path's trajectory AND its time axis (restored correct CFL — the
    reference hard-codes max|u|=1, Burgers3d_Baseline/main.c:193)."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, cfl=0.3, adaptive_dt=True, nu=1e-5,
                            dtype="float32", ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            assert solver._fused_stepper() is not None, "fast path not taken"
        st = solver.run(solver.initial_state(), 5)
        outs[impl] = (np.asarray(st.u), float(st.t))
    scale = float(np.max(np.abs(outs["xla"][0])))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1], rtol=1e-5)


# Sharded-vs-unsharded fused equality bound: the kernels are identical
# in both worlds, but in interpret mode (CPU tests) the kernel body is
# compiled by XLA as ordinary ops, and XLA's per-program FMA-contraction
# freedom perturbs the WENO nonlinear weights by a few ulp between the
# two programs (same phenomenon as test_sharded._WENO_ULPS; on real TPU
# the Mosaic-compiled kernel is one artifact with no such freedom).
# Relative bound, f32.
_FUSED_WENO_ULPS = 32 * np.finfo(np.float32).eps


def _assert_fused_close(actual, desired):
    a, d = np.asarray(actual), np.asarray(desired)
    scale = max(float(np.max(np.abs(d))), 1e-30)
    assert float(np.max(np.abs(a - d))) / scale <= _FUSED_WENO_ULPS


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_sharded_matches_unsharded_fused(
    devices, adaptive
):
    """The fused Burgers stepper shard-local under shard_map (ppermute
    ghost refresh between stages, pmax dt reduction) must reproduce the
    single-device fused run to the documented interpret-mode ulp bound —
    the tuned kernel under the mesh, as the reference runs its tuned
    kernels under MPI (MultiGPU/Burgers3d_Baseline/main.c:189-317)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        adaptive_dt=adaptive, impl="pallas")
    ref_solver = BurgersSolver(cfg)
    assert ref_solver._fused_stepper() is not None
    ref = ref_solver.run(ref_solver.initial_state(), 5)
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded, "sharded fast path not taken"
    out = solver.run(solver.initial_state(), 5)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)


@pytest.mark.parametrize("order", [5, 7], ids=["weno5", "weno7"])
@pytest.mark.parametrize("flux", ["linear", "buckley"])
def test_fused_burgers3d_generic_flux_matches_xla(flux, order):
    """The 3-D fused kernel's generic Lax-Friedrichs split (any Flux,
    not just the Burgers-specialized identity) plus the emitted
    max|f'(u)| for a non-identity df must match the XLA path — only the
    2-D whole-run stepper covered non-Burgers fluxes before. Both
    orders: the split and the emission are shared across the radius-
    parameterized family, and this pins that for halo 4 too."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, flux=flux, weno_order=order,
                            cfl=0.3, dtype="float32",
                            ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            fused = solver._fused_stepper()
            assert fused is not None and fused._emit_max
        st = solver.run(solver.initial_state(), 4)
        outs[impl] = (np.asarray(st.u), float(st.t))
    scale = float(np.max(np.abs(outs["xla"][0])))
    # order 7 carries the wider e-form/q-form rounding band of the
    # adaptive weno7-vs-XLA tests (dt feeds the gap back per step)
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5,
                               atol=(2e-6 if order == 5 else 6e-5) * scale)
    np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1], rtol=1e-6)


def test_fused_burgers_adaptive_emits_wave_speed_in_kernel(devices):
    """Adaptive runs emit max|f'(u_next)| from the final stage kernel(s)
    — no between-step HBM re-read (measured: the adaptive row closes to
    ~0.4% of the fixed-dt rate); fixed-dt runs don't build the machinery
    at all. The trajectory equality vs XLA/sharded/split is covered by
    the adaptive tests above — dt comes from the same max, so the
    chains are identical."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 24, lengths=2.0)
    adaptive = BurgersSolver(BurgersConfig(
        grid=grid, nu=1e-5, dtype="float32", impl="pallas"))
    assert adaptive._fused_stepper()._emit_max
    fixed = BurgersSolver(BurgersConfig(
        grid=grid, nu=1e-5, dtype="float32", adaptive_dt=False,
        impl="pallas"))
    assert not fixed._fused_stepper()._emit_max
    # sharded serialized refresh: emission works (local max, pmax in
    # dt_from_max) — execution equality is in the sharded adaptive tests
    sh = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, dtype="float32", impl="pallas"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"))
    assert sh._fused_stepper()._emit_max
    # split overlap emits too: the three stage-3 calls each fold their
    # own blocks, combined by two scalar maxes in the step
    grid_s = Grid.make(16, 16, 48, lengths=2.0)
    sp = BurgersSolver(
        BurgersConfig(grid=grid_s, nu=1e-5, dtype="float32",
                      impl="pallas", overlap="split"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"))
    f = sp._fused_stepper()
    assert f.overlap_split and f._emit_max


@pytest.mark.parametrize("ny", [14, 19])
def test_fused_burgers_non_multiple_ny_rounds_with_dead_columns(ny):
    """Unsharded fused Burgers rounds y up to the sublane tile instead of
    rejecting unaligned extents (the reference's 1601x986x35 workload);
    the dead columns are re-filled as edge replicas every stage, so
    results match XLA. Dead columns must actually exist or the path is
    untested."""
    grid = Grid.make(24, ny, 16, lengths=2.0)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                            adaptive_dt=True, impl=impl)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            fused = solver._fused_stepper()
            assert fused is not None
            assert fused.padded_shape[1] - 16 > ny, "need dead y columns"
        st = solver.run(solver.initial_state(), 5)
        outs[impl] = np.asarray(st.u)
    assert outs["pallas"].shape == outs["xla"].shape
    scale = float(np.max(np.abs(outs["xla"])))
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=2e-5, atol=2e-6 * scale)


def test_fused_burgers_y_rounding_composes_with_z_sharding(devices):
    """y-rounding is legal when the y axis is NOT sharded: a z-slab
    decomposition never ships y columns as ghosts, so an unaligned ny
    may still take the fused path — matching the unsharded fused run to
    the documented interpret-mode ulp bound. (A y-sharded unaligned ny
    falls back instead.)"""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 14, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        adaptive_dt=True, impl="pallas")
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded
    out = solver.run(solver.initial_state(), 5)
    ref_solver = BurgersSolver(cfg)
    ref = ref_solver.run(ref_solver.initial_state(), 5)
    _assert_fused_close(out.u, ref.u)

    # y-sharded + unaligned ny must NOT take the fused path
    ysolver = BurgersSolver(
        cfg, mesh=make_mesh({"dy": 2}), decomp=Decomposition.of({1: "dy"})
    )
    assert ysolver._fused_stepper() is None


def test_fused_burgers_ineligible_configs_fall_back():
    """Configs outside the fused Burgers kernel's assumptions must
    quietly use the generic path (and still run)."""
    grid = Grid.make(16, 16, 16, lengths=4.0)
    for kw in (
        {"dtype": "float64"},
        # order 7 is fused-eligible since round 5; f64 still declines it
        {"weno_order": 7, "dtype": "float64"},
        {"integrator": "ssp_rk2"},
        {"bc": "periodic"},
        {"nu": 1e-3, "laplacian_order": 2},
    ):
        cfg = BurgersConfig(grid=grid, ic="gaussian", impl="pallas",
                            **{"adaptive_dt": False, **kw})
        solver = BurgersSolver(cfg)
        assert solver._fused_stepper() is None, kw
        solver.run(solver.initial_state(), 2)
    # adaptive dt is a fused-eligible config (runtime SMEM dt + global
    # max|f'(u)| reduction between steps) — no longer a fallback case
    cfg = BurgersConfig(grid=grid, ic="gaussian", impl="pallas",
                        adaptive_dt=True)
    assert BurgersSolver(cfg)._fused_stepper() is not None


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_advance_to_matches_xla(adaptive):
    """advance_to (the reference Burgers drivers' *native* `while
    (t < tEnd)` mode, MultiGPU/Burgers3d_Baseline/main.c:190-317) must
    engage the fused stepper's run_to and reproduce the generic path's
    trajectory, landing time, and step count."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    # ~4.5 generic steps at this CFL: exercises the trimmed last step
    t_end = 0.05
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, cfl=0.3, adaptive_dt=adaptive,
                            nu=1e-5, dtype="float32", ic="gaussian",
                            impl=impl)
        solver = BurgersSolver(cfg)
        st = solver.advance_to(solver.initial_state(), t_end)
        if impl == "pallas":
            assert "fused_adv" in solver._cache, "fused t_end path not taken"
        outs[impl] = (np.asarray(st.u), float(st.t), int(st.it))
    scale = float(np.max(np.abs(outs["xla"][0])))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(outs["pallas"][1], t_end, rtol=1e-6)
    assert outs["pallas"][2] == outs["xla"][2] > 0


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_advance_to_sharded_matches_unsharded(devices, adaptive):
    """Fused run_to shard-local under shard_map (ppermute ghost refresh,
    pmax dt) must reproduce the single-device fused advance_to
    bit-for-bit, with the same step count."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        adaptive_dt=adaptive, impl="pallas")
    t_end = 0.01
    ref_solver = BurgersSolver(cfg)
    ref = ref_solver.advance_to(ref_solver.initial_state(), t_end)
    assert "fused_adv" in ref_solver._cache
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded
    out = solver.advance_to(solver.initial_state(), t_end)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)
    assert int(out.it) == int(ref.it) > 0


@pytest.mark.parametrize("order", [5, 7], ids=["weno5", "weno7"])
@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_xsharded_matches_unsharded(devices, adaptive, order):
    """An x-sharded mesh engages the stored-x-ghost layout (interior at
    lane offset r, ppermute refresh rewriting real ghost lanes) instead
    of falling back to the generic path, and must reproduce the
    unsharded fused run — the lane-axis analog of the tuned-kernel-
    under-MPI property (SURVEY §2.1.5: decomposition on any axis)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 48, lengths=2.0)
    cfg = BurgersConfig(grid=grid, weno_order=order, nu=1e-5,
                        dtype="float32", adaptive_dt=adaptive,
                        impl="pallas")
    ref_solver = BurgersSolver(cfg)
    ref_fused = ref_solver._fused_stepper()
    assert ref_fused is not None and not ref_fused.x_sharded
    ref = ref_solver.run(ref_solver.initial_state(), 5)
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dx": 2}), decomp=Decomposition.of({2: "dx"})
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded and fused.x_sharded, (
        getattr(solver, "_fused_fallback", None)
    )
    assert fused.core_offsets[2] == fused.halo
    out = solver.run(solver.initial_state(), 5)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)


def test_fused_burgers_extent1_mesh_axis_still_engages_fused(devices):
    """An extent-1 mesh axis exchanges no ghosts, so it must not trip
    the y-rounding (or x-layout) eligibility gates: a {dz:4, dy:1} mesh
    with ly % 8 != 0 engages the fused stepper exactly like {dz:4}."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 50, 16, lengths=2.0)  # ly = 50, not 8-aligned
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32", impl="pallas")
    s = BurgersSolver(cfg, mesh=make_mesh({"dz": 4, "dy": 1}),
                      decomp=Decomposition.of({0: "dz", 1: "dy"}))
    fused = s._fused_stepper()
    assert fused is not None and not fused.x_sharded, (
        getattr(s, "_fused_fallback", None)
    )
    ref = BurgersSolver(cfg)
    r = ref.run(ref.initial_state(), 3)
    o = s.run(s.initial_state(), 3)
    _assert_fused_close(o.u, r.u)


def test_fused_burgers_xsharded_block_mesh_split_overlap(devices):
    """A {dz, dx} block mesh with overlap='split': the z halo rides the
    overlapped exchanged-slab schedule while the x ghosts (stored-x-ghost
    layout) keep the serialized per-stage refresh — and the exchanged z
    slabs must carry fresh x ghost lanes. Matches the all-serialized
    fused path and the unsharded run."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 48, lengths=2.0)
    unsharded = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, dtype="float32", impl="pallas")
    )
    ref = unsharded.run(unsharded.initial_state(), 5)
    outs = {}
    for overlap in ("split", "padded"):
        cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                            impl="pallas", overlap=overlap)
        solver = BurgersSolver(
            cfg,
            mesh=make_mesh({"dz": 2, "dx": 2}),
            decomp=Decomposition.of({0: "dz", 2: "dx"}),
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.x_sharded
        assert fused.overlap_split == (overlap == "split"), (
            overlap, getattr(solver, "_fused_fallback", None)
        )
        st = solver.run(solver.initial_state(), 5)
        outs[overlap] = np.asarray(st.u)
        np.testing.assert_allclose(float(st.t), float(ref.t), rtol=1e-6)
    _assert_fused_close(outs["split"], outs["padded"])
    _assert_fused_close(outs["split"], ref.u)


def test_fused_burgers_block_mesh_8dev_split_overlap(devices):
    """A full {dz:2, dy:2, dx:2} BLOCK mesh (all 8 virtual devices) with
    overlap='split': y_sharded AND x_sharded engage simultaneously under
    the split-overlap schedule — the z halo rides the exchanged-slab
    operands while BOTH the y ghosts and the stored-x-ghost lanes take
    the serialized per-stage refresh. This is the one decomposition the
    _split_overlap_requested gate accepts that had no coverage (ADVICE
    round 5). Must match the all-serialized fused path and the
    unsharded fused run."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    # local (24, 16, 24): z hosts a 3-block interior band (bz<=8), local
    # ly=16 is sublane-aligned (y_sharded), lx=24 >= halo
    grid = Grid.make(48, 32, 48, lengths=2.0)
    unsharded = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, dtype="float32", impl="pallas")
    )
    ref = unsharded.run(unsharded.initial_state(), 4)
    outs = {}
    for overlap in ("split", "padded"):
        cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                            impl="pallas", overlap=overlap)
        solver = BurgersSolver(
            cfg,
            mesh=make_mesh({"dz": 2, "dy": 2, "dx": 2}),
            decomp=Decomposition.of({0: "dz", 1: "dy", 2: "dx"}),
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded, (
            overlap, getattr(solver, "_fused_fallback", None)
        )
        assert fused.x_sharded
        assert fused.overlap_split == (overlap == "split"), (
            overlap, getattr(solver, "_fused_fallback", None)
        )
        st = solver.run(solver.initial_state(), 4)
        outs[overlap] = np.asarray(st.u)
        np.testing.assert_allclose(float(st.t), float(ref.t), rtol=1e-6)
    _assert_fused_close(outs["split"], outs["padded"])
    _assert_fused_close(outs["split"], ref.u)


def test_fused_diffusion_block_mesh_8dev_split_overlap(devices):
    """A full {dz:2, dy:2, dx:2} BLOCK mesh (all 8 virtual devices) with
    overlap='split' for DIFFUSION: the z halo rides the exchanged-slab
    operands while the y and x ghosts (stored on every axis for
    diffusion) take the serialized per-stage refresh. Completes the
    ADVICE r5 coverage of the _split_overlap_requested gate: the Burgers
    8-device block-mesh test pins the WENO side; this pins the O4
    stencil family on the same decomposition. Must match the
    all-serialized fused path and the unsharded fused run."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    # local (48, 8, 16): z's largest block divisor (16) hosts a 3-slab
    # interior band, y/x locals clear the O4 halo (2)
    grid = Grid.make(32, 16, 96, lengths=2.0)
    unsharded = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_stage")
    )
    ref = unsharded.run(unsharded.initial_state(), 5)
    outs = {}
    for overlap in ("split", "padded"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                              overlap=overlap)
        solver = DiffusionSolver(
            cfg,
            mesh=make_mesh({"dz": 2, "dy": 2, "dx": 2}),
            decomp=Decomposition.of({0: "dz", 1: "dy", 2: "dx"}),
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded, (
            overlap, getattr(solver, "_fused_fallback", None)
        )
        assert fused.overlap_split == (overlap == "split"), (
            overlap, getattr(solver, "_fused_fallback", None),
            fused.n_slabs,
        )
        st = solver.run(solver.initial_state(), 5)
        outs[overlap] = np.asarray(st.u)
        np.testing.assert_allclose(float(st.t), float(ref.t), rtol=1e-6)
    _assert_fused_close(outs["split"], outs["padded"])
    _assert_fused_close(outs["split"], ref.u)


def test_fused_diffusion_xsharded_split_overlap(devices):
    """The split-overlap broadening also exposes {dz, dx} DIFFUSION
    meshes: the z halo rides the exchanged-slab schedule while the x
    ghosts (stored layout — diffusion keeps ghosts on every axis) take
    the serialized refresh. Must match the serialized fused path and
    the unsharded fused run to the same ulp band the z-slab split test
    uses (interpret mode compiles each schedule separately, so FMA
    fusion may differ by an ulp)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    # local lz = 96 hosts a >= 3-block interior band for diffusion's
    # larger block sizes
    grid = Grid.make(32, 16, 192, lengths=2.0)
    unsharded = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    )
    ref = unsharded.run(unsharded.initial_state(), 5)
    outs = {}
    for overlap in ("split", "padded"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                              overlap=overlap)
        solver = DiffusionSolver(
            cfg,
            mesh=make_mesh({"dz": 2, "dx": 2}),
            decomp=Decomposition.of({0: "dz", 2: "dx"}),
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded
        assert fused.overlap_split == (overlap == "split"), (
            overlap, getattr(solver, "_fused_fallback", None)
        )
        st = solver.run(solver.initial_state(), 5)
        outs[overlap] = np.asarray(st.u)
    scale = float(np.max(np.abs(outs["padded"])))
    np.testing.assert_allclose(outs["split"], outs["padded"],
                               rtol=1e-6, atol=1e-7 * scale)
    np.testing.assert_allclose(outs["split"], np.asarray(ref.u),
                               rtol=1e-6, atol=1e-7 * scale)


def test_fused_burgers_xsharded_advance_to(devices):
    """run_to through the stored-x-ghost layout (adaptive dt, emitted
    wave speed, x refresh between stages) matches the unsharded fused
    trajectory and step count."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 48, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        adaptive_dt=True, impl="pallas")
    ref_s = BurgersSolver(cfg)
    t_end = 0.04
    ref = ref_s.advance_to(ref_s.initial_state(), t_end)
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dx": 2}), decomp=Decomposition.of({2: "dx"})
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.x_sharded
    out = solver.advance_to(solver.initial_state(), t_end)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)
    assert int(out.it) == int(ref.it) > 0


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_split_overlap_matches_serialized(devices, adaptive):
    """overlap='split' on a z-slab mesh runs the three-call overlapped
    schedule (interior blocks concurrent with the z-halo ppermute; edge
    blocks consume the exchanged slabs as separate operands) and must
    match both the serialized-refresh fused path and the generic XLA
    path. Match: the reference's five-stream boundary/interior split
    (MultiGPU/Diffusion3d_Baseline/main.c:203-260) applied to the tuned
    kernel."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 48, lengths=2.0)  # local lz=24 -> n_bz=3
    outs = {}
    for overlap in ("split", "padded"):
        cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                            adaptive_dt=adaptive, impl="pallas",
                            overlap=overlap)
        solver = BurgersSolver(
            cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded
        assert fused.overlap_split == (overlap == "split")
        st = solver.run(solver.initial_state(), 5)
        outs[overlap] = np.asarray(st.u)
    _assert_fused_close(outs["split"], outs["padded"])

    xcfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                         adaptive_dt=adaptive, impl="xla")
    xs = BurgersSolver(xcfg)
    ref = np.asarray(xs.run(xs.initial_state(), 5).u)
    scale = float(np.max(np.abs(ref)))
    np.testing.assert_allclose(outs["split"], ref, rtol=2e-5,
                               atol=2e-6 * scale)


@pytest.mark.parametrize("model", ["burgers", "diffusion"])
@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_split_overlap_pencil_matches_serialized(
    devices, model, adaptive
):
    """overlap='split' on a {dz, dy} PENCIL mesh: the z halo rides the
    three-call overlapped schedule while the y halo keeps the
    serialized per-stage refresh on each stage's composed output. Must
    match the all-serialized fused path and the unsharded fused run —
    the reference's boundary/interior stream split generalized past
    what its 1-D MPI slabs could decompose (SURVEY §2.1.5)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    if model == "diffusion" and adaptive:
        pytest.skip("diffusion has no adaptive dt")
    # local z must host a 3-block interior band for each model's block
    # picker: burgers bz<=8 -> lz=24; diffusion bz=20 -> lz=60
    grid = (
        Grid.make(24, 16, 48, lengths=2.0)
        if model == "burgers"
        else Grid.make(24, 16, 120, lengths=2.0)
    )
    mk = (
        (lambda **kw: BurgersSolver(
            BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                          adaptive_dt=adaptive, impl="pallas", **kw)))
        if model == "burgers"
        else (lambda **kw: DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                            **kw)))
    )
    unsharded = mk()
    assert unsharded._fused_stepper() is not None
    ref = unsharded.run(unsharded.initial_state(), 5)

    outs = {}
    for overlap in ("split", "padded"):
        solver = mk(overlap=overlap).__class__(
            mk(overlap=overlap).cfg,
            mesh=make_mesh({"dz": 2, "dy": 2}),
            decomp=Decomposition.of({0: "dz", 1: "dy"}),
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded
        assert fused.overlap_split == (overlap == "split"), (
            model, overlap, getattr(solver, "_fused_fallback", None)
        )
        st = solver.run(solver.initial_state(), 5)
        outs[overlap] = np.asarray(st.u)
        np.testing.assert_allclose(float(st.t), float(ref.t), rtol=1e-6)
    _assert_fused_close(outs["split"], outs["padded"])
    _assert_fused_close(outs["split"], ref.u)


def test_fused_burgers_split_overlap_pencil_run_to(devices):
    """advance_to through the pencil split-overlap schedule (run_to
    inside shard_map with both the exchanged-slab z path and the y
    refresh) matches the unsharded fused trajectory and step count."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 48, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        adaptive_dt=True, impl="pallas", overlap="split")
    ref_s = BurgersSolver(cfg)
    t_end = 0.04
    ref = ref_s.advance_to(ref_s.initial_state(), t_end)
    solver = BurgersSolver(
        cfg,
        mesh=make_mesh({"dz": 2, "dy": 2}),
        decomp=Decomposition.of({0: "dz", 1: "dy"}),
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.overlap_split
    out = solver.advance_to(solver.initial_state(), t_end)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)
    assert int(out.it) == int(ref.it) > 0


@pytest.mark.parametrize(
    "nz_global",
    [16, 44],
    ids=["thin-band", "thin-block"],
)
def test_fused_burgers_split_overlap_small_shard_falls_back(
    devices, nz_global
):
    """Shards that can't host a safe interior band silently use the
    serialized-refresh schedule instead of failing: local lz=8 gives
    n_bz=1 (< 3), and local lz=22 forces bz=2 < R — a thin block whose
    first interior-role box would reach into the never-refreshed ghost
    rows."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, nz_global, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        impl="pallas", overlap="split")
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded and not fused.overlap_split
    if nz_global == 44:
        assert fused.block[0] < 3, "expected a thin z-block"
    out = solver.run(solver.initial_state(), 2)  # executes on the fallback
    ref_s = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, dtype="float32", impl="pallas")
    )
    ref = ref_s.run(ref_s.initial_state(), 2)
    _assert_fused_close(out.u, ref.u)


@pytest.mark.parametrize("axis", [0, 1, 2], ids=["z", "y", "x"])
def test_fused_burgers_weno7_single_axis_sweeps(axis):
    """Each WENO7 sweep of the fused kernel in isolation: an IC varying
    along only one axis exercises exactly that direction's halo-4
    reconstruction (z row slices / y sublane rolls / x lane rolls with
    4-lane ghost synthesis); the other sweeps see constant data and
    contribute zero divergence. Must match the XLA WENO7 solver."""
    # Grid.make takes physical-order (nx, ny, nz); arrays are (z, y, x)
    grid = Grid.make(32, 16, 12, lengths=2.0)
    shape = grid.shape
    assert shape == (12, 16, 32)
    x = np.linspace(0.0, 2.0, shape[axis], endpoint=False)
    prof = np.exp(-18.0 * (x / 2.0 - 0.45) ** 2)
    u0 = np.broadcast_to(
        prof.reshape([-1 if d == axis else 1 for d in range(3)]), shape
    ).astype(np.float32)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, weno_order=7, cfl=0.3,
                            adaptive_dt=False, dtype="float32", impl=impl)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            assert solver._fused_stepper() is not None, "fast path not taken"
        from multigpu_advectiondiffusion_tpu.models.state import SolverState

        st = solver.run(SolverState.create(jnp.asarray(u0)), 4)
        outs[impl] = np.asarray(st.u)
    scale = float(np.max(np.abs(outs["xla"])))
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=2e-5, atol=2e-5 * scale)


def test_fused_burgers_weno7_adaptive_dt_matches_xla():
    """Adaptive-dt WENO7 on the fused path: the stage-emitted
    max|f'(u)| and the halo-4 reconstruction together must reproduce the
    XLA trajectory and its time axis."""
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, weno_order=7, cfl=0.3,
                            adaptive_dt=True, dtype="float32",
                            ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            assert solver._fused_stepper() is not None, "fast path not taken"
        st = solver.run(solver.initial_state(), 5)
        outs[impl] = (np.asarray(st.u), float(st.t))
    scale = float(np.max(np.abs(outs["xla"][0])))
    # wider than the fixed-dt band: the e-form/q-form rounding gap in
    # max|f'(u)| feeds back through dt, compounding across steps
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5, atol=6e-5 * scale)
    np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1], rtol=1e-5)


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_weno7_sharded_matches_unsharded(devices, adaptive):
    """The fused WENO7 stepper under a z-slab mesh: the 4-row ppermute
    ghost refresh between stages must reproduce the single-device fused
    run to the interpret-mode ulp bound."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(24, 16, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, weno_order=7, dtype="float32",
                        adaptive_dt=adaptive, impl="pallas")
    ref_solver = BurgersSolver(cfg)
    assert ref_solver._fused_stepper() is not None
    ref = ref_solver.run(ref_solver.initial_state(), 5)
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz")
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded, "sharded fast path not taken"
    assert fused.halo == 4
    out = solver.run(solver.initial_state(), 5)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers_weno7_advance_to_matches_xla(adaptive):
    """run_to (t_end mode) through the fused WENO7 stepper: trajectory,
    final time, and step count must match the generic path."""
    grid = Grid.make(16, 16, 16, lengths=2.0)
    t_end = 0.05
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, weno_order=7, cfl=0.3,
                            adaptive_dt=adaptive, dtype="float32",
                            ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        st = solver.advance_to(solver.initial_state(), t_end)
        outs[impl] = (np.asarray(st.u), float(st.t), int(st.it))
    scale = float(np.max(np.abs(outs["xla"][0])))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5, atol=2e-5 * scale)
    np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1], rtol=1e-6)
    assert outs["pallas"][2] == outs["xla"][2]


def test_fused_burgers_ghost_maintenance_long_run():
    """Many fused steps: the persistent padded state's edge ghosts must
    track the evolving boundary cells (a stale-ghost bug shows up as
    drift against the per-step-padded XLA path — the failure mode the
    reference actually has, SURVEY §3.2)."""
    grid = Grid.make(16, 12, 20, lengths=[3.0, 2.0, 2.5])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, cfl=0.25, adaptive_dt=False,
                            dtype="float32", ic="gaussian", impl=impl)
        solver = BurgersSolver(cfg)
        outs[impl] = np.asarray(solver.run(solver.initial_state(), 25).u)
    scale = float(np.max(np.abs(outs["xla"])))
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=5e-5, atol=5e-6 * scale)


def test_fused_diffusion2d_run_matches_xla():
    """The whole-run VMEM-resident 2-D stepper (run() with impl='pallas'
    on an eligible 2-D config) must agree with the generic XLA path to
    f32 rounding, including the accumulated t."""
    grid = Grid.make(40, 28, lengths=10.0)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl=impl)
        solver = DiffusionSolver(cfg)
        if impl == "pallas":
            fused = solver._fused_stepper()
            assert fused is not None, "2-D fast path not taken"
            assert type(fused).__name__ == "FusedDiffusion2DStepper"
        st = solver.run(solver.initial_state(), 9)
        outs[impl] = (np.asarray(st.u), float(st.t))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=1e-5, atol=1e-6)
    assert outs["pallas"][1] == outs["xla"][1]


def test_fused_diffusion2d_zero_iters_identity():
    grid = Grid.make(24, 16, lengths=4.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas"))
    st0 = solver.initial_state()
    st = solver.run(st0, 0)
    np.testing.assert_array_equal(np.asarray(st.u), np.asarray(st0.u))
    assert float(st.t) == float(st0.t)


def test_fused_diffusion2d_too_large_falls_back():
    """Grids whose padded state cannot fit the VMEM budget quietly use
    the generic path."""
    from multigpu_advectiondiffusion_tpu.ops.pallas.fused_diffusion2d import (
        FusedDiffusion2DStepper,
    )

    assert not FusedDiffusion2DStepper.supported((8192, 8192), jnp.float32)
    grid = Grid.make(8192, 8192, lengths=10.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas"))
    assert solver._fused_stepper() is None


@pytest.mark.parametrize(
    "kw",
    [{}, {"weno_variant": "z"}, {"nu": 1e-3}, {"flux": "buckley"},
     {"adaptive_dt": True}, {"adaptive_dt": True, "nu": 1e-3}],
    ids=["js", "z", "viscous", "buckley", "adaptive", "adaptive-viscous"],
)
def test_fused_burgers2d_run_matches_xla(kw):
    """The whole-run VMEM-resident 2-D Burgers stepper must agree with
    the generic XLA path to f32 rounding, including accumulated t —
    in both dt modes (adaptive recomputes the in-core max|f'(u)|
    reduction before every step, LFWENO5FDM2d.m:71)."""
    grid = Grid.make(40, 24, lengths=[4.0, 2.5])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, cfl=0.3,
                            dtype="float32", ic="gaussian", impl=impl,
                            **{"adaptive_dt": False, **kw})
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            fused = solver._fused_stepper()
            assert type(fused).__name__ == "FusedBurgers2DStepper", kw
        st = solver.run(solver.initial_state(), 8)
        outs[impl] = (np.asarray(st.u), float(st.t))
    scale = float(np.max(np.abs(outs["xla"][0])))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=3e-5, atol=3e-6 * scale)
    if kw.get("adaptive_dt"):
        np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1],
                                   rtol=1e-5)
    else:
        assert outs["pallas"][1] == outs["xla"][1]


# --------------------------------------------------------------------- #
# Sharded 2-D fused path (fused2d_sharded): the tuned 2-D kernel under a
# mesh — per-stage whole-shard kernels + ppermute ghost refresh, matching
# the reference's MPI deployment of its 2-D kernels
# (MultiGPU/Diffusion2d_Baseline/main.c:189-280, Burgers2d_Baseline/
# main.c:186+).
# --------------------------------------------------------------------- #

_DECOMPS_2D = [
    ({"dy": 4}, {0: "dy"}),  # reference-style slab (outer axis)
    ({"dx": 4}, {1: "dx"}),  # lane-axis slab
    ({"dy": 2, "dx": 2}, {0: "dy", 1: "dx"}),  # pencil
]


@pytest.mark.parametrize("mesh_axes,decomp_map", _DECOMPS_2D,
                         ids=["slab-y", "slab-x", "pencil"])
def test_fused2d_sharded_diffusion_bit_identical(devices, mesh_axes,
                                                 decomp_map):
    """The per-stage 2-D diffusion kernel shard-local under shard_map
    (global wall masks via the offsets operand, ppermute ghost refresh
    between stages) must reproduce the single-chip whole-run fused
    stepper bit-for-bit — identical per-cell op sequence over identical
    values."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 32, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    ref_solver = DiffusionSolver(cfg)
    assert type(ref_solver._fused_stepper()).__name__ == (
        "FusedDiffusion2DStepper"
    )
    ref = ref_solver.run(ref_solver.initial_state(), 8)
    solver = DiffusionSolver(
        cfg, mesh=make_mesh(mesh_axes), decomp=Decomposition.of(decomp_map)
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded, solver._fused_fallback
    assert type(fused).__name__ == "ShardedFusedDiffusion2DStepper"
    out = solver.run(solver.initial_state(), 8)
    assert float(jnp.max(jnp.abs(ref.u - out.u))) == 0.0


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
@pytest.mark.parametrize("mesh_axes,decomp_map", _DECOMPS_2D,
                         ids=["slab-y", "slab-x", "pencil"])
def test_fused2d_sharded_burgers_matches_unsharded(devices, mesh_axes,
                                                   decomp_map, adaptive):
    """The per-stage 2-D Burgers kernel under the mesh (both dt modes;
    adaptive rides the pmax reduction between steps) must reproduce the
    single-chip whole-run fused stepper to the documented interpret-mode
    ulp bound, with identical accumulated t."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 32, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-4, dtype="float32",
                        adaptive_dt=adaptive, impl="pallas")
    ref_solver = BurgersSolver(cfg)
    assert type(ref_solver._fused_stepper()).__name__ == (
        "FusedBurgers2DStepper"
    )
    ref = ref_solver.run(ref_solver.initial_state(), 6)
    solver = BurgersSolver(
        cfg, mesh=make_mesh(mesh_axes), decomp=Decomposition.of(decomp_map)
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded, solver._fused_fallback
    assert type(fused).__name__ == "ShardedFusedBurgers2DStepper"
    out = solver.run(solver.initial_state(), 6)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused_burgers2d_weno7_matches_xla(adaptive):
    """The 2-D whole-run stepper at order 7 (halo 4, LFWENO7FDM2d.m)
    must agree with the generic XLA path in both dt modes — order
    parity for the 2-D fused family, matching what the 3-D family
    already serves."""
    grid = Grid.make(40, 24, lengths=[4.0, 2.5])
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, weno_order=7, cfl=0.3, nu=1e-4,
                            dtype="float32", ic="gaussian", impl=impl,
                            adaptive_dt=adaptive)
        solver = BurgersSolver(cfg)
        if impl == "pallas":
            fused = solver._fused_stepper()
            assert type(fused).__name__ == "FusedBurgers2DStepper", (
                getattr(solver, "_fused_fallback", None)
            )
            assert fused.halo == 4
        st = solver.run(solver.initial_state(), 8)
        outs[impl] = (np.asarray(st.u), float(st.t))
    scale = float(np.max(np.abs(outs["xla"][0])))
    # same band as the 3-D WENO7-vs-XLA tests: the fused e-form and the
    # XLA q-form round differently through the order-7 nonlinear
    # weights, compounding over the 8 steps (adaptive additionally
    # feeds the gap back through dt)
    np.testing.assert_allclose(
        outs["pallas"][0], outs["xla"][0], rtol=2e-5,
        atol=(6e-5 if adaptive else 3e-5) * scale,
    )
    np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1], rtol=1e-5)


@pytest.mark.parametrize(
    "mesh_axes,decomp_map",
    [({"dy": 2, "dx": 2}, {0: "dy", 1: "dx"})],
    ids=["pencil"],
)
def test_fused2d_sharded_burgers_weno7(devices, mesh_axes, decomp_map):
    """Order 7 through the sharded per-stage 2-D kernels: the 4-deep
    ppermute refresh on both axes must reproduce the single-chip
    whole-run order-7 stepper (adaptive dt, pmax in the loop)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 32, lengths=2.0)
    cfg = BurgersConfig(grid=grid, weno_order=7, nu=1e-4, dtype="float32",
                        adaptive_dt=True, impl="pallas")
    ref_solver = BurgersSolver(cfg)
    assert type(ref_solver._fused_stepper()).__name__ == (
        "FusedBurgers2DStepper"
    )
    ref = ref_solver.run(ref_solver.initial_state(), 6)
    solver = BurgersSolver(
        cfg, mesh=make_mesh(mesh_axes), decomp=Decomposition.of(decomp_map)
    )
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded and fused.halo == 4, (
        getattr(solver, "_fused_fallback", None)
    )
    out = solver.run(solver.initial_state(), 6)
    _assert_fused_close(out.u, ref.u)
    np.testing.assert_allclose(float(out.t), float(ref.t), rtol=1e-6)


def test_fused2d_weno7_split_overlap(devices):
    """Order 7 through the 2-D split-overlap band schedule (halo-4 edge
    bands consuming the exchanged slabs) matches the serialized refresh
    and the unsharded whole-run stepper."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 48, lengths=2.0)  # ly local 12 >= 3*4
    ref_solver = BurgersSolver(
        BurgersConfig(grid=grid, weno_order=7, nu=1e-4, dtype="float32",
                      impl="pallas")
    )
    ref = ref_solver.run(ref_solver.initial_state(), 6)
    outs = {}
    for overlap in ("split", "padded"):
        cfg = BurgersConfig(grid=grid, weno_order=7, nu=1e-4,
                            dtype="float32", impl="pallas",
                            overlap=overlap)
        solver = BurgersSolver(
            cfg, mesh=make_mesh({"dy": 4}), decomp=Decomposition.of({0: "dy"})
        )
        fused = solver._fused_stepper()
        assert fused is not None and fused.halo == 4
        assert fused.overlap_split == (overlap == "split"), (
            overlap, getattr(solver, "_fused_fallback", None)
        )
        st = solver.run(solver.initial_state(), 6)
        outs[overlap] = np.asarray(st.u)
    _assert_fused_close(outs["split"], outs["padded"])
    _assert_fused_close(outs["split"], ref.u)


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
def test_fused2d_sharded_burgers_advance_to(devices, adaptive):
    """Sharded 2-D t_end mode runs the fused run_to (trimmed last step
    through the runtime SMEM dt) and reproduces the generic path's
    trajectory, landing time, and step count — a capability the
    single-chip whole-run stepper doesn't have (no run_to)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 32, lengths=2.0)
    mesh_axes, decomp_map = {"dy": 4}, {0: "dy"}
    t_end = 0.05  # ~4.5 steps at this CFL: exercises the trimmed step
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = BurgersConfig(grid=grid, cfl=0.3, nu=1e-4, dtype="float32",
                            adaptive_dt=adaptive, impl=impl)
        solver = BurgersSolver(
            cfg, mesh=make_mesh(mesh_axes),
            decomp=Decomposition.of(decomp_map),
        )
        st = solver.advance_to(solver.initial_state(), t_end)
        if impl == "pallas":
            assert "fused_adv" in solver._cache, "fused t_end not engaged"
        outs[impl] = (np.asarray(st.u), float(st.t), int(st.it))
    scale = float(np.max(np.abs(outs["xla"][0])))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(outs["pallas"][1], t_end, rtol=1e-6)
    assert outs["pallas"][2] == outs["xla"][2] > 0


def test_fused2d_sharded_diffusion_run_to_matches_run(devices):
    """Sharded 2-D diffusion run_to landing exactly on n*dt must agree
    with the fixed-count fused run of the same n."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 32, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    mesh = make_mesh({"dy": 4})
    a = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.of({0: "dy"}))
    run = a.run(a.initial_state(), 5)
    b = DiffusionSolver(cfg, mesh=mesh, decomp=Decomposition.of({0: "dy"}))
    adv = b.advance_to(b.initial_state(), float(run.t))
    assert "fused_adv" in b._cache
    assert int(adv.it) == 5
    np.testing.assert_allclose(np.asarray(adv.u), np.asarray(run.u),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("model", ["burgers", "diffusion"])
def test_fused2d_split_overlap_matches_serialized(devices, model):
    """overlap='split' on a 2-D y-slab mesh runs the three-band schedule
    (interior band concurrent with the in-flight slab ppermute; only the
    two h-row edge bands consume the exchanged slabs) — matching the
    serialized-refresh path and the unsharded fused run at ulp level, in
    run() and run_to."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 40, lengths=10.0)  # ly=10/shard >= 3*halo
    mesh_kw = dict(mesh=make_mesh({"dy": 4}),
                   decomp=Decomposition.of({0: "dy"}))
    outs = {}
    for overlap in ("padded", "split"):
        if model == "burgers":
            cfg = BurgersConfig(grid=grid, nu=1e-4, dtype="float32",
                                impl="pallas", overlap=overlap)
            solver = BurgersSolver(cfg, **mesh_kw)
        else:
            cfg = DiffusionConfig(grid=grid, dtype="float32",
                                  impl="pallas", overlap=overlap)
            solver = DiffusionSolver(cfg, **mesh_kw)
        fused = solver._fused_stepper()
        assert fused is not None and fused.sharded
        assert fused.overlap_split == (overlap == "split")
        want = "split" if overlap == "split" else "serialized-refresh"
        assert solver.engaged_path()["overlap"] == want
        outs[overlap] = solver.run(solver.initial_state(), 6)
    a, b = np.asarray(outs["padded"].u), np.asarray(outs["split"].u)
    scale = float(np.abs(a).max())
    # band slicing/assembly compiles different FMA contractions than the
    # whole-shard call — same values, few-ulp freedom (as in 3-D split)
    assert float(np.abs(a - b).max()) <= 8 * np.finfo(np.float32).eps * scale
    # adaptive dt inherits the state's few-ulp freedom through the CFL
    # max, so the accumulated t may differ in the last ulp
    assert abs(float(outs["padded"].t) - float(outs["split"].t)) <= (
        8 * np.finfo(np.float32).eps * max(1.0, abs(float(outs["padded"].t)))
    )


def test_fused2d_split_overlap_run_to(devices):
    """The split schedule serves run_to (trimmed last step) with the
    generic path's step count and landing time."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 40, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                          overlap="split")
    solver = DiffusionSolver(cfg, mesh=make_mesh({"dy": 4}),
                             decomp=Decomposition.of({0: "dy"}))
    assert solver._fused_stepper().overlap_split
    st0 = solver.initial_state()
    t_end = float(st0.t) + 4.4 * solver.dt
    out = solver.advance_to(st0, t_end)
    assert "fused_adv" in solver._cache
    assert int(out.it) == 5
    np.testing.assert_allclose(float(out.t), t_end, rtol=1e-6)


def test_fused2d_split_overlap_thin_band_falls_back(devices):
    """Shards without a non-degenerate interior band (ly < 3*halo) fall
    back to the serialized refresh — and still match."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 32, lengths=10.0)  # ly=8/shard < 3*3 for WENO5
    cfg = BurgersConfig(grid=grid, nu=1e-4, dtype="float32",
                        impl="pallas", overlap="split")
    solver = BurgersSolver(cfg, mesh=make_mesh({"dy": 4}),
                           decomp=Decomposition.of({0: "dy"}))
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded and not fused.overlap_split
    ref = BurgersSolver(BurgersConfig(grid=grid, nu=1e-4, dtype="float32",
                                      impl="pallas"))
    r = ref.run(ref.initial_state(), 4)
    o = solver.run(solver.initial_state(), 4)
    _assert_fused_close(o.u, r.u)


def test_fused2d_sharded_thin_shard_declines_loudly(devices):
    """A sharded axis thinner than the WENO5 halo declines the fused
    path with a specific reason — and the generic path then fails with
    a loud halo error too (no silent wrong answer at any rung)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(40, 8, lengths=2.0)  # ly = 2 < halo 3 over dy=4
    cfg = BurgersConfig(grid=grid, dtype="float32", impl="pallas")
    solver = BurgersSolver(
        cfg, mesh=make_mesh({"dy": 4}), decomp=Decomposition.of({0: "dy"})
    )
    assert solver._fused_stepper() is None
    assert "halo" in solver._fused_fallback
    with pytest.raises(ValueError, match="halo"):
        solver.run(solver.initial_state(), 2)


def test_fused_diffusion_bf16_storage_rung():
    """The bf16-storage/f32-compute rung (HBM bytes halved on the
    roof-bound ref grid): trajectories must stay within bf16 rounding of
    the f32 fused run — storage is the only thing quantized; the RK
    arithmetic runs f32."""
    grid = Grid.make(32, 24, 24, lengths=10.0)
    outs = {}
    for dtype in ("float32", "bfloat16"):
        s = DiffusionSolver(
            DiffusionConfig(grid=grid, dtype=dtype, impl="pallas")
        )
        fused = s._fused_stepper()
        assert fused is not None, (dtype, s._fused_fallback)
        # f32 may ride the slab whole-run rung; bf16 storage exists only
        # in the per-stage stepper
        want = (
            ("fused-stage",)
            if dtype == "bfloat16"
            else ("fused-stage", "fused-whole-run-slab")
        )
        assert fused.engaged_label in want
        st = s.run(s.initial_state(), 5)
        outs[dtype] = np.asarray(st.u, np.float32)
    scale = float(np.abs(outs["float32"]).max())
    diff = float(np.abs(outs["float32"] - outs["bfloat16"]).max())
    # the IC itself is bf16-quantized (~0.4% relative) and each stage
    # stores through bf16: a few percent of drift over 5 steps is the
    # storage price — but the f32 arithmetic must keep it at that level
    assert diff <= 0.05 * scale, (diff, scale)
    # ...and strictly better than computing IN bf16 (the XLA path with
    # the same dtype), which loses the stencil's cancellation digits
    s_xla = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="bfloat16", impl="xla")
    )
    xla_bf16 = np.asarray(s_xla.run(s_xla.initial_state(), 5).u, np.float32)
    diff_xla = float(np.abs(outs["float32"] - xla_bf16).max())
    assert diff <= diff_xla * 1.05, (diff, diff_xla)


def test_fused_diffusion_bf16_declines_off_design():
    """bf16 storage exists only where it pays: the 3-D per-stage
    stepper. 2-D and whole-step configs decline with a reason."""
    s2 = DiffusionSolver(DiffusionConfig(
        grid=Grid.make(24, 24, lengths=10.0), dtype="bfloat16",
        impl="pallas"))
    assert s2._fused_stepper() is None
    assert "bf16" in s2._fused_fallback
    s3 = DiffusionSolver(DiffusionConfig(
        grid=Grid.make(24, 24, 24, lengths=10.0), dtype="bfloat16",
        impl="pallas_step"))
    assert s3._fused_stepper() is None


def test_step_fused_diffusion_matches_xla():
    """The whole-step (3-stages-per-HBM-pass) ladder variant must match
    the generic path; it is not the default (measured slower than the
    per-stage pipeline on v5e — compute growth outweighs the HBM saving;
    kept as an explicit rung of the kernel-strategy ladder)."""
    from multigpu_advectiondiffusion_tpu.ops.pallas.fused_diffusion_step import (
        StepFusedDiffusionStepper,
    )

    grid = Grid.make(36, 28, 24, lengths=10.0)
    cfg = DiffusionConfig(grid=grid, dtype="float32")
    ref = DiffusionSolver(cfg)
    st = ref.run(ref.initial_state(), 7)
    s = DiffusionSolver(cfg)
    f = StepFusedDiffusionStepper(grid.shape, s.dtype, grid.spacing,
                                  [1.0] * 3, s.dt, 2, 0.0, block_z=8)
    st0 = s.initial_state()
    u, t = f.run(st0.u, st0.t, 7)
    np.testing.assert_allclose(np.asarray(u), np.asarray(st.u),
                               rtol=1e-5, atol=1e-6)
    assert float(t) == float(st.t)


def test_fused_pencil_split_requires_refresh(devices):
    """A pencil split-overlap stepper driven directly with only `exch`
    (no serialized refresh for the non-leading sharded axes) must raise
    — silently-frozen y ghosts are the failure mode the guard exists
    for."""
    from multigpu_advectiondiffusion_tpu.ops.pallas.fused_burgers import (
        FusedBurgersStepper,
    )
    from multigpu_advectiondiffusion_tpu.ops import flux as flux_lib

    st = FusedBurgersStepper(
        (24, 8, 48), "float32", (0.1, 0.1, 0.1), flux_lib.burgers(),
        "js", 0.0, dt=0.01, global_shape=(48, 16, 48), y_sharded=True,
        overlap_split=True,
    )
    assert st.overlap_split
    u = jnp.zeros((24, 8, 48), jnp.float32)
    with pytest.raises(ValueError, match="non-leading"):
        st.run(u, jnp.zeros((), jnp.float32), 1,
               exch=lambda P: (P[:3], P[:3]))
