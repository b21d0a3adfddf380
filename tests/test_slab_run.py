"""The slab-pipelined whole-run stepper (fused-whole-run-slab).

One Pallas program whose grid is (timestep, z-slab): slabs stream
HBM->VMEM double-buffered, all three RK stages fuse in VMEM per step
(redundant ghost-region recompute, G = 3*stage-radius), state ping-pongs
across steps on a stacked buffer. These tests pin its numerics against
the XLA path (the fused-stage equality tests in test_pallas.py keep
covering the per-stage rung), its dispatch position at the top of the
3-D ladder, and its sharded per-step composition with the ghost
refresh / split-overlap machinery.
"""

import jax
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

from f32_bound import assert_f32_close

_ULPS = 32 * np.finfo(np.float32).eps


def _rel_close(actual, desired, tol):
    a, d = np.asarray(actual), np.asarray(desired)
    scale = max(float(np.max(np.abs(d))), 1e-30)
    assert float(np.max(np.abs(a - d))) <= tol * scale, (
        float(np.max(np.abs(a - d))) / scale
    )


def test_slab_diffusion_multi_slab_matches_xla():
    """A forced multi-slab pipeline (block_z=4 -> 9 slabs, deep enough
    to engage the cross-step prefetch) must reproduce the generic XLA
    trajectory — the strongest check that the revolving write-drain
    schedule never lets a slab read a neighbor's same-step output."""
    from multigpu_advectiondiffusion_tpu.ops.pallas.fused_slab_run import (
        SlabRunDiffusionStepper,
        _cross_ok,
    )

    grid = Grid.make(24, 28, 36, lengths=10.0)  # shape (36, 28, 24)
    ref = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="xla")
    )
    want = ref.run(ref.initial_state(), 9)
    st = SlabRunDiffusionStepper(
        grid.shape, jnp.float32, grid.spacing, [1.0] * 3, ref.dt, 2, 0.0,
        block_z=4,
    )
    assert st.n_slabs == 9
    assert _cross_ok(st.bz, st.halo, st.n_slabs), "want the prefetch path"
    st0 = ref.initial_state()
    u, t = jax.jit(lambda u, t: st.run(u, t, 9))(st0.u, st0.t)
    _rel_close(u, want.u, 1e-5)
    assert float(t) == float(want.t)


@pytest.mark.parametrize("order", [5, 7], ids=["weno5", "weno7"])
def test_slab_burgers_multi_slab_matches_xla(order):
    """Multi-slab Burgers (both WENO orders, viscous) vs the XLA path:
    the z-sweep row windows, the in-VMEM edge synthesis at the global
    walls, and the slab chaining must agree with the reference
    discipline across slab boundaries."""
    from multigpu_advectiondiffusion_tpu.ops import flux as flux_lib
    from multigpu_advectiondiffusion_tpu.ops.pallas.fused_slab_run import (
        SlabRunBurgersStepper,
    )

    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    ref = BurgersSolver(
        BurgersConfig(grid=grid, weno_order=order, cfl=0.3, nu=1e-3,
                      adaptive_dt=False, dtype="float32", ic="gaussian",
                      impl="xla")
    )
    want = ref.run(ref.initial_state(), 5)
    st = SlabRunBurgersStepper(
        grid.shape, jnp.float32, grid.spacing, flux_lib.burgers(), "js",
        1e-3, dt=ref.dt, order=order, block_z=4,
    )
    assert st.n_slabs == 4
    st0 = ref.initial_state()
    u, t = jax.jit(lambda u, t: st.run(u, t, 5))(st0.u, st0.t)
    # same rounding classes as the fused-stage-vs-XLA tests: order 7's
    # large beta coefficients widen the band
    _rel_close(u, want.u, 2e-5 if order == 5 else 5e-5)
    assert float(t) == float(want.t)


def test_slab_engagement_ladder():
    """Dispatch: 3-D fixed-dt impl='pallas' engages the slab stepper
    where the model says it wins (small z extents always qualify);
    adaptive dt, t_end mode, bf16 and the 'pallas_stage' pin keep the
    per-stage stepper; 'pallas_slab' pins slab."""
    g3 = Grid.make(24, 16, 16, lengths=2.0)

    def eng(s, mode="iters"):
        return s.engaged_path(mode)["stepper"]

    d = DiffusionSolver(DiffusionConfig(grid=g3, dtype="float32",
                                        impl="pallas"))
    assert eng(d) == "fused-whole-run-slab"
    assert eng(d, "t_end") == "fused-stage"  # slab has no run_to
    assert eng(DiffusionSolver(DiffusionConfig(
        grid=g3, dtype="float32", impl="pallas_stage"))) == "fused-stage"
    assert eng(DiffusionSolver(DiffusionConfig(
        grid=g3, dtype="float32", impl="pallas_slab"))) == (
        "fused-whole-run-slab"
    )
    assert eng(DiffusionSolver(DiffusionConfig(
        grid=g3, dtype="bfloat16", impl="pallas"))) == "fused-stage"

    b = BurgersSolver(BurgersConfig(grid=g3, nu=1e-5, adaptive_dt=False,
                                    dtype="float32", impl="pallas"))
    assert eng(b) == "fused-whole-run-slab"
    assert eng(b, "t_end") == "fused-stage"
    assert eng(BurgersSolver(BurgersConfig(
        grid=g3, nu=1e-5, adaptive_dt=True, dtype="float32",
        impl="pallas"))) == "fused-stage"

    # profitability: a deep-z grid whose slabs come out thin keeps the
    # measured per-stage path under plain 'pallas' (the redundant
    # recompute tax), but 'pallas_slab' still pins slab
    from multigpu_advectiondiffusion_tpu.ops.pallas import fused_slab_run

    assert not fused_slab_run.SlabRunBurgersStepper.profitable(
        (512, 512, 512), jnp.float32
    )
    assert not fused_slab_run.SlabRunDiffusionStepper.profitable(
        (160, 204, 508), jnp.float32
    )


def test_slab_pallas_stage_pin_matches_xla():
    """impl='pallas_stage' pins the per-stage stepper for unsharded
    fixed-dt configs (the rung 'pallas' used to select) and matches XLA
    — keeps the per-stage fixed-dt path covered now that 'pallas'
    prefers the slab stepper."""
    grid = Grid.make(24, 16, 16, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                        dtype="float32", impl="pallas_stage")
    s = BurgersSolver(cfg)
    fused = s._fused_stepper()
    assert fused is not None and fused.engaged_label == "fused-stage"
    out = s.run(s.initial_state(), 5)
    ref = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                      dtype="float32", impl="xla")
    )
    want = ref.run(ref.initial_state(), 5)
    _rel_close(out.u, want.u, 2e-5)


def test_slab_diffusion_f64_storage_matches_xla_f64():
    """The f64-storage/f32-compute rung: state stays f64, kernels run
    f32 — the trajectory must match the XLA f64 path to f32 accuracy,
    and the returned state must still be f64 (the storage half of the
    convention)."""
    grid = Grid.make(24, 16, 16, lengths=2.0)
    sp = DiffusionSolver(DiffusionConfig(grid=grid, dtype="float64",
                                         impl="pallas"))
    assert sp.engaged_path()["stepper"] in (
        "fused-whole-run-slab", "fused-stage"
    )
    out = sp.run(sp.initial_state(), 5)
    assert out.u.dtype == jnp.float64
    sx = DiffusionSolver(DiffusionConfig(grid=grid, dtype="float64",
                                         impl="xla"))
    want = sx.run(sx.initial_state(), 5)
    _rel_close(out.u, want.u, 1e-5)
    # f64 Burgers stays off the fused ladder (kernels are f32-only and
    # Burgers has no storage rung)
    bf = BurgersSolver(BurgersConfig(grid=grid, dtype="float64",
                                     impl="pallas"))
    assert bf._fused_stepper() is None


def test_slab_sharded_zslab_split_matches_unsharded(devices):
    """The sharded slab composition (pinned via impl='pallas_slab',
    z-slab mesh): per-step slab-pipelined calls with ONE G-deep z-ghost
    exchange per step. overlap='split' runs the three-call schedule
    (interior slabs concurrent with the in-flight ppermute; the two
    edge calls consume the exchanged G-slabs) and must match the
    unsharded whole-run stepper — diffusion to a few f32 ulps (the same
    per-cell op sequence, up to XLA-CPU's per-program FMA contraction),
    Burgers to the interpret-mode ulp bound."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    # diffusion: local lz=36 -> split picks bz=12, n_slabs=3
    grid = Grid.make(16, 16, 72, lengths=2.0)
    ref_s = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab")
    )
    assert ref_s._fused_stepper().engaged_label == "fused-whole-run-slab"
    ref = ref_s.run(ref_s.initial_state(), 4)
    cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab",
                          overlap="split")
    s = DiffusionSolver(cfg, mesh=make_mesh({"dz": 2}),
                        decomp=Decomposition.slab("dz"))
    f = s._fused_stepper()
    assert f is not None and f.sharded and f.overlap_split, (
        getattr(s, "_fused_fallback", None), f and f.n_slabs
    )
    assert f.engaged_label == "fused-whole-run-slab"
    assert s.engaged_path()["overlap"] == "split"
    out = s.run(s.initial_state(), 4)
    assert_f32_close(out.u, ref.u, what="split sharded vs unsharded slab")
    assert float(out.t) == float(ref.t)

    # burgers: local lz=30 -> split picks bz=10 (>= G=9), n_slabs=3
    grid = Grid.make(16, 16, 60, lengths=2.0)
    ref_b = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                      dtype="float32", impl="pallas_slab")
    )
    refu = ref_b.run(ref_b.initial_state(), 4)
    cfgb = BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                         dtype="float32", impl="pallas_slab",
                         overlap="split")
    sb = BurgersSolver(cfgb, mesh=make_mesh({"dz": 2}),
                       decomp=Decomposition.slab("dz"))
    fb = sb._fused_stepper()
    assert fb is not None and fb.overlap_split, (
        getattr(sb, "_fused_fallback", None), fb and (fb.bz, fb.n_slabs)
    )
    outb = sb.run(sb.initial_state(), 4)
    a, d = np.asarray(outb.u), np.asarray(refu.u)
    scale = max(float(np.max(np.abs(d))), 1e-30)
    assert float(np.max(np.abs(a - d))) <= _ULPS * scale
    assert float(outb.t) == float(refu.t)


def test_slab_sharded_serialized_refresh_matches_unsharded(devices):
    """The serialized per-step G-deep refresh (no split): one exchange
    + one slab-pipelined call per step, bit-identical to the unsharded
    slab run for diffusion."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 72, lengths=2.0)
    ref_s = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab")
    )
    ref = ref_s.run(ref_s.initial_state(), 4)
    s = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
    )
    f = s._fused_stepper()
    assert f is not None and f.sharded and not f.overlap_split
    assert s.engaged_path()["overlap"] == "serialized-refresh"
    out = s.run(s.initial_state(), 4)
    assert float(jnp.max(jnp.abs(out.u - ref.u))) == 0.0


def test_slab_dma_exchange_matches_collective(devices):
    """The in-kernel remote-DMA exchange (exchange='dma', ISSUE 13):
    the sharded whole-run program pushes its ghost rows to the ±z
    neighbors from inside the Pallas kernel instead of breaking out to
    an XLA ppermute between per-step calls. Same rows move, same
    per-cell op sequence computes — diffusion must match the collective
    transport bit-for-bit, at both the per-step (k=1) and the deep
    (k=2) exchange cadence, in interpret mode on a dz=2 mesh."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 72, lengths=2.0)
    for k in (1, 2):
        ref_s = DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32",
                            impl="pallas_slab", steps_per_exchange=k),
            mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
        )
        ref = ref_s.run(ref_s.initial_state(), 5)
        s = DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32",
                            impl="pallas_slab", steps_per_exchange=k,
                            exchange="dma"),
            mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
        )
        eng = s.engaged_path()
        assert eng["stepper"] == "fused-whole-run-slab"
        assert eng["exchange"] == "dma"
        assert eng["overlap"] == "in-kernel"
        fused = s._fused_stepper()
        spec = fused.stencil_spec()
        assert spec["remote_dma"] is not None
        assert spec["remote_dma"]["window_rows"] == fused.exchange_depth
        out = s.run(s.initial_state(), 5)
        assert float(jnp.max(jnp.abs(out.u - ref.u))) == 0.0, k
        assert float(out.t) == float(ref.t)


def test_slab_dma_burgers_matches_collective(devices):
    """WENO5 Burgers through the dma transport vs the collective
    transport: identical consumed values (the wall replicas are
    re-synthesized in VMEM either way), ulp-level equality like every
    sharded WENO pin."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 72, lengths=2.0)
    ref_b = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                      dtype="float32", impl="pallas_slab"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
    )
    ref = ref_b.run(ref_b.initial_state(), 4)
    sb = BurgersSolver(
        BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                      dtype="float32", impl="pallas_slab",
                      exchange="dma"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
    )
    assert sb.engaged_path()["exchange"] == "dma"
    out = sb.run(sb.initial_state(), 4)
    a, d = np.asarray(out.u), np.asarray(ref.u)
    scale = max(float(np.max(np.abs(d))), 1e-30)
    assert float(np.max(np.abs(a - d))) <= _ULPS * scale
    assert float(out.t) == float(ref.t)


def test_slab_dma_declines_loudly(devices):
    """exchange='dma' is pin-semantics: every config that cannot host
    the in-kernel exchange fails at construction/dispatch instead of
    silently running the collective cadence — unsharded, pencil
    meshes, split-overlap, non-TPU/non-interpret backends, and the
    batched ensemble engine."""
    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 72, lengths=2.0)

    # unsharded: no neighbor to push to
    with pytest.raises(ValueError, match="needs a device mesh"):
        DiffusionSolver(DiffusionConfig(grid=grid, dtype="float32",
                                        impl="pallas_slab",
                                        exchange="dma"))
    # pencil mesh: the remote-DMA ring is z-slab only
    with pytest.raises(ValueError, match="z-slab"):
        DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32",
                            impl="pallas_slab", exchange="dma"),
            mesh=make_mesh({"dz": 2, "dy": 2}),
            decomp=Decomposition.of({0: "dz", 1: "dy"}),
        )
    # split-overlap: nothing left at the XLA level to overlap
    with pytest.raises(ValueError, match="split-overlap"):
        DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32",
                            impl="pallas_slab", exchange="dma",
                            overlap="split"),
            mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
        )
    # generic impl cannot host it
    with pytest.raises(ValueError, match="sharded slab rung"):
        DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32", impl="xla",
                            exchange="dma"),
            mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
        )
    # ensemble fold: the member axis does not ride the dma ring (the
    # inner solver is unsharded-spatial, so the mesh gate fires first;
    # _ensemble_gate backstops the batched dispatch itself)
    with pytest.raises(ValueError, match="dma"):
        EnsembleSolver(
            DiffusionSolver,
            DiffusionConfig(grid=grid, dtype="float32",
                            impl="pallas_slab", exchange="dma"),
            4,
        )


def test_slab_dma_backend_gate(devices, monkeypatch):
    """A backend with neither the Mosaic TPU target nor the CPU
    interpret simulator (i.e. a real CPU/GPU lowering) declines the
    dma rung LOUDLY at dispatch — never a silent collective run."""
    from multigpu_advectiondiffusion_tpu.ops.pallas import laplacian
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    s = DiffusionSolver(
        DiffusionConfig(grid=Grid.make(16, 16, 72, lengths=2.0),
                        dtype="float32", impl="pallas_slab",
                        exchange="dma"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
    )
    monkeypatch.setattr(laplacian, "interpret_mode", lambda: False)
    with pytest.raises(ValueError, match="remote DMA needs the TPU"):
        s._fused_stepper()


def test_slab_dma_mosaic_rejection_degrades_to_split(devices, monkeypatch):
    """The dma rung's own ladder: a Mosaic rejection of the in-kernel
    program degrades to the split-overlap COLLECTIVE exchange on the
    same rung/cadence (recorded in engaged_path()['degraded']), and the
    run completes with the collective trajectory."""
    from multigpu_advectiondiffusion_tpu.ops.pallas import fused_slab_run
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )
    from multigpu_advectiondiffusion_tpu.resilience.errors import (
        SimulatedMosaicError,
    )

    grid = Grid.make(16, 16, 72, lengths=2.0)
    ref_s = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
    )
    ref = ref_s.run(ref_s.initial_state(), 4)

    def boom(self, *a, **kw):
        raise SimulatedMosaicError("Mosaic rejected the dma program")

    monkeypatch.setattr(
        fused_slab_run._SlabRunStepper, "_run_dma", boom
    )
    s = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab",
                        exchange="dma"),
        mesh=make_mesh({"dz": 2}), decomp=Decomposition.slab("dz"),
    )
    out = s.run(s.initial_state(), 4)
    engaged = s.engaged_path()
    assert engaged["stepper"] == "fused-whole-run-slab"
    assert engaged["exchange"] == "collective"
    chain = [(e["from"], e["to"]) for e in engaged["degraded"]]
    assert chain == [
        ("fused-whole-run-slab[dma]", "fused-whole-run-slab[split]")
    ]
    assert_f32_close(out.u, ref.u, what="degraded split vs padded slab")


def test_slab_sharded_declines_off_design(devices):
    """Sharded slab stays pinned-only and z-slab-only: plain 'pallas'
    under a mesh keeps the measured per-stage path, pencil meshes
    decline the pin, and 'pallas' on a y-sharded mesh is untouched by
    the slab machinery."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    grid = Grid.make(16, 16, 48, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                        dtype="float32", impl="pallas")
    s = BurgersSolver(cfg, mesh=make_mesh({"dz": 2}),
                      decomp=Decomposition.slab("dz"))
    assert s.engaged_path()["stepper"] == "fused-stage"

    pin = BurgersConfig(grid=grid, nu=1e-5, adaptive_dt=False,
                        dtype="float32", impl="pallas_slab")
    sp = BurgersSolver(pin, mesh=make_mesh({"dz": 2, "dy": 2}),
                       decomp=Decomposition.of({0: "dz", 1: "dy"}))
    # pencil mesh: slab pin declines to per-stage (still fused)
    assert sp.engaged_path()["stepper"] == "fused-stage"
