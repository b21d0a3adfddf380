"""Benchmark matrix: cell-update rates (MLUPS) against every reference
baseline family, one JSON line per metric (headline first).

Mirrors the reference's measurement ladder (`SingleGPU/RunAll.m:1-17`
plus the per-project `Run.m` timings archived in BASELINE.md), but
machine-captured instead of hand-pasted into Run.m comments.

Unit: MLUPS = cells * iters * RK_stages / seconds (stage-update rate).
The reference's own "GFLOPS" differs per tier — the MultiGPU and
single-GPU *Burgers* conventions include the x3 RK factor, the
single-GPU *Diffusion* one omits it (BASELINE.md footnote 1) — so every
`vs_baseline` below divides by the reference number converted to the
same stage-update MLUPS.

Prints one JSON line per metric:
  {"metric", "value", "unit", "vs_baseline"}

Timing methodology (sync via device→host fetch, fixed overhead
subtracted): see ``multigpu_advectiondiffusion_tpu/bench/timing.py``.
"""

from __future__ import annotations

import json

def _cases(on_tpu: bool):
    """(metric, make_solver, mode, work, baseline, expected) rows. CPU
    mode shrinks the grids — it validates mechanics only (Pallas runs
    interpreted there)."""
    # Reference baselines in stage-update MLUPS — single source of truth
    # is bench/matrix.py BASELINES_MLUPS (derivations in BASELINE.md).
    # Imported here so main() can set the platform before any jax import.
    from multigpu_advectiondiffusion_tpu.bench.matrix import BASELINES_MLUPS

    B_DIFF3D = BASELINES_MLUPS["diffusion3d_multigpu"][0]
    B_DIFF2D = BASELINES_MLUPS["diffusion2d"][0]
    B_BURG3D = BASELINES_MLUPS["burgers3d_512"][0]
    B_BURG2D = BASELINES_MLUPS["burgers2d_multigpu"][0]
    B_ADR3D = BASELINES_MLUPS["adr3d"][0]
    B_ADR2D = BASELINES_MLUPS["adr2d"][0]
    from multigpu_advectiondiffusion_tpu import (
        ADRConfig,
        ADRSolver,
        BurgersConfig,
        BurgersSolver,
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )

    def diff3d_tiled():
        # Reference interior 400x200x206 (~16.5M cells) re-proportioned
        # to exact (8,128) f32 tiles at the same scale: (nz,ny,nx) =
        # (160,204,508) => padded trailing dims (208,512), zero slack.
        g = (
            Grid.make(508, 204, 160, lengths=(12.7, 5.1, 4.0))
            if on_tpu
            else Grid.make(64, 28, 16, lengths=(1.6, 0.7, 0.4))
        )
        return DiffusionSolver(
            DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                            impl="pallas")
        )

    def diff3d_ref_grid():
        # The literal MultiGPU north-star interior, NOT tile-aligned
        # (padded trailing dims carry slack) — reported next to the
        # headline so the number is not best-case-only.
        g = (
            Grid.make(400, 200, 206, lengths=(10.0, 5.0, 5.15))
            if on_tpu
            else Grid.make(50, 25, 26, lengths=(1.0, 0.5, 0.52))
        )
        return DiffusionSolver(
            DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                            impl="pallas")
        )

    def diff2d():
        # SingleGPU Diffusion2d ladder grid (1001^2).
        g = (
            Grid.make(1001, 1001, lengths=20.0)
            if on_tpu
            else Grid.make(65, 65, lengths=2.0)
        )
        return DiffusionSolver(
            DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                            impl="pallas")
        )

    def burg3d(adaptive: bool):
        def make():
            # SingleGPU Burgers3d_WENO5 512^3 config: WENO5-JS, viscous
            # nu=1e-5 (main.cpp:56-59). adaptive=False reproduces the
            # reference's hard-coded unit wave speed (main.c:193);
            # adaptive=True is the physically-correct default.
            g = (
                Grid.make(512, 512, 512, lengths=2.0)
                if on_tpu
                else Grid.make(24, 16, 16, lengths=2.0)
            )
            return BurgersSolver(
                BurgersConfig(grid=g, nu=1e-5, dtype="float32",
                              adaptive_dt=adaptive, impl="pallas")
            )

        return make

    def burg3d_grid(nx, ny, nz):
        def make():
            # The other two published single-GPU viscous-Burgers
            # workloads (SingleGPU/Burgers3d_WENO5/Run.m:3-13 slab,
            # :27-37 wide), literal grids.
            g = (
                Grid.make(nx, ny, nz, lengths=2.0)
                if on_tpu
                else Grid.make(max(16, nx // 64), max(12, ny // 64),
                               max(8, nz // 8), lengths=2.0)
            )
            return BurgersSolver(
                BurgersConfig(grid=g, nu=1e-5, dtype="float32",
                              adaptive_dt=False, impl="pallas")
            )

        return make

    def burg2d():
        # MultiGPU Burgers2d interior 400x406 (Run.m:4-14), here on one
        # chip via the whole-run VMEM stepper (fixed dt, CUDA parity).
        g = (
            Grid.make(400, 406, lengths=2.0)
            if on_tpu
            else Grid.make(40, 46, lengths=2.0)
        )
        return BurgersSolver(
            BurgersConfig(grid=g, dtype="float32", adaptive_dt=False,
                          impl="pallas")
        )

    def burg3d_multigpu():
        # The reference's MultiGPU Burgers3d headline config
        # (Burgers3d_Baseline/Run.m:4-14): interior 400x400x406 run as
        # 400x400x408 (matrix.py's TPU-friendly z rounding), fixed dt
        # (the CUDA drivers' hard-coded wave speed), on one chip via the
        # fused stepper. x = 400 interior lanes pad to 512 — the same
        # lane tax as the literal diffusion grid.
        g = (
            Grid.make(400, 400, 408, lengths=2.0)
            if on_tpu
            else Grid.make(24, 16, 16, lengths=2.0)
        )
        return BurgersSolver(
            BurgersConfig(grid=g, dtype="float32", adaptive_dt=False,
                          impl="pallas")
        )

    def diff3d_f64():
        # The literal MultiGPU interior (400x200x206, same grid as
        # diff3d_ref_grid) in the reference's own precision (USE_FLOAT
        # false, DiffusionMPICUDA.h:66) — the apples-to-apples row
        # against its 731 MLUPS. Since the slab-run round this rides the
        # fused 3-D path through the f64-storage/f32-compute convention
        # (state at f64, kernels f32 — Mosaic has no f64 vector path;
        # accuracy priced in PARITY.md) instead of falling to
        # generic-xla. Runs under a scoped enable_x64 (see main()).
        g = (
            Grid.make(400, 200, 206, lengths=(10.0, 5.0, 5.15))
            if on_tpu
            else Grid.make(50, 25, 26, lengths=(1.0, 0.5, 0.52))
        )
        return DiffusionSolver(
            DiffusionConfig(grid=g, diffusivity=1.0, dtype="float64",
                            impl="pallas")
        )

    def burg2d_weno7():
        # The 2-D order-7 rung on the MultiGPU Burgers2d workload: the
        # halo-4 whole-run VMEM stepper (LFWENO7FDM2d.m is MATLAB-only,
        # never benchmarked; the anchor is the 2-D order-5 baseline).
        g = (
            Grid.make(400, 406, lengths=2.0)
            if on_tpu
            else Grid.make(40, 46, lengths=2.0)
        )
        return BurgersSolver(
            BurgersConfig(grid=g, weno_order=7, dtype="float32",
                          adaptive_dt=False, impl="pallas")
        )

    def burg3d_weno7():
        # The order-7 rung of the fused family at the flagship 512^3
        # viscous workload (halo-4 kernels). The reference's WENO7 is
        # MATLAB-only (LFWENO7FDM3d.m, never benchmarked); the baseline
        # anchor is its order-5 rate on the same grid.
        g = (
            Grid.make(512, 512, 512, lengths=2.0)
            if on_tpu
            else Grid.make(24, 16, 16, lengths=2.0)
        )
        return BurgersSolver(
            BurgersConfig(grid=g, weno_order=7, nu=1e-5, dtype="float32",
                          adaptive_dt=False, impl="pallas")
        )

    def burg3d_axis():
        # The per-axis Pallas rung at 512^3 — the explicit non-fused
        # ladder rung (the reference benches its non-winning variants
        # too, SingleGPU/RunAll.m).
        g = (
            Grid.make(512, 512, 512, lengths=2.0)
            if on_tpu
            else Grid.make(24, 16, 16, lengths=2.0)
        )
        return BurgersSolver(
            BurgersConfig(grid=g, nu=1e-5, dtype="float32",
                          adaptive_dt=False, impl="pallas_axis")
        )

    def adr3d():
        # the title workload (ISSUE 15): variable-K advection–
        # diffusion–reaction on the fused per-stage rung — same
        # tile-aligned grid class as the diffusion headline
        g = (
            Grid.make(508, 204, 160, lengths=(12.7, 5.1, 4.0))
            if on_tpu
            else Grid.make(64, 28, 16, lengths=(1.6, 0.7, 0.4))
        )
        return ADRSolver(
            ADRConfig(grid=g, dtype="float32", impl="pallas",
                      velocity=0.5, kappa_variation=0.2,
                      reaction_rate=0.25)
        )

    def adr2d():
        # 2-D ADR rides the generic rung (the fused ADR kernel is 3-D
        # only); the row pins that expectation so a future fused 2-D
        # rung shows up as an engagement change, not silently
        g = (
            Grid.make(1001, 1001, lengths=20.0)
            if on_tpu
            else Grid.make(65, 65, lengths=2.0)
        )
        return ADRSolver(
            ADRConfig(grid=g, dtype="float32", impl="xla",
                      velocity=0.5, kappa_variation=0.2,
                      reaction_rate=0.25)
        )

    it = (lambda n: n) if on_tpu else (lambda n: min(n, 4))
    # rows: (metric, make_solver, mode, work, baseline, expected) where
    # mode is "iters" (fixed-count run) or "t_end" (the drivers' native
    # `while t < tEnd` loop; work = equivalent fixed-dt step count) and
    # expected is the set of stepper labels this config may legitimately
    # engage (grids/VMEM budgets differ between CPU smoke mode and TPU,
    # so slab-vs-stage may flip; a silent fall to generic-xla or
    # per-axis-pallas is NEVER legitimate for a fused row and fails the
    # run loudly — the engagement guard, see main()).
    SLAB_OR_STAGE = {"fused-whole-run-slab", "fused-stage"}
    return [
        # ~1 s windows for the 3-D diffusion rows: at ~0.5 s the captured
        # headline sat 15-18% below repeated local runs on the pre-PR-1
        # setup (r3 artifact vs ROUND3.md) — the longer window narrows
        # the band the driver can land in
        ("diffusion3d_mlups", diff3d_tiled, "iters", it(1010), B_DIFF3D,
         SLAB_OR_STAGE),
        ("diffusion3d_ref_grid_mlups", diff3d_ref_grid, "iters", it(606),
         B_DIFF3D, SLAB_OR_STAGE),
        # 20000 iters (~500 ms): the whole-run VMEM stepper finishes 2000
        # in ~50 ms, inside the per-call sync-overhead noise band
        # (measured 44k-112k MLUPS run to run at 6000); the window must
        # dwarf the per-call sync jitter for the median to be stable
        ("diffusion2d_mlups", diff2d, "iters", it(20000), B_DIFF2D,
         {"fused-whole-run"}),
        # 60 iters (~2.7 s window): at 20 the per-call dispatch overhead
        # still shaved ~1% off the steady-state rate
        ("burgers3d_mlups", burg3d(False), "iters", it(60), B_BURG3D,
         SLAB_OR_STAGE),
        ("burgers3d_adaptive_mlups", burg3d(True), "iters", it(60), B_BURG3D,
         {"fused-stage"}),
        # the drivers' native t_end mode must run at the fused rate
        # (VERDICT r2 item 1) — captured, not claimed
        ("burgers3d_tend_mlups", burg3d(False), "t_end", it(60), B_BURG3D,
         {"fused-stage"}),
        ("burgers3d_slab_mlups", burg3d_grid(1601, 986, 35), "iters",
         it(60), BASELINES_MLUPS["burgers3d_slab"][0], SLAB_OR_STAGE),
        ("burgers3d_wide_mlups", burg3d_grid(1000, 1000, 200), "iters",
         it(30), BASELINES_MLUPS["burgers3d_wide"][0], SLAB_OR_STAGE),
        # 24000 iters: the 2-D whole-run stepper clears ~30k MLUPS, so
        # the 600-iter window was ~10 ms — pure sync-jitter; ~400 ms
        # makes the median trustworthy
        ("burgers2d_mlups", burg2d, "iters", it(24000), B_BURG2D,
         {"fused-whole-run"}),
        # the reference's MultiGPU 3-D Burgers headline workload — the
        # last published config not driver-captured
        ("burgers3d_multigpu_mlups", burg3d_multigpu, "iters", it(60),
         BASELINES_MLUPS["burgers3d_multigpu"][0], SLAB_OR_STAGE),
        # the reference's own precision (f64) on its literal grid, and
        # the per-axis ladder rung — previously measured but living only
        # in PARITY/README prose (VERDICT r3 item 3b): now driver-captured
        ("diffusion3d_f64_mlups", diff3d_f64, "iters", it(31),
         BASELINES_MLUPS["diffusion3d_multigpu_f64"][0], SLAB_OR_STAGE),
        ("burgers3d_axis_mlups", burg3d_axis, "iters", it(15),
         BASELINES_MLUPS["burgers3d_512_axis"][0], {"per-axis-pallas"}),
        # ~30 iters x 3 stages at ~4.7k MLUPS => ~2.5 s window
        ("burgers3d_weno7_mlups", burg3d_weno7, "iters", it(30),
         BASELINES_MLUPS["burgers3d_512_weno7"][0], SLAB_OR_STAGE),
        # 12000 iters (~0.9 s at ~6.2k MLUPS): the 2-D window rule —
        # whole-run calls must dwarf the per-call sync jitter
        ("burgers2d_weno7_mlups", burg2d_weno7, "iters", it(12000),
         BASELINES_MLUPS["burgers2d_weno7"][0], {"fused-whole-run"}),
        # the title ADR workload (ISSUE 15): 3-D on the fused per-stage
        # rung (engagement-guarded like every fused row), 2-D on the
        # generic rung; baselines are the nearest published diffusion
        # anchors — the reference never shipped ADR (matrix.py note)
        ("adr3d_mlups", adr3d, "iters", it(404), B_ADR3D,
         {"fused-stage"}),
        ("adr2d_mlups", adr2d, "iters", it(2000), B_ADR2D,
         {"generic-xla"}),
    ]


def _ensemble_cases(on_tpu: bool):
    """Batched-ensemble rows (ISSUE 9): (family, make_case) where
    make_case() -> (solver_cls, cfg, iters, member_fn). Each family is
    measured at B in {1, 8, 64} as ONE vmapped dispatch vs the looped
    single-run baseline (same compiled single program dispatched B
    times) — MLUPS*members against MLUPS*members."""
    from multigpu_advectiondiffusion_tpu import (
        BurgersConfig,
        BurgersSolver,
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )

    def diff3d():
        g = (
            Grid.make(256, 128, 64, lengths=(6.4, 3.2, 1.6))
            if on_tpu
            else Grid.make(16, 12, 10, lengths=(1.6, 1.2, 1.0))
        )
        cfg = DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                              impl="pallas", ic="gaussian")
        # member-varying ICs (a width sweep): the parameter-sweep
        # workload, physics uniform so the fused rung engages
        member = lambda i: {  # noqa: E731
            "ic_params": (("width", 0.1 + 0.002 * i),)
        }
        return DiffusionSolver, cfg, (60 if on_tpu else 4), member

    def burg3d():
        g = (
            Grid.make(128, 64, 64, lengths=2.0)
            if on_tpu
            else Grid.make(16, 8, 8, lengths=2.0)
        )
        cfg = BurgersConfig(grid=g, nu=1e-5, dtype="float32",
                            adaptive_dt=False, impl="pallas")
        member = lambda i: {  # noqa: E731
            "ic_params": (("width", 0.1 + 0.002 * i),)
        }
        return BurgersSolver, cfg, (30 if on_tpu else 4), member

    def diff3d_xla():
        # the generic rung under batching in the many-small-problems
        # regime (per-user scenarios; HipBone's batched-small-FEM
        # argument, PAPERS arXiv 2202.12477): per-member programs small
        # enough that launch/dispatch overhead is a real fraction of a
        # run — the regime where one batched dispatch amortizes most
        g = (
            Grid.make(64, 48, 32, lengths=(6.4, 4.8, 3.2))
            if on_tpu
            else Grid.make(12, 10, 8, lengths=(1.2, 1.0, 0.8))
        )
        cfg = DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                              impl="xla", ic="gaussian")
        member = lambda i: {  # noqa: E731
            "ic_params": (("width", 0.1 + 0.002 * i),)
        }
        return DiffusionSolver, cfg, (60 if on_tpu else 2), member

    # impl="pallas" families may legitimately land on EITHER fused
    # batched shape — the per-stage vmap or (since ISSUE 11) the
    # B-folded slab grid: grids/VMEM budgets differ between CPU smoke
    # mode and TPU, so the profitability pick flips like the single-run
    # SLAB_OR_STAGE rows. Generic-xla is still never legitimate here.
    FUSED_BATCH = {
        "ensemble-vmap[fused-stage]",
        "ensemble-fold[fused-whole-run-slab]",
    }
    return [
        ("ensemble_diffusion3d", diff3d, FUSED_BATCH),
        ("ensemble_burgers3d", burg3d, FUSED_BATCH),
        ("ensemble_diffusion3d_xla", diff3d_xla,
         {"ensemble-vmap[generic-xla]"}),
    ]


def _wall_timed(fn, reps: int = 3):
    """Raw wall seconds (median-of-reps, first call untimed warm-up) —
    the ensemble rows compare WHOLE dispatches including their launch
    overhead, because amortizing that overhead is the point."""
    import statistics
    import time

    from multigpu_advectiondiffusion_tpu.bench.timing import sync

    sync(fn())  # compile + warm-up
    times = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        sync(fn())
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    spread = (max(times) - min(times)) / med if med > 0 else 0.0
    return med, spread


def _ensemble_rows(on_tpu: bool):
    """One row per (family, B): MLUPS*members of the batched dispatch,
    with the looped single-run baseline measured on the SAME compiled
    single program (compile excluded from both sides — the batched win
    reported here is dispatch/streaming amortization, not compile; the
    compile-amortization story is the AOT cache's, gated separately in
    out/ensemble_gate.sh)."""
    import jax.numpy as jnp

    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )
    from multigpu_advectiondiffusion_tpu.models.state import SolverState
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    rows = []
    for family, make_case, expect in _ensemble_cases(on_tpu):
        solver_cls, cfg, iters, member_fn = make_case()
        for B in (1, 8, 64):
            es = EnsembleSolver(
                solver_cls, cfg, [member_fn(i) for i in range(B)]
            )
            est = es.initial_state()
            batched_s, spread = _wall_timed(
                lambda: es.run(est, iters).u, reps=3
            )
            # looped baseline: ONE single-run solver (compile paid
            # once, outside the timing) dispatched B times over the
            # same member initial states
            single = es.member_solver(0)

            def looped():
                outs = [
                    single.run(
                        SolverState(u=est.u[i], t=est.t[i],
                                    it=est.it[i]),
                        iters,
                    ).u
                    for i in range(B)
                ]
                return jnp.stack(outs)

            looped_s, looped_spread = _wall_timed(looped, reps=3)
            engaged = es.engaged_path()
            rate = mlups(
                cfg.grid.num_cells * B, iters,
                STAGES[cfg.integrator], batched_s,
            )
            looped_rate = mlups(
                cfg.grid.num_cells * B, iters,
                STAGES[cfg.integrator], looped_s,
            )
            row = {
                "metric": f"{family}_b{B}_mlups_members",
                "value": round(rate, 2),
                "unit": "MLUPS*members",
                "ensemble": B,
                "iters": iters,
                "seconds": round(batched_s, 5),
                "spread": round(spread, 4),
                "looped_mlups_members": round(looped_rate, 2),
                "looped_seconds": round(looped_s, 5),
                "looped_spread": round(looped_spread, 4),
                # the amortization headline: batched throughput over
                # the looped single-run baseline
                "vs_looped": round(looped_s / batched_s, 3)
                if batched_s > 0 else None,
                "engaged": engaged["stepper"],
                # member-placement provenance (ISSUE 11): single-device
                # rows carry 1/1 so the bench gate reads one convention
                "member_sharding": engaged.get("member_sharding", 1),
                "devices": engaged.get("devices", 1),
                "mesh": engaged.get("mesh"),
                "tuned": engaged.get("tuned"),
            }
            ok = engaged["stepper"] in expect
            if not ok:
                row["engagement_error"] = {
                    "expected": sorted(expect),
                    "fallback": engaged.get("fallback"),
                }
            rows.append((row, ok))
    return rows


def _ensemble_mesh_rows(on_tpu: bool):
    """Mesh-scale ensemble rows (ISSUE 11): a B=64 uniform-physics
    diffusion ensemble dispatched through ``impl="auto"`` on the
    8-device 'members' mesh. The tuner MEASURES the batched candidate
    space (generic vmap / fused-stage vmap / B-folded slab) at the
    actual B and the row records its decision; the engagement guard
    fails the row if the dispatch silently fell back to the
    single-device path (devices == 1) or the decision was not
    measured. Emits nothing when fewer than 8 devices exist."""
    import jax
    import jax.numpy as jnp

    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )
    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )
    from multigpu_advectiondiffusion_tpu.models.state import (
        EnsembleState,
        SolverState,
    )
    from multigpu_advectiondiffusion_tpu.parallel.mesh import make_mesh
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    if len(jax.devices()) < 8:
        return []
    # the dispatch-bound many-small-problems regime at its sharpest
    # (one step per request — the serving shape): per-member work is
    # small enough that launch overhead dominates the looped baseline,
    # which is exactly what one batched mesh dispatch amortizes
    g = (
        Grid.make(64, 48, 32, lengths=(6.4, 4.8, 3.2))
        if on_tpu
        else Grid.make(8, 8, 10, lengths=(0.8, 0.8, 1.0))
    )
    cfg = DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                          impl="auto", ic="gaussian")
    iters = 60 if on_tpu else 1
    B = 64
    mesh = make_mesh({"members": 8})
    member = lambda i: {  # noqa: E731
        "ic_params": (("width", 0.1 + 0.002 * i),)
    }
    es = EnsembleSolver(DiffusionSolver, cfg,
                        [member(i) for i in range(B)], mesh=mesh)
    est = es.initial_state()
    batched_s, spread = _wall_timed(lambda: es.run(est, iters).u, reps=3)
    single = es.member_solver(0)
    # the looped baseline follows the _ensemble_rows convention
    # EXACTLY (r06's 5.95x was measured this way: per-member states
    # sliced from the batched state inside the timed loop) — but from
    # an UNSHARDED copy staged outside the timing, so the baseline is
    # never billed for cross-device gathers off the member-sharded
    # array
    import numpy as _np

    est_host = EnsembleState(
        u=jnp.asarray(_np.asarray(est.u)),
        t=jnp.asarray(_np.asarray(est.t)),
        it=jnp.asarray(_np.asarray(est.it)),
    )

    def looped():
        outs = [
            single.run(
                SolverState(u=est_host.u[i], t=est_host.t[i],
                            it=est_host.it[i]),
                iters,
            ).u
            for i in range(B)
        ]
        return jnp.stack(outs)

    looped_s, looped_spread = _wall_timed(looped, reps=3)
    engaged = es.engaged_path()
    rate = mlups(cfg.grid.num_cells * B, iters,
                 STAGES[cfg.integrator], batched_s)
    looped_rate = mlups(cfg.grid.num_cells * B, iters,
                        STAGES[cfg.integrator], looped_s)
    row = {
        "metric": f"ensemble_diffusion3d_mesh_b{B}_mlups_members",
        "value": round(rate, 2),
        "unit": "MLUPS*members",
        "ensemble": B,
        "iters": iters,
        "seconds": round(batched_s, 5),
        "spread": round(spread, 4),
        "looped_mlups_members": round(looped_rate, 2),
        "looped_seconds": round(looped_s, 5),
        "looped_spread": round(looped_spread, 4),
        "vs_looped": round(looped_s / batched_s, 3)
        if batched_s > 0 else None,
        "engaged": engaged["stepper"],
        "member_sharding": engaged.get("member_sharding", 1),
        "devices": engaged.get("devices", 1),
        "mesh": engaged.get("mesh"),
        "tuned": engaged.get("tuned"),
    }
    # engagement guard, mesh edition: a batched row built on a mesh
    # that silently fell back to the single-device path — or whose
    # impl="auto" decision came from anything but measurement at this
    # B — is a mislabeled rate and fails the run loudly
    ok = True
    if row["devices"] < 8 or row["member_sharding"] < 8:
        row["engagement_error"] = {
            "fell_back_to_single_device": {
                "devices": row["devices"],
                "member_sharding": row["member_sharding"],
            }
        }
        ok = False
    elif (engaged.get("tuned") or {}).get("source") not in (
        "measured", "cache"
    ):
        row["engagement_error"] = {
            "decision_not_measured": engaged.get("tuned")
        }
        ok = False
    return [(row, ok)]


def _serving_rows(on_tpu: bool):
    """Request-serving rows (ISSUE 17): requests/sec and latency
    percentiles of the coalesced request server (``service/server.py``,
    one batched EnsembleSolver dispatch per slice) against a sequential
    ``max_batch=1`` server answering the SAME B=8 mixed-width diffusion
    request set. Both rounds run warm (an unmeasured round per
    configuration pays the compiles first) and without journal fsync,
    so the row measures serving mechanics — coalescing vs per-request
    dispatch — not disk latency. On CPU this is a mechanics-grade
    number; the coalesced-beats-sequential guard still applies because
    dispatch amortization is exactly what the tiny-grid regime shows."""
    import os
    import shutil
    import tempfile
    import time

    from multigpu_advectiondiffusion_tpu.service.requests import (
        RequestSpec,
        submit_request_to_spool,
    )
    from multigpu_advectiondiffusion_tpu.service.server import (
        RequestServer,
    )

    B = 8
    n = [64, 64] if on_tpu else [16, 16]
    # horizon in steps, not wall time: the diffusion family starts at
    # its config t0 with a grid-dependent stability dt — derive both so
    # every request marches the same ~3 slices regardless of grid
    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig as _DCfg,
        DiffusionSolver as _DSolver,
        Grid as _Grid,
    )

    _probe_cfg = _DCfg(grid=_Grid.make(*n), dtype="float32", impl="xla")
    t_end = float(_probe_cfg.t0) + 24 * float(_DSolver(_probe_cfg).dt)

    def _round(root, max_batch):
        os.makedirs(root, exist_ok=True)
        rids = []
        for i in range(B):
            rid = f"bench-{max_batch}-{i}"
            submit_request_to_spool(root, RequestSpec(
                request_id=rid, model="diffusion", n=list(n),
                t_end=t_end, dtype="float32", ic="gaussian",
                ic_params={"width": 0.08 + 0.01 * i},
            ))
            rids.append(rid)
        srv = RequestServer(root, max_batch=max_batch, slice_steps=8,
                            fsync=False)
        t0 = time.perf_counter()
        out = srv.serve(until_idle=True, poll_seconds=0.001)
        wall = time.perf_counter() - t0
        srv.close()
        # max queue depth comes from the server's own exported gauge
        # watermark (the snapshot the close() above just published)
        max_depth = None
        g = srv.metrics.gauges.get("serve_queue_depth")
        if g is not None and g.max is not None:
            max_depth = int(g.max)
        lat = []
        for rid in rids:
            p = os.path.join(root, "requests", rid, "result.json")
            if os.path.exists(p):
                with open(p) as fh:
                    s = json.load(fh)
                if s.get("seconds") is not None:
                    lat.append(s["seconds"] * 1000.0)
        occ = []
        ev = os.path.join(root, "serve_events.jsonl")
        if os.path.exists(ev):
            with open(ev) as fh:
                for line in fh:
                    try:
                        e = json.loads(line)
                    except ValueError:
                        continue
                    if (e.get("kind") == "serve"
                            and e.get("name") == "slice"
                            and e.get("occupancy") is not None):
                        occ.append(e["occupancy"])
        done = (out.get("states") or {}).get("done", 0)
        return wall, sorted(lat), occ, done, max_depth

    work = tempfile.mkdtemp(prefix="tpucfd_bench_serve_")
    try:
        # warm round per configuration: pays the B=8 and B=1 compiles
        _round(os.path.join(work, "warm_coal"), B)
        _round(os.path.join(work, "warm_seq"), 1)
        coal_s, lat, occ, coal_done, max_depth = _round(
            os.path.join(work, "coalesced"), B
        )
        seq_s, seq_lat, _, seq_done, _ = _round(
            os.path.join(work, "sequential"), 1
        )
    finally:
        shutil.rmtree(work, ignore_errors=True)

    # one quantile codepath (ISSUE 18): latencies go through the shared
    # fixed-log-boundary histogram — the SAME estimator the fleet's
    # merged snapshots and tpucfd-status report, so a bench row and a
    # dashboard never disagree about what "p99" means
    from multigpu_advectiondiffusion_tpu.telemetry.metrics import (
        Histogram,
    )

    def _pct(ms_values, q):
        h = Histogram("bench_latency_ms")
        for v in ms_values:
            h.observe(v)
        est = h.quantile(q)
        return round(est, 3) if est is not None else None

    row = {
        "metric": f"serving_diffusion2d_b{B}_rps",
        "value": round(B / coal_s, 2) if coal_s > 0 else None,
        "unit": "req/s",
        "requests": B,
        "seconds": round(coal_s, 5),
        "p50_ms": _pct(lat, 0.50),
        "p95_ms": _pct(lat, 0.95),
        "p99_ms": _pct(lat, 0.99),
        "max_queue_depth": max_depth,
        "occupancy": round(sum(occ) / len(occ), 4) if occ else None,
        "sequential_seconds": round(seq_s, 5),
        "sequential_p50_ms": _pct(seq_lat, 0.50),
        "vs_sequential": round(seq_s / coal_s, 3) if coal_s > 0 else None,
        "ensemble": B,
    }
    # serving guard: every request must be answered in both rounds, and
    # the coalesced round must beat the sequential one at B=8 — a server
    # whose batching lost to per-request dispatch is a mislabeled row
    ok = coal_done == B and seq_done == B
    if not ok:
        row["engagement_error"] = {
            "unanswered": {"coalesced_done": coal_done,
                           "sequential_done": seq_done,
                           "expected": B}
        }
    elif not row["vs_sequential"] or row["vs_sequential"] <= 1.0:
        row["engagement_error"] = {
            "coalescing_lost_to_sequential": {
                "coalesced_seconds": row["seconds"],
                "sequential_seconds": row["sequential_seconds"],
            }
        }
        ok = False
    return [(row, ok)]


def _serving_pipelined_rows(on_tpu: bool):
    """Zero-copy pipelined serving rows (ISSUE 19): the SAME B=8
    mixed-width diffusion request set served twice by the coalesced
    server — once synchronous (``pipeline=False``, the ISSUE 17 loop)
    and once pipelined (``pipeline=True``: donated state buffers,
    dispatch-ahead depth 2, non-blocking finished-lane publish) — one
    row per mode with req/s, p50/p99 latency and the measured
    device-idle fraction (``serve_device_idle_fraction``, 1 - busy/wall
    per dissolved batch). Both rounds run warm and without fsync. On
    CPU this is a mechanics-grade number (the overlap hides *host*
    work — publish, journal, health-stat collection — behind dispatch;
    there is no device to keep busy), so the guard checks engagement
    (every request answered in both modes, the pipelined round actually
    dispatched ahead), not a speedup ratio — the on/off perf regression
    gate is ``out/serving_perf_gate.sh``."""
    import os
    import shutil
    import tempfile
    import time

    from multigpu_advectiondiffusion_tpu.service.requests import (
        RequestSpec,
        submit_request_to_spool,
    )
    from multigpu_advectiondiffusion_tpu.service.server import (
        RequestServer,
    )

    B = 8
    n = [64, 64] if on_tpu else [16, 16]
    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig as _DCfg,
        DiffusionSolver as _DSolver,
        Grid as _Grid,
    )

    _probe_cfg = _DCfg(grid=_Grid.make(*n), dtype="float32", impl="xla")
    t_end = float(_probe_cfg.t0) + 24 * float(_DSolver(_probe_cfg).dt)

    def _round(root, pipeline):
        os.makedirs(root, exist_ok=True)
        rids = []
        for i in range(B):
            rid = f"bench-pl{int(pipeline)}-{i}"
            submit_request_to_spool(root, RequestSpec(
                request_id=rid, model="diffusion", n=list(n),
                t_end=t_end, dtype="float32", ic="gaussian",
                ic_params={"width": 0.08 + 0.01 * i},
            ))
            rids.append(rid)
        srv = RequestServer(root, max_batch=B, slice_steps=8,
                            fsync=False, pipeline=pipeline,
                            pipeline_depth=2)
        t0 = time.perf_counter()
        out = srv.serve(until_idle=True, poll_seconds=0.001)
        wall = time.perf_counter() - t0
        srv.close()
        lat = []
        for rid in rids:
            p = os.path.join(root, "requests", rid, "result.json")
            if os.path.exists(p):
                with open(p) as fh:
                    s = json.load(fh)
                if s.get("seconds") is not None:
                    lat.append(s["seconds"] * 1000.0)
        idle = srv.metrics.histograms.get("serve_device_idle_fraction")
        idle_frac = (round(idle.mean(), 4)
                     if idle is not None and idle.count else None)
        stall = srv.metrics.histograms.get(
            "serve_pipeline_stall_seconds"
        )
        overlap = srv.metrics.histograms.get(
            "serve_pipeline_overlap_fraction"
        )
        disp = srv.metrics.counters.get(
            "serve_pipeline_dispatches_total"
        )
        done = (out.get("states") or {}).get("done", 0)
        return {
            "wall": wall,
            "lat": sorted(lat),
            "done": done,
            "idle_frac": idle_frac,
            "stall_s": (round(stall.sum, 5)
                        if stall is not None and stall.count else None),
            "overlap": (round(overlap.mean(), 4)
                        if overlap is not None and overlap.count
                        else None),
            "dispatches": disp.value if disp is not None else 0,
        }

    work = tempfile.mkdtemp(prefix="tpucfd_bench_pipe_")
    try:
        # warm round per mode: pays the B=8 compile (donated and
        # undonated executables key separately in the dispatch cache)
        _round(os.path.join(work, "warm_sync"), False)
        _round(os.path.join(work, "warm_pipe"), True)
        sync = _round(os.path.join(work, "sync"), False)
        pipe = _round(os.path.join(work, "pipelined"), True)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    from multigpu_advectiondiffusion_tpu.telemetry.metrics import (
        Histogram,
    )

    def _pct(ms_values, q):
        h = Histogram("bench_latency_ms")
        for v in ms_values:
            h.observe(v)
        est = h.quantile(q)
        return round(est, 3) if est is not None else None

    rows = []
    for mode, r in (("sync", sync), ("pipelined", pipe)):
        row = {
            "metric": f"serving_diffusion2d_b{B}_{mode}_rps",
            "value": (round(B / r["wall"], 2)
                      if r["wall"] > 0 else None),
            "unit": "req/s",
            "requests": B,
            "seconds": round(r["wall"], 5),
            "p50_ms": _pct(r["lat"], 0.50),
            "p99_ms": _pct(r["lat"], 0.99),
            "device_idle_frac": r["idle_frac"],
            "pipeline": mode == "pipelined",
            "ensemble": B,
        }
        if mode == "pipelined":
            row["pipeline_depth"] = 2
            row["stall_seconds"] = r["stall_s"]
            row["overlap_fraction"] = r["overlap"]
            row["vs_sync"] = (round(sync["wall"] / r["wall"], 3)
                              if r["wall"] > 0 else None)
        ok = r["done"] == B
        if not ok:
            row["engagement_error"] = {
                "unanswered": {"done": r["done"], "expected": B}
            }
        elif mode == "pipelined" and r["dispatches"] <= 0:
            # a "pipelined" row whose loop never dispatched ahead is a
            # mislabeled synchronous row
            row["engagement_error"] = {"pipeline_never_engaged": {
                "dispatches": r["dispatches"],
            }}
            ok = False
        rows.append((row, ok))
    return rows


def main() -> None:
    import os
    import sys
    import tempfile

    # the mesh-scale ensemble rows need a device mesh: CPU rounds get
    # the test suite's 8 virtual devices (a real TPU topology provides
    # its own); must land before the first jax import initializes the
    # backend
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    import jax

    from multigpu_advectiondiffusion_tpu.utils.compile_cache import (
        use_compile_cache,
    )

    use_compile_cache()
    # telemetry rides every bench run: the stream is the forensic record
    # an engagement-guard failure prints (see the tail dump below) — a
    # degraded/fell-back row is diagnosable from the bench output alone.
    # TPUCFD_BENCH_METRICS overrides the default tempfile destination.
    from multigpu_advectiondiffusion_tpu import telemetry

    metrics_path = os.environ.get("TPUCFD_BENCH_METRICS") or os.path.join(
        tempfile.gettempdir(), f"bench_telemetry_{os.getpid()}.jsonl"
    )
    sink = telemetry.install(metrics_path)

    # measured dispatch rides every bench run: rows built with
    # impl="auto" (the multichip scaling rows) may measure their
    # (rung x steps_per_exchange) candidates on a cache miss and
    # persist the decision — the tune:* events land in the same stream
    from multigpu_advectiondiffusion_tpu import tuning

    tuning.configure(enabled=True)
    if jax.default_backend() == "cpu":
        # CPU mechanics rounds: smoke-grade measurement cost for the
        # batched candidate races (interpret-mode Pallas candidates at
        # B=64 would otherwise dominate the round); env overrides win
        tuning.configure(
            measure_iters=int(os.environ.get("TPUCFD_TUNE_ITERS", "4")),
            measure_reps=int(os.environ.get("TPUCFD_TUNE_REPS", "2")),
        )

    from multigpu_advectiondiffusion_tpu.bench.timing import (
        timed_advance,
        timed_run,
    )
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import STAGES
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    platform = jax.devices()[0].platform
    if platform not in ("tpu", "cpu"):
        raise SystemExit(
            f"bench.py measures on a TPU (or checks mechanics on the "
            f"CPU); jax found {platform!r}"
        )
    on_tpu = platform == "tpu"
    mismatches = []
    for metric, make_solver, mode, work, baseline, expect in _cases(on_tpu):
        # x64 scoped per row: a process-wide flip would poison
        # the f32 Pallas rows' Mosaic lowering with i64 constants
        with jax.enable_x64(metric.endswith("_f64_mlups")):
            solver = make_solver()
            state = solver.initial_state()
            if mode == "t_end":
                # fixed-dt equivalent of `work` steps, landing exactly —
                # the solver's own fixed dt, not a re-derivation of its
                # formula (which would silently diverge for solvers whose
                # fixed dt is not cfl*min(spacing), e.g. diffusion)
                dt = solver.dt
                assert dt is not None, f"{metric}: t_end rows need fixed dt"
                adv = timed_advance(solver, state, work * dt, reps=5)
                timing, iters = adv.timing, adv.steps
            else:
                timing = timed_run(solver, state, work, reps=5)
                iters = work
        # median-of-5 with the observed spread AND discarded-stall count
        # recorded: the artifact is self-qualifying, and a stalled rep
        # can no longer sit inside the median (VERDICT r3 weak item 1)
        rate = mlups(
            solver.grid.num_cells, iters, STAGES[solver.cfg.integrator],
            timing.median_seconds,
        )
        # the artifact records which kernel path actually ran — a row
        # that silently fell back to the generic path would say so
        # instead of publishing a mislabeled rate
        engaged = solver.engaged_path(
            "t_end" if mode == "t_end" else "iters"
        )
        # roofline efficiency of the measured rate on the engaged rung's
        # static bytes/FLOPs model (telemetry/costmodel): the row says
        # how close to the hardware roof it ran, not just how fast
        from multigpu_advectiondiffusion_tpu.telemetry import costmodel

        cost = costmodel.summarize_run(
            solver, engaged["stepper"], iters, timing.median_seconds
        )
        # measured introspection beside the modeled columns: the
        # compiled executable's own XLA-reported per-step flops/bytes
        # and peak-footprint estimate (telemetry/xprof; None when no
        # executable was captured). Coverage-checked but non-gating in
        # bench/compare.py — measurement provenance, not a pass bar.
        from multigpu_advectiondiffusion_tpu.telemetry import xprof

        meas = xprof.measured_summary(
            solver, iters, timing.median_seconds
        ) or {}
        row = {
            "metric": metric,
            "value": round(rate, 2),
            "unit": "MLUPS",
            "vs_baseline": round(rate / baseline, 3),
            "spread": round(timing.spread, 4),
            "outliers": timing.outliers,
            # pre-filter dispersion incl. discarded stalls, so
            # the artifact keeps the full evidence (ADVICE r4)
            "raw_spread": round(timing.raw_spread, 4),
            "engaged": engaged["stepper"],
            # comm-avoiding exchange cadence + tuner provenance: a row
            # whose configuration was MEASURED into place says so, and
            # says what the tuner picked (ISSUE 4)
            "steps_per_exchange": engaged.get("steps_per_exchange", 1),
            # halo transport actually engaged (collective ppermute vs
            # in-kernel remote DMA) — sharded rows only ever publish
            # the transport that really ran (ISSUE 13)
            "exchange": engaged.get("exchange", "collective"),
            "tuned": engaged.get("tuned"),
            "roofline_pct": (cost or {}).get("roofline_pct"),
            # measured XLA columns (per step; peak_bytes = executable
            # footprint estimate) beside the modeled roofline_pct
            "xla_flops": meas.get("xla_flops_per_step"),
            "xla_bytes": meas.get("xla_bytes_per_step"),
            "peak_bytes": meas.get("peak_bytes"),
            # single-run rows carry the member count explicitly so the
            # bench gate reads one convention across rounds (older
            # rounds without the field read as 1 — bench/compare.py)
            "ensemble": 1,
        }
        # engagement guard: a row running on an unexpected (slower)
        # stepper is recorded AND fails the run — a silent fallback to
        # generic-xla/per-axis-pallas must not just publish a slow rate.
        # A run that DEGRADED off its requested rung mid-measurement
        # (resilience ladder: Mosaic failure -> lower rung) fails the
        # bench the same way even when the landing rung is in `expect`:
        # the row would otherwise silently record the slower rung's rate
        # under the headline metric name.
        if engaged["stepper"] not in expect or engaged.get("degraded"):
            row["engagement_error"] = {
                "expected": sorted(expect),
                "fallback": engaged["fallback"],
                "degraded": engaged.get("degraded"),
            }
            mismatches.append(metric)
        # tuned-regression guard: a tuner-selected configuration that
        # lands BELOW the reference baseline (BASELINE.md) is a silent
        # regression dressed up as a decision — fail the run, don't
        # just publish it (TPU rows only; CPU mode validates mechanics)
        elif on_tpu and engaged.get("tuned") and rate < baseline:
            row["engagement_error"] = {
                "tuned_below_baseline": {
                    "baseline_mlups": baseline,
                    "tuned": engaged.get("tuned"),
                }
            }
            mismatches.append(metric)
        print(json.dumps(row), flush=True)

    # Multi-chip strong-scaling rows: engage automatically whenever the
    # live topology has > 1 device (the reference's headline artifact is
    # measured 2-GPU scaling, MultiGPU/Diffusion3d_Baseline/Run.m:4-13);
    # a single chip emits nothing. Mechanics are CPU-mesh tested
    # (tests/test_cli.py), so the first real multi-chip session
    # produces scaling numbers with zero new code.
    from multigpu_advectiondiffusion_tpu.bench.scaling import scaling_rows

    for row in scaling_rows(on_tpu=on_tpu):
        # the multichip rows dispatch through impl="auto": the tuner's
        # measured (rung, steps_per_exchange) must not silently regress
        # below the reference's published multi-GPU rate
        if on_tpu and row.get("tuned") and row["vs_baseline"] < 1.0:
            row["engagement_error"] = {
                "tuned_below_baseline": row.get("tuned")
            }
            mismatches.append(row["metric"])
        print(json.dumps(row), flush=True)

    # In-kernel halo exchange head-to-head (ISSUE 13): the dma rung vs
    # the split-overlap collective rung, pinned, on the 2-way z-slab
    # mesh (the reference's own 2-GPU artifact shape). A dma row that
    # SILENTLY degraded off the in-kernel transport fails the run; a
    # config that declined loudly (e.g. no dma-capable backend) is
    # recorded as declined, not failed.
    from multigpu_advectiondiffusion_tpu.bench.scaling import (
        exchange_head_to_head_rows,
    )

    for row in exchange_head_to_head_rows(on_tpu=on_tpu):
        if row.get("engagement_error"):
            mismatches.append(row["metric"])
        print(json.dumps(row), flush=True)

    # Batched-ensemble rows (ISSUE 9): MLUPS*members of one vmapped
    # dispatch vs the looped single-run baseline at B in {1, 8, 64} —
    # engagement-guarded like every other row (a row that silently fell
    # off the vmapped fused rung fails the run, it does not just
    # publish a slow amortization ratio)
    for row, ok in _ensemble_rows(on_tpu):
        if not ok:
            mismatches.append(row["metric"])
        print(json.dumps(row), flush=True)

    # Mesh-scale ensemble row (ISSUE 11): B=64 on the 8-device members
    # mesh through impl="auto" — the tuner measures the batched
    # candidate space at the actual B; the guard fails a row that fell
    # back to one device or served an unmeasured decision
    for row, ok in _ensemble_mesh_rows(on_tpu):
        if not ok:
            mismatches.append(row["metric"])
        print(json.dumps(row), flush=True)

    # Request-serving rows (ISSUE 17): requests/sec, latency
    # percentiles and batch occupancy of the coalesced request server
    # vs a sequential max_batch=1 server over the same request set —
    # guarded on every request being answered and on coalescing
    # actually beating sequential dispatch at B=8
    for row, ok in _serving_rows(on_tpu):
        if not ok:
            mismatches.append(row["metric"])
        print(json.dumps(row), flush=True)

    # Pipelined-serving head-to-head (ISSUE 19): the same request set
    # served synchronous vs pipelined (donated buffers, dispatch-ahead,
    # async publish) — one row per mode with req/s, p50/p99 and the
    # measured device-idle fraction; engagement-guarded on every
    # request answered and the pipeline actually dispatching ahead
    for row, ok in _serving_pipelined_rows(on_tpu):
        if not ok:
            mismatches.append(row["metric"])
        print(json.dumps(row), flush=True)

    if mismatches:
        # forensic dump: the tail of the run's telemetry event stream
        # (dispatch builds, ladder degrades, spans) so a degraded or
        # fell-back row is diagnosable from the bench artifact alone
        print(
            f"engagement guard tripped; last telemetry events "
            f"(full stream: {metrics_path}):",
            file=sys.stderr,
        )
        for ev in sink.tail(30):
            print(json.dumps(ev), file=sys.stderr)
        raise SystemExit(
            "engagement guard: unexpected stepper for "
            + ", ".join(mismatches)
        )


if __name__ == "__main__":
    main()
