"""Benchmark matrix — drivers mirroring the reference's published configs.

Each entry reproduces one row of BASELINE.md (the author's archived
``Run.m`` numbers) with the same grid/iteration workload, and records the
TPU result next to the reference GFLOPS/MLUPS. Replaces the reference's
pitched/texture/shared *memory* variants (no TPU meaning) with the
framework's kernel-strategy axis: pure-XLA vs Pallas (``impl`` field).

Run:  python -m multigpu_advectiondiffusion_tpu.bench [--name X] [--quick]
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Tuple

import jax

from multigpu_advectiondiffusion_tpu.bench.timing import timed_run

# name -> (reference MLUPS, reference source). Single source of truth
# (bench.py imports these). All values are STAGE-update rates
# (cells*iters*3/time) so they divide our stage-counting mlups() metric
# like-for-like; the single-GPU *diffusion* Run.m numbers omit the x3 RK
# factor in the reference's own GFLOPS (BASELINE.md footnote 1), so those
# rows are converted here (x3) rather than quoted raw.
BASELINES_MLUPS = {
    "diffusion2d": (2681.0, "SingleGPU/Diffusion2d_PitchedMem/Run.m:3-12"),
    "diffusion3d": (2782.0, "SingleGPU/Diffusion3d_Blocking/Run.m:3-12"),
    "diffusion3d_multigpu": (731.0, "MultiGPU/Diffusion3d_Baseline/Run.m:4-13"),
    # the reference number IS f64 (USE_FLOAT false) — this row is the
    # apples-to-apples precision comparison
    "diffusion3d_multigpu_f64": (
        731.0, "MultiGPU/Diffusion3d_Baseline/Run.m:4-13"
    ),
    "burgers3d_512": (879.8, "SingleGPU/Burgers3d_WENO5/Run.m:15-25"),
    "burgers3d_512_axis": (879.8, "SingleGPU/Burgers3d_WENO5/Run.m:15-25"),
    "burgers3d_512_xla": (879.8, "SingleGPU/Burgers3d_WENO5/Run.m:15-25"),
    # the reference's WENO7 exists only as MATLAB prototypes
    # (LFWENO7FDM{1,2,3}d.m) with no benchmark; its nearest published
    # config — the same 512^3 viscous workload at order 5 — anchors the
    # row so the (heavier) order-7 rate is read against a real number
    "burgers3d_512_weno7": (879.8, "SingleGPU/Burgers3d_WENO5/Run.m:15-25"),
    # 1601*986*35*1067*3/563.49 s
    "burgers3d_slab": (313.9, "SingleGPU/Burgers3d_WENO5/Run.m:3-13"),
    # 1000*1000*200*167*3/247.54 s
    "burgers3d_wide": (404.8, "SingleGPU/Burgers3d_WENO5/Run.m:27-37"),
    "burgers2d_multigpu": (15.5, "MultiGPU/Burgers2d_Baseline/Run.m:4-14"),
    # 2-D order 7 has the same MATLAB-only status as 3-D
    # (LFWENO7FDM2d.m, never benchmarked); anchored on the same 2-D
    # workload's published order-5 number
    "burgers2d_weno7": (15.5, "MultiGPU/Burgers2d_Baseline/Run.m:4-14"),
    "burgers3d_multigpu": (37.9, "MultiGPU/Burgers3d_Baseline/Run.m:4-14"),
    # The reference never shipped the title ADR workload (its name
    # notwithstanding) — no published number exists. These rows anchor
    # on the diffusion baselines for the same grid class so vs_baseline
    # reads "vs the nearest published reference rate", explicitly NOT
    # a same-physics comparison (ISSUE 15).
    "adr3d": (731.0,
              "anchor: MultiGPU/Diffusion3d_Baseline/Run.m:4-13 (no "
              "reference ADR exists; nearest published 3-D rate)"),
    "adr2d": (2681.0,
              "anchor: SingleGPU/Diffusion2d_PitchedMem/Run.m:3-12 (no "
              "reference ADR exists; nearest published 2-D rate)"),
}


@dataclasses.dataclass(frozen=True)
class BenchCase:
    name: str
    kind: str  # diffusion | burgers
    grid_xyz: Tuple[int, ...]
    iters: int
    quick_scale: int = 4  # divide grid/iters by this in --quick mode
    weno_order: int = 5
    fixed_dt: bool = True  # reference parity: CUDA drivers fix dt
    nu: float = 0.0  # single-GPU Burgers are viscous (main.cpp:56-59)
    # kernel-strategy rung (f32 only; other dtypes run XLA): "pallas"
    # engages the fused steppers, "pallas_axis" pins the per-axis slab
    # kernels, "xla" the shifted-slice stencils — the ladder axis that
    # replaces the reference's pitched/texture/shared variants.
    impl: str = "pallas"
    # per-case precision (the --dtype flag overrides it for every case);
    # "float64" rows quantify the TPU's emulated-f64 cost against the
    # reference's only precision (USE_FLOAT false, DiffusionMPICUDA.h:66)
    dtype: str = "float32"


CASES = [
    # reference grids rounded to TPU-friendly multiples where needed
    BenchCase("diffusion2d", "diffusion", (1024, 1024), 1000),
    BenchCase("diffusion3d", "diffusion", (208, 200, 200), 605),
    BenchCase("diffusion3d_multigpu", "diffusion", (400, 200, 208), 101),
    # the reference's only precision, on the same literal grid: measures
    # the emulated-f64 cost ratio on TPU (no native f64 VPU path)
    BenchCase("diffusion3d_multigpu_f64", "diffusion", (400, 200, 208), 31,
              dtype="float64"),
    BenchCase("burgers3d_512", "burgers", (512, 512, 512), 86, nu=1e-5),
    # explicit slower rungs of the same flagship config (the reference
    # benches its non-winning variants too, RunAll.m)
    BenchCase("burgers3d_512_axis", "burgers", (512, 512, 512), 21,
              impl="pallas_axis", nu=1e-5),
    BenchCase("burgers3d_512_xla", "burgers", (512, 512, 512), 21,
              impl="xla", nu=1e-5),
    # order-7 rung of the fused family (halo 4), same flagship workload
    BenchCase("burgers3d_512_weno7", "burgers", (512, 512, 512), 40,
              weno_order=7, nu=1e-5),
    # the other two published single-GPU viscous-Burgers workloads
    # (Run.m:3-13 / :27-37); literal grids, reduced iteration counts
    # (MLUPS is a rate — the reference ran 1067x3 / 167x3 stages)
    BenchCase("burgers3d_slab", "burgers", (1601, 986, 35), 60, nu=1e-5),
    BenchCase("burgers3d_wide", "burgers", (1000, 1000, 200), 60, nu=1e-5),
    BenchCase("burgers2d_multigpu", "burgers", (400, 408), 200),
    # 2-D order-7 rung (halo-4 whole-run stepper), same 2-D workload
    BenchCase("burgers2d_weno7", "burgers", (400, 408), 200, weno_order=7),
    BenchCase("burgers3d_multigpu", "burgers", (400, 400, 408), 267),
    # the title workload (ISSUE 15): variable-K advection–diffusion–
    # reaction; 3-D rides the fused per-stage rung, 2-D the generic
    BenchCase("adr3d", "adr", (208, 200, 200), 300),
    BenchCase("adr2d", "adr", (1024, 1024), 400, impl="xla"),
]


@dataclasses.dataclass(frozen=True)
class EnsembleBenchCase:
    """One batched-ensemble row (ISSUE 9): B members advanced by ONE
    vmapped dispatch, reported as MLUPS*members next to the looped
    single-run baseline (``vs_looped``)."""

    name: str
    kind: str  # diffusion | burgers
    grid_xyz: Tuple[int, ...]
    iters: int
    members: int
    quick_scale: int = 4
    impl: str = "pallas"
    nu: float = 0.0


ENSEMBLE_CASES = [
    EnsembleBenchCase("ensemble_diffusion3d_b8", "diffusion",
                      (128, 128, 64), 60, 8),
    EnsembleBenchCase("ensemble_diffusion3d_b64", "diffusion",
                      (128, 128, 64), 20, 64),
    EnsembleBenchCase("ensemble_burgers3d_b8", "burgers",
                      (64, 64, 64), 30, 8, nu=1e-5),
]


@dataclasses.dataclass(frozen=True)
class PrecisionBenchCase:
    """One error-vs-speed precision row (ISSUE 16): the SAME workload
    timed at native f32 and at ``precision='bf16'`` (bf16 storage /
    f32 compute), recording the bf16 rung's MLUPS next to the native
    rate AND both runs' solution error — a speedup the science cannot
    cash is a regression, so the row carries the evidence for the
    per-dtype gate (``out/precision_gate.sh`` /
    ``diagnostics/compare``) right next to the rate."""

    name: str
    kind: str  # diffusion | burgers | adr
    grid_xyz: Tuple[int, ...]
    iters: int
    quick_scale: int = 4
    impl: str = "pallas"
    weno_order: int = 5
    fixed_dt: bool = True  # bf16 Burgers requires fixed dt
    nu: float = 0.0


PRECISION_CASES = [
    PrecisionBenchCase("precision_diffusion3d", "diffusion",
                       (208, 200, 200), 151),
    # Burgers' bf16 rung is the whole-run slab (per-stage WENO has no
    # split-dtype machinery) — pinned so a silent per-stage fallback
    # cannot masquerade as the bf16 measurement
    PrecisionBenchCase("precision_burgers3d", "burgers",
                       (256, 256, 256), 40, impl="pallas_slab",
                       nu=1e-5),
    PrecisionBenchCase("precision_adr3d", "adr", (208, 200, 200), 100),
]


def run_precision_case(case: PrecisionBenchCase, quick: bool = False,
                       repeats: int = 3) -> dict:
    """Time one workload at native f32 AND at ``precision='bf16'``,
    and record both runs' error: the analytic L1/L2/Linf norms where
    the family has them (diffusion/ADR heat-kernel workloads), plus
    the bf16 trajectory's relative L2 distance from the native one
    (always available — Burgers has no analytic 3-D solution). The
    row's gated value is the bf16 ``mlups``; the error fields are the
    science evidence the precision gate reads."""
    import jax.numpy as jnp

    from multigpu_advectiondiffusion_tpu.core.grid import Grid
    from multigpu_advectiondiffusion_tpu.models import registry
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    grid_xyz = case.grid_xyz
    iters = case.iters
    if quick:
        grid_xyz = tuple(max(16, g // case.quick_scale) for g in grid_xyz)
        iters = max(3, iters // case.quick_scale)
    grid = Grid.make(*grid_xyz, lengths=[10.0] * len(grid_xyz))
    spec = registry.get(case.kind)
    cfg32 = spec.bench_build(grid, "float32", case.impl, case)
    cfg16 = dataclasses.replace(cfg32, precision="bf16")

    rows = {}
    outs = {}
    for label, cfg in (("native", cfg32), ("bf16", cfg16)):
        solver = spec.solver_cls(cfg)
        state = solver.initial_state()
        timed = timed_run(solver, state, iters, reps=repeats)
        outs[label] = (solver, solver.run(state, iters))
        cells = 1
        for g in grid_xyz:
            cells *= g
        rows[label] = {
            "engaged": solver.engaged_path()["stepper"],
            "storage_dtype": str(solver.storage_dtype),
            "seconds": round(timed.seconds, 4),
            "spread": round(timed.spread, 4),
            "mlups": round(
                mlups(cells, iters, STAGES[cfg.integrator], timed.seconds),
                1,
            ),
        }

    s16, out16 = outs["bf16"]
    s32, out32 = outs["native"]
    ref = float(jnp.linalg.norm(out32.u.astype(jnp.float32).ravel()))
    dist = float(jnp.linalg.norm(
        (out16.u.astype(jnp.float32) - out32.u.astype(jnp.float32)).ravel()
    ))
    result = {
        "name": case.name,
        "grid": "x".join(map(str, grid_xyz)),
        "iters": iters,
        "dtype": "float32",
        "precision": "bf16",
        "storage_dtype": rows["bf16"]["storage_dtype"],
        "impl": case.impl,
        "engaged": rows["bf16"]["engaged"],
        "seconds": rows["bf16"]["seconds"],
        "spread": rows["bf16"]["spread"],
        "mlups": rows["bf16"]["mlups"],
        "native_engaged": rows["native"]["engaged"],
        "native_mlups": rows["native"]["mlups"],
        "native_seconds": rows["native"]["seconds"],
        "speedup_vs_native": (
            round(rows["native"]["seconds"] / rows["bf16"]["seconds"], 3)
            if rows["bf16"]["seconds"]
            else None
        ),
        # bf16 trajectory vs the native one, relative L2 — nonzero by
        # construction (storage rounding), gated by the precision
        # gate's per-dtype band, never by the MLUPS thresholds
        "vs_native_rel_l2": round(dist / ref, 8) if ref else None,
        "ensemble": 1,
        "quick": quick,
    }
    for label, (solver, out) in outs.items():
        if hasattr(solver, "error_norms"):
            try:
                norms = solver.error_norms(out)
            except ValueError:
                # workloads without a closed form (variable-K ADR,
                # Burgers) gate on vs_native_rel_l2 instead
                continue
            key = "error" if label == "bf16" else "native_error"
            result[f"{key}_l2"] = round(float(norms.l2), 10)
            result[f"{key}_linf"] = round(float(norms.linf), 10)
    return result


def run_ensemble_case(case: EnsembleBenchCase, quick: bool = False,
                      repeats: int = 3) -> dict:
    """Time one batched-ensemble case: B members in ONE vmapped
    dispatch, plus the looped single-run baseline on the same compiled
    single program. Value convention: ``mlups`` is MLUPS*members (the
    batch's aggregate stage-update rate), so the bench gate diffs it
    like every other row."""
    import statistics
    import time

    import jax.numpy as jnp

    from multigpu_advectiondiffusion_tpu.bench.timing import sync
    from multigpu_advectiondiffusion_tpu.core.grid import Grid
    from multigpu_advectiondiffusion_tpu.models import registry
    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )
    from multigpu_advectiondiffusion_tpu.models.state import SolverState
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    grid_xyz = case.grid_xyz
    iters = case.iters
    if quick:
        grid_xyz = tuple(max(8, g // case.quick_scale) for g in grid_xyz)
        iters = max(2, iters // case.quick_scale)
    grid = Grid.make(*grid_xyz, lengths=[2.0] * len(grid_xyz))
    # family config via the registry's bench hook; the width-swept
    # gaussian IC is the ensemble rows' common member-varying workload
    spec = registry.get(case.kind)
    cls = spec.solver_cls
    cfg = dataclasses.replace(
        spec.bench_build(grid, "float32", case.impl, case),
        ic="gaussian",
    )
    members = [
        {"ic_params": (("width", 0.1 + 0.002 * i),)}
        for i in range(case.members)
    ]
    es = EnsembleSolver(cls, cfg, members)
    est = es.initial_state()

    def wall(fn):
        sync(fn())  # compile + warm-up, untimed
        times = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            sync(fn())
            times.append(time.perf_counter() - t0)
        med = statistics.median(times)
        return med, (max(times) - min(times)) / med if med > 0 else 0.0

    batched_s, spread = wall(lambda: es.run(est, iters).u)
    single = es.member_solver(0)

    def looped():
        return jnp.stack([
            single.run(
                SolverState(u=est.u[i], t=est.t[i], it=est.it[i]), iters
            ).u
            for i in range(case.members)
        ])

    looped_s, _ = wall(looped)
    engaged = es.engaged_path()
    cells = 1
    for g in grid_xyz:
        cells *= g
    rate = mlups(cells * case.members, iters, STAGES[cfg.integrator],
                 batched_s)
    return {
        "name": case.name,
        "grid": "x".join(map(str, grid_xyz)),
        "iters": iters,
        "dtype": "float32",
        "impl": case.impl,
        "ensemble": case.members,
        "engaged": engaged["stepper"],
        "seconds": round(batched_s, 4),
        "spread": round(spread, 4),
        "mlups": round(rate, 1),
        "looped_seconds": round(looped_s, 4),
        "vs_looped": round(looped_s / batched_s, 3) if batched_s else None,
        "tuned": engaged.get("tuned"),
        "quick": quick,
    }


def resolve_impl(case: BenchCase, dtype: str,
                 mesh_spec: Optional[str] = None) -> str:
    """Kernel strategy actually benchmarked: the Pallas rungs' DMA tiling
    is f32-calibrated, so non-f32 dtypes run XLA — EXCEPT 3-D diffusion
    f64, which rides the fused f32 kernels through the
    f64-storage/f32-compute convention (the solver's own eligibility
    gate; non-eligible configs still land on the generic path and the
    'engaged' field says so). Multichip f32 rows (``--mesh``, e.g. the
    burgers3d_multigpu / split-overlap cases) route ``pallas`` through
    ``auto`` so the measured tuner picks the rung and the
    communication-avoiding ``steps_per_exchange`` from its decision
    cache. One definition — the JSON 'impl' field and the constructed
    solver must never diverge."""
    if dtype == "float32":
        if mesh_spec and case.impl == "pallas":
            return "auto"
        return case.impl
    if dtype == "float64" and case.kind == "diffusion" and len(
        case.grid_xyz
    ) == 3:
        return case.impl
    return "xla"


def build_solver(case: BenchCase, dtype: str, grid_xyz, mesh_spec: Optional[str]):
    """Case -> solver, resolved through the plugin registry: the
    family's ``bench_build`` hook constructs the config, so a third
    model brings its own bench cases without touching this function
    (ISSUE 15)."""
    from multigpu_advectiondiffusion_tpu.cli.drivers import (
        decomposition_for,
        parse_mesh_spec,
    )
    from multigpu_advectiondiffusion_tpu.core.grid import Grid
    from multigpu_advectiondiffusion_tpu.models import registry

    grid = Grid.make(*grid_xyz, lengths=[10.0] * len(grid_xyz))
    mesh, sizes = parse_mesh_spec(mesh_spec)
    decomp = decomposition_for(grid, sizes)
    impl = resolve_impl(case, dtype, mesh_spec)
    spec = registry.get(case.kind)
    cfg = spec.bench_build(grid, dtype, impl, case)
    return spec.solver_cls(cfg, mesh=mesh, decomp=decomp)


def run_case(
    case: BenchCase,
    dtype: Optional[str] = None,
    quick: bool = False,
    mesh_spec: Optional[str] = None,
    repeats: int = 3,
) -> dict:
    dtype = dtype or case.dtype
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import STAGES
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    grid_xyz = case.grid_xyz
    iters = case.iters
    if quick:
        grid_xyz = tuple(max(16, g // case.quick_scale) for g in grid_xyz)
        iters = max(3, iters // case.quick_scale)

    solver = build_solver(case, dtype, grid_xyz, mesh_spec)
    state = solver.initial_state()

    timed = timed_run(solver, state, iters, reps=repeats)
    best = timed.seconds
    engaged = solver.engaged_path()
    # warm-up = compile + one full execution of the benchmarked program
    compile_s = max(timed.warmup_seconds - best, 0.0)

    cells = 1
    for g in grid_xyz:
        cells *= g
    rate = mlups(cells, iters, STAGES[solver.cfg.integrator], best)
    base, src = BASELINES_MLUPS.get(case.name, (None, None))
    # roofline efficiency on the engaged rung's static bytes/FLOPs model
    from multigpu_advectiondiffusion_tpu.telemetry import costmodel

    cost = costmodel.summarize_run(solver, engaged["stepper"], iters, best)
    # measured introspection beside the modeled roofline: the compiled
    # executable's own XLA-reported per-step numbers (telemetry/xprof)
    from multigpu_advectiondiffusion_tpu.telemetry import xprof

    meas = xprof.measured_summary(solver, iters, best) or {}
    result = {
        "name": case.name,
        "grid": "x".join(map(str, grid_xyz)),
        "iters": iters,
        "dtype": dtype,
        "impl": resolve_impl(case, dtype, mesh_spec),
        # which stepper rung actually executed (fused-whole-run-slab /
        # fused-whole-run / fused-stage / ... / generic-xla) — a row
        # that silently fell off the fused ladder is visible in the
        # artifact, not just slow (bench.py's engagement guard is the
        # hard-failing counterpart for the headline rows)
        "engaged": engaged["stepper"],
        # comm-avoiding cadence in effect + tuner provenance (non-None
        # exactly when impl resolved through "auto")
        "steps_per_exchange": engaged.get("steps_per_exchange", 1),
        # halo transport actually engaged: collective ppermute or the
        # in-kernel remote-DMA ring (ISSUE 13)
        "exchange": engaged.get("exchange", "collective"),
        # storage-precision provenance (ISSUE 16): rows predating the
        # fields read as native/compute-dtype in bench/compare.py
        "precision": engaged.get("precision", "native"),
        "storage_dtype": engaged.get("storage_dtype", dtype),
        "tuned": engaged.get("tuned"),
        "seconds": round(best, 4),
        "compile_seconds": round(compile_s, 3),
        "mlups": round(rate, 1),
        "roofline_pct": (cost or {}).get("roofline_pct"),
        # measured XLA columns (coverage-checked, non-gating in
        # bench/compare.py): per-step flops/bytes the compiled
        # executable reports, and its peak-footprint estimate
        "xla_flops": meas.get("xla_flops_per_step"),
        "xla_bytes": meas.get("xla_bytes_per_step"),
        "peak_bytes": meas.get("peak_bytes"),
        # single-run rows carry the member count explicitly (older
        # rounds without the field read as 1 — bench/compare.py)
        "ensemble": 1,
        "quick": quick,
        "mesh": mesh_spec,
    }
    if engaged.get("degraded"):
        # a mid-measurement kernel-ladder downgrade (Mosaic failure ->
        # slower rung) is recorded in the artifact; bench.py's guard is
        # the hard-failing counterpart
        result["degraded"] = engaged["degraded"]
    if base and not quick:
        result["reference_mlups"] = base
        result["vs_reference"] = round(rate / base, 3)
        result["reference_source"] = src
    return result


def main(argv=None):
    import argparse


    ap = argparse.ArgumentParser(prog="multigpu_advectiondiffusion_tpu.bench")
    ap.add_argument("--name", default=None,
                    help="run one case (default: all)")
    ap.add_argument("--dtype", default=None,
                    help="override every case's precision (default: "
                         "per-case, float32 unless the row says f64)")
    ap.add_argument("--quick", action="store_true",
                    help="shrunken grids for smoke-benching")
    ap.add_argument("--mesh", default=None, help="e.g. dz=4")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None, help="write JSON lines here")
    ap.add_argument("--compare", default=None, metavar="PRIOR",
                    help="regression gate: after the run, diff the "
                         "produced rows against a prior round's "
                         "artifact (bench/compare.py thresholds) and "
                         "exit nonzero on any regression")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="tuning decision cache for the impl='auto' "
                         "multichip rows (default: $TPUCFD_TUNING_CACHE "
                         "or the user cache dir)")
    args = ap.parse_args(argv)

    # multichip rows dispatch through impl="auto": enable measurement so
    # a cache miss tunes (and persists) instead of falling back
    from multigpu_advectiondiffusion_tpu import tuning

    tuning.configure(cache_path=args.tuning_cache, enabled=True)

    cases = [c for c in CASES if args.name is None or c.name == args.name]
    ens_cases = [
        c for c in ENSEMBLE_CASES
        if args.name is None or c.name == args.name
    ]
    prec_cases = [
        c for c in PRECISION_CASES
        if args.name is None or c.name == args.name
    ]
    if not cases and not ens_cases and not prec_cases:
        raise SystemExit(
            f"no case {args.name!r}; have "
            f"{[c.name for c in CASES + ENSEMBLE_CASES + PRECISION_CASES]}"
        )
    lines = []
    for case in cases:
        # x64 scoped per case: a process-wide flip would poison
        # the f32 Pallas rows' Mosaic lowering with i64 constants. The
        # resolved dtype is passed down so the scope and the solver
        # can't diverge.
        dtype = args.dtype or case.dtype
        with jax.enable_x64(dtype == "float64"):
            res = run_case(case, dtype=dtype, quick=args.quick,
                           mesh_spec=args.mesh, repeats=args.repeats)
        line = json.dumps(res)
        print(line, flush=True)
        lines.append(line)
    for case in ens_cases:
        # batched-ensemble rows (ISSUE 9): the ensemble engine declines
        # meshes, so these never take --mesh; f32 only
        res = run_ensemble_case(case, quick=args.quick,
                                repeats=args.repeats)
        line = json.dumps(res)
        print(line, flush=True)
        lines.append(line)
    for case in prec_cases:
        # error-vs-speed precision rows (ISSUE 16): f32-facing configs
        # (no x64 scoping), single-run only — never take --mesh
        res = run_precision_case(case, quick=args.quick,
                                 repeats=args.repeats)
        line = json.dumps(res)
        print(line, flush=True)
        lines.append(line)
    if args.out:
        from multigpu_advectiondiffusion_tpu.utils.io import (
            atomic_write_text,
        )

        # atomic publish: bench/compare.py gates against this file —
        # it must never read a half-written round
        atomic_write_text(args.out, "\n".join(lines) + "\n")
    if args.compare:
        # measured regression gate: this run's rows against the prior
        # round, per-row noise thresholds, loud nonzero exit
        from multigpu_advectiondiffusion_tpu.bench import compare as cmp

        new_rows = {}
        for line in lines:
            row = json.loads(line)
            key = cmp.row_key(row)
            if key and cmp.row_value(row) is not None:
                new_rows[key] = row
        # --name may have subsetted the cases: gate only what ran (the
        # full-round coverage check lives in out/bench_gate.sh)
        old_rows = {
            k: v for k, v in cmp.load_rows(args.compare).items()
            if k in new_rows
        }
        result = cmp.compare(new_rows, old_rows)
        print(result.format_text(), flush=True)
        if not result.ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
