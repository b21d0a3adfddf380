"""Engaged-path regression guard for the benchmark configs.

Every bench row promises a rung of the stepper ladder; a refactor that
silently drops a config to generic-xla/per-axis-pallas would otherwise
just publish a slow rate. bench.py enforces this at run time (the
engagement guard fails the run); this test enforces it at suite time —
WITHOUT timing anything, just by building each row's solver and asking
``engaged_path``.
"""

import importlib.util
import os

import jax


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_module():
    spec = importlib.util.spec_from_file_location(
        "bench_artifact", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_rows_engage_expected_steppers():
    """Each bench.py row's solver must engage a stepper from its
    expected set (CPU grids here; the TPU grids may legitimately sit on
    the other member of a {slab, stage} pair, never below it)."""
    bench = _bench_module()
    rows = bench._cases(on_tpu=False)
    assert len(rows) >= 13
    seen = {}
    for metric, make_solver, mode, work, baseline, expect in rows:
        with jax.enable_x64(metric.endswith("_f64_mlups")):
            solver = make_solver()
            engaged = solver.engaged_path(
                "t_end" if mode == "t_end" else "iters"
            )
        assert engaged["stepper"] in expect, (
            metric, engaged["stepper"], engaged["fallback"]
        )
        # every row publishes its exchange cadence (ISSUE 4: bench rows
        # gain steps_per_exchange + tuner-provenance fields); the
        # single-chip pinned rows run the per-step cadence untuned
        assert engaged.get("steps_per_exchange") == 1, metric
        assert engaged.get("tuned") is None, metric
        seen[metric] = engaged["stepper"]
    # the slab-run round's acceptance rows: the 3-D headline Burgers
    # config and the f64 diffusion row must ride a fused path on the
    # CPU grids — specifically the new slab whole-run stepper
    assert seen["burgers3d_mlups"] == "fused-whole-run-slab"
    assert seen["diffusion3d_f64_mlups"] == "fused-whole-run-slab"
    # the pinned explicit rungs stay pinned
    assert seen["burgers3d_axis_mlups"] == "per-axis-pallas"


def test_bench_matrix_cases_report_engaged():
    """bench/matrix.py rows carry the engaged stepper in the artifact;
    the fused-impl cases must sit on the fused ladder (CPU-quick
    grids), and the f64 diffusion case must no longer report
    generic-xla."""
    from multigpu_advectiondiffusion_tpu.bench.matrix import (
        CASES,
        build_solver,
        resolve_impl,
    )

    for case in CASES:
        dtype = case.dtype
        grid_xyz = tuple(
            max(16, g // case.quick_scale) for g in case.grid_xyz
        )
        with jax.enable_x64(dtype == "float64"):
            solver = build_solver(case, dtype, grid_xyz, None)
            engaged = solver.engaged_path()["stepper"]
        impl = resolve_impl(case, dtype)
        if impl == "pallas":
            assert engaged.startswith("fused-"), (case.name, engaged)
        elif impl == "pallas_axis":
            assert engaged == "per-axis-pallas", (case.name, engaged)
        if case.name == "diffusion3d_multigpu_f64":
            assert engaged != "generic-xla", engaged


def test_matrix_multichip_rows_route_through_auto():
    """With a --mesh spec the f32 pallas cases dispatch through the
    measured tuner (impl='auto'); single-chip and explicitly pinned
    rows are untouched (ISSUE 4 satellite)."""
    from multigpu_advectiondiffusion_tpu.bench.matrix import (
        CASES,
        resolve_impl,
    )

    by_name = {c.name: c for c in CASES}
    b3 = by_name["burgers3d_multigpu"]
    assert resolve_impl(b3, "float32", "dz=2") == "auto"
    assert resolve_impl(b3, "float32", None) == "pallas"
    assert resolve_impl(b3, "float32") == "pallas"  # legacy signature
    assert resolve_impl(
        by_name["burgers3d_512_axis"], "float32", "dz=2"
    ) == "pallas_axis"
    assert resolve_impl(
        by_name["diffusion3d_multigpu_f64"], "float64", "dz=2"
    ) == "pallas"


def test_scaling_configs_use_measured_dispatch():
    """The strong-scaling rows (the only standing multichip bench
    surface) dispatch through impl='auto' so a real multichip session
    tunes rung + steps_per_exchange instead of guessing."""
    from multigpu_advectiondiffusion_tpu.bench.scaling import _configs

    for cfg, _, _ in _configs(on_tpu=False).values():
        assert cfg.impl == "auto", cfg


def test_bench_ensemble_rows_engage_vmapped_steppers():
    """The ensemble_* rows' batched dispatch must ride the promised
    vmapped inner rung (ISSUE 9) — at B=2/1 iter so no timing is paid,
    just the dispatch record."""
    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )

    bench = _bench_module()
    fams = bench._ensemble_cases(on_tpu=False)
    assert len(fams) >= 3
    for family, make_case, expect in fams:
        solver_cls, cfg, _iters, member_fn = make_case()
        es = EnsembleSolver(solver_cls, cfg,
                            [member_fn(i) for i in range(2)])
        es.run(es.initial_state(), 1)
        engaged = es.engaged_path()
        assert engaged["stepper"] in expect, (family, engaged)
        assert engaged["ensemble"] == 2, family
