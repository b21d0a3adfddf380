"""Turnkey multi-chip strong-scaling rows.

The reference's headline artifact is *measured two-GPU scaling* of the
MultiGPU baselines (``MultiGPU/Diffusion3d_Baseline/Run.m:4-13`` — 5.87
GFLOPS on 2 GPUs over z-slabs; ``Burgers3d_Baseline/Run.m:4-14``). This
module is the standing equivalent: :func:`scaling_rows` measures the
same published global grids sharded over ``dz = 2..N`` z-slabs whenever
the live topology has more than one device, and returns nothing on one
chip — so the first session on real multi-chip hardware produces
scaling numbers with zero new code (``bench.py`` calls it on every run).

Strong scaling, deliberately: the reference holds the global grid fixed
and splits it over ranks (``main.c:84-101``), so per-chip MLUPS directly
exposes the halo-exchange tax the split-overlap schedule is designed to
hide. Each row reports the aggregate rate, the per-chip rate, and the
halo schedule actually engaged (``engaged_path``), and divides
``vs_baseline`` by the reference's own 2-GPU number — the ``dz=2`` row
is the apples-to-apples comparison, higher ``dz`` rows chart scaling
the reference never published.
"""

from __future__ import annotations

from typing import Sequence

from multigpu_advectiondiffusion_tpu.bench.matrix import BASELINES_MLUPS
from multigpu_advectiondiffusion_tpu.bench.timing import timed_run
from multigpu_advectiondiffusion_tpu.utils.metrics import mlups


def candidate_counts(n_devices: int, nz: int) -> list:
    """Slab counts to measure: powers of two up to the device count,
    plus the full count itself (even or odd), each restricted to
    divisors of the global z extent (the reference's own divisibility
    rule, ``main.c:88``)."""
    out = []
    d = 2
    while d <= n_devices:
        if nz % d == 0:
            out.append(d)
        d *= 2
    if n_devices >= 2 and n_devices not in out and nz % n_devices == 0:
        out.append(n_devices)
    return out


def _configs(on_tpu: bool):
    """The two published MultiGPU 3-D workloads (matrix.py's z-rounded
    grids), shrunk on CPU where the fused kernels run interpreted."""
    from multigpu_advectiondiffusion_tpu import (
        BurgersConfig,
        DiffusionConfig,
        Grid,
    )

    if on_tpu:
        dgrid = Grid.make(400, 200, 208, lengths=(10.0, 5.0, 5.2))
        bgrid = Grid.make(400, 400, 408, lengths=2.0)
        diters, biters = 606, 60
    else:
        dgrid = Grid.make(16, 16, 24, lengths=2.0)
        bgrid = Grid.make(16, 16, 24, lengths=2.0)
        diters, biters = 4, 4
    # impl="auto": the multichip rows dispatch through the measured
    # tuner — rung AND steps_per_exchange (the comm-avoiding k-step
    # cadence) come from the persisted decision cache, measured on a
    # miss when tuning is enabled (bench.py enables it)
    return {
        "diffusion3d": (
            DiffusionConfig(grid=dgrid, dtype="float32", impl="auto",
                            overlap="split"),
            diters,
            BASELINES_MLUPS["diffusion3d_multigpu"][0],
        ),
        "burgers3d": (
            BurgersConfig(grid=bgrid, dtype="float32", adaptive_dt=False,
                          impl="auto", overlap="split"),
            biters,
            BASELINES_MLUPS["burgers3d_multigpu"][0],
        ),
    }


def scaling_rows(
    devices: Sequence | None = None,
    on_tpu: bool | None = None,
    models: Sequence[str] = ("diffusion3d", "burgers3d"),
    reps: int = 5,
) -> list:
    """Measure z-slab strong scaling on the live topology.

    Returns a list of JSON-ready row dicts (empty on a single device):
    ``metric`` = ``{model}_scale_dz{d}_mlups``, ``value`` (aggregate
    MLUPS over ``d`` chips), ``per_chip``, ``devices``, ``spread``,
    ``outliers``, ``raw_spread``, ``engaged`` (stepper + halo schedule
    in effect), and ``vs_baseline`` against the reference's published
    2-GPU rate for the same workload.
    """
    import jax

    from multigpu_advectiondiffusion_tpu.models import registry
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )

    devices = list(devices if devices is not None else jax.devices())
    if on_tpu is None:
        on_tpu = devices[0].platform == "tpu"
    rows = []
    if len(devices) < 2:
        return rows
    configs = _configs(on_tpu)
    for model in models:
        cfg, iters, baseline = configs[model]
        # run names resolve to solver families through the registry
        solver_cls = registry.solver_for_run_name(model)
        nz = cfg.grid.shape[0]
        for d in candidate_counts(len(devices), nz):
            mesh = make_mesh({"dz": d}, devices=devices[:d])
            solver = solver_cls(
                cfg, mesh=mesh, decomp=Decomposition.slab("dz")
            )
            engaged = solver.engaged_path("iters")
            timing = timed_run(solver, solver.initial_state(), iters,
                               reps=reps)
            stages = STAGES.get(cfg.integrator, 3)
            rate = mlups(cfg.grid.num_cells, iters, stages,
                         timing.median_seconds)
            rows.append(
                {
                    "metric": f"{model}_scale_dz{d}_mlups",
                    "value": round(rate, 2),
                    "unit": "MLUPS",
                    "vs_baseline": round(rate / baseline, 3),
                    "per_chip": round(rate / d, 2),
                    "devices": d,
                    "spread": round(timing.spread, 4),
                    "outliers": timing.outliers,
                    "raw_spread": round(timing.raw_spread, 4),
                    "engaged": (
                        engaged["stepper"]
                        + (
                            f"+{engaged['overlap']}"
                            if engaged.get("overlap")
                            else ""
                        )
                    ),
                    # the comm-avoiding cadence + where the decision
                    # came from (tuner cache/measurement/heuristic)
                    "steps_per_exchange": engaged.get(
                        "steps_per_exchange", 1
                    ),
                    # halo transport actually engaged (ISSUE 13)
                    "exchange": engaged.get("exchange", "collective"),
                    "tuned": engaged.get("tuned"),
                }
            )
    return rows


def exchange_head_to_head_rows(
    devices: Sequence | None = None,
    on_tpu: bool | None = None,
    models: Sequence[str] = ("diffusion3d", "burgers3d"),
    reps: int = 5,
) -> list:
    """The dma-vs-split-overlap halo-transport head-to-head
    (ISSUE 13): the same workload, same 2-way z-slab mesh (the
    reference's own 2-GPU artifact shape), pinned to the slab rung —
    once with the split-overlap XLA collective exchange, once with the
    in-kernel remote-DMA exchange. Metric pair
    ``{model}_dz2_halo_{split|dma}_mlups``.

    Engagement guard: the dma row must have ACTUALLY run the in-kernel
    transport — a silent degrade back to the collective exchange gets
    an ``engagement_error`` (bench.py fails the run on it). A loud
    decline (a backend with neither the Mosaic TPU target nor the CPU
    interpret simulator) is recorded as a ``declined`` row instead:
    unservable is a fact, not a regression.
    """
    import dataclasses

    import jax

    from multigpu_advectiondiffusion_tpu.models import registry
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )

    devices = list(devices if devices is not None else jax.devices())
    if on_tpu is None:
        on_tpu = devices[0].platform == "tpu"
    rows = []
    if len(devices) < 2:
        return rows
    configs = _configs(on_tpu)
    for model in models:
        cfg, iters, baseline = configs[model]
        if cfg.grid.shape[0] % 2:
            continue
        solver_cls = registry.solver_for_run_name(model)
        pair = (
            ("split", dataclasses.replace(
                cfg, impl="pallas_slab", overlap="split",
                exchange="collective",
            )),
            ("dma", dataclasses.replace(
                cfg, impl="pallas_slab", overlap="padded",
                exchange="dma",
            )),
        )
        for name, pcfg in pair:
            metric = f"{model}_dz2_halo_{name}_mlups"
            try:
                solver = solver_cls(
                    pcfg,
                    mesh=make_mesh({"dz": 2}, devices=devices[:2]),
                    decomp=Decomposition.slab("dz"),
                )
                engaged = solver.engaged_path("iters")
                timing = timed_run(
                    solver, solver.initial_state(), iters, reps=reps
                )
            except ValueError as exc:
                rows.append({
                    "metric": metric,
                    "declined": f"{exc}"[:200],
                })
                continue
            stages = STAGES.get(pcfg.integrator, 3)
            rate = mlups(pcfg.grid.num_cells, iters, stages,
                         timing.median_seconds)
            row = {
                "metric": metric,
                "value": round(rate, 2),
                "unit": "MLUPS",
                "vs_baseline": round(rate / baseline, 3),
                "per_chip": round(rate / 2, 2),
                "devices": 2,
                "spread": round(timing.spread, 4),
                "outliers": timing.outliers,
                "raw_spread": round(timing.raw_spread, 4),
                "engaged": (
                    engaged["stepper"]
                    + (f"+{engaged['overlap']}"
                       if engaged.get("overlap") else "")
                ),
                "steps_per_exchange": engaged.get(
                    "steps_per_exchange", 1
                ),
                "exchange": engaged.get("exchange", "collective"),
            }
            if name == "dma" and (
                engaged.get("exchange") != "dma"
                or engaged.get("degraded")
            ):
                row["engagement_error"] = {
                    "expected_exchange": "dma",
                    "engaged_exchange": engaged.get("exchange"),
                    "degraded": engaged.get("degraded"),
                }
            rows.append(row)
    return rows
