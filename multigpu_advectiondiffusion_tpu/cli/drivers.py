"""Shared CLI run driver — the re-design of the reference's per-project
``main.c``/``main.cpp`` drivers and ``Run.m`` harnesses (SURVEY §3.1, §3.5):
build solver → save ``initial.bin`` → timed hot loop → save ``result.bin``
→ PrintSummary block (+ JSON + optional PNG render, replacing MATLAB).
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Optional

import numpy as np


from multigpu_advectiondiffusion_tpu.bench.timing import sync
from multigpu_advectiondiffusion_tpu.models.base import SolverBase
from multigpu_advectiondiffusion_tpu.parallel.mesh import Decomposition, make_mesh
from multigpu_advectiondiffusion_tpu.timestepping.integrators import STAGES
from multigpu_advectiondiffusion_tpu.utils import io as io_utils
from multigpu_advectiondiffusion_tpu.utils.summary import RunSummary


def parse_mesh_spec(spec: Optional[str]):
    """``'dz=4,dy=2'`` -> (mesh, Decomposition) or (None, None).

    Mesh axis names map to grid axes by suffix: dz/dy/dx/dr -> z/y/x/r.
    A ``_suffix`` after the letter declares members of a *compound* axis
    splitting one grid axis over several mesh axes, outermost first in
    spec order — the multi-host layout: ``'dz_dcn=2,dz_ici=4'`` puts z
    over ``('dz_dcn', 'dz_ici')`` with the DCN hop between process
    granules (``parallel/mesh.py`` Decomposition docstring).
    """
    if not spec:
        return None, None
    sizes = {}
    for part in spec.split(","):
        name, _, num = part.partition("=")
        sizes[name.strip()] = int(num)
    mesh = make_mesh(sizes)
    return mesh, sizes


def decomposition_for(grid, mesh_sizes) -> Optional[Decomposition]:
    if not mesh_sizes:
        return None
    suffix_to_axis = {}
    names = grid.axis_names  # e.g. ('z','y','x'); axisym grids use ('y','x')
    for ax, n in enumerate(names):
        suffix_to_axis[n] = ax
    # r is the innermost axis of axisymmetric grids
    suffix_to_axis.setdefault("r", grid.ndim - 1)
    groups = {}  # grid axis -> mesh axis names, spec order (dcn first)
    for mesh_name in mesh_sizes:
        suffix = mesh_name.lstrip("d").split("_", 1)[0]
        if suffix not in suffix_to_axis:
            raise ValueError(
                f"mesh axis {mesh_name!r} has no grid axis (grid axes: {names})"
            )
        groups.setdefault(suffix_to_axis[suffix], []).append(mesh_name)
    mapping = {
        ax: (ns[0] if len(ns) == 1 else tuple(ns))
        for ax, ns in groups.items()
    }
    return Decomposition.of(mapping)


def physics_meta(solver: SolverBase) -> dict:
    """JSON-safe snapshot of the config fields that define the physics a
    checkpoint will continue under (diffusivity/nu/bc/weno/cfl/...).
    Excludes the grid (validated separately), the IC (irrelevant once a
    state exists), and kernel-strategy knobs that cannot change results."""
    import dataclasses

    # steps_per_exchange/exchange are kernel-strategy knobs like
    # impl/overlap: they change the exchange cadence/transport, not the
    # physics a checkpoint continues under
    skip = {"grid", "ic", "ic_params", "impl", "overlap",
            "steps_per_exchange", "exchange"}
    out = {}
    for f in dataclasses.fields(solver.cfg):
        if f.name in skip:
            continue
        v = getattr(solver.cfg, f.name)
        if isinstance(v, tuple):
            v = list(v)
        try:
            json.dumps(v)
        except TypeError:
            # non-serializable fields (e.g. a source-term callable) have
            # no stable representation across processes — recording repr()
            # would spuriously reject legitimate resumes
            continue
        out[f.name] = v
    return out


def build_ensemble_members(sweeps, members: int, aliases=None):
    """CLI ``--sweep`` specs -> per-member override dicts.

    ``NAME=a:b`` sweeps linearly, ``NAME=v1,...`` lists one value per
    member. ``aliases`` maps CLI names to config fields (``K`` ->
    ``diffusivity``); an ``ic.PARAM`` name lands in the member's
    ``ic_params`` (Riemann-state sweeps: ``ic.left=2:1``)."""
    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        parse_sweep_spec,
    )

    aliases = aliases or {}
    out = [dict() for _ in range(members)]
    ic_params = [dict() for _ in range(members)]
    for spec in sweeps or []:
        name, values = parse_sweep_spec(spec, members)
        if name.startswith("ic."):
            key = name[3:]
            for i, v in enumerate(values):
                ic_params[i][key] = v
            continue
        name = aliases.get(name, name)
        for i, v in enumerate(values):
            out[i][name] = v
    for i, p in enumerate(ic_params):
        if p:
            out[i]["ic_params"] = tuple(sorted(p.items()))
    return out


def parse_ensemble_mesh(mesh_spec, grid):
    """``--mesh members=8`` / ``members=4,dz=2`` -> ``(mesh,
    spatial_decomp)`` for the batched ensemble engine. The member axis
    shards the batched state's leading axis (halo-free); remaining
    axes map to grid axes like any spatial mesh. A spec WITHOUT a
    members axis declines loudly — a purely spatial mesh shards one
    member's grid."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import MEMBER_AXIS

    if not mesh_spec:
        return None, None
    mesh, sizes = parse_mesh_spec(mesh_spec)
    if MEMBER_AXIS not in sizes:
        raise ValueError(
            "--ensemble composes with --mesh through a 'members' axis "
            "(e.g. --mesh members=8 or --mesh members=4,dz=2); a "
            "purely spatial mesh shards one member's grid — drop "
            "--mesh or add the members axis"
        )
    spatial = {k: v for k, v in sizes.items() if k != MEMBER_AXIS}
    decomp = decomposition_for(grid, spatial) if spatial else None
    return mesh, decomp


def run_ensemble_solver(solver_cls, cfg, name: str, args, aliases=None):
    """The batched-ensemble CLI driver (``--ensemble B [--sweep ...]``):
    ONE batched dispatch advances all B members; per-member summaries
    (max|u|, mass drift) and member-attributed divergence come out of
    the batch (models/ensemble.py). ``--mesh members=P[,dz=Q]``
    composes: the member axis shards over the device mesh (optionally
    x a z-slab spatial subgroup), so one dispatch serves B x P users.
    Supervision machinery that rolls state back (checkpoints, SDC
    guard, diagnostics cadence) stays single-run; ``--sentinel-every``
    is served as a chunked per-member health probe."""
    import time as _time

    import jax

    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    B = int(args.ensemble)
    unsupported = {
        "--coordinator": getattr(args, "coordinator", None),
        "--resume": getattr(args, "resume", None),
        "--checkpoint-every": getattr(args, "checkpoint_every", 0),
        "--snapshot-every": getattr(args, "snapshot_every", 0),
        "--snapshots": getattr(args, "snapshots", 0),
        "--sdc-every": getattr(args, "sdc_every", 0),
        "--diag-every": getattr(args, "diag_every", 0),
        "--progress": getattr(args, "progress", False),
        "--watchdog-timeout": getattr(args, "watchdog_timeout", 0.0),
        "--dt-scale": (getattr(args, "dt_scale", 1.0) or 1.0) != 1.0,
    }
    offending = [k for k, v in unsupported.items() if v]
    if offending:
        raise ValueError(
            f"--ensemble does not compose with {offending} (single-run "
            "supervision machinery); drop them or run members "
            "individually"
        )
    members = build_ensemble_members(args.sweep, B, aliases=aliases)
    mesh, spatial_decomp = parse_ensemble_mesh(
        getattr(args, "mesh", None), cfg.grid
    )
    es = EnsembleSolver(solver_cls, cfg, members, mesh=mesh,
                        decomp=spatial_decomp)
    estate = es.initial_state()
    iters = args.iters
    if iters is None and args.t_end is None:
        iters = 100

    from multigpu_advectiondiffusion_tpu import telemetry

    scope = telemetry.get_sink()
    span = (
        scope.span("run_solver", run=name, ensemble=B)
        if scope.active
        else contextlib.nullcontext()
    )
    with span:
        # untimed warm-up/compile of the batched program (the
        # reference's untimed warm phase), then the timed dispatch
        t0 = _time.perf_counter()
        warm = es.run(estate, 1) if iters is not None else es.advance_to(
            estate, float(estate.t.max())
        )
        sync(warm.u)
        compile_s = _time.perf_counter() - t0

        sentinel = int(getattr(args, "sentinel_every", 0) or 0)
        t0 = _time.perf_counter()
        if iters is not None:
            if sentinel:
                out, done = estate, 0
                while done < iters:
                    n = min(sentinel, iters - done)
                    out = es.run(out, n)
                    done += n
                    # member-attributed divergence: one blown-up member
                    # names its index, the batch result stays valid
                    es.check_health(
                        out, growth=getattr(args, "sentinel_growth", 1e3)
                    )
            else:
                out = es.run(estate, iters)
        else:
            out = es.advance_to(estate, args.t_end)
        sync(out.u)
        seconds = _time.perf_counter() - t0

        work = iters if iters is not None else int(
            np.asarray(out.it).max()
        )
        rate = mlups(
            cfg.grid.num_cells * B, max(1, work),
            STAGES[cfg.integrator], seconds,
        )
        summaries = es.member_summaries(out)
        if sentinel == 0:
            es.check_health(
                out, growth=getattr(args, "sentinel_growth", 1e3)
            )
        engaged = es.engaged_path()
        result = {
            "name": name,
            "ensemble": B,
            "grid_xyz": list(cfg.grid.shape_xyz),
            "iters": work,
            "seconds": round(seconds, 6),
            "compile_seconds": round(compile_s, 4),
            "mlups_members": round(rate, 2),
            "devices": engaged.get("devices", 1),
            "member_sharding": engaged.get("member_sharding", 1),
            "mesh": engaged.get("mesh"),
            "engaged": engaged,
            "members": summaries,
        }
        if scope.active:
            scope.event(
                "summary", name, seconds=round(seconds, 6),
                mlups=round(rate, 3), ensemble=B,
                stepper=engaged["stepper"],
            )

    # Safe rank divergence: single-process engine (the gate above
    # rejects --coordinator), so the coordinator gate is vestigial
    # uniprocess hygiene — there is no peer to desynchronize from and
    # no collective below this point.
    # tpucfd-check: allow[rank-divergent-effect]
    if jax.process_index() == 0:
        placement = ""
        if engaged.get("devices", 1) > 1:
            placement = (
                f", {engaged['member_sharding']}-way member sharding "
                f"over {engaged['devices']} devices"
            )
        print(f"-- {name} ensemble: B={B} members, {work} iters, "
              f"{seconds:.4f}s, {rate:,.1f} MLUPS*members "
              f"({engaged['stepper']}{placement})")
        for row in summaries:
            drift = row.get("mass_drift")
            print(
                f"   member {row['member']:3d}: t={row['t']:.5g} "
                f"max|u|={row['max_abs']:.5g}"
                + (f" mass_drift={drift:+.3e}" if drift is not None
                   else "")
                + (f" {row['overrides']}" if row.get("overrides") else "")
            )
        if args.save:
            os.makedirs(args.save, exist_ok=True)
            io_utils.save_binary(
                np.asarray(out.u),
                os.path.join(args.save, "ensemble_result.bin"),
            )
            tmp = os.path.join(args.save, "ensemble_summary.json.tmp")
            with open(tmp, "w") as f:
                json.dump(result, f, indent=1)
            os.replace(
                tmp, os.path.join(args.save, "ensemble_summary.json")
            )
    return result


def run_solver(
    solver: SolverBase,
    name: str,
    *args,
    metrics_path: Optional[str] = None,
    metrics_max_bytes: int = 0,
    watchdog_timeout: float = 0.0,
    **kwargs,
) -> RunSummary:
    """Public run driver; see :func:`_run_solver` for the full contract.

    ``metrics_path`` opens a structured-telemetry JSONL sink for the
    run's duration (the CLI's ``--metrics``); when a sink is already
    installed (e.g. by ``cli.main`` before the multihost join) it is
    reused and left alone. The whole run executes under a top-level
    ``run_solver`` span so every dispatch/physics/resilience/io event is
    attributable to this run.

    ``watchdog_timeout`` > 0 arms the rank-liveness watchdog for
    multi-process runs (heartbeat records under ``save_dir``): a peer
    dead or silent past the timeout aborts this process with the
    documented rank-failure exit code instead of hanging in a
    collective; any exception raised while a peer is down is classified
    as the structured ``RankFailureError`` it really is."""
    import jax

    from multigpu_advectiondiffusion_tpu import telemetry
    from multigpu_advectiondiffusion_tpu.parallel import multihost

    watchdog = None
    if watchdog_timeout and watchdog_timeout > 0 and jax.process_count() > 1:
        save_dir = kwargs.get("save_dir")
        if not save_dir:
            raise ValueError(
                "--watchdog-timeout needs --save DIR (the heartbeat "
                "records live under it)"
            )
        os.makedirs(save_dir, exist_ok=True)
        watchdog = multihost.RankWatchdog(
            os.path.join(save_dir, ".heartbeats"),
            timeout_seconds=watchdog_timeout,
            report_dir=save_dir,
        )

    with contextlib.ExitStack() as scope:
        if metrics_path and not telemetry.get_sink().active:
            sink = telemetry.install(metrics_path,
                                     max_bytes=metrics_max_bytes)
            scope.callback(telemetry.uninstall, sink)
        if watchdog is not None:
            # after the sink install, so direct run_solver(metrics_path=
            # ...) callers get the armed record in their stream too
            telemetry.event(
                "rank", "watchdog_armed",
                timeout=float(watchdog_timeout),
                interval=watchdog.interval,
                processes=jax.process_count(),
            )
        t_sink = telemetry.get_sink()
        if t_sink.active:
            scope.enter_context(t_sink.span("run_solver", run=name))
        # the scope covers warm-up, the timed solve AND the gathered
        # file output — every cross-process collective of the run
        scope.enter_context(multihost.watchdog_scope(watchdog))
        return _run_solver(solver, name, *args, **kwargs)


def _run_solver(
    solver: SolverBase,
    name: str,
    iters: Optional[int] = None,
    t_end: Optional[float] = None,
    save_dir: Optional[str] = None,
    plot: bool = False,
    check_error: bool = False,
    repeats: int = 1,
    snapshot_every: int = 0,
    checkpoint_every: int = 0,
    checkpoint_keep: int = 0,
    checkpoint_sharded: bool = False,
    resume: Optional[str] = None,
    profile_dir: Optional[str] = None,
    sentinel_every: int = 0,
    sentinel_growth: float = 1e3,
    max_retries: int = 3,
    dt_backoff: float = 0.5,
    sdc_every: int = 0,
    progress: bool = False,
    diag_every: int = 0,
    diag_strict: bool = False,
    snapshots: int = 0,
    snapshot_stride: int = 1,
    snapshot_max_bytes: int = 0,
    dt_scale: float = 1.0,
) -> RunSummary:
    """Execute the timed solve exactly the way the reference drivers do:
    untimed warm-up/compile, barrier-sandwiched hot loop
    (``MultiGPU/Diffusion3d_Baseline/main.c:184-307``), then I/O.

    ``snapshot_every``/``checkpoint_every`` (iters mode only) emit
    float32 ``snap_*.bin`` via the async writer / restartable,
    CRC-verified ``.ckpt`` checkpoints every N iterations — the restart
    capability the reference lacks (SURVEY §5). ``checkpoint_keep``
    bounds disk use by deleting all but the newest N checkpoints.

    Resilience (README/PARITY "Failure modes & resilience"):
    ``sentinel_every`` > 0 supervises the run — a mesh-aware health
    probe every N steps, rollback to the last good checkpoint and a
    ``dt_backoff`` retry schedule on divergence (at most
    ``max_retries``). ``resume='auto'`` scans ``save_dir`` for the
    newest CRC-valid checkpoint, skipping corrupt ones. SIGTERM/SIGINT
    end the run at the next chunk boundary with a final atomic
    checkpoint + ``preempt.json`` manifest and exit code 75
    (``resilience.EXIT_PREEMPTED``).
    """
    if (iters is None) == (t_end is None):
        raise ValueError("provide exactly one of iters/t_end")
    import jax

    from multigpu_advectiondiffusion_tpu.resilience.preemption import (
        PreemptionExit,
        PreemptionGuard,
    )
    from multigpu_advectiondiffusion_tpu.resilience.recovery import (
        find_latest_checkpoint,
    )
    from multigpu_advectiondiffusion_tpu.resilience.supervisor import (
        supervise_run,
    )

    # Multi-process runs (the mpirun analog, --coordinator): file output
    # happens once, on the coordinator; shards living on other processes
    # are allgathered first. _fetch is a COLLECTIVE when sharded across
    # processes — every process must call it, only the write is gated.
    is_coord = jax.process_index() == 0

    def _fetch(u):
        if getattr(u, "is_fully_addressable", True):
            return u
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(u, tiled=True)

    if resume == "auto":
        # newest CRC-valid checkpoint in the run directory; corrupt/
        # truncated candidates are reported and skipped (selection rules
        # in resilience/recovery.py). Nothing valid -> fresh start.
        if not save_dir:
            raise ValueError("--resume auto needs --save DIR to scan")
        resume = find_latest_checkpoint(save_dir)
        if resume is None and is_coord:
            print(
                f"--resume auto: no valid checkpoint under {save_dir}; "
                "starting from the initial condition"
            )

    if resume:
        import jax.numpy as jnp

        # sharded checkpoint directories reassemble straight onto this
        # run's mesh (which may differ from the saving run's) — each
        # process reads only the regions its shards need
        state = io_utils.load_checkpoint(
            resume,
            sharding=None if solver.mesh is None else solver.sharding(),
        )
        if tuple(state.u.shape) != tuple(solver.grid.shape):
            raise ValueError(
                f"checkpoint grid {tuple(state.u.shape)} != configured "
                f"grid {tuple(solver.grid.shape)}"
            )
        u = jnp.asarray(state.u, solver.dtype)
        if solver.mesh is not None:
            u = jax.device_put(u, solver.sharding())
        state = type(state)(u=u, t=state.t, it=state.it)
        # recorded physical bounds (.npz meta field / .ckpt sidecar) — a
        # matching node count on a different domain is silently wrong
        # physics
        meta = io_utils.read_checkpoint_meta(resume)
        # elastic reshard: a .ckptd written on mesh A restoring onto a
        # different process/device topology (the restart-after-losing-a-
        # host path) is legitimate and worth recording — each process
        # read only the shard regions overlapping its NEW placement
        saved_procs = (meta or {}).get("num_processes")
        if saved_procs is not None and int(saved_procs) != jax.process_count():
            from multigpu_advectiondiffusion_tpu import telemetry

            telemetry.event(
                "resilience", "elastic_resume",
                checkpoint=resume,
                saved_processes=int(saved_procs),
                processes=jax.process_count(),
            )
            if is_coord:
                print(
                    f"elastic resume: checkpoint {resume} was written "
                    f"by {int(saved_procs)} process(es); restoring onto "
                    f"{jax.process_count()}"
                )
        got = (meta or {}).get("bounds")
        if got is not None:
            want = [list(b) for b in solver.grid.bounds]
            if not np.allclose(got, want):
                raise ValueError(
                    f"checkpoint domain bounds {got} != configured "
                    f"bounds {want}"
                )
        # matching grid + bounds but different physics (e.g. another --K
        # or WENO variant) would silently continue the wrong equation
        # under the same artifact numbering
        recorded = (meta or {}).get("physics")
        if recorded is not None:
            current = physics_meta(solver)
            diffs = {
                k: (recorded[k], current[k])
                for k in recorded
                if k in current and recorded[k] != current[k]
            }
            if diffs:
                detail = ", ".join(
                    f"{k}: checkpoint={a!r} configured={b!r}"
                    for k, (a, b) in sorted(diffs.items())
                )
                raise ValueError(
                    f"checkpoint physics parameters differ: {detail}"
                )
    else:
        state = solver.initial_state()
    start_it = int(state.it)

    if dt_scale and float(dt_scale) != 1.0:
        # dt-backoff inheritance (--dt-scale, the scheduler's retry
        # knob): start at the reduced step a failed attempt backed off
        # to. Applied AFTER resume validation — the checkpoint's
        # recorded physics are compared against the unscaled config —
        # and through the same scale_dt path the supervisor's in-run
        # backoff uses, so the two schedules compose.
        from multigpu_advectiondiffusion_tpu.resilience.supervisor import (
            scale_dt,
        )

        what = scale_dt(solver, float(dt_scale))
        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.event(
            "resilience", "dt_inherit",
            factor=float(dt_scale), action=what,
        )
        if is_coord:
            print(f"dt-scale {float(dt_scale):g}: {what} "
                  "(inherited backoff)")

    # measured introspection: run-scoped device-memory watermarks
    # (supervised chunks sample at cadence; every run samples at the
    # warm-up and final boundaries, so RunSummary.memory always lands)
    from multigpu_advectiondiffusion_tpu.telemetry import xprof

    xprof.reset_watermarks()

    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        u_host = _fetch(state.u)
        # Safe rank divergence: every rank joined the _fetch allgather
        # above; only the write is gated (one writer per artifact),
        # and no peer reads initial.bin during the run.
        # tpucfd-check: allow[rank-divergent-effect]
        if is_coord:
            io_utils.save_binary(u_host, os.path.join(save_dir, "initial.bin"))

    # compile (untimed, like the reference's untimed warm phase)
    t0 = time.perf_counter()
    if iters is not None:
        out = solver.run(state, 1)
    else:
        out = solver.step(state)
    sync(out.u)
    compile_s = time.perf_counter() - t0
    xprof.sample_watermark(step=int(out.it))

    supervised = sentinel_every > 0
    periodic = (
        (snapshot_every or checkpoint_every)
        and iters is not None
        and not supervised
    )
    if supervised and snapshot_every:
        raise ValueError(
            "--sentinel-every supervises checkpoint-grain chunks; "
            "combine it with --checkpoint-every, not --snapshot-every"
        )
    if sdc_every and not supervised:
        raise ValueError(
            "--sdc-every rides the sentinel cadence; it needs "
            "--sentinel-every > 0"
        )
    if progress and not supervised:
        raise ValueError(
            "--progress renders the supervisor's chunk-cadence events; "
            "it needs --sentinel-every > 0"
        )
    if diag_every and not supervised:
        raise ValueError(
            "--diag-every rides the sentinel's jitted probe cadence; "
            "it needs --sentinel-every > 0"
        )
    if diag_strict and not diag_every:
        raise ValueError(
            "--diag-strict escalates diagnostic violations; it needs "
            "--diag-every > 0"
        )
    if snapshots and not supervised:
        raise ValueError(
            "--snapshots streams at the supervised chunk cadence; it "
            "needs --sentinel-every > 0 (unsupervised periodic output: "
            "--snapshot-every)"
        )
    if (
        periodic or (supervised and (checkpoint_every or snapshots))
    ) and not save_dir:
        raise ValueError("snapshot/checkpoint output needs save_dir")

    def _write_checkpoint(st):
        """One restartable checkpoint named by global iteration (atomic,
        CRC-verified; sharded -> per-shard .ckptd directory). Collective
        when sharded across processes."""
        glob_it = int(st.it)
        if checkpoint_sharded:
            path = os.path.join(save_dir, f"checkpoint_{glob_it:06d}.ckptd")
            io_utils.save_checkpoint_sharded(
                path, st, grid=solver.grid, physics=physics_meta(solver)
            )
        else:
            path = os.path.join(save_dir, f"checkpoint_{glob_it:06d}.ckpt")
            u_host = _fetch(st.u)
            # Safe rank divergence: the single-file checkpoint has one
            # writer by design; every rank already joined the _fetch
            # allgather, and the .ckpt publish is atomic + CRC-gated
            # so a resuming reader sees complete-or-absent.
            # tpucfd-check: allow[rank-divergent-effect]
            if is_coord:
                io_utils.save_checkpoint(
                    path,
                    type(st)(u=u_host, t=st.t, it=st.it),
                    grid=solver.grid,
                    physics=physics_meta(solver),
                )
        io_utils.rotate_checkpoints(save_dir, checkpoint_keep)
        return path

    best = float("inf")
    io_s = None
    sup_report = None
    # trace() itself is exception-safe and idempotent (utils/profiling):
    # it closes on every exit path and a leaked predecessor can no
    # longer poison start_trace — no extra guard logic needed here
    profiled = contextlib.nullcontext()
    if profile_dir:
        from multigpu_advectiondiffusion_tpu.utils.profiling import trace

        # Multi-process launches write one trace dir per process —
        # the %q{OMPI_COMM_WORLD_RANK} per-rank naming of the
        # reference's profile.sh (MultiGPU/Diffusion3d_Baseline/
        # profile.sh:2), keyed on jax.process_index().
        if jax.process_count() > 1:
            profile_dir = os.path.join(
                profile_dir, f"rank{jax.process_index()}"
            )
        profiled = trace(profile_dir)
    guard = PreemptionGuard()
    with profiled, guard:
        if supervised:
            # supervised chunked loop: sentinel probes at cadence,
            # rollback + dt-backoff retries on divergence; the disk
            # checkpoints (when requested) are the rollback grain
            io_acc = [0.0]

            def save_ckpt(st):
                sync(st.u)  # don't book device compute as I/O
                io_t0 = time.perf_counter()
                _write_checkpoint(st)
                io_acc[0] += time.perf_counter() - io_t0

            # --snapshots: downsampled field-snapshot streaming through
            # the double-buffered background writer (atomic publishes,
            # rotation-capped by --snapshot-max-bytes). _fetch is a
            # collective when sharded — every process calls, only the
            # coordinator writes.
            snap_streamer = None
            save_snap = None
            if snapshots:
                if is_coord:
                    snap_streamer = io_utils.SnapshotStreamer(
                        save_dir, stride=snapshot_stride,
                        max_bytes=snapshot_max_bytes,
                    )

                def save_snap(st):
                    sync(st.u)
                    io_t0 = time.perf_counter()
                    u_host = _fetch(st.u)
                    if snap_streamer is not None:
                        snap_streamer.write(u_host, int(st.it))
                    io_acc[0] += time.perf_counter() - io_t0

            # --progress: the coordinator renders the supervisor's
            # chunk-cadence progress events as one status line (other
            # ranks still emit the events into their own streams)
            progress_line = None
            if progress and is_coord:
                from multigpu_advectiondiffusion_tpu.telemetry.live import (
                    ProgressLine,
                )

                progress_line = ProgressLine(label=name)
            t0 = time.perf_counter()
            try:
                out, sup_report = supervise_run(
                    solver,
                    state,
                    iters=iters,
                    t_end=t_end,
                    sentinel_every=sentinel_every,
                    growth=sentinel_growth,
                    max_retries=max_retries,
                    dt_backoff=dt_backoff,
                    checkpoint_every=checkpoint_every,
                    save_checkpoint=save_ckpt if checkpoint_every else None,
                    should_stop=lambda: guard.should_stop,
                    sdc_every=sdc_every,
                    progress=(
                        progress_line.update if progress_line else None
                    ),
                    diag_every=diag_every,
                    diag_strict=diag_strict,
                    snapshot_every=snapshots,
                    save_snapshot=save_snap,
                )
            finally:
                if progress_line is not None:
                    progress_line.close()
                if snap_streamer is not None:
                    snap_streamer.close()
            sync(out.u)
            io_s = io_acc[0] if (checkpoint_every or snapshots) else None
            best = time.perf_counter() - t0 - (io_s or 0.0)
        elif periodic:
            chunk = min(x for x in (snapshot_every, checkpoint_every) if x)
            io_s = 0.0  # shadows the outer None: periodic runs report it
            # the streamer wraps the async writer with atomic publishes,
            # optional striding and the --snapshot-max-bytes rotation cap
            with io_utils.SnapshotStreamer(
                save_dir, stride=snapshot_stride,
                max_bytes=snapshot_max_bytes,
            ) as writer:
                t0 = time.perf_counter()
                out, done = state, 0
                while done < iters:
                    n = min(chunk, iters - done)
                    out = solver.run(out, n)
                    done += n
                    # filenames carry the GLOBAL iteration so a resumed
                    # run continues the numbering instead of overwriting
                    # earlier artifacts in the same directory
                    glob_it = start_it + done
                    # host I/O is timed separately and excluded from the
                    # solve rate — the reference times only kernel work
                    # (main.c:184-307; output happens after the loop).
                    # Drain the async-dispatched chunk FIRST: otherwise
                    # the device compute blocks inside np.asarray in the
                    # writers and books as I/O, inflating the solve rate.
                    sync(out.u)
                    io_t0 = time.perf_counter()
                    snap_now = (
                        snapshot_every and done % snapshot_every == 0
                    )
                    ckpt_now = (
                        checkpoint_every and done % checkpoint_every == 0
                    )
                    # one gather serves both writers when they coincide
                    u_host = (
                        _fetch(out.u)
                        if snap_now or (ckpt_now and not checkpoint_sharded)
                        else None
                    )
                    if snap_now:
                        if is_coord:
                            writer.write(u_host, glob_it)
                    if ckpt_now:
                        if checkpoint_sharded:
                            # per-shard directory: no gather to one host
                            io_utils.save_checkpoint_sharded(
                                os.path.join(
                                    save_dir,
                                    f"checkpoint_{glob_it:06d}.ckptd",
                                ),
                                out,
                                grid=solver.grid,
                                physics=physics_meta(solver),
                            )
                        else:
                            # single-writer .ckpt publish — same audit
                            # as the supervised _write_checkpoint path
                            # tpucfd-check: allow[rank-divergent-effect]
                            if is_coord:
                                io_utils.save_checkpoint(
                                    os.path.join(
                                        save_dir,
                                        f"checkpoint_{glob_it:06d}.ckpt",
                                    ),
                                    type(out)(u=u_host, t=out.t, it=out.it),
                                    grid=solver.grid,
                                    physics=physics_meta(solver),
                                )
                        io_utils.rotate_checkpoints(save_dir, checkpoint_keep)
                    io_s += time.perf_counter() - io_t0
                    if guard.should_stop:
                        break  # preemption: finalize below with what ran
                sync(out.u)
                best = time.perf_counter() - t0 - io_s
        else:
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                if iters is not None:
                    out = solver.run(state, iters)
                else:
                    out = solver.advance_to(state, t_end)
                sync(out.u)
                best = min(best, time.perf_counter() - t0)
                if guard.should_stop:
                    break  # preemption between repeats

    if guard.should_stop:
        # preemption-safe exit: final atomic checkpoint + manifest, then
        # the documented exit code (resume with --resume auto). A
        # multi-process run must receive the signal on every process
        # (sharded checkpoint saves are collective).
        from multigpu_advectiondiffusion_tpu.resilience.preemption import (
            EXIT_PREEMPTED,
        )

        ckpt_path = None
        if save_dir:
            sync(out.u)
            ckpt_path = _write_checkpoint(out)
            # Safe rank divergence: every rank wrote (or gathered for)
            # the final checkpoint above; the preempt.json breadcrumb
            # is advisory single-writer metadata published atomically.
            # tpucfd-check: allow[rank-divergent-effect]
            if is_coord:
                manifest = {
                    "signal": int(guard.signum),
                    "iteration": int(out.it),
                    "t": float(out.t),
                    "checkpoint": ckpt_path,
                    "exit_code": EXIT_PREEMPTED,
                    "resume": "--resume auto",
                }
                tmp = os.path.join(save_dir, "preempt.json.tmp")
                with open(tmp, "w") as f:
                    json.dump(manifest, f, indent=2)
                os.replace(tmp, os.path.join(save_dir, "preempt.json"))
        if is_coord:
            where = f"; checkpoint: {ckpt_path}" if ckpt_path else ""
            print(
                f"preempted by signal {guard.signum} at iteration "
                f"{int(out.it)}{where}; exiting {EXIT_PREEMPTED}"
            )
        raise PreemptionExit(guard.signum, ckpt_path)

    # iterations executed THIS run — a resumed state's it starts at the
    # checkpoint's cumulative count, which must not inflate the summary
    n_iters = iters if iters is not None else max(1, int(out.it) - start_it)
    dt = getattr(solver, "dt", None)
    if dt is None:
        dt = (float(out.t) - float(state.t)) / max(n_iters, 1)

    summary = RunSummary(
        name=name,
        grid_xyz=solver.grid.shape_xyz,
        iters=n_iters,
        stages=STAGES[solver.cfg.integrator],
        seconds=best,
        dt=float(dt),
        t_final=float(out.t),
        devices=1 if solver.mesh is None else solver.mesh.devices.size,
        dtype=str(solver.cfg.dtype),
        compile_seconds=compile_s,
        io_seconds=io_s,
        engaged=solver.engaged_path(
            mode="iters" if iters is not None else "t_end"
        ),
        resilience=sup_report.to_dict() if sup_report is not None else None,
    )
    # static cost model for the ENGAGED rung: bytes/FLOPs per step and
    # the roofline efficiency of the measured rate (telemetry/costmodel)
    from multigpu_advectiondiffusion_tpu.telemetry import costmodel

    summary.cost_model = costmodel.summarize_run(
        solver, summary.engaged["stepper"], n_iters, best
    )
    # measured introspection: the final watermark sample plus the
    # per-executable XLA capture reconciled against the modeled cost
    sync(out.u)
    xprof.sample_watermark(step=int(out.it))
    summary.memory = xprof.watermark_summary()
    summary.xla = xprof.measured_summary(solver, n_iters, best)
    from multigpu_advectiondiffusion_tpu import telemetry

    t_sink = telemetry.get_sink()
    if t_sink.active:
        t_sink.event(
            "summary", name,
            seconds=round(best, 6),
            mlups=round(summary.mlups, 3),
            stepper=summary.engaged["stepper"],
            roofline_pct=(summary.cost_model or {}).get("roofline_pct"),
            mass_drift=(
                summary.resilience.get("mass_drift")
                if summary.resilience
                else None
            ),
        )
        if summary.xla is not None:
            # the per-run measured-vs-modeled record the trace report
            # renders: XLA bytes/flops per step, model ratio + band
            # flag, achieved vs peak bandwidth
            t_sink.event("xla", "measured", run=name, **summary.xla)
    if summary.xla is not None and summary.cost_model is not None:
        # feed the measured-peak calibration with the run's achieved
        # rate on its BINDING resource (the non-binding one never
        # approaches its roof — calibrating it down would be noise);
        # consumed by costmodel.peak_rates and the tuner's pruning
        from multigpu_advectiondiffusion_tpu.telemetry import calibration

        bound = summary.cost_model.get("bound")
        kwargs = {}
        if bound == "hbm" and summary.xla.get("achieved_gbs"):
            kwargs["bytes_per_s"] = (
                summary.xla["achieved_gbs"] * 1e9
                / max(1, summary.xla.get("devices", 1))
            )
        elif bound == "flops" and summary.xla.get("achieved_gflops"):
            kwargs["flops_per_s"] = (
                summary.xla["achieved_gflops"] * 1e9
                / max(1, summary.xla.get("devices", 1))
            )
        if kwargs and is_coord:
            calibration.observe(
                jax.local_devices()[0].device_kind, run=name, **kwargs
            )

    if check_error and hasattr(solver, "error_norms"):
        # gathered first: eager norm arithmetic mixes the state with a
        # process-local analytic field, which non-fully-addressable
        # arrays cannot do (_fetch is collective — all processes call)
        norms = solver.error_norms(
            type(out)(u=_fetch(out.u), t=out.t, it=out.it)
        )
        summary.error_l1, summary.error_l2, summary.error_linf = tuple(norms)

    if save_dir:
        u_host = _fetch(out.u)
        # Safe rank divergence: the allgather above was collective
        # (every rank calls _fetch); result/summary publishing is
        # single-writer by design and nothing downstream of it holds
        # a rendezvous this rank could miss.
        # tpucfd-check: allow[rank-divergent-effect]
        if is_coord:
            io_utils.save_binary(u_host, os.path.join(save_dir, "result.bin"))
            summary.write_json(os.path.join(save_dir, "summary.json"))
            if plot:
                from multigpu_advectiondiffusion_tpu.utils.plot import (
                    plot_field,
                )

                plot_field(
                    u_host,
                    grid=solver.grid,
                    title=f"{name} t={float(out.t):.4f}",
                    path=os.path.join(save_dir, f"{name}.png"),
                )

    if is_coord:
        summary.print_block()
    return summary
