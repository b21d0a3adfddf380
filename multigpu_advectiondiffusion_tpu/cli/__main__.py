"""Command-line interface.

Replaces the reference's per-project positional-arg binaries + shell/MATLAB
harness (``./Diffusion3d.run K L W H Nx Ny Nz iters bX bY bZ``,
``run.sh``/``Run.m`` — SURVEY §3.1/§3.5) with one argparse CLI:

    python -m multigpu_advectiondiffusion_tpu.cli diffusion3d \
        --K 1.0 --lengths 2 2 2 --n 400 200 200 --iters 1000 --save out/
    python -m multigpu_advectiondiffusion_tpu.cli burgers3d \
        --t-end 0.06 --cfl 0.3 --n 400 400 400 --save out/ --plot
    python -m multigpu_advectiondiffusion_tpu.cli convergence --ndim 3
    python -m multigpu_advectiondiffusion_tpu.cli diffusion3d \
        --n 256 256 256 --iters 100 --mesh dz=4,dy=2
    python -m multigpu_advectiondiffusion_tpu.cli --model adr \
        --n 128 128 128 --velocity 0.5 --kappa-variation 0.2 \
        --reaction 0.3 --iters 200 --mesh dz=4

Model subcommands (``diffusion3d``, ``burgers2d``, ``adr3d``, ...) are
GENERATED from the solver-plugin registry (``models/registry.py``);
``--model NAME`` resolves through the same registry (dimensionality
from ``--ndim`` or the ``--n`` arity), so a newly registered family is
immediately runnable with no CLI edits.

Block sizes (bX/bY/bZ) have no TPU meaning and are not taken; XLA/Pallas
choose tiling.

Exit codes (full table in README "Failure modes & resilience"):
0 success; 1 failure; 75 preempted (SIGTERM/SIGINT landed; a final
CRC-valid checkpoint + ``preempt.json`` manifest were written to
``--save DIR`` — rerun the same command with ``--resume auto``);
76 rank failure (a peer process of a multi-process run died or stalled
past ``--watchdog-timeout``; restart — on the surviving topology if a
host is gone — with ``--resume auto``); 77 silent data corruption
detected (``--sdc-every``) and the rollback budget exhausted.
"""

from __future__ import annotations

import argparse
import sys

from multigpu_advectiondiffusion_tpu.cli.drivers import (
    decomposition_for,
    parse_mesh_spec,
    run_ensemble_solver,
    run_solver,
)


def _add_common(p: argparse.ArgumentParser, ndim: int):
    p.add_argument("--n", type=int, nargs=ndim, required=True,
                   metavar=tuple("N" + c for c in "xyz"[:ndim]),
                   help="grid nodes per physical axis (x [y [z]])")
    p.add_argument("--lengths", type=float, nargs=ndim, default=None,
                   help="physical extents (L [W [H]]); domain centered at 0")
    p.add_argument("--iters", type=int, default=None,
                   help="fixed iteration count (reference main.c mode)")
    p.add_argument("--t-end", type=float, default=None,
                   help="march to this simulated time instead of --iters")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "float64", "bfloat16"])
    p.add_argument("--precision", default="native",
                   choices=["native", "bf16"],
                   help="storage precision rung: bf16 = store the "
                        "run-resident state (HBM buffers, every halo/"
                        "remote-DMA wire byte) in bfloat16 while all "
                        "stencil taps and RK stages compute in float32, "
                        "with compensated (Kahan hi/lo) accumulation on "
                        "the generic path — half the memory traffic at "
                        "float32 arithmetic; requires --dtype float32 "
                        "and validates loudly per rung (single-run "
                        "only; per-stage Burgers needs --fixed-dt and "
                        "engages the slab rung)")
    p.add_argument("--ic", default=None, help="initial-condition name")
    p.add_argument("--bc", default=None, nargs="*",
                   help="boundary kind(s): one value or one per axis "
                        "(dirichlet|edge|periodic)")
    p.add_argument("--integrator", default="ssp_rk3",
                   choices=["euler", "ssp_rk2", "ssp_rk3"])
    p.add_argument("--mesh", default=None,
                   help="device-mesh spec, e.g. 'dz=4' or 'dz=4,dy=2'; a "
                        "'_suffix' groups members of a compound axis for "
                        "one grid axis, outermost first — the multi-host "
                        "layout 'dz_dcn=2,dz_ici=4' splits z over 2 "
                        "process granules x 4 chips")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-process launch (the mpirun analog): run "
                        "one CLI process per host with the same "
                        "--coordinator and --num-processes and a unique "
                        "--process-id; jax.distributed joins them and "
                        "the mesh spans every process's devices")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--save", default=None, metavar="DIR",
                   help="write initial.bin/result.bin/summary.json here")
    p.add_argument("--plot", action="store_true",
                   help="also render a PNG into --save DIR")
    p.add_argument("--check-error", action="store_true",
                   help="report L1/L2/Linf vs the analytic solution")
    p.add_argument("--repeats", type=int, default=1,
                   help="timed repetitions; best time is reported")
    p.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                   help="write snap_NNNNNN.bin every N iters (async)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="write restartable checkpoint_NNNNNN.ckpt every N "
                        "iters (atomic, CRC-verified)")
    p.add_argument("--checkpoint-keep", type=int, default=0, metavar="N",
                   help="keep only the newest N checkpoints (0 = keep all)")
    p.add_argument("--checkpoint-sharded", action="store_true",
                   help="write per-shard checkpoint directories (.ckptd: "
                        "each process saves only its addressable shards + "
                        "a layout manifest — no gather to one host; resume "
                        "reassembles onto any mesh)")
    p.add_argument("--resume", default=None, metavar="CKPT",
                   help="resume from a .ckpt/.npz/.ckptd checkpoint "
                        "instead of the initial condition; 'auto' scans "
                        "--save DIR for the newest CRC-valid checkpoint, "
                        "skipping corrupt/truncated ones")
    p.add_argument("--sentinel-every", type=int, default=0, metavar="N",
                   help="divergence-sentinel cadence: a mesh-aware "
                        "all-finite + norm-growth probe every N steps "
                        "between fused-run calls; on divergence the run "
                        "rolls back to the last good checkpoint and "
                        "retries with dt scaled by --dt-backoff "
                        "(0 = unsupervised)")
    p.add_argument("--sentinel-growth", type=float, default=1e3,
                   metavar="G",
                   help="sentinel norm bound: max|u| may not exceed G x "
                        "max(1, initial max|u|)")
    p.add_argument("--max-retries", type=int, default=3, metavar="N",
                   help="rollback-and-retry budget before the "
                        "divergence error propagates")
    p.add_argument("--dt-backoff", type=float, default=0.5, metavar="F",
                   help="dt (fixed-dt solvers) or CFL (adaptive) "
                        "multiplier applied per rollback retry")
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   metavar="S",
                   help="rank-liveness watchdog for multi-process runs "
                        "(needs --save DIR): every process writes a "
                        "heartbeat record and monitors its peers'; a "
                        "peer dead or silent for S seconds aborts THIS "
                        "process with exit code 76 and a structured "
                        "rank_failure report instead of hanging in a "
                        "collective forever (0 = off, the MPI "
                        "abort-the-world model)")
    p.add_argument("--checkify", action="store_true",
                   help="runtime sanitizer: compile every dispatch "
                        "program with jax.experimental.checkify NaN/"
                        "div-by-zero/OOB checks discharged in; a trip "
                        "names the offending primitive and recovers "
                        "through the supervisor's rollback path (the "
                        "cuda-memcheck analog; single-device runs "
                        "only — see README 'Static analysis & "
                        "sanitizers')")
    p.add_argument("--sdc-every", type=int, default=0, metavar="M",
                   help="silent-data-corruption guard: every M-th "
                        "sentinel probe re-executes one step from the "
                        "probed state and compares bit-exact; a "
                        "mismatch emits an sdc:detect event and "
                        "recovers via rollback WITHOUT a dt backoff "
                        "(0 = off; needs --sentinel-every; costs two "
                        "extra steps per check)")
    p.add_argument("--diag-every", type=int, default=0, metavar="M",
                   help="in-situ physics diagnostics: every M-th "
                        "sentinel probe evaluates the fused observable "
                        "suite (conservation budgets, total variation, "
                        "spectral high-wavenumber tail, per-solver "
                        "extras — all inside the sentinel's ONE jitted "
                        "probe) and emits a phys:diag event; tolerance-"
                        "rule breaches (max-principle, TV growth) emit "
                        "phys:violation warnings; the trajectory lands "
                        "in summary.json's diagnostics block for the "
                        "science gate (0 = off; needs --sentinel-every)")
    p.add_argument("--diag-strict", action="store_true",
                   help="escalate a phys:violation into the rollback + "
                        "dt-backoff retry path instead of a warning "
                        "(needs --diag-every)")
    p.add_argument("--snapshots", type=int, default=0, metavar="N",
                   help="supervised field-snapshot streaming: write a "
                        "downsampled snap_NNNNNN.bin every N steps "
                        "through the double-buffered background writer "
                        "(atomic publish, io:snapshot_write events; "
                        "needs --sentinel-every — unsupervised runs use "
                        "--snapshot-every)")
    p.add_argument("--snapshot-stride", type=int, default=1, metavar="S",
                   help="downsample snapshots by striding every axis "
                        "(u[::S, ::S, ...]) before writing — 1/S^d of "
                        "the field's bytes per snapshot (default 1)")
    p.add_argument("--snapshot-max-bytes", type=int, default=0,
                   metavar="N",
                   help="rotation cap for snapshot files (both "
                        "--snapshots and --snapshot-every): delete the "
                        "oldest snapshots once their total exceeds N "
                        "bytes, keeping the newest — the --metrics-max-"
                        "bytes discipline for fields (0 = unbounded)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler device trace of the timed "
                        "solve into DIR (TensorBoard/Perfetto viewable) — "
                        "the nvprof wrapping of profile.sh, TPU-style")
    p.add_argument("--trace", dest="profile", metavar="DIR",
                   help="alias for --profile: the captured trace carries "
                        "the dispatch layer and the rung hierarchy as "
                        "labeled spans (tpucfd.dispatch per driver call "
                        "with its steps/cache/programs stats, and its "
                        "children tpucfd.dispatch.resolve|execute|state "
                        "and tpucfd.run[<stepper>]; tpucfd.compile and "
                        "tpucfd.introspect on a program's first call; "
                        "tpucfd.halo_exchange_*, tpucfd.<rung> step "
                        "bodies) viewable in Perfetto")
    p.add_argument("--metrics", default=None, metavar="PATH",
                   help="stream structured telemetry to PATH as JSONL: "
                        "span/counter events from dispatch and halo "
                        "exchanges, per-executable XLA cost/memory "
                        "capture (xla:cost — compiler-reported flops/"
                        "bytes + compile seconds per compiled program), "
                        "chunk-cadence physics probes and device-memory "
                        "watermarks (mem:watermark, supervised runs), "
                        "resilience events (rollbacks, retries, "
                        "preemption), checkpoint writes and calibration "
                        "updates — see README 'Observability' for the "
                        "event schema; analyze or merge streams with "
                        "the 'trace' subcommand (incl. the measured-vs-"
                        "modeled report section)")
    p.add_argument("--metrics-max-bytes", type=int, default=0,
                   metavar="N",
                   help="size-capped rotation for the --metrics stream: "
                        "when the file exceeds N bytes it rotates to "
                        "PATH.1 (previous rotation dropped) and a "
                        "sink:rotate event opens the fresh tail — "
                        "long supervised runs keep the newest ~2N "
                        "bytes of evidence (0 = unbounded)")
    p.add_argument("--progress", action="store_true",
                   help="live terminal status line at the supervised "
                        "chunk cadence (step, rate, MLUPS, ETA, mass "
                        "drift, outliers) rendered from the "
                        "supervisor's progress events; needs "
                        "--sentinel-every > 0")
    p.add_argument("--impl", default="xla",
                   choices=["xla", "pallas", "pallas_axis", "pallas_step",
                            "pallas_slab", "pallas_stage", "auto"],
                   help="kernel strategy (pallas = best available: fused/"
                        "VMEM-slab TPU kernels where eligible, XLA "
                        "otherwise — incl. for WENO7 and non-f32 dtypes, "
                        "where XLA measures faster / Pallas has no "
                        "lowering; pallas_slab = pin the 3-D whole-run "
                        "slab stepper; pallas_stage = pin the 3-D "
                        "per-stage stepper; pallas_axis = pin the "
                        "per-axis slab kernels; pallas_step = whole-step "
                        "temporal blocking; auto = measured: resolve the "
                        "rung AND --steps-per-exchange from the tuning "
                        "cache, measuring candidates on a miss when "
                        "--tune is given; the summary's 'kernel path' "
                        "line reports what actually ran)")
    p.add_argument("--steps-per-exchange", type=int, default=1,
                   metavar="K",
                   help="communication-avoiding halo cadence: exchange a "
                        "K*G-deep ghost zone once per K steps (redundant "
                        "ghost recompute in between) instead of G-deep "
                        "every step — sharded z-slab slab-rung runs "
                        "only; 1 = the reference's per-step MPI cadence; "
                        "with --impl auto the tuner picks K")
    p.add_argument("--exchange", choices=["collective", "dma"],
                   default="collective",
                   help="halo-exchange transport for sharded slab-rung "
                        "runs: collective = XLA ppermute between "
                        "compiled calls (default, the reference's MPI "
                        "shape); dma = in-kernel remote DMA — the "
                        "sharded whole-run Pallas program pushes its "
                        "ghost rows to the ±z neighbors itself and "
                        "never returns to XLA between steps (z-slab "
                        "meshes, TPU backend or the CPU interpret "
                        "simulator; validated loudly like --impl pins; "
                        "with --impl auto the tuner picks it)")
    p.add_argument("--tune", action="store_true",
                   help="allow the --impl auto tuner to MEASURE on a "
                        "cache miss: time the (rung x K) candidate "
                        "space (cost-model pruned) and persist the "
                        "winner to the tuning cache; without this, auto "
                        "uses the cache or falls back to --impl pallas")
    p.add_argument("--tuning-cache", default=None, metavar="PATH",
                   help="tuning decision cache file (default: "
                        "$TPUCFD_TUNING_CACHE or ~/.cache/"
                        "multigpu_advectiondiffusion_tpu/tuning.json); "
                        "atomic JSON, one audited decision per (solver, "
                        "shape, dtype, mesh, backend) key")
    p.add_argument("--ensemble", type=int, default=0, metavar="B",
                   help="batched ensemble engine: advance B independent "
                        "members (varying ICs and/or swept scalars — see "
                        "--sweep) in ONE compiled batched dispatch "
                        "instead of B serialized runs; per-member "
                        "summaries (max|u|, mass drift) and member-"
                        "attributed divergence ride the batch. Composes "
                        "with --mesh through a 'members' axis (--mesh "
                        "members=8, or members=4,dz=2 for the members x "
                        "z-slab composition — one dispatch serves B x P "
                        "users); uniform-physics ensembles fold B into "
                        "the whole-run slab rung's Pallas grid where it "
                        "engages. A purely spatial --mesh still declines "
                        "loudly (README 'Ensemble engine'; 0 = off)")
    p.add_argument("--sweep", action="append", default=[],
                   metavar="NAME=a:b",
                   help="member-varying parameter for --ensemble B: "
                        "NAME=a:b sweeps linearly across the B members, "
                        "NAME=v1,v2,... lists one value per member. NAME "
                        "is a member-varying scalar (diffusion: K/"
                        "diffusivity; burgers: cfl) or an IC parameter "
                        "as ic.PARAM (e.g. ic.width, ic.left/ic.right "
                        "for Riemann-state sweeps); repeatable")
    p.add_argument("--aot-cache", default=None, metavar="DIR",
                   help="persistent AOT executable cache (also "
                        "$TPUCFD_AOT_CACHE): compiled dispatch programs "
                        "are serialized here keyed by (solver, shape, "
                        "dtype, mesh, impl, steps-per-exchange, ensemble "
                        "B, operand avals, backend, jax version); a "
                        "repeat request deserializes instead of "
                        "recompiling (aot_cache:hit events; xla:cost "
                        "records compile_seconds_saved). Corrupt/stale "
                        "entries are misses, writes are atomic")
    p.add_argument("--dt-scale", type=float, default=1.0, metavar="F",
                   help="scale the initial time step (fixed-dt "
                        "solvers) or CFL (adaptive) by F before the "
                        "run — the scheduler's dt-backoff INHERITANCE "
                        "knob: a retried job starts at the reduced dt "
                        "its failed attempt backed off to instead of "
                        "re-diverging at full dt (applied after resume "
                        "validation; 1.0 = off)")
    p.add_argument("--overlap", default="padded",
                   choices=["padded", "split"],
                   help="sharded halo schedule: 'padded' exchanges before "
                        "each stencil, 'split' overlaps interior compute "
                        "with the in-flight exchange (on z-slab meshes the "
                        "fused steppers run the three-call interior/edge "
                        "schedule — the reference's five-stream "
                        "choreography, main.c:203-260)")


def _grid(args, ndim):
    from multigpu_advectiondiffusion_tpu.core.grid import Grid

    lengths = args.lengths if args.lengths is not None else [2.0] * ndim
    if args.bc and all(b == "periodic" for b in args.bc):
        return Grid.make_periodic(*args.n, lengths=lengths)
    return Grid.make(*args.n, lengths=lengths)


def _mesh_decomp(args, grid):
    mesh, sizes = parse_mesh_spec(args.mesh)
    return mesh, decomposition_for(grid, sizes)


def _run_model(spec, args, ndim, name=None, **build_extra):
    """ONE runner for every registered solver family: build the config
    through the spec's ``cli_build`` hook, then drive the shared
    single-run / batched-ensemble machinery. Adding a model touches the
    registry, never this function (ISSUE 15)."""
    grid = _grid(args, ndim)
    cfg = spec.cli_build(args, grid, ndim, **build_extra)
    name = name or f"{spec.name}{ndim}d"
    from multigpu_advectiondiffusion_tpu import telemetry

    # registry-resolution provenance: which family/spec served this
    # run (lands in the --metrics stream for --model AND subcommand
    # invocations alike)
    telemetry.event("model", "resolve", model=spec.name, ndim=ndim,
                    command=name)
    if args.ensemble and args.ensemble > 1:
        # batched ensemble engine: one vmapped dispatch advances every
        # member; sweep aliases (e.g. K -> diffusivity) come from the
        # family's registration spec
        return run_ensemble_solver(
            spec.solver_cls, cfg, name, args,
            aliases=dict(spec.sweep_aliases),
        )
    mesh, decomp = _mesh_decomp(args, grid)
    solver = spec.solver_cls(cfg, mesh=mesh, decomp=decomp)
    iters = args.iters if args.t_end is None else None
    if iters is None and args.t_end is None:
        iters = 100
    return run_solver(solver, name, iters=iters, t_end=args.t_end,
                      save_dir=args.save, plot=args.plot,
                      check_error=spec.check_error and args.check_error,
                      repeats=args.repeats,
                      snapshot_every=args.snapshot_every,
                      checkpoint_every=args.checkpoint_every,
                      checkpoint_keep=args.checkpoint_keep,
                      checkpoint_sharded=args.checkpoint_sharded,
                      resume=args.resume, profile_dir=args.profile,
                      sentinel_every=args.sentinel_every,
                      sentinel_growth=args.sentinel_growth,
                      max_retries=args.max_retries,
                      dt_backoff=args.dt_backoff,
                      watchdog_timeout=args.watchdog_timeout,
                      sdc_every=args.sdc_every,
                      progress=args.progress,
                      diag_every=args.diag_every,
                      diag_strict=args.diag_strict,
                      snapshots=args.snapshots,
                      snapshot_stride=args.snapshot_stride,
                      snapshot_max_bytes=args.snapshot_max_bytes,
                      dt_scale=args.dt_scale,
                      metrics_path=getattr(args, "metrics", None),
                      metrics_max_bytes=args.metrics_max_bytes)


def _run_convergence(args):
    """The TestingAccuracy.m equivalent: grid-refinement OOA study.

    ``--save DIR`` archives the study the way TestingAccuracy.m does
    (``Matlab_Prototipes/DiffusionNd/TestingAccuracy.m:51-70`` saves
    ``TestAccuracy.fig`` + ``.log``): the printed table as
    ``convergence.log``, machine-readable rows as ``convergence.json``,
    and a loglog error-vs-h figure as ``convergence.png`` (when
    matplotlib is available).
    """
    import json as _json

    from multigpu_advectiondiffusion_tpu.core.grid import Grid
    from multigpu_advectiondiffusion_tpu.models.diffusion import (
        DiffusionConfig,
        DiffusionSolver,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import observed_order

    ndim = args.ndim
    ns = args.cells or {1: [17, 33, 65, 129], 2: [17, 33, 65],
                        3: [9, 17, 33]}[ndim]
    lines = [
        f"-- diffusion{ndim}d grid-refinement study "
        f"(TestingAccuracy.m analog), dtype={args.dtype}",
        f"{'n':>6} {'L1':>12} {'Linf':>12} {'OOA(L1)':>8}",
    ]
    rows = []
    prev_l1 = None
    for n in ns:
        grid = Grid.make(*(n,) * ndim, lengths=10.0)
        solver = DiffusionSolver(
            DiffusionConfig(grid=grid, dtype=args.dtype, order=args.order)
        )
        out = solver.advance_to(solver.initial_state(), args.t_end)
        norms = solver.error_norms(out, t=args.t_end)
        ooa = observed_order(prev_l1, norms.l1) if prev_l1 else None
        lines.append(
            f"{n:>6} {norms.l1:>12.4e} {norms.linf:>12.4e} "
            + (f"{ooa:8.2f}" if ooa is not None else " " * 8)
        )
        rows.append({"n": n, "h": grid.spacing[0], "l1": norms.l1,
                     "linf": norms.linf, "ooa_l1": ooa})
        prev_l1 = norms.l1
    print("\n".join(lines))
    if args.save:
        import os

        os.makedirs(args.save, exist_ok=True)
        from multigpu_advectiondiffusion_tpu.utils.io import (
            atomic_write_text,
        )

        atomic_write_text(os.path.join(args.save, "convergence.log"),
                          "\n".join(lines) + "\n")
        atomic_write_text(
            os.path.join(args.save, "convergence.json"),
            _json.dumps({"ndim": ndim, "dtype": args.dtype,
                         "order": args.order, "t_end": args.t_end,
                         "rows": rows}, indent=1),
        )
        from multigpu_advectiondiffusion_tpu.utils.plot import (
            plot_convergence,
        )

        try:
            plot_convergence(
                rows, args.order,
                os.path.join(args.save, "convergence.png"),
                title=f"diffusion{ndim}d OOA study",
            )
        except ImportError:
            pass  # matplotlib not installed: log/json still archived
    return None


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="multigpu_advectiondiffusion_tpu")
    sub = ap.add_subparsers(dest="command", required=True)

    # model subcommands are GENERATED from the solver-plugin registry:
    # every registered family gets <name>{1,2,3}d commands with its
    # spec's flags — a new model registers itself (models/registry.py)
    # and appears here with zero CLI edits (ISSUE 15)
    from multigpu_advectiondiffusion_tpu.models import (
        registry as model_registry,
    )

    for spec in model_registry.specs():
        for ndim in spec.cli_dims:
            p = sub.add_parser(
                f"{spec.name}{ndim}d",
                help=f"{ndim}-D {spec.description}",
            )
            _add_common(p, ndim)
            spec.cli_configure(p, ndim)
            p.set_defaults(
                fn=lambda a, s=spec, d=ndim: _run_model(s, a, d)
            )

    # the axisymmetric r-y geometry stays a dedicated command (its
    # defaults differ), but runs through the SAME registry spec
    p = sub.add_parser("diffusion-axisym",
                       help="axisymmetric r-y diffusion "
                            "(heat2d_axisymmetric.m)")
    _add_common(p, 2)
    model_registry.get("diffusion").cli_configure(p, 2, axisym=True)
    p.set_defaults(fn=lambda a: _run_model(
        model_registry.get("diffusion"), a, 2,
        name="diffusion_axisym", geometry="axisymmetric",
    ))

    p = sub.add_parser("convergence",
                       help="grid-refinement accuracy study "
                            "(TestingAccuracy.m)")
    p.add_argument("--ndim", type=int, default=3, choices=[1, 2, 3])
    p.add_argument("--cells", type=int, nargs="*", default=None)
    p.add_argument("--t-end", type=float, default=0.2)
    p.add_argument("--dtype", default="float64")
    p.add_argument("--order", type=int, default=4, choices=[2, 4])
    p.add_argument("--save", default=None, metavar="DIR",
                   help="archive the study (convergence.log/.json + "
                        "loglog .png) like TestingAccuracy.m's "
                        "TestAccuracy.fig/.log")
    p.set_defaults(fn=_run_convergence)

    # tpucfd-trace: the consumable layer over --metrics streams (also
    # runnable standalone: python -m multigpu_advectiondiffusion_tpu.cli.trace)
    from multigpu_advectiondiffusion_tpu.cli import trace as trace_cli

    p = sub.add_parser("trace",
                       help="analyze/merge --metrics JSONL streams "
                            "(tpucfd-trace): cross-rank clock-aligned "
                            "merge, phase breakdown, measured-vs-"
                            "roofline per rung, critical path, "
                            "Chrome/Perfetto trace_event export")
    trace_cli.configure_parser(p)

    # tpucfd-check: project static analysis (also standalone:
    # python -m multigpu_advectiondiffusion_tpu.analysis)
    from multigpu_advectiondiffusion_tpu.analysis import cli as check_cli

    p = sub.add_parser("check",
                       help="static analysis (tpucfd-check): AST lint "
                            "rules (closure constants, host syncs in "
                            "traced code, non-atomic writes, "
                            "unregistered telemetry) + the stencil/"
                            "halo consistency verifier; --selftest "
                            "proves every rule trips on a seeded "
                            "violation")
    check_cli.configure_parser(p)

    # crash-safe multi-run scheduler (service/): a journaled queue of
    # run requests multiplexed onto the device budget
    from multigpu_advectiondiffusion_tpu.service import cli as service_cli

    p = sub.add_parser("serve",
                       help="run the crash-safe job scheduler daemon: "
                            "journaled queue, admission control "
                            "(memory watermarks + AOT-warm), priority "
                            "preemption via the checkpoint-and-exit-75 "
                            "path, bounded per-policy retries; "
                            "--verify replays and linearization-checks "
                            "the journal offline (README 'Service "
                            "mode')")
    service_cli.configure_serve(p)

    p = sub.add_parser("submit",
                       help="park one run request in the scheduler's "
                            "spool (atomic; works while no daemon "
                            "runs): submit --root DIR [--priority N "
                            "--devices P] -- diffusion3d --n ... "
                            "--iters ...")
    service_cli.configure_submit(p)

    # continuous-batching request server (service/server.py): scenario
    # requests coalesced onto the ensemble member axis and marched as
    # one batched dispatch, crash-safe by journal replay
    p = sub.add_parser("serve-requests",
                       help="run the crash-safe continuous-batching "
                            "request server: compatible requests "
                            "coalesce onto one batched ensemble "
                            "dispatch, march in bounded slices "
                            "(finished members return, joiners enter "
                            "at slice boundaries), shed-with-retry-"
                            "after under overload; --verify replays "
                            "and linearization-checks the request "
                            "journal offline (README 'Request "
                            "serving')")
    service_cli.configure_serve_requests(p)

    p = sub.add_parser("request",
                       help="park one scenario request in the "
                            "server's spool (atomic; works while no "
                            "server runs): request --root DIR --model "
                            "diffusion --n 64 64 --t-end 0.2 "
                            "[--operand diffusivity=0.5 --wait 60]")
    service_cli.configure_request(p)

    p = sub.add_parser("migrate",
                       help="upgrade a service root's journal to the "
                            "current schema version in place (atomic "
                            "tempfile + rename; idempotent; refuses "
                            "journals stamped with a future version)")
    service_cli.configure_migrate(p)

    # tpucfd-status: the fleet dashboard (also standalone:
    # python -m multigpu_advectiondiffusion_tpu.cli.status)
    from multigpu_advectiondiffusion_tpu.cli import status as status_cli

    p = sub.add_parser("status",
                       help="fleet status dashboard (tpucfd-status): "
                            "journal-replayed request/job states + "
                            "merged cross-process metrics snapshots "
                            "(latency quantiles, queue depth, SLO "
                            "verdict) — live tty redraw, --once for "
                            "scripts, --json for machines")
    status_cli.configure_parser(p)
    p.set_defaults(fn=status_cli.run)

    return ap


def _resolve_model_argv(argv):
    """``--model NAME [--ndim N] ...`` -> the registry-resolved
    ``<NAME><N>d`` subcommand (``tpucfd --model adr --n 64 64 64 ...``).
    ``N`` comes from an explicit ``--ndim`` or the arity of ``--n``;
    unknown model names fail listing the registered families. Leaves
    every other argv untouched."""
    if not argv or argv[0] != "--model":
        return argv
    if len(argv) < 2:
        raise SystemExit("--model needs a model name")
    from multigpu_advectiondiffusion_tpu.models import (
        registry as model_registry,
    )

    name = argv[1]
    rest = list(argv[2:])
    try:
        spec = model_registry.get(name)
    except KeyError as err:
        raise SystemExit(str(err))
    ndim = None
    if "--ndim" in rest:
        i = rest.index("--ndim")
        try:
            ndim = int(rest[i + 1])
        except (IndexError, ValueError):
            raise SystemExit("--ndim wants an integer")
        del rest[i:i + 2]
    elif "--n" in rest:
        j = rest.index("--n") + 1
        ndim = 0
        while j + ndim < len(rest):
            tok = rest[j + ndim]
            try:
                int(tok)
            except ValueError:
                break
            ndim += 1
    if not ndim:
        raise SystemExit(
            "--model needs --ndim N or --n (to infer dimensionality)"
        )
    if ndim not in spec.cli_dims:
        raise SystemExit(
            f"model {name!r} serves {spec.cli_dims}-D grids, not {ndim}-D"
        )
    return [f"{name}{ndim}d"] + rest


def main(argv=None):
    from multigpu_advectiondiffusion_tpu.utils.compile_cache import (
        use_compile_cache,
    )

    use_compile_cache()
    if argv is None:
        argv = sys.argv[1:]
    argv = _resolve_model_argv(list(argv))
    args = build_parser().parse_args(argv)
    # telemetry sink BEFORE any distributed/backend work, so the
    # multihost join's retry loop and every later subsystem stream into
    # the same --metrics file
    owned_sink = None
    if getattr(args, "metrics", None):
        from multigpu_advectiondiffusion_tpu import telemetry

        owned_sink = telemetry.install(
            args.metrics,
            max_bytes=getattr(args, "metrics_max_bytes", 0),
        )
    if getattr(args, "aot_cache", None):
        # persistent AOT executable cache: every dispatch program this
        # process compiles is serialized under DIR, and every repeat
        # request (this process or a later one) deserializes instead
        from multigpu_advectiondiffusion_tpu.tuning import aot_cache

        aot_cache.configure(cache_dir=args.aot_cache, enabled=True)
    if getattr(args, "checkify", False):
        # runtime sanitizer: arm process-wide BEFORE any solver builds
        # its dispatch programs (analysis/sanitizer.py)
        from multigpu_advectiondiffusion_tpu.analysis import sanitizer

        sanitizer.configure(enabled=True)
    if getattr(args, "tune", False) or getattr(args, "tuning_cache", None):
        # tuner surface: --tune allows measurement on a cache miss,
        # --tuning-cache points both lookup and persistence at PATH
        from multigpu_advectiondiffusion_tpu import tuning

        tuning.configure(
            cache_path=getattr(args, "tuning_cache", None),
            enabled=True if getattr(args, "tune", False) else None,
        )
    if getattr(args, "num_processes", None) is not None or getattr(
        args, "process_id", None
    ) is not None:
        # symmetric validation: without it, forgetting --coordinator
        # would silently run N independent solves racing on --save
        if not getattr(args, "coordinator", None):
            raise SystemExit(
                "--num-processes/--process-id need --coordinator"
            )
    if getattr(args, "coordinator", None):
        # the mpirun analog (MultiGPU/*/run.sh `mpirun -np 2 ...`): join
        # this process into the jax.distributed runtime BEFORE any
        # backend/mesh work, so jax.devices() spans every process
        if args.num_processes is None or args.process_id is None:
            raise SystemExit(
                "--coordinator needs --num-processes and --process-id"
            )
        from multigpu_advectiondiffusion_tpu.parallel import multihost

        multihost.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    if getattr(args, "dtype", None) == "float64":
        import jax

        jax.config.update("jax_enable_x64", True)
    from multigpu_advectiondiffusion_tpu.resilience.errors import (
        EXIT_RANK_FAILURE,
        EXIT_SDC,
        RankFailureError,
        SDCDetectedError,
    )

    try:
        return args.fn(args)
    except RankFailureError as err:
        # a peer is dead/wedged: exit with the documented code (the
        # watchdog's monitor thread takes the os._exit path instead
        # when the main thread is unreachable inside a collective)
        print(f"rank failure: {err}; exiting {EXIT_RANK_FAILURE}",
              file=sys.stderr, flush=True)
        import jax

        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.get_sink().close()
        if jax.process_count() > 1:
            # a normal SystemExit would run jax.distributed's atexit
            # shutdown, which blocks on the DEAD peer's disconnect —
            # the hang this exit path exists to rule out
            import os

            os._exit(EXIT_RANK_FAILURE)
        raise SystemExit(EXIT_RANK_FAILURE)
    except SDCDetectedError as err:
        # only reaches the CLI when the rollback budget ran out
        print(f"unrecovered silent data corruption: {err}; "
              f"exiting {EXIT_SDC}", file=sys.stderr)
        raise SystemExit(EXIT_SDC)
    finally:
        if owned_sink is not None:
            from multigpu_advectiondiffusion_tpu import telemetry

            telemetry.uninstall(owned_sink)


if __name__ == "__main__":
    sys.exit(0 if main() is not False else 1)
