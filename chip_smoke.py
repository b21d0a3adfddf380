#!/usr/bin/env python3
"""Bring-up smoke: the solver, sharded and served paths on TPU chips.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the dz=4 sharded phase

(a) diffusion3d at the reference's 400x200x206 interior
    (``MultiGPU/Diffusion3d_Baseline``) through the CLI, ``--impl pallas``
    against ``--impl xla`` on the same chip, plus the analytic error.
(b) burgers3d WENO5-JS at 512^3, nu = 1e-5, fixed dt
    (``SingleGPU/Burgers3d_WENO5``), ``--impl pallas`` against ``xla``.
(c) ``serve-requests --until-idle --pipeline`` (donation on) answering
    four spooled diffusion3d 128^3 requests of one coalesce key, each
    compared with a direct solve of the same request.

``--chips 4`` runs diffusion3d (508x204x240) on a ``dz=4`` mesh with the
collective split-overlap exchange and with the in-kernel ``dma``
exchange, and burgers3d (400x400x408) with the split-overlap exchange,
each compared with the unsharded run on device 0. Burgers has no ``dma``
case: its slab program takes ~8 minutes to compile (484.5 s for a
described v5e at 192x192x408, PR 21), four chips' worth of budget.

Every phase prints one line (phase, engaged rung, compile and run
seconds, max error). The last line is the JSON verdict. Any failed check,
or a first device that is not a TPU, exits non-zero before that line.
The phases are functions of their sizes so that a CPU test can run them
small (``tests/test_chip_smoke.py``); the script itself runs on a TPU
only.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# the rungs that mean "a fused Pallas kernel runs the step"
FUSED_RUNGS = ("fused-whole-run-slab", "fused-stage")


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


# --------------------------------------------------------------------- #
# Checks (pure functions of what a phase produced)
# --------------------------------------------------------------------- #
def check_engaged(engaged: dict, rungs=FUSED_RUNGS, **want) -> str:
    """The stepper that ran is a fused rung, nothing fell back or
    degraded, and every ``want`` key has its value; returns the rung."""
    stepper = engaged.get("stepper")
    if stepper not in rungs:
        raise SmokeFailure(f"engaged {stepper!r}, want one of {rungs}")
    if engaged.get("fallback"):
        raise SmokeFailure(f"fused fallback: {engaged['fallback']}")
    if engaged.get("degraded"):
        raise SmokeFailure(f"kernel ladder degraded: {engaged['degraded']}")
    for key, val in want.items():
        if engaged.get(key) != val:
            raise SmokeFailure(
                f"engaged {key}={engaged.get(key)!r}, want {val!r}"
            )
    return stepper


def check_close(got, ref, rtol: float, atol_scale: float,
                what: str) -> float:
    """``got`` agrees with ``ref`` within ``rtol`` and an absolute
    ``atol_scale * max|ref|``; returns the max abs difference."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise SmokeFailure(f"{what}: shape {got.shape} != {ref.shape}")
    if not np.isfinite(got).all():
        raise SmokeFailure(f"{what}: non-finite values")
    diff = np.abs(got - ref)
    atol = atol_scale * float(np.max(np.abs(ref)))
    bad = diff > atol + rtol * np.abs(ref)
    if bad.any():
        raise SmokeFailure(
            f"{what}: {int(bad.sum())} cells outside rtol={rtol:g}, "
            f"atol={atol:.3e}; max diff {float(diff.max()):.3e}"
        )
    return float(diff.max())


def check_kernels(line: dict, what: str) -> int:
    """The phase's compiled program holds Mosaic kernels
    (``tpu_custom_call``): the Pallas path ran compiled for the chip,
    not interpreted."""
    n = int(line.get("kernel_calls") or 0)
    if n <= 0:
        raise SmokeFailure(f"{what}: no tpu_custom_call in the program")
    return n


def check_devices(arr, n: int, what: str) -> None:
    """The output lives on ``n`` distinct devices."""
    got = len(arr.sharding.device_set)
    if got != n:
        raise SmokeFailure(f"{what}: output on {got} device(s), want {n}")


# --------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------- #
def _cli(argv) -> object:
    from multigpu_advectiondiffusion_tpu.cli.__main__ import main

    out = main([str(a) for a in argv])
    if out is False:
        raise SmokeFailure(f"CLI failed: {' '.join(map(str, argv))}")
    return out


def _cli_run(workdir: str, tag: str, argv) -> tuple:
    """One CLI solve saved under ``workdir/tag``: (summary dict, field)."""
    from multigpu_advectiondiffusion_tpu.utils.io import load_binary

    d = os.path.join(workdir, tag)
    _cli([*argv, "--save", d])
    with open(os.path.join(d, "summary.json")) as f:
        summary = json.load(f)
    nx, ny, nz = summary["grid_xyz"]
    u = load_binary(os.path.join(d, "result.bin"), (nz, ny, nx))
    shutil.rmtree(d)
    return summary, u


def _line(phase: str, rung: str, compile_s: float, run_s: float,
          max_err: float, **extra) -> dict:
    rec = {"phase": phase, "rung": rung,
           "compile_s": round(float(compile_s), 3),
           "run_s": round(float(run_s), 4),
           "max_err": float(max_err), **extra}
    print("phase " + json.dumps(rec), flush=True)
    return rec


def _pallas_vs_xla(workdir: str, phase: str, argv, rtol: float,
                   atol_scale: float) -> tuple:
    """Run ``argv`` with ``--impl pallas`` and ``--impl xla``; check the
    fused rung and the agreement. Returns (pallas summary, max diff)."""
    sp, up = _cli_run(workdir, "pallas", [*argv, "--impl", "pallas"])
    check_engaged(sp["engaged"])
    _, ux = _cli_run(workdir, "xla", [*argv, "--impl", "xla"])
    err = check_close(up, ux, rtol, atol_scale, f"{phase} pallas vs xla")
    return sp, err


def phase_diffusion(workdir: str, n=(400, 200, 206),
                    lengths=(10.0, 5.0, 5.15), iters: int = 300) -> dict:
    """(a) diffusion3d through the CLI: fused rung, analytic error under
    ``tests/test_cli.py``'s bound, pallas = xla at ``tests/test_pallas.py``
    tolerances."""
    argv = ["diffusion3d", "--n", *n, "--lengths", *lengths,
            "--iters", iters, "--repeats", 2, "--check-error"]
    sp, err = _pallas_vs_xla(workdir, "diffusion3d", argv, 1e-4, 1e-6)
    l1 = sp.get("error_l1")
    if l1 is None or not np.isfinite(l1) or l1 >= 1.0:
        raise SmokeFailure(f"diffusion3d analytic L1 error {l1}")
    return _line("diffusion3d", sp["engaged"]["stepper"],
                 sp["xla"]["compile_seconds"], sp["seconds"], err,
                 error_l1=l1, error_linf=sp.get("error_linf"),
                 kernel_calls=sp["xla"].get("kernel_calls"),
                 mlups=sp.get("mlups"))


def phase_burgers(workdir: str, n=(512, 512, 512), iters: int = 5) -> dict:
    """(b) burgers3d WENO5-JS, nu=1e-5, fixed dt, through the CLI:
    fused rung, pallas = xla at ``tests/test_pallas.py:478-480``."""
    argv = ["burgers3d", "--n", *n, "--lengths", 2, 2, 2, "--nu", 1e-5,
            "--fixed-dt", "--iters", iters, "--repeats", 2]
    sp, err = _pallas_vs_xla(workdir, "burgers3d", argv, 2e-5, 2e-6)
    return _line("burgers3d", sp["engaged"]["stepper"],
                 sp["xla"]["compile_seconds"], sp["seconds"], err,
                 kernel_calls=sp["xla"].get("kernel_calls"),
                 mlups=sp.get("mlups"))


def phase_serving(workdir: str, n=(128, 128, 128),
                  t_ends=(0.11, 0.115, 0.12, 0.125)) -> dict:
    """(c) the request server through the CLI: four spooled requests of
    one coalesce key answered by ``serve-requests --until-idle
    --pipeline`` (donation on), each equal to a direct solve."""
    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )
    from multigpu_advectiondiffusion_tpu.utils.io import load_binary

    root = os.path.join(workdir, "serve")
    lengths = (10.0, 10.0, 10.0)
    for i, te in enumerate(t_ends):
        _cli(["request", "--root", root, "--request-id", f"r{i}",
              "--model", "diffusion", "--n", *n, "--lengths", *lengths,
              "--t-end", te])
    # the server's own stream holds its queue; the dispatch layer's
    # xla:cost (donation) and ensemble:dispatch (rung) events go to the
    # process sink
    from multigpu_advectiondiffusion_tpu import telemetry

    events_path = os.path.join(workdir, "serve_xla.jsonl")
    sink = telemetry.install(events_path)
    t0 = time.perf_counter()
    try:
        _cli(["serve-requests", "--root", root, "--until-idle",
              "--pipeline", "--poll", 0.01])
    finally:
        telemetry.uninstall(sink)
    serve_s = time.perf_counter() - t0

    events = []
    with open(events_path) as f:
        for line in f:
            try:
                events.append(json.loads(line))
            except ValueError:
                pass
    costs = [e for e in events
             if e.get("kind") == "xla" and e.get("name") == "cost"]
    if not any(e.get("donated") for e in costs):
        raise SmokeFailure("serving: no donated program was compiled")
    rungs = sorted({e.get("stepper") for e in events
                    if e.get("kind") == "ensemble"
                    and e.get("name") == "dispatch"})
    compile_s = sum(float(e.get("compile_seconds") or 0) for e in costs)

    grid = Grid.make(*n, lengths=lengths)
    solver = DiffusionSolver(DiffusionConfig(grid=grid))
    err = 0.0
    for i, te in enumerate(t_ends):
        d = os.path.join(root, "requests", f"r{i}")
        with open(os.path.join(d, "verdict.json")) as f:
            verdict = json.load(f)
        if verdict.get("status") != "done":
            raise SmokeFailure(f"serving: request r{i} {verdict}")
        got = load_binary(os.path.join(d, "result.bin"), grid.shape)
        ref = solver.advance_to(solver.initial_state(), te)
        err = max(err, check_close(got, ref.u, 1e-4, 1e-6,
                                   f"serving r{i} vs direct solve"))
    shutil.rmtree(root)
    return _line("serving", ",".join(map(str, rungs)), compile_s,
                 serve_s, err, requests=len(t_ends))


def _timed(fn) -> tuple:
    t0 = time.perf_counter()
    out = fn()
    out.u.block_until_ready()
    return out, time.perf_counter() - t0


def phase_sharded(n_dev: int = 4, diff_n=(508, 204, 240),
                  burg_n=(400, 400, 408), iters: int = 6) -> list:
    """``--chips 4``: diffusion3d with the collective split-overlap and
    the in-kernel ``dma`` exchanges and burgers3d with split-overlap on
    a ``dz=n_dev`` mesh, each against the unsharded run on device 0 of
    the same host."""
    import jax

    from multigpu_advectiondiffusion_tpu import (
        BurgersConfig,
        BurgersSolver,
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    devs = jax.devices()
    if len(devs) < n_dev:
        raise SmokeFailure(f"sharded: {len(devs)} device(s), want {n_dev}")
    mesh = make_mesh({"dz": n_dev}, devices=devs[:n_dev])
    split = ("split", {"impl": "pallas", "overlap": "split"},
             {"stepper": "fused-stage", "overlap": "split"})
    dma = ("dma", {"impl": "pallas_slab", "exchange": "dma"},
           {"stepper": "fused-whole-run-slab", "exchange": "dma"})
    burgers = {"nu": 1e-5, "adaptive_dt": False}
    cases = [
        ("diffusion3d", DiffusionSolver, DiffusionConfig,
         Grid.make(*diff_n, lengths=(12.7, 5.1, 6.0)), {}, 1e-4, 1e-6,
         (split, dma)),
        ("burgers3d", BurgersSolver, BurgersConfig,
         Grid.make(*burg_n, lengths=2.0), burgers, 2e-5, 2e-6, (split,)),
    ]
    lines = []
    for name, solver_cls, cfg_cls, grid, kw, rtol, atol_scale, scheds in (
            cases):
        with jax.default_device(devs[0]):
            ref_s = solver_cls(cfg_cls(grid=grid, dtype="float32",
                                       impl="pallas", **kw))
            check_engaged(ref_s.engaged_path())
            ref, _ = _timed(lambda: ref_s.run(ref_s.initial_state(),
                                              iters))
            ref_u = np.asarray(ref.u)
        del ref, ref_s
        for tag, cfg_kw, want in scheds:
            s = solver_cls(cfg_cls(grid=grid, dtype="float32", **kw,
                                   **cfg_kw),
                           mesh=mesh, decomp=Decomposition.slab("dz"))
            st = s.initial_state()
            out, first_s = _timed(lambda: s.run(st, iters))
            out, run_s = _timed(lambda: s.run(st, iters))
            rung = check_engaged(s.engaged_path(), **want)
            check_devices(out.u, n_dev, f"{name}[{tag}]")
            err = check_close(out.u, ref_u, rtol, atol_scale,
                              f"{name}[{tag}] sharded vs unsharded")
            nx, ny, nz = grid.shape_xyz
            lines.append(_line(f"{name}-dz{n_dev}-{tag}", rung,
                               first_s - run_s, run_s, err,
                               grid=f"{nx}x{ny}x{nz}"))
            del s, st, out
            gc.collect()
        del ref_u
    return lines


# --------------------------------------------------------------------- #
def _require_tpu():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax's first device is "
            f"{devs[0].platform!r} ({devs[0].device_kind})"
        )
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dz=4 sharded phase")
    args = ap.parse_args(argv)

    devs = _require_tpu()
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but jax "
                         f"found {len(devs)} device(s)")
    from multigpu_advectiondiffusion_tpu.utils.compile_cache import (
        use_compile_cache,
    )

    print(f"compile cache: {use_compile_cache()}", flush=True)
    # the opt-in AOT executable store would turn a compile into a load
    os.environ.pop("TPUCFD_AOT_CACHE", None)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phase_sharded(4)
        else:
            check_kernels(phase_diffusion(workdir), "diffusion3d")
            gc.collect()
            check_kernels(phase_burgers(workdir), "burgers3d")
            gc.collect()
            phase_serving(workdir)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
