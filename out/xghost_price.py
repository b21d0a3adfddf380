"""Price the stored-x-ghost layout of the fused 3-D Burgers stepper.

The lane-aligned default layout stores no x ghosts (every transfer and
non-x op runs at round128(nx) lanes); the x-sharded layout stores real
ghost lanes at round128(nx + 2r), paying one extra lane tile at the
bench shape. This script measures that tax on one chip at 512^3 viscous
fixed-dt (the ladder's flagship workload) and compares it against what
an x-sharded mesh would otherwise get — the generic XLA path — so the
engage-or-decline decision in models/burgers.py is evidence, not
argument. Table lands in PARITY.md ("x-sharded fused Burgers").

Run: python out/xghost_price.py  (real TPU; ~2 min)
     python out/xghost_price.py --sweep  (block sweep for the 640-lane
     layout; the order-5 preference (8,64) ties the best block there
     within run-to-run drift — see sweep()'s docstring)
"""

import dataclasses
import os
import sys

# repo-root import bootstrap: run as a script from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from multigpu_advectiondiffusion_tpu.bench.timing import _timed
from multigpu_advectiondiffusion_tpu.core.grid import Grid
from multigpu_advectiondiffusion_tpu.models.burgers import (
    BurgersConfig,
    BurgersSolver,
)
from multigpu_advectiondiffusion_tpu.ops import flux as flux_lib
from multigpu_advectiondiffusion_tpu.ops.pallas.fused_burgers import (
    FusedBurgersStepper,
)

N = 512
ITERS = 50
REPS = 5


def mlups(tr, iters=ITERS):
    # stage-update convention (3 RK stages/step), as everywhere else
    return N**3 * iters * 3 / tr.seconds / 1e6


def sweep():
    """Block sweep of the stored-x-ghost layout at 512^3 (the default
    preference was tuned on the 512-lane layout; this checks it holds
    at 640 lanes). Measured 2026-07-31 over 4 independent passes:
    (8,64) and (16,32) tie within run-to-run drift (8,067-8,399 vs
    8,146-8,602 MLUPS, means ~1% apart); the rest are clearly behind
    ((8,32) ~8,0xx > (4,64) ~7,9xx > (8,16)/(16,16) ~7,3-7,8xx >
    (16,64) ~7,1-7,3xx) — the production preference stays correct."""
    grid = Grid.make(N, N, N, lengths=2.0)
    dt = 0.4 * min(grid.spacing)
    u0 = jnp.zeros((N, N, N), jnp.float32)
    t0 = jnp.zeros((), jnp.float32)
    iters = 20
    for blk in [(8, 64), (8, 32), (16, 32), (8, 16), (4, 64), (16, 64)]:
        try:
            st = FusedBurgersStepper(
                (N, N, N), jnp.float32, grid.spacing,
                flux_lib.get("burgers"), "js", 1e-5, dt=dt,
                x_sharded=True, block=blk,
            )
        except ValueError as e:  # the constructor's documented decline
            print(blk, "unsupported:", e)
            continue
        run = jax.jit(lambda u, t, s=st: s.run(u, t, iters)[0])
        zero = jax.jit(lambda u, t, s=st: s.run(u, t, 0)[0])
        tr = _timed(lambda: run(u0, t0), lambda: zero(u0, t0), 3)
        print(blk, f"{mlups(tr, iters):.0f} MLUPS")


def main():
    grid = Grid.make(N, N, N, lengths=2.0)
    cfg = BurgersConfig(grid=grid, nu=1e-5, dtype="float32",
                        adaptive_dt=False, impl="pallas")
    solver = BurgersSolver(cfg)
    state = solver.initial_state()
    u0, t0 = state.u, state.t

    rows = []

    def time_stepper(label, stepper):
        run = jax.jit(lambda u, t: stepper.run(u, t, ITERS)[0])
        zero = jax.jit(lambda u, t: stepper.run(u, t, 0)[0])
        tr = _timed(lambda: run(u0, t0), lambda: zero(u0, t0), REPS)
        rows.append((label, mlups(tr), tr.spread, stepper.padded_shape[2]))
        return run

    fused = solver._fused_stepper()
    assert fused is not None and not fused.x_sharded
    run_std = time_stepper("fused lane-aligned (default)", fused)

    xg = FusedBurgersStepper(
        (N, N, N), jnp.float32, grid.spacing, flux_lib.get("burgers"),
        "js", 1e-5, dt=solver.dt, x_sharded=True,
    )
    run_xg = time_stepper("fused stored-x-ghost", xg)

    # trajectory equality: same kernels, different x layout
    a = np.asarray(run_std(u0, t0))
    b = np.asarray(run_xg(u0, t0))
    scale = float(np.max(np.abs(a)))
    err = float(np.max(np.abs(a - b))) / scale
    assert err <= 32 * np.finfo(np.float32).eps, err

    # generic path via the solver API (jit cache inside the solver)
    from multigpu_advectiondiffusion_tpu.bench.timing import timed_run

    xs = BurgersSolver(dataclasses.replace(cfg, impl="xla"))
    tr = timed_run(xs, state, ITERS, reps=REPS)
    rows.append(("generic XLA (the x-sharded fallback before)",
                 mlups(tr), tr.spread, N))

    print(f"\n512^3 viscous Burgers, fixed dt, f32, one chip "
          f"({jax.devices()[0].platform}):")
    print(f"{'path':<44} {'MLUPS':>8} {'spread':>7} {'lanes':>6}")
    for label, rate, spread, px in rows:
        print(f"{label:<44} {rate:>8.0f} {spread:>7.2f} {px:>6}")
    std = rows[0][1]
    print(f"\nx-ghost tax vs default: {(1 - rows[1][1] / std) * 100:.1f}%  "
          f"(layout {rows[1][3]} vs {rows[0][3]} lanes)")
    print(f"x-ghost vs generic: {rows[1][1] / rows[2][1]:.2f}x")
    print(f"max-trajectory-diff/scale after {ITERS} steps: {err:.2e}")


if __name__ == "__main__":
    if "--sweep" in sys.argv:
        sweep()
    else:
        main()
