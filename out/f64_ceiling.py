"""Measure what bounds the f64 diffusion row (VERDICT r4 item 8).

The apples-to-apples `diffusion3d_f64` bench row (literal 400x200x206
grid, XLA path) holds the table's slimmest margin (~1.96x the
reference's own f64 rate). This script pins down WHY the rate is what
it is — emulation op mix vs HBM bytes — with three direct measurements
on one chip:

1. stream roof, f32 vs f64: a fused elementwise pass (y = x*a+b) at
   fixed element count — if f64 were bandwidth-bound it would run at
   half the f32 element rate (2x bytes);
2. ALU roof, f32 vs f64: a 64-deep in-register multiply-add chain per
   element (XLA fuses it into one pass, traffic amortized away) — the
   f32/f64 rate ratio IS the chip's f64 software-emulation factor;
3. the diffusion solver itself, f32 vs f64, same grid and XLA path —
   whichever ratio (bytes 2x vs emulation Nx) the solver ratio lands on
   names the binding resource.

Also probes whether an XLA-level knob moves the f64 row (the only
plausible lever once the bound is arithmetic): `xla_allow_excess
_precision`-style flags don't apply to true f64 semantics, and there is
no "fast-f64" mode in XLA:TPU — the probe documents the absence rather
than asserting it. Findings land in PARITY.md's precision story.

Run: python out/f64_ceiling.py  (real TPU; ~3 min)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from multigpu_advectiondiffusion_tpu.bench.timing import _timed, timed_run
from multigpu_advectiondiffusion_tpu.core.grid import Grid
from multigpu_advectiondiffusion_tpu.models.diffusion import (
    DiffusionConfig,
    DiffusionSolver,
)

REPS = 5
N_ELEMS = 64 * 1024 * 1024  # 256 MiB f32 / 512 MiB f64 per operand
CHAIN = 64
ITERS = 50


K = 100  # in-jit repetitions: amortize dispatch/fetch over many passes


def _rate(dtype, body, n=N_ELEMS):
    """Gelem/s per pass of ``body``, measured as K fori_loop passes
    inside ONE jit (each pass reads + writes the carry through HBM) so
    the per-program dispatch/host-fetch overhead — which the sync-fetch
    timing discipline (bench/timing.py) pays once per program — is
    amortized to nothing. Returns element rate per pass."""
    from jax import lax

    x = jnp.arange(n, dtype=dtype) * jnp.asarray(1e-9, dtype)

    def loop(v, k):
        return lax.fori_loop(0, k, lambda i, u: body(u), v)

    f = jax.jit(lambda v: loop(v, K))
    z = jax.jit(lambda v: loop(v, 0))
    tr = _timed(lambda: f(x), lambda: z(x), REPS)
    return n * K / tr.seconds / 1e9  # Gelem/s per pass


def main():
    print(f"device: {jax.devices()[0]}\n")

    # 1) stream: one fused elementwise pass, read + write
    stream = {}
    for dt in ("float32", "float64"):
        # dtype-typed constants: a python-float literal would promote
        # the f32 row to f64 under enable_x64
        g = _rate(
            dt,
            lambda v: v * jnp.asarray(1.000001, v.dtype)
            + jnp.asarray(0.5, v.dtype),
        )
        stream[dt] = g
        bytes_per = 2 * jnp.dtype(dt).itemsize
        print(f"stream  {dt}: {g:6.1f} Gelem/s = {g * bytes_per:6.0f} GB/s")
    print(f"stream f32/f64 element-rate ratio: "
          f"{stream['float32'] / stream['float64']:.2f}x "
          f"(pure bytes would be 2.00x)\n")

    # 2) ALU chain: 64 multiply-adds per element, fused into one pass
    def chain(v):
        a = jnp.asarray(1.000001, v.dtype)
        b = jnp.asarray(1e-12, v.dtype)
        for _ in range(CHAIN):
            v = v * a + b
        return v

    alu = {}
    for dt in ("float32", "float64"):
        g = _rate(dt, chain, n=N_ELEMS // 16)
        alu[dt] = g
        print(f"alu-chain  {dt}: {g * CHAIN:7.1f} GFMA/s")
    emu = alu["float32"] / alu["float64"]
    print(f"f64 emulation factor (ALU): {emu:.1f}x\n")

    # 3) the solver row itself, f32 vs f64, literal grid, XLA path
    grid = Grid.make(400, 200, 206, lengths=(10.0, 5.0, 5.15))
    rates = {}
    for dt in ("float32", "float64"):
        s = DiffusionSolver(
            DiffusionConfig(grid=grid, diffusivity=1.0, dtype=dt,
                            impl="xla")
        )
        tr = timed_run(s, s.initial_state(), ITERS, reps=REPS)
        rates[dt] = grid.num_cells * ITERS * 3 / tr.seconds / 1e6
        print(f"diffusion XLA {dt}: {rates[dt]:7.0f} MLUPS "
              f"(spread {tr.spread:.2f})")
    ratio = rates["float32"] / rates["float64"]
    print(f"solver f32/f64 ratio: {ratio:.1f}x "
          f"(bytes-bound would be ~2x; emulation-bound ~{emu:.0f}x)")

    # implied HBM traffic of the f64 row at a conservative >= 2
    # passes/stage (stage read + write; XLA materializes more)
    gbs = rates["float64"] * 1e6 * 2 * 8 / 1e9
    print(f"f64 row implied HBM floor: {gbs:.0f} GB/s "
          f"(vs ~819 GB/s pin) -> "
          f"{'HBM-bound' if gbs > 600 else 'NOT HBM-bound'}")


if __name__ == "__main__":
    main()
