"""Time the fused WENO5 stage kernels alone at several y blocks, on the
shard shape of the four-chip Burgers deployment (1000x1000x200 local,
inviscid, split-overlap roles), one chip.

The shard's stepper is built as one of four z-slabs (``global_shape``
four times as deep), so the interior/bottom/top calls of the split
schedule run; the exchange is stood in for by the shard's own boundary
planes, so no collective runs and the step is the kernels plus two
small slices a stage. Each block's rows are worked on ``round_up(ly,
by)`` rows, the rule the stepper applies to an explicit block.

Prints one JSON line per block: ``by``, the worked rows, the step in ms
(median of ``--reps`` differences of ``--steps`` steps and none), and
the compile seconds.

Run: python out/weno_yblock_exp.py  (real TPU; ~8 min)
     JAX_PLATFORMS=cpu python out/weno_yblock_exp.py --shape 24 40 16
         --global-z 96 --steps 1 --reps 1  (interpret-mode rehearsal)
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from multigpu_advectiondiffusion_tpu.ops import flux as flux_lib
from multigpu_advectiondiffusion_tpu.ops.pallas.fused_burgers import (
    FusedBurgersStepper,
)


def time_block(shape, global_z, by, steps, reps, u0):
    lz, ly, lx = shape
    h = 0.002
    st = FusedBurgersStepper(
        shape, jnp.float32, (h, h, h), flux_lib.get("burgers"), "js", 0.0,
        dt=0.3 * h, block=(8, by), global_shape=(global_z, ly, lx),
        overlap_split=True,
    )
    assert st.overlap_split, "the split schedule did not engage"
    r = st.halo

    def exch(P, repeats=1):
        del repeats
        return P[r:2 * r], P[lz:lz + r]

    t0 = jnp.zeros((), jnp.float32)
    full = jax.jit(lambda u, t: st.run(u, t, steps, exch=exch)[0])
    zero = jax.jit(lambda u, t: st.run(u, t, 0, exch=exch)[0])
    c0 = time.perf_counter()
    full(u0, t0).block_until_ready()
    zero(u0, t0).block_until_ready()
    compile_s = time.perf_counter() - c0
    diffs = []
    for _ in range(reps):
        a = time.perf_counter()
        zero(u0, t0).block_until_ready()
        b = time.perf_counter()
        full(u0, t0).block_until_ready()
        c = time.perf_counter()
        diffs.append(((c - b) - (b - a)) / steps)
    return {"by": by, "rows": st.padded_shape[1] - 16,
            "block": st.plan()["block"],
            "hbm_bytes_per_step": st.hbm_bytes_per_step,
            "step_ms": 1e3 * statistics.median(diffs),
            "step_ms_all": [round(1e3 * d, 4) for d in diffs],
            "compile_s": round(compile_s, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=3, default=(200, 1000, 1000))
    ap.add_argument("--global-z", type=int, default=800)
    ap.add_argument("--blocks", default="8,40,64,56,48,72,32,24,16,8")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    shape = tuple(args.shape)
    u0 = jax.random.uniform(jax.random.PRNGKey(26), shape, jnp.float32,
                            -0.5, 0.5)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "shape": shape}), flush=True)
    for by in (int(b) for b in args.blocks.split(",")):
        try:
            out = time_block(shape, args.global_z, by, args.steps,
                             args.reps, u0)
        except Exception as e:  # noqa: BLE001 — report and go on
            out = {"by": by, "error": f"{type(e).__name__}: {e}"[:400]}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
