"""Verification of the halo-exchange / interior-compute overlap.

The reference hand-builds overlap with five CUDA streams — boundary RHS
on send streams while the interior RHS runs on the compute stream
(``MultiGPU/Diffusion3d_Baseline/main.c:203-260``). The rebuild's
``overlap="split"`` schedule claims the same property via dataflow: the
interior stencil must not depend on the in-flight ``ppermute`` ghosts,
so XLA's async collective scheduler can run both concurrently. This
file checks the precondition on any backend: poison the exchanged ghost
slabs with NaN — the interior output cells must stay finite, proving
the interior computation consumes no ghost data (a dependency would
serialize it). The overlap as the TPU compiler actually schedules it is
read out of compiled v5e programs in ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from multigpu_advectiondiffusion_tpu.ops.stencils import split_axis_apply


def test_split_schedule_interior_is_ghost_independent():
    """NaN-poisoned ghosts must not reach interior output cells: the
    interior compute consumes only local data, so nothing forces it to
    wait for the exchange."""
    r = 2
    u = jnp.asarray(np.random.default_rng(0).normal(size=(16, 8, 8)),
                    jnp.float32)
    nan = jnp.full((r,) + u.shape[1:], jnp.nan, u.dtype)

    def f(lo, hi):
        return split_axis_apply(
            lambda up: up[2 * r :] - up[: -2 * r], u, 0, r, lo, hi
        )

    out = jax.jit(f)(nan, nan)
    core = np.asarray(out)[r:-r]
    edges = np.asarray(out)[:r], np.asarray(out)[-r:]
    assert np.isfinite(core).all(), "interior depends on ghost data"
    assert all(np.isnan(e).all() for e in edges), (
        "boundary bands should be exactly the ghost-dependent region"
    )
