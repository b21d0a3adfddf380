"""How far two f32 programs that do the same arithmetic may differ.

XLA's CPU backend contracts ``a*b + c`` into one fused multiply-add where
the host ISA has FMA, and whether a given multiply-add contracts depends
on how the program around it was fused. Two programs that run the same
stencil in different shapes (sharded and unsharded, batched and looped,
one kernel rung and another) can then round a cell differently. With the
ISA capped below FMA (``XLA_FLAGS=--xla_cpu_max_isa=AVX``) the same
comparisons agree bit for bit (PR 21). The tests below bound the
difference by a few f32 ulps of max|u| instead: a halo, offset or member
mix-up moves cells by orders of magnitude more.
"""

from __future__ import annotations

import numpy as np

F32_ULPS = 4


def f32_bound(ref, ulps: int = F32_ULPS) -> float:
    """``ulps`` f32 ulps of max|ref|."""
    scale = float(np.max(np.abs(np.asarray(ref, np.float64))))
    return ulps * float(np.finfo(np.float32).eps) * scale


def assert_f32_close(got, ref, ulps: int = F32_ULPS, what: str = ""):
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    assert got.shape == ref.shape, (what, got.shape, ref.shape)
    diff = float(np.max(np.abs(got - ref)))
    bound = f32_bound(ref, ulps)
    assert diff <= bound, (
        f"{what}: max diff {diff:.3e} exceeds {ulps} f32 ulps of max|u| "
        f"({bound:.3e})"
    )
