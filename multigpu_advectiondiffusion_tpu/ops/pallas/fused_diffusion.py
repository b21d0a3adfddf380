"""Fully-fused SSP-RK3 diffusion stepping on a persistent padded state.

The reference's hot loop runs, per RK stage, a Laplacian kernel and an
RK-update kernel over HBM-resident arrays plus ghost-cell maintenance
(``MultiGPU/Diffusion3d_Baseline/main.c:189-303``). The generic JAX path
here mirrors that structure (pad → stencil → axpy → clamp as separate
XLA fusions), which costs several full-array HBM passes per stage.

This module collapses each RK stage to ONE Pallas kernel at minimum HBM
traffic (read stage input + read step input + write output, ~12 B/cell):

* The state lives in a *padded, tile-aligned* layout ``(nz+4, Y8, X128)``
  for the whole run; ghost cells are materialized once and then never
  rewritten — with ``reference_parity`` Dirichlet walls the RHS is zeroed
  on the 2-cell boundary band (``Laplace3d.m:21``), so boundary cells and
  ghosts are constant through every stage.
* Each stage kernel marches z-blocks through a VMEM ring of z-planes
  that fetches every plane of its input from HBM once per call,
  evaluates the 13-point Laplacian plane by plane with ring planes (z)
  and circular shifts (y/x — wraparound touches only masked ghost
  columns), applies the RK stage combination ``a*u + b*(v + dt*L(v))``,
  re-imposes the Dirichlet faces (``heat3d.m:65-67``), and writes only
  the core z-rows back — the output buffer is aliased to a dead input
  buffer whose ghost cells are already valid.
* Buffer choreography per step (three live padded buffers, zero allocs):
  ``T1 = stage1(S)``, ``T2 = stage2(T1, S)``, ``S' = stage3(T2, S) → S``.
  Stage 3 writes in place over ``S`` while reading it: each grid block
  reads its ``u`` rows strictly before writing them, and other blocks'
  reads are row-disjoint from its writes.

Sharded mode (``global_shape`` != ``interior_shape``): the same stage
kernels run shard-local inside ``shard_map`` — wall masks take this
shard's global offsets from an SMEM operand, and a per-stage ghost
refresh (``parallel.halo.make_ghost_refresh``) rewrites the sharded-axis
ghost slabs by ``ppermute`` between stages.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multigpu_advectiondiffusion_tpu.ops.pallas.laplacian import (
    LANE,
    O4_COEFFS,
    R,
    SUBLANE,
    VMEM_LIMIT,
    _aligned_row_bytes_3d,
    compiler_params,
    interpret_mode,
    pick_block,
    round_up,
)
from multigpu_advectiondiffusion_tpu.ops.pallas.stepper_base import (
    FusedStepperBase,
)

# SSP-RK3 stage combinations u_next = a*u + b*(v + dt*L(v))
# (Compute_RK, MultiGPU/Diffusion3d_Baseline/Kernels.cu:266-300)
_STAGES = ((0.0, 1.0), (0.75, 0.25), (1.0 / 3.0, 2.0 / 3.0))


def _shift(x, off: int, axis: int):
    """Full-width circular shift: result[i] = x[i + off] along ``axis``.

    Wraparound rows/columns land only in ghost/slack positions, whose
    outputs are masked back to the stage input. A zero shift returns
    ``x`` unchanged — Mosaic's roll lowering builds a zero-width slice
    for amount 0, which some toolchain versions reject.
    """
    n = x.shape[axis]
    if off % n == 0:
        return x
    if interpret_mode():
        return jnp.roll(x, -off, axis)
    return pltpu.roll(x, (-off) % n, axis)


def _ring_geometry(bz: int, n_grid: int):
    """``(depth, shift)`` of a stage call's VMEM ring of ``v`` z-planes.

    Call-relative plane ``p`` lives at ring index ``(p + shift) % depth``.
    Block ``k`` reads planes ``[k*bz, k*bz + bz + 2R)`` while block
    ``k+1``'s ``bz`` new planes land, so the ring holds ``2*bz + 2R``
    planes at once. ``depth`` rounds that up to a block multiple and
    ``shift`` aligns each block's new planes on a block boundary, so
    every fetch is one contiguous DMA (block 0's whole slab included:
    it sits at ``[shift, shift + bz + 2R)``). A one-block call keeps
    just its slab.
    """
    if n_grid == 1:
        return bz + 2 * R, 0
    lead = -(-2 * R // bz) * bz  # 2R rounded up to a block multiple
    return 2 * bz + lead, lead - 2 * R


def _stage_kernel(
    dt_ref,
    v_hbm,
    u_hbm,
    g_hbm,
    out_hbm,
    ring,
    us,
    res,
    sem_v,
    sem_u,
    sem_w,
    sem_gv,
    *,
    bz: int,
    n_blocks: int,
    global_shape: Sequence[int],
    offs_ref=None,
    scales: Sequence[float],
    a: float,
    b: float,
    band: int,
    bc_value: float,
    kz_base: int = 0,
    n_blocks_grid: int | None = None,
    ghost_src: str | None = None,
    compute_dtype=None,
):
    """One z-block of one RK stage; ``v`` streams through a VMEM ring.

    The TPU grid is a sequential loop. Block 0 fetches its whole
    ``(bz + 2R)``-plane slab of the stage input ``v``; every later block
    fetches only the ``bz`` planes past the 2R it shares with the block
    before, which are still in the ring (:func:`_ring_geometry`). So each
    plane of ``v`` leaves HBM once per call, as the reference's
    register-pipelined ``LaplaceO4_async`` marches its window along z
    (``MultiGPU/Diffusion3d_Baseline/Kernels.cu:207-261``). Block ``k``
    prefetches block ``k+1``'s new planes (and ``u`` rows) while it
    computes, and defers the wait on its output DMA until the same slot
    is reused at ``k+2``. Output row ranges of distinct blocks are
    disjoint, so the in-flight writes never alias the prefetched reads
    (the in-place final stage reads its ``u`` rows strictly before the
    overwriting DMA of the same block).

    ``dt`` is a runtime SMEM scalar, so the same compiled stages serve
    fixed-count runs AND the trimmed last step of ``run_to``. Roles for
    the overlapped z-slab schedule (as in :mod:`fused_burgers`):
    ``kz_base`` offsets this call's blocks, ``n_blocks_grid`` is this
    call's grid extent, and ``ghost_src`` = ``"lo"``/``"hi"`` DMAs the R
    z-ghost rows from the separately exchanged slab operand ``g_hbm``
    instead of the padded buffer (whose z-ghost rows are stale in split
    mode — frozen Dirichlet values are only correct at global edges).
    """
    nz, ny, nx = global_shape
    if n_blocks_grid is None:
        n_blocks_grid = n_blocks
    depth, shift = _ring_geometry(bz, n_blocks_grid)
    k = pl.program_id(0)  # this call's linear block index
    kz = k + kz_base  # absolute z-block index
    z_start = kz_base * bz  # padded-buffer plane of call-relative plane 0
    slot = lax.rem(k, jnp.asarray(2, k.dtype))
    nslot = lax.rem(k + 1, jnp.asarray(2, k.dtype))

    def wrap(q):  # ring index of the plane at shifted position q
        return lax.rem(q, jnp.asarray(depth, q.dtype))

    def copy_slab():
        # block 0: its whole slab, ghost rows from g_hbm in the edge roles
        if ghost_src is None:
            return [
                pltpu.make_async_copy(
                    v_hbm.at[pl.ds(z_start, bz + 2 * R)],
                    ring.at[pl.ds(shift, bz + 2 * R)],
                    sem_v.at[0],
                )
            ]
        if ghost_src == "lo":
            return [
                pltpu.make_async_copy(
                    g_hbm, ring.at[pl.ds(shift, R)], sem_gv.at[0]
                ),
                pltpu.make_async_copy(
                    v_hbm.at[pl.ds(z_start + R, bz + R)],
                    ring.at[pl.ds(shift + R, bz + R)],
                    sem_v.at[0],
                ),
            ]
        return [
            pltpu.make_async_copy(
                v_hbm.at[pl.ds(z_start, bz + R)],
                ring.at[pl.ds(shift, bz + R)],
                sem_v.at[0],
            ),
            pltpu.make_async_copy(
                g_hbm, ring.at[pl.ds(shift + bz + R, R)], sem_gv.at[0]
            ),
        ]

    def copy_new(j, s):
        # block j >= 1: the bz planes past the 2R it shares with block j-1
        p = j * bz + 2 * R
        return pltpu.make_async_copy(
            v_hbm.at[pl.ds(z_start + p, bz)],
            ring.at[pl.ds(wrap(p + shift), bz)],
            sem_v.at[s],
        )

    def copy_u(j, s):
        # u rows come from u_hbm — which for the in-place final stage is
        # the output buffer itself (read strictly before the overwrite;
        # other blocks' reads are row-disjoint from any in-flight write).
        src = u_hbm if u_hbm is not None else out_hbm
        return pltpu.make_async_copy(
            src.at[pl.ds(R + (j + kz_base) * bz, bz)], us.at[s], sem_u.at[s]
        )

    def copy_w(j, s):
        return pltpu.make_async_copy(
            res.at[s],
            out_hbm.at[pl.ds(R + (j + kz_base) * bz, bz)],
            sem_w.at[s],
        )

    @pl.when(k == 0)
    def _():
        for cp in copy_slab():
            cp.start()
        if us is not None:
            copy_u(0, 0).start()

    if n_blocks_grid > 1:

        @pl.when(k + 1 < n_blocks_grid)
        def _():
            copy_new(k + 1, nslot).start()
            if us is not None:
                copy_u(k + 1, nslot).start()

    if us is not None:
        copy_u(k, slot).wait()

    @pl.when(k == 0)
    def _():
        for cp in copy_slab():
            cp.wait()

    if n_blocks_grid > 1:

        @pl.when(k > 0)
        def _():
            copy_new(k, slot).wait()

    # the res slot is recycled every other block: drain its previous
    # write before this block fills it
    @pl.when(k >= 2)
    def _():
        copy_w(k - 2, slot).wait()

    # bf16-storage rung: the state lives (and moves through HBM) at half
    # the bytes; arithmetic runs in ``compute_dtype`` (f32) so the RK
    # accumulation doesn't lose the stencil's cancellation digits
    stored_dtype = ring.dtype
    dtype = jnp.dtype(compute_dtype or stored_dtype)
    dt = dt_ref[0].astype(dtype)
    first = wrap(k * bz + shift)  # ring index of the slab's plane 0

    # When sharded, ``offs_ref`` holds this shard's global offsets so the
    # band/face tests keep using *global* coordinates (reference-parity
    # walls are global, Laplace3d.m:21 / heat3d.m:65-67).
    oz, oy, ox = (
        (offs_ref[0], offs_ref[1], offs_ref[2])
        if offs_ref is not None
        else (0, 0, 0)
    )

    def between(g, n):
        return (g >= band) & (g < n - band)

    def plane(i, carry):
        # output plane i of the block: slab planes i .. i + 2R
        def slab_plane(j):
            return ring[pl.ds(wrap(first + i + j), 1)].astype(dtype)

        rows = [slab_plane(j) for j in range(2 * R + 1)]
        vc = rows[R]  # stage input, core z-row, full y/x width

        # 13-point O4 Laplacian (z-term via ring planes, y/x via masked
        # circular shifts). Diffusivity is folded into each term's
        # coefficient, so the rounding differs from the XLA path's
        # per-axis-then-scale association by ~1 ulp per term.
        acc = None
        for axis in range(3):
            for j, c in enumerate(O4_COEFFS):
                coef = jnp.asarray(c * scales[axis], dtype)
                tap = rows[j] if axis == 0 else _shift(vc, j - R, axis)
                term = tap * coef
                acc = term if acc is None else acc + term

        rk = (
            b * (vc + dt * acc)
            if a == 0.0
            else a * us[slot, pl.ds(i, 1)].astype(dtype) + b * (vc + dt * acc)
        )

        # Global interior-cell indices of this plane (ghost offset
        # already removed for z: the written rows are exactly the core
        # rows).
        shp = vc.shape
        gz = lax.broadcasted_iota(jnp.int32, shp, 0) + kz * bz + i + oz
        gy = lax.broadcasted_iota(jnp.int32, shp, 1) - R + oy
        gx = lax.broadcasted_iota(jnp.int32, shp, 2) - R + ox
        interior = between(gz, nz) & between(gy, ny) & between(gx, nx)
        face = (
            (gz == 0) | (gz == nz - 1)
            | (gy == 0) | (gy == ny - 1)
            | (gx == 0) | (gx == nx - 1)
        )
        frozen = jnp.where(face, jnp.asarray(bc_value, dtype), vc)
        res[slot, pl.ds(i, 1)] = jnp.where(interior, rk, frozen).astype(
            stored_dtype
        )
        return carry

    lax.fori_loop(0, bz, plane, 0)

    # issue this block's write and leave it in flight (drained at k+2,
    # or below on the last blocks)
    copy_w(k, slot).start()

    @pl.when(k == n_blocks_grid - 1)
    def _():
        copy_w(k, slot).wait()
        if n_blocks_grid >= 2:
            copy_w(k - 1, nslot).wait()


def _make_stage(padded_shape, interior_shape, dtype, *, bz, scales, a, b,
                band, bc_value, u_source, global_shape=None, sharded=False,
                role=None, compute_dtype=None):
    """Build one fused RK-stage call; output aliased onto the last operand.

    Returns ``(call, planes)``: ``planes`` is the padded z-planes the
    call DMAs from and to HBM — each ``v`` plane of its blocks' slabs
    once (ring), plus ``u`` rows read and output rows written.

    ``u_source``: where the step-input ``u`` (the ``a*u`` term) is read
    from — ``"none"`` (stage 1, a == 0), ``"operand"`` (separate input
    buffer), or ``"target"`` (the aliased output buffer itself, for the
    in-place final stage — avoids passing one buffer as two operands,
    which would force XLA to insert a defensive copy).

    ``role``: ``"full"`` (default) or the overlapped z-slab schedule's
    ``"interior"``/``"bottom"``/``"top"`` (see :func:`_stage_kernel`).

    ``sharded``: prepend an int32 ``(3,)`` SMEM operand carrying this
    shard's global offsets (the stage then runs shard-local inside
    ``shard_map``; ``global_shape`` is the global interior for the
    band/face tests).
    """
    trailing = padded_shape[1:]
    use_u = u_source != "none"
    # blocks cover the padded buffer's (possibly block-rounded) z extent;
    # dead tail rows beyond the real interior stay frozen via the masks
    n_blocks = (padded_shape[0] - 2 * R) // bz

    role = role or "full"
    if role == "full":
        kz_base, n_grid, ghost_src = 0, n_blocks, None
    elif role == "interior":
        kz_base, n_grid, ghost_src = 1, n_blocks - 2, None
    elif role == "bottom":
        kz_base, n_grid, ghost_src = 0, 1, "lo"
    elif role == "top":
        kz_base, n_grid, ghost_src = n_blocks - 1, 1, "hi"
    else:
        raise ValueError(f"unknown stage role {role!r}")
    use_g = ghost_src is not None

    kern = functools.partial(
        _stage_kernel,
        bz=bz,
        n_blocks=n_blocks,
        global_shape=tuple(global_shape or interior_shape),
        scales=tuple(scales),
        a=a,
        b=b,
        band=band,
        bc_value=bc_value,
        kz_base=kz_base,
        n_blocks_grid=n_grid,
        ghost_src=ghost_src,
        compute_dtype=compute_dtype,
    )

    def kernel(*refs):
        dt_ref, *refs = refs
        offs_ref, g_hbm, sem_gv = None, None, None
        if sharded:
            offs_ref, *refs = refs
        if u_source == "operand":
            v_hbm, u_hbm, *refs = refs
        else:
            v_hbm, *refs = refs
            u_hbm = None  # "target": read from out_hbm
        if use_g:
            g_hbm, *refs = refs
        _tgt, out_hbm, ring, *refs = refs
        if use_u:
            us, *refs = refs
        else:
            us = None
        res, sem_v, *refs = refs
        if use_u:
            sem_u, *refs = refs
        else:
            sem_u = None
        sem_w, *refs = refs
        if use_g:
            (sem_gv,) = refs
        kern(dt_ref, v_hbm, u_hbm, g_hbm, out_hbm, ring, us, res,
             sem_v, sem_u, sem_w, sem_gv, offs_ref=offs_ref)

    n_in = (
        1  # dt
        + (1 if sharded else 0)
        + (2 if u_source == "operand" else 1)
        + (1 if use_g else 0)
        + 1  # aliased target
    )
    depth, _ = _ring_geometry(bz, n_grid)
    scratch = [pltpu.VMEM((depth,) + trailing, dtype)]
    if use_u:
        scratch.append(pltpu.VMEM((2, bz) + trailing, dtype))
    scratch.append(pltpu.VMEM((2, bz) + trailing, dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))
    if use_u:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))
    if use_g:
        scratch.append(pltpu.SemaphoreType.DMA((1,)))

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]  # dt
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * (n_in - 1)
    if sharded:
        in_specs[1] = pl.BlockSpec(memory_space=pltpu.SMEM)

    rows = n_grid * bz
    planes = rows + 2 * R + rows * (2 if use_u else 1)
    return pl.pallas_call(
        kernel,
        grid=(n_grid,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(tuple(padded_shape), dtype),
        scratch_shapes=scratch,
        input_output_aliases={n_in - 1: 0},  # last operand -> out
        compiler_params=None if interpret_mode() else compiler_params(),
        interpret=interpret_mode(),
    ), planes


class FusedDiffusionStepper(FusedStepperBase):
    """Jit-cached fused runner for one (grid, dtype, dt) configuration.

    ``global_shape`` (when it differs from ``interior_shape``) switches
    the stages to shard-local mode: ``interior_shape`` is this shard's
    block, mask tests use global coordinates from a runtime offsets
    operand, and :meth:`run` accepts a per-stage ghost-``refresh``
    callback (``parallel.halo.make_ghost_refresh``). This is the tuned
    kernel running under the mesh — the reference's MultiGPU tier runs
    the same ``LaplaceO4_async`` kernel its single-GPU ladder tuned
    (``MultiGPU/Diffusion3d_Baseline/main.c:189-303``,
    ``Kernels.cu:207-261``).
    """

    halo = R
    stencil_radius = R  # O4 Laplacian reach; ghosts refresh per stage
    needs_offsets = True  # global wall masks take an offsets operand

    def __init__(self, interior_shape, dtype, spacing, diffusivity, dt,
                 band, bc_value, block_z=None, global_shape=None,
                 overlap_split: bool = False, storage_dtype=None):
        nz, ny, nx = interior_shape
        self.interior_shape = tuple(interior_shape)
        self.global_shape = tuple(global_shape or interior_shape)
        self.sharded = self.global_shape != self.interior_shape
        self.dtype = jnp.dtype(dtype)
        # f64-storage/f32-compute rung: the *state* stays f64 between
        # runs, the kernels (and every HBM-resident padded buffer) run
        # ``dtype`` — embed downcasts, extract restores (Mosaic has no
        # f64 vector path; accuracy is f32, priced in PARITY.md)
        self._storage = jnp.dtype(storage_dtype or dtype)
        # bf16-storage rung: state/DMA at 2 B/cell (the stage kernel is
        # HBM-bound, PARITY.md), arithmetic in f32
        compute_dtype = (
            jnp.float32 if self.dtype == jnp.bfloat16 else None
        )
        self.bc_value = float(bc_value)
        row_bytes = _aligned_row_bytes_3d((nz, ny, nx), self.dtype.itemsize)
        # VMEM model calibrated on v5e at the bench grid (row =
        # 208*512*4 B): ~9 live row-sized buffers per block row plus ~56
        # rows of fixed overhead; bz=20 measured fastest, bz=32 exceeds
        # VMEM. Capped at the largest measured-safe block.
        budget_rows = max(1, min(20, int((VMEM_LIMIT // row_bytes - 56) // 9)))
        if block_z is None:
            if self.sharded:
                # sharded shards exchange their core rows — dead padding
                # rows inside the domain would corrupt neighbor ghosts,
                # so the block must divide the local extent exactly
                block_z = pick_block(nz, budget_rows)
            else:
                # unsharded: pad z up to a block multiple instead of
                # shrinking the block to a divisor (nz=206 would force
                # bz=2). Dead tail rows hold bc_value from embed() and
                # stay frozen (they are neither interior nor face in the
                # global-index masks), so interior cell nz-1 reads them
                # as the Dirichlet ghosts it needs. Score balances z-halo
                # amortization bz/(bz+2R) against wasted dead rows. The
                # ring reads each z-halo plane once per call, so the
                # first factor now prices only the grid's per-block
                # overhead; the picks it makes are kept as measured.
                def score(b):
                    blocks = -(-nz // b)
                    return (b / (b + 2 * R)) * (nz / (blocks * b))

                block_z = max(range(1, budget_rows + 1), key=score)
        elif self.sharded and nz % block_z != 0:
            raise ValueError(
                f"block_z={block_z} must divide local nz={nz} when "
                "sharded; a non-divisor would leave dead rows inside "
                "the exchanged core"
            )
        bz = block_z
        self.block = (bz,)
        # nz rounded up to a block multiple (== nz when sharded: both
        # branches above guarantee an exact divisor there)
        nz_eff = -(-nz // bz) * bz
        # narrow dtypes pack more rows per native (sublane, 128) tile —
        # bf16's tile is (16, 128) — so the y padding rounds accordingly
        sub = SUBLANE * max(1, 4 // self.dtype.itemsize)
        self.padded_shape = (
            nz_eff + 2 * R,
            round_up(ny + 2 * R, sub),
            round_up(nx + 2 * R, LANE),
        )
        scales = [
            float(diffusivity[i]) / (12.0 * spacing[i] * spacing[i])
            for i in range(3)
        ]
        # split-overlap needs a strict interior band (>= 3 blocks) and
        # bz >= R so interior boxes never reach the stale ghost rows
        self.overlap_split = bool(
            overlap_split and self.sharded and nz // bz >= 3 and bz >= R
        )
        sources = ("none", "operand", "target")
        plan = []  # HBM planes of every stage call one step makes

        def mk(role):
            calls = []
            for (a, b), src in zip(_STAGES, sources):
                call, planes = _make_stage(
                    self.padded_shape, self.interior_shape, self.dtype,
                    bz=bz, scales=scales, a=a, b=b,
                    band=band, bc_value=float(bc_value), u_source=src,
                    global_shape=self.global_shape, sharded=self.sharded,
                    role=role, compute_dtype=compute_dtype,
                )
                calls.append(call)
                plan.append(planes)
            return tuple(calls)

        self.dt = float(dt)

        if self.overlap_split:
            (s1i, s2i, s3i) = mk("interior")
            (s1b, s2b, s3b) = mk("bottom")
            (s1t, s2t, s3t) = mk("top")

            def step(S, T1, T2, dt_arr, offsets=None, refresh=None,
                     exch=None):
                # Interior blocks run concurrently with the z-halo
                # ppermute; only the two edge calls consume the
                # exchanged slabs — the reference's five-stream
                # boundary/interior split (main.c:203-260) as dataflow.
                # On pencil meshes ``refresh`` serializes the non-z
                # sharded axes' ghosts on each stage's composed output
                # (the next stage reads them from the buffer).
                fix = refresh if refresh is not None else (lambda P: P)
                pre = (dt_arr, offsets)
                lo, hi = exch(S)
                T1 = fix(s1t(*pre, S, hi, s1b(*pre, S, lo, s1i(*pre, S, T1))))
                lo, hi = exch(T1)
                T2 = fix(s2t(*pre, T1, S, hi,
                             s2b(*pre, T1, S, lo, s2i(*pre, T1, S, T2))))
                lo, hi = exch(T2)
                S = fix(s3t(*pre, T2, hi, s3b(*pre, T2, lo, s3i(*pre, T2, S))))
                return S, T1, T2

        else:
            s1, s2, s3 = mk("full")

            def step(S, T1, T2, dt_arr, offsets=None, refresh=None,
                     exch=None):
                del exch
                pre = (
                    (dt_arr,) if offsets is None else (dt_arr, offsets)
                )
                fix = refresh if refresh is not None else (lambda P: P)
                T1 = fix(s1(*pre, S, T1))     # u1 = u + dt L(u)
                T2 = fix(s2(*pre, T1, S, T2))  # 3/4 u + 1/4 (u1 + dt L(u1))
                S = fix(s3(*pre, T2, S))      # 1/3 u + 2/3 (u2 + dt L(u2))
                return S, T1, T2              # in place

        self._step = step
        #: bytes the stage calls of one step DMA from and to HBM (this
        #: shard's), summed from the block plan
        self.hbm_bytes_per_step = sum(plan) * (
            self.padded_shape[1] * self.padded_shape[2] * self.dtype.itemsize
        )

    def embed(self, u):
        full = jnp.full(self.padded_shape, self.bc_value, self.dtype)
        return lax.dynamic_update_slice(full, u.astype(self.dtype), (R, R, R))

    def extract(self, S):
        nz, ny, nx = self.interior_shape
        out = lax.slice(S, (R, R, R), (R + nz, R + ny, R + nx))
        return out.astype(self._storage)

    def _dt_value(self, S):
        return jnp.asarray(self.dt, jnp.float32)

    # run()/run_to() come from FusedStepperBase (the MATLAB heat
    # drivers' native mode is run_to's `while t < t_end`,
    # heat3d.m:48-77); the kernels' global wall masks make ``offsets``
    # mandatory when sharded (needs_offsets).
