"""Slab-pipelined whole-run SSP-RK3 stepping for 3-D (diffusion + Burgers).

The 2-D solvers reach their 400-813x rates through the whole-run VMEM
stepper (:mod:`whole_run`): state on-chip for the entire run, zero HBM
traffic per step. A 3-D reference grid does not fit VMEM, so the 3-D
fused path has been the per-stage stepper — three Pallas calls per step,
each a full HBM round trip of the state (~9 array passes per step
counting stage inputs, ``u`` reads and writes).

This module is the 3-D rung between the two: ONE Pallas program whose
grid is ``(timestep, z-slab)``. The TPU grid is a sequential loop, so the
program streams z-slabs HBM->VMEM with double-buffered async copies,
fuses all three RK stages of the step in VMEM while the next slab's DMA
is in flight, and writes each slab's core back once — one HBM round trip
per step (``1 + (bz + 2G)/bz`` array passes) instead of three.

Slab independence comes from **redundant ghost-region recompute** (the
reference's revolving-buffer idea, and the standard trapezoid rule of
temporal blocking): each slab loads ``G = 3h`` extra rows per side
(``h`` = per-stage stencil radius: 2 for the O4 Laplacian, 3/4 for
WENO5/7), recomputes stage 1 on a ``bz + 4h``-row window and stage 2 on
``bz + 2h``, so the stage-3 core needs nothing from neighboring slabs
within the step. No slab ever reads another slab's output of the same
step — which is what lets the whole step run inside one sequential grid
with plain double-buffered DMA and no inter-slab synchronization.

Step-level state ping-pong rides a single stacked ``(2,) + padded``
buffer: step ``k`` reads ``buf[k % 2]`` and writes ``buf[1 - k % 2]``
(slab ``j+1`` of step ``k`` still reads rows that slab ``j`` would
overwrite in place). The buffer parity of the final state is
``num_iters % 2``, known statically. Across the step boundary the
prefetch of the next step's first slab reads rows this step already
wrote; it is issued only when the write-drain schedule proves those
writes have landed (``cross_ok``), else the first slab of each step
loads synchronously.

Redundant recompute is paid in VPU work: ``2h/bz`` extra rows per
stage. The dispatch (``models/*._fused_stepper``) therefore engages
this stepper only where the traffic saving can win — large-``bz`` slabs
(HBM-bound diffusion) or grids whose z extent fits one or two slabs —
and falls back to the per-stage ``fused-stage`` path otherwise;
``impl='pallas_slab'`` pins it for measurement.

Sharded mode (z-slab decomposition only, pinned): the whole-run grid
cannot cross ghost refreshes, so each step runs as one slab-pipelined
Pallas call per step under ``shard_map``, with a single ``G``-deep
z-halo exchange per STEP (same bytes as the per-stage path's three
``h``-deep exchanges, a third of the messages, and one kernel launch
per step instead of three). With ``overlap='split'`` the step runs the
familiar three-call schedule (interior slabs concurrent with the
in-flight ``ppermute``; only the two edge slabs consume the exchanged
``G``-deep slabs), mirroring :mod:`fused_diffusion`'s per-stage split.

**Communication-avoiding k-step schedule** (``steps_per_exchange=k``):
the within-step G=3h trick generalized ACROSS steps. The padded buffer
carries ``k*G`` ghost rows per side; ONE ``k*G``-deep exchange per
k-step block, and in-block step ``j`` evolves the core extended by
``(k-1-j)*G`` rows per side — the standard trapezoid of temporal
blocking, here spanning both the RK stages *and* k whole steps. Step 0
consumes the exchanged ghosts; every later step reads exactly the
previous step's output window, so the block needs no communication at
all. Bytes per step are unchanged (``2*k*G`` rows every k steps);
messages and collective latencies drop by 1/k, paid for with the
redundant window growth ``~(k-1)*G/lz`` in VPU work and slab traffic.
Split-overlap composes: the block-start exchange overlaps the interior
call (output window exactly the locally valid core), with single-slab
edge calls consuming the ``k*G``-deep operands. Exchange cadence is
selected per measured tuning decision (``impl='auto'``,
:mod:`multigpu_advectiondiffusion_tpu.tuning`) or pinned via the
``steps_per_exchange`` config knob.

**In-kernel remote-DMA exchange** (``exchange='dma'``, ROADMAP item 2):
the sharded composition above still breaks out of the Pallas program
every step (or every k-step block) to run the ``ppermute`` between
compiled calls. The dma mode instead runs the ENTIRE sharded run as one
whole-run Pallas program per shard — grid ``(timestep, z-slab)`` like
the unsharded rung — and moves the ``k*G`` ghost rows over ICI from
*inside* the kernel via ``pltpu.make_async_remote_copy``: at each
block's last step the freshly written core edge windows are pushed to
the ±z neighbors' dedicated 2-slot landing buffer (cyclic ring pushes,
every shard in lockstep — the wall shards' wrapped slabs land in rows
the receiver never reads, the ``ppermute`` discipline in-kernel), and
the next block's first iteration waits the paired send/recv semaphores
and splices the landed slabs into the read parity's ghost rows with a
local DMA (wall sides keep their frozen embed BC ghosts). Pushes land
in the landing buffer only — never over state rows — so a fast
neighbor can never overwrite rows the local step is still computing;
the static halo verifier (``analysis/halo_verify``) proves the
declared send/recv windows (``stencil_spec()['remote_dma']``) against
exactly that invariant before any hardware run. The in-block step
windows shrink by the usual ``(k-1-j)*G`` trapezoid, realized on a
uniform z-block (``bz | lz``, and ``bz | 2G`` when k > 1) with the
out-of-window grid iterations predicated off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multigpu_advectiondiffusion_tpu.ops.flux import Flux
from multigpu_advectiondiffusion_tpu.ops.pallas.fused_burgers import (
    _div_roll,
    _div_z,
    _split,
)
from multigpu_advectiondiffusion_tpu.ops.pallas.fused_diffusion import (
    _STAGES,
    _shift,
)
from multigpu_advectiondiffusion_tpu.ops.pallas.fused_diffusion_step import (
    _stage_rows,
)
from multigpu_advectiondiffusion_tpu.ops.pallas.laplacian import (
    LANE,
    O4_COEFFS,
    R,
    SUBLANE,
    VMEM_LIMIT,
    compiler_params,
    interpret_mode,
    round_up,
)
from multigpu_advectiondiffusion_tpu.ops.pallas.whole_run import accumulate_t
from multigpu_advectiondiffusion_tpu.ops.weno import HALO

# Conservative budget for the slab working set (the Mosaic scoped
# ceiling is VMEM_LIMIT = 100 MiB; leave headroom for Mosaic's own
# scheduling slack, as fused_burgers does).
_VMEM_BUDGET = 72 * 1024 * 1024


def _dma_compiler_params():
    """Mosaic params for the in-kernel remote-DMA program: the scoped
    VMEM ceiling of every slab kernel, plus the collective id of its
    neighbor barrier and the side-effect pin the cross-chip DMAs
    require. Only built on the TPU lowering path — interpret mode
    passes None like every other slab call."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT, collective_id=0, has_side_effects=True
    )


def _check_steps_per_exchange(k, sharded: bool, nz: int, G: int) -> int:
    """Validate the communication-avoiding chunk length for a stepper
    instance: sharded-only (an unsharded run exchanges nothing, so k is
    meaningless there) and the shard must be thick enough to *serve* the
    ``k*G``-deep exchange from its core."""
    k = int(k)
    if k < 1:
        raise ValueError(f"steps_per_exchange must be >= 1, got {k}")
    if k == 1:
        return 1
    if not sharded:
        raise ValueError(
            "the k-step communication-avoiding schedule applies to "
            "sharded (z-slab) runs only"
        )
    if nz < k * G:
        raise ValueError(
            f"local z extent {nz} cannot serve the k-step schedule's "
            f"{k * G}-deep exchange (steps_per_exchange={k}, G={G})"
        )
    return k


def _cross_ok(bz: int, G: int, n_slabs: int) -> bool:
    """Whether the next step's first-slab prefetch may be issued at the
    current step's last slab. The prefetch reads dst rows ``[0, bz+2G)``:
    ghost rows (never written) plus the cores of slabs ``0..M``. At
    prefetch time the drain schedule has waited writes through ``i-3``
    (slab ``n_slabs-4``), so all read rows have landed iff
    ``M <= n_slabs - 4`` — which also keeps the two still-in-flight
    writes (slabs ``n_slabs-3``/``-2``) disjoint from the read."""
    M = 1 + (G - 1) // bz
    return M <= n_slabs - 4


def _whole_run_kernel(s_in, ss, vs, res, sem_v, sem_w, *, step_fn, bz: int,
                      G: int, n_slabs: int, n_iters: int, cross: bool,
                      batched: bool = False):
    """(timestep, z-slab) grid body; ``ss`` is the stacked (2, pz, Y, X)
    state (output aliased onto the input — all access goes through the
    out ref). ``step_fn(v, j) -> (bz, Y, X)`` fuses the three RK stages
    of slab ``j`` on the ``(bz + 2G)``-row VMEM box ``v``.

    ``batched``: the B-folded ensemble variant — the grid gains a
    LEADING member axis (``(B, timestep, z-slab)``), ``ss`` a leading
    member dimension (``(B, 2, pz, Y, X)``), and every DMA indexes the
    current member's stack. The sequential TPU grid finishes member
    ``m`` (including the end-of-member write drain at ``i == total-1``)
    before ``m+1`` starts, and no copy ever addresses another member's
    rows — the member axis is halo-free by construction (statically
    proven by ``analysis/halo_verify``)."""
    del s_in  # aliased with ss
    # canonical i32 indices: interpret mode under x64 hands the two grid
    # dimensions different integer widths
    if batched:
        m = jnp.asarray(pl.program_id(0), jnp.int32)
        k = jnp.asarray(pl.program_id(1), jnp.int32)
        j = jnp.asarray(pl.program_id(2), jnp.int32)
    else:
        m = None
        k = jnp.asarray(pl.program_id(0), jnp.int32)
        j = jnp.asarray(pl.program_id(1), jnp.int32)
    n = jnp.asarray(n_slabs, jnp.int32)
    two = jnp.asarray(2, jnp.int32)
    i = k * n + j
    total = n_iters * n_slabs
    slot = lax.rem(i, two)
    nslot = lax.rem(i + 1, two)

    def _stack(parity):
        # the (2, pz, Y, X) ping-pong stack of the current member
        return ss.at[m, parity] if batched else ss.at[parity]

    def copy_in(kk, jj, s):
        kk = jnp.asarray(kk, jnp.int32)  # literal 0s stay i32 under x64
        jj = jnp.asarray(jj, jnp.int32)
        return pltpu.make_async_copy(
            _stack(lax.rem(kk, two)).at[pl.ds(jj * bz, bz + 2 * G)],
            vs.at[s],
            sem_v.at[s],
        )

    def copy_out(ii, s):
        ii = jnp.asarray(ii, jnp.int32)
        kk = lax.div(ii, n)
        jj = lax.rem(ii, n)
        return pltpu.make_async_copy(
            res.at[s],
            _stack(1 - lax.rem(kk, two)).at[pl.ds(G + jj * bz, bz)],
            sem_w.at[s],
        )

    # ---- load schedule ----
    if cross:
        # steady 2-deep pipeline across step boundaries (see _cross_ok)
        @pl.when(i == 0)
        def _():
            copy_in(0, 0, slot).start()

        @pl.when(i + 1 < total)
        def _():
            wrap = j + 1 == n
            kk = jnp.where(wrap, k + 1, k)
            jj = jnp.where(wrap, jnp.asarray(0, jnp.int32), j + 1)
            copy_in(kk, jj, nslot).start()

    else:
        # the next step's slab-0 read races this step's tail writes on
        # thin slab counts: drain the outstanding writes of the previous
        # step, then load slab 0 synchronously. With a single slab per
        # step only one write is ever outstanding (the previous
        # iteration drained i-2 as *its* i-1) — waiting it twice would
        # hang the semaphore.
        if n_slabs >= 2:
            @pl.when((j == 0) & (i >= 2))
            def _():
                copy_out(i - 2, slot).wait()

        @pl.when((j == 0) & (i >= 1))
        def _():
            copy_out(i - 1, nslot).wait()

        @pl.when(j == 0)
        def _():
            copy_in(k, 0, slot).start()

        @pl.when((i + 1 < total) & (j + 1 < n))
        def _():
            copy_in(k, j + 1, nslot).start()

    copy_in(k, j, slot).wait()
    out = step_fn(vs[slot], j)

    # ---- write-drain schedule (invariant: writes <= i-3 have landed at
    # iteration start; at j == 0 both outstanding writes are drained,
    # at j >= 2 the slot's previous write) ----
    if cross:
        @pl.when((j == 0) & (i >= 2))
        def _():
            copy_out(i - 2, slot).wait()

        @pl.when((j == 0) & (i >= 1))
        def _():
            copy_out(i - 1, nslot).wait()

    @pl.when(j >= 2)
    def _():
        copy_out(i - 2, slot).wait()

    res[slot] = out
    copy_out(i, slot).start()

    @pl.when(i == total - 1)
    def _():
        copy_out(i, slot).wait()
        if n_slabs > 1:  # at the last iteration j >= 1, so i-1 is live
            copy_out(i - 1, nslot).wait()


def _pick_dma_block(lz: int, G: int, k: int, viable) -> int | None:
    """Largest z-block serving the uniform-bz in-kernel dma grid: it
    must tile the final core window exactly (``bz | lz``) and — deep
    schedules only — every in-block window extent ``lz + 2*(k-1-j)*G``
    too (``bz | 2G`` suffices, the extents differing by 2G per step)."""
    for b in range(lz, 0, -1):
        if lz % b:
            continue
        if k > 1 and (2 * G) % b:
            continue
        if viable(b):
            return b
    return None


def _whole_run_dma_kernel(offs, s_in, land_in, ss, land, vs, res, sem_v,
                          sem_w, sem_land, send_sem, recv_sem, *, step_fn,
                          bz: int, G: int, k: int, lz: int, n0: int,
                          n_iters: int, mesh_axis: str, num_shards: int,
                          barrier: bool):
    """Sharded whole-run grid with in-kernel neighbor halo exchange.

    Grid ``(timestep, z-slab)`` per shard; ``ss`` is the stacked
    ``(2, pz, Y, X)`` ping-pong state (aliased out), ``land`` the
    dedicated ``(2 slots, 2 sides, k*G, Y, X)`` remote-DMA landing
    buffer (aliased out; written ONLY by the neighbors' pushes). The
    schedule, with ``depth = k*G`` and in-block step ``j = s % k``:

    * step ``j`` computes the core extended by ``(k-1-j)*G`` rows per
      side (the deep-halo trapezoid) on a uniform ``bz`` z-block —
      grid iterations beyond the step's window are predicated off;
      slab loads double-buffer within the step, writes drain fully at
      each step's tail (the next step's reads are exactly this step's
      output window);
    * at each block's last step, after the write drain, every shard
      pushes its freshly written core edge windows (rows
      ``[depth, 2*depth)`` and ``[pz-2*depth, pz-depth)`` of the
      parity the next block reads) to the ±z neighbors' landing slot
      ``(b+1) % 2`` via ``make_async_remote_copy`` — cyclic ring
      pushes issued by EVERY shard in lockstep (rank-uniform sites;
      the wall shards' wrapped slabs land in rows the receiver never
      consumes, mirroring the XLA path's cyclic ``ppermute``);
    * at each block's first iteration the paired send/recv semaphores
      are waited (send: my source rows are reusable; recv: the
      neighbors' rows landed) and the landed slabs are spliced into
      the read parity's ghost rows with a local DMA — predicated per
      side on the shard's rank, so the wall sides keep their frozen
      embed BC ghosts (Dirichlet values / edge replicas, maintained
      across steps by the step windows' out-of-domain pass-through);
    * block 0 has no prior block to push for it: its exchange is the
      same pair of pushes issued at the first iteration from the
      embedded initial state (the XLA path's block-start refresh of
      the fresh embed, in-kernel).

    Pushes never address state rows — the landing buffer is the only
    remote-DMA destination — so the send/recv windows are disjoint
    from every locally computed row by construction (the invariant
    ``analysis/halo_verify`` proves from the declared
    ``remote_dma`` windows), and the 2-slot landing ping-pong plus
    the block-dependency chain (a neighbor cannot start block ``b+1``
    before receiving my block-``b`` push) bound the skew: a push for
    block ``b+2`` cannot arrive before my block-``b`` reads of that
    slot are done."""
    del s_in, land_in  # aliased with ss / land
    depth = k * G
    pz = lz + 2 * depth
    box = bz + 2 * G
    s = jnp.asarray(pl.program_id(0), jnp.int32)
    jj = jnp.asarray(pl.program_id(1), jnp.int32)
    two = jnp.asarray(2, jnp.int32)
    kk = jnp.asarray(k, jnp.int32)
    j = lax.rem(s, kk)
    b = lax.div(s, kk)
    read_par = lax.rem(s, two)
    write_par = 1 - read_par
    total = jnp.asarray(n_iters, jnp.int32)
    if k == 1:
        n_act = jnp.asarray(n0, jnp.int32)
    else:
        # bz | lz and bz | 2G make every in-block extent tile exactly
        n_act = jnp.asarray(lz // bz, jnp.int32) + (
            (kk - 1 - j) * jnp.asarray((2 * G) // bz, jnp.int32)
        )
    active = jj < n_act
    oz = offs[0]
    me = jnp.asarray(lax.axis_index(mesh_axis), jnp.int32)
    P = jnp.asarray(num_shards, jnp.int32)

    def remote_pair(slot, par):
        # my top core window -> +z neighbor's LO landing slab; my
        # bottom -> -z neighbor's HI. Sources sit inside the core
        # (rows this shard itself computed), destinations inside the
        # landing buffer only.
        up = pltpu.make_async_remote_copy(
            ss.at[par, pl.ds(pz - 2 * depth, depth)],
            land.at[slot, 0],
            send_sem.at[slot, 0],
            recv_sem.at[slot, 0],
            device_id=lax.rem(me + 1, P),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        down = pltpu.make_async_remote_copy(
            ss.at[par, pl.ds(depth, depth)],
            land.at[slot, 1],
            send_sem.at[slot, 1],
            recv_sem.at[slot, 1],
            device_id=lax.rem(me + P - 1, P),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        return up, down

    def land_copy(side: int, par):
        dst0 = 0 if side == 0 else pz - depth
        return pltpu.make_async_copy(
            land.at[lax.rem(b, two), side],
            ss.at[par, pl.ds(dst0, depth)],
            sem_land.at[side],
        )

    # ---- block start: wait the pushes, splice into the read parity's
    # ghost rows (wall sides keep the frozen embed BC ghosts) ----
    @pl.when((j == 0) & (jj == 0))
    def _():
        @pl.when(b == 0)
        def _():
            if barrier:
                # neighbor barrier before the first push: a neighbor's
                # landing buffer is only ours to write once its kernel
                # has started (XLA may still be initializing it before
                # then). The CPU interpreter's copies are synchronous
                # and it has no barrier semaphore.
                sem = pltpu.get_barrier_semaphore()
                for nb in (lax.rem(me + 1, P), lax.rem(me + P - 1, P)):
                    pltpu.semaphore_signal(
                        sem, 1, device_id=nb,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    )
                pltpu.semaphore_wait(sem, 2)
            up, down = remote_pair(jnp.asarray(0, jnp.int32), read_par)
            up.start()
            down.start()

        up, down = remote_pair(lax.rem(b, two), read_par)
        up.wait()
        down.wait()

        @pl.when(me > 0)
        def _():
            land_copy(0, read_par).start()
            land_copy(0, read_par).wait()

        @pl.when(me < P - 1)
        def _():
            land_copy(1, read_par).start()
            land_copy(1, read_par).wait()

    def copy_in(slab, slot):
        return pltpu.make_async_copy(
            ss.at[read_par, pl.ds(j * G + slab * bz, box)],
            vs.at[slot],
            sem_v.at[slot],
        )

    def copy_out(slab, slot):
        return pltpu.make_async_copy(
            res.at[slot],
            ss.at[write_par, pl.ds((j + 1) * G + slab * bz, bz)],
            sem_w.at[slot],
        )

    @pl.when(jj == 0)
    def _():
        copy_in(jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)).start()

    @pl.when(active & (jj + 1 < n_act))
    def _():
        copy_in(jj + 1, lax.rem(jj + 1, two)).start()

    @pl.when(active)
    def _():
        slot = lax.rem(jj, two)
        copy_in(jj, slot).wait()
        out = step_fn(vs[slot], j, jj, oz)

        @pl.when(jj >= 2)
        def _():
            copy_out(jj - 2, slot).wait()

        res[slot] = out
        copy_out(jj, slot).start()

    # ---- step tail (the step's last grid iteration, active or not):
    # drain the step's outstanding writes, then — at block ends — push
    # the fresh core edges for the neighbors' next block
    @pl.when(jj == n0 - 1)
    def _():
        @pl.when(n_act >= 2)
        def _():
            copy_out(n_act - 2, lax.rem(n_act - 2, two)).wait()

        copy_out(n_act - 1, lax.rem(n_act - 1, two)).wait()

        @pl.when((j == kk - 1) & (s + 1 < total))
        def _():
            up, down = remote_pair(lax.rem(b + 1, two),
                                   lax.rem(s + 1, two))
            up.start()
            down.start()


def _step_call_kernel(*refs, step_fn, bz: int, G: int, z_out0: int,
                      n_grid: int, ghost_src, op_rows: int, g_start: int,
                      sharded: bool):
    """One sharded per-step call (grid = this call's slab range): reads
    the padded state ``s_in``, writes the step result into a separate
    ping-pong target (aliased out).

    The call is parameterized on its *output window*: ``n_grid`` slabs
    of ``bz`` rows starting at padded row ``z_out0``, each computed from
    a ``bz + 2G``-row input box starting ``G`` rows above. The per-step
    schedule uses one full-core window; the communication-avoiding deep
    schedule builds one call per in-block step, the windows shrinking by
    ``G`` per step (the cross-step trapezoid of redundant ghost
    recompute). Roles mirror the per-stage split schedule: ``ghost_src``
    = "lo"/"hi" DMAs ``op_rows`` rows of the box (at its start/end) from
    the separately exchanged slab operand — ``g_hbm[g_start:]`` — instead
    of the buffer (whose exchanged-depth z ghosts are stale in split
    mode). Ghost-consuming calls are always single-slab (``n_grid == 1``)
    so the operand/buffer split is static."""
    offs = None
    if sharded:
        offs, *refs = refs
    s_in, *refs = refs
    g_hbm = None
    if ghost_src is not None:
        g_hbm, *refs = refs
    _tgt, out, vs, res, sem_v, sem_w, *refs = refs
    sem_g = refs[0] if refs else None

    k = jnp.asarray(pl.program_id(0), jnp.int32)
    slot = lax.rem(k, jnp.asarray(2, jnp.int32))
    nslot = lax.rem(k + 1, jnp.asarray(2, jnp.int32))
    box = bz + 2 * G

    def copy_in(kk, s):
        z0 = (z_out0 - G) + kk * bz  # padded row of the box's first row
        if ghost_src is None:
            return [
                pltpu.make_async_copy(
                    s_in.at[pl.ds(z0, box)], vs.at[s], sem_v.at[s]
                )
            ]
        # single-slab ghost calls: z0 == z_out0 - G, all splits static
        cps = []
        if ghost_src == "lo":
            cps.append(
                pltpu.make_async_copy(
                    g_hbm.at[pl.ds(g_start, op_rows)],
                    vs.at[s, pl.ds(0, op_rows)],
                    sem_g.at[s],
                )
            )
            if op_rows < box:
                cps.append(
                    pltpu.make_async_copy(
                        s_in.at[pl.ds(z0 + op_rows, box - op_rows)],
                        vs.at[s, pl.ds(op_rows, box - op_rows)],
                        sem_v.at[s],
                    )
                )
            return cps
        head = box - op_rows
        if head:
            cps.append(
                pltpu.make_async_copy(
                    s_in.at[pl.ds(z0, head)],
                    vs.at[s, pl.ds(0, head)],
                    sem_v.at[s],
                )
            )
        cps.append(
            pltpu.make_async_copy(
                g_hbm.at[pl.ds(g_start, op_rows)],
                vs.at[s, pl.ds(head, op_rows)],
                sem_g.at[s],
            )
        )
        return cps

    def copy_out(kk, s):
        return pltpu.make_async_copy(
            res.at[s],
            out.at[pl.ds(z_out0 + kk * bz, bz)],
            sem_w.at[s],
        )

    @pl.when(k == 0)
    def _():
        for cp in copy_in(0, 0):
            cp.start()

    @pl.when(k + 1 < n_grid)
    def _():
        for cp in copy_in(k + 1, nslot):
            cp.start()

    for cp in copy_in(k, slot):
        cp.wait()

    oz = offs[0] if offs is not None else 0
    out_rows = step_fn(vs[slot], k, oz)

    @pl.when(k >= 2)
    def _():
        copy_out(k - 2, slot).wait()

    res[slot] = out_rows
    copy_out(k, slot).start()

    @pl.when(k == n_grid - 1)
    def _():
        copy_out(k, slot).wait()
        if n_grid >= 2:
            copy_out(k - 1, nslot).wait()


class _SlabRunStepper:
    """Shared driver for the two slab whole-run steppers.

    Subclasses provide the layout (``padded_shape``, ``core_offsets``),
    ``embed``/``extract``, and ``_step_fn(v, base_z) -> (bz, Y, X)``
    (``base_z``: traced global z index of the box's first row). ``halo``
    is the fused-step halo ``G`` — under a mesh the base class's ghost
    machinery then exchanges G-deep slabs once per step."""

    engaged_label = "fused-whole-run-slab"
    needs_offsets = True  # global-coordinate masks / edge synthesis
    overlap_split = False  # sharded split instances set True in __init__
    # interface parity with the per-stage steppers (probed by callers):
    # slab mode is fixed-dt only (no stage-emitted wave speed) and never
    # runs the stored-x-ghost layout (z-slab decompositions only)
    _emit_max = False
    x_sharded = False
    # communication-avoiding chunk length k and the per-exchange ghost
    # depth k*G; sharded instances with steps_per_exchange > 1 override
    # in __init__ (models/base._fused_sharded_ctx exchanges
    # ``exchange_depth`` rows instead of the per-step stencil halo)
    k = steps_per_exchange = 1
    #: queryable stencil metadata (analysis/halo_verify.py): all three
    #: RK stages recompute per ghost refresh, so G = halo = 3 * h
    fused_stages = 3
    stencil_radius = None  # subclasses declare h (R / HALO[order])
    #: B-folded member grid axis (run_batched): declared member count of
    #: a batched instance (1 = unbatched). The member axis carries NO
    #: stencil reach — each member owns its own (2, pz, Y, X) stack and
    #: no DMA crosses members — so its halo is 0 by construction; the
    #: static verifier proves the declaration and that a batched
    #: instance never composes with spatial sharding in one program.
    members = 1
    member_halo = 0
    #: halo-exchange transport of a sharded instance: "collective"
    #: (XLA ppermute between the per-step slab calls — every schedule
    #: above) or "dma" (ONE whole-run Pallas program per shard with
    #: in-kernel `make_async_remote_copy` neighbor pushes; declared to
    #: the static verifier via ``remote_dma``); ``_init_exchange``
    #: sets the instance state
    exchange = "collective"
    remote_dma = None
    mesh_axis = None
    num_shards = None

    def stencil_spec(self) -> dict:
        """Stencil/halo contract of the slab rung (see
        ``stepper_base.FusedStepperBase.stencil_spec``): ``halo`` is
        the fused-step ghost depth ``G = 3h``, the exchange moves
        ``k * G`` rows, and the deep schedule's in-block windows shrink
        by ``G`` per step — all statically provable from these fields
        plus ``interior_shape``/``padded_shape``/``core_offsets``.
        ``members``/``member_halo`` declare the B-folded leading member
        grid axis (halo-free; ``run_batched``)."""
        return {
            "kernel": self.engaged_label,
            "stage_radius": int(self.stencil_radius),
            "fused_stages": int(self.fused_stages),
            "ghost_depth": int(self.halo),
            "exchange_depth": int(self.exchange_depth),
            "steps_per_exchange": int(self.steps_per_exchange),
            "members": int(self.members),
            "member_halo": int(self.member_halo),
            # halo-exchange transport actually engaged on this instance
            "exchange": self.exchange,
            # declared in-kernel remote-DMA window (ROADMAP item 2) —
            # None while the exchange rides XLA ppermute between slab
            # calls; exchange='dma' instances declare it and
            # halo_verify proves window/disjointness/semaphore pairing
            # against the exchange arithmetic BEFORE any hardware run
            "remote_dma": getattr(self, "remote_dma", None),
            # HBM/wire storage declaration (halo_verify derives every
            # declared byte count from it; bf16 rungs carry 2 B/cell)
            "storage_dtype": str(jnp.dtype(self.dtype)),
            "bytes_per_cell": int(jnp.dtype(self.dtype).itemsize),
        }

    def _dma_block_viable(self, b: int) -> bool:  # pragma: no cover
        raise NotImplementedError

    def _init_exchange(self, exchange, mesh_axis, num_shards) -> None:
        """Validate + arm the halo-exchange transport. ``'dma'``
        requires a sharded z-slab instance with a concrete (string)
        mesh axis — a compound multihost axis spans DCN, which remote
        DMA cannot cross — picks the uniform dma z-block, and declares
        the ``remote_dma`` contract the static verifier proves."""
        exchange = str(exchange)
        if exchange not in ("collective", "dma"):
            raise ValueError(
                f"unknown exchange mode {exchange!r}; "
                "'collective' (XLA ppermute) or 'dma' (in-kernel)"
            )
        self.exchange = exchange
        if exchange != "dma":
            return
        if not self.sharded:
            raise ValueError(
                "exchange='dma' serves sharded (z-slab) slab instances "
                "only — an unsharded run has no neighbor to push to"
            )
        if self.overlap_split:
            raise ValueError(
                "exchange='dma' replaces the XLA exchange entirely; "
                "the split-overlap schedule does not compose with it"
            )
        if not isinstance(mesh_axis, str) or num_shards is None:
            raise ValueError(
                "exchange='dma' needs the z mesh axis name and shard "
                "count (a compound/multihost mesh axis cannot host the "
                "ICI remote-DMA ring)"
            )
        self.mesh_axis = mesh_axis
        self.num_shards = int(num_shards)
        depth = self.exchange_depth
        lz = self.interior_shape[0]
        if lz < depth:
            raise ValueError(
                f"local z extent {lz} cannot serve the {depth}-deep "
                "in-kernel exchange (the pushed core edge windows "
                "would leave the shard's own rows)"
            )
        bz = _pick_dma_block(lz, self.halo, self.k,
                             self._dma_block_viable)
        if bz is None:
            raise ValueError(
                "no viable uniform z-block for the in-kernel dma grid "
                f"(lz={lz}, G={self.halo}, k={self.k})"
            )
        self._dma_bz = bz
        self._dma_n0 = (lz + 2 * (self.k - 1) * self.halo) // bz
        pz = self.padded_shape[0]
        self.remote_dma = {
            "axis": 0,
            "window_rows": depth,
            "buffers": 2,
            # pushed rows: my freshly computed core edge windows...
            "send_windows": ((depth, 2 * depth),
                             (pz - 2 * depth, pz - depth)),
            # ...landing OUTSIDE the neighbor's core — first in the
            # dedicated landing buffer, spliced into these ghost rows
            "recv_windows": ((0, depth), (pz - depth, pz)),
            "semaphores": ("send", "recv"),
            "landing": "dedicated",
        }

    def _run_dma(self, u, t, num_iters: int, offsets):
        """The whole sharded run as ONE Pallas program per shard (must
        run inside ``shard_map``): ghost rows move over ICI from inside
        the kernel — the program never returns to XLA between steps."""
        from multigpu_advectiondiffusion_tpu.ops.pallas.stepper_base import (
            chunk_counts,
        )
        from multigpu_advectiondiffusion_tpu.parallel.halo import (
            record_remote_dma,
        )

        G, k = self.halo, self.k
        depth = self.exchange_depth
        bz, n0 = self._dma_bz, self._dma_n0
        lz = self.interior_shape[0]
        full, rem_steps = chunk_counts(num_iters, k)
        blocks = full + (1 if rem_steps else 0)
        trailing = self.padded_shape[1:]
        record_remote_dma(
            kernel=self.engaged_label,
            plane_shape=trailing,
            itemsize=self.dtype.itemsize,
            window_rows=depth,
            blocks=blocks,
            mesh_axis=self.mesh_axis,
        )
        kern = functools.partial(
            _whole_run_dma_kernel,
            step_fn=lambda v, j, jj, oz: self._step_fn(
                v, j * G + jj * bz - depth + oz
            ),
            bz=bz, G=G, k=k, lz=lz, n0=n0, n_iters=num_iters,
            mesh_axis=self.mesh_axis, num_shards=self.num_shards,
            barrier=not interpret_mode(),
        )
        S = self.embed(u)
        SS = jnp.stack([S, S])
        land = jnp.zeros((2, 2, depth) + tuple(trailing), self.dtype)
        scratch = [
            pltpu.VMEM((2, bz + 2 * G) + tuple(trailing), self.dtype),
            pltpu.VMEM((2, bz) + tuple(trailing), self.dtype),
            pltpu.SemaphoreType.DMA((2,)),   # slab loads
            pltpu.SemaphoreType.DMA((2,)),   # slab writes
            pltpu.SemaphoreType.DMA((2,)),   # landing -> state splices
            pltpu.SemaphoreType.DMA((2, 2)),  # send [slot, side]
            pltpu.SemaphoreType.DMA((2, 2)),  # recv [slot, side]
        ]
        with jax.named_scope(f"tpucfd.{self.engaged_label}[dma]"):
            out, _ = pl.pallas_call(
                kern,
                grid=(num_iters, n0),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=(
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ),
                out_shape=(
                    jax.ShapeDtypeStruct(SS.shape, SS.dtype),
                    jax.ShapeDtypeStruct(land.shape, land.dtype),
                ),
                input_output_aliases={1: 0, 2: 1},
                scratch_shapes=scratch,
                compiler_params=(
                    None if interpret_mode() else _dma_compiler_params()
                ),
                interpret=interpret_mode(),
            )(offsets, SS, land)
        return (
            self.extract(out[num_iters % 2]),
            accumulate_t(t, self.dt, num_iters),
        )

    def _check_members(self, members: int) -> int:
        """Validate a declared member fold: the batched grid serves
        unsharded (single-chip or member-sharded) instances only — a
        spatially sharded instance runs per-step calls whose ghost
        refresh the member fold cannot cross."""
        members = int(members)
        if members < 1:
            raise ValueError(f"members must be >= 1, got {members}")
        if members > 1 and self.sharded:
            raise ValueError(
                "the B-folded slab grid composes with member sharding "
                "only; a spatially sharded slab instance cannot fold a "
                "member axis (its per-step ghost refresh would have to "
                "cross the fold)"
            )
        return members

    # populated by subclass __init__:
    #   interior_shape, global_shape, sharded, overlap_split, halo (=G),
    #   exchange_depth (=k*G), core_offsets, padded_shape, dtype
    #   (kernel), _storage, dt, bz, n_slabs, _step_fn
    #: window ledger of every sharded call built (_make_call), in
    #: construction order — the static halo verifier
    #: (analysis/halo_verify.py) proves these against the trapezoid
    #: arithmetic it re-derives from stencil_spec()
    _call_windows = ()

    def _scratch(self):
        trailing = self.padded_shape[1:]
        return [
            pltpu.VMEM((2, self.bz + 2 * self.halo) + trailing, self.dtype),
            pltpu.VMEM((2, self.bz) + trailing, self.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]

    def _whole_run(self, P, num_iters: int):
        G, bz, n_slabs = self.halo, self.bz, self.n_slabs
        kern = functools.partial(
            _whole_run_kernel,
            step_fn=lambda v, j: self._step_fn(v, j * bz - G),
            bz=bz, G=G, n_slabs=n_slabs, n_iters=num_iters,
            cross=_cross_ok(bz, G, n_slabs),
        )
        SS = jnp.stack([P, P])
        out = pl.pallas_call(
            kern,
            grid=(num_iters, n_slabs),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(SS.shape, SS.dtype),
            scratch_shapes=self._scratch(),
            input_output_aliases={0: 0},
            compiler_params=None if interpret_mode() else compiler_params(),
            interpret=interpret_mode(),
        )(SS)
        return out[num_iters % 2]

    def run_batched(self, us, ts, num_iters: int):
        """Advance B independent members ``num_iters`` fused steps in
        ONE Pallas program: the ``(timestep, z-slab)`` grid gains a
        LEADING member axis — grid ``(B, num_iters, n_slabs)``, stacked
        state ``(B, 2, pz, Y, X)``. The sequential TPU grid streams one
        member's whole run, drains its writes, then starts the next;
        scratch (the double-buffered slab/result slots) is shared
        because members never overlap in time. The member axis carries
        no stencil reach — uniform-physics ensembles ride the fastest
        rung instead of being declined (ROADMAP item 1). Unsharded
        instances only (``_check_members``); under a member-sharded
        mesh each device runs this program over its own members."""
        if self.sharded:
            raise ValueError(
                "run_batched serves unsharded slab instances only "
                "(member-sharded meshes run one fold per device; "
                "spatial sharding declines the member fold)"
            )
        B = int(us.shape[0])
        if num_iters == 0:
            return us, ts
        G, bz, n_slabs = self.halo, self.bz, self.n_slabs
        kern = functools.partial(
            _whole_run_kernel,
            step_fn=lambda v, j: self._step_fn(v, j * bz - G),
            bz=bz, G=G, n_slabs=n_slabs, n_iters=num_iters,
            cross=_cross_ok(bz, G, n_slabs), batched=True,
        )
        with jax.named_scope(f"tpucfd.{self.engaged_label}[members]"):
            S = jax.vmap(self.embed)(us)      # (B, pz, Y, X)
            SS = jnp.stack([S, S], axis=1)    # (B, 2, pz, Y, X)
            out = pl.pallas_call(
                kern,
                grid=(B, num_iters, n_slabs),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                out_shape=jax.ShapeDtypeStruct(SS.shape, SS.dtype),
                scratch_shapes=self._scratch(),
                input_output_aliases={0: 0},
                compiler_params=(
                    None if interpret_mode() else compiler_params()
                ),
                interpret=interpret_mode(),
            )(SS)
            final = jax.vmap(self.extract)(out[:, num_iters % 2])
        return final, accumulate_t(ts, self.dt, num_iters)

    def _make_call(self, z_out0: int, bz: int, n_grid: int, ghost_src=None):
        """One sharded step call writing ``n_grid`` slabs of ``bz`` rows
        at padded row ``z_out0`` (input boxes reach ``G`` rows beyond on
        both sides). ``ghost_src`` = "lo"/"hi" sources the box rows that
        fall inside the exchanged-depth ghost region from the separately
        exchanged slab operand (single-slab calls only: the split is
        computed statically here)."""
        G = self.halo
        depth = self.exchange_depth  # k*G rows per exchanged operand
        pz = self.padded_shape[0]
        box = bz + 2 * G
        op_rows = g_start = 0
        if ghost_src is not None:
            if n_grid != 1:  # pragma: no cover - internal invariant
                raise ValueError("ghost-consuming calls are single-slab")
            b0 = z_out0 - G
            if ghost_src == "lo":
                # operand covers padded rows [0, depth)
                op_rows = min(depth - b0, box)
                g_start = b0
            else:
                # operand covers padded rows [pz - depth, pz)
                op_rows = min(b0 + box - (pz - depth), box)
                g_start = b0 + (box - op_rows) - (pz - depth)
            if op_rows <= 0:  # pragma: no cover - internal invariant
                raise ValueError("ghost call consumes no operand rows")
        # global z of a box's first row: padded row minus the core
        # offset (exchange depth) plus this shard's global offset (oz,
        # traced — applied in-kernel)
        gz_base = z_out0 - G - self.core_offsets[0]
        self._call_windows.append({
            "z_out0": int(z_out0), "bz": int(bz), "n_grid": int(n_grid),
            "ghost_src": ghost_src, "op_rows": int(op_rows),
            "g_start": int(g_start),
        })

        kern = functools.partial(
            _step_call_kernel,
            step_fn=lambda v, kk, oz: self._step_fn(
                v, gz_base + kk * bz + oz
            ),
            bz=bz, G=G, z_out0=z_out0, n_grid=n_grid,
            ghost_src=ghost_src, op_rows=op_rows, g_start=g_start,
            sharded=True,
        )
        use_g = ghost_src is not None
        n_in = 1 + 1 + (1 if use_g else 0) + 1  # offs, s_in, [g], tgt
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * (n_in - 1)
        trailing = self.padded_shape[1:]
        scratch = [
            pltpu.VMEM((2, box) + trailing, self.dtype),
            pltpu.VMEM((2, bz) + trailing, self.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        if use_g:
            scratch.append(pltpu.SemaphoreType.DMA((2,)))
        return pl.pallas_call(
            kern,
            grid=(n_grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(self.padded_shape, self.dtype),
            scratch_shapes=scratch,
            input_output_aliases={n_in - 1: 0},  # ping-pong target -> out
            compiler_params=None if interpret_mode() else compiler_params(),
            interpret=interpret_mode(),
        )

    def _pick_call_bz(self, extent: int) -> int:
        """Largest viable z-block tiling ``extent`` exactly (the deep
        schedule's windows all differ, so each call picks its own)."""
        raise NotImplementedError

    def _build_sharded_calls(self):
        self._call_windows = []
        G, bz, n_slabs = self.halo, self.bz, self.n_slabs
        if self.k > 1:
            self._build_deep_calls()
            return
        if self.overlap_split:
            self._calls = (
                self._make_call(G + bz, bz, n_slabs - 2),        # interior
                self._make_call(G, bz, 1, ghost_src="lo"),       # bottom
                self._make_call(G + (n_slabs - 1) * bz, bz, 1,
                                ghost_src="hi"),                  # top
            )
        else:
            self._calls = (self._make_call(G, bz, n_slabs),)

    def _build_deep_calls(self):
        """The communication-avoiding k-step block: one call per in-block
        step ``j``, its output window the core extended by
        ``(k-1-j) * G`` rows per side — the cross-step trapezoid. Step 0
        consumes the freshly exchanged ``k*G``-deep ghosts (from the
        buffer after a deep refresh, or — split mode — from the
        exchanged slab operands via single-slab edge calls that overlap
        the interior call with the in-flight ppermute); each later step
        reads exactly the previous step's output window, so nothing else
        in the block depends on communication."""
        G, k, lz = self.halo, self.k, self.interior_shape[0]
        depth = self.exchange_depth  # k*G
        pz = self.padded_shape[0]
        calls = []
        for j in range(k):
            ext = lz + 2 * (k - 1 - j) * G
            bz_j = self._pick_call_bz(ext)
            calls.append(self._make_call((j + 1) * G, bz_j, ext // bz_j))
        self._deep_calls = tuple(calls)
        if not self.overlap_split:
            return
        # split step 0: the interior call covers the window computable
        # from the locally valid core alone (box exactly [depth,
        # pz-depth)); the ghost-region output rows come from unrolled
        # single-slab edge calls consuming the exchanged operands
        ext_i = lz - 2 * G
        bz_i = self._pick_call_bz(ext_i)
        self._deep_interior = self._make_call(G + depth, bz_i,
                                              ext_i // bz_i)
        bz_e = self._pick_call_bz(depth)
        self._deep_bottom = tuple(
            self._make_call(G + i * bz_e, bz_e, 1, ghost_src="lo")
            for i in range(depth // bz_e)
        )
        self._deep_top = tuple(
            self._make_call(pz - G - depth + i * bz_e, bz_e, 1,
                            ghost_src="hi")
            for i in range(depth // bz_e)
        )

    def run(self, u, t, num_iters: int, refresh=None, offsets=None,
            exch=None):
        """``num_iters`` fused steps; returns ``(u, t)``. Unsharded: one
        whole-run Pallas program. Sharded (inside ``shard_map``): one
        slab-pipelined call per step with a G-deep z-ghost ``refresh``
        per step — or, in split mode, ``exch``'s exchanged G-slabs
        consumed by the two edge calls while the interior call overlaps
        the ppermute. With ``steps_per_exchange = k > 1`` the
        communication-avoiding schedule runs instead: ONE ``k*G``-deep
        exchange per k-step block, the in-between steps recomputing the
        ghost zone redundantly on shrinking windows (split mode overlaps
        each block's exchange with the block-start interior call)."""
        if num_iters == 0:
            return u, t
        if not self.sharded:
            with jax.named_scope(f"tpucfd.{self.engaged_label}"):
                S = self._whole_run(self.embed(u), num_iters)
            return self.extract(S), accumulate_t(t, self.dt, num_iters)

        if offsets is None:
            raise ValueError("sharded slab stepper needs offsets")
        if self.exchange == "dma":
            # in-kernel remote-DMA exchange: no refresh/exch closures —
            # the whole run is one Pallas program per shard
            return self._run_dma(u, t, num_iters, offsets)
        if self.overlap_split:
            if exch is None:
                raise ValueError("split-overlap slab stepper needs exch")
        elif refresh is None:
            raise ValueError("sharded slab stepper needs a ghost refresh")

        from multigpu_advectiondiffusion_tpu.ops.pallas.stepper_base import (
            _with_repeats,
            chunk_counts,
        )

        S = self.embed(u)
        T = S
        if self.k > 1:
            full_blocks, rem = chunk_counts(num_iters, self.k)

            def block(S, T, nsteps, refresh_b, exch_b):
                if self.overlap_split:
                    with jax.named_scope("tpucfd.slab_deep_exchange"):
                        lo, hi = exch_b(S)
                    with jax.named_scope(
                        f"tpucfd.{self.engaged_label}[deep-split]"
                    ):
                        T = self._deep_interior(offsets, S, T)
                        for c in self._deep_bottom:
                            T = c(offsets, S, lo, T)
                        for c in self._deep_top:
                            T = c(offsets, S, hi, T)
                else:
                    with jax.named_scope("tpucfd.slab_deep_refresh"):
                        S = refresh_b(S)
                    with jax.named_scope(
                        f"tpucfd.{self.engaged_label}[deep]"
                    ):
                        T = self._deep_calls[0](offsets, S, T)
                S, T = T, S
                with jax.named_scope(f"tpucfd.{self.engaged_label}[deep]"):
                    for j in range(1, nsteps):
                        T = self._deep_calls[j](offsets, S, T)
                        S, T = T, S
                return S, T

            if full_blocks:
                S, T = lax.fori_loop(
                    0, full_blocks,
                    lambda i, c: block(
                        c[0], c[1], self.k,
                        _with_repeats(refresh, full_blocks),
                        _with_repeats(exch, full_blocks),
                    ),
                    (S, T),
                )
            if rem:
                # partial tail block: a full-depth exchange still buys
                # only ``rem`` steps (priced in PARITY.md); the core is
                # valid after any prefix of a block's steps
                S, T = block(S, T, rem, refresh, exch)
            return self.extract(S), accumulate_t(t, self.dt, num_iters)

        if self.overlap_split:
            interior, bottom, top = self._calls
            exch_loop = _with_repeats(exch, num_iters)

            def body(it, carry):
                # named_scope: the split-overlap schedule's pieces are
                # separately labeled in --trace captures — the exchanged
                # G-slabs next to the interior call they overlap with
                S, T = carry
                with jax.named_scope("tpucfd.slab_split_exchange"):
                    lo, hi = exch_loop(S)
                with jax.named_scope(
                    f"tpucfd.{self.engaged_label}[split]"
                ):
                    T = top(offsets, S, hi,
                            bottom(offsets, S, lo, interior(offsets, S, T)))
                return T, S

        else:
            (full,) = self._calls
            refresh_loop = _with_repeats(refresh, num_iters)

            def body(it, carry):
                S, T = carry
                with jax.named_scope("tpucfd.slab_ghost_refresh"):
                    S = refresh_loop(S)
                with jax.named_scope(f"tpucfd.{self.engaged_label}"):
                    T = full(offsets, S, T)
                return T, S

        S, T = lax.fori_loop(0, num_iters, body, (S, T))
        return self.extract(S), accumulate_t(t, self.dt, num_iters)


# --------------------------------------------------------------------- #
# Diffusion
# --------------------------------------------------------------------- #

_G_DIFF = 3 * R  # 6: three O4 stages of redundant recompute


def _diff_row_bytes(interior_shape, itemsize: int) -> int:
    ny, nx = interior_shape[1], interior_shape[2]
    return (
        round_up(ny + 2 * R, SUBLANE) * round_up(nx + 2 * R, LANE) * itemsize
    )


def _diff_budget_rows(row_bytes: int) -> int:
    # the same calibrated shape as the whole-step stepper's picker (~8
    # live row-sized buffers per block row + fixed overhead incl. the
    # doubled slab/result slots), against the Mosaic scoped ceiling
    return max(1, min(20, int((VMEM_LIMIT // row_bytes - 130) // 8)))


def _split_block(nz: int, cap: int, G: int, viable) -> int | None:
    """Largest viable divisor of ``nz`` that can host the three-call
    split-overlap schedule: an interior band of >= 1 slab (n_slabs >= 3)
    whose boxes never reach the stale ghost rows (bz >= G)."""
    for b in range(min(cap, nz // 3), G - 1, -1):
        if nz % b == 0 and viable(b):
            return b
    return None


def _pick_bz_diffusion(nz: int, row_bytes: int, sharded: bool,
                       G: int = _G_DIFF, want_split: bool = False):
    cap = _diff_budget_rows(row_bytes)
    if sharded:
        if want_split:
            b = _split_block(nz, cap, G, lambda b: True)
            if b is not None:
                return b
        # exchanged cores forbid dead rows: largest divisor <= cap
        for b in range(min(cap, nz), 0, -1):
            if nz % b == 0:
                return b
        return 1
    # unsharded: dead tail rows are legal — score the halo amortization
    # bz/(bz+2G) against the wasted dead rows (as FusedDiffusionStepper)
    def score(b):
        blocks = -(-nz // b)
        return (b / (b + 2 * G)) * (nz / (blocks * b))

    return max(range(1, cap + 1), key=score)


class SlabRunDiffusionStepper(_SlabRunStepper):
    """Whole-run slab-pipelined diffusion stepper.

    Constructor signature mirrors :class:`FusedDiffusionStepper` so the
    two are interchangeable at the dispatch site. ``storage_dtype``
    (e.g. f64) keeps the *state* at that precision while the kernels run
    ``dtype`` (f32) — the f64-storage/f32-compute rung: Mosaic has no
    f64 vector path, so TPU f64 configs ride the f32 kernels and pay
    only the cast at the run boundary (accuracy priced in PARITY.md).
    """

    halo = _G_DIFF
    stencil_radius = R  # O4 Laplacian reach; G = 3 * R

    def __init__(self, interior_shape, dtype, spacing, diffusivity, dt,
                 band, bc_value, block_z=None, global_shape=None,
                 overlap_split: bool = False, storage_dtype=None,
                 steps_per_exchange: int = 1, members: int = 1,
                 exchange: str = "collective", mesh_axis=None,
                 num_shards=None):
        nz, ny, nx = interior_shape
        G = _G_DIFF
        self.interior_shape = tuple(interior_shape)
        self.global_shape = tuple(global_shape or interior_shape)
        self.sharded = self.global_shape != self.interior_shape
        self.dtype = jnp.dtype(dtype)
        self._storage = jnp.dtype(storage_dtype or dtype)
        self.bc_value = float(bc_value)
        self.members = self._check_members(members)
        k = _check_steps_per_exchange(steps_per_exchange, self.sharded,
                                      nz, G)
        self.k = self.steps_per_exchange = k
        self.exchange_depth = k * G
        row_bytes = _diff_row_bytes(interior_shape, self.dtype.itemsize)
        if block_z is None:
            block_z = _pick_bz_diffusion(
                nz, row_bytes, self.sharded,
                want_split=bool(overlap_split and self.sharded),
            )
        elif self.sharded and nz % block_z != 0:
            raise ValueError(
                f"block_z={block_z} must divide local nz={nz} when sharded"
            )
        bz = self.bz = block_z
        nz_eff = nz if self.sharded else -(-nz // bz) * bz
        self.n_slabs = nz_eff // bz
        # bf16 buffers need the doubled sublane tile (min tile (16, 128))
        sub = SUBLANE * max(1, 4 // self.dtype.itemsize)
        self.padded_shape = (
            nz_eff + 2 * self.exchange_depth,
            round_up(ny + 2 * R, sub),
            round_up(nx + 2 * R, LANE),
        )
        self.core_offsets = (self.exchange_depth, R, R)
        scales = tuple(
            float(diffusivity[i]) / (12.0 * spacing[i] * spacing[i])
            for i in range(3)
        )
        self.dt = float(dt)
        # split-overlap needs interior work that never touches the stale
        # z-ghost rows: per-step (k=1) that is >= 3 slabs with bz >= G;
        # the deep schedule's block-start interior call just needs a
        # non-empty window strictly inside the exchanged core (nz > 2G)
        if k > 1:
            self.overlap_split = bool(
                overlap_split and self.sharded and nz > 2 * G
            )
        else:
            self.overlap_split = bool(
                overlap_split and self.sharded
                and self.n_slabs >= 3 and bz >= G
            )

        stage = functools.partial(
            _stage_rows, interior_shape=self.global_shape, scales=scales,
            dt=self.dt, band=band, bc_value=float(bc_value),
        )
        (a1, b1), (a2, b2), (a3, b3) = _STAGES

        def step_fn(v, base_z):
            # the whole-step chain (fused_diffusion_step) on one slab:
            # windows narrow by 2R per stage, masks at global z indices.
            # Window extents derive from the box (not self.bz) so the
            # deep schedule's per-call block sizes all serve; rows
            # outside the global domain pass through _stage_rows
            # untouched (neither interior nor face), keeping the
            # exchanged Dirichlet ghosts frozen across a k-step block.
            w = v.shape[0]
            t1 = stage(v, None, gz0=base_z + R, a=a1, b=b1)
            t2 = stage(t1, v[2 * R: w - 2 * R],
                       gz0=base_z + 2 * R, a=a2, b=b2)
            return stage(t2, v[3 * R: w - 3 * R],
                         gz0=base_z + 3 * R, a=a3, b=b3)

        if self.dtype == jnp.bfloat16:
            # bf16-storage/f32-compute (ISSUE 16): the slab buffers (and
            # every wire byte) stay bf16; each slab upcasts once, runs
            # the three RK stages in f32, and downcasts the core rows
            inner = step_fn

            def step_fn(v, base_z):
                return inner(
                    v.astype(jnp.float32), base_z
                ).astype(jnp.bfloat16)

        self._step_fn = step_fn
        self._init_exchange(exchange, mesh_axis, num_shards)
        if self.sharded and self.exchange != "dma":
            self._build_sharded_calls()

    def _dma_block_viable(self, b: int) -> bool:
        row = _diff_row_bytes(self.interior_shape, self.dtype.itemsize)
        return b <= _diff_budget_rows(row)

    @staticmethod
    def supported(interior_shape, dtype, sharded: bool = False) -> bool:
        row = _diff_row_bytes(interior_shape, jnp.dtype(dtype).itemsize)
        if _diff_budget_rows(row) < 1:
            return False
        if sharded:
            return interior_shape[0] >= 1
        return True

    @staticmethod
    def profitable(interior_shape, dtype, sharded: bool = False) -> bool:
        """Where the slab schedule is modeled to beat the per-stage
        path. Deliberately conservative: the whole-step rung — the same
        fused-3-stages-with-redundant-recompute structure, minus the
        multi-step grid — *measured slower* than per-stage on v5e
        ("compute growth outweighs the HBM saving", PARITY.md), so deep
        multi-slab grids keep the measured per-stage default until a
        TPU session measures the whole-run variant
        (``impl='pallas_slab'`` pins it for that). The structural wins
        engage automatically: z extents served by one or two slabs
        (near-whole-state-in-VMEM per step, minimal redundant rows),
        and hypothetically slabs thick enough that the recompute tax is
        noise (bz >= 4G — above today's VMEM-budget cap at bench-scale
        rows, so effectively future-proofing)."""
        nz = interior_shape[0]
        row = _diff_row_bytes(interior_shape, jnp.dtype(dtype).itemsize)
        bz = _pick_bz_diffusion(nz, row, sharded)
        n_slabs = -(-nz // bz)
        return bz >= 4 * _G_DIFF or n_slabs <= 2

    def _pick_call_bz(self, extent: int) -> int:
        row = _diff_row_bytes(self.interior_shape, self.dtype.itemsize)
        return _pick_bz_diffusion(extent, row, True, G=self.halo)

    def embed(self, u):
        full = jnp.full(self.padded_shape, self.bc_value, self.dtype)
        return lax.dynamic_update_slice(
            full, u.astype(self.dtype), self.core_offsets
        )

    def extract(self, S):
        nz, ny, nx = self.interior_shape
        d = self.exchange_depth
        out = lax.slice(S, (d, R, R), (d + nz, R + ny, R + nx))
        return out.astype(self._storage)


# --------------------------------------------------------------------- #
# Burgers / WENO
# --------------------------------------------------------------------- #


def _burg_row_bytes(interior_shape, itemsize: int, r: int) -> int:
    ny, nx = interior_shape[1], interior_shape[2]
    return (
        round_up(ny + 2 * r, SUBLANE) * round_up(nx + 2 * r, LANE) * itemsize
    )


def _burg_live_rows(bz: int, r: int, order: int) -> int:
    """Model of the live full-width row count: pipeline slots + stage
    windows + the widest stage's sweep intermediates (as fused_burgers's
    ``_live_bytes``, but on full-width rows)."""
    G = 3 * r
    k = 14 if order == 5 else 20
    return 2 * (bz + 2 * G) + 2 * bz + (bz + 4 * r) + (bz + 2 * r) + k * (
        bz + 4 * r
    )


def _pick_bz_burgers(nz: int, row_bytes: int, r: int, order: int,
                     want_split: bool = False):
    """Largest divisor of nz whose modeled working set fits the budget
    (no dead z rows: edge replication indexes the last interior row at a
    static slab-local position only when blocks tile nz exactly).
    ``want_split``: prefer a block the split-overlap schedule can use
    (n_slabs >= 3, bz >= G) when one fits."""
    def fits(b):
        return _burg_live_rows(b, r, order) * row_bytes <= _VMEM_BUDGET

    if want_split:
        b = _split_block(nz, nz, 3 * r, fits)
        if b is not None:
            return b
    for b in range(nz, 0, -1):
        if nz % b == 0 and fits(b):
            return b
    return None


class SlabRunBurgersStepper(_SlabRunStepper):
    """Whole-run slab-pipelined Burgers/WENO stepper (fixed dt).

    Layout is the 2-D whole-run stepper's, extruded: trailing dims
    ``(round8(ny+2r), round128(nx+2r))`` with inline edge-replicated
    ghosts re-synthesized in VMEM after every stage (x/y always; z at
    the global walls, keyed on global coordinates so sharded shards
    leave their neighbor-filled ghost rows alone). Adaptive dt needs a
    global reduction between steps, which the whole-run grid cannot
    host — adaptive configs keep the per-stage stepper.
    """

    def __init__(self, interior_shape, dtype, spacing, flux: Flux,
                 variant: str, nu: float, dt: float, block_z=None,
                 global_shape=None, overlap_split: bool = False,
                 order: int = 5, steps_per_exchange: int = 1,
                 members: int = 1, exchange: str = "collective",
                 mesh_axis=None, num_shards=None, storage_dtype=None):
        if order not in HALO:
            raise ValueError(f"unsupported WENO order {order}")
        if order == 7 and variant != "js":
            raise ValueError("WENO7 supports only the 'js' variant")
        r = HALO[order]
        G = 3 * r
        self.order = order
        self.halo = G
        self.stencil_radius = r  # WENO reach; G = 3 * r
        nz, ny, nx = interior_shape
        self.interior_shape = tuple(interior_shape)
        self.global_shape = tuple(global_shape or interior_shape)
        self.sharded = self.global_shape != self.interior_shape
        self.dtype = jnp.dtype(dtype)
        # storage_dtype is the FACING dtype (the fused-stepper
        # convention): extract restores it; bf16 kernel buffers under
        # precision='bf16' face an f32 state
        self._storage = jnp.dtype(storage_dtype or dtype)
        self.members = self._check_members(members)
        k = _check_steps_per_exchange(steps_per_exchange, self.sharded,
                                      nz, G)
        self.k = self.steps_per_exchange = k
        self.exchange_depth = k * G
        row_bytes = _burg_row_bytes(interior_shape, self.dtype.itemsize, r)
        if block_z is None:
            block_z = _pick_bz_burgers(
                nz, row_bytes, r, order,
                want_split=bool(overlap_split and self.sharded),
            )
            if block_z is None:
                raise ValueError(
                    f"no viable slab block for interior {interior_shape}"
                )
        elif nz % block_z != 0:
            raise ValueError(f"block_z={block_z} must divide nz={nz}")
        bz = self.bz = block_z
        self.n_slabs = nz // bz
        # bf16 buffers need the doubled sublane tile (min tile (16, 128))
        sub = SUBLANE * max(1, 4 // self.dtype.itemsize)
        self.padded_shape = (
            nz + 2 * self.exchange_depth,
            round_up(ny + 2 * r, sub),
            round_up(nx + 2 * r, LANE),
        )
        self.r = r
        self.core_offsets = (self.exchange_depth, r, r)
        self.dt = float(dt)
        if k > 1:
            self.overlap_split = bool(
                overlap_split and self.sharded and nz > 2 * G
            )
        else:
            self.overlap_split = bool(
                overlap_split and self.sharded
                and self.n_slabs >= 3 and bz >= G
            )
        inv_dx = tuple(1.0 / spacing[i] for i in range(3))
        nu_scales = None
        if nu:
            nu_scales = tuple(
                float(nu) / (12.0 * spacing[i] * spacing[i])
                for i in range(3)
            )
        NZ, NY, NX = self.global_shape

        deep = k > 1

        def fill(t, base, zsrc):
            """Edge-replicate ghost/slack cells (WENO5resAdv_X.m:53):
            x/y from the static boundary columns; z keyed on *global*
            row indices, so the masks are nonempty only on the slabs
            (and shards) that actually touch a wall. ``zsrc``: ``None``
            skips the z fill (the window has no out-of-domain rows), a
            static ``(lo_src, hi_src)`` pair names the replica source
            rows at fixed slab-local positions (per-step schedule), and
            ``"dyn"`` indexes them dynamically from the traced window
            origin — the deep schedule's windows shift per in-block
            step, so the wall row has no fixed slab-local position
            (clipped: when the wall is outside this box the mask is
            empty and the clipped read is harmless)."""
            gx = lax.broadcasted_iota(jnp.int32, t.shape, 2) - r
            t = jnp.where(gx < 0, t[:, :, r: r + 1], t)
            t = jnp.where(gx >= NX, t[:, :, r + NX - 1: r + NX], t)
            gy = lax.broadcasted_iota(jnp.int32, t.shape, 1) - r
            t = jnp.where(gy < 0, t[:, r: r + 1], t)
            t = jnp.where(gy >= NY, t[:, r + NY - 1: r + NY], t)
            if zsrc is None:
                return t
            gz = lax.broadcasted_iota(jnp.int32, t.shape, 0) + base
            if zsrc == "dyn":
                n = t.shape[0]
                zero = jnp.asarray(0, jnp.int32)
                top = jnp.asarray(n - 1, jnp.int32)
                lo = lax.dynamic_slice_in_dim(
                    t, jnp.clip(-base, zero, top), 1, axis=0
                )
                hi = lax.dynamic_slice_in_dim(
                    t, jnp.clip(NZ - 1 - base, zero, top), 1, axis=0
                )
            else:
                lo_src, hi_src = zsrc
                lo = t[lo_src: lo_src + 1]
                hi = t[hi_src: hi_src + 1]
            t = jnp.where(gz < 0, lo, t)
            t = jnp.where(gz >= NZ, hi, t)
            return t

        def stage(u, vwin, a, b, w_out, base, zsrc, dtv):
            vc = vwin[r: r + w_out]
            vp, vm = _split(flux, vwin)
            Y = vwin.shape[1]
            rhs = -(
                _div_z(vp, vm, w_out, Y, inv_dx[0], variant, order, r, y0=0)
                + _div_roll(vp[r: r + w_out], vm[r: r + w_out], 1,
                            inv_dx[1], variant, order)
                + _div_roll(vp[r: r + w_out], vm[r: r + w_out], 2,
                            inv_dx[2], variant, order)
            )
            if nu_scales is not None:
                acc = None
                for axis in range(3):
                    for jj, c in enumerate(O4_COEFFS):
                        coef = jnp.asarray(c * nu_scales[axis], vwin.dtype)
                        if axis == 0:
                            term = vwin[r - 2 + jj: r - 2 + jj + w_out] * coef
                        else:
                            term = _shift(vc, jj - 2, axis) * coef
                        acc = term if acc is None else acc + term
                rhs = rhs + acc
            rk = b * (vc + dtv * rhs) if a == 0.0 else (
                a * u + b * (vc + dtv * rhs)
            )
            return fill(rk.astype(vwin.dtype), base, zsrc)

        (a1, b1), (a2, b2), (a3, b3) = _STAGES
        dt_f = self.dt  # python float: materialized in-kernel, not captured

        def step_fn(v, base_z):
            d = jnp.asarray(dt_f, v.dtype)
            # windows derive from the box (not self.bz): the deep
            # schedule's per-call block sizes all route through here.
            # Step-input z ghosts are stale in HBM (never rewritten):
            # re-synthesize at the global walls; shard-interior ghosts
            # hold fresh neighbor rows (refresh/exch) and pass through
            w = v.shape[0]
            bw = w - 2 * G
            v = fill(v, base_z, "dyn" if deep else (G, bw + G - 1))
            t1 = stage(None, v, a1, b1, w - 2 * r, base_z + r,
                       "dyn" if deep else (G - r, bw + 2 * r - 1), d)
            t2 = stage(v[2 * r: w - 2 * r], t1, a2, b2, w - 4 * r,
                       base_z + 2 * r,
                       "dyn" if deep else (G - 2 * r, bw + r - 1), d)
            # k=1: stage-3 output is exactly the core — no z-ghost rows
            # left; deep windows still carry ghost-region rows, which on
            # wall shards may sit outside the domain and need the
            # replica fill like every other stage
            return stage(v[G: w - G], t2, a3, b3, bw,
                         base_z + G, "dyn" if deep else None, d)

        if self.dtype == jnp.bfloat16:
            # bf16-storage/f32-compute (ISSUE 16): slab buffers and
            # wire bytes stay bf16; the WENO reconstruction and RK
            # stages run in f32 per slab
            inner = step_fn

            def step_fn(v, base_z):
                return inner(
                    v.astype(jnp.float32), base_z
                ).astype(jnp.bfloat16)

        self._step_fn = step_fn
        self._init_exchange(exchange, mesh_axis, num_shards)
        if self.sharded and self.exchange != "dma":
            self._build_sharded_calls()

    def _dma_block_viable(self, b: int) -> bool:
        row = _burg_row_bytes(
            self.interior_shape, self.dtype.itemsize, self.r
        )
        return _burg_live_rows(b, self.r, self.order) * row <= _VMEM_BUDGET

    @staticmethod
    def supported(interior_shape, dtype, order: int = 5) -> bool:
        r = HALO[order]
        row = _burg_row_bytes(interior_shape, jnp.dtype(dtype).itemsize, r)
        return _pick_bz_burgers(interior_shape[0], row, r, order) is not None

    @staticmethod
    def profitable(interior_shape, dtype, order: int = 5) -> bool:
        """The WENO stages are VPU-bound, so the 2r/bz redundant-compute
        tax must stay small for the traffic cut to matter: engage only
        with thick slabs or a one/two-slab z extent (where the per-call
        overhead saving dominates anyway). ``impl='pallas_slab'``
        overrides for measurement."""
        r = HALO[order]
        nz = interior_shape[0]
        row = _burg_row_bytes(interior_shape, jnp.dtype(dtype).itemsize, r)
        bz = _pick_bz_burgers(nz, row, r, order)
        if bz is None:
            return False
        return bz >= 6 * r or nz // bz <= 2

    def _pick_call_bz(self, extent: int) -> int:
        row = _burg_row_bytes(
            self.interior_shape, self.dtype.itemsize, self.r
        )
        b = _pick_bz_burgers(extent, row, self.r, self.order)
        if b is None:  # pragma: no cover - _VMEM_BUDGET admits bz=1
            raise ValueError(
                f"no viable slab block for a {extent}-row deep window"
            )
        return b

    def embed(self, u):
        d, r = self.exchange_depth, self.r
        nz, ny, nx = self.interior_shape
        pz, py, px = self.padded_shape
        return jnp.pad(
            u.astype(self.dtype),
            ((d, d), (r, py - ny - r), (r, px - nx - r)),
            mode="edge",
        )

    def extract(self, S):
        d, r = self.exchange_depth, self.r
        nz, ny, nx = self.interior_shape
        out = lax.slice(S, (d, r, r), (d + nz, r + ny, r + nx))
        return out.astype(self._storage)
