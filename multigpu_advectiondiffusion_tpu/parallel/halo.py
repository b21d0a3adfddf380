"""Distributed halo exchange.

TPU-native replacement for the reference's five-stream MPI choreography
(``MultiGPU/Diffusion3d_Baseline/main.c:203-297``: pack kernel → DtH copy →
``MPI_Isend``/``Irecv`` → HtD copy → unpack kernel, per RK stage). Here the
whole exchange is two ``jax.lax.ppermute`` shifts per sharded axis inside
``shard_map`` — data moves HBM→ICI→HBM with XLA's async collective
scheduler providing the compute/communication overlap the reference
hand-builds with streams.

Two deliberate upgrades over the reference (SURVEY §2.1.5, §3.2):
  * the *state* ``u`` is exchanged before computing, not the RHS ``Lu``,
    which fixes the stale-``u`` z-halo defect of the multi-GPU Burgers;
  * any subset of axes may be decomposed (the reference supports only
    1-D slabs).
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from multigpu_advectiondiffusion_tpu.core.bc import Boundary, boundary_halo, pad_axis
from multigpu_advectiondiffusion_tpu.ops.stencils import Padder, slice_axis
from multigpu_advectiondiffusion_tpu.parallel.mesh import (
    Decomposition,
    axis_extent,
)


def exchange_spec() -> dict:
    """Queryable exchange metadata (the ``stencil_spec()`` discipline
    applied to the halo layer): one exchange site is two ``ppermute``
    shifts per sharded axis, and every traced site records itself
    through the listed counters — what the collective-schedule
    verifier's dynamic cross-check (``analysis/collective_verify.
    halo_counter_profile``) reads back out of per-rank streams to
    assert every rank traced the same exchange sites."""
    return {
        "ppermute_shifts": 2,
        "counters": (
            "halo.exchanges_traced",
            "halo.bytes_per_execution",
        ),
    }


def remote_dma_spec() -> dict:
    """Queryable metadata of the IN-KERNEL halo exchange (the
    ``exchange_spec()`` discipline for the remote-DMA rung,
    ``ops/pallas/fused_slab_run._whole_run_dma_kernel``): the slab
    rung's dma mode replaces the ppermute site entirely — ghost rows
    move over ICI via ``pltpu.make_async_remote_copy`` from inside the
    Pallas program — so its traffic is recorded through these counters
    and the ``halo:in_kernel`` event instead of the ppermute pair. The
    collective-schedule verifier's dynamic cross-check
    (``analysis/collective_verify.halo_counter_profile``) reads BOTH
    specs so a dma-mode stream profiles rank-uniform without a stale
    ppermute expectation."""
    return {
        "kernel": "fused-whole-run-slab",
        "counters": ("halo.dma_bytes_per_execution",),
        "events": (("halo", "in_kernel"),),
    }


def record_remote_dma(kernel: str, plane_shape, itemsize: int,
                      window_rows: int, blocks: int,
                      mesh_axis: str) -> None:
    """Telemetry record of one in-kernel remote-DMA exchange *site*.

    Runs at TRACE time (the slab rung's ``_run_dma`` executes under
    ``jit``/``shard_map``), mirroring :func:`_record_exchange`:
    ``bytes`` is the ICI payload per compiled execution — two
    ``window_rows``-deep slabs of the padded trailing plane (the rows
    actually pushed), times ``blocks`` (one exchange per k-step block,
    the initial embed push included: ``ceil(num_iters / k)`` pushes per
    run call). The ``halo:in_kernel`` event carries the same facts so
    ``tpucfd-trace``'s phase breakdown attributes the comm to the
    in-kernel path instead of reading zero exchanged bytes."""
    from multigpu_advectiondiffusion_tpu import telemetry

    sink = telemetry.get_sink()
    if not sink.active:
        return
    plane = int(itemsize)
    for n in plane_shape:
        plane *= int(n)
    nbytes = 2 * int(window_rows) * plane * int(blocks)
    sink.counter(
        "halo.dma_bytes_per_execution", nbytes,
        axis=0, mesh_axis=mesh_axis, window_rows=int(window_rows),
        blocks=int(blocks),
    )
    sink.event(
        "halo", "in_kernel",
        kernel=kernel, axis=0, mesh_axis=mesh_axis,
        depth=int(window_rows), blocks=int(blocks),
        bytes_per_execution=nbytes,
    )


def exchange_ghosts(
    u: jnp.ndarray,
    axis: int,
    halo: int,
    mesh_axis: str,
    num_shards: int,
    bc: Boundary,
    repeats: int = 1,
    wire_dtype=None,
):
    """The two ``ppermute`` shifts of a halo exchange, returned as the
    ``(lo, hi)`` ghost slabs without concatenating onto ``u``.

    Building block for the overlapped interior/boundary schedule
    (:func:`ops.stencils.split_axis_apply`): keeping the ghosts as
    separate values lets XLA schedule the collectives concurrently with
    interior compute that does not depend on them — the role of the
    reference's boundary-first five-stream choreography
    (``MultiGPU/Diffusion3d_Baseline/main.c:203-297``).

    ``halo`` is the exchange *depth* — the communication-avoiding k-step
    schedule passes ``k * G`` here (one deep exchange per k-step block)
    while the per-step schedules pass the stencil halo. ``repeats`` is a
    telemetry-only hint: how many times the compiled program executes
    this trace site per run (e.g. the loop trip count when the exchange
    sits inside a ``fori_loop`` body), so ``halo.bytes_per_execution``
    reports true bytes moved instead of one trace-site's worth.

    ``wire_dtype`` (ISSUE 16, the bf16-storage rung): when set to a
    narrower dtype than ``u``, ONLY the exchanged ghost slabs are
    down-cast before the ``ppermute`` and up-cast on receipt — the
    interior never leaves ``u.dtype``. BC ghosts on global-edge shards
    take the same round trip so edge shards see the same declared
    storage rounding as interior shards. Byte counters report the wire
    dtype's (halved) payload.
    """
    n_local = u.shape[axis]
    if n_local < halo:
        raise ValueError(
            f"shard of {n_local} cells can't serve a halo of {halo} on axis {axis}"
        )
    wire = None if wire_dtype is None else jnp.dtype(wire_dtype)
    if wire == jnp.dtype(u.dtype):
        wire = None
    _record_exchange(u, axis, halo, mesh_axis, repeats, wire_dtype=wire)
    fwd = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    bwd = [((i + 1) % num_shards, i) for i in range(num_shards)]
    # left halo <- left neighbor's rightmost cells; right halo <- right
    # neighbor's leftmost cells (tags 1/5 pair messaging in main.c:218,234).
    # named_scope: the two shifts appear as one labeled region per axis
    # in --trace captures, under the enclosing stepper span
    with jax.named_scope(f"tpucfd.halo_exchange_ax{axis}"):
        send_hi = slice_axis(u, axis, n_local - halo, n_local)
        send_lo = slice_axis(u, axis, 0, halo)
        if wire is not None:
            send_hi = send_hi.astype(wire)
            send_lo = send_lo.astype(wire)
        from_left = lax.ppermute(send_hi, mesh_axis, fwd)
        from_right = lax.ppermute(send_lo, mesh_axis, bwd)
        if bc.kind != "periodic":
            idx = lax.axis_index(mesh_axis)
            bc_left = boundary_halo(u, axis, halo, bc, "left")
            bc_right = boundary_halo(u, axis, halo, bc, "right")
            if wire is not None:
                bc_left = bc_left.astype(wire)
                bc_right = bc_right.astype(wire)
            from_left = jnp.where(idx == 0, bc_left, from_left)
            from_right = jnp.where(
                idx == num_shards - 1, bc_right, from_right
            )
        if wire is not None:
            from_left = from_left.astype(u.dtype)
            from_right = from_right.astype(u.dtype)
        return from_left, from_right


def exchange_bytes(shape, axis: int, halo: int, itemsize: int) -> int:
    """Bytes one exchange on ``axis`` hands to ``ppermute`` from a shard
    of ``shape``: two ``halo``-deep slabs (lo + hi), one to each
    neighbour."""
    return 2 * halo * math.prod(shape) // shape[axis] * itemsize


def _record_exchange(
    u, axis: int, halo: int, mesh_axis: str, repeats: int = 1,
    wire_dtype=None,
) -> None:
    """Telemetry record of one halo exchange *site*.

    Runs at TRACE time (``exchange_ghosts`` executes under ``jit``).
    ``bytes`` is the ICI/DCN payload of the site per compiled execution:
    two ``halo``-deep slabs (lo + hi) of the shard-local block, times
    ``repeats`` — the caller's static count of how often the site runs
    inside one execution (loop trip count for exchanges traced inside a
    ``fori_loop`` body, number of k-step blocks for the deep
    communication-avoiding schedule; 1 for straight-line sites). Sites
    in dynamic-trip loops (``while_loop`` run_to) cannot know their
    count and record ``repeats=1`` — the stream still carries the depth
    so a consumer can scale by the summary's step count."""
    from multigpu_advectiondiffusion_tpu import telemetry

    sink = telemetry.get_sink()
    if not sink.active:
        return
    # wire_dtype: the bf16-storage rung moves ghost slabs down-cast on
    # the wire — the payload is the wire dtype's itemsize, not the
    # resident block's (ISSUE 16)
    item = jnp.dtype(wire_dtype if wire_dtype is not None
                     else u.dtype).itemsize
    nbytes = exchange_bytes(u.shape, axis, halo, item)
    sink.counter(
        "halo.exchanges_traced", 1, axis=axis, mesh_axis=mesh_axis
    )
    sink.counter(
        "halo.bytes_per_execution", int(repeats) * nbytes,
        axis=axis, mesh_axis=mesh_axis, halo=halo, repeats=int(repeats),
    )


def exchange_axis(
    u: jnp.ndarray,
    axis: int,
    halo: int,
    mesh_axis: str,
    num_shards: int,
    bc: Boundary,
    wire_dtype=None,
) -> jnp.ndarray:
    """Pad one axis of a shard-local block with neighbor (or BC) ghost cells.

    Must run inside ``shard_map`` with ``mesh_axis`` in scope. Uses cyclic
    permutes; for non-periodic axes the global-edge shards overwrite the
    wrapped block with BC ghosts (Dirichlet fill or edge replication).
    """
    from_left, from_right = exchange_ghosts(
        u, axis, halo, mesh_axis, num_shards, bc, wire_dtype=wire_dtype
    )
    return jnp.concatenate([from_left, u, from_right], axis=axis)


def make_padder(
    decomp: Decomposition,
    mesh_axis_sizes: Dict[str, int],
    bcs: Sequence[Boundary],
    wire_dtype=None,
) -> Padder:
    """Padder closure for use inside ``shard_map``: ppermute on sharded
    axes, plain BC padding on local axes. ``wire_dtype`` down-casts only
    the exchanged ghost slabs on the wire (see
    :func:`exchange_ghosts`)."""

    def padder(u: jnp.ndarray, axis: int, halo: int) -> jnp.ndarray:
        name = decomp.mesh_axis(axis)
        if name is None or axis_extent(mesh_axis_sizes, name) == 1:
            return pad_axis(u, axis, halo, bcs[axis])
        return exchange_axis(
            u, axis, halo, name, axis_extent(mesh_axis_sizes, name),
            bcs[axis], wire_dtype=wire_dtype,
        )

    return padder


def make_ghost_fn(
    decomp: Decomposition,
    mesh_axis_sizes: Dict[str, int],
    bcs: Sequence[Boundary],
    wire_dtype=None,
):
    """Ghost-slab closure for the overlapped schedule: returns
    ``(lo, hi)`` for sharded axes, ``None`` for local axes (whose ghosts
    are plain BC padding with nothing to overlap). ``wire_dtype``
    down-casts only the exchanged slabs on the wire (see
    :func:`exchange_ghosts`)."""

    def ghost_fn(u: jnp.ndarray, axis: int, halo: int):
        name = decomp.mesh_axis(axis)
        if name is None or axis_extent(mesh_axis_sizes, name) == 1:
            return None
        return exchange_ghosts(
            u, axis, halo, name, axis_extent(mesh_axis_sizes, name),
            bcs[axis], wire_dtype=wire_dtype,
        )

    return ghost_fn


def make_ghost_refresh(
    decomp: Decomposition,
    mesh_axis_sizes: Dict[str, int],
    bcs: Sequence[Boundary],
    halo: int,
    interior_local: Sequence[int],
    core_offsets: Sequence[int] | None = None,
):
    """Refresh the ghost slabs of a *persistent padded* buffer in place.

    The fused Pallas steppers keep the state in a padded layout whose
    ghost cells are written once and treated as frozen
    (:mod:`ops.pallas.fused_diffusion`). Under a mesh the ghosts on
    sharded axes are neighbor data and go stale after every RK stage —
    this closure re-runs the ``ppermute`` exchange on the padded buffer's
    core window and writes the fresh slabs back into the ghost rows
    (``lax.dynamic_update_slice_in_dim``, in-place under XLA). This is
    the per-stage ghost rewrite of the reference's MPI loop
    (``MultiGPU/Diffusion3d_Baseline/main.c:203-297``) applied to the
    *tuned* kernel's persistent buffer. Must run inside ``shard_map``.

    ``interior_local`` is the shard-local interior shape; axes whose mesh
    extent is 1 (or unsharded) keep their frozen BC ghosts untouched.
    ``core_offsets`` gives the interior origin in the padded layout per
    axis (default ``halo`` on every axis — steppers with alignment
    margins, e.g. the fused Burgers y axis, sit deeper).

    ``halo`` is the refresh *depth*: the per-step schedules pass the
    stepper's stencil halo, the communication-avoiding k-step schedule
    passes its deep ``k * G`` exchange depth (with ``core_offsets``
    sitting ``k * G`` in). The closure takes an optional ``repeats``
    telemetry hint (see :func:`exchange_ghosts`) so loop-resident
    refreshes report true bytes per compiled execution.
    """
    offs = (
        tuple(core_offsets)
        if core_offsets is not None
        else (halo,) * len(interior_local)
    )
    sharded = [
        (ax, decomp.mesh_axis(ax))
        for ax in range(len(interior_local))
        if decomp.mesh_axis(ax) is not None
        and axis_extent(mesh_axis_sizes, decomp.mesh_axis(ax)) > 1
    ]

    def refresh(P: jnp.ndarray, repeats: int = 1) -> jnp.ndarray:
        for ax, name in sharded:
            n_loc = interior_local[ax]
            off = offs[ax]
            core = slice_axis(P, ax, off, off + n_loc)
            lo, hi = exchange_ghosts(
                core, ax, halo, name, axis_extent(mesh_axis_sizes, name),
                bcs[ax], repeats=repeats,
            )
            P = lax.dynamic_update_slice_in_dim(P, lo, off - halo, axis=ax)
            P = lax.dynamic_update_slice_in_dim(P, hi, off + n_loc, axis=ax)
        return P

    return refresh


def axis_offsets(decomp: Decomposition, local_shape: Sequence[int]):
    """Global index offset of this shard's block, per array axis.

    Inside ``shard_map``: ``offset = axis_index * local_n``
    (the analog of ``k + rank*_Nz`` in ``Tools.c:192``).
    """
    offs = []
    for ax in range(len(local_shape)):
        name = decomp.mesh_axis(ax)
        if name is None:
            offs.append(0)
        else:
            offs.append(lax.axis_index(name) * local_shape[ax])
    return offs
