"""Shared execution driver for the fused per-stage steppers.

Both 3-D fused steppers (:mod:`fused_diffusion`, :mod:`fused_burgers`)
expose the same two execution modes over their per-stage kernels:

* :meth:`run` — fixed-count `lax.fori_loop` (the CUDA drivers'
  ``max_iters`` mode, ``MultiGPU/Diffusion3d_Baseline/main.c:189``);
* :meth:`run_to` — ``while t < t_end`` with the last step trimmed (the
  Burgers drivers' and MATLAB heat drivers' *native* mode,
  ``MultiGPU/Burgers3d_Baseline/main.c:190-317``, ``heat3d.m:48-77``),
  at full fused speed because dt enters the stage kernels as a runtime
  SMEM scalar.

Termination and trimming mirror ``SolverBase.advance_to`` exactly (same
eps guard) — defined ONCE here so step counts and trajectories cannot
desynchronize between the generic and fused paths or between solvers.

Subclasses provide ``embed``/``extract``, ``_step(S, T1, T2, dt_arr,
offsets=, refresh=, exch=)``, ``_dt_value(S)`` (a traced f32 scalar —
constant for diffusion, the CFL reduction for adaptive Burgers), and the
``sharded``/``overlap_split`` flags; ``needs_offsets`` marks steppers
whose kernels take a global-offset SMEM operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from multigpu_advectiondiffusion_tpu.parallel.halo import exchange_bytes

#: what a fused stepper declares of its plan (:meth:`FusedStepperBase.plan`)
PLAN_KEYS = ("hbm_bytes_per_step", "halo_bytes_per_step", "block",
             "y_dead_rows")


def chunk_counts(num_iters: int, steps_per_exchange: int):
    """``(full_blocks, remainder)`` of the communication-avoiding k-step
    chunk schedule: ``full_blocks`` whole blocks of ``steps_per_exchange``
    steps (one deep halo exchange each) plus one partial block of
    ``remainder`` steps (which still pays a full-depth exchange — the
    per-run cost of a non-multiple iteration count, not a correctness
    issue: every block starts from a fully refreshed buffer). The ONE
    definition both the slab stepper's run loop and the telemetry
    byte-accounting use, so "exchanges per run" cannot fork between the
    executed schedule and the reported one."""
    if steps_per_exchange < 1:
        raise ValueError(
            f"steps_per_exchange must be >= 1, got {steps_per_exchange}"
        )
    return num_iters // steps_per_exchange, num_iters % steps_per_exchange


def _with_repeats(fn, repeats: int):
    """Bind the static telemetry ``repeats`` hint into a refresh/exch
    closure headed into a ``fori_loop`` body (trace-once, execute-N)."""
    if fn is None:
        return None
    return lambda P: fn(P, repeats=repeats)


class FusedStepperBase:
    needs_offsets = False
    engaged_label = "fused-stage"  # what engaged_path()/PrintSummary report
    #: per-stage stencil radius h, queryable metadata for the static
    #: halo verifier (analysis/halo_verify.py). None -> equals the
    #: per-refresh ghost depth ``halo`` (true for the per-stage family:
    #: ghosts refresh every RK stage at exactly the stencil radius)
    stencil_radius = None
    #: RK stages recomputed per ghost refresh (the trapezoid factor):
    #: 1 for the per-stage family; the whole-step/slab rungs override
    fused_stages = 1
    # communication-avoiding chunk length: the per-stage kernels bake
    # one stencil-halo refresh per RK stage into their dataflow, so the
    # per-stage family serves k=1 only — the k-step deep-halo schedule
    # lives on the slab whole-run rung (ops/pallas/fused_slab_run.py),
    # which overrides this per instance. Dispatch validates the knob
    # against the engaged rung (models/base.py) and fails loudly rather
    # than silently running the per-step cadence.
    steps_per_exchange = 1

    #: in-kernel remote-DMA exchange contract (ROADMAP item 2), or
    #: ``None`` (every shipped rung today: the exchange is an XLA
    #: ppermute between compiled calls). A rung that moves its ghost
    #: rows inside the Pallas program via ``pltpu.make_async_remote_
    #: copy`` declares ``{"axis": 0, "window_rows": k*G,
    #: "buffers": >=2}`` and the static halo verifier proves the
    #: declaration against the exchange arithmetic BEFORE any hardware
    #: run — where a schedule mismatch stops being a hang and becomes
    #: silent corruption (a neighbor push landing over rows the
    #: consumer already read).
    remote_dma = None
    #: the engaged kernel block, ``(bz, by)`` / ``(bz,)``, where a
    #: stepper sets it
    block = None
    #: bytes its kernels DMA from and to HBM per step, where a stepper
    #: counts them from its block plan
    hbm_bytes_per_step = None
    #: y rows its kernels work that hold no cell (the plane height
    #: rounded up to whole y blocks), where a stepper rounds y
    y_dead_rows = None

    def plan(self) -> dict:
        """What the stepper declares of its plan, as ``engaged_path()``
        and the ``tpucfd.<op>[<stepper>]`` span report it:
        ``hbm_bytes_per_step``, ``halo_bytes_per_step``, ``block``
        (joined, as ``"8x8"``) and ``y_dead_rows``, each where
        declared."""
        out = {k: getattr(self, k) for k in PLAN_KEYS}
        if self.block is not None:
            out["block"] = "x".join(str(b) for b in self.block)
        return {k: v for k, v in out.items() if v is not None}

    @property
    def halo_bytes_per_step(self):
        """Bytes this shard sends through the halo exchange per step, or
        ``None`` unsharded: one exchange of the padded buffer on every
        sharded axis after each of the three SSP-RK3 stages, counted by
        ``parallel.halo.exchange_bytes`` as its
        ``halo.bytes_per_execution`` counter counts it."""
        if not self.sharded:
            return None
        depth = int(getattr(self, "exchange_depth", self.halo))
        item = jnp.dtype(self.dtype).itemsize
        return 3 * sum(
            exchange_bytes(self.padded_shape, ax, depth, item)
            for ax, (g, n) in enumerate(zip(self.global_shape,
                                            self.interior_shape))
            if g != n
        )

    def stencil_spec(self) -> dict:
        """Queryable stencil/halo metadata — the ``R = 3``-style radius
        constants promoted to a contract the static verifier
        (``analysis/halo_verify.py``) can prove consistent with the
        ghost/exchange/BlockSpec arithmetic. Keys: ``stage_radius`` (h,
        one stage's stencil reach), ``fused_stages`` (stages recomputed
        per ghost refresh), ``ghost_depth`` (rows refreshed per
        exchange site, ``>= fused_stages * h``), ``exchange_depth``
        (rows ppermuted per exchange, ``k * ghost_depth``; None for
        single-chip-only steppers), ``steps_per_exchange`` (k),
        ``remote_dma`` (the declared in-kernel exchange window, None
        while the exchange rides XLA collectives — see the class
        attribute), and the storage declaration (ISSUE 16):
        ``storage_dtype`` is the HBM-resident buffer dtype — the dtype
        every halo/DMA wire byte carries — and ``bytes_per_cell`` its
        itemsize, from which the verifier derives every declared byte
        count (f64-facing states run f32 buffers; ``precision='bf16'``
        runs bf16 buffers at 2 B/cell)."""
        h = int(self.stencil_radius or self.halo)
        buf = jnp.dtype(self.dtype)
        return {
            "kernel": self.engaged_label,
            "stage_radius": h,
            "fused_stages": int(self.fused_stages),
            "ghost_depth": int(self.halo),
            "exchange_depth": int(
                getattr(self, "exchange_depth", self.halo)
            ),
            "steps_per_exchange": int(
                getattr(self, "steps_per_exchange", 1) or 1
            ),
            "remote_dma": getattr(self, "remote_dma", None),
            "storage_dtype": str(buf),
            "bytes_per_cell": int(buf.itemsize),
        }

    def _dt_value(self, S):
        raise NotImplementedError

    def _check_sharded_args(self, refresh, offsets, exch):
        if not self.sharded:
            return
        if self.needs_offsets and offsets is None:
            raise ValueError("sharded fused stepper needs offsets")
        if self.overlap_split and exch is None:
            raise ValueError("split-overlap fused stepper needs exch")
        if not self.overlap_split and refresh is None:
            raise ValueError("sharded fused stepper needs a ghost refresh")
        if (
            self.overlap_split
            and refresh is None
            and any(
                g != l
                for g, l in zip(
                    self.global_shape[1:], self.interior_shape[1:]
                )
            )
        ):
            # pencil meshes: only the leading axis rides the exchanged
            # slabs — the other sharded axes' ghosts need the serialized
            # refresh, or they silently stay frozen at embed time
            raise ValueError(
                "pencil split-overlap stepper needs a ghost refresh for "
                "its non-leading sharded axes"
            )

    def run(self, u, t, num_iters: int, refresh=None, offsets=None,
            exch=None):
        """``num_iters`` fused SSP-RK3 steps; returns ``(u, t)``.

        Sharded mode (must run inside ``shard_map``): ``refresh``
        rewrites the padded buffers' sharded-axis ghosts after every RK
        stage — or, in split-overlap mode, ``exch`` produces the
        ``(lo, hi)`` exchanged z-slabs the stages consume as separate
        operands. ``offsets`` is this shard's int32 global-offset vector
        (consumed only by steppers with global wall masks).

        Steppers with ``_emit_max`` (adaptive Burgers) carry the
        stage-emitted ``max|f'(u)|`` scalar between steps instead of
        re-reading the state for the CFL reduction — ``_dt_from_max``
        must reproduce ``_dt_value`` exactly given the same max, so the
        two modes are trajectory-identical.
        """
        self._check_sharded_args(refresh, offsets, exch)
        S = self.embed(u)
        if refresh is not None:
            # non-split: full sharded-axis refresh of the fresh embed;
            # pencil split mode: the serialized (non-z) axes' refresh —
            # the z ghosts ride the exchanged-slab operands instead
            S = refresh(S)
        # exchanges inside the fori body trace ONCE but execute
        # num_iters times: bind the static count so the telemetry byte
        # counters report true bytes per compiled execution
        dt_of, step_of, m0 = self._loop_pieces(
            u, _with_repeats(refresh, num_iters), offsets,
            _with_repeats(exch, num_iters),
        )

        def body(i, carry):
            S, T1, T2, t, m = carry
            # named_scope: the fused step body shows as one labeled
            # region per rung in --trace captures
            with jax.named_scope(f"tpucfd.{self.engaged_label}"):
                dt = dt_of(S, m)
                S, T1, T2, m = step_of(S, T1, T2, dt, m)
            return S, T1, T2, t + dt.astype(t.dtype), m

        S, T1, T2, t, _ = lax.fori_loop(0, num_iters, body, (S, S, S, t, m0))
        return self.extract(S), t

    def run_to(self, u, t, t_end, refresh=None, offsets=None, exch=None):
        """March fused steps until ``t_end``; returns ``(u, t, steps)``.

        The reference drivers' native ``while (t < tEnd)`` mode at the
        fused stepper's speed, with the final step trimmed through the
        runtime SMEM dt scalar. (The halo telemetry counters record
        this mode's loop-resident exchange sites at ``repeats=1`` — a
        ``while_loop`` trip count is dynamic, so per-execution bytes
        are not statically knowable here; scale by the summary's step
        count instead.)
        """
        self._check_sharded_args(refresh, offsets, exch)
        S = self.embed(u)
        if refresh is not None:
            # non-split: full sharded-axis refresh of the fresh embed;
            # pencil split mode: the serialized (non-z) axes' refresh —
            # the z ghosts ride the exchanged-slab operands instead
            S = refresh(S)
        te = jnp.asarray(t_end, t.dtype)
        eps = 1e-12 * jnp.maximum(1.0, jnp.abs(te))
        dt_of, step_of, m0 = self._loop_pieces(u, refresh, offsets, exch)

        def cond(carry):
            return carry[3] < te - eps

        def body(carry):
            S, T1, T2, t, it, m = carry
            with jax.named_scope(f"tpucfd.{self.engaged_label}"):
                dt = jnp.minimum(dt_of(S, m), (te - t).astype(jnp.float32))
                S, T1, T2, m = step_of(S, T1, T2, dt, m)
            return S, T1, T2, t + dt.astype(t.dtype), it + 1, m

        S, T1, T2, t, steps, _ = lax.while_loop(
            cond, body, (S, S, S, t, jnp.zeros((), jnp.int32), m0)
        )
        return self.extract(S), t, steps

    def _loop_pieces(self, u, refresh, offsets, exch):
        """``(dt_of(S, m), step_of(S, T1, T2, dt, m), m0)`` — the single
        place the dt source is chosen, so run()/run_to() each have ONE
        loop body and the trim/termination semantics cannot fork between
        the read-back and emit-max modes. Non-emitting steppers carry a
        dummy scalar ``m``."""
        emit = getattr(self, "_emit_max", False)
        m0 = (
            self._wave_fn(u).astype(jnp.float32)
            if emit
            else jnp.zeros((), jnp.float32)
        )

        def dt_of(S, m):
            return (
                self._dt_from_max(m).astype(jnp.float32)
                if emit
                else self._dt_value(S)
            )

        def step_of(S, T1, T2, dt, m):
            out = self._step(S, T1, T2, dt.reshape(1), offsets=offsets,
                             refresh=refresh, exch=exch)
            if emit:
                return out
            S, T1, T2 = out
            return S, T1, T2, m

        return dt_of, step_of, m0
