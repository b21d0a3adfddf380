"""Shared solver machinery: one step definition, two execution worlds.

Each concrete solver implements :meth:`build_local` — the shard-local
physics (RHS, dt rule, post-step fix-up) expressed against a
:class:`StepContext`. The base class then runs that same definition either

* single-device: plain ``jit``, ghost cells from BC padding; or
* sharded: ``jit(shard_map(...))`` over a ``jax.sharding.Mesh``, ghost
  cells from ``ppermute`` halo exchanges, reductions via ``lax.pmax``.

This replaces the reference's split between the SingleGPU drivers and the
MPI drivers (``SingleGPU/*/main.cpp`` vs ``MultiGPU/*/main.c``), which
duplicate the whole time loop to add communication. The entire time loop
(``lax.fori_loop`` / ``lax.while_loop``) lives *inside* one jit — and, when
sharded, inside one ``shard_map`` — so XLA sees the full program and can
overlap halo collectives with interior compute (the reference builds this
overlap by hand with five CUDA streams, ``main.c:189-303``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from multigpu_advectiondiffusion_tpu.core.bc import Boundary, pad_axis
from multigpu_advectiondiffusion_tpu.core.dtypes import canonicalize
from multigpu_advectiondiffusion_tpu.core.grid import Grid
from multigpu_advectiondiffusion_tpu.models.state import (
    EnsembleState,
    SolverState,
)
from multigpu_advectiondiffusion_tpu.ops.pallas.stepper_base import PLAN_KEYS
from multigpu_advectiondiffusion_tpu.ops.stencils import Padder
from multigpu_advectiondiffusion_tpu.ops.stencils import slice_axis
from multigpu_advectiondiffusion_tpu.parallel.halo import (
    axis_offsets,
    exchange_ghosts,
    make_ghost_fn,
    make_ghost_refresh,
    make_padder,
)
from multigpu_advectiondiffusion_tpu.parallel.mesh import (
    Decomposition,
    axis_extent,
    reduce_axis_names,
    shard_map,
)
from multigpu_advectiondiffusion_tpu.timestepping.integrators import INTEGRATORS
from multigpu_advectiondiffusion_tpu.utils.ic import initial_condition
from multigpu_advectiondiffusion_tpu.utils.profiling import (
    EXECUTE_SPAN,
    RESOLVE_SPAN,
    STATE_SPAN,
    annotate,
    dispatch,
)


def _consume_donated(*arrays) -> None:
    """Enforce donation semantics on EVERY backend (ISSUE 19).

    After a donated dispatch the input state is dead: XLA:TPU/GPU alias
    its buffer into the output (the in-place HBM update donation buys),
    but XLA:CPU implements no donation and would silently keep the
    input alive — a reuse-after-donate bug would then pass the CPU
    tier-1 suite and corrupt data on the accelerator. The dispatch
    layer therefore deletes the donated operands itself, so ANY later
    use raises jax's loud "Array has been deleted" RuntimeError
    identically on every backend. PJRT defers the actual free until
    in-flight computations drop their usage holds, so deleting right
    after the (async) dispatch is safe."""
    for arr in arrays:
        delete = getattr(arr, "delete", None)
        if delete is None:
            continue  # tracer/numpy operand: nothing to consume
        is_deleted = getattr(arr, "is_deleted", None)
        if is_deleted is not None and is_deleted():
            continue
        delete()


@dataclasses.dataclass
class StepContext:
    """What the shard-local physics may depend on."""

    padder: Padder
    offsets: Sequence  # global index offset of this block, per axis
    local_shape: Tuple[int, ...]
    global_shape: Tuple[int, ...]
    reduce_max: Callable[[jnp.ndarray], jnp.ndarray]
    # (lo, hi) ghost slabs for sharded axes (None per-axis when local;
    # None entirely when unsharded) — enables the overlapped
    # interior/boundary schedule (ops.stencils.split_axis_apply)
    ghost_fn: Optional[Callable] = None


@dataclasses.dataclass
class LocalPhysics:
    """Product of :meth:`SolverBase.build_local`."""

    rhs: Callable[[jnp.ndarray], jnp.ndarray]
    dt_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None  # None -> static
    static_dt: Optional[float] = None
    post: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None


class SolverBase:
    # the innermost open tpucfd.dispatch span (_dispatch), which
    # _compiled tells of each program it hands out
    _open_dispatch = None

    def __init__(self, cfg, mesh=None, decomp: Decomposition | None = None):
        self.cfg = cfg
        self.mesh = mesh
        self.decomp = decomp
        if mesh is not None and decomp is None:
            self.decomp = Decomposition.slab(tuple(mesh.shape)[0])
        if mesh is not None:
            self.decomp.validate(mesh, cfg.grid.shape)
        self.dtype = canonicalize(cfg.dtype)
        self._cache = {}
        # kernel-ladder degradation bookkeeping: the impl the user asked
        # for (engaged_path reports it even after a downgrade swapped
        # cfg.impl) and the downgrade events themselves
        self._requested_impl = getattr(cfg, "impl", "xla")
        self._degrade_events = []
        # measured introspection (telemetry/xprof.py): one ExecRecord
        # per compiled executable, appended at first call — survives
        # _cache.clear() (records are history, not dispatch state)
        self._xla_records = []
        self._tuned = None
        if self._requested_impl == "auto":
            # measured dispatch: the tuner resolves (rung, k) per
            # (solver, shape, dtype, mesh, backend) key from its
            # persisted decision cache, measuring candidates on a miss
            # when tuning is enabled (tuning.configure / --tune); the
            # concrete rung replaces cfg.impl before any dispatch runs
            from multigpu_advectiondiffusion_tpu import tuning

            decision = tuning.resolve(type(self), cfg, mesh, self.decomp)
            self._tuned = decision
            self.cfg = cfg = dataclasses.replace(
                cfg,
                impl=decision["impl"],
                steps_per_exchange=decision.get("steps_per_exchange", 1),
                exchange=decision.get("exchange", "collective"),
            )
        self._validate_steps_per_exchange()
        self._validate_exchange()
        self._validate_precision()

    def _validate_steps_per_exchange(self) -> None:
        """Gate the communication-avoiding chunk knob the way impl
        strings are gated (``ops.IMPLS``): a config that cannot honor
        ``steps_per_exchange > 1`` fails at construction instead of
        silently running the per-step exchange cadence. Deeper
        eligibility (VMEM fit, dtype, adaptive dt, shard thickness) is
        enforced at dispatch by ``_select_slab``, which raises rather
        than declines when k > 1."""
        k = int(getattr(self.cfg, "steps_per_exchange", 1) or 1)
        if k == 1:
            return
        if self.grid.ndim != 3:
            raise ValueError(
                "steps_per_exchange > 1 rides the 3-D slab stepper only"
            )
        if self.mesh is None:
            raise ValueError(
                "steps_per_exchange > 1 needs a device mesh — it trades "
                "deeper halo exchanges for fewer of them"
            )
        if any(ax != 0 for ax in self._sharded_axes()):
            raise ValueError(
                "steps_per_exchange > 1 serves z-slab decompositions only"
            )
        if self.cfg.impl not in ("pallas", "pallas_slab"):
            raise ValueError(
                f"steps_per_exchange={k} needs the sharded slab rung "
                f"(impl='pallas'/'pallas_slab'/'auto'), not "
                f"impl={self.cfg.impl!r}"
            )

    def _exchange_mode(self) -> str:
        return str(getattr(self.cfg, "exchange", "collective")
                   or "collective")

    def _validate_exchange(self) -> None:
        """Gate the halo-exchange transport knob the way impl strings
        and ``steps_per_exchange`` are gated: a config that cannot
        honor ``exchange='dma'`` (the in-kernel remote-DMA whole-run
        rung) fails at construction instead of silently running the
        XLA collective cadence. Backend/VMEM eligibility is enforced
        at dispatch by ``_select_slab``, which raises rather than
        declines when dma is requested."""
        if self._exchange_mode() != "dma":
            return
        if self.grid.ndim != 3:
            raise ValueError(
                "exchange='dma' rides the 3-D sharded slab rung only"
            )
        if self.mesh is None:
            raise ValueError(
                "exchange='dma' pushes ghost rows between z neighbors "
                "— it needs a device mesh (an unsharded run has no "
                "neighbor to push to)"
            )
        if any(ax != 0 for ax in self._sharded_axes()):
            raise ValueError(
                "exchange='dma' serves z-slab decompositions only"
            )
        if self.cfg.impl not in ("pallas", "pallas_slab"):
            raise ValueError(
                "exchange='dma' needs the sharded slab rung "
                "(impl='pallas'/'pallas_slab'/'auto'), not "
                f"impl={self.cfg.impl!r}"
            )
        if getattr(self.cfg, "overlap", None) == "split":
            raise ValueError(
                "exchange='dma' replaces the XLA exchange entirely — "
                "the split-overlap schedule does not compose with it "
                "(drop overlap='split')"
            )
        name = self.decomp.mesh_axis(0)
        if not isinstance(name, str):
            raise ValueError(
                "exchange='dma' cannot ride a compound (multihost) "
                "mesh axis — remote DMA moves over ICI, not DCN"
            )
        if len(dict(self.mesh.shape)) != 1:
            raise ValueError(
                "exchange='dma' serves single-axis z-slab meshes: the "
                "remote-DMA ring addresses logical device ids along "
                "ONE mesh axis"
            )
        if jax.process_count() > 1:
            raise ValueError(
                "exchange='dma' is single-process (ICI) only — "
                "multihost z layouts keep the collective exchange"
            )

    def _precision_mode(self) -> str:
        return str(getattr(self.cfg, "precision", "native") or "native")

    def _validate_precision(self) -> None:
        """Gate the low-precision-storage knob the way impl strings and
        ``exchange`` are gated: a config that cannot honor
        ``precision='bf16'`` (the bf16-storage / f32-compute bandwidth
        rung, ISSUE 16) fails at construction instead of silently
        running native storage. The rung stores the run-resident state
        (HBM buffers, halo/remote-DMA wires) in bfloat16 while every
        stencil tap and RK stage computes in float32; the generic-XLA
        loop additionally carries a bf16 compensation term (hi/lo
        split) so long-horizon error stays bounded
        (``core.dtypes.bf16_carry_enabled``)."""
        from multigpu_advectiondiffusion_tpu.core.dtypes import (
            bf16_carry_enabled,
        )

        mode = self._precision_mode()
        if mode == "native":
            self._bf16_carry = False
            return
        if mode != "bf16":
            raise ValueError(
                f"unknown precision {mode!r}; use 'native' or 'bf16'"
            )
        if self.dtype == jnp.bfloat16:
            raise ValueError(
                "precision='bf16' with dtype='bfloat16' is redundant — "
                "the knob downcasts a float32 compute state to bf16 "
                "storage; the all-bf16 compute experiment remains the "
                "separate dtype='bfloat16' opt-in"
            )
        if self.dtype != jnp.float32:
            raise ValueError(
                "precision='bf16' stores a float32 compute state in "
                f"bfloat16; cfg.dtype must be float32, got {self.dtype}"
            )
        self._bf16_carry = bf16_carry_enabled()
        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.event(
            "precision", "engage",
            storage_dtype="bfloat16", compute_dtype="float32",
            carry=bool(self._bf16_carry),
        )

    @property
    def storage_dtype(self):
        """The dtype the run-resident state occupies in HBM and on
        every halo/remote-DMA wire under the engaged configuration —
        the itemsize the cost model prices HBM passes with and the
        tuner/AOT keys fingerprint. Equals :attr:`dtype` except under
        ``precision='bf16'``."""
        if self._precision_mode() == "bf16":
            return jnp.dtype(jnp.bfloat16)
        return self.dtype

    # -- bf16-storage generic-loop plumbing (precision='bf16') -------- #
    def _bf16_pack(self, u):
        """Facing f32 state -> the loop-resident bf16 representation:
        ``(hi,)`` (plain downcast) or ``(hi, lo)`` with the Kahan-style
        compensation term ``lo = bf16(u - f32(hi))`` when the carry is
        armed. ``bf16(u) == hi`` exactly, so a wire transfer of the
        reconstructed state truncated to bf16 transmits precisely
        ``hi`` — the carry never doubles halo bytes."""
        hi = u.astype(jnp.bfloat16)
        if not self._bf16_carry:
            return (hi,)
        lo = (u - hi.astype(u.dtype)).astype(jnp.bfloat16)
        return (hi, lo)

    def _bf16_unpack(self, packed):
        """Inverse of :meth:`_bf16_pack`: reconstruct the f32 compute
        state from the stored representation (``f32(hi) [+ f32(lo)]``).
        Without the carry, small-dt increments round away entirely at
        the bf16 ulp — the stall the compensation exists to prevent
        (tests/test_precision.py proves both directions)."""
        u = packed[0].astype(self.dtype)
        if len(packed) > 1:
            u = u + packed[1].astype(self.dtype)
        return u

    @staticmethod
    def _dma_backend_ok() -> bool:
        """Whether this process can execute the in-kernel remote-DMA
        program: the Mosaic TPU target, or the CPU backend's interpret
        simulator (which models the remote copies — the tier-1 test
        surface). GPU has neither."""
        from multigpu_advectiondiffusion_tpu.ops.pallas.laplacian import (
            interpret_mode,
        )

        return jax.default_backend() == "tpu" or interpret_mode()

    def _dma_stepper_kwargs(self) -> dict:
        """Constructor kwargs arming a slab stepper's in-kernel
        exchange: the (validated, single, string) z mesh axis and its
        shard count."""
        sizes = dict(self.mesh.shape)
        name = self.decomp.mesh_axis(0)
        return {
            "exchange": "dma",
            "mesh_axis": name,
            "num_shards": axis_extent(sizes, name),
        }

    # ------------------------------------------------------------------ #
    # To be provided by subclasses
    # ------------------------------------------------------------------ #
    def build_local(self, ctx: StepContext, overrides=None) -> LocalPhysics:
        """Shard-local physics. ``overrides`` (ensemble mode only) maps
        member-varying scalar names — the keys of
        :meth:`ensemble_operands` — to *traced* 0-d values that must
        enter the step as operands, not closure constants: the batched
        dispatch vmaps one compiled program over the member axis, so a
        per-member diffusivity/CFL arrives here as a traced scalar."""
        raise NotImplementedError

    def ensemble_operands(self) -> dict:
        """The member-varying scalar contract of the batched ensemble
        engine: ``{name: default}`` for every scalar
        :meth:`build_local` can take as a traced override. The base
        class supports none — ensembles of such solvers vary initial
        conditions only."""
        return {}

    def diagnostics_spec(self) -> dict:
        """Per-solver in-situ physics-diagnostics contract
        (``diagnostics/physics.py``). Optional keys:

        * ``observables`` — extra :class:`~.diagnostics.physics.
          Observable` entries fused into the sentinel's jitted probe
          beyond the standard suite (budgets/TV/spectral tail);
        * ``rules`` — :class:`~.diagnostics.physics.ViolationRule`
          tolerance checks of the probed stats against the run-initial
          baseline (max-principle, TV-monotonicity, ...);
        * ``meta`` — fields riding every ``phys:diag`` event (e.g. the
          analytic decay rate the trace analyzer fits against).

        The base class registers nothing: every solver still gets the
        standard suite; overrides add what their physics guarantees."""
        return {}

    def stencil_spec(self) -> dict:
        """Family-level stencil metadata — part of the solver-plugin
        registration contract (``models/registry.
        REQUIRED_SOLVER_CONTRACT``; the steppers' per-instance
        ``stencil_spec`` remains the halo verifier's per-rung source).
        Expected keys: ``stage_radius`` (the max of the advective and
        diffusive tap reaches) plus per-term radii. The base class
        declares nothing — REGISTERED solvers must override (enforced
        at ``register_model`` and by the ``registry-completeness``
        lint rule); ad-hoc unregistered subclasses may ignore it."""
        return {}

    def cfl_rule(self) -> dict:
        """Queryable time-step contract — part of the registration
        contract: what rule produced this solver's dt (``kind`` plus
        ``dt``/``cfl``/``safety`` as applicable). Base declares
        nothing; registered solvers must override (same enforcement as
        :meth:`stencil_spec`)."""
        return {}

    # ------------------------------------------------------------------ #
    # Config plumbing
    # ------------------------------------------------------------------ #
    @property
    def grid(self) -> Grid:
        return self.cfg.grid

    @property
    def bcs(self) -> Tuple[Boundary, ...]:
        spec = self.cfg.bc
        if isinstance(spec, (list, tuple)):
            out = tuple(Boundary.parse(s) for s in spec)
            if len(out) != self.grid.ndim:
                raise ValueError("per-axis bc list rank mismatch")
            return out
        return (Boundary.parse(spec),) * self.grid.ndim

    @property
    def integrator(self):
        return INTEGRATORS[self.cfg.integrator]

    def sharding(self):
        if self.mesh is None:
            return None
        return self.decomp.sharding(self.mesh, self.grid.ndim)

    def mesh_reduce_max(self):
        """Cross-device max reduction for this solver's mesh (identity
        when unsharded / all extents 1). Must run inside ``shard_map``.
        The pmax axis-name set comes from the ONE
        ``parallel.mesh.reduce_axis_names`` source — the generic step,
        the fused steppers' adaptive dt AND the static sharding pass
        (``analysis/collective_verify``) must agree exactly."""
        if self.mesh is None:
            return None
        names = reduce_axis_names(self.decomp, self.mesh.shape)
        if not names:
            return None
        return lambda x: lax.pmax(x, names)

    def mesh_reduce_sum(self):
        """Cross-device sum reduction over the same axis-name set as
        :meth:`mesh_reduce_max` (the physics probe's mass/L2 integrals
        must span exactly the shards the divergence probe spans). Must
        run inside ``shard_map``; ``None`` when unsharded."""
        if self.mesh is None:
            return None
        names = reduce_axis_names(self.decomp, self.mesh.shape)
        if not names:
            return None
        return lambda x: lax.psum(x, names)

    # ------------------------------------------------------------------ #
    # State creation
    # ------------------------------------------------------------------ #
    def ic_spec(self):
        """IC name and default params; subclasses override to thread config
        (e.g. diffusivity/t0) into parameterized ICs."""
        return self.cfg.ic, {}

    def initial_state(self, t: float | None = None) -> SolverState:
        name, defaults = self.ic_spec()
        params = {**defaults, **dict(self.cfg.ic_params)}
        u0 = initial_condition(name, self.grid, dtype=self.dtype, **params)
        if self.mesh is not None:
            sharding = self.sharding()
            if jax.process_count() > 1:
                # multi-process: device_put onto a global sharding runs a
                # consistency collective some backends (CPU) can't host —
                # assemble the global array from each process's
                # addressable shards instead (the IC is computed globally
                # on every host, as the reference computes its IC on
                # every rank, main.c:112-130)
                u0 = jax.make_array_from_callback(
                    u0.shape, sharding, lambda idx: u0[idx]
                )
            else:
                u0 = jax.device_put(u0, sharding)
        t0 = t if t is not None else getattr(self.cfg, "t0", 0.0)
        return SolverState.create(u0, t=t0)

    # ------------------------------------------------------------------ #
    # Shard-local step assembly
    # ------------------------------------------------------------------ #
    def _context(self, u: jnp.ndarray) -> StepContext:
        gshape = self.grid.shape
        if self.mesh is None:
            return StepContext(
                padder=lambda x, axis, halo: pad_axis(x, axis, halo, self.bcs[axis]),
                offsets=[0] * self.grid.ndim,
                local_shape=gshape,
                global_shape=gshape,
                reduce_max=lambda x: x,
            )
        sizes = dict(self.mesh.shape)
        reduce = self.mesh_reduce_max()
        lshape = self.decomp.local_shape(self.mesh, gshape)
        # precision='bf16': ghost slabs cross the wire at the declared
        # storage dtype (half the bytes); the interior stays f32
        wire = (
            jnp.bfloat16 if self._precision_mode() == "bf16" else None
        )
        return StepContext(
            padder=make_padder(self.decomp, sizes, self.bcs,
                               wire_dtype=wire),
            offsets=axis_offsets(self.decomp, lshape),
            local_shape=lshape,
            global_shape=gshape,
            reduce_max=reduce if reduce is not None else (lambda x: x),
            ghost_fn=make_ghost_fn(self.decomp, sizes, self.bcs,
                                   wire_dtype=wire),
        )

    def _local_step(self, u, t, t_end=None, overrides=None):
        """One time step on a (possibly shard-local) block.
        ``overrides`` threads member-varying traced scalars into
        :meth:`build_local` (ensemble dispatch only)."""
        # named_scope: the generic step shows up as one labeled region
        # in --trace captures, matching the fused steppers' spans
        with jax.named_scope("tpucfd.step"):
            phys = self.build_local(self._context(u), overrides=overrides)
            dt = phys.dt_fn(u) if phys.dt_fn is not None else phys.static_dt
            if t_end is not None:
                dt = jnp.minimum(dt, t_end - t)
            dt = jnp.asarray(dt, dtype=t.dtype)
            u = self.integrator(phys.rhs, u, dt.astype(u.dtype), phys.post)
            return u, t + dt

    # ------------------------------------------------------------------ #
    # Execution: wrap a (u, t) -> (u, t) block program for this world
    # ------------------------------------------------------------------ #
    def _wrap(self, fn, n_out_scalars: int = 1, n_in_scalars: int = 1,
              check: bool | None = None):
        """Jit a block program ``(u, *scalars) -> (u, *scalars)``;
        sharded, the field follows the decomposition spec and scalars
        are replicated.

        The replication/vma checker stays on except for Pallas-flavored
        configs (whose ``pallas_call`` outputs carry no vma typing) and
        blocks that force ``check=False`` — jax ships no replication
        rule for ``lax.while_loop``, so the generic ``advance_to`` loop
        cannot be checked on any impl."""
        from multigpu_advectiondiffusion_tpu.ops import is_pallas_impl

        # opt-in checkify sanitizer (--checkify, analysis/sanitizer.py):
        # the block program compiles with NaN/div0/OOB checks discharged
        # in, and a trip surfaces as SanitizerError through the
        # supervisor's existing rollback path. Single-device only —
        # shard_map carries no checkify rule, so a meshed config fails
        # loudly here (pin semantics) instead of silently unchecked.
        from multigpu_advectiondiffusion_tpu.analysis import sanitizer

        if sanitizer.enabled():
            if self.mesh is not None:
                raise ValueError(
                    "--checkify instruments single-device programs; "
                    "shard_map carries no checkify rule — run unsharded "
                    "or drop --checkify"
                )
            return sanitizer.checked_jit(fn)
        if self.mesh is None:
            return jax.jit(fn)
        if check is None:
            check = not is_pallas_impl(getattr(self.cfg, "impl", ""))
        spec = self.decomp.partition_spec(self.grid.ndim)
        return jax.jit(
            shard_map(
                fn,
                mesh=self.mesh,
                in_specs=(spec,) + (P(),) * n_in_scalars,
                out_specs=(spec,) + (P(),) * n_out_scalars,
                check=check,
            )
        )

    def _compiled(self, key, builder, steps=None, donate=False):
        """One dispatch-cache entry per program. ``steps`` is the
        iteration count the program bakes in (None for data-dependent
        trip counts, e.g. the t_end while_loop) — threaded to the
        measured-introspection layer so the executable's XLA-reported
        bytes/FLOPs read against the per-step cost model. ``donate``
        marks a program compiled with its state operand donated (ISSUE
        19) — a DIFFERENT executable than the undonated build, so the
        bit separates the local cache entry and rides the AOT key."""
        if donate:
            key = (*key, "donated") if isinstance(key, tuple) else (
                key, "donated"
            )
        if self._open_dispatch is not None:
            self._open_dispatch.program(built=key not in self._cache)
        if key not in self._cache:
            from multigpu_advectiondiffusion_tpu import telemetry
            from multigpu_advectiondiffusion_tpu.telemetry import xprof

            sink = telemetry.get_sink()
            if sink.active:
                # rung-selection record: one event per program the
                # dispatch layer builds (the compile itself happens at
                # first call, inside the caller's span — where the
                # xprof wrapper captures the executable's cost/memory
                # analyses and compile seconds as an xla:cost event)
                sink.event(
                    "dispatch", "build",
                    key=str(key),
                    impl=getattr(self.cfg, "impl", "xla"),
                    requested_impl=self._requested_impl,
                )
            # persistent AOT executable cache (tuning/aot_cache.py):
            # when enabled, the introspection wrapper resolves this key
            # against the on-disk store before paying lower+compile
            from multigpu_advectiondiffusion_tpu.tuning import aot_cache

            aot_key = None
            if aot_cache.enabled():
                aot_key = aot_cache.dispatch_key(self, key, steps=steps,
                                                 donate=donate)
            self._cache[key] = xprof.wrap_dispatch(
                builder(), solver=self, key=str(key), steps=steps,
                aot_key=aot_key, donated=donate,
            )
        return self._cache[key]

    @contextlib.contextmanager
    def _dispatch(self, op: str, steps=None):
        """The top-level ``tpucfd.dispatch`` span of one public driver
        call (:class:`~multigpu_advectiondiffusion_tpu.utils.profiling.
        dispatch`), opened before anything else the call does;
        :meth:`_compiled` counts on it the programs the call invokes."""
        span = dispatch(op, steps)
        outer, self._open_dispatch = self._open_dispatch, span
        try:
            with span:
                yield
        finally:
            self._open_dispatch = outer

    def _dispatch_span(self, op: str, mode: str = "iters", **fields):
        """Context labeling one public driver call with the engaged
        rung: a ``jax.profiler.TraceAnnotation`` (so ``--trace``
        captures show ``tpucfd.run[fused-whole-run-slab]``-style spans
        over the whole rung hierarchy) plus, when a telemetry sink is
        installed, a structured ``solver.<op>`` span carrying the
        engaged stepper/impl/overlap. The profiler span takes the
        stepper's plan (``hbm_bytes_per_step``, ``halo_bytes_per_step``,
        ``block``, ``y_dead_rows``) as stats where it declares them
        (:meth:`engaged_path`)."""
        from multigpu_advectiondiffusion_tpu import telemetry

        with annotate(RESOLVE_SPAN):
            eng = self.engaged_path(mode=mode)
        stats = {k: eng[k] for k in PLAN_KEYS if k in eng}
        stack = contextlib.ExitStack()
        stack.enter_context(
            annotate(f"tpucfd.{op}[{eng['stepper']}]", **stats)
        )
        sink = telemetry.get_sink()
        if sink.active:
            stack.enter_context(
                sink.span(
                    f"solver.{op}",
                    stepper=eng["stepper"],
                    impl=eng["impl"],
                    overlap=eng.get("overlap"),
                    **fields,
                )
            )
        return stack

    # ------------------------------------------------------------------ #
    # Graceful kernel-ladder degradation
    # ------------------------------------------------------------------ #
    def _with_ladder(self, call, mode: str = "iters"):
        """Execute ``call()`` (a public driver's body), falling down the
        kernel ladder on a Pallas/Mosaic compile or launch failure at
        dispatch: ``pallas_slab -> pallas_stage -> xla``.

        Only ``impl='pallas'`` (the best-*available* promise) degrades;
        an explicit rung pin (``pallas_slab``/``pallas_stage``/...) fails
        loudly — the user asked for that kernel, not a slower answer.
        Failures surfacing asynchronously after dispatch (a launch fault
        found at a later sync) propagate to the caller; the ladder
        guards the dispatch/compile point, where Mosaic rejections
        actually appear."""
        while True:
            try:
                return call()
            except Exception as exc:  # noqa: BLE001 — classifier filters
                if not self._degrade_after(exc, mode):
                    raise

    def _degrade_after(self, exc, mode: str) -> bool:
        """Record a downgrade and retarget ``cfg.impl`` one rung down;
        True if the caller should retry. The classifier keeps this
        narrow: only kernel-infrastructure failures under an auto
        (``impl='pallas'``) config degrade."""
        from multigpu_advectiondiffusion_tpu.resilience.errors import (
            is_kernel_failure,
        )

        if not is_kernel_failure(exc):
            return False
        if self._exchange_mode() == "dma":
            # the in-kernel remote-DMA rung has its own ladder: a
            # Mosaic rejection of the dma program degrades to the
            # split-overlap XLA exchange on the SAME rung/cadence —
            # same physics, same k-schedule, comm back between
            # compiled calls — rather than failing the run
            ev = {
                "from": "fused-whole-run-slab[dma]",
                "to": "fused-whole-run-slab[split]",
                "reason": f"{type(exc).__name__}: {exc}"[:300],
            }
            self._degrade_events.append(ev)
            from multigpu_advectiondiffusion_tpu import telemetry

            telemetry.event(
                "ladder", "degrade",
                **{"from": ev["from"], "to": ev["to"],
                   "reason": ev["reason"]},
            )
            self.cfg = dataclasses.replace(
                self.cfg, exchange="collective", overlap="split"
            )
            self._cache.clear()
            return True
        if self._requested_impl != "pallas":
            return False
        if int(getattr(self.cfg, "steps_per_exchange", 1) or 1) > 1:
            # the k-step schedule exists only on the slab rung: falling
            # down the ladder would silently drop the requested exchange
            # cadence — fail loudly instead (pin semantics)
            return False
        engaged = self.engaged_path(mode=mode)["stepper"]
        if engaged in ("generic-xla", "per-axis-pallas") and getattr(
            self.cfg, "impl", "xla"
        ) == "xla":
            return False  # already at the bottom of the ladder
        nxt = (
            "pallas_stage"
            if engaged == "fused-whole-run-slab"
            else "xla"
        )
        ev = {
            "from": engaged,
            "to": nxt,
            "reason": f"{type(exc).__name__}: {exc}"[:300],
        }
        self._degrade_events.append(ev)
        from multigpu_advectiondiffusion_tpu import telemetry

        # the downgrade is an attributable event, not just a summary
        # footnote: the stream shows WHEN the ladder fell and under what
        # error, ordered against the chunks around it
        telemetry.event(
            "ladder", "degrade",
            **{"from": ev["from"], "to": ev["to"], "reason": ev["reason"]},
        )
        self.cfg = dataclasses.replace(self.cfg, impl=nxt)
        self._cache.clear()
        return True

    # ------------------------------------------------------------------ #
    # Public drivers
    # ------------------------------------------------------------------ #
    def step(self, state: SolverState) -> SolverState:
        with self._dispatch("step", steps=1):
            def call():
                with self._dispatch_span("step"):
                    with annotate(RESOLVE_SPAN):
                        f = self._compiled(
                            "step", lambda: self._wrap(self._local_step),
                            steps=1,
                        )
                    return self._finish(
                        f, (state.u, state.t),
                        lambda u, t: SolverState(u=u, t=t, it=state.it + 1),
                    )

            return self._with_ladder(call)

    @staticmethod
    def _finish(f, args, build):
        """The end of every public driver: call the resolved program
        ``f`` on ``args`` (asynchronous: back before the device runs it) and
        build the returned state from its outputs, each in its span."""
        with annotate(EXECUTE_SPAN):
            out = f(*args)
        with annotate(STATE_SPAN):
            return build(*out)

    def _fused_stepper(self, mode: str = "iters"):
        """Solver-specific fully-fused fast path, or ``None`` (generic).
        Overridden by solvers that have a fused Pallas stepper.

        ``mode`` mirrors the execution dispatch: the whole-run slab
        stepper has no ``run_to`` (its grid bakes the step count), so
        ``advance_to`` asks for the ``"t_end"`` selection and gets the
        per-stage stepper instead of a dead-end slab instance."""
        del mode
        return None

    def _decline(self, reason: str):
        """Record why the fused fast path was declined (read by
        :meth:`engaged_path`) and return ``None`` for the caller to
        propagate. Solvers call this at every eligibility exit.

        ``steps_per_exchange > 1`` turns every decline into a hard
        error: the k-step communication-avoiding schedule exists only on
        the sharded slab rung, so a config that falls off the fused
        ladder cannot honor the requested exchange cadence — pin
        semantics, like an undispatachable explicit rung pin."""
        self._fused_fallback = reason
        if int(getattr(self.cfg, "steps_per_exchange", 1) or 1) > 1:
            raise ValueError(
                "steps_per_exchange > 1 needs the sharded slab rung; "
                f"this config declined fusion: {reason}"
            )
        if self._exchange_mode() == "dma":
            raise ValueError(
                "exchange='dma' needs the sharded slab rung; "
                f"this config declined fusion: {reason}"
            )
        return None

    def _pallas_f32_gate(self, impl: str) -> str:
        """Route non-f32 dtypes off the per-axis Pallas kernels: they
        are f32-calibrated and Mosaic has no f64 vector path — a TPU
        run would fail in the compiler rather than fall back. The ONE
        definition both solvers' ``_op_impl`` use; the reason lands in
        :meth:`engaged_path`."""
        import jax.numpy as jnp

        if impl == "pallas" and self.dtype != jnp.float32:
            self._op_fallback = (
                "per-axis Pallas kernels are float32-only; XLA runs"
            )
            return "xla"
        return impl

    def engaged_path(self, mode: str = "iters") -> dict:
        """Which kernel strategy actually executes for this config.

        The reference's ``PrintSummary`` tells the user what ran
        (``MultiGPU/Diffusion3d_Baseline/Tools.c:255-269``); without this
        a ``--impl pallas`` config that fails fused eligibility would
        silently benchmark the generic path. Keys: ``impl`` (requested),
        ``stepper`` (what executes: ``fused-stage`` / ``fused-whole-run``
        / ``fused-step`` / ``per-axis-pallas`` / ``generic-xla``),
        ``overlap`` (sharded halo schedule actually in effect),
        ``fallback`` (reason the fused stepper was declined, or None),
        and — when the kernel ladder degraded after a Mosaic/Pallas
        dispatch failure — ``degraded``, the downgrade event list
        (from/to rung + failure text); absent on healthy runs. A fused
        stepper that declares its plan adds ``hbm_bytes_per_step`` (the
        bytes its kernels DMA from and to HBM per step on one shard),
        ``halo_bytes_per_step`` (the bytes one shard sends through the
        halo exchange per step, when sharded), ``block`` (the kernel
        block that runs, joined: ``"8x64"`` for Burgers' ``(bz, by)``,
        ``"19"`` for diffusion's ``(bz,)``) and ``y_dead_rows`` (the y
        rows Burgers' kernels work past the plane, rounding it up to
        whole blocks).

        ``mode`` mirrors the execution dispatch: ``"t_end"`` engages the
        fused stepper only when it has ``run_to`` (``advance_to``'s extra
        requirement) — the whole-run/whole-step classes don't, and their
        t_end runs use the generic loop.
        """
        from multigpu_advectiondiffusion_tpu.ops import (
            is_fused_impl,
            is_pallas_impl,
        )

        impl = getattr(self, "_requested_impl", None) or getattr(
            self.cfg, "impl", "xla"
        )
        fused = self._fused_stepper(mode="t_end" if mode == "t_end" else "iters")
        if fused is not None and mode == "t_end" and not hasattr(
            fused, "run_to"
        ):
            self._fused_fallback = (
                f"{fused.engaged_label} stepper has no run_to; "
                "t_end mode runs the generic loop"
            )
            fused = None
        if fused is not None:
            overlap = None
            exchange = getattr(fused, "exchange", "collective")
            if getattr(fused, "sharded", False):
                if exchange == "dma":
                    # the whole-run program exchanges in-kernel: there
                    # is no XLA-level halo schedule to overlap
                    overlap = "in-kernel"
                elif getattr(fused, "overlap_split", False):
                    overlap = "split"
                else:
                    overlap = "serialized-refresh"
            out = {
                "impl": impl,
                "stepper": fused.engaged_label,
                "overlap": overlap,
                # comm-avoiding chunk length actually in effect (1 =
                # the per-step exchange cadence)
                "steps_per_exchange": int(
                    getattr(fused, "steps_per_exchange", 1)
                ),
                # halo-exchange transport actually engaged
                "exchange": exchange,
                # HBM-resident dtype of the engaged stepper's buffers
                # (f64 facing states live as f32 in-kernel; bf16 under
                # precision='bf16')
                "storage_dtype": str(
                    jnp.dtype(getattr(fused, "dtype", self.dtype))
                ),
                "precision": self._precision_mode(),
                "fallback": None,
            }
            plan = getattr(fused, "plan", None)  # FusedStepperBase's
            if plan is not None:
                out.update(plan())
            if self._tuned is not None:
                out["tuned"] = self._tuned_summary()
            if self._degrade_events:
                out["degraded"] = list(self._degrade_events)
            return out
        # honor solver-level per-op dispatch rules (e.g. Burgers keeps
        # XLA for WENO7 under impl="pallas" — measured faster)
        op = (
            self._op_impl()
            if hasattr(self, "_op_impl")
            else ("pallas" if is_pallas_impl(impl) else "xla")
        )
        stepper = "per-axis-pallas" if op == "pallas" else "generic-xla"
        fallback = None
        if is_fused_impl(impl):
            fallback = getattr(
                self, "_fused_fallback", None
            ) or "config not fused-eligible"
            op_reason = getattr(self, "_op_fallback", None)
            if op_reason:
                fallback += "; " + op_reason
        elif is_pallas_impl(impl) and op == "xla":
            # explicit per-axis rung requested but undispatchable
            fallback = getattr(self, "_op_fallback", None)
        overlap = (
            getattr(self.cfg, "overlap", None)
            if self.mesh is not None
            else None
        )
        out = {
            "impl": impl,
            "stepper": stepper,
            "overlap": overlap,
            "steps_per_exchange": int(
                getattr(self.cfg, "steps_per_exchange", 1) or 1
            ),
            "exchange": self._exchange_mode(),
            "storage_dtype": str(jnp.dtype(self.storage_dtype)),
            "precision": self._precision_mode(),
            "fallback": fallback,
        }
        if self._tuned is not None:
            out["tuned"] = self._tuned_summary()
        if self._degrade_events:
            out["degraded"] = list(self._degrade_events)
        return out

    def _tuned_summary(self) -> dict:
        """Compact tuner provenance for engaged_path/bench rows: where
        the decision came from and what it measured — enough to audit a
        published rate without re-opening the cache file."""
        d = self._tuned or {}
        return {
            k: d.get(k)
            for k in ("source", "impl", "steps_per_exchange", "exchange",
                      "mlups", "key")
            if k in d
        }

    def _sharded_axes(self):
        """Array axes that are *actually* decomposed: listed in the
        decomposition AND backed by a mesh extent > 1. The single
        definition of "sharded" for every eligibility gate — extent-1
        axes exchange no ghosts and must never trip layout/rounding
        gates (axis_extent, not sizes.get: compound (tuple) mesh-axis
        entries — the multihost z layout ('dz_dcn', 'dz_ici') — are
        never keys of mesh.shape and would silently read as extent 1).
        """
        if self.mesh is None:
            return []
        sizes = dict(self.mesh.shape)
        return [
            ax for ax, name in self.decomp.axes
            if axis_extent(sizes, name) > 1
        ]

    def _split_overlap_requested(self) -> bool:
        """``overlap='split'`` with a decomposition the fused steppers'
        three-call overlapped schedule serves: the leading (z) axis
        sharded, and — 3-D only — optionally y and/or x as well (pencil/
        block meshes: the z halo rides the overlapped exchanged-slab
        schedule, the other sharded axes a serialized per-stage
        refresh). Single definition for every solver's eligibility."""
        if self.mesh is None or getattr(self.cfg, "overlap", None) != "split":
            return False
        sharded = self._sharded_axes()
        if sharded == [0]:
            return True
        return self.grid.ndim == 3 and bool(sharded) and sharded[0] == 0

    def _fused_sharded_ctx(self, fused):
        """``(refresh, offsets_fn, exch)`` for running a fused stepper
        shard-local inside ``shard_map``: ghosts ppermute-refreshed after
        every RK stage, global wall masks fed this shard's offsets (the
        reference runs its tuned kernel under MPI the same way,
        ``MultiGPU/Diffusion3d_Baseline/main.c:189-303``). All ``None``
        when unsharded. ``offsets_fn``/``exch`` must be called inside
        ``shard_map`` (they read ``lax.axis_index``/``ppermute``).

        When the stepper runs the split-overlap schedule
        (``fused.overlap_split``), ``exch`` replaces ``refresh``: it
        returns the ``(lo, hi)`` exchanged z-slabs of the padded
        buffer's core, which the stage's edge calls consume as separate
        operands — so XLA schedules the interior call concurrently with
        the ppermute instead of serializing on a buffer rewrite.

        Both closures exchange at the stepper's ``exchange_depth``
        (the stencil halo per stage/step, or ``k * G`` for the
        communication-avoiding k-step slab schedule) and take an
        optional ``repeats`` telemetry hint (see
        ``parallel.halo.exchange_ghosts``)."""
        if self.mesh is None or not fused.sharded:
            return None, None, None
        sizes = dict(self.mesh.shape)
        depth = int(getattr(fused, "exchange_depth", fused.halo))

        def offsets_fn():
            return jnp.stack(
                [
                    jnp.asarray(o, jnp.int32)
                    for o in axis_offsets(self.decomp, fused.interior_shape)
                ]
            )

        if getattr(fused, "exchange", "collective") == "dma":
            # in-kernel remote-DMA exchange: the stepper's whole-run
            # program moves its own ghost rows over ICI — no ppermute
            # refresh/exch closures exist at the XLA level
            return None, offsets_fn, None

        if getattr(fused, "overlap_split", False):
            name = self.decomp.mesh_axis(0)
            nsh = axis_extent(sizes, name)
            offs = getattr(
                fused, "core_offsets",
                (fused.halo,) * len(fused.interior_shape),
            )
            off = offs[0]
            lz = fused.interior_shape[0]

            def exch(P, repeats: int = 1):
                core = slice_axis(P, 0, off, off + lz)
                return exchange_ghosts(
                    core, 0, depth, name, nsh, self.bcs[0],
                    repeats=repeats,
                )

            # Pencil meshes: the non-z sharded axes keep the serialized
            # per-stage buffer refresh — only the z halo rides the
            # overlapped exchanged-slab schedule (the stages' y-ghost
            # reads come from the buffer, so each stage's composed
            # output is refreshed before the next consumes it).
            others = {
                ax: nm
                for ax, nm in self.decomp.axes
                if ax != 0 and axis_extent(sizes, nm) > 1
            }
            refresh = None
            if others:
                refresh = make_ghost_refresh(
                    Decomposition.of(others), sizes, self.bcs, fused.halo,
                    fused.interior_shape,
                    core_offsets=getattr(fused, "core_offsets", None),
                )
            return refresh, offsets_fn, exch

        refresh = make_ghost_refresh(
            self.decomp, sizes, self.bcs, depth, fused.interior_shape,
            core_offsets=getattr(fused, "core_offsets", None),
        )
        return refresh, offsets_fn, None

    def run(self, state: SolverState, num_iters: int) -> SolverState:
        """Fixed-count loop (the CUDA drivers' ``max_iters`` mode,
        ``MultiGPU/Diffusion3d_Baseline/main.c:189``). A Mosaic/Pallas
        failure at dispatch under ``impl='pallas'`` retries one kernel-
        ladder rung down (:meth:`_with_ladder`)."""
        with self._dispatch("run", steps=num_iters):
            def call():
                with self._dispatch_span("run", iters=int(num_iters)):
                    return self._run_impl(state, num_iters)

            return self._with_ladder(call)

    def _run_impl(self, state: SolverState, num_iters: int) -> SolverState:
        with annotate(RESOLVE_SPAN):
            f = self._run_program(num_iters)
        return self._finish(
            f, (state.u, state.t),
            lambda u, t: SolverState(u=u, t=t, it=state.it + num_iters),
        )

    def _run_program(self, num_iters: int):
        """The compiled program that marches ``num_iters`` steps on the
        engaged rung."""
        fused = self._fused_stepper()
        if fused is not None:
            refresh, offsets_fn, exch = self._fused_sharded_ctx(fused)

            def block(u, t):
                # kwargs only when sharded — the 2-D whole-run steppers
                # are single-chip and take none of these
                kw = {}
                if refresh is not None:
                    kw["refresh"] = refresh
                if exch is not None:
                    kw["exch"] = exch
                if offsets_fn is not None:
                    kw["offsets"] = offsets_fn()
                return fused.run(u, t, num_iters, **kw)

            return self._compiled(
                ("fused_run", num_iters), lambda: self._wrap(block),
                steps=int(num_iters),
            )

        if self._precision_mode() == "bf16":
            # bf16-storage generic rung: the loop-resident state is the
            # packed bf16 representation (hi, or hi+compensation lo) —
            # the facing/public state stays f32; every step
            # reconstructs f32, marches, re-splits. With the carry the
            # loop carries 2+2 bytes/cell (f32 traffic parity — the win
            # is the halo wire and the carry-free fused rungs); without
            # it, 2 bytes/cell at bf16 rounding error.
            def block(u, t):
                def body(i, c):
                    u2, t2 = self._local_step(
                        self._bf16_unpack(c[:-1]), c[-1]
                    )
                    return self._bf16_pack(u2) + (t2,)

                out = lax.fori_loop(
                    0, num_iters, body, self._bf16_pack(u) + (t,)
                )
                return self._bf16_unpack(out[:-1]), out[-1]
        else:
            def block(u, t):
                return lax.fori_loop(
                    0, num_iters, lambda i, c: self._local_step(*c), (u, t)
                )

        return self._compiled(("run", num_iters), lambda: self._wrap(block),
                              steps=int(num_iters))

    def advance_to(self, state: SolverState, t_end: float) -> SolverState:
        """March until ``t_end`` with the last step trimmed to land exactly
        (the corrected version of the MATLAB drivers' loop, heat3d.m:48-77).

        ``t_end`` is a traced operand: one compilation serves every end
        time, so parameter sweeps do not recompile per value.

        When the config is fused-eligible and the stepper has a
        ``run_to`` (the 3-D fused Burgers), this mode runs at the fused
        stepper's speed — the reference Burgers drivers' *only* execution
        mode is ``while (t < tEnd)`` over the tuned kernels
        (``MultiGPU/Burgers3d_Baseline/main.c:190-317``)."""
        with self._dispatch("advance_to"):
            def call():
                with self._dispatch_span("advance_to", mode="t_end",
                                         t_end=float(t_end)):
                    return self._advance_impl(state, t_end)

            return self._with_ladder(call, mode="t_end")

    def _advance_impl(self, state: SolverState, t_end: float) -> SolverState:
        with annotate(RESOLVE_SPAN):
            f = self._advance_program()
            te = jnp.asarray(t_end, state.t.dtype)
        return self._finish(
            f, (state.u, state.t, te),
            lambda u, t, steps: SolverState(u=u, t=t, it=state.it + steps),
        )

    def _advance_program(self):
        """The compiled program that marches to a traced ``t_end`` on
        the engaged rung."""
        fused = self._fused_stepper(mode="t_end")
        if fused is not None and hasattr(fused, "run_to"):
            refresh, offsets_fn, exch = self._fused_sharded_ctx(fused)

            def fblock(u, t, te):
                offs = offsets_fn() if offsets_fn is not None else None
                return fused.run_to(u, t, te, refresh=refresh, offsets=offs,
                                    exch=exch)

            return self._compiled("fused_adv",
                                  lambda: self._wrap(fblock, 2, 2))

        if self._precision_mode() == "bf16":
            # bf16-storage generic rung, t_end mode: same packed loop
            # state as _run_impl's fori body (the arity n is static —
            # 1 without the compensation carry, 2 with it)
            def block(u, t, te):
                eps = 1e-12 * jnp.maximum(1.0, jnp.abs(te))
                n = len(self._bf16_pack(u))

                def cond(c):
                    return c[n] < te - eps

                def body(c):
                    u2, t2 = self._local_step(
                        self._bf16_unpack(c[:n]), c[n], t_end=te
                    )
                    return self._bf16_pack(u2) + (t2, c[n + 1] + 1)

                out = lax.while_loop(
                    cond, body,
                    self._bf16_pack(u) + (t, jnp.zeros((), jnp.int32)),
                )
                return self._bf16_unpack(out[:n]), out[n], out[n + 1]
        else:
            def block(u, t, te):
                eps = 1e-12 * jnp.maximum(1.0, jnp.abs(te))

                def cond(c):
                    return c[1] < te - eps

                def body(c):
                    u, t, it = c
                    u, t = self._local_step(u, t, t_end=te)
                    return (u, t, it + 1)

                return lax.while_loop(cond, body,
                                      (u, t, jnp.zeros((), jnp.int32)))

        # check=False: no vma/replication rule exists for while_loop
        return self._compiled("adv", lambda: self._wrap(block, 2, 2,
                                                        check=False))

    # ------------------------------------------------------------------ #
    # Ensemble (leading-member-axis) execution — one compiled
    # executable advances B independent members per dispatch,
    # amortizing compile, dispatch and HBM streaming across the batch
    # (ROADMAP item 1; front end in models/ensemble.py)
    # ------------------------------------------------------------------ #
    def _ensemble_gate(self, operand_names=()) -> None:
        """Loud eligibility gate for batched dispatch — mirror of the
        impl/steps_per_exchange construction gates: a config the
        ensemble engine cannot serve fails here instead of silently
        running something else. Since the mesh-scale round (ROADMAP
        item 1) device meshes and the slab rung are ADMITTED: a mesh
        composes through a ``members`` axis (members-sharded, optionally
        x a z-slab spatial subgroup) and uniform-physics ensembles fold
        B into the slab rung's grid; the declines left below are the
        genuinely unservable configs, each with its reason."""
        emesh = getattr(self, "_ensemble_mesh", None)
        if self.mesh is not None and emesh is None:
            raise ValueError(
                "a purely spatial device mesh shards ONE member's grid; "
                "ensembles compose with a mesh through a 'members' axis "
                "— build via EnsembleSolver(..., mesh=make_mesh("
                "{'members': P}) or {'members': P, 'dz': Q})"
            )
        if int(getattr(self.cfg, "steps_per_exchange", 1) or 1) > 1:
            raise ValueError(
                "steps_per_exchange > 1 rides the spatially sharded "
                "slab rung, whose k-step deep-halo schedule does not "
                "fold a member axis — run ensembles at the per-step "
                "exchange cadence"
            )
        if self._exchange_mode() == "dma":
            raise ValueError(
                "exchange='dma' rides the spatially sharded slab "
                "rung, whose in-kernel remote-DMA ring does not fold "
                "a member axis — the batched ensemble engine keeps "
                "the collective exchange"
            )
        if self._precision_mode() == "bf16":
            raise ValueError(
                "precision='bf16' is a single-run rung: neither the "
                "vmapped fused stepper nor the B-folded slab grid "
                "threads the bf16 storage split (and its compensation "
                "carry) through the member axis — run ensembles at "
                "native precision"
            )
        if getattr(self.cfg, "impl", "xla") == "pallas_slab":
            if self.mesh is not None:
                raise ValueError(
                    "the B-folded slab grid serves unsharded-spatial "
                    "instances only (members-only meshes run one fold "
                    "per device); a spatial z-slab x slab-rung ensemble "
                    "remains unservable — its per-step ghost refresh "
                    "cannot cross the member fold"
                )
            if operand_names:
                raise ValueError(
                    "the B-folded slab grid bakes uniform physics "
                    "(fixed dt, closure coefficients); member-varying "
                    f"operand(s) {sorted(operand_names)} ride the "
                    "generic rung — drop the impl='pallas_slab' pin"
                )
        supported = set(self.ensemble_operands())
        unknown = sorted(set(operand_names) - supported)
        if unknown:
            raise ValueError(
                f"{type(self).__name__} has no member-varying operand(s) "
                f"{unknown}; supported: {sorted(supported) or 'none'}"
            )

    def _ensemble_fused(self):
        """The fused stepper the batched dispatch may ride, or ``None``
        (generic vmapped loop). Two fused shapes are served: the
        per-stage rung under ``jax.vmap``, and — new this round — the
        whole-run slab rung with B FOLDED into its Pallas grid
        (``fused_slab_run.run_batched``: a leading member grid axis,
        one program for the whole batched run). Spatially sharded fused
        steppers decline (their ghost refresh does not fold a member
        axis); the 2-D whole-run steppers' in-core padding stays
        unproven under batching."""
        fused = self._fused_stepper(mode="iters")
        if fused is None:
            return None
        if getattr(fused, "sharded", False):
            return self._decline(
                "spatially sharded fused steppers decline the member "
                "axis (ghost refresh cannot cross the fold); the "
                "generic rung serves members x spatial meshes"
            )
        if fused.engaged_label in ("fused-stage", "fused-whole-run-slab"):
            return fused
        return self._decline(
            f"ensemble batching serves the fused-stage (vmap) and "
            f"whole-run-slab (B-fold) rungs; {fused.engaged_label} "
            f"declines batching"
        )

    # -- ensemble mesh plumbing (set by models/ensemble.EnsembleSolver:
    # the full device mesh whose 'members' axis shards the batched
    # state's leading axis; None = single-device batching) ----------- #
    _ensemble_mesh = None
    _ensemble_spatial = None  # spatial Decomposition (grid axes only)

    def arm_ensemble_mesh(self, mesh, spatial_decomp) -> None:
        """Attach the members(-x-spatial) mesh the batched dispatch
        wraps its programs over. ``spatial_decomp`` maps GRID axes to
        the mesh's non-member axes (None = members-only sharding); the
        member axis itself is halo-free by construction and never
        appears in it (verified statically by
        ``analysis/halo_verify.verify_member_mesh``)."""
        from multigpu_advectiondiffusion_tpu.parallel.mesh import (
            MEMBER_AXIS,
        )

        if mesh is not None and MEMBER_AXIS not in dict(mesh.shape):
            raise ValueError(
                "an ensemble mesh needs a 'members' axis"
            )
        self._ensemble_mesh = mesh
        self._ensemble_spatial = spatial_decomp

    def _ensemble_specs(self):
        """``(state_spec, member_spec)`` PartitionSpecs of the batched
        ``(B, *grid)`` state and the per-member ``(B,)`` scalars under
        the armed ensemble mesh (``None`` when unmeshed)."""
        if self._ensemble_mesh is None:
            return None
        from multigpu_advectiondiffusion_tpu.parallel.mesh import (
            MEMBER_AXIS,
        )

        ndim = self.grid.ndim
        spatial = [None] * ndim
        if self._ensemble_spatial is not None:
            mapping = self._ensemble_spatial.mapping
            spatial = [mapping.get(ax) for ax in range(ndim)]
        return P(MEMBER_AXIS, *spatial), P(MEMBER_AXIS)

    def _ensemble_wrap(self, fn, n_in_scalars: int, n_out_scalars: int,
                       n_in_global: int = 0, donate: bool = False):
        """Jit a batched block ``(us, *member_scalars, *globals) ->
        (us, *member_scalars)``. Under the armed ensemble mesh the
        block runs inside ``shard_map``: the state follows
        ``(members, *spatial)``, per-member operands follow the member
        axis, trailing globals (t_end) replicate. ``check=False``
        throughout — the bodies host vmapped while/fori loops and
        Pallas calls, neither of which carries vma typing.

        ``donate`` (ISSUE 19) donates the batched state operand
        (argument 0): XLA aliases the input ``(B, *grid)`` buffer into
        the output, so the slice march updates HBM in place instead of
        holding two copies of the ensemble state per dispatch. Input
        and output state share one PartitionSpec, so the alias is
        always layout-compatible. Backends without donation support
        (XLA:CPU) ignore the hint — the dispatch layer's
        :func:`_consume_donated` makes the semantics uniform anyway."""
        if donate:
            import warnings

            # XLA:CPU implements no donation and warns per dispatch;
            # semantics stay uniform via _consume_donated, so the
            # warning is noise on the tier-1 path
            warnings.filterwarnings(
                "ignore",
                message="Some donated buffers were not usable",
            )
        kwargs = {"donate_argnums": (0,)} if donate else {}
        specs = self._ensemble_specs()
        if specs is None:
            return jax.jit(fn, **kwargs)
        uspec, mspec = specs
        return jax.jit(
            shard_map(
                fn,
                mesh=self._ensemble_mesh,
                in_specs=(uspec,) + (mspec,) * n_in_scalars
                + (P(),) * n_in_global,
                out_specs=(uspec,) + (mspec,) * n_out_scalars,
                check=False,
            ),
            **kwargs,
        )

    def _ensemble_mesh_token(self):
        """Dispatch-cache/AOT key component naming the ensemble mesh
        layout (device placement changes the compiled executable)."""
        emesh = self._ensemble_mesh
        if emesh is None:
            return None
        return ",".join(f"{n}:{s}" for n, s in emesh.shape.items())

    def _ensemble_pack(self, operands, members: int):
        """Normalize ``{name: (B,)-array}`` member-varying operands to
        ``(names, (B, P) matrix)`` in a deterministic column order; an
        empty/None dict packs to a zero-width matrix (uniform physics,
        fused-eligible)."""
        if not operands:
            return (), jnp.zeros((members, 0), jnp.float32)
        names = tuple(sorted(operands))
        self._ensemble_gate(names)
        cols = []
        for n in names:
            col = jnp.asarray(operands[n], jnp.float32).reshape(-1)
            if col.shape[0] != members:
                raise ValueError(
                    f"operand {n!r} has {col.shape[0]} values for "
                    f"{members} members"
                )
            cols.append(col)
        return names, jnp.stack(cols, axis=1)

    def _ensemble_record(self, members, stepper, mode, names):
        """Record + emit the dispatch facts (``ensemble:dispatch``
        events; ``engaged`` provenance for bench rows and the CLI
        summary — the reference's PrintSummary discipline applied to
        the batched engine). Carries the mesh placement since the
        mesh-scale round: ``devices`` (total devices the dispatch
        spans) and ``member_sharding`` (member-axis shard count), so a
        batched row that silently fell back to one device is visible
        in the stream (and failed by the bench engagement guard)."""
        from multigpu_advectiondiffusion_tpu.parallel.mesh import (
            member_extent,
        )

        emesh = self._ensemble_mesh
        devices = 1 if emesh is None else int(emesh.devices.size)
        msh = member_extent(emesh)
        self._ensemble_last = {
            "members": int(members),
            "stepper": stepper,
            "mode": mode,
            "operands": list(names),
            "devices": devices,
            "member_sharding": msh,
            "mesh": self._ensemble_mesh_token(),
        }
        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.event(
            "ensemble", "dispatch",
            members=int(members), stepper=stepper, mode=mode,
            operands=list(names), devices=devices, member_sharding=msh,
            mesh=self._ensemble_mesh_token(),
        )

    def run_ensemble(self, estate: EnsembleState, num_iters: int,
                     operands=None, donate: bool = False) -> EnsembleState:
        """Advance every member ``num_iters`` steps in ONE dispatch.

        Uniform-physics ensembles (no ``operands``) ``vmap`` the fused
        per-stage stepper where the config engages it — bit-exact
        against the looped single runs (tests/test_ensemble.py);
        member-varying scalars (``{name: (B,) values}`` for the names
        in :meth:`ensemble_operands`) ride the generic stepper with the
        scalars as batched operands.

        ``donate=True`` donates ``estate.u`` into the dispatch (in-place
        HBM update, no second ``(B,*grid)`` buffer) and CONSUMES it:
        ``estate`` must not be touched after this returns — any reuse
        raises loudly on every backend (:func:`_consume_donated`)."""
        with self._dispatch("run_ensemble", steps=num_iters):
            with annotate(RESOLVE_SPAN):
                B = estate.members
                names, ops = self._ensemble_pack(operands, B)
                self._ensemble_gate(names)
                fused = self._ensemble_fused() if not names else None
                mtok = self._ensemble_mesh_token()
                slab_fold = (
                    fused is not None
                    and fused.engaged_label == "fused-whole-run-slab"
                )
                if slab_fold:
                    label = "ensemble-fold[fused-whole-run-slab]"
                elif fused is not None:
                    label = f"ensemble-vmap[{fused.engaged_label}]"
                else:
                    label = "ensemble-vmap[generic-xla]"
                self._ensemble_record(B, label, "iters", names)
            with self._dispatch_span("run_ensemble", mode="t_end",
                                     iters=int(num_iters), members=B):
                with annotate(RESOLVE_SPAN):
                    f, args = self._ensemble_run_program(
                        estate, num_iters, names, ops, fused, slab_fold,
                        mtok, donate,
                    )

                def done(u, t):
                    if donate:
                        _consume_donated(estate.u)
                    return EnsembleState(u=u, t=t, it=estate.it + num_iters)

                return self._finish(f, args, done)

    def _ensemble_run_program(self, estate, num_iters, names, ops, fused,
                              slab_fold, mtok, donate):
        """``(program, operands)`` of :meth:`run_ensemble`'s dispatch."""
        B = estate.members
        if slab_fold:
            # B folded into the slab rung's Pallas grid: ONE whole-run
            # program per device advances its members (under a
            # members-only mesh each device runs the fold over its own
            # member shard)
            def block(us, ts):
                return fused.run_batched(us, ts, num_iters)

            f = self._compiled(
                ("ens_slab_run", num_iters, B, mtok),
                lambda: self._ensemble_wrap(block, 1, 1, donate=donate),
                steps=int(num_iters), donate=donate,
            )
            return f, (estate.u, estate.t)

        if fused is not None:
            def block(us, ts):
                return jax.vmap(
                    lambda u, t: fused.run(u, t, num_iters)
                )(us, ts)

            f = self._compiled(
                ("ens_fused_run", num_iters, B, mtok),
                lambda: self._ensemble_wrap(block, 1, 1, donate=donate),
                steps=int(num_iters), donate=donate,
            )
            return f, (estate.u, estate.t)

        def member(u, t, p):
            ov = {n: p[i] for i, n in enumerate(names)} or None

            def body(i, c):
                return self._local_step(c[0], c[1], overrides=ov)

            return lax.fori_loop(0, num_iters, body, (u, t))

        def block(us, ts, ps):
            return jax.vmap(member)(us, ts, ps)

        f = self._compiled(
            ("ens_run", num_iters, B, names, mtok),
            lambda: self._ensemble_wrap(block, 2, 1, donate=donate),
            steps=int(num_iters), donate=donate,
        )
        return f, (estate.u, estate.t, ops)

    def _ensemble_advance_block(self, names, max_steps,
                                per_member_te: bool):
        """The ``advance_to_ensemble`` batched program, as a function —
        shared VERBATIM between the real dispatch and
        :meth:`prewarm_advance_to_ensemble` so a prewarmed executable
        is bit-identical to (and cache-keyed the same as) the one the
        live call would build."""
        def member(u, t, p, te):
            ov = {n: p[i] for i, n in enumerate(names)} or None
            eps = 1e-12 * jnp.maximum(1.0, jnp.abs(te))

            if max_steps is not None:
                def fbody(i, c):
                    u, t, it = c
                    u2, t2 = self._local_step(u, t, t_end=te,
                                              overrides=ov)
                    live = t < te - eps
                    return (
                        jnp.where(live, u2, u),
                        jnp.where(live, t2, t),
                        it + live.astype(jnp.int32),
                    )

                return lax.fori_loop(
                    0, int(max_steps), fbody,
                    (u, t, jnp.zeros((), jnp.int32)),
                )

            def cond(c):
                return c[1] < te - eps

            def body(c):
                u, t, it = c
                u, t = self._local_step(u, t, t_end=te, overrides=ov)
                return (u, t, it + 1)

            return lax.while_loop(
                cond, body, (u, t, jnp.zeros((), jnp.int32))
            )

        if per_member_te:
            # te rides the member axis like t/operands do: the vmap
            # batches it, the ensemble mesh shards it with mspec
            def block(us, ts, ps, tes):
                return jax.vmap(member, in_axes=(0, 0, 0, 0))(
                    us, ts, ps, tes
                )
        else:
            def block(us, ts, ps, te):
                return jax.vmap(member, in_axes=(0, 0, 0, None))(
                    us, ts, ps, te
                )
        return block

    def advance_to_ensemble(self, estate: EnsembleState, t_end: float,
                            operands=None,
                            max_steps: int | None = None,
                            donate: bool = False) -> EnsembleState:
        """March every member to ``t_end`` in one dispatch (vmapped
        while-loop; finished members freeze while stragglers — e.g.
        smaller member dt — keep stepping). Generic rung only: the
        fused ``run_to`` loops host their own scalar plumbing that the
        member axis does not fold.

        ``max_steps`` switches the data-dependent ``while_loop`` for a
        bounded ``fori_loop`` whose finished members freeze via masked
        updates — semantically identical when ``max_steps`` covers the
        longest member trajectory, and REVERSE-MODE DIFFERENTIABLE
        (``jax.grad`` through a dynamic-trip ``while_loop`` is
        undefined): the gradient-based inverse-problem path
        (``examples/inverse_diffusivity.py``) differentiates through
        this dispatch with respect to the member operands.

        ``t_end`` may be a scalar (every member marches to the same
        horizon) or a ``(B,)`` sequence — the request-serving shape
        (service/server.py): coalesced requests asking different
        horizons ride ONE dispatch, each member freezing at its own
        ``te``. The scalar path keeps its original compiled key; the
        per-member path compiles a variant with ``te`` as a batched
        member scalar.

        ``donate=True`` donates ``estate.u`` into the dispatch (ISSUE
        19): XLA updates the ensemble state in place instead of holding
        a second ``(B, *grid)`` buffer, and the input ``estate`` is
        CONSUMED — touching ``estate.u`` afterwards is a loud
        ``RuntimeError`` on every backend."""
        import numpy as _np

        with self._dispatch("advance_to_ensemble"):
            with annotate(RESOLVE_SPAN):
                B = estate.members
                names, ops = self._ensemble_pack(operands, B)
                self._ensemble_gate(names)
                mtok = self._ensemble_mesh_token()
                te_host = _np.asarray(t_end, dtype=_np.float64)
                per_member_te = te_host.ndim > 0
                if per_member_te and te_host.reshape(-1).shape[0] != B:
                    raise ValueError(
                        f"t_end has {te_host.reshape(-1).shape[0]} values "
                        f"for {B} members — pass a scalar or one horizon "
                        f"per member"
                    )
                self._ensemble_record(B, "ensemble-vmap[generic-xla]",
                                      "t_end", names)
            with self._dispatch_span("advance_to_ensemble", mode="t_end",
                                     t_end=float(_np.max(te_host)),
                                     members=B):
                with annotate(RESOLVE_SPAN):
                    block = self._ensemble_advance_block(names, max_steps,
                                                         per_member_te)
                    if per_member_te:
                        f = self._compiled(
                            ("ens_adv", B, names, mtok, max_steps, "vte"),
                            lambda: self._ensemble_wrap(block, 3, 2,
                                                        donate=donate),
                            donate=donate,
                        )
                        te = jnp.asarray(te_host.reshape(-1), estate.t.dtype)
                    else:
                        f = self._compiled(
                            ("ens_adv", B, names, mtok, max_steps),
                            lambda: self._ensemble_wrap(
                                block, 2, 2, n_in_global=1, donate=donate),
                            donate=donate,
                        )
                        te = jnp.asarray(t_end, estate.t.dtype)

                def done(u, t, steps):
                    if donate:
                        _consume_donated(estate.u)
                    return EnsembleState(u=u, t=t, it=estate.it + steps)

                return self._finish(f, (estate.u, estate.t, ops, te), done)

    def prewarm_advance_to_ensemble(self, members: int,
                                    operand_names=(),
                                    max_steps: int | None = None,
                                    donate: bool = False,
                                    per_member_te: bool = True):
        """Speculative AOT prewarm (ISSUE 19): resolve the
        ``advance_to_ensemble`` executable for ``(members,
        operand_names, max_steps, donate)`` from the persistent AOT
        store WITHOUT concrete operands and WITHOUT ever compiling —
        ``jax.ShapeDtypeStruct`` avals fingerprint identically to the
        concrete arrays the live call will pass, so a deserialized hit
        is the executable the next batch dispatches.

        Returns ``"hit"`` (deserialized and resident), ``"resident"``
        (already compiled/loaded in this process), ``"miss"`` (no
        store entry — the live call will pay the compile), or ``None``
        (prewarm unavailable: xprof/AOT cache disabled). Never
        compiles cold, never raises on a cache problem.

        The block builder is :meth:`_ensemble_advance_block` — the
        SAME function the live dispatch uses — so even on a miss the
        jit function parked in the dispatch cache is exactly the one
        the live call would have built."""
        B = int(members)
        names = tuple(sorted(operand_names)) if operand_names else ()
        self._ensemble_gate(names)
        mtok = self._ensemble_mesh_token()
        block = self._ensemble_advance_block(names, max_steps,
                                             per_member_te)
        if per_member_te:
            f = self._compiled(
                ("ens_adv", B, names, mtok, max_steps, "vte"),
                lambda: self._ensemble_wrap(block, 3, 2,
                                            donate=donate),
                donate=donate,
            )
        else:
            f = self._compiled(
                ("ens_adv", B, names, mtok, max_steps),
                lambda: self._ensemble_wrap(block, 2, 2, n_in_global=1,
                                            donate=donate),
                donate=donate,
            )
        prewarm = getattr(f, "prewarm", None)
        if prewarm is None:
            return None  # introspection wrapper absent: no AOT path
        rdt = (jnp.float64 if self.dtype == jnp.dtype(jnp.float64)
               else jnp.float32)
        te_shape = (B,) if per_member_te else ()
        shaped = (
            jax.ShapeDtypeStruct((B, *self.grid.shape), self.dtype),
            jax.ShapeDtypeStruct((B,), rdt),
            jax.ShapeDtypeStruct((B, len(names)), jnp.float32),
            jax.ShapeDtypeStruct(te_shape, rdt),
        )
        return prewarm(shaped)
