"""Static per-rung cost model: HBM bytes moved and FLOPs per step.

Turns a measured wall-clock rate into a *roofline efficiency*: how close
the engaged stepper rung ran to the rate its memory traffic (or compute)
allows on the hardware — the bytes/FLOPs accounting HipBone (PAPERS:
arXiv 2202.12477) treats as the baseline for every kernel. The model is
static and documented, not profiled: every count below is derived from
the operator definitions in ``ops/`` and the steppers' data flow, so a
test can hand-compute the same numbers (tests/test_telemetry.py).

FLOP conventions (per cell, per RK stage; adds/subs/muls/divs each = 1):

* O4 Laplacian axis term, factored ``c*(16*(q1+q3) - (q0+q4) - 30*q2)``:
  2 pair-adds + (16*, -) + (30*, -) + c* = 4 add/sub + 3 mul = **7/axis**.
* O2 Laplacian axis term ``c*((q0+q2) - 2*q1)``: 2 add/sub + 2 mul =
  **4/axis**.
* Cross-axis accumulation: **ndim-1** adds.
* SSP-RK3 stage combine ``u = a*u0 + b*(u_s + dt*L)``: 3 mul + 2 add =
  **5**.
* WENO5 axis sweep (ops/weno.py, single-division form), per cell-stage:
  LF split 7; per reconstruction side: betas 33 + eps-shifts 3 +
  unnormalized alphas 9 + normalization (2 add, 1 div, 3 mul) 6 +
  candidate stencils 15 + weighted combine 5 = 71; two sides 142; flux
  divergence 2 → **151/axis**. WENO7 (4 stencils, wider betas) is the
  analogous count, **232/axis** (estimate at the same conventions; no
  test pins it — the reference never benchmarked WENO7 either).

HBM traffic (field passes per *step*; 1 pass = cells * itemsize bytes,
itemsize = the STORAGE dtype, so the f64-storage/f32-compute rung pays
f64 bytes):

* ``fused-whole-run-slab`` / ``fused-step``: read state + write state
  once per step (the one-HBM-round-trip-per-step schedule) = **2**.
* ``fused-whole-run``: state is VMEM-resident for the entire run — HBM
  traffic only at run boundaries, modeled as **0** (the roofline is then
  compute-only).
* ``fused-stage``: SSP-RK3 ping-pong S/T1/T2 — stage 1 reads S writes
  T1 (2), stages 2/3 read the previous stage plus S and write (3 each)
  = **8**. The stage kernel streams its input through a VMEM ring of
  z-planes, so it moves these passes plus 2R halo planes per stage
  call (``FusedDiffusionStepper.hbm_bytes_per_step`` counts them).
* ``per-axis-pallas``: per stage, one read+write sweep per axis
  (2*ndim) plus the RK combine (read L, u_s, u0; write u = 4) =
  **3*(2*ndim+4)**.
* ``generic-xla``: per stage, L materialized (read u_s, write L) then
  combined (read L, u_s, u0; write u) = **3*6 = 18** — an idealized
  lower bound; XLA may fuse better or worse.

Peaks are keyed by the device's ``device_kind`` (env-overridable with
``TPUCFD_PEAK_BYTES_PER_S`` / ``TPUCFD_PEAK_FLOPS_PER_S``); a kind that
is not in :data:`PEAKS` is an error, not a default. The v5e row is the
published datasheet (819 GB/s HBM; f32 at a quarter of the 197 TFLOP/s
bf16 peak — stencil code is VPU-bound and will not approach the compute
roof, so the meaningful number on TPU is the HBM roofline). The CPU row
is a nominal (50 GB/s, 100 GFLOP/s) placeholder so the plumbing is
testable without hardware; CPU percentages are not performance claims.

A *measured* calibration record (``telemetry/calibration.py`` — the
best achieved rate any run's XLA-reported byte/FLOP counts have
demonstrated on this rig) takes precedence over BOTH the defaults and
the env assumptions (:func:`peak_rates` docstring); roofline
percentages then read against demonstrated capability rather than a
datasheet, and the autotuner prunes with measured peaks.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Sequence

# per-axis FLOPs of the diffusion RHS by Laplacian order
DIFFUSION_AXIS_FLOPS = {2: 4, 4: 7}
# per-axis FLOPs of the WENO flux-divergence sweep by order
WENO_AXIS_FLOPS = {5: 151, 7: 232}
RK_COMBINE_FLOPS = 5
# ADR family (models/adr.py) conventions:
# * first-order upwind advective term per axis, folded coefficients
#   ``cp*(u - u_lo) + cm*(u_hi - u)``: 2 sub + 2 mul + 1 add = **5**;
#   WENO5 linear advection reuses the Burgers sweep count (151/axis).
# * variable-K coefficient ``K0 (1 + eps prod cos(pi x̂))``: ndim
#   cos + (ndim-1) muls + axpy + the K*lap multiply, counted as
#   **3*ndim + 2** (cos = 1 at these conventions — VPU-transcendental,
#   roofline-irrelevant next to the HBM bound); constant K is the one
#   K*lap multiply = **1**.
# * linear-decay reaction ``- lambda u``: mul + sub = **2**.
ADR_UPWIND_AXIS_FLOPS = 5

# (peak HBM bytes/s, peak FLOP/s) by jax ``device_kind``
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM at 819 GB/s,
    # 197 TFLOP/s bf16; f32 runs at a quarter of the bf16 rate
    "TPU v5 lite": (819.0e9, 4.92e13),
    "cpu": (5.0e10, 1.0e11),  # nominal, for plumbing/tests only
}


def hbm_passes_per_step(stepper: str, ndim: int, stages: int = 3) -> float:
    """Field passes (cells * itemsize each) one step moves through HBM
    for the given engaged-stepper label; derivations in the module
    docstring."""
    if stepper in ("fused-whole-run-slab", "fused-step"):
        return 2.0
    if stepper == "fused-whole-run":
        return 0.0
    if stepper == "fused-stage":
        return float(stages - 1) * 3.0 + 2.0  # 8 for SSP-RK3
    if stepper == "per-axis-pallas":
        return float(stages) * (2.0 * ndim + 4.0)
    # generic-xla and anything unrecognized: the materialized-RHS bound
    return float(stages) * 6.0


def rhs_flops_per_cell(
    kind: str,
    ndim: int,
    order: int = 4,
    weno_order: int = 5,
    viscous: bool = False,
    advect: str = "upwind",
    reaction: bool = False,
    variable_k: bool = False,
) -> float:
    """FLOPs of one RHS evaluation per cell (no RK combine)."""
    if kind == "diffusion":
        return DIFFUSION_AXIS_FLOPS[order] * ndim + (ndim - 1)
    if kind == "burgers":
        f = WENO_AXIS_FLOPS[weno_order] * ndim + (ndim - 1)
        if viscous:
            # nu*lap(u) rides the O2 Laplacian plus one axpy per cell
            f += DIFFUSION_AXIS_FLOPS[2] * ndim + (ndim - 1) + 2
        return float(f)
    if kind == "adr":
        # diffusive taps + K multiply (+ the variable-K profile)
        f = DIFFUSION_AXIS_FLOPS[order] * ndim + (ndim - 1)
        f += (3 * ndim + 2) if variable_k else 1
        # advective divergence + cross-axis accumulation + the
        # RHS-level subtraction
        adv = (
            ADR_UPWIND_AXIS_FLOPS
            if advect == "upwind"
            else WENO_AXIS_FLOPS[5]
        )
        f += adv * ndim + (ndim - 1) + 1
        if reaction:
            f += 2  # -lambda u: mul + sub
        return float(f)
    raise ValueError(f"unknown solver kind {kind!r}")


@dataclasses.dataclass(frozen=True)
class StepCost:
    """Modeled cost of ONE time step over the whole (global) grid."""

    hbm_bytes: float
    flops: float
    passes: float
    flops_per_cell_stage: float

    def to_dict(self) -> dict:
        return {
            "hbm_bytes_per_step": self.hbm_bytes,
            "flops_per_step": self.flops,
            "hbm_passes_per_step": self.passes,
            "flops_per_cell_stage": self.flops_per_cell_stage,
        }


def step_cost(
    kind: str,
    shape: Sequence[int],
    itemsize: int,
    stepper: str,
    stages: int = 3,
    order: int = 4,
    weno_order: int = 5,
    viscous: bool = False,
    advect: str = "upwind",
    reaction: bool = False,
    variable_k: bool = False,
) -> StepCost:
    cells = math.prod(shape)
    ndim = len(shape)
    per_cell_stage = (
        rhs_flops_per_cell(kind, ndim, order=order, weno_order=weno_order,
                           viscous=viscous, advect=advect,
                           reaction=reaction, variable_k=variable_k)
        + RK_COMBINE_FLOPS
    )
    passes = hbm_passes_per_step(stepper, ndim, stages)
    return StepCost(
        hbm_bytes=passes * cells * itemsize,
        flops=float(stages) * cells * per_cell_stage,
        passes=passes,
        flops_per_cell_stage=per_cell_stage,
    )


# Per-message fixed cost of one halo exchange (ppermute pair): launch +
# interconnect latency, not bandwidth. Crude by design — it only has to
# rank k-candidates for the tuner's pruning, and the measured pass
# decides. Env-overridable like the peaks.
EXCHANGE_LATENCY_S = 25e-6


def halo_exchange_seconds(
    nbytes: float,
    messages: int = 1,
    kind: Optional[str] = None,
) -> float:
    """Modeled wall time of halo traffic: ``messages`` fixed per-message
    latencies (``TPUCFD_EXCHANGE_LATENCY_S`` overrides the default) plus
    the payload at the device's peak bandwidth. The communication-
    avoiding tradeoff in one line: a k-step schedule moves the same
    bytes per step but pays the latency term only once per k steps."""
    lat = float(
        os.environ.get("TPUCFD_EXCHANGE_LATENCY_S", EXCHANGE_LATENCY_S)
    )
    peak_b, _ = peak_rates(kind)
    return messages * lat + (nbytes / peak_b if peak_b else 0.0)


def deep_halo_recompute_factor(local_nz: int, G: int, k: int) -> float:
    """Mean redundant-work multiplier of the k-step deep-halo schedule
    on a ``local_nz``-row shard: in-block step ``j`` evolves the core
    plus ``(k-1-j)*G`` ghost rows per side, so the average window is
    ``local_nz + (k-1)*G`` rows — the FLOP (and slab-traffic) price paid
    for exchanging once per k steps."""
    if local_nz <= 0:
        return 1.0
    return 1.0 + (k - 1) * G / float(local_nz)


def device_kind(kind: Optional[str] = None) -> str:
    """``kind``, or the first device's ``device_kind``; raises for a
    kind with no row in :data:`PEAKS` — a roofline against another
    chip's peaks would be a wrong number, not an approximate one."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise KeyError(
            f"no peak rates for device kind {kind!r} (have "
            f"{sorted(PEAKS)}); add its datasheet row to costmodel.PEAKS"
        )
    return kind


def peak_rates(kind: Optional[str] = None):
    """(bytes/s, FLOP/s) peaks for a device kind (default: this
    process's first device).

    Precedence: a *measured* calibration record
    (:mod:`telemetry.calibration` — the max achieved rate any run's XLA
    byte/FLOP counts demonstrated on this rig) beats the env overrides
    (``TPUCFD_PEAK_BYTES_PER_S``/``_FLOPS_PER_S``), which beat the
    datasheet rows. Measured > assumed: set
    ``TPUCFD_CALIBRATION_PATH=off`` to fall back to assumptions."""
    info = peak_info(kind)
    return info["bytes_per_s"], info["flops_per_s"]


def peak_info(kind: Optional[str] = None) -> dict:
    """:func:`peak_rates` plus provenance: where each peak came from
    (``calibrated`` / ``env`` / ``default``) — carried in the tuner's
    ``tune:candidates`` event so a pruning decision is auditable."""
    kind = device_kind(kind)
    peak_b, peak_f = PEAKS[kind]
    src_b = src_f = "default"
    env_b = os.environ.get("TPUCFD_PEAK_BYTES_PER_S")
    env_f = os.environ.get("TPUCFD_PEAK_FLOPS_PER_S")
    if env_b:
        peak_b, src_b = float(env_b), "env"
    if env_f:
        peak_f, src_f = float(env_f), "env"
    from multigpu_advectiondiffusion_tpu.telemetry import calibration

    cal = calibration.lookup(kind)
    if cal:
        if cal.get("bytes_per_s"):
            peak_b, src_b = float(cal["bytes_per_s"]), "calibrated"
        if cal.get("flops_per_s"):
            peak_f, src_f = float(cal["flops_per_s"]), "calibrated"
    return {
        "device_kind": kind,
        "bytes_per_s": peak_b,
        "flops_per_s": peak_f,
        "bytes_source": src_b,
        "flops_source": src_f,
    }


def roofline(
    cost: StepCost,
    iters: int,
    seconds: float,
    kind: Optional[str] = None,
    devices: int = 1,
) -> dict:
    """Measured seconds vs the model's minimum time on the peak rates.

    ``roofline_pct = 100 * t_model / t_measured`` where ``t_model`` is
    the binding resource's time ``max(bytes/peak_bw, flops/peak_flops)``
    for the whole run (aggregate peaks scale with ``devices``).
    ``bound`` names the binding resource. VMEM-resident rungs (0 modeled
    bytes) are compute-bound by construction.
    """
    peak_b, peak_f = peak_rates(kind)
    peak_b *= max(1, devices)
    peak_f *= max(1, devices)
    bytes_total = cost.hbm_bytes * iters
    flops_total = cost.flops * iters
    t_mem = bytes_total / peak_b if peak_b else 0.0
    t_cmp = flops_total / peak_f if peak_f else 0.0
    t_model = max(t_mem, t_cmp)
    out = {
        "achieved_gbs": (
            round(bytes_total / seconds / 1e9, 3) if seconds > 0 else None
        ),
        "achieved_gflops": (
            round(flops_total / seconds / 1e9, 3) if seconds > 0 else None
        ),
        "peak_gbs": round(peak_b / 1e9, 3),
        "peak_gflops": round(peak_f / 1e9, 3),
        "bound": "hbm" if t_mem >= t_cmp else "flops",
        "roofline_pct": (
            round(100.0 * t_model / seconds, 2) if seconds > 0 else None
        ),
    }
    return out


# --------------------------------------------------------------------- #
# Solver-facing conveniences
# --------------------------------------------------------------------- #
def solver_kind(cfg) -> Optional[str]:
    """Solver family from its config: the plugin registry first
    (``models/registry.spec_for_config`` — the single source for
    registered families, so a third model never edits this), then the
    legacy duck-typed fallback for ad-hoc config doubles in tests."""
    try:
        from multigpu_advectiondiffusion_tpu.models import registry

        spec = registry.spec_for_config(cfg)
        if spec is not None:
            return spec.family_kind
    except Exception:
        pass
    if hasattr(cfg, "weno_order"):
        return "burgers"
    if hasattr(cfg, "velocity"):
        return "adr"
    if hasattr(cfg, "diffusivity"):
        return "diffusion"
    return None


def solver_cost_kwargs(cfg) -> dict:
    """Per-family ``step_cost`` kwargs, resolved through the registry's
    ``cost_kwargs`` hook (legacy literal fallback for unregistered
    configs)."""
    try:
        from multigpu_advectiondiffusion_tpu.models import registry

        spec = registry.spec_for_config(cfg)
        if spec is not None and spec.cost_kwargs is not None:
            return dict(spec.cost_kwargs(cfg))
    except Exception:
        pass
    kind = solver_kind(cfg)
    if kind == "diffusion":
        return {"order": getattr(cfg, "order", 4)}
    if kind == "burgers":
        return {
            "weno_order": getattr(cfg, "weno_order", 5),
            "viscous": bool(getattr(cfg, "nu", 0.0)),
        }
    if kind == "adr":
        return {
            "order": getattr(cfg, "order", 4),
            "advect": getattr(cfg, "advect", "upwind"),
            "reaction": bool(getattr(cfg, "reaction_rate", 0.0)),
            "variable_k": bool(getattr(cfg, "kappa_variation", 0.0)),
        }
    return {}


def solver_step_cost(solver, stepper: str) -> Optional[StepCost]:
    """The static cost of one of ``solver``'s steps on the engaged
    ``stepper`` rung, or ``None`` for solver families the model does not
    cover (e.g. axisymmetric geometry is priced as cartesian — close
    enough for a roofline)."""
    import numpy as np

    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )

    cfg = solver.cfg
    kind = solver_kind(cfg)
    if kind is None:
        return None
    kwargs = solver_cost_kwargs(cfg)
    # HBM passes are priced at the STORAGE dtype — what actually sits
    # in (and streams from) HBM: the precision='bf16' rung pays
    # 2 B/cell, not the facing f32's 4
    # (models/base.SolverBase.storage_dtype)
    storage = getattr(solver, "storage_dtype", solver.dtype)
    try:
        return step_cost(
            kind,
            cfg.grid.shape,
            np.dtype(storage).itemsize,
            stepper,
            stages=STAGES[cfg.integrator],
            **kwargs,
        )
    except (KeyError, ValueError):
        # a registered family without a documented FLOP convention:
        # runs fine, just publishes no roofline (the model is static
        # and documented per family — new families opt in by adding
        # their counts here)
        return None


def summarize_run(
    solver,
    stepper: str,
    iters: int,
    seconds: float,
    kind: Optional[str] = None,
) -> Optional[dict]:
    """Cost-model block for a finished run: per-step bytes/FLOPs plus
    the roofline efficiency — what ``RunSummary.cost_model`` and the
    bench rows carry."""
    cost = solver_step_cost(solver, stepper)
    if cost is None or iters <= 0 or seconds <= 0:
        return None
    devices = 1 if solver.mesh is None else solver.mesh.devices.size
    out = cost.to_dict()
    out["stepper"] = stepper
    out.update(roofline(cost, iters, seconds, kind=kind,
                        devices=devices))
    return out


def _dispatch_step_memory(solver, state) -> Optional[dict]:
    """XLA memory accounting of the solver's OWN dispatched step
    executable — captured by the measured-introspection layer
    (``telemetry/xprof.py``) at dispatch, so no second copy of the step
    is lowered or compiled just to inspect it. Runs one step to
    populate the dispatch cache when nothing has executed yet."""
    from multigpu_advectiondiffusion_tpu.telemetry import xprof

    def step_record():
        for r in reversed(xprof.records(solver)):
            if r.key == "step" and (
                r.argument_bytes or r.output_bytes or r.temp_bytes
            ):
                return r
        return None

    rec = step_record()
    if rec is None and xprof.enabled():
        try:
            solver.step(state)
        except Exception:
            return None
        rec = step_record()
    if rec is None:
        return None
    return {
        "argument_size_in_bytes": rec.argument_bytes,
        "output_size_in_bytes": rec.output_bytes,
        "temp_size_in_bytes": rec.temp_bytes,
        "generated_code_size_in_bytes": rec.generated_code_bytes,
    }


def solver_memory_cross_check(solver, state,
                              stepper: Optional[str] = None) -> Optional[dict]:
    """Cross-check the static model against XLA's OWN memory accounting
    for one compiled step of ``solver`` (tests/test_telemetry.py holds
    the two within documented bounds).

    The accounting comes from the dispatch layer's already-compiled
    step executable (:func:`_dispatch_step_memory` — the measured
    introspection captured at ``dispatch:build``); only when that layer
    is disabled does the legacy :func:`xla_memory_analysis` hook
    lower+compile a standalone copy.

    Returns ``None`` where the backend exposes no accounting; otherwise
    a dict with the model's :class:`StepCost`, XLA's byte attributes,
    the single-field byte size, and ``min_traffic_bytes`` — the
    argument+output footprint the compiled step cannot avoid moving,
    which the model must never undercut."""
    cost = solver_step_cost(
        solver, stepper or solver.engaged_path()["stepper"]
    )
    if cost is None:
        return None
    mem = _dispatch_step_memory(solver, state)
    if mem is None:
        mem = xla_memory_analysis(solver.step, state)
    if mem is None:
        return None
    import numpy as np

    field_bytes = math.prod(solver.grid.shape) * np.dtype(
        solver.dtype
    ).itemsize
    return {
        "model": cost.to_dict(),
        "xla": mem,
        "field_bytes": int(field_bytes),
        "min_traffic_bytes": int(
            mem.get("argument_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
        ),
    }


def xla_memory_analysis(fn, *args) -> Optional[dict]:
    """Generic introspection hook: lower+compile ``fn(*args)`` and read
    XLA's own ``memory_analysis()`` where the backend provides one.
    This compiles a standalone copy of ``fn`` — for a solver's own step
    the dispatch path reuses its already-compiled executable instead
    (:func:`_dispatch_step_memory` via ``telemetry/xprof.py``); this
    hook remains for ad-hoc callables and as the disabled-introspection
    fallback. Returns a plain dict of the byte-sized attributes so
    tests can compare magnitudes against the static model without
    depending on the exact HLO schedule."""
    try:
        import jax

        compiled = jax.jit(fn).lower(*args).compile()
        m = compiled.memory_analysis()
    except Exception:
        return None
    if m is None:
        return None
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(m, attr, None)
        if v is not None:
            out[attr] = int(v)
    return out or None
