"""Measured performance introspection: XLA's own numbers, first-class.

The reference archives ``nvprof`` counters next to every ``Run.m``
timing; until this module, our perf-observability stack (roofline %,
the tuner's pruning, the trace report) ran entirely on the *modeled*
cost model (``telemetry/costmodel.py``) with env-assumed peaks, and the
repo had zero memory observability. Three measured layers close that
gap (TPU scientific-computing framework, PAPERS arXiv 2108.11076;
HipBone, arXiv 2202.12477 — compiler/hardware-reported FLOPs, bytes
and footprints as first-class outputs of every run):

* **Executable capture** (:func:`wrap_dispatch`): every program the
  dispatch layer builds (``models/base.SolverBase._compiled``) is
  compiled *ahead-of-time once* — the same single compile the jit
  wrapper would have paid — and the compiled executable is kept both
  for execution and for introspection: XLA's ``cost_analysis()``
  (flops / bytes-accessed / transcendentals), ``memory_analysis()``
  (argument/output/temp bytes, the peak-footprint estimate) and the
  measured compile seconds become an :class:`ExecRecord` on the solver
  and an ``xla:cost`` telemetry event. This is also how
  ``costmodel.solver_memory_cross_check`` now reads XLA's accounting —
  reusing the dispatched executable instead of re-lowering a second
  copy of the step.

  *Semantics*: XLA's HLO cost analysis counts loop bodies ONCE
  (trip-count-independent), so for the dispatch programs — whose body
  is one time step (or one k-step block) — the reported flops/bytes
  are per-step-shaped and read directly against the cost model's
  per-step numbers. Sharded programs report per-device counts; global
  figures multiply by the mesh size. Pallas custom calls are opaque to
  the analysis (their interior flops read as 0) — the generic-XLA
  rungs, which the CPU tier-1 path runs, are fully visible.

* **Device-memory watermarks** (:func:`sample_watermark`): chunk-
  cadence ``mem:watermark`` events from ``device.memory_stats()``
  where the backend provides it (TPU/GPU: true device-reported
  bytes-in-use / peak / limit), falling back to a ``jax.live_arrays()``
  byte census (logical array bytes, host-tracked peak) so the CPU
  tier-1 path exercises the same plumbing. The run-level peak and
  headroom land in ``RunSummary.memory`` — the real-HBM-headroom
  numbers ROADMAP items 1 and 5 need to admit work safely.

* **Measured-vs-modeled** (:func:`measured_summary`): the per-run
  reconciliation — XLA bytes/flops per step against the cost model's
  prediction (ratio flagged outside the documented tolerance band,
  default ``TPUCFD_XPROF_TOLERANCE`` = 3x, reported rather than
  hidden), achieved bandwidth against the assumed peak — emitted as an
  ``xla:measured`` event, carried in ``RunSummary.xla`` and bench rows
  (``xla_flops``/``xla_bytes``/``peak_bytes``), rendered by the
  ``tpucfd-trace`` report, and fed to :mod:`telemetry.calibration` so
  the cost model and the autotuner prune with measured rather than
  assumed peaks.

``TPUCFD_XPROF=0`` disables the capture layer (dispatch falls back to
plain jit); every introspection step is individually fault-tolerant —
a backend that cannot answer an analysis question degrades that field
to ``None``/0, never the solve. The solve's own failures are not
caught here (:class:`_IntrospectedDispatch`).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional

ENABLE_ENV = "TPUCFD_XPROF"
TOLERANCE_ENV = "TPUCFD_XPROF_TOLERANCE"
# modeled/measured bytes (or flops) ratio outside [1/F, F] is reported
# as a discrepancy: the model is an idealized pass count, XLA's is an
# HLO-schedule count — a 3x band separates "different conventions"
# from "one of them is wrong"
DEFAULT_TOLERANCE = 3.0


def enabled() -> bool:
    return os.environ.get(ENABLE_ENV, "").strip().lower() not in (
        "0", "off", "false", "no"
    )


def tolerance_factor() -> float:
    try:
        return float(os.environ.get(TOLERANCE_ENV, DEFAULT_TOLERANCE))
    except ValueError:
        return DEFAULT_TOLERANCE


# --------------------------------------------------------------------- #
# Executable capture
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class ExecRecord:
    """One compiled executable's XLA-reported cost/memory facts."""

    key: str
    solver: str
    stepper: Optional[str]
    impl: Optional[str]
    backend: str
    devices: int
    # iteration count the program bakes in (None for data-dependent
    # trip counts, e.g. the t_end while_loop)
    steps: Optional[int]
    flops: float
    bytes_accessed: float
    transcendentals: float
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    generated_code_bytes: int
    peak_bytes: int
    compile_seconds: float
    # the static model's per-step prediction for the engaged rung
    # (None where the model has no opinion)
    model_bytes_per_step: Optional[float]
    model_flops_per_step: Optional[float]
    # persistent AOT executable cache (tuning/aot_cache.py): "hit"
    # when this executable was deserialized instead of compiled (then
    # compile_seconds is the load time and compile_seconds_saved the
    # original build's compile cost), "store"/"miss" otherwise; None
    # when the cache is disabled
    aot: Optional[str] = None
    compile_seconds_saved: Optional[float] = None
    # buffer donation (ISSUE 19): True when the program donates its
    # state operand (XLA aliases it into the output — no second
    # state-sized HBM buffer per dispatch)
    donated: bool = False
    # Mosaic kernels in the compiled program (``tpu_custom_call``
    # sites in its text): 0 means no Pallas kernel runs on the chip —
    # a generic program, or interpret mode on the CPU
    kernel_calls: int = 0

    def to_fields(self) -> dict:
        return dataclasses.asdict(self)


def _normalize_cost(ca) -> dict:
    """``Compiled.cost_analysis()`` -> flat floats (it returns a list of
    one dict on current jax; keys are XLA's own strings)."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
    }


def _normalize_memory(ma) -> dict:
    """``Compiled.memory_analysis()`` -> byte-sized ints. ``peak_bytes``
    prefers an explicit backend-reported peak attribute and falls back
    to the argument+output+temp footprint sum (the executable's
    unavoidable live set)."""
    out = {"argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
           "generated_code_bytes": 0, "peak_bytes": 0}
    if ma is None:
        return out
    for field, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[field] = int(v)
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None:
        alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
        peak = (out["argument_bytes"] + out["output_bytes"]
                + out["temp_bytes"] + alias)
    out["peak_bytes"] = int(peak)
    return out


def records(solver) -> List[ExecRecord]:
    """The executables captured for one solver's dispatch cache (in
    build order; survives ``_cache.clear()`` — they are history)."""
    return list(getattr(solver, "_xla_records", ()) or ())


def primary_record(recs: List[ExecRecord]) -> Optional[ExecRecord]:
    """The record of the run's main program: the deepest-stepping
    executable built last (warm-up programs bake ``steps=1``; the timed
    chunk program bakes the chunk length)."""
    best = None
    for i, r in enumerate(recs):
        rank = ((r.steps or 1), i)
        if best is None or rank >= best[0]:
            best = (rank, r)
    return best[1] if best else None


class _IntrospectedDispatch:
    """Callable wrapping one dispatch-cache entry.

    First call: AOT lower+compile the jitted program on the concrete
    arguments (the one compile the jit wrapper would have paid at the
    same moment), capture the executable's cost/memory analyses and
    compile seconds, emit ``xla:cost``, then execute the compiled
    object — this call and every later one whose arguments carry the
    same shapes, dtypes and shardings. A call whose arguments differ
    falls back permanently to the plain jitted callable, which
    retraces; a call under a transformation (tracer arguments) goes to
    it too. Nothing else is caught: a compile failure (a Mosaic
    rejection) reaches the kernel ladder as raised, and a failure while
    the executable runs (a device error, an OOM) reaches the caller —
    re-running it through jit would hide it and, with donation, read a
    consumed buffer.
    """

    def __init__(self, fn, solver, key: str, steps: Optional[int] = None,
                 aot_key: Optional[str] = None, donated: bool = False):
        self._fn = fn
        self._solver = solver
        self._key = key
        self._steps = steps
        self._aot_key = aot_key
        self._donated = bool(donated)
        self._compiled = None
        self._signature = None
        self._fallback = False
        self.record: Optional[ExecRecord] = None

    def prewarm(self, shaped_args) -> Optional[str]:
        """Speculative AOT resolve (ISSUE 19): look the program up in
        the persistent store against ABSTRACT operands
        (``jax.ShapeDtypeStruct`` leaves carry the same aval
        fingerprint as the concrete arrays) and deserialize on a hit —
        NEVER compiles cold, so a miss costs one file stat. Returns
        ``"hit"`` (executable now resident — the first real call skips
        both compile and load), ``"miss"``, ``"resident"`` (already
        compiled), or ``None`` (cache off / fallback engaged)."""
        from multigpu_advectiondiffusion_tpu.tuning import aot_cache

        if self._fallback:
            return None
        if self._compiled is not None:
            return "resident"
        if not (self._aot_key and aot_cache.enabled()):
            return None
        full_key = (
            f"{self._aot_key}|"
            f"avals={aot_cache.aval_fingerprint(shaped_args)}"
        )
        loaded = aot_cache.load(full_key, shaped_args)
        if loaded is None:
            return "miss"
        compiled, meta = loaded
        self._compiled = compiled
        self.record = _capture(
            compiled, self._solver, self._key, self._steps,
            meta["load_seconds"], aot="hit",
            compile_seconds_saved=meta["compile_seconds_saved"],
            donated=self._donated,
        )
        return "hit"

    def _aot_resolve(self, args):
        """Persistent AOT cache (tuning/aot_cache.py): returns
        ``(compiled, compile_seconds, aot_status, saved)`` — loading
        the stored executable on a hit, compiling (and storing) on a
        miss. ``aot_status`` is None when the cache is off."""
        from multigpu_advectiondiffusion_tpu.tuning import aot_cache

        full_key = None
        if self._aot_key and aot_cache.enabled():
            full_key = (
                f"{self._aot_key}|avals={aot_cache.aval_fingerprint(args)}"
            )
            loaded = aot_cache.load(full_key, args)
            if loaded is not None:
                compiled, meta = loaded
                return (
                    compiled, meta["load_seconds"], "hit",
                    meta["compile_seconds_saved"],
                )
        t0 = time.perf_counter()
        compiled = self._fn.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        if full_key is not None:
            persisted = aot_cache.store(full_key, args, compiled,
                                        compile_s)
            return compiled, compile_s, "store" if persisted else "miss", None
        return compiled, compile_s, None, None

    def __call__(self, *args):
        import jax

        if self._fallback or any(isinstance(a, jax.core.Tracer)
                                 for a in jax.tree.leaves(args)):
            # under a transformation (jax.grad through a solve) only
            # the jitted callable can trace
            return self._fn(*args)
        sig = _signature(args)
        if self._compiled is None:
            from multigpu_advectiondiffusion_tpu.utils.profiling import (
                COMPILE_SPAN,
                INTROSPECT_SPAN,
                annotate,
            )

            with annotate(COMPILE_SPAN, key=self._key):
                compiled, compile_s, aot, saved = self._aot_resolve(args)
            self._compiled = compiled
            self._signature = sig
            with annotate(INTROSPECT_SPAN, key=self._key):
                self.record = _capture(
                    compiled, self._solver, self._key, self._steps,
                    compile_s, aot=aot, compile_seconds_saved=saved,
                    donated=self._donated,
                )
        elif self._signature is None:
            self._signature = sig  # prewarmed from shapes: first call
        elif sig != self._signature:
            # aval/sharding drift vs the first call: retrace via jit
            self._fallback = True
            return self._fn(*args)
        return self._compiled(*args)


def _signature(args) -> tuple:
    """What a compiled executable was built for: each argument leaf's
    shape, dtype and sharding (``None`` for plain Python scalars)."""
    import jax

    return tuple(
        (getattr(a, "shape", None), getattr(a, "dtype", type(a)),
         getattr(a, "sharding", None))
        for a in jax.tree.leaves(args)
    )


def _capture(compiled, solver, key: str, steps: Optional[int],
             compile_s: float, aot: Optional[str] = None,
             compile_seconds_saved: Optional[float] = None,
             donated: bool = False,
             ) -> Optional[ExecRecord]:
    """Build (and register + emit) the ExecRecord for one compiled
    executable; every probe is individually fault-tolerant."""
    try:
        import jax

        backend = jax.default_backend()
    except Exception:
        backend = "unknown"
    try:
        cost = _normalize_cost(compiled.cost_analysis())
    except Exception:
        cost = _normalize_cost(None)
    try:
        mem = _normalize_memory(compiled.memory_analysis())
    except Exception:
        mem = _normalize_memory(None)
    try:
        kernel_calls = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"'
        )
    except Exception:
        kernel_calls = 0
    stepper = impl = None
    model_bytes = model_flops = None
    devices = 1
    try:
        devices = (
            1 if solver.mesh is None else int(solver.mesh.devices.size)
        )
        mode = "t_end" if key in ("adv", "fused_adv") else "iters"
        eng = solver.engaged_path(mode=mode)
        stepper, impl = eng.get("stepper"), eng.get("impl")
        from multigpu_advectiondiffusion_tpu.telemetry import costmodel

        model = costmodel.solver_step_cost(solver, stepper)
        if model is not None:
            model_bytes = float(model.hbm_bytes)
            model_flops = float(model.flops)
    except Exception:
        pass
    record = ExecRecord(
        key=key,
        solver=type(solver).__name__,
        stepper=stepper,
        impl=impl,
        backend=backend,
        devices=devices,
        steps=steps,
        compile_seconds=round(compile_s, 6),
        model_bytes_per_step=model_bytes,
        model_flops_per_step=model_flops,
        aot=aot,
        compile_seconds_saved=(
            None if compile_seconds_saved is None
            else round(compile_seconds_saved, 6)
        ),
        donated=bool(donated),
        kernel_calls=kernel_calls,
        **cost,
        **mem,
    )
    try:
        recs = getattr(solver, "_xla_records", None)
        if recs is None:
            recs = solver._xla_records = []
        recs.append(record)
    except Exception:
        pass
    from multigpu_advectiondiffusion_tpu import telemetry

    telemetry.event("xla", "cost", **record.to_fields())
    return record


def wrap_dispatch(fn, solver, key: str, steps: Optional[int] = None,
                  aot_key: Optional[str] = None,
                  donated: bool = False):
    """Dispatch-layer hook: wrap a freshly built jitted program for
    measured introspection (no-op passthrough when ``TPUCFD_XPROF=0``
    or the builder returned something un-lowerable). ``aot_key``
    additionally routes the first-call compile through the persistent
    AOT executable cache (tuning/aot_cache.py); ``donated`` marks a
    program that donates its state operand (recorded on the
    ``xla:cost`` event — the bit also rides the AOT key upstream)."""
    if not enabled() or not hasattr(fn, "lower"):
        return fn
    return _IntrospectedDispatch(fn, solver, key, steps=steps,
                                 aot_key=aot_key, donated=donated)


# --------------------------------------------------------------------- #
# Device-memory watermarks
# --------------------------------------------------------------------- #
_watermark = {
    "peak": 0, "last": 0, "limit": None, "source": None, "samples": 0,
}


def device_memory_stats() -> Optional[list]:
    """Per-device ``memory_stats()`` dicts, or ``None`` when the
    backend provides none (CPU)."""
    try:
        import jax

        stats = [d.memory_stats() for d in jax.local_devices()]
    except Exception:
        return None
    stats = [s for s in stats if s]
    return stats or None


def live_array_bytes() -> int:
    """Byte census of every live ``jax.Array`` in the process (logical
    nbytes — the CPU-testable fallback when the backend reports no
    memory stats)."""
    try:
        import jax

        return int(sum(
            int(getattr(a, "nbytes", 0) or 0) for a in jax.live_arrays()
        ))
    except Exception:
        return 0


def sample_watermark(emit: bool = True, **fields) -> dict:
    """One device-memory sample: backend-reported where available,
    live-arrays census otherwise. Updates the process-level running
    peak and (``emit``) streams a ``mem:watermark`` event; extra
    ``fields`` (e.g. ``step``) ride along."""
    stats = device_memory_stats()
    if stats:
        in_use = sum(int(s.get("bytes_in_use", 0) or 0) for s in stats)
        peak = sum(
            int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)) or 0)
            for s in stats
        )
        limit = sum(int(s.get("bytes_limit", 0) or 0) for s in stats) or None
        source = "device_stats"
    else:
        in_use = live_array_bytes()
        peak = in_use
        limit = None
        source = "live_arrays"
    _watermark["samples"] += 1
    _watermark["last"] = int(in_use)
    _watermark["peak"] = max(_watermark["peak"], int(peak), int(in_use))
    _watermark["limit"] = limit if limit is not None else _watermark["limit"]
    _watermark["source"] = source
    sample = {
        "bytes_in_use": int(in_use),
        "peak_bytes": _watermark["peak"],
        "limit_bytes": limit,
        "source": source,
    }
    if emit:
        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.event("mem", "watermark", **sample, **fields)
    return sample


def reset_watermarks() -> None:
    """Zero the running peak (run boundary)."""
    _watermark.update(
        peak=0, last=0, limit=None, source=None, samples=0
    )


def watermark_summary() -> Optional[dict]:
    """The run-level memory block (``RunSummary.memory``): peak bytes
    in use, last sample, the backend-reported limit and the headroom
    under it (``None`` without a sample or a limit)."""
    if not _watermark["samples"]:
        return None
    limit = _watermark["limit"]
    return {
        "peak_bytes_in_use": _watermark["peak"],
        "bytes_in_use": _watermark["last"],
        "limit_bytes": limit,
        "headroom_bytes": (
            int(limit) - _watermark["peak"] if limit else None
        ),
        "source": _watermark["source"],
        "samples": _watermark["samples"],
    }


# --------------------------------------------------------------------- #
# Measured-vs-modeled reconciliation
# --------------------------------------------------------------------- #
def measured_summary(solver, iters: Optional[int] = None,
                     seconds: Optional[float] = None) -> Optional[dict]:
    """The run's measured-introspection block: the primary executable's
    XLA per-step bytes/flops (global: per-device counts x mesh size)
    next to the cost model's prediction (ratio + tolerance-band flag),
    achieved rates against the configured peak, compile seconds over
    every program built. ``None`` when no executable was captured."""
    recs = records(solver)
    rec = primary_record(recs)
    if rec is None:
        return None
    devices = max(1, rec.devices)
    xla_bytes = rec.bytes_accessed * devices
    xla_flops = rec.flops * devices
    out = {
        "stepper": rec.stepper,
        "executables": len(recs),
        "devices": devices,
        "xla_bytes_per_step": xla_bytes,
        "xla_flops_per_step": xla_flops,
        "transcendentals_per_step": rec.transcendentals * devices,
        "peak_bytes": rec.peak_bytes,
        "kernel_calls": rec.kernel_calls,
        "compile_seconds": round(
            sum(r.compile_seconds for r in recs), 6
        ),
    }
    tol = tolerance_factor()
    out["tolerance_factor"] = tol
    if rec.model_bytes_per_step and xla_bytes > 0:
        ratio = rec.model_bytes_per_step / xla_bytes
        out["model_bytes_per_step"] = rec.model_bytes_per_step
        out["model_bytes_ratio"] = round(ratio, 4)
        out["bytes_within_tolerance"] = bool(1.0 / tol <= ratio <= tol)
    if rec.model_flops_per_step and xla_flops > 0:
        ratio = rec.model_flops_per_step / xla_flops
        out["model_flops_per_step"] = rec.model_flops_per_step
        out["model_flops_ratio"] = round(ratio, 4)
        out["flops_within_tolerance"] = bool(1.0 / tol <= ratio <= tol)
    if iters and seconds and seconds > 0:
        out["achieved_gbs"] = round(
            xla_bytes * iters / seconds / 1e9, 4
        )
        out["achieved_gflops"] = round(
            xla_flops * iters / seconds / 1e9, 4
        )
        from multigpu_advectiondiffusion_tpu.telemetry import costmodel

        peak_b, peak_f = costmodel.peak_rates()
        out["peak_gbs"] = round(peak_b * devices / 1e9, 3)
        out["peak_gflops"] = round(peak_f * devices / 1e9, 3)
        if peak_b:
            out["measured_bw_pct"] = round(
                100.0 * out["achieved_gbs"] / out["peak_gbs"], 2
            )
    return out
