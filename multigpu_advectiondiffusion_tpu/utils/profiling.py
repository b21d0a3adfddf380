"""Profiling and tracing helpers.

TPU replacement for the reference's profiling layer (SURVEY §5): per-rank
``nvprof`` wrapping (``Diffusion3d_Baseline/profile.sh:2``) becomes
``jax.profiler`` traces viewable in TensorBoard/Perfetto. The program's
host spans (``TraceAnnotation`` events) land in the same capture as the
device ops, on the host's clock; a v5e's device planes read up to ~2 ms
early against it, so compare host times with host times:

* ``tpucfd.dispatch`` — one per public driver call (``run``, ``step``,
  ``advance_to``, ``run_ensemble``, ``advance_to_ensemble``), opened
  first and closed on return, with the stats ``op``, ``steps`` (where
  the call fixes them), ``programs`` and ``cache`` (:class:`dispatch`);
  its children ``tpucfd.dispatch.resolve`` (rung, stepper, halo context
  and dispatch-cache lookup), ``tpucfd.<op>[<stepper>]`` (the call's
  body under the engaged rung; stats ``hbm_bytes_per_step``, the
  engaged stepper's planned HBM traffic, ``halo_bytes_per_step``, what
  a shard sends through the halo exchange, ``block``, its kernel
  block, and ``y_dead_rows``, the y rows its kernels work past the
  plane, where it declares them), ``tpucfd.dispatch.execute`` (the
  compiled program's call, which returns before the device runs it) and
  ``tpucfd.dispatch.state`` (the returned state, its ``it`` update);
* ``tpucfd.compile`` (stat ``key``) around a program's first-call
  lower+compile or AOT load, and ``tpucfd.introspect`` around the
  capture of its cost and memory analyses (``telemetry/xprof.py``).
"""

from __future__ import annotations

import contextlib
import functools

import jax

DISPATCH_SPAN = "tpucfd.dispatch"
RESOLVE_SPAN = "tpucfd.dispatch.resolve"
EXECUTE_SPAN = "tpucfd.dispatch.execute"
STATE_SPAN = "tpucfd.dispatch.state"
COMPILE_SPAN = "tpucfd.compile"
INTROSPECT_SPAN = "tpucfd.introspect"


# jax.profiler supports exactly one trace per process; this flag makes
# trace() idempotent (a nested/duplicate request no-ops instead of
# raising) and lets the recovery path below distinguish "we hold the
# trace" from "someone else leaked one".
_trace_active = [False]


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace: ``with trace('/tmp/trace'): run(...)``.

    View with TensorBoard (profile plugin) or Perfetto.

    Exception-safe and idempotent: the trace is stopped on EVERY exit
    path (an exception raised mid-solve can never leak an open
    ``jax.profiler`` trace that poisons the process's next
    ``start_trace``); a nested ``trace()`` inside an active one is a
    no-op (one capture, the outer owner closes it); and if a *previous*
    context leaked an open trace anyway (e.g. a hard-killed thread),
    the stale trace is stopped and the capture retried once instead of
    failing every later profiling request in the process.
    """
    if _trace_active[0]:
        yield  # nested request: the outer trace already captures this
        return
    try:
        jax.profiler.start_trace(log_dir)
    except Exception:
        # a leaked open trace from a poisoned predecessor: close it and
        # retry once — a second failure is a real error and propagates
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        jax.profiler.start_trace(log_dir)
    _trace_active[0] = True
    try:
        yield
    finally:
        _trace_active[0] = False
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass  # teardown must never mask the body's exception


class annotate:
    """Named ``jax.profiler.TraceAnnotation`` span, usable two ways:

    * decorator — ``@annotate("solve")`` wraps the function in the span
      (``functools.wraps`` preserved, so profiler timelines and
      tracebacks keep the wrapped function's name/docstring);
    * context manager — ``with annotate("halo-exchange"): ...`` labels
      an ad-hoc host-side region (e.g. one supervised chunk) in the
      captured trace.

    Keyword ``stats`` become stats of the span's trace event; pass only
    values the host already holds.
    """

    def __init__(self, name: str, **stats):
        self.name = name
        self.stats = stats
        self._span = None

    def __call__(self, fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(self.name, **self.stats):
                return fn(*a, **k)

        return inner

    def __enter__(self):
        self._span = jax.profiler.TraceAnnotation(self.name, **self.stats)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        span, self._span = self._span, None
        return span.__exit__(*exc)


class dispatch(annotate):
    """The ``tpucfd.dispatch`` span of one public driver call.

    Stats ``op`` and ``steps`` (omitted where the trip count is data-
    dependent) are set on entry; on exit ``programs`` (compiled programs
    the call invoked through the dispatch cache, :meth:`program`) and,
    when there was one, ``cache`` (``miss`` if the cache built a program
    for this call, else ``hit``)."""

    def __init__(self, op: str, steps=None):
        stats = {"op": op}
        if steps is not None:
            stats["steps"] = int(steps)
        super().__init__(DISPATCH_SPAN, **stats)
        self.programs = 0
        self.built = False

    def program(self, built: bool) -> None:
        """One program invoked through the dispatch cache; ``built``
        when the cache had to build it."""
        self.programs += 1
        self.built = self.built or built

    def __exit__(self, *exc):
        counts = {"programs": self.programs}
        if self.programs:
            counts["cache"] = "miss" if self.built else "hit"
        self._span.set_metadata(**counts)
        return super().__exit__(*exc)
