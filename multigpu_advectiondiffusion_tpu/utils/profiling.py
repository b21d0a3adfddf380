"""Profiling and tracing helpers.

TPU replacement for the reference's profiling layer (SURVEY §5): per-rank
``nvprof`` wrapping (``Diffusion3d_Baseline/profile.sh:2``) becomes
``jax.profiler`` traces viewable in TensorBoard/Perfetto, and the
MPI_Wtime double-barrier walltime sandwich (``main.c:139-147,184-187``)
becomes :class:`Stopwatch` segments around ``block_until_ready``.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, Optional

import jax


def _fetch_sync(x):
    if hasattr(x, "dtype"):
        from multigpu_advectiondiffusion_tpu.bench.timing import sync

        sync(x)


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


class Stopwatch:
    """Named walltime segments (HtD/compute/DtH in the reference's
    summary become e.g. init/compile/solve/io here)."""

    def __init__(self):
        self.segments: Dict[str, float] = {}

    @contextlib.contextmanager
    def segment(self, name: str, sync: Optional[object] = None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if sync is not None:
                # Host-fetch sync, the benchmark's discipline (see
                # bench/timing.py).
                jax.tree.map(_fetch_sync, sync)
            self.segments[name] = (
                self.segments.get(name, 0.0) + time.perf_counter() - t0
            )

    def report(self) -> str:
        total = sum(self.segments.values())
        lines = [f"{'segment':<16} {'seconds':>10} {'share':>7}"]
        for name, s in self.segments.items():
            share = 100.0 * s / total if total else 0.0
            lines.append(f"{name:<16} {s:>10.4f} {share:>6.1f}%")
        lines.append(f"{'total':<16} {total:>10.4f}")
        return "\n".join(lines)


class annotate:
    """Named ``jax.profiler.TraceAnnotation`` span, usable two ways:

    * decorator — ``@annotate("solve")`` wraps the function in the span
      (``functools.wraps`` preserved, so profiler timelines and
      tracebacks keep the wrapped function's name/docstring);
    * context manager — ``with annotate("halo-exchange"): ...`` labels
      an ad-hoc host-side region (e.g. one supervised chunk) in the
      captured trace.
    """

    def __init__(self, name: str):
        self.name = name
        self._span = None

    def __call__(self, fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(self.name):
                return fn(*a, **k)

        return inner

    def __enter__(self):
        self._span = jax.profiler.TraceAnnotation(self.name)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        span, self._span = self._span, None
        return span.__exit__(*exc)
