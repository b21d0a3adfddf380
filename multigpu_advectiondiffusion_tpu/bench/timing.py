"""Device-honest wall-clock timing for benchmarks.

Synchronization is an actual host fetch: :func:`sync` fetches a scalar
reduction of the result, which (a) depends on every element of every
shard, so the timed window cannot end before the device work does, and
(b) is replicated, so it is addressable from any process in multi-host
runs. (The discipline dates from a pre-PR-1 setup on which
``Array.block_until_ready`` returned before the device had finished;
the benchmark keeps it so its numbers stay comparable.)

The fetch and the reduction cost a fixed overhead per call, so
:func:`timed_run` measures a zero-iteration run of the same jitted
program (same shapes, same sync) and subtracts it. This mirrors the
reference's accounting, which reports *kernel* time with the HtD/DtH
transfer segments timed separately between MPI barriers
(``MultiGPU/Diffusion3d_Baseline/main.c:139-147,184-187,305-307``), so
the MLUPS numbers remain comparable to the ``Run.m`` baselines. If the
subtraction is in the noise (tiny --quick grids), the raw, unsubtracted
time is used instead — conservative, never inflating.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, NamedTuple

import jax.numpy as jnp


def sync(arr) -> None:
    """Force completion of ``arr``'s producing computation via a
    device→host fetch that depends on all elements of all shards."""
    float(jnp.sum(arr))


class TimedRun(NamedTuple):
    seconds: float  # best-of-reps net execution time
    warmup_seconds: float  # compile + first full execution + sync
    median_seconds: float  # median-of-reps net execution time
    spread: float  # (max - min) / median of the per-rep net times
    outliers: int = 0  # stalled reps discarded and re-measured
    # spread over ALL measured reps including later-discarded ones —
    # keeps the full dispersion evidence in the artifact (a genuinely
    # bimodal row shows raw_spread >> spread, a stall shows one fat
    # outlier); equal to ``spread`` when nothing was discarded
    raw_spread: float = 0.0


# A rep whose net time exceeds this multiple of the running median is a
# stall (host preemption, a dispatch hiccup), not a measurement: with only
# ~5 reps a single stalled rep can land *in* the median. Driver-captured
# evidence: BENCH_r03 burgers2d spread 148x — one rep of a ~0.95 s
# window took minutes.
_OUTLIER_FACTOR = 3.0


def _timed(full: Callable, zero: Callable, reps: int) -> TimedRun:
    """Measure ``full()`` minus the fixed sync/dispatch overhead of
    ``zero()`` (the same jitted program at zero work), best- and
    median-of-``reps``. Stalled reps (> ``_OUTLIER_FACTOR`` x the
    running median of accepted reps) are discarded and re-measured, up
    to ``reps`` extra attempts; the count is reported so the artifact
    stays self-qualifying."""
    reps = max(1, reps)
    t0 = time.perf_counter()
    sync(full())  # compile + warm-up
    warmup = time.perf_counter() - t0
    sync(zero())

    bases, raws = [], []
    discarded = []  # stalled raw times, kept for raw_spread evidence
    outliers = 0
    budget = reps  # extra attempts for discarded reps
    while len(raws) < reps:
        t0 = time.perf_counter()
        sync(zero())
        base = time.perf_counter() - t0
        t0 = time.perf_counter()
        sync(full())
        raw = time.perf_counter() - t0
        if (
            len(raws) >= 1
            and budget > 0
            and raw > _OUTLIER_FACTOR * statistics.median(raws)
        ):
            outliers += 1
            budget -= 1
            discarded.append(raw)
            continue  # a stall, not a measurement — re-measure
        bases.append(base)
        raws.append(raw)
    base = min(bases)
    nets = [r - base for r in raws]
    # If the subtraction is within the observed jitter of the overhead
    # measurement itself (tiny --quick grids), publish the raw time
    # instead of a jitter-dominated rate — conservative, never inflating.
    noise = max(bases) - base
    raw_mode = min(nets) <= noise
    if raw_mode:
        nets = list(raws)
    # Retrospective guard: the running-median filter above cannot catch a
    # stall in the FIRST rep (nothing to compare against yet) — drop any
    # rep that still exceeds the factor against the full set's median.
    # loop-discarded stalls, converted once into the published units
    discarded = [d if raw_mode else d - base for d in discarded]
    med0 = statistics.median(nets)
    kept = [n for n in nets if n <= _OUTLIER_FACTOR * med0]
    if kept and len(kept) < len(nets):
        outliers += len(nets) - len(kept)
        discarded.extend(n for n in nets if n > _OUTLIER_FACTOR * med0)
        nets = kept
    best, med = min(nets), statistics.median(nets)
    spread = (max(nets) - min(nets)) / med if med > 0 else 0.0
    # pre-filter dispersion over every measured rep (kept + discarded),
    # in the same units as the published nets
    all_nets = nets + discarded
    raw_spread = (
        (max(all_nets) - min(all_nets)) / med if med > 0 else 0.0
    )
    return TimedRun(best, warmup, med, spread, outliers, raw_spread)


def timed_run(solver, state, iters: int, reps: int = 3) -> TimedRun:
    """Best/median-of-``reps`` net seconds for ``solver.run(state, iters)``."""
    return _timed(
        lambda: solver.run(state, iters).u,
        lambda: solver.run(state, 0).u,
        reps,
    )


class TimedAdvance(NamedTuple):
    timing: TimedRun
    steps: int  # steps the while-loop actually took to reach t_end


def timed_advance(solver, state, t_end: float, reps: int = 3) -> TimedAdvance:
    """Best/median-of-``reps`` net seconds for
    ``solver.advance_to(state, t_end)`` — the reference drivers' native
    ``while (t < tEnd)`` mode. The zero-work overhead run is the same
    jitted program asked to advance to ``state.t`` (zero loop trips)."""
    steps = int(solver.advance_to(state, t_end).it - state.it)
    t_start = float(state.t)
    timing = _timed(
        lambda: solver.advance_to(state, t_end).u,
        lambda: solver.advance_to(state, t_start).u,
        reps,
    )
    return TimedAdvance(timing, steps)
