"""Measured rung + exchange-cadence selection (``impl="auto"``).

PR 1 left the stepper ladder's top-rung selection to a deliberately
conservative static model ("deep grids keep the measured per-stage
default until a TPU session measures the slab rung"), and the
communication-avoiding k-step schedule adds a second axis — exchange
cadence — no static model prices credibly across interconnects. This
module replaces that last hand-tuned heuristic with *measurement*:

1. build the candidate list for the config's ``(rung, k)`` space —
   ``fused-stage`` at the per-step cadence plus the slab rung at every
   k the shard can serve;
2. seed with the PR 3 cost model (``telemetry/costmodel``): modeled
   step time = max(HBM, FLOP) roofline x the deep-halo recompute factor
   + the exchange latency/bandwidth term — candidates far off the
   modeled best are pruned before any device time is spent. The peak
   rates behind that roofline consult the measured calibration record
   (``telemetry/calibration.py``) ahead of the env-assumed defaults,
   so once any run has demonstrated real bandwidth on this rig the
   pruning runs on measured rather than assumed peaks (the
   ``tune:candidates`` event carries the provenance);
3. time the survivors with the bench harness's own ``timed_run``
   (median-of-reps, same sync discipline as every published number);
4. persist the winner to the atomic JSON cache (``tuning/cache.py``),
   keyed by ``(solver, shape, dtype, mesh, backend)`` — the same key
   resolves to the same decision forever after, without re-measurement.

Every lookup, measurement, pruning and decision is a ``tune:*``
telemetry event, so a tuned bench row is auditable from the stream.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

from multigpu_advectiondiffusion_tpu.telemetry import costmodel
from multigpu_advectiondiffusion_tpu.tuning.cache import TuningCache

# candidate chunk lengths for the comm-avoiding schedule (1 = per-step)
K_CANDIDATES = (1, 2, 4, 8)


def _emit(name: str, **fields) -> None:
    from multigpu_advectiondiffusion_tpu import telemetry

    telemetry.event("tune", name, **fields)


def _fused_halo(kind: str, cfg) -> int:
    """Per-step fused ghost depth G = 3h of the config's stencil,
    resolved through the registry's ``stage_radius`` hook (legacy
    literal fallback for unregistered config doubles)."""
    from multigpu_advectiondiffusion_tpu.models import registry

    spec = registry.spec_for_config(cfg)
    if spec is not None and spec.stage_radius is not None:
        return 3 * int(spec.stage_radius(cfg))
    if kind == "diffusion":
        from multigpu_advectiondiffusion_tpu.ops.pallas.laplacian import R

        return 3 * R
    from multigpu_advectiondiffusion_tpu.ops.weno import HALO

    return 3 * HALO[getattr(cfg, "weno_order", 5)]


def _mesh_tokens(mesh, decomp):
    if mesh is None:
        return "mesh=1"
    sizes = ",".join(f"{n}:{s}" for n, s in mesh.shape.items())
    if decomp is None:
        # members-only ensemble meshes carry no spatial decomposition
        return f"mesh={sizes};decomp=-"
    axes = ",".join(
        f"{ax}:{'|'.join(nm) if isinstance(nm, tuple) else nm}"
        for ax, nm in decomp.axes
    )
    return f"mesh={sizes};decomp={axes}"


def make_key(solver_cls, cfg, mesh, decomp, backend: str,
             ensemble: int = 1) -> str:
    """The tuning key: everything that changes which ``(rung, k)`` wins.
    Kernel-strategy knobs that the tuner itself decides (impl,
    steps_per_exchange) are excluded; physics scalars that do not change
    kernel structure (diffusivity value, flux params) are too.
    ``ensemble`` is the batched-engine member count B — a B=64 decision
    (amortized dispatch, different winning rung economics) must never
    be served to a B=1 run, so it is a first-class key dimension."""
    kind = costmodel.solver_kind(cfg) or type(cfg).__name__
    shape = "x".join(map(str, cfg.grid.shape))
    parts = [
        solver_cls.__name__,
        kind,
        f"shape={shape}",
        f"dtype={cfg.dtype}",
        # storage precision (ISSUE 16): a bf16-storage decision (half
        # the HBM/wire bytes — different winning rung economics) must
        # never be served to a native-precision run, and vice versa
        f"prec={getattr(cfg, 'precision', 'native') or 'native'}",
        f"integ={cfg.integrator}",
        f"overlap={getattr(cfg, 'overlap', None)}",
        _mesh_tokens(mesh, decomp),
        f"backend={backend}",
        f"ens={max(1, int(ensemble))}",
    ]
    from multigpu_advectiondiffusion_tpu.models import registry

    spec = registry.spec_for_config(cfg)
    if spec is not None and spec.key_extras is not None:
        # family-specific key parts come from the registration spec —
        # a third model brings its own, never edits this function
        parts += [str(p) for p in spec.key_extras(cfg)]
    elif kind == "burgers":
        parts += [
            f"weno={cfg.weno_order}-{cfg.weno_variant}",
            f"adaptive={bool(cfg.adaptive_dt)}",
            f"viscous={bool(getattr(cfg, 'nu', 0.0))}",
        ]
    elif kind == "diffusion":
        parts += [
            f"order={getattr(cfg, 'order', 4)}",
            f"geom={getattr(cfg, 'geometry', 'cartesian')}",
        ]
    return "|".join(parts)


def _zslab_only(solver) -> bool:
    sharded = solver._sharded_axes()
    return bool(sharded) and all(ax == 0 for ax in sharded)


def candidates(solver_cls, cfg, mesh, decomp) -> list:
    """``[{"impl", "steps_per_exchange"}, ...]`` the config can engage.

    A probe solver (impl="pallas") answers the eligibility questions the
    dispatch layer already owns — the tuner never re-implements VMEM /
    dtype / decomposition gates, it asks them."""
    probe = solver_cls(
        dataclasses.replace(
            cfg, impl="pallas", steps_per_exchange=1,
            exchange="collective",
        ),
        mesh=mesh,
        decomp=decomp,
    )
    kind = costmodel.solver_kind(cfg)
    out = [{"impl": "pallas", "steps_per_exchange": 1,
            "exchange": "collective"}]
    fused = probe._fused_stepper()
    if fused is None or probe.grid.ndim != 3 or kind is None:
        return out  # heuristic best-available is the only candidate
    fixed_dt = not getattr(cfg, "adaptive_dt", False)
    out = [{"impl": "pallas_stage", "steps_per_exchange": 1,
            "exchange": "collective"}]
    slab_ok = fixed_dt
    if slab_ok:
        # slab eligibility via the dispatch's own gate: a pinned probe
        # either engages the slab rung or raises/declines
        try:
            pin = solver_cls(
                dataclasses.replace(
                    cfg, impl="pallas_slab", steps_per_exchange=1,
                    exchange="collective",
                ),
                mesh=mesh,
                decomp=decomp,
            )
            slab_ok = (
                pin.engaged_path()["stepper"] == "fused-whole-run-slab"
            )
        except ValueError:
            slab_ok = False
    if not slab_ok:
        return out
    out.append({"impl": "pallas_slab", "steps_per_exchange": 1,
                "exchange": "collective"})
    if mesh is not None and _zslab_only(probe):
        lz = probe.decomp.local_shape(mesh, cfg.grid.shape)[0]
        G = _fused_halo(kind, cfg)
        for k in K_CANDIDATES[1:]:
            if lz >= k * G:
                out.append({"impl": "pallas_slab",
                            "steps_per_exchange": k,
                            "exchange": "collective"})
        # in-kernel remote-DMA rung (exchange='dma'): eligibility is
        # asked from the dispatch's own gates (backend, single-axis
        # mesh, uniform dma block viability) by constructing a pinned
        # probe per servable cadence — a raise means the combo cannot
        # engage. The rung has no credible static cost model (its
        # point is comm/compute overlap the roofline cannot see), so
        # it is never pruned: it enters the decision only by WINNING
        # measurements.
        for k in K_CANDIDATES:
            if lz < k * G:
                continue
            try:
                pin = solver_cls(
                    dataclasses.replace(
                        cfg, impl="pallas_slab", steps_per_exchange=k,
                        exchange="dma",
                    ),
                    mesh=mesh,
                    decomp=decomp,
                )
                eng = pin.engaged_path()
            except ValueError:
                continue
            if (
                eng["stepper"] == "fused-whole-run-slab"
                and eng.get("exchange") == "dma"
            ):
                out.append({"impl": "pallas_slab",
                            "steps_per_exchange": k,
                            "exchange": "dma"})
    return out


def modeled_step_seconds(cfg, lshape, cand, devices: int,
                         device_kind: Optional[str] = None
                         ) -> Optional[float]:
    """Cost-model seconds for ONE step of one shard under a candidate —
    the pruning metric. None when the model has no opinion (the
    candidate is then never pruned)."""
    import numpy as np

    kind = costmodel.solver_kind(cfg)
    if kind is None:
        return None
    if cand.get("exchange", "collective") == "dma":
        # the in-kernel rung's value is overlap the per-step roofline
        # cannot price; no opinion -> never pruned, always measured
        return None
    stepper = {
        "pallas_slab": "fused-whole-run-slab",
        "pallas_stage": "fused-stage",
    }.get(cand["impl"])
    if stepper is None:
        return None
    kwargs = costmodel.solver_cost_kwargs(cfg)
    itemsize = np.dtype(cfg.dtype).itemsize
    cost = costmodel.step_cost(kind, lshape, itemsize, stepper, **kwargs)
    peak_b, peak_f = costmodel.peak_rates(device_kind)
    t = max(
        cost.hbm_bytes / peak_b if peak_b else 0.0,
        cost.flops / peak_f if peak_f else 0.0,
    )
    k = cand["steps_per_exchange"]
    G = _fused_halo(kind, cfg)
    if stepper == "fused-whole-run-slab" and k > 1:
        t *= costmodel.deep_halo_recompute_factor(lshape[0], G, k)
    if devices > 1:
        plane = itemsize
        for n in lshape[1:]:
            plane *= n
        if stepper == "fused-stage":
            # one h-deep refresh per RK stage
            h = G // 3
            t += costmodel.halo_exchange_seconds(
                3 * 2 * h * plane, messages=3, kind=device_kind
            )
        else:
            # one k*G-deep exchange per k steps: same bytes per step,
            # 1/k of the messages — the comm-avoiding tradeoff
            t += costmodel.halo_exchange_seconds(
                2 * G * plane, messages=1.0 / k, kind=device_kind
            )
    return t


def measure_candidate(solver_cls, cfg, mesh, decomp, cand,
                      iters: int, reps: int) -> dict:
    """Median-of-reps MLUPS of one candidate, via the bench harness's
    own timing discipline (``bench/timing.timed_run``)."""
    from multigpu_advectiondiffusion_tpu.bench.timing import timed_run
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    solver = solver_cls(
        dataclasses.replace(
            cfg,
            impl=cand["impl"],
            steps_per_exchange=cand["steps_per_exchange"],
            exchange=cand.get("exchange", "collective"),
        ),
        mesh=mesh,
        decomp=decomp,
    )
    timing = timed_run(solver, solver.initial_state(), iters, reps=reps)
    rate = mlups(
        cfg.grid.num_cells, iters, STAGES[cfg.integrator],
        timing.median_seconds,
    )
    return {
        "mlups": round(rate, 2),
        "seconds": round(timing.median_seconds, 6),
        "spread": round(timing.spread, 4),
        "engaged": solver.engaged_path()["stepper"],
    }


def ensemble_candidates(solver_cls, cfg, mesh, decomp,
                        members: int) -> list:
    """The rung space the batched engine serves at ``B = members``,
    asked from the dispatch's own eligibility gates (the tuner never
    re-implements them): the generic vmapped rung always serves; the
    fused-stage vmap and the B-folded slab rung serve where an
    unsharded-spatial probe engages them. A members x spatial mesh
    (``decomp`` with real extents) serves the generic rung only —
    spatially sharded fused steppers decline the member axis."""
    out = [{"impl": "xla", "steps_per_exchange": 1}]
    if decomp is not None and bool(decomp.axes):
        return out
    for impl, label in (
        ("pallas_stage", "fused-stage"),
        ("pallas_slab", "fused-whole-run-slab"),
    ):
        try:
            probe = solver_cls(
                dataclasses.replace(cfg, impl=impl, steps_per_exchange=1)
            )
            fused = probe._fused_stepper()
        except ValueError:
            continue
        if fused is not None and fused.engaged_label == label:
            out.append({"impl": impl, "steps_per_exchange": 1})
    return out


def measure_ensemble_candidate(solver_cls, cfg, mesh, decomp, cand,
                               members: int, iters: int,
                               reps: int) -> dict:
    """Median-of-reps MLUPS*members of one candidate MEASURED AT THE
    ACTUAL B — one wall-timed batched dispatch (launch overhead
    included: amortizing it is the point), B identical uniform-physics
    members, under the caller's mesh."""
    import statistics
    import time as _time

    from multigpu_advectiondiffusion_tpu.bench.timing import sync
    from multigpu_advectiondiffusion_tpu.models.ensemble import (
        EnsembleSolver,
    )
    from multigpu_advectiondiffusion_tpu.timestepping.integrators import (
        STAGES,
    )
    from multigpu_advectiondiffusion_tpu.utils.metrics import mlups

    es = EnsembleSolver(
        solver_cls,
        dataclasses.replace(
            cfg, impl=cand["impl"], steps_per_exchange=1
        ),
        members, mesh=mesh, decomp=decomp,
    )
    est = es.initial_state()
    sync(es.run(est, iters).u)  # compile + warm-up, untimed
    times = []
    for _ in range(max(1, reps)):
        t0 = _time.perf_counter()
        sync(es.run(est, iters).u)
        times.append(_time.perf_counter() - t0)
    med = statistics.median(times)
    rate = mlups(
        cfg.grid.num_cells * members, iters, STAGES[cfg.integrator], med
    )
    return {
        "mlups": round(rate, 2),
        "seconds": round(med, 6),
        "spread": round(
            (max(times) - min(times)) / med if med > 0 else 0.0, 4
        ),
        "engaged": es.engaged_path()["stepper"],
    }


def autotune(solver_cls, cfg, mesh, decomp, cache: TuningCache, key: str,
             iters: int, reps: int, prune_ratio: float,
             ensemble: int = 1) -> dict:
    """Measure the pruned candidate space and persist the winner.
    ``ensemble > 1`` measures the BATCHED candidate space at the
    actual B (generic vmap / fused-stage vmap / B-folded slab, under
    the caller's members mesh) — no single-run proxy; every
    ``tune:measure`` row carries the member count."""
    import jax

    backend = jax.default_backend()
    devices = 1 if mesh is None else mesh.devices.size
    if ensemble > 1:
        return _autotune_ensemble(
            solver_cls, cfg, mesh, decomp, cache, key, iters, reps,
            ensemble, backend, devices,
        )
    lshape = (
        cfg.grid.shape
        if mesh is None
        else decomp.local_shape(mesh, cfg.grid.shape)
    )
    cands = candidates(solver_cls, cfg, mesh, decomp)
    best_model = None
    for c in cands:
        t = modeled_step_seconds(cfg, lshape, c, devices)
        c["modeled_us"] = None if t is None else round(t * 1e6, 3)
        if t is not None and (best_model is None or t < best_model):
            best_model = t
    for c in cands:
        # cost-model pruning: never prune the per-step baseline (k=1 on
        # the modeled-best rung family keeps the comparison honest) or
        # model-less candidates
        c["pruned"] = bool(
            best_model is not None
            and c["modeled_us"] is not None
            and c["steps_per_exchange"] > 1
            and c["modeled_us"] > prune_ratio * best_model * 1e6
        )
    _emit(
        "candidates", key=key,
        # pruning-peak provenance: modeled_us was computed against
        # these rates — "calibrated" means a measured peak
        # (telemetry/calibration.py) replaced the env/default
        # assumption, i.e. the tuner pruned with measured numbers
        peaks=costmodel.peak_info(),
        considered=[
            {k: c.get(k) for k in ("impl", "steps_per_exchange",
                                   "exchange", "modeled_us", "pruned")}
            for c in cands
        ],
    )
    live = [c for c in cands if not c["pruned"]]
    measured = []
    if len(live) == 1:
        choice = dict(live[0])
        choice["source"] = "static"  # nothing to race: no device time
    else:
        for c in live:
            try:
                m = measure_candidate(
                    solver_cls, cfg, mesh, decomp, c, iters, reps
                )
            except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                c["error"] = f"{type(exc).__name__}: {exc}"[:200]
                _emit("measure", key=key, impl=c["impl"],
                      steps_per_exchange=c["steps_per_exchange"],
                      exchange=c.get("exchange", "collective"),
                      error=c["error"])
                continue
            c.update(m)
            measured.append(c)
            _emit("measure", key=key, impl=c["impl"],
                  steps_per_exchange=c["steps_per_exchange"],
                  exchange=c.get("exchange", "collective"),
                  mlups=m["mlups"], seconds=m["seconds"])
        if not measured:
            raise RuntimeError(
                f"autotune: every candidate failed for key {key}"
            )
        choice = dict(max(measured, key=lambda c: c["mlups"]))
        choice["source"] = "measured"
    decision = {
        "impl": choice["impl"],
        "steps_per_exchange": choice["steps_per_exchange"],
        "exchange": choice.get("exchange", "collective"),
        "mlups": choice.get("mlups"),
        "source": choice["source"],
        "backend": backend,
        "devices": devices,
        "ensemble": max(1, int(ensemble)),
        "key": key,
        "tuner": {"iters": iters, "reps": reps,
                  "prune_ratio": prune_ratio},
        "candidates": [
            {
                k: c.get(k)
                for k in ("impl", "steps_per_exchange", "exchange",
                          "modeled_us", "pruned", "mlups", "seconds",
                          "spread", "engaged", "error")
                if k in c
            }
            for c in cands
        ],
        "created": time.time(),
    }
    cache.put(key, decision)
    _emit(
        "decision", key=key, impl=decision["impl"],
        steps_per_exchange=decision["steps_per_exchange"],
        exchange=decision["exchange"],
        mlups=decision["mlups"], source=decision["source"],
        cache=cache.path,
    )
    return decision


def _autotune_ensemble(solver_cls, cfg, mesh, decomp, cache, key,
                       iters, reps, ensemble, backend, devices):
    """The batched half of :func:`autotune`: enumerate the rungs the
    ensemble engine serves, MEASURE each at the actual B under the
    caller's mesh, persist the winner. The cost model has no batched
    opinion (its per-step roofline does not price vmap/fold overheads
    or dispatch amortization), so nothing is pruned — every candidate
    is raced, and the ``tune:measure`` rows carry B so a published
    batched decision is auditable from the stream."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        member_extent,
    )

    B = max(1, int(ensemble))
    msh = member_extent(mesh)
    cands = ensemble_candidates(solver_cls, cfg, mesh, decomp, B)
    _emit(
        "candidates", key=key, ensemble=B, member_sharding=msh,
        considered=[
            {k: c[k] for k in ("impl", "steps_per_exchange")}
            for c in cands
        ],
    )
    measured = []
    for c in cands:
        try:
            m = measure_ensemble_candidate(
                solver_cls, cfg, mesh, decomp, c, B, iters, reps
            )
        except Exception as exc:  # noqa: BLE001 — recorded, not fatal
            c["error"] = f"{type(exc).__name__}: {exc}"[:200]
            _emit("measure", key=key, impl=c["impl"],
                  steps_per_exchange=c["steps_per_exchange"],
                  ensemble=B, error=c["error"])
            continue
        c.update(m)
        measured.append(c)
        _emit("measure", key=key, impl=c["impl"],
              steps_per_exchange=c["steps_per_exchange"],
              ensemble=B, member_sharding=msh,
              mlups=m["mlups"], seconds=m["seconds"],
              engaged=m["engaged"])
    if not measured:
        raise RuntimeError(
            f"autotune: every batched candidate failed for key {key}"
        )
    choice = dict(max(measured, key=lambda c: c["mlups"]))
    choice["source"] = "measured"
    decision = {
        "impl": choice["impl"],
        "steps_per_exchange": 1,
        "exchange": "collective",
        "mlups": choice.get("mlups"),
        "source": "measured",
        "backend": backend,
        "devices": devices,
        "ensemble": B,
        "member_sharding": msh,
        "engaged": choice.get("engaged"),
        "key": key,
        "tuner": {"iters": iters, "reps": reps, "batched": True},
        "candidates": [
            {
                k: c.get(k)
                for k in ("impl", "steps_per_exchange", "mlups",
                          "seconds", "spread", "engaged", "error")
                if k in c
            }
            for c in cands
        ],
        "created": time.time(),
    }
    cache.put(key, decision)
    _emit(
        "decision", key=key, impl=decision["impl"],
        steps_per_exchange=1, mlups=decision["mlups"],
        source="measured", ensemble=B, member_sharding=msh,
        cache=cache.path,
    )
    return decision


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default
