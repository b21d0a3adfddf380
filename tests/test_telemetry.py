"""Telemetry subsystem suite (tier-1, CPU).

Covers the observability layer end to end: the JSONL event sink (span
nesting, counter accumulation, well-formedness), the profiling helpers
(the fixed ``annotate``, the dispatch layer's ``tpucfd.dispatch`` spans
in a captured trace), the static cost model against
hand-computed bytes/FLOPs for one diffusion and one WENO5 rung, the
supervised CLI run's ``--metrics`` stream (span + counter + physics
events, schema'd summary with mass-drift and roofline fields), the
ordered rollback events of a fault-injected run, and the multihost
initialize retry events.
"""

from __future__ import annotations

import json
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from multigpu_advectiondiffusion_tpu import (
    BurgersConfig,
    BurgersSolver,
    DiffusionConfig,
    DiffusionSolver,
    Grid,
    telemetry,
)
from multigpu_advectiondiffusion_tpu.cli.__main__ import main as cli_main
from multigpu_advectiondiffusion_tpu.models.ensemble import EnsembleSolver
from multigpu_advectiondiffusion_tpu.parallel.mesh import Decomposition
from multigpu_advectiondiffusion_tpu.resilience import faults, supervise_run
from multigpu_advectiondiffusion_tpu.telemetry import costmodel
from multigpu_advectiondiffusion_tpu.utils import profiling
from multigpu_advectiondiffusion_tpu.utils.profiling import annotate
from multigpu_advectiondiffusion_tpu.utils.summary import (
    SUMMARY_SCHEMA,
    RunSummary,
)

from host_trace import host_events


def _events(path) -> list:
    """Parse a JSONL stream; every line must be a JSON object."""
    out = []
    with open(path) as f:
        for line in f:
            assert line.endswith("\n"), "unterminated JSONL line"
            out.append(json.loads(line))
    return out


def _diffusion2d(**kw):
    cfg = DiffusionConfig(
        grid=Grid.make(16, 12, lengths=4.0), dtype="float32", **kw
    )
    return DiffusionSolver(cfg)


# --------------------------------------------------------------------- #
# Profiling helpers (annotate, the dispatch layer's spans)
# --------------------------------------------------------------------- #
def test_annotate_preserves_wrapped_metadata():
    @annotate("labeled-span")
    def solve_step(x):
        """Docstring the profiler label must not eat."""
        return x + 1

    assert solve_step.__name__ == "solve_step"
    assert "profiler label" in solve_step.__doc__
    assert solve_step(1) == 2


def test_annotate_usable_as_context_manager():
    with annotate("ad-hoc-region"):
        x = jnp.sum(jnp.ones((4, 4)))
    assert float(x) == 16.0


def _tops(events) -> list:
    return [e for e in events if e.name == profiling.DISPATCH_SPAN]


def _diffusion3d_small(**kw):
    cfg = DiffusionConfig(grid=Grid.make(12, 10, 8, lengths=3.0),
                          dtype="float32", **kw)
    return DiffusionSolver(cfg)


def test_dispatch_span_holds_every_host_call_of_run(tmp_path):
    """``SolverBase.run`` opens ``tpucfd.dispatch`` before anything
    else: every Python call ``run`` makes, bar the span's own machinery,
    lies inside it (the Python tracer records them on the same clock),
    and so do the host events of both device programs a chunk starts
    (the compiled block and the ``it`` update). The counts ride the
    span as stats: the first call builds its program, the second finds
    it in the dispatch cache."""
    solver = _diffusion3d_small(impl="xla")

    def two_chunks():
        state = solver.run(solver.initial_state(), 3)
        solver.run(state, 3).u.block_until_ready()

    events = host_events(tmp_path, two_chunks, python_tracer=True)
    tops = _tops(events)
    assert [t.stats for t in tops] == [
        {"op": "run", "steps": 3, "programs": 1, "cache": "miss"},
        {"op": "run", "steps": 3, "programs": 1, "cache": "hit"},
    ]
    calls = [e for e in events if re.fullmatch(r"\$base\.py:\d+ run", e.name)]
    assert len(calls) == 2
    machinery = re.compile(r"\$(profiling\.py|contextlib\.py|builtins)"
                           r"|\$base\.py:\d+ _dispatch$")
    for call, top in zip(calls, tops):
        assert call.holds(top)
        inside = [e for e in events if call.holds(e) and e is not call]
        made = [e for e in inside
                if e.name.startswith("$") and not machinery.match(e.name)]
        names = {e.name.split()[-1] for e in made}
        assert {"_with_ladder", "engaged_path", "_run_program", "_compiled",
                "_finish"} <= names
        outside = [e.name for e in made if not top.holds(e)]
        assert outside == []
        children = [e for e in inside
                    if e.name.startswith("tpucfd.") and e is not top]
        assert {profiling.RESOLVE_SPAN, profiling.EXECUTE_SPAN,
                profiling.STATE_SPAN, "tpucfd.run[generic-xla]"} <= {
                    e.name for e in children}
        assert all(top.holds(e) for e in children)
        assert top.start < min(e.start for e in children
                               if e.name == profiling.RESOLVE_SPAN)
        programs = {e.name for e in inside
                    if e.name.startswith("PjitFunction(")}
        assert any("block" in n for n in programs)
        assert any("add" in n for n in programs)
        assert all(top.holds(e) for e in inside
                   if e.name.startswith("PjitFunction("))


def test_dispatch_span_stats_read_no_device_value(tmp_path, monkeypatch):
    """No stat of the dispatch spans (nor anything else ``run`` does)
    reads a device value on the host: with every host read of a
    ``jax.Array`` made to fail, a cached chunk still dispatches and its
    span carries its stats."""
    from jax._src.array import ArrayImpl

    solver = _diffusion3d_small(impl="xla")
    state = solver.run(solver.initial_state(), 2)
    state.u.block_until_ready()

    def host_read(self):
        raise AssertionError("a device value was read on the host")

    out = []
    monkeypatch.setattr(ArrayImpl, "_value", property(host_read))
    events = host_events(tmp_path, lambda: out.append(solver.run(state, 2)))
    monkeypatch.undo()
    assert [t.stats for t in _tops(events)] == [
        {"op": "run", "steps": 2, "programs": 1, "cache": "hit"}]
    assert int(out[0].it) == int(state.it) + 2


def _ensemble(members=2):
    g = Grid.make(12, 10, 8, lengths=(1.2, 1.0, 0.8))
    cfg = DiffusionConfig(grid=g, dtype="float32", impl="xla",
                          ic="gaussian")
    return EnsembleSolver(DiffusionSolver, cfg, members)


# public driver -> (its ``steps`` stat, one call of it)
DRIVERS = {
    "run": (3, lambda s: s.run(s.initial_state(), 3)),
    "step": (1, lambda s: s.step(s.initial_state())),
    "advance_to": (None, lambda s: s.advance_to(s.initial_state(), 0.01)),
    "run_ensemble": (2, lambda e: e.run(e.initial_state(), 2)),
    "advance_to_ensemble": (
        None, lambda e: e.advance_to(e.initial_state(), 0.001)),
}


@pytest.mark.parametrize("op", sorted(DRIVERS))
def test_every_public_driver_opens_one_dispatch_span(tmp_path, op):
    """Each public driver is one top-level ``tpucfd.dispatch`` span that
    holds its resolve, execute and state children and the rung's
    ``tpucfd.<op>[<stepper>]`` span; ``steps`` is a stat only where the
    call fixes the trip count."""
    steps, drive = DRIVERS[op]
    target = (_ensemble() if op.endswith("_ensemble")
              else _diffusion3d_small(impl="xla"))
    out = []
    events = host_events(tmp_path, lambda: out.append(drive(target)))
    jax.block_until_ready(out)
    (top,) = _tops(events)
    want = {"op": op, "programs": 1, "cache": "miss"}
    if steps is not None:
        want["steps"] = steps
    assert top.stats == want
    children = {e.name for e in events
                if e.name.startswith("tpucfd.") and e is not top
                and top.holds(e)}
    assert {profiling.RESOLVE_SPAN, profiling.EXECUTE_SPAN,
            profiling.STATE_SPAN} <= children
    assert any(n.startswith(f"tpucfd.{op}[") for n in children)


@pytest.mark.parametrize("impl,stepper", [("pallas_stage", "fused-stage"),
                                          ("xla", "generic-xla")])
def test_run_span_carries_the_stage_plan(tmp_path, impl, stepper):
    """``tpucfd.run[<stepper>]`` carries the engaged stepper's
    ``hbm_bytes_per_step`` (its block plan, a host int) and its
    ``block`` where the stepper declares them (unsharded, no
    ``halo_bytes_per_step``); the generic path declares none."""
    solver = _diffusion3d_small(impl=impl)
    state = solver.run(solver.initial_state(), 1)
    events = host_events(
        tmp_path, lambda: solver.run(state, 1).u.block_until_ready())
    (span,) = [e for e in events if e.name == f"tpucfd.run[{stepper}]"]
    plan = solver.engaged_path().get("hbm_bytes_per_step")
    if impl == "xla":
        assert plan is None and span.stats == {}
    else:
        fused = solver._fused_stepper()
        assert plan == fused.hbm_bytes_per_step > 0
        assert fused.halo_bytes_per_step is None
        (bz,) = fused.block
        # the profiler reads a numeric string stat back as a number
        assert span.stats == {"hbm_bytes_per_step": plan, "block": bz}


# --------------------------------------------------------------------- #
# Event sink
# --------------------------------------------------------------------- #
def test_sink_jsonl_well_formed_and_ordered(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path) as sink:
        sink.event("physics", "probe", step=1, mass=2.5)
        sink.counter("halo.bytes_per_execution", 128)
        with sink.span("chunk", iters=3):
            sink.event("io", "checkpoint_write", path="x", bytes=64)
    evs = _events(path)
    assert evs[0]["kind"] == "meta" and evs[0]["name"] == "open"
    assert evs[0]["schema"] == telemetry.EVENT_SCHEMA
    for ev in evs:
        assert {"t", "proc", "kind", "name"} <= set(ev)
    ts = [ev["t"] for ev in evs]
    assert ts == sorted(ts), "timestamps must be monotonic"


def test_sink_span_nesting(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path) as sink:
        with sink.span("outer"):
            with sink.span("inner"):
                pass
        with sink.span("second"):
            pass
    spans = [e for e in _events(path) if e["kind"] == "span"]
    outer_b, inner_b, inner_e, outer_e, sec_b, sec_e = spans
    assert outer_b["phase"] == "begin" and outer_b["depth"] == 0
    assert inner_b["parent"] == outer_b["id"] and inner_b["depth"] == 1
    assert inner_e["phase"] == "end" and inner_e["id"] == inner_b["id"]
    assert outer_e["id"] == outer_b["id"] and "seconds" in outer_e
    assert sec_b["parent"] is None and sec_b["id"] != outer_b["id"]


def test_sink_counter_accumulation(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path) as sink:
        sink.counter("bytes", 100)
        sink.counter("bytes", 50)
        sink.counter("calls", 1)
        assert sink.counters() == {"bytes": 150, "calls": 1}
    evs = [e for e in _events(path) if e["kind"] == "counter"]
    assert [(e["name"], e["inc"], e["total"]) for e in evs] == [
        ("bytes", 100, 100), ("bytes", 50, 150), ("calls", 1, 1),
    ]


def test_sink_tail_and_null_sink(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path) as sink:
        for i in range(5):
            sink.event("dispatch", "build", key=str(i))
        tail = sink.tail(2)
        assert [e["key"] for e in tail] == ["3", "4"]
    # after capture ends the null sink is active: no-ops, no raise
    assert not telemetry.get_sink().active
    telemetry.event("physics", "probe")
    telemetry.counter("x", 1)
    with telemetry.span("noop"):
        pass
    assert telemetry.get_sink().tail() == []


# --------------------------------------------------------------------- #
# Cost model vs hand-computed bytes/FLOPs
# --------------------------------------------------------------------- #
def test_costmodel_diffusion_fused_stage_hand_computed():
    """3-D O4 diffusion on the per-stage fused rung, 8^3 f32 cells.

    Hand computation at the documented conventions:
      FLOPs/cell/stage = O4 axis term (7) x 3 axes + 2 cross-axis adds
                         + 5 RK combine = 28
      FLOPs/step       = 3 stages x 512 cells x 28 = 43008
      HBM passes/step  = 8 (S->T1: 2; T1,S->T2: 3; T2,S->S: 3)
      bytes/step       = 8 x 512 x 4 = 16384
    """
    c = costmodel.step_cost(
        "diffusion", (8, 8, 8), 4, "fused-stage", stages=3, order=4
    )
    assert c.flops_per_cell_stage == 28
    assert c.flops == 3 * 512 * 28 == 43008
    assert c.passes == 8
    assert c.hbm_bytes == 8 * 512 * 4 == 16384
    # the slab whole-run rung's selling point: one HBM round trip/step
    slab = costmodel.step_cost(
        "diffusion", (8, 8, 8), 4, "fused-whole-run-slab", stages=3, order=4
    )
    assert slab.hbm_bytes == 2 * 512 * 4 == 4096
    assert slab.flops == c.flops  # same math, less traffic


def test_costmodel_weno5_hand_computed():
    """3-D inviscid WENO5 Burgers on generic-xla, 16^3 f32 cells.

    Hand computation:
      WENO5 axis sweep = LF split 7 + 2 sides x (betas 33 + eps 3 +
        alphas 9 + normalize 6 + stencils 15 + combine 5 = 71) + flux
        divergence 2 = 151
      FLOPs/cell/stage = 151 x 3 axes + 2 cross-axis adds + 5 RK = 460
      HBM passes/step  = 3 stages x 6 (materialized-RHS bound) = 18
    """
    cells = 16 ** 3
    c = costmodel.step_cost(
        "burgers", (16, 16, 16), 4, "generic-xla", stages=3, weno_order=5
    )
    assert c.flops_per_cell_stage == 151 * 3 + 2 + 5 == 460
    assert c.flops == 3 * cells * 460
    assert c.hbm_bytes == 18 * cells * 4
    # viscous adds the O2 Laplacian (4x3 + 2) plus one axpy (2) = 16
    v = costmodel.step_cost(
        "burgers", (16, 16, 16), 4, "generic-xla", stages=3, weno_order=5,
        viscous=True,
    )
    assert v.flops_per_cell_stage == 460 + 16


def test_costmodel_f64_storage_pays_f64_bytes():
    f32 = costmodel.step_cost("diffusion", (8, 8, 8), 4, "fused-stage")
    f64 = costmodel.step_cost("diffusion", (8, 8, 8), 8, "fused-stage")
    assert f64.hbm_bytes == 2 * f32.hbm_bytes


def test_costmodel_roofline_pct(monkeypatch):
    monkeypatch.setenv("TPUCFD_PEAK_BYTES_PER_S", "1e9")
    monkeypatch.setenv("TPUCFD_PEAK_FLOPS_PER_S", "1e15")
    c = costmodel.step_cost("diffusion", (64, 64), 4, "fused-stage")
    iters = 10
    model_seconds = c.hbm_bytes * iters / 1e9  # memory-bound by forced peaks
    r = costmodel.roofline(c, iters, model_seconds)
    assert r["bound"] == "hbm"
    assert r["roofline_pct"] == pytest.approx(100.0)
    # twice as slow as the roof -> 50%
    r2 = costmodel.roofline(c, iters, 2 * model_seconds)
    assert r2["roofline_pct"] == pytest.approx(50.0)


def test_costmodel_solver_summary_matches_step_cost():
    solver = _diffusion2d()
    out = costmodel.summarize_run(solver, "generic-xla", 10, 0.5)
    by_hand = costmodel.step_cost("diffusion", (12, 16), 4, "generic-xla")
    assert out["hbm_bytes_per_step"] == by_hand.hbm_bytes
    assert out["flops_per_step"] == by_hand.flops
    assert out["stepper"] == "generic-xla"
    assert out["roofline_pct"] is not None
    # burgers duck-typing picks the WENO branch
    b = BurgersSolver(
        BurgersConfig(grid=Grid.make(32, lengths=2.0), dtype="float32")
    )
    bout = costmodel.summarize_run(b, "generic-xla", 10, 0.5)
    assert bout["flops_per_cell_stage"] == 151 + 0 + 5  # 1-D WENO5 + RK


def test_costmodel_vmem_resident_rung_is_compute_bound():
    c = costmodel.step_cost("diffusion", (64, 64), 4, "fused-whole-run")
    assert c.hbm_bytes == 0.0
    r = costmodel.roofline(c, 10, 1.0)
    assert r["bound"] == "flops"


def test_xla_memory_analysis_cross_check():
    """Where the backend exposes memory_analysis(), the argument bytes
    must match the static model's per-field size (the model's
    cells*itemsize unit is real, not invented)."""
    x = np.ones((32, 32), np.float32)
    res = costmodel.xla_memory_analysis(lambda a: a * 2.0, x)
    if res is None:
        pytest.skip("backend provides no memory_analysis()")
    assert res.get("argument_size_in_bytes", 0) >= x.nbytes


# --------------------------------------------------------------------- #
# Supervised CLI run: the acceptance stream
# --------------------------------------------------------------------- #
def test_cli_metrics_stream_and_summary(tmp_path, devices):
    """A supervised, sharded CLI run with --metrics produces a parseable
    JSONL stream containing span, counter, physics and io events, and
    the summary JSON carries schema/mass-drift/roofline fields."""
    run = tmp_path / "run"
    mpath = str(tmp_path / "events.jsonl")
    cli_main([
        "diffusion2d", "--n", "16", "12", "--iters", "6",
        "--mesh", "dy=2", "--sentinel-every", "2",
        "--checkpoint-every", "2", "--save", str(run),
        "--metrics", mpath,
    ])
    evs = _events(mpath)
    kinds = {e["kind"] for e in evs}
    assert {"meta", "span", "counter", "physics", "resilience", "io",
            "dispatch"} <= kinds
    armed = [e for e in evs if e["name"] == "sentinel_armed"]
    assert armed and armed[0]["cadence"] == 2
    # spans nest under the run_solver root
    roots = [
        e for e in evs
        if e["kind"] == "span" and e["name"] == "run_solver"
        and e["phase"] == "begin"
    ]
    assert len(roots) == 1
    runs = [
        e for e in evs
        if e["kind"] == "span" and e["name"] == "solver.run"
        and e["phase"] == "begin"
    ]
    assert runs and all(e["parent"] == roots[0]["id"] for e in runs)
    assert all("stepper" in e for e in runs)
    # halo counters: trace-time record of the sharded exchange; the
    # (12, 16) grid sharded dy=2 gives (6, 16) shards, and the O4 halo
    # (2) moves 2 slabs x (2 x 16) cells x 4 B = 256 B per exchange
    halo = [e for e in evs if e["name"] == "halo.bytes_per_execution"]
    assert halo and all(e["inc"] % 256 == 0 for e in halo)
    # physics probes stream min/max/l2/mass + drift
    phys = [e for e in evs if e["kind"] == "physics"]
    assert len(phys) >= 3
    assert {"min", "max", "l2", "mass", "mass_drift"} <= set(phys[-1])
    # checkpoint writes are attributable io events
    io_evs = [e for e in evs if e["kind"] == "io"]
    assert any(e["name"] == "checkpoint_write" for e in io_evs)
    # summary JSON: schema'd, with the acceptance fields
    summary = json.loads((run / "summary.json").read_text())
    assert summary["schema"] == SUMMARY_SCHEMA
    assert summary["mass_drift"] == pytest.approx(
        phys[-1]["mass_drift"], rel=1e-6
    )
    assert summary["roofline_pct"] is not None
    assert summary["cost_model"]["stepper"] == summary["engaged"]["stepper"]
    # no leftover tmp file from the atomic summary write
    assert not [n for n in os.listdir(run) if ".tmp" in n]


def test_rollback_shows_as_ordered_events(tmp_path):
    """A fault-injected rollback run shows the rollback as ORDERED
    events: probes before it, the rollback record, then the retried
    chunks and a final healthy probe (the acceptance stream)."""
    mpath = str(tmp_path / "events.jsonl")
    solver = _diffusion2d()
    state = solver.initial_state()
    t_end = 30 * solver.dt
    with telemetry.capture(mpath):
        with faults.nan_at_step(solver, 6):
            out, report = supervise_run(
                solver, state, t_end=t_end, sentinel_every=3,
                max_retries=2, dt_backoff=0.5,
            )
    assert report.retries == 1
    evs = _events(mpath)
    names = [(e["kind"], e["name"]) for e in evs]
    rb = names.index(("resilience", "rollback"))
    # at least one chunk dispatched and probed before the rollback...
    pre = names[:rb]
    assert ("span", "solver.advance_to") in pre or (
        "span", "solver.step") in pre
    assert ("physics", "probe") in pre
    # ...and the retry continues after it: more chunks, healthy probes
    post = names[rb + 1:]
    assert ("physics", "probe") in post
    assert any(k == "span" for k, _ in post)
    ev = evs[rb]
    assert ev["reason"] == "non-finite field"
    assert "dt" in ev["action"] and ev["retry"] == 1
    assert ev["rollback_to_it"] >= 0
    # the report's last probe stats mirror the stream's last physics event
    last_phys = [e for e in evs if e["kind"] == "physics"][-1]
    assert report.mass_drift == pytest.approx(
        last_phys["mass_drift"], rel=1e-6
    )


def test_ladder_degrade_emits_event(tmp_path):
    mpath = str(tmp_path / "events.jsonl")
    grid = Grid.make(24, 16, 16, lengths=[4.0, 4.0, 6.0])
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas")
    )
    with telemetry.capture(mpath):
        with faults.mosaic_failure():
            solver.run(solver.initial_state(), 2)
    degrades = [
        e for e in _events(mpath) if (e["kind"], e["name"]) ==
        ("ladder", "degrade")
    ]
    assert degrades, "kernel-ladder downgrade must appear in the stream"
    assert degrades[-1]["to"] == "xla"
    assert all("Mosaic" in e["reason"] for e in degrades)


def test_multihost_initialize_emits_retry_events(monkeypatch, tmp_path):
    from multigpu_advectiondiffusion_tpu.parallel import multihost

    calls = {"n": 0}

    def flaky(**kwargs):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("coordinator not reachable yet")

    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    mpath = str(tmp_path / "events.jsonl")
    with telemetry.capture(mpath):
        multihost.initialize(
            coordinator_address="localhost:1234", num_processes=1,
            process_id=0, attempts=3, backoff_seconds=0.0,
        )
    evs = [e for e in _events(mpath) if e["kind"] == "dist_init"]
    assert [e["name"] for e in evs] == [
        "attempt", "retry", "attempt", "retry", "attempt", "ok",
    ]
    assert evs[0]["attempt"] == 1 and evs[0]["attempts"] == 3
    assert "coordinator not reachable" in evs[1]["error"]
    assert evs[-1]["attempt"] == 3

    def always_down(**kwargs):
        raise RuntimeError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", always_down)
    mpath2 = str(tmp_path / "events2.jsonl")
    with telemetry.capture(mpath2):
        with pytest.raises(RuntimeError, match="after 2 attempt"):
            multihost.initialize(
                coordinator_address="localhost:1234", num_processes=1,
                process_id=0, attempts=2, backoff_seconds=0.0,
            )
    evs2 = [e for e in _events(mpath2) if e["kind"] == "dist_init"]
    assert evs2[-1]["name"] == "failed" and evs2[-1]["attempts"] == 2


# --------------------------------------------------------------------- #
# Halo byte accounting: deep / k-step exchanges report true bytes
# --------------------------------------------------------------------- #
def _halo_counter_events(path):
    return [
        e for e in _events(path)
        if e.get("name") == "halo.bytes_per_execution"
    ]


def test_halo_bytes_deep_k_step_schedule(tmp_path, devices):
    """The k-step comm-avoiding schedule must report its true per-
    compiled-execution traffic: one k*G-deep exchange site, repeated
    once per block (loop trip count folded in), not a per-step h-deep
    estimate. 4 iters at k=2 -> 2 blocks of a 12-row-deep exchange."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import make_mesh

    grid = Grid.make(16, 16, 48, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab",
                        steps_per_exchange=2),
        mesh=make_mesh({"dz": 2}, devices=devices[:2]),
        decomp=Decomposition.of({0: "dz"}),
    )
    fused = solver._fused_stepper()
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path):
        solver.run(solver.initial_state(), 4)
    evs = _halo_counter_events(path)
    assert len(evs) == 1, evs  # ONE deep site, traced once
    ev = evs[0]
    py, px = fused.padded_shape[1:]
    per_exchange = 2 * fused.exchange_depth * py * px * 4  # lo+hi slabs
    assert ev["halo"] == fused.exchange_depth == 12
    assert ev["repeats"] == 2  # 4 iters / k=2 -> 2 blocks
    assert ev["inc"] == 2 * per_exchange


def test_halo_bytes_per_step_slab_counts_loop_trips(tmp_path, devices):
    """The per-step (k=1) sharded slab schedule exchanges G-deep once
    per step inside a fori_loop: the counter must carry the trip count,
    not one trace-site's worth (the pre-ISSUE-4 under-report)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import make_mesh

    grid = Grid.make(16, 16, 48, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab"),
        mesh=make_mesh({"dz": 2}, devices=devices[:2]),
        decomp=Decomposition.of({0: "dz"}),
    )
    fused = solver._fused_stepper()
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path):
        solver.run(solver.initial_state(), 5)
    evs = _halo_counter_events(path)
    assert len(evs) == 1
    ev = evs[0]
    py, px = fused.padded_shape[1:]
    assert ev["halo"] == fused.halo == 6
    assert ev["repeats"] == 5
    assert ev["inc"] == 5 * 2 * fused.halo * py * px * 4


def test_halo_bytes_fused_stage_counts_loop_trips(tmp_path, devices):
    """The per-stage fused stepper refreshes h-deep ghosts after every
    RK stage inside the run loop: 3 sites, each repeated num_iters
    times per compiled execution."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import make_mesh

    grid = Grid.make(16, 16, 48, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_stage"),
        mesh=make_mesh({"dz": 2}, devices=devices[:2]),
        decomp=Decomposition.of({0: "dz"}),
    )
    fused = solver._fused_stepper()
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path):
        solver.run(solver.initial_state(), 4)
    evs = _halo_counter_events(path)
    # one embed-time refresh (repeats=1) + 3 per-stage loop sites
    loop = [e for e in evs if e["repeats"] == 4]
    assert len(loop) == 3, evs
    py, px = fused.padded_shape[1:]
    per = 2 * fused.halo * py * px * 4
    assert all(e["inc"] == 4 * per for e in loop)
    embed = [e for e in evs if e["repeats"] == 1]
    assert len(embed) == 1 and embed[0]["inc"] == per


# --------------------------------------------------------------------- #
# Summary schema + atomic write
# --------------------------------------------------------------------- #
def test_write_json_atomic_and_schema(tmp_path):
    s = RunSummary(
        name="t", grid_xyz=(8, 8), iters=4, stages=3, seconds=0.5,
        dt=1e-3, t_final=0.1,
    )
    path = str(tmp_path / "summary.json")
    s.write_json(path)
    d = json.loads(open(path).read())
    assert d["schema"] == SUMMARY_SCHEMA
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]


def test_sharded_probe_physics_stats_are_global(devices):
    """min/max/mass/L2 must span the whole mesh, not one shard."""
    from jax.sharding import Mesh

    from multigpu_advectiondiffusion_tpu.resilience.sentinel import (
        make_health_probe,
    )

    mesh = Mesh(np.asarray(devices[:2]), ("dy",))
    cfg = DiffusionConfig(grid=Grid.make(16, 12, lengths=4.0),
                          dtype="float32")
    sharded = DiffusionSolver(cfg, mesh=mesh,
                              decomp=Decomposition.of({0: "dy"}))
    local = DiffusionSolver(cfg)
    st = local.initial_state()
    st_sh = sharded.initial_state()
    a = make_health_probe(local)(st)
    b = make_health_probe(sharded)(st_sh)
    for key in ("max_abs", "min", "max", "l2", "mass"):
        assert b[key] == pytest.approx(a[key], rel=1e-5), key
    vol = math.prod(cfg.grid.spacing)
    assert a["mass"] == pytest.approx(
        vol * float(jnp.sum(st.u)), rel=1e-5
    )


# --------------------------------------------------------------------- #
# Cost-model cross-check vs XLA's own memory accounting (ISSUE 6
# satellite: the dormant memory_analysis() hook promoted to tier-1)
# --------------------------------------------------------------------- #
def _memory_cross_check_case(solver):
    state = solver.initial_state()
    res = costmodel.solver_memory_cross_check(solver, state)
    if res is None:
        pytest.skip("backend provides no memory_analysis()")
    field = res["field_bytes"]
    assert field == math.prod(solver.grid.shape) * 4  # f32 storage
    xla = res["xla"]
    # XLA's own accounting confirms the model's unit: one compiled step
    # reads at least the state field and writes at least the state field
    assert xla["argument_size_in_bytes"] >= field
    assert xla["output_size_in_bytes"] >= field
    model_bytes = res["model"]["hbm_bytes_per_step"]
    min_traffic = res["min_traffic_bytes"]
    # the static model must never claim LESS traffic than the compiled
    # program's own unavoidable in+out footprint ...
    assert model_bytes >= 0.9 * min_traffic, (model_bytes, min_traffic)
    # ... nor more than the documented generic-xla pass count allows
    # (18 passes vs the 2-pass in/out floor, plus scalar/padding slop)
    assert model_bytes <= 20 * min_traffic, (model_bytes, min_traffic)
    return res


def test_memory_cross_check_diffusion_rung():
    res = _memory_cross_check_case(_diffusion2d(impl="xla"))
    # generic-xla diffusion models 18 field passes per step
    assert res["model"]["hbm_passes_per_step"] == 18


def test_memory_cross_check_weno5_rung():
    solver = BurgersSolver(BurgersConfig(
        grid=Grid.make(24, 16, lengths=2.0), weno_order=5,
        adaptive_dt=False, dtype="float32", impl="xla",
    ))
    res = _memory_cross_check_case(solver)
    # WENO5's FLOP model rides the same traffic model (18 passes) but a
    # far heavier per-cell count — both halves are cross-checked
    assert res["model"]["hbm_passes_per_step"] == 18
    assert res["model"]["flops_per_cell_stage"] >= 2 * 151
