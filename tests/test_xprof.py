"""Measured-introspection suite (ISSUE 7 tentpole, tier-1, CPU).

Covers the xprof layer end to end: per-executable XLA cost/memory
capture at dispatch (one compile, reused for execution) on a diffusion
and a WENO5 rung, device-memory watermark sampling with the
live-arrays fallback, the calibration record's round-trip and its
precedence over env-assumed peaks (consulted by both the cost model
and the tuner's pruning), the dispatch-executable reuse of
``solver_memory_cross_check``, the exception-safe idempotent
``profiling.trace``, and a real supervised CLI run whose ``--metrics``
stream carries ``xla:cost`` / ``mem:watermark`` / ``calib:update``
events and whose summary gains the ``memory``/``xla`` blocks.
"""

from __future__ import annotations

import json

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
from multigpu_advectiondiffusion_tpu.telemetry import (
    calibration,
    costmodel,
    schema,
    xprof,
)
from multigpu_advectiondiffusion_tpu.utils import profiling

from host_trace import host_events


def _events(path) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f]


def _diffusion3d(**kw):
    cfg = DiffusionConfig(
        grid=Grid.make(12, 10, 8, lengths=3.0), dtype="float32", **kw
    )
    return DiffusionSolver(cfg)


def _burgers2d(**kw):
    cfg = BurgersConfig(
        grid=Grid.make(20, 16, lengths=2.0), weno_order=5,
        adaptive_dt=False, dtype="float32", **kw
    )
    return BurgersSolver(cfg)


# --------------------------------------------------------------------- #
# Executable capture at dispatch
# --------------------------------------------------------------------- #
def test_dispatch_captures_diffusion_executable(tmp_path):
    """One solver.run dispatch produces exactly one ExecRecord with
    nonzero XLA-reported flops/bytes, the modeled per-step prediction
    alongside, and a schema-valid xla:cost event."""
    path = str(tmp_path / "ev.jsonl")
    solver = _diffusion3d(impl="xla")
    with telemetry.capture(path):
        solver.run(solver.initial_state(), 3)
    recs = xprof.records(solver)
    assert len(recs) == 1
    rec = recs[0]
    assert rec.key == "('run', 3)" and rec.steps == 3
    assert rec.stepper == "generic-xla"
    assert rec.flops > 0 and rec.bytes_accessed > 0
    assert rec.compile_seconds > 0
    # XLA's argument accounting covers at least the state field
    field = 12 * 10 * 8 * 4
    assert rec.argument_bytes >= field
    assert rec.peak_bytes >= field
    # the static model's per-step numbers ride the record
    by_hand = costmodel.step_cost(
        "diffusion", (8, 10, 12), 4, "generic-xla"
    )
    assert rec.model_bytes_per_step == by_hand.hbm_bytes
    assert rec.model_flops_per_step == by_hand.flops
    evs = [e for e in _events(path) if e["kind"] == "xla"]
    assert len(evs) == 1 and evs[0]["name"] == "cost"
    assert schema.validate_event(evs[0]) == []
    assert evs[0]["flops"] == rec.flops


def test_dispatch_captures_weno5_executable():
    """The WENO5 rung's capture: the executable's flop count must
    reflect the far heavier per-cell sweep (>= the model's 151/axis
    convention at the same order of magnitude as the diffusion rung's
    discrepancy band allows nonzero, real numbers)."""
    solver = _burgers2d(impl="xla")
    solver.run(solver.initial_state(), 2)
    rec = xprof.primary_record(xprof.records(solver))
    assert rec is not None and rec.steps == 2
    assert rec.flops > 0 and rec.bytes_accessed > 0
    # WENO5 is flop-heavy: XLA's per-cell count must clearly exceed
    # the diffusion rung's (the margin is well under the modeled 11x —
    # boundary padding dominates these tiny grids — but the ordering
    # must hold for the captured numbers to be real)
    diff = _diffusion3d(impl="xla")
    diff.run(diff.initial_state(), 2)
    drec = xprof.primary_record(xprof.records(diff))
    cells_b = 20 * 16
    cells_d = 12 * 10 * 8
    assert rec.flops / cells_b > 1.5 * drec.flops / cells_d


def test_dispatch_capture_reuses_one_compile_per_program():
    """Repeat calls of the same program never re-capture (one record
    per dispatch-cache entry), and the compiled object is reused."""
    solver = _diffusion3d(impl="xla")
    st = solver.initial_state()
    st = solver.run(st, 2)
    st = solver.run(st, 2)
    assert len(xprof.records(solver)) == 1
    entry = solver._cache[("run", 2)]
    assert entry._compiled is not None and not entry._fallback


def test_compile_and_introspection_spans_on_the_first_call_only(tmp_path):
    """A program's first call compiles inside ``tpucfd.compile`` (stat
    ``key``: the dispatch-cache key) and captures its analyses inside
    ``tpucfd.introspect``, both within that call's execute span; the
    next call opens neither."""
    solver = _diffusion3d(impl="xla")

    def two_calls():
        st = solver.run(solver.initial_state(), 2)
        solver.run(st, 2).u.block_until_ready()

    events = host_events(tmp_path, two_calls)
    executes = [e for e in events if e.name == profiling.EXECUTE_SPAN]
    assert len(executes) == 2
    for name in (profiling.COMPILE_SPAN, profiling.INTROSPECT_SPAN):
        (span,) = [e for e in events if e.name == name]
        assert span.stats == {"key": "('run', 2)"}
        assert executes[0].holds(span)
    compile_span = next(e for e in events
                        if e.name == profiling.COMPILE_SPAN)
    introspect = next(e for e in events
                      if e.name == profiling.INTROSPECT_SPAN)
    assert compile_span.end <= introspect.start


def test_dispatch_retraces_only_on_argument_drift():
    """A call whose arguments differ in shape, dtype or sharding from
    the compiled program's retraces through jit; a failure while the
    compiled program runs is the caller's — never re-run through jit,
    which would hide a device error and re-read a donated buffer."""
    solver = _diffusion3d(impl="xla")
    entry = xprof.wrap_dispatch(jax.jit(lambda u: u * 2.0), solver, "k")
    x = jnp.ones((4, 4), jnp.float32)
    assert float(entry(x).sum()) == 32.0
    assert float(entry(jnp.ones((2, 4), jnp.float32)).sum()) == 16.0
    assert entry._fallback  # shape drift: plain jit from here on

    entry = xprof.wrap_dispatch(jax.jit(lambda u: u * 2.0), solver, "k2")
    entry(x)

    def device_error(*args):
        raise RuntimeError("simulated device error")

    entry._compiled = device_error
    with pytest.raises(RuntimeError, match="simulated device error"):
        entry(x)
    assert not entry._fallback


def test_peaks_are_keyed_by_device_kind():
    """The v5e row is the datasheet's; a kind with no row is an error,
    not another chip's peaks."""
    assert costmodel.PEAKS["TPU v5 lite"][0] == 819.0e9
    assert costmodel.peak_info()["device_kind"] == "cpu"
    with pytest.raises(KeyError, match="TPU v9"):
        costmodel.peak_rates("TPU v9")


def test_xprof_disabled_falls_back_to_plain_jit(monkeypatch):
    monkeypatch.setenv("TPUCFD_XPROF", "0")
    solver = _diffusion3d(impl="xla")
    out = solver.run(solver.initial_state(), 2)
    assert int(out.it) == 2
    assert xprof.records(solver) == []


def test_measured_summary_reconciles_model():
    solver = _diffusion3d(impl="xla")
    solver.run(solver.initial_state(), 4)
    out = xprof.measured_summary(solver, iters=4, seconds=0.25)
    assert out["executables"] == 1
    assert out["xla_bytes_per_step"] > 0
    assert out["model_bytes_per_step"] == costmodel.step_cost(
        "diffusion", (8, 10, 12), 4, "generic-xla"
    ).hbm_bytes
    # ratio + band flag are present and consistent
    ratio = out["model_bytes_ratio"]
    tol = out["tolerance_factor"]
    assert out["bytes_within_tolerance"] == (1 / tol <= ratio <= tol)
    assert out["achieved_gbs"] == pytest.approx(
        out["xla_bytes_per_step"] * 4 / 0.25 / 1e9, rel=5e-2
    )  # loose: the summary rounds to 4 decimals
    assert out["peak_gbs"] > 0


# --------------------------------------------------------------------- #
# Device-memory watermarks (live-arrays fallback is the CPU path)
# --------------------------------------------------------------------- #
def test_watermark_live_arrays_fallback(tmp_path):
    """CPU devices report no memory_stats(): the sample must fall back
    to the live-arrays census, see a held array, and keep the running
    peak after it dies."""
    xprof.reset_watermarks()
    held = jnp.ones((64, 64), jnp.float32)  # 16 KiB live
    path = str(tmp_path / "ev.jsonl")
    with telemetry.capture(path):
        s1 = xprof.sample_watermark(step=1)
    assert s1["source"] == "live_arrays"
    assert s1["bytes_in_use"] >= held.nbytes
    del held
    s2 = xprof.sample_watermark(emit=False)
    summary = xprof.watermark_summary()
    assert summary["peak_bytes_in_use"] >= s1["bytes_in_use"]
    assert summary["peak_bytes_in_use"] >= s2["bytes_in_use"]
    assert summary["samples"] == 2
    assert summary["headroom_bytes"] is None  # census has no limit
    evs = [e for e in _events(path) if e["kind"] == "mem"]
    assert len(evs) == 1  # emit=False stayed out of the stream
    assert schema.validate_event(evs[0]) == []
    assert evs[0]["step"] == 1


def test_watermark_reset_zeroes_peak():
    xprof.sample_watermark(emit=False)
    assert xprof.watermark_summary() is not None
    xprof.reset_watermarks()
    assert xprof.watermark_summary() is None


# --------------------------------------------------------------------- #
# Calibration: round-trip + precedence over env peaks
# --------------------------------------------------------------------- #
def test_calibration_roundtrip_max_merge(tmp_path, monkeypatch):
    path = str(tmp_path / "cal.json")
    monkeypatch.setenv(calibration.ENV_PATH, path)
    mpath = str(tmp_path / "ev.jsonl")
    with telemetry.capture(mpath):
        calibration.observe("cpu", bytes_per_s=2.0e9, run="r1")
        calibration.observe("cpu", bytes_per_s=1.0e9, run="r2")  # slower
        calibration.observe("cpu", flops_per_s=3.0e9, run="r3")
    rec = calibration.lookup("cpu")
    assert rec["bytes_per_s"] == 2.0e9  # max-merge kept the faster run
    assert rec["flops_per_s"] == 3.0e9
    assert rec["samples"] == 3 and rec["run"] == "r3"
    # the file itself is the artifact: schema'd, reread equals lookup
    data = json.loads(open(path).read())
    assert data["schema"] == calibration.CALIBRATION_SCHEMA
    assert data["entries"]["cpu"]["bytes_per_s"] == 2.0e9
    evs = [e for e in _events(mpath) if e["kind"] == "calib"]
    assert [e["persisted"] for e in evs] == [True, False, True]
    assert all(schema.validate_event(e) == [] for e in evs)


def test_calibration_beats_env_peaks(tmp_path, monkeypatch):
    """Measured beats assumed: with a calibration record present,
    peak_rates returns it even when the env override is set; without
    one, the env override still wins over the static default."""
    monkeypatch.setenv("TPUCFD_PEAK_BYTES_PER_S", "1e9")
    monkeypatch.setenv("TPUCFD_PEAK_FLOPS_PER_S", "1e12")
    monkeypatch.setenv(
        calibration.ENV_PATH, str(tmp_path / "cal.json")
    )
    assert costmodel.peak_rates("cpu") == (1e9, 1e12)  # env over default
    calibration.observe("cpu", bytes_per_s=7.5e9)
    peak_b, peak_f = costmodel.peak_rates("cpu")
    assert peak_b == 7.5e9  # calibrated over env
    assert peak_f == 1e12   # uncalibrated component keeps the env value
    info = costmodel.peak_info("cpu")
    assert info["bytes_source"] == "calibrated"
    assert info["flops_source"] == "env"


def test_calibration_disabled_by_env(monkeypatch):
    monkeypatch.setenv(calibration.ENV_PATH, "off")
    assert calibration.default_path() is None
    assert calibration.observe("cpu", bytes_per_s=1e9) is None
    assert calibration.lookup("cpu") is None


def test_tuner_pruning_consults_calibrated_peaks(tmp_path, monkeypatch):
    """The autotuner's pruning metric (modeled_step_seconds) runs on
    peak_rates — a calibrated peak must change the modeled time, i.e.
    the tuner prunes with measured rather than assumed rates."""
    from multigpu_advectiondiffusion_tpu.tuning.autotuner import (
        modeled_step_seconds,
    )

    monkeypatch.setenv(
        calibration.ENV_PATH, str(tmp_path / "cal.json")
    )
    cfg = DiffusionConfig(
        grid=Grid.make(16, 16, 32, lengths=2.0), dtype="float32",
        impl="pallas_slab",
    )
    cand = {"impl": "pallas_slab", "steps_per_exchange": 1}
    before = modeled_step_seconds(cfg, (32, 16, 16), cand, 1, "cpu")
    assert before is not None and before > 0
    # this candidate is flops-bound on the assumed CPU peaks: a rig
    # that demonstrated 100x the assumed FLOP rate prices it cheaper
    _, peak_f = costmodel.peak_rates("cpu")
    calibration.observe("cpu", flops_per_s=100.0 * peak_f)
    after = modeled_step_seconds(cfg, (32, 16, 16), cand, 1, "cpu")
    assert after < before


# --------------------------------------------------------------------- #
# solver_memory_cross_check reuses the dispatched executable
# --------------------------------------------------------------------- #
def test_memory_cross_check_reuses_dispatch_executable(monkeypatch):
    """The cross-check must read XLA's accounting from the dispatch
    layer's own compiled step — never lower/compile a second copy
    (the legacy hook is monkeypatched to prove it is not consulted)."""
    solver = _diffusion3d(impl="xla")
    state = solver.initial_state()

    def forbidden(fn, *args):  # pragma: no cover - failing path
        raise AssertionError(
            "xla_memory_analysis recompiled a second copy of the step"
        )

    monkeypatch.setattr(costmodel, "xla_memory_analysis", forbidden)
    res = costmodel.solver_memory_cross_check(solver, state)
    assert res is not None
    field = 12 * 10 * 8 * 4
    assert res["field_bytes"] == field
    assert res["xla"]["argument_size_in_bytes"] >= field
    # the record the cross-check consumed is the dispatched step's
    rec = [r for r in xprof.records(solver) if r.key == "step"]
    assert rec and res["xla"]["argument_size_in_bytes"] == \
        rec[0].argument_bytes


# --------------------------------------------------------------------- #
# profiling.trace: exception-safe + idempotent (satellite)
# --------------------------------------------------------------------- #
def test_trace_closes_on_exception_and_recovers(tmp_path, monkeypatch):
    from multigpu_advectiondiffusion_tpu.utils import profiling

    calls = {"start": 0, "stop": 0, "open": False}

    def fake_start(log_dir):
        if calls["open"]:
            raise RuntimeError("profiler already running")
        calls["start"] += 1
        calls["open"] = True

    def fake_stop():
        if not calls["open"]:
            raise RuntimeError("no trace running")
        calls["stop"] += 1
        calls["open"] = False

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake_stop)
    # an exception inside the traced body must still stop the trace
    with pytest.raises(ValueError, match="boom"):
        with profiling.trace(str(tmp_path / "t1")):
            raise ValueError("boom")
    assert calls == {"start": 1, "stop": 1, "open": False}
    # a trace leaked by some OTHER owner poisons start_trace: trace()
    # must close it and retry instead of failing forever
    calls["open"] = True
    with profiling.trace(str(tmp_path / "t2")):
        pass
    assert calls["open"] is False and calls["start"] == 2


def test_trace_is_idempotent_under_nesting(tmp_path, monkeypatch):
    from multigpu_advectiondiffusion_tpu.utils import profiling

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d: calls.__setitem__("start", calls["start"] + 1),
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.__setitem__("stop", calls["stop"] + 1),
    )
    with profiling.trace(str(tmp_path / "outer")):
        with profiling.trace(str(tmp_path / "inner")):  # no-op
            pass
    assert calls == {"start": 1, "stop": 1}


# --------------------------------------------------------------------- #
# The acceptance run: supervised CLI solves with --metrics
# --------------------------------------------------------------------- #
def _assert_measured_stream(mpath, run_dir, name):
    evs = _events(mpath)
    # per-executable xla:cost with nonzero XLA-reported numbers
    costs = [e for e in evs if (e["kind"], e["name"]) == ("xla", "cost")]
    assert costs, "no xla:cost events in the stream"
    assert all(e["flops"] > 0 and e["bytes_accessed"] > 0 for e in costs)
    assert all(schema.validate_event(e) == [] for e in costs)
    # chunk-cadence mem:watermark events (live-arrays fallback on CPU)
    marks = [e for e in evs
             if (e["kind"], e["name"]) == ("mem", "watermark")]
    assert len(marks) >= 3
    assert all(e["source"] == "live_arrays" for e in marks)
    assert all(e["bytes_in_use"] > 0 for e in marks)
    # the measured-vs-modeled reconciliation + the calibration write
    assert any(
        (e["kind"], e["name"]) == ("xla", "measured") for e in evs
    )
    calib = [e for e in evs if e["kind"] == "calib"]
    assert calib and calib[-1]["persisted"]
    # summary carries the memory block with peak bytes and the xla block
    summary = json.loads((run_dir / "summary.json").read_text())
    assert summary["schema"] >= 3
    assert summary["memory"]["peak_bytes_in_use"] > 0
    assert summary["memory"]["source"] == "live_arrays"
    assert summary["xla"]["xla_bytes_per_step"] > 0
    assert summary["xla"]["model_bytes_ratio"] is not None
    assert summary["name"] == name
    return evs


def test_cli_supervised_diffusion3d_measured_stream(tmp_path):
    run = tmp_path / "run"
    mpath = str(tmp_path / "events.jsonl")
    cli_main([
        "diffusion3d", "--n", "12", "10", "8", "--iters", "6",
        "--sentinel-every", "2", "--save", str(run),
        "--metrics", mpath,
    ])
    evs = _assert_measured_stream(mpath, run, "diffusion3d")
    # the calibration record is on disk and consulted by peak_rates
    rec = calibration.lookup("cpu")
    assert rec is not None and rec.get("bytes_per_s", 0) > 0
    info = costmodel.peak_info("cpu")
    assert "calibrated" in (info["bytes_source"], info["flops_source"])
    # dispatch builds and xla:cost captures pair up
    builds = [e for e in evs if e["kind"] == "dispatch"]
    assert len(builds) == len(
        [e for e in evs if (e["kind"], e["name"]) == ("xla", "cost")]
    )


def test_cli_supervised_burgers3d_measured_stream(tmp_path):
    run = tmp_path / "run"
    mpath = str(tmp_path / "events.jsonl")
    cli_main([
        "burgers3d", "--n", "10", "8", "8", "--iters", "6",
        "--fixed-dt", "--sentinel-every", "2", "--save", str(run),
        "--metrics", mpath,
    ])
    _assert_measured_stream(mpath, run, "burgers3d")


def test_trace_report_measured_section(tmp_path):
    """tpucfd-trace renders the measured-vs-modeled section from a
    real supervised stream: per-executable rows with ratio + band flag
    (discrepancies reported, not hidden) and the per-rank memory peak."""
    from multigpu_advectiondiffusion_tpu.telemetry.analyze import analyze

    run = tmp_path / "run"
    mpath = str(tmp_path / "events.jsonl")
    cli_main([
        "diffusion2d", "--n", "16", "12", "--iters", "6",
        "--sentinel-every", "3", "--save", str(run),
        "--metrics", mpath,
    ])
    report = analyze([mpath])
    x = report.xla
    assert x["executables"], "no xla:cost rows in the report"
    row = x["executables"][-1]
    assert row["xla_bytes"] > 0
    assert row["model_bytes_ratio"] is not None
    assert row["within_tolerance"] in (True, False)
    assert x["runs"] and x["runs"][0]["run"] == "diffusion2d"
    assert x["memory"]["proc0"]["peak_bytes"] > 0
    text = report.format_text()
    assert "measured vs modeled" in text
    flag = "ok" if row["within_tolerance"] else "DISCREPANT"
    assert flag in text
