"""End-to-end CLI smoke tests.

The CLI replaces the reference's experiment harness (``run.sh`` argument
strings regenerated by ``Run.m`` and the per-project positional-arg
binaries, SURVEY §3.5). These drive the real entry point —
``multigpu_advectiondiffusion_tpu.cli.main(argv)`` — through a solve,
the save/plot pipeline, and the convergence study.
"""

import json
import os

import numpy as np
import pytest

from multigpu_advectiondiffusion_tpu.cli.__main__ import main
from multigpu_advectiondiffusion_tpu.utils.io import load_binary


def test_cli_diffusion2d_save_and_error(tmp_path, capsys):
    main([
        "diffusion2d", "--n", "24", "20", "--iters", "3",
        "--save", str(tmp_path), "--check-error",
    ])
    out = capsys.readouterr().out
    assert "MLUPS" in out or "mlups" in out.lower()
    # Run.m-compatible artifacts (initial.bin/result.bin float32 +
    # machine-readable summary), SURVEY §5 checkpoint/metrics rows.
    result = load_binary(str(tmp_path / "result.bin"), (20, 24))
    initial = load_binary(str(tmp_path / "initial.bin"), (20, 24))
    assert np.isfinite(result).all() and np.isfinite(initial).all()
    summary = json.loads((tmp_path / "summary.json").read_text())
    assert summary["iters"] == 3
    assert summary["error_l1"] is not None and summary["error_l1"] < 1.0


def test_cli_burgers1d_adaptive_dt(tmp_path):
    main([
        "burgers1d", "--n", "48", "--t-end", "0.05",
        "--weno-order", "5", "--weno-variant", "z",
        "--save", str(tmp_path),
    ])
    u = load_binary(str(tmp_path / "result.bin"), (48,))
    assert np.isfinite(u).all()


def test_cli_diffusion3d_sharded_mesh(tmp_path):
    main([
        "diffusion3d", "--n", "16", "16", "16", "--iters", "2",
        "--mesh", "dz=2,dy=2", "--save", str(tmp_path),
    ])
    u = load_binary(str(tmp_path / "result.bin"), (16, 16, 16))
    assert np.isfinite(u).all()


def test_cli_overlap_split_fused_tend(tmp_path):
    """--overlap split + --impl pallas + --t-end on a z-slab mesh drives
    the fused split-overlap schedule through the native `while t < tEnd`
    mode from the command line."""
    main([
        "burgers3d", "--n", "16", "16", "48", "--t-end", "0.02",
        "--impl", "pallas", "--overlap", "split", "--mesh", "dz=2",
        "--save", str(tmp_path),
    ])
    u = load_binary(str(tmp_path / "result.bin"), (48, 16, 16))
    assert np.isfinite(u).all()


def test_cli_convergence_reports_order(capsys):
    main(["convergence", "--ndim", "1", "--cells", "9", "17",
               "--t-end", "0.15"])
    out = capsys.readouterr().out
    # TestingAccuracy.m-style table: norms for each resolution + observed
    # order of accuracy between them.
    assert "ooa" in out.lower()  # observed order of accuracy column


def test_cli_axisym_runs(tmp_path):
    main([
        "diffusion-axisym", "--n", "20", "16", "--iters", "2",
        "--save", str(tmp_path),
    ])
    assert os.path.exists(tmp_path / "result.bin")


def test_cli_checkpoint_resume_continues_run(tmp_path):
    """--checkpoint-every writes .ckpt files; --resume continues from one
    and reproduces the uninterrupted run exactly (same fixed-dt path)."""
    full = tmp_path / "full"
    half = tmp_path / "half"
    main([
        "diffusion3d", "--n", "12", "12", "12", "--iters", "8",
        "--save", str(full),
    ])
    main([
        "diffusion3d", "--n", "12", "12", "12", "--iters", "4",
        "--save", str(half), "--checkpoint-every", "4",
    ])
    ckpt = half / "checkpoint_000004.ckpt"
    assert ckpt.exists() and (half / "checkpoint_000004.ckpt.json").exists()
    resumed = tmp_path / "resumed"
    main([
        "diffusion3d", "--n", "12", "12", "12", "--iters", "4",
        "--save", str(resumed), "--resume", str(ckpt),
    ])
    a = load_binary(str(full / "result.bin"), (12, 12, 12))
    b = load_binary(str(resumed / "result.bin"), (12, 12, 12))
    np.testing.assert_array_equal(a, b)


def test_cli_sharded_checkpoint_resume_across_meshes(tmp_path):
    """--checkpoint-sharded writes a per-shard .ckptd directory (no
    gather); --resume reassembles it onto a DIFFERENT mesh and
    reproduces the uninterrupted run exactly."""
    full = tmp_path / "full"
    half = tmp_path / "half"
    main([
        "diffusion3d", "--n", "12", "12", "16", "--iters", "8",
        "--save", str(full), "--mesh", "dz=2",
    ])
    main([
        "diffusion3d", "--n", "12", "12", "16", "--iters", "4",
        "--save", str(half), "--checkpoint-every", "4",
        "--checkpoint-sharded", "--mesh", "dz=2",
    ])
    ckpt = half / "checkpoint_000004.ckptd"
    assert ckpt.is_dir() and (ckpt / "manifest.json").exists()
    resumed = tmp_path / "resumed"
    main([
        "diffusion3d", "--n", "12", "12", "16", "--iters", "4",
        "--save", str(resumed), "--resume", str(ckpt), "--mesh", "dz=4",
    ])
    a = load_binary(str(full / "result.bin"), (16, 12, 12))
    b = load_binary(str(resumed / "result.bin"), (16, 12, 12))
    np.testing.assert_array_equal(a, b)


def test_cli_resume_rejects_wrong_grid(tmp_path):
    main([
        "diffusion2d", "--n", "16", "16", "--iters", "2",
        "--save", str(tmp_path), "--checkpoint-every", "2",
    ])
    with pytest.raises(ValueError, match="grid"):
        main([
            "diffusion2d", "--n", "20", "20", "--iters", "2",
            "--resume", str(tmp_path / "checkpoint_000002.ckpt"),
        ])


def test_cli_resume_rejects_different_physics(tmp_path):
    """Same grid, different physical parameter (--K) must refuse to
    resume — it would silently continue the wrong equation."""
    main([
        "diffusion2d", "--n", "16", "16", "--K", "1.0", "--iters", "2",
        "--save", str(tmp_path), "--checkpoint-every", "2",
    ])
    with pytest.raises(ValueError, match="physics"):
        main([
            "diffusion2d", "--n", "16", "16", "--K", "2.5", "--iters", "2",
            "--resume", str(tmp_path / "checkpoint_000002.ckpt"),
        ])


def test_cli_summary_reports_engaged_kernel_path(tmp_path, capsys):
    """The summary must say which kernel strategy actually executed
    (the reference's PrintSummary what-ran contract, Tools.c:255-269):
    a fused-eligible config reports its stepper, and a config that
    silently fails fused eligibility reports the fallback reason."""
    # eligible: 3-D diffusion f32 + impl=pallas -> the slab whole-run
    # stepper (the top of the 3-D ladder; this small z extent fits one
    # or two slabs, where the model always engages it)
    d = tmp_path / "eligible"
    main([
        "diffusion3d", "--n", "16", "12", "12", "--iters", "2",
        "--impl", "pallas", "--save", str(d),
    ])
    out = capsys.readouterr().out
    s = json.loads((d / "summary.json").read_text())
    assert s["engaged"]["stepper"] == "fused-whole-run-slab"
    assert s["engaged"]["impl"] == "pallas"
    assert s["engaged"]["fallback"] is None
    assert "kernel path" in out and "fused-whole-run-slab" in out

    # fallback: euler integrator disqualifies the fused stepper -> the
    # per-axis pallas kernels run, and the summary says why
    f = tmp_path / "fallback"
    main([
        "diffusion3d", "--n", "16", "12", "12", "--iters", "2",
        "--impl", "pallas", "--integrator", "euler", "--save", str(f),
    ])
    out = capsys.readouterr().out
    s = json.loads((f / "summary.json").read_text())
    assert s["engaged"]["stepper"] == "per-axis-pallas"
    assert "SSP-RK3" in s["engaged"]["fallback"]
    assert "fused fallback" in out


def test_cli_summary_engaged_honest_in_tend_mode(tmp_path):
    """A --t-end run of a stepper without run_to (the 2-D whole-run
    classes) actually executes the generic loop — the summary must say
    so, not claim the fused stepper engaged."""
    main([
        "diffusion2d", "--n", "24", "20", "--t-end", "0.11",
        "--impl", "pallas", "--save", str(tmp_path),
    ])
    s = json.loads((tmp_path / "summary.json").read_text())
    assert s["engaged"]["stepper"] == "per-axis-pallas"
    assert "run_to" in s["engaged"]["fallback"]


def test_cli_summary_engaged_overlap_sharded(tmp_path):
    """A sharded fused run reports the halo schedule actually in effect
    (split vs serialized-refresh)."""
    main([
        "burgers3d", "--n", "16", "16", "48", "--iters", "2",
        "--impl", "pallas", "--overlap", "split", "--mesh", "dz=2",
        "--save", str(tmp_path),
    ])
    s = json.loads((tmp_path / "summary.json").read_text())
    assert s["engaged"]["stepper"] == "fused-stage"
    assert s["engaged"]["overlap"] in ("split", "serialized-refresh")


def test_cli_impl_flag_pallas(tmp_path):
    """--impl pallas must produce the same physics as the default
    (interpret-mode kernels on CPU), through the full CLI pipeline."""
    outs = {}
    for impl in ("xla", "pallas"):
        d = tmp_path / impl
        main([
            "diffusion3d", "--n", "16", "12", "12", "--iters", "4",
            "--save", str(d), "--impl", impl,
        ])
        outs[impl] = load_binary(str(d / "result.bin"), (12, 12, 16))
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-4, atol=1e-6)


def test_cli_resume_npz_validates_bounds(tmp_path):
    """Bounds recorded inside a legacy .npz checkpoint are enforced on
    resume: same node count on a different physical domain must fail."""
    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig, DiffusionSolver, Grid)
    from multigpu_advectiondiffusion_tpu.utils import io as tio

    grid = Grid.make(16, 16, lengths=4.0)
    s = DiffusionSolver(DiffusionConfig(grid=grid, dtype="float32"))
    ckpt = str(tmp_path / "state.npz")
    tio.save_checkpoint(ckpt, s.run(s.initial_state(), 2), grid=grid)
    with pytest.raises(ValueError, match="bounds"):
        main([
            "diffusion2d", "--n", "16", "16", "--lengths", "8", "8",
            "--iters", "2", "--resume", ckpt,
        ])


def test_cli_profile_flag_writes_trace(tmp_path):
    main([
        "diffusion2d", "--n", "16", "16", "--iters", "2",
        "--profile", str(tmp_path / "trace"),
    ])
    # jax.profiler lays out plugins/profile/<date>/*; existence of any
    # file under the dir is the contract we need
    found = [
        os.path.join(r, f)
        for r, _, fs in os.walk(tmp_path / "trace")
        for f in fs
    ]
    assert found, "no trace artifacts written"


def test_bench_matrix_quick_sharded(tmp_path, capsys):
    """The RunAll.m-equivalent benchmark matrix must run end-to-end in
    quick mode, including a sharded case over the virtual mesh, and emit
    machine-readable JSON lines."""
    from multigpu_advectiondiffusion_tpu.bench.matrix import main as bench_main

    out = tmp_path / "bench.jsonl"
    bench_main(["--quick", "--name", "diffusion3d_multigpu",
                "--mesh", "dz=4", "--repeats", "1", "--out", str(out)])
    rec = json.loads(out.read_text().strip())
    assert rec["name"] == "diffusion3d_multigpu"
    assert rec["mesh"] == "dz=4"
    assert rec["quick"] is True and rec["mlups"] > 0


def test_bench_py_matrix_covers_baselines():
    """bench.py must expose >=8 baseline metrics (VERDICT-driven driver
    contract): the whole published Burgers family (512^3 fixed/adaptive
    + slab + wide + the native t_end mode) plus the diffusion rows — no
    perf claim may live only in README prose. Its headline case must run
    end-to-end."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module", os.path.join(root, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    cases = bench._cases(on_tpu=False)
    names = [c[0] for c in cases]
    assert len(cases) >= 12
    for expected in ("diffusion3d_mlups", "diffusion3d_ref_grid_mlups",
                     "diffusion2d_mlups", "burgers3d_mlups",
                     "burgers3d_adaptive_mlups", "burgers3d_tend_mlups",
                     "burgers3d_slab_mlups", "burgers3d_wide_mlups",
                     "burgers2d_mlups", "burgers3d_multigpu_mlups",
                     # the f64 precision row and the per-axis ladder rung:
                     # no measured claim may live only in PARITY prose
                     "diffusion3d_f64_mlups", "burgers3d_axis_mlups"):
        assert expected in names
    assert all(baseline > 0 for _, _, _, _, baseline, _ in cases)
    assert all(mode in ("iters", "t_end") for _, _, mode, _, _, _ in cases)
    # every row carries a non-empty expected-engagement set (the
    # engagement guard's contract)
    assert all(expect for *_, expect in cases)

    from multigpu_advectiondiffusion_tpu.bench.timing import timed_run

    metric, make_solver, _, _, _, _ = cases[0]
    solver = make_solver()
    tr = timed_run(solver, solver.initial_state(), 2, reps=1)
    assert tr.seconds > 0 and tr.median_seconds > 0 and tr.spread >= 0
    assert tr.outliers == 0


def test_bench_timing_discards_stalled_reps():
    """A rep that stalls (host preemption, a dispatch hiccup) must be
    discarded and re-measured, not averaged into the median — the
    driver artifact showed a rep of a ~1 s window taking minutes
    (BENCH_r03 burgers2d spread 148x)."""
    import time as _time

    import jax.numpy as jnp

    from multigpu_advectiondiffusion_tpu.bench import timing

    calls = {"n": 0}

    def full():
        calls["n"] += 1
        # the 4th measured call stalls 20x the normal window
        _time.sleep(0.5 if calls["n"] == 4 else 0.025)
        return jnp.zeros(())

    def zero():
        return jnp.zeros(())

    tr = timing._timed(full, zero, reps=5)
    assert tr.outliers >= 1
    assert tr.median_seconds < 0.1, tr
    assert tr.spread < 3.0, tr
    # the discarded stall must remain visible in the pre-filter spread
    assert tr.raw_spread > tr.spread and tr.raw_spread > 3.0, tr


def test_cli_plot_renders_png(tmp_path):
    """--plot renders the Run.m/myplot.m-equivalent slice figure."""
    pytest.importorskip("matplotlib")
    main([
        "diffusion3d", "--n", "16", "12", "12", "--iters", "2",
        "--save", str(tmp_path), "--plot",
    ])
    pngs = [f for f in os.listdir(tmp_path) if f.endswith(".png")]
    assert pngs, f"no PNG in {os.listdir(tmp_path)}"
    assert os.path.getsize(tmp_path / pngs[0]) > 1000


def test_bench_scaling_rows_mechanics():
    """The turnkey multi-chip scaling harness end-to-end on the virtual
    8-device CPU mesh: a timed sharded run per slab count with complete
    row fields — so the first real multi-chip session produces scaling
    numbers with zero new code (the reference's headline artifact is
    measured 2-GPU scaling, MultiGPU/Diffusion3d_Baseline/Run.m:4-13)."""
    import jax

    from multigpu_advectiondiffusion_tpu.bench.scaling import (
        candidate_counts,
        scaling_rows,
    )

    # one device -> no rows, gracefully
    assert scaling_rows(devices=jax.devices()[:1], on_tpu=False) == []
    # slab counts honor divisibility and include the full count
    assert candidate_counts(8, 24) == [2, 4, 8]
    assert candidate_counts(6, 24) == [2, 4, 6]
    assert candidate_counts(4, 30) == [2]  # 4 doesn't divide 30

    rows = scaling_rows(devices=jax.devices()[:4], on_tpu=False, reps=1)
    metrics = [r["metric"] for r in rows]
    assert "diffusion3d_scale_dz2_mlups" in metrics
    assert "burgers3d_scale_dz4_mlups" in metrics
    for r in rows:
        assert r["value"] > 0 and r["per_chip"] > 0
        # both fields round to 2 decimals independently
        assert abs(r["per_chip"] - r["value"] / r["devices"]) < 0.01
        assert r["vs_baseline"] > 0
        # the rows dispatch through impl='auto': a fused rung must be
        # what ran (cache/measured when tuning is enabled, the pallas
        # heuristic fallback otherwise — never generic), and the row
        # carries the cadence + provenance fields bench.py publishes
        assert r["engaged"].startswith("fused-"), r
        assert r["tuned"] is not None and "source" in r["tuned"], r
        assert r["steps_per_exchange"] >= 1
        assert set(r) >= {"spread", "outliers", "raw_spread", "engaged",
                          "steps_per_exchange", "tuned"}


def test_cli_convergence_save_archives_study(tmp_path, capsys):
    """convergence --save persists the study the way TestingAccuracy.m
    archives TestAccuracy.fig + .log (TestingAccuracy.m:51-70): the
    printed table, machine-readable rows with observed orders, and the
    loglog figure."""
    pytest.importorskip("matplotlib")
    main(["convergence", "--ndim", "1", "--cells", "9", "17", "33",
          "--t-end", "0.15", "--save", str(tmp_path)])
    out = capsys.readouterr().out
    assert "ooa" in out.lower()
    log = (tmp_path / "convergence.log").read_text()
    assert "OOA" in log and " 33 " in log
    study = json.loads((tmp_path / "convergence.json").read_text())
    assert [r["n"] for r in study["rows"]] == [9, 17, 33]
    # O4 stencil + RK3: observed L1 order between halvings ~3.5+
    assert study["rows"][-1]["ooa_l1"] > 3.0
    assert (tmp_path / "convergence.png").stat().st_size > 0


def test_cli_compile_cache_lands_where_the_env_says(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the CLI's compiled
    programs are cached there and the repo's default directory gets
    nothing."""
    import subprocess
    import sys

    from multigpu_advectiondiffusion_tpu.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = (set(os.listdir(compile_cache.DEFAULT_DIR))
              if os.path.isdir(compile_cache.DEFAULT_DIR) else set())
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=repo)
    subprocess.run(
        [sys.executable, "-m", "multigpu_advectiondiffusion_tpu.cli",
         "diffusion2d", "--n", "16", "16", "--iters", "2"],
        env=env, cwd=str(tmp_path), check=True, capture_output=True,
    )
    assert any(p.name.endswith("-cache") for p in cache.iterdir())
    after = (set(os.listdir(compile_cache.DEFAULT_DIR))
             if os.path.isdir(compile_cache.DEFAULT_DIR) else set())
    assert after == before


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    """Without the env var the cache sits at ``<repo>/.jax_cache``: a
    fixed path, so a later run finds what an earlier one stored."""
    import jax

    from multigpu_advectiondiffusion_tpu.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert compile_cache.use_compile_cache() == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == (
            compile_cache.DEFAULT_DIR)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", saved)
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == saved
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
