"""Multi-host (DCN) layer tests on the virtual 8-device CPU backend.

The reference's multi-node story is MPI process management
(``InitializeMPI``, ``MultiGPU/Diffusion3d_Baseline/Tools.c:228-242``;
``MPIDeviceCheck``/``AssignDevices``, ``Util.cu:43-74``) and is untestable
without a cluster. The TPU-native layer (``parallel/multihost.py``) is
validated here without one: hybrid-mesh construction (DCN-outermost axis
ordering, clear failures on impossible topologies) in-process, and the
``jax.distributed`` runtime bring-up as a ``num_processes=1`` smoke in a
subprocess (so this process's backend stays pristine).
"""

import os
import socket
import subprocess
import sys

import pytest

import jax

from multigpu_advectiondiffusion_tpu.parallel import multihost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hybrid_mesh_no_dcn_axis_uses_all_devices(devices):
    """dcn extent 1: plain mesh over all devices, ici axes innermost."""
    mesh = multihost.hybrid_mesh({"dz_ici": 8}, {})
    assert mesh.axis_names == ("dz_ici",)
    assert mesh.devices.shape == (8,)
    assert list(mesh.devices.ravel()) == list(jax.devices())


def test_hybrid_mesh_dcn_axis_is_outermost(devices):
    """Axis order is DCN axes then ICI axes — the outermost decomposition
    axis rides the slow links, matching the module's design contract."""
    mesh = multihost.hybrid_mesh({"a": 2, "b": 4}, {"d": 1})
    assert mesh.axis_names == ("d", "a", "b")
    assert mesh.devices.shape == (1, 2, 4)


def test_hybrid_mesh_runs_sharded_solve(devices):
    """A hybrid mesh is a plain Mesh: the standard sharded solver runs on
    it with z decomposed over the DCN-outermost compound axis, exactly as
    the module docstring prescribes for multi-host runs."""
    import numpy as np

    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )
    from multigpu_advectiondiffusion_tpu.parallel.mesh import Decomposition

    mesh = multihost.hybrid_mesh({"dz_ici": 8}, {"dz_dcn": 1})
    # 3 cells per shard: bit-identity vs unsharded holds empirically for
    # shards >= 3 cells; degenerate 2-cell shards (= stencil halo) let XLA
    # reassociate the stencil sum differently (~1e-6 drift, still correct)
    grid = Grid.make(12, 12, 24, lengths=2.0)
    # decompose z over both mesh axes: dcn hop outermost, ici inside
    sharded = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32"),
        mesh=mesh,
        decomp=Decomposition.of({0: ("dz_dcn", "dz_ici")}),
    )
    plain = DiffusionSolver(DiffusionConfig(grid=grid, dtype="float32"))
    a = sharded.run(sharded.initial_state(), 3)
    b = plain.run(plain.initial_state(), 3)
    np.testing.assert_array_equal(np.asarray(a.u), np.asarray(b.u))


def test_compound_axis_all_eight_devices_bit_identical(devices):
    """z split over a (2, 4) compound axis — 8 shards across two mesh
    axes — reproduces the unsharded solve bit-for-bit. This is the full
    multi-host layout (2 'hosts' x 4 'chips') on the virtual backend."""
    import numpy as np

    from multigpu_advectiondiffusion_tpu import (
        BurgersConfig,
        BurgersSolver,
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    mesh = make_mesh({"dz_dcn": 2, "dz_ici": 4})
    decomp = Decomposition.of({0: ("dz_dcn", "dz_ici")})
    grid = Grid.make(8, 8, 24, lengths=2.0)

    for cfg_cls, solver_cls, kw in (
        (DiffusionConfig, DiffusionSolver, {}),
        (BurgersConfig, BurgersSolver, {"nu": 1e-5}),
    ):
        sharded = solver_cls(
            cfg_cls(grid=grid, dtype="float32", **kw),
            mesh=mesh,
            decomp=decomp,
        )
        plain = solver_cls(cfg_cls(grid=grid, dtype="float32", **kw))
        a = sharded.run(sharded.initial_state(), 3)
        b = plain.run(plain.initial_state(), 3)
        if cfg_cls is DiffusionConfig:
            np.testing.assert_array_equal(np.asarray(a.u), np.asarray(b.u))
        else:
            # WENO: per-shape FMA contraction drifts a few ulps per step
            # (see tests/test_sharded.py::_WENO_ULPS for the rationale)
            bound = 32 * np.finfo(np.float32).eps
            assert np.abs(np.asarray(a.u) - np.asarray(b.u)).max() <= bound


def test_hybrid_mesh_device_count_mismatch_is_loud(devices):
    with pytest.raises(ValueError, match="devices"):
        multihost.hybrid_mesh({"dz_ici": 4}, {})


def test_hybrid_mesh_impossible_dcn_extent_is_loud(devices):
    """CPU devices carry no slice topology; a DCN extent > process count
    cannot be satisfied and must raise, not silently mis-place."""
    with pytest.raises(ValueError):
        multihost.hybrid_mesh({"dz_ici": 4}, {"dz_dcn": 2})


def test_process_local_devices_and_coordinator(devices):
    assert list(multihost.process_local_devices()) == list(jax.local_devices())
    assert multihost.is_coordinator()  # single-process: process_index 0


_WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
pid, port = int(sys.argv[1]), sys.argv[2]

from multigpu_advectiondiffusion_tpu.parallel import multihost
multihost.initialize(coordinator_address=f"localhost:{port}",
                     num_processes=2, process_id=pid)
import numpy as np
from multigpu_advectiondiffusion_tpu import (
    BurgersConfig, BurgersSolver, DiffusionConfig, DiffusionSolver, Grid)
from multigpu_advectiondiffusion_tpu.parallel.mesh import Decomposition

assert jax.process_count() == 2 and len(jax.devices()) == 8
mesh = multihost.hybrid_mesh({"dz_ici": 4}, {"dz_dcn": 2})
assert mesh.devices.shape == (2, 4)
grid = Grid.make(12, 12, 24, lengths=2.0)
decomp = Decomposition.of({0: ("dz_dcn", "dz_ici")})
for name, cfg_cls, solver_cls, kw, tol in (
    ("diffusion", DiffusionConfig, DiffusionSolver, {}, 0.0),
    # WENO: per-shape FMA contraction drifts a few f32 ulps per step
    # (see test_sharded.py::_WENO_ULPS); adaptive dt adds a cross-
    # process gloo pmax to the mix
    ("burgers", BurgersConfig, BurgersSolver, {"nu": 1e-5},
     32 * np.finfo(np.float32).eps),
):
    cfg = cfg_cls(grid=grid, dtype="float32", **kw)
    solver = solver_cls(cfg, mesh=mesh, decomp=decomp)
    out = solver.run(solver.initial_state(), 4)
    ref_solver = solver_cls(cfg_cls(grid=grid, dtype="float32", **kw))
    ref = np.asarray(ref_solver.run(ref_solver.initial_state(), 4).u)
    worst = max(
        float(np.abs(np.asarray(sh.data) - ref[sh.index]).max())
        for sh in out.u.addressable_shards
    )
    assert worst <= tol, (name, worst, tol)
    print(f"proc {pid}: {name} ok (worst {worst:.2e})", flush=True)

# ---- the FUSED steppers across the real process boundary: ppermute
# ghost refresh, the split-overlap exch, the adaptive-dt pmax, and the
# 2-D per-stage kernels all riding gloo over the DCN axis — the
# reference's only deployment mode is its tuned kernels under mpirun
# (MultiGPU/*/run.sh) ----
ulp = 32 * np.finfo(np.float32).eps
fused_cases = (
    # serialized refresh + global wall offsets (diffusion: the same
    # arithmetic, up to XLA-CPU's per-program FMA contraction — the
    # 4-ulp bound of tests/f32_bound.py)
    ("diffusion3d-fused", DiffusionSolver,
     DiffusionConfig(grid=grid, dtype="float32", impl="pallas"),
     decomp, 4 * np.finfo(np.float32).eps, False),
    # adaptive dt: the pmax wave-speed reduction crosses processes
    ("burgers3d-fused-adaptive", BurgersSolver,
     BurgersConfig(grid=grid, dtype="float32", nu=1e-5, impl="pallas"),
     decomp, ulp, False),
    # split overlap: the exchanged z-slab operands cross the DCN axis
    # while interior stage kernels run (lz=9 -> bz=3, n_bz=3)
    ("burgers3d-fused-split", BurgersSolver,
     BurgersConfig(grid=Grid.make(8, 8, 72, lengths=2.0),
                   dtype="float32", nu=1e-5, adaptive_dt=False,
                   impl="pallas", overlap="split"),
     decomp, ulp, True),
    # 2-D per-stage whole-shard kernels (the 2-D MultiGPU baselines'
    # tuned-kernel-under-MPI configuration)
    ("burgers2d-fused", BurgersSolver,
     BurgersConfig(grid=Grid.make(24, 24, lengths=2.0),
                   dtype="float32", nu=1e-4, impl="pallas"),
     Decomposition.of({0: ("dz_dcn", "dz_ici")}), ulp, False),
)
for name, solver_cls, cfg, dec, tol, want_split in fused_cases:
    solver = solver_cls(cfg, mesh=mesh, decomp=dec)
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded, (name, solver._fused_fallback)
    assert getattr(fused, "overlap_split", False) == want_split, name
    out = solver.run(solver.initial_state(), 3)
    assert ("fused_run", 3) in solver._cache, (name, "fused path not engaged")
    ref_solver = solver_cls(cfg)
    assert ref_solver._fused_stepper() is not None, name
    ref = np.asarray(ref_solver.run(ref_solver.initial_state(), 3).u)
    scale = max(float(np.abs(ref).max()), 1e-30)
    worst = max(
        float(np.abs(np.asarray(sh.data) - ref[sh.index]).max())
        for sh in out.u.addressable_shards
    )
    assert worst <= tol * scale or worst <= tol, (name, worst, tol)
    print(f"proc {pid}: {name} ok (worst {worst:.2e})", flush=True)

# ---- per-shard checkpoint across the process boundary: each process
# writes ONLY its addressable shards (+ manifest), then the state is
# reassembled onto the same mesh — no gather to one host at any point ----
from jax.experimental import multihost_utils
from multigpu_advectiondiffusion_tpu.utils import io as tio

ckdir = sys.argv[4]
cksolver = DiffusionSolver(
    DiffusionConfig(grid=grid, dtype="float32"), mesh=mesh, decomp=decomp)
ckstate = cksolver.run(cksolver.initial_state(), 2)
tio.save_checkpoint_sharded(ckdir, ckstate, grid=grid)
multihost_utils.sync_global_devices("ckpt-written")
back = tio.load_checkpoint_sharded(ckdir, sharding=cksolver.sharding())
assert float(back.t) == float(ckstate.t) and int(back.it) == int(ckstate.it)
want = {tuple(str(s) for s in sh.index): np.asarray(sh.data)
        for sh in ckstate.u.addressable_shards}
got = {tuple(str(s) for s in sh.index): np.asarray(sh.data)
       for sh in back.u.addressable_shards}
assert want.keys() == got.keys()
for k in want:
    assert np.array_equal(want[k], got[k]), k
print(f"proc {pid}: sharded-checkpoint ok", flush=True)
print(f"proc {pid}: MULTIPROC-OK", flush=True)
'''


def test_two_process_distributed_execution(tmp_path):
    """REAL multi-process execution — the capability the reference gets
    from mpirun (``MultiGPU/*/run.sh``): two OS processes, 4 virtual CPU
    devices each, joined by ``multihost.initialize``; ``hybrid_mesh``
    places the DCN axis on process granules; the unchanged sharded
    solvers run with ppermute halo hops (and the adaptive-dt pmax)
    crossing the process boundary over gloo — including the FUSED
    steppers (serialized ghost refresh, the split-overlap exch, and the
    2-D per-stage kernels), the reference's mpirun-plus-tuned-kernels
    deployment mode. Every process's local shards must match a
    locally-computed unsharded reference — bit-exactly for diffusion,
    to the documented WENO ulp bound for Burgers."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # worker output goes to files, not pipes: a full 64 KiB pipe would
    # stall that worker mid-collective, deadlocking its peer until the
    # timeout AND losing all diagnostics
    logs = [tmp_path / f"worker{i}.log" for i in range(2)]
    handles = [open(log, "w") for log in logs]
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(i), str(port), REPO,
                 str(tmp_path / "ckpt.ckptd")],
                stdout=handles[i],
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
            for i in range(2)
        ]
        try:
            for p in procs:
                p.wait(timeout=300)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    finally:
        for h in handles:
            h.close()
    for i, (p, log) in enumerate(zip(procs, logs)):
        out = log.read_text()
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert "MULTIPROC-OK" in out


_CLI_WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, outdir = int(sys.argv[1]), sys.argv[2], sys.argv[4]

from multigpu_advectiondiffusion_tpu.cli.__main__ import main
main([
    "diffusion3d", "--n", "16", "16", "24", "--iters", "3",
    "--mesh", "dz_dcn=2,dz_ici=4", "--impl", "pallas",
    "--save", outdir, "--check-error",
    "--profile", outdir + "/trace",
    "--coordinator", f"localhost:{port}",
    "--num-processes", "2", "--process-id", str(pid),
])
print(f"proc {pid}: CLI-MULTIPROC-OK", flush=True)
'''


def test_two_process_cli_launch(tmp_path):
    """The mpirun analog end-to-end THROUGH THE CLI: two OS processes
    each run `diffusion3d --coordinator ... --mesh dz_dcn=2,dz_ici=4
    --impl pallas --save`, joining via jax.distributed; the compound
    mesh axis puts the DCN hop between process granules, the fused
    per-stage stepper runs shard-local, and file output happens once on
    the coordinator via a cross-process allgather. The reference's only
    deployment mode (`mpirun -np 2 ./Diffusion3d.run ...`,
    MultiGPU/*/run.sh) with restartable, validated artifacts on top."""
    import json

    import numpy as np

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    script = tmp_path / "cli_worker.py"
    script.write_text(_CLI_WORKER)
    outdir = tmp_path / "run"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    logs = [tmp_path / f"cli_worker{i}.log" for i in range(2)]
    handles = [open(log, "w") for log in logs]
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(i), str(port), REPO,
                 str(outdir)],
                stdout=handles[i],
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
            for i in range(2)
        ]
        try:
            for p in procs:
                p.wait(timeout=300)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    finally:
        for h in handles:
            h.close()
    for i, (p, log) in enumerate(zip(procs, logs)):
        out = log.read_text()
        assert p.returncode == 0, f"cli proc {i} failed:\n{out[-3000:]}"
        assert "CLI-MULTIPROC-OK" in out

    # coordinator wrote the artifacts exactly once, from gathered shards
    from multigpu_advectiondiffusion_tpu.utils.io import load_binary

    u = load_binary(str(outdir / "result.bin"), (24, 16, 16))
    assert np.isfinite(u).all()
    summary = json.loads((outdir / "summary.json").read_text())
    assert summary["devices"] == 8
    assert summary["engaged"]["stepper"] == "fused-stage"
    # --check-error computed from allgathered shards on every process
    assert summary["error_l1"] is not None and summary["error_l1"] < 1.0
    # only the coordinator prints the summary block
    assert "kernel path" in logs[0].read_text()
    # --profile in a multi-process launch writes one trace dir PER
    # PROCESS (profile.sh's %q{OMPI_COMM_WORLD_RANK} per-rank naming,
    # MultiGPU/Diffusion3d_Baseline/profile.sh:2), each non-empty
    for rank in (0, 1):
        d = outdir / "trace" / f"rank{rank}"
        assert d.is_dir() and any(d.rglob("*")), f"missing trace for {rank}"
    assert "kernel path" not in logs[1].read_text()


def test_initialize_single_process_smoke():
    """``initialize()`` brings up jax.distributed with one process — the
    InitializeMPI analog — in a subprocess so this process's runtime is
    untouched."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    code = (
        "import os;"
        "os.environ['JAX_PLATFORMS']='cpu';"
        f"import sys; sys.path.insert(0, {REPO!r});"
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "from multigpu_advectiondiffusion_tpu.parallel import multihost;"
        f"multihost.initialize(coordinator_address='localhost:{port}',"
        " num_processes=1, process_id=0);"
        "assert jax.process_count() == 1, jax.process_count();"
        "assert multihost.is_coordinator();"
        "print('initialize-ok')"
    )
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr
    assert "initialize-ok" in res.stdout
