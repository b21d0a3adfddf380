"""Mosaic compiles for a described TPU v5e, no chip attached.

The CPU suite runs every Pallas kernel in interpret mode, which accepts
programs the chip's compiler refuses (unaligned slices, VMEM overruns,
memory-space and aliasing rules). Here the kernels of the main paths are
compiled for a ``v5e:2x2`` topology described by the installed TPU
compiler, with interpret mode forced off in the test: the smoke's
single-chip rungs at their real sizes, the slab whole-run rung, and the
``dz=4`` split-overlap schedules on the four described devices. A
compiler refusal fails the test; only a topology that cannot be
described at all skips.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file (see the ``on-chip-measurement`` guide, §2).

The split-overlap cases also read the overlap out of the compiled
schedule, the machine-checked analog of reading the reference's
five-stream choreography out of an nvprof trace
(``MultiGPU/Diffusion3d_Baseline/main.c:203-260``): a compute fusion or
stage kernel must sit between a ``collective-permute-start`` and its
``-done``.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from multigpu_advectiondiffusion_tpu import (
    BurgersConfig,
    BurgersSolver,
    DiffusionConfig,
    DiffusionSolver,
    Grid,
)
from multigpu_advectiondiffusion_tpu.ops.pallas import (
    fused2d_sharded,
    fused_burgers,
    fused_diffusion,
    fused_slab_run,
    laplacian,
)
from multigpu_advectiondiffusion_tpu.parallel.mesh import Decomposition

KERNEL = (r"= .*custom-call.*tpu_custom_call", "kernel")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower Pallas calls for the chip (the CPU-pinned suite defaults to
    interpret mode, which would compile plain fusions instead), with
    x64 off: the kernels are f32/i32 by design and the suite's x64
    default would put i64 constants into them."""
    for mod in (fused2d_sharded, fused_burgers, fused_diffusion,
                fused_slab_run, laplacian):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    with jax.enable_x64(False):
        yield


def _compile(fn, grid, sharding) -> str:
    u = jax.ShapeDtypeStruct(grid.shape, jnp.float32, sharding=sharding)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=(
        sharding if isinstance(sharding, SingleDeviceSharding) else None))
    return fn.lower(u, t).compile().as_text()


def _schedule_events(txt, extra=()):
    """(line, kind) events of a compiled module's entry schedule: async
    collective-permute starts/dones, compute fusions, and any extra
    (pattern, kind) pairs — text order == schedule order."""
    events = []
    pats = [
        (r"= .*collective-permute-start", "start"),
        (r"= .*collective-permute-done", "done"),
        (r"= .*fusion\(", "fusion"),
        *extra,
    ]
    for i, line in enumerate(txt.splitlines()):
        ls = line.strip()
        for pat, kind in pats:
            if re.search(pat, ls):
                events.append((i, kind))
                break
    return events


def _count_in_windows(events, kind):
    starts = [i for i, k in events if k == "start"]
    dones = [i for i, k in events if k == "done"]
    n = 0
    for s in starts:
        d = min((d for d in dones if d > s), default=None)
        if d is None:
            continue
        n += sum(1 for i, k in events if k == kind and s < i < d)
    return n, bool(starts and dones)


def _kernels_hide_exchange(txt):
    events = _schedule_events(txt, extra=[KERNEL])
    kernels_in, have_pairs = _count_in_windows(events, "kernel")
    assert have_pairs, "expected async collective-permute pairs"
    assert kernels_in > 0, (
        "no tpu_custom_call inside the collective-permute windows — the "
        "interior stage kernel is serialized with the exchange"
    )


# --------------------------------------------------------------------- #
# One chip: the smoke's rungs at their real sizes
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("model,n", [
    ("diffusion", (508, 204, 160)),
    ("burgers", (512, 512, 512)),
])
def test_fused_stage_compiles_at_real_size(topo, one_chip, mosaic, model,
                                           n):
    """One step of the fused-stage rung — three stage kernels — at the
    bench's tiled diffusion grid and the reference's Burgers WENO5 512^3
    (``SingleGPU/Burgers3d_WENO5``)."""
    grid = Grid.make(*n, lengths=2.0)
    if model == "diffusion":
        solver = DiffusionSolver(DiffusionConfig(grid=grid, dtype="float32",
                                                 impl="pallas_stage"))
    else:
        solver = BurgersSolver(BurgersConfig(grid=grid, nu=1e-5,
                                             dtype="float32",
                                             adaptive_dt=False,
                                             impl="pallas_stage"))
    fused = solver._fused_stepper()
    assert fused.engaged_label == "fused-stage"
    txt = _compile(jax.jit(lambda u, t: fused.run(u, t, 1)), grid,
                   one_chip)
    assert txt.count("tpu_custom_call") >= 3, "stage kernels not lowered"


@pytest.mark.parametrize("model", [
    "diffusion",
    # 624 s to compile here (PR 21): far outside tier-1's budget
    pytest.param("burgers", marks=pytest.mark.slow),
])
def test_slab_whole_run_compiles(topo, one_chip, mosaic, model):
    """The slab-pipelined whole-run program (one Pallas program over a
    (timestep, z-slab) grid, stacked ping-pong state) on a multi-slab
    grid: Mosaic's rules for its dynamically indexed stacked-buffer
    DMAs."""
    if model == "diffusion":
        grid = Grid.make(128, 128, 64, lengths=2.0)
        solver = DiffusionSolver(DiffusionConfig(grid=grid, dtype="float32",
                                                 impl="pallas_slab"))
    else:
        grid = Grid.make(128, 64, 64, lengths=2.0)
        solver = BurgersSolver(BurgersConfig(grid=grid, nu=1e-5,
                                             dtype="float32",
                                             adaptive_dt=False,
                                             impl="pallas_slab"))
    fused = solver._fused_stepper()
    assert fused.engaged_label == "fused-whole-run-slab"
    assert fused.n_slabs >= 2, "want a multi-slab pipeline"
    txt = _compile(jax.jit(lambda u, t: fused.run(u, t, 3)), grid,
                   one_chip)
    assert "tpu_custom_call" in txt, "slab kernel did not lower via Mosaic"


# --------------------------------------------------------------------- #
# Four described chips: the sharded schedules
# --------------------------------------------------------------------- #
def test_generic_split_overlap_hides_collectives(topo):
    """The generic XLA ``overlap='split'`` diffusion step on ``dz=4``:
    compute fusions sit inside the collective-permute windows."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("dz",))
    grid = Grid.make(128, 128, 128, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", overlap="split"),
        mesh=mesh, decomp=Decomposition.slab("dz"),
    )
    txt = _compile(solver._wrap(solver._local_step), grid,
                   solver.sharding())
    overlapped, have_pairs = _count_in_windows(_schedule_events(txt),
                                               "fusion")
    assert have_pairs, "expected async collective-permute pairs"
    assert overlapped > 0, "the split overlap is not being hidden"


@pytest.mark.parametrize("model", ["diffusion", "burgers",
                                   "burgers-pencil", "burgers-xghost"])
def test_fused_split_overlap_hides_collectives(topo, mosaic, model):
    """The fused split-overlap schedules on four described chips: the
    interior stage kernel runs while the z halo rides the ICI. Pencil
    and x-sharded meshes add a serialized non-z refresh beside the
    overlapped z exchange."""
    devs = np.asarray(topo.devices[:4])
    burgers = {"nu": 1e-5, "dtype": "float32", "adaptive_dt": False,
               "impl": "pallas", "overlap": "split"}
    if model == "diffusion":
        # local lz = 60 -> bz = 20 -> a real interior band of 3 blocks
        grid = Grid.make(128, 16, 240, lengths=2.0)
        solver = DiffusionSolver(
            DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                            overlap="split"),
            mesh=Mesh(devs, ("dz",)), decomp=Decomposition.slab("dz"),
        )
    else:
        grid = Grid.make(128, 16, 128, lengths=2.0)
        mesh, decomp = {
            "burgers": (Mesh(devs, ("dz",)), Decomposition.slab("dz")),
            "burgers-pencil": (Mesh(devs.reshape(2, 2), ("dz", "dy")),
                               Decomposition.of({0: "dz", 1: "dy"})),
            "burgers-xghost": (Mesh(devs.reshape(2, 2), ("dz", "dx")),
                               Decomposition.of({0: "dz", 2: "dx"})),
        }[model]
        solver = BurgersSolver(BurgersConfig(grid=grid, **burgers),
                               mesh=mesh, decomp=decomp)
    fused = solver._fused_stepper()
    assert fused is not None and fused.overlap_split
    if model == "burgers-xghost":
        assert fused.x_sharded
    refresh, offsets_fn, exch = solver._fused_sharded_ctx(fused)
    assert exch is not None
    assert (refresh is not None) == (
        model in ("burgers-pencil", "burgers-xghost")
    )

    def block(u, t):
        kw = {"exch": exch}
        if refresh is not None:
            kw["refresh"] = refresh
        if offsets_fn is not None and model == "diffusion":
            kw["offsets"] = offsets_fn()
        return fused.run(u, t, 2, **kw)

    _kernels_hide_exchange(_compile(solver._wrap(block), grid,
                                    solver.sharding()))


def test_fused_stage_compiles_at_dz4_shard_size(topo, mosaic):
    """The benchmark's 1024^3 ``dz=4`` march: each chip's 256x1024x1024
    shard runs the fused-stage rung one 4.76 MB plane per block (bz=1),
    its ``v`` planes streaming through a six-plane VMEM ring. A VMEM
    overrun or a lowering refusal at that plane size fails here."""
    grid = Grid.make(1024, 1024, 1024, lengths=10.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                        overlap="split"),
        mesh=Mesh(np.asarray(topo.devices[:4]), ("dz",)),
        decomp=Decomposition.slab("dz"),
    )
    fused = solver._fused_stepper()
    assert fused.engaged_label == "fused-stage"
    assert fused.padded_shape == (260, 1032, 1152)
    assert not fused.overlap_split  # bz = 1 < R: serialized refresh
    refresh, offsets_fn, _ = solver._fused_sharded_ctx(fused)

    def block(u, t):
        return fused.run(u, t, 1, refresh=refresh, offsets=offsets_fn())

    txt = _compile(solver._wrap(block), grid, solver.sharding())
    assert txt.count("tpu_custom_call") >= 3, "stage kernels not lowered"


def test_dma_whole_run_compiles(topo, mosaic, monkeypatch):
    """The in-kernel remote-DMA rung on ``dz=4``: one Pallas program per
    shard whose halo moves by ``make_async_remote_copy`` behind a
    neighbor barrier (collective id 0) — Mosaic refuses a collective id
    without that barrier."""
    from multigpu_advectiondiffusion_tpu.models.base import SolverBase

    # the gate asks the live backend (the CPU here) whether it can run
    # the program; this test only compiles it for the described chips
    monkeypatch.setattr(SolverBase, "_dma_backend_ok",
                        staticmethod(lambda: True))
    grid = Grid.make(128, 16, 144, lengths=2.0)
    solver = DiffusionSolver(
        DiffusionConfig(grid=grid, dtype="float32", impl="pallas_slab",
                        exchange="dma"),
        mesh=Mesh(np.asarray(topo.devices[:4]), ("dz",)),
        decomp=Decomposition.slab("dz"),
    )
    fused = solver._fused_stepper()
    assert fused.exchange == "dma"
    _, offsets_fn, _ = solver._fused_sharded_ctx(fused)

    def block(u, t):
        return fused.run(u, t, 3, offsets=offsets_fn())

    txt = _compile(solver._wrap(block), grid, solver.sharding())
    assert "tpu_custom_call" in txt, "dma program did not lower via Mosaic"


@pytest.mark.parametrize("overlap", ["padded", "split"])
@pytest.mark.parametrize("model", ["burgers", "diffusion", "burgers-weno7"])
def test_fused2d_sharded_compiles(topo, mosaic, model, overlap):
    """The sharded 2-D per-stage steppers (whole-shard VMEM kernels and
    ppermute ghost refresh, or the three-band split-overlap schedule) on
    a ``dy=4`` mesh; under ``split`` a stage kernel hides the exchange."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("dy",))
    grid = Grid.make(256, 256, lengths=2.0)
    decomp = Decomposition.of({0: "dy"})
    if model == "diffusion":
        cfg = DiffusionConfig(grid=grid, dtype="float32", impl="pallas",
                              overlap=overlap)
        solver = DiffusionSolver(cfg, mesh=mesh, decomp=decomp)
    else:
        cfg = BurgersConfig(grid=grid, nu=1e-4, dtype="float32",
                            impl="pallas", overlap=overlap,
                            weno_order=7 if model == "burgers-weno7" else 5)
        solver = BurgersSolver(cfg, mesh=mesh, decomp=decomp)
    fused = solver._fused_stepper()
    assert fused is not None and fused.sharded
    assert fused.overlap_split == (overlap == "split")
    refresh, offsets_fn, exch = solver._fused_sharded_ctx(fused)

    def block(u, t):
        return fused.run(u, t, 2, refresh=refresh, offsets=offsets_fn(),
                         exch=exch)

    txt = _compile(solver._wrap(block), grid, solver.sharding())
    assert "tpu_custom_call" in txt, "stage kernels did not lower via Mosaic"
    assert "collective-permute" in txt, "ghost refresh lost its ppermute"
    if overlap == "split":
        _kernels_hide_exchange(txt)
