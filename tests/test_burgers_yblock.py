"""How the fused WENO stage kernel picks its y block: the widest
multiple of 8 that tiles the plane's rows, up to the order's measured
best height, so a plane of 1,000 rows runs 40-row blocks where powers of
two left it 8. The picker's choices are checked from shapes alone (no
kernel runs); interpret-mode runs on a dz=4 split schedule check such
blocks against the generic XLA path."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multigpu_advectiondiffusion_tpu import BurgersConfig, BurgersSolver, Grid
from multigpu_advectiondiffusion_tpu.ops import flux as flux_lib
from multigpu_advectiondiffusion_tpu.ops.pallas.fused_burgers import (
    FusedBurgersStepper,
)
from multigpu_advectiondiffusion_tpu.parallel.mesh import (
    Decomposition,
    make_mesh,
)

F32 = 4


def _stepper(shape, order=5, **kw):
    return FusedBurgersStepper(
        shape, jnp.float32, (0.01, 0.01, 0.01), flux_lib.get("burgers"),
        "js", 0.0, dt=1e-3, order=order, **kw)


def test_512_cube_keeps_its_block_and_plan():
    """64 divides 512: the one-chip 512^3 cell's block, padded shape and
    planned bytes stay as they were, with no dead rows."""
    st = _stepper((512, 512, 512))
    assert st.block == (8, 64) and st.y_dead_rows == 0
    assert st.padded_shape == (518, 528, 512)
    assert st.hbm_bytes_per_step == 6_276_775_936
    assert st.plan() == {"hbm_bytes_per_step": 6_276_775_936,
                         "block": "8x64", "y_dead_rows": 0}


def test_dz4_burgers_shard_takes_a_wide_block():
    """The four-chip Burgers cell's 1000x1000x200 shard: 1,000 rows, which
    64 does not divide, run 8x40 blocks with no dead rows. Its planned
    bytes count 25 z-blocks x 25 y-blocks of a (8 + 6) x (40 + 16)-row box
    and one or two 8x40-row cores, plus the edge y-blocks' 8x8-row
    margins, in each of the split schedule's calls; the halo is as
    before."""
    st = _stepper((200, 1000, 1000), global_shape=(800, 1000, 1000),
                  overlap_split=True)
    bz, by = st.block
    assert by >= 32 and st.overlap_split
    assert (bz, by) == (8, 40) and st.y_dead_rows == 0
    assert st.padded_shape == (206, 1016, 1024)
    box, core, margins = 14 * 56, 8 * 40, 2 * 8 * 8
    per_stage = [25 * (25 * (box + k * core) + margins) for k in (1, 2, 2)]
    assert st.hbm_bytes_per_step == sum(per_stage) * 1024 * F32
    assert st.hbm_bytes_per_step == 10_156_441_600
    assert st.halo_bytes_per_step == 74_907_648
    assert st.plan() == {"hbm_bytes_per_step": 10_156_441_600,
                         "halo_bytes_per_step": 74_907_648,
                         "block": "8x40", "y_dead_rows": 0}


@pytest.mark.parametrize("ly,by", [(1000, 40), (960, 64), (48, 48), (24, 24)])
def test_y_sharded_shards_keep_exact_tiling(ly, by):
    """A y-sharded axis ships its core's edge rows as ghosts, so its
    shards work exactly their rows, and an explicit block that does not
    divide them is refused."""
    st = _stepper((24, ly, 128), y_sharded=True)
    assert st.block[1] == by and st.y_dead_rows == 0
    assert st.padded_shape[1] == ly + 16
    with pytest.raises(ValueError, match="block"):
        _stepper((24, ly, 128), y_sharded=True, block=(8, 56))


def test_explicit_block_rounds_rows_up():
    """An explicit block that does not tile the rows works them rounded
    up to whole blocks, the rows past the plane dead."""
    st = _stepper((200, 1000, 1000), block=(8, 64))
    assert st.y_dead_rows == 24 and st.padded_shape == (206, 1040, 1024)


@pytest.mark.parametrize("shape,block,padded", [
    ((512, 512, 512), (8, 32), (520, 528, 512)),
    ((200, 1000, 1000), (8, 8), (208, 1016, 1024)),  # 40 > 32
])
def test_weno7_keeps_its_height(shape, block, padded):
    st = _stepper(shape, order=7)
    assert st.block == block and st.y_dead_rows == 0
    assert st.padded_shape == padded


@pytest.mark.parametrize("shape,kw", [
    ((200, 1000, 1000), {}),
    ((24, 20, 16), {}),
    ((24, 24, 16), {}),
    ((24, 1000, 128), {"y_sharded": True}),
    ((24, 20, 128), {"y_sharded": True}),  # 20 rows cannot tile exactly
    ((8, 16, 200_000), {}),  # no block fits the VMEM budget
    ((512, 512, 512), {"order": 7}),
    ((200, 1000, 1000), {"order": 7}),
])
def test_supported_agrees_with_the_constructor(shape, kw):
    ok = FusedBurgersStepper.supported(shape, jnp.float32, **kw)
    try:
        _stepper(shape, **kw)
    except ValueError:
        built = False
    else:
        built = True
    assert ok == built


def _noise(grid, dtype):
    """Values up to 0.5 in every cell, the plane's last rows included,
    whose ghosts the dead rows carry."""
    return jax.random.uniform(jax.random.PRNGKey(26), grid.shape, dtype,
                              -0.5, 0.5)


@pytest.mark.parametrize("ny,block,dead", [(20, "8x24", 4),
                                            (78, "8x40", 2)])
def test_wide_y_block_split_schedule_matches_xla(devices, ny, block, dead):
    """The smallest dz=4 split shapes whose blocks the rule widened past
    a power of two: 20-row planes run one 24-row block (they ran three
    8-row ones), 78-row planes two 40-row blocks (ten 8-row ones), each
    with the dead rows that round the plane to the sublane tile, in
    24-plane shards. Five steps with edge ghosts from noise match the
    generic XLA path."""
    grid = Grid.make(16, ny, 96, lengths=(1.0, ny / 16, 6.0))
    cfg = dict(nu=0.0, cfl=0.3, dtype="float32", adaptive_dt=False,
               bc="edge", ic=_noise)
    fused = BurgersSolver(
        BurgersConfig(grid=grid, impl="pallas", overlap="split", **cfg),
        mesh=make_mesh({"dz": 4}, devices=devices[:4]),
        decomp=Decomposition.slab("dz"))
    eng = fused.engaged_path()
    assert eng["stepper"] == "fused-stage" and eng["overlap"] == "split"
    assert eng["block"] == block and eng["y_dead_rows"] == dead
    xla = BurgersSolver(BurgersConfig(grid=grid, impl="xla", **cfg))
    got = np.asarray(fused.run(fused.initial_state(), 5).u)
    ref = np.asarray(xla.run(xla.initial_state(), 5).u)
    scale = float(np.max(np.abs(ref)))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6 * scale)
