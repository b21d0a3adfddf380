"""Batched ensemble engine (ISSUE 9): member independence, loud
declines, member-attributed divergence, and the persistent AOT
executable cache.

Acceptance pins:

* a batched B=8 run is bit-exact (f32) against 8 looped single runs on
  the generic AND fused-stage rungs;
* one member injected to diverge names its index — the others'
  results are unaffected;
* the slab rung and device meshes decline batching loudly;
* a repeat request against a warm AOT cache loads the serialized
  executable (aot_cache:hit, compile seconds saved) instead of
  recompiling; corrupt/stale entries are misses, never crashes.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from multigpu_advectiondiffusion_tpu import (
    BurgersConfig,
    BurgersSolver,
    DiffusionConfig,
    DiffusionSolver,
    EnsembleSolver,
    Grid,
    telemetry,
)
from multigpu_advectiondiffusion_tpu.models.state import (
    EnsembleState,
    SolverState,
)
from multigpu_advectiondiffusion_tpu.resilience.errors import (
    EnsembleMemberDivergedError,
)
from multigpu_advectiondiffusion_tpu.tuning import aot_cache

from f32_bound import assert_f32_close


@pytest.fixture(autouse=True)
def _isolate_aot_cache(monkeypatch):
    """The AOT executable cache is opt-in and per-test: no ambient env
    enablement, fresh process state before and after."""
    monkeypatch.delenv(aot_cache.ENV_PATH, raising=False)
    saved = dict(aot_cache._state)
    aot_cache._state.update(dir=None, enabled=None)
    yield
    aot_cache._state.clear()
    aot_cache._state.update(saved)


def _diff_cfg(impl="xla", **kw):
    g = Grid.make(12, 10, 8, lengths=(1.2, 1.0, 0.8))
    return DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                           impl=impl, ic="gaussian", **kw)


def _members(B):
    return [
        {"ic_params": (("width", 0.1 + 0.02 * i),)} for i in range(B)
    ]


def _assert_bit_exact(es, B, iters, ulps=0):
    """Every member equals its looped single run: bit for bit, or
    within ``ulps`` f32 ulps where the batched program is a different
    fused kernel program (``f32_bound``)."""
    est = es.initial_state()
    out = es.run(est, iters)
    assert isinstance(out, EnsembleState) and out.members == B
    for i in range(B):
        ms = es.member_solver(i)
        ref = ms.run(ms.initial_state(), iters)
        what = f"member {i} diverged from its looped single run"
        if ulps:
            assert_f32_close(out.u[i], ref.u, ulps, what)
        else:
            np.testing.assert_array_equal(
                np.asarray(out.u[i]), np.asarray(ref.u), err_msg=what,
            )
        assert float(out.t[i]) == float(ref.t)
    return out


# --------------------------------------------------------------------- #
# Member independence: batched == looped, bit-exact
# --------------------------------------------------------------------- #
def test_batched_b8_bit_exact_generic_diffusion():
    es = EnsembleSolver(DiffusionSolver, _diff_cfg("xla"), _members(8))
    _assert_bit_exact(es, 8, 3)
    assert es.engaged_path()["stepper"] == "ensemble-vmap[generic-xla]"


def test_batched_b8_bit_exact_fused_stage_diffusion():
    g = Grid.make(16, 12, 10, lengths=(1.6, 1.2, 1.0))
    cfg = DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                          impl="pallas_stage")
    es = EnsembleSolver(DiffusionSolver, cfg, 8)
    _assert_bit_exact(es, 8, 2, ulps=4)
    assert es.engaged_path()["stepper"] == "ensemble-vmap[fused-stage]"


def test_batched_b8_bit_exact_generic_burgers():
    cfg = BurgersConfig(grid=Grid.make(24, 8, 8, lengths=2.0), nu=1e-5,
                        adaptive_dt=False, dtype="float32", impl="xla")
    es = EnsembleSolver(BurgersSolver, cfg, _members(8))
    _assert_bit_exact(es, 8, 3)


@pytest.mark.slow
def test_batched_b8_ulp_exact_fused_stage_burgers():
    """Heavy variant (WENO5 per-stage Pallas kernels, interpret mode on
    CPU, vmapped B=8) — slow-marked so tier-1 stays inside its window;
    the fused-stage rung's BIT-exactness is tier-1-proven on diffusion
    above. WENO under a batched lowering reassociates at ulp level
    (measured max 1.2e-7 over 2 steps here) — the same equality grade
    the PR 4 deep-halo suite holds WENO5 to (diffusion bit-exact,
    WENO ulp; tests/test_comm_avoid.py)."""
    cfg = BurgersConfig(grid=Grid.make(16, 8, 8, lengths=2.0), nu=1e-5,
                        adaptive_dt=False, dtype="float32",
                        impl="pallas_stage")
    es = EnsembleSolver(BurgersSolver, cfg, _members(8))
    est = es.initial_state()
    out = es.run(est, 2)
    assert es.engaged_path()["stepper"] == "ensemble-vmap[fused-stage]"
    assert np.isfinite(np.asarray(out.u)).all()
    for i in range(8):
        ms = es.member_solver(i)
        ref = ms.run(ms.initial_state(), 2)
        np.testing.assert_allclose(
            np.asarray(out.u[i]), np.asarray(ref.u), rtol=0, atol=1e-6,
            err_msg=f"member {i} diverged past ulp from its single run",
        )
        assert float(out.t[i]) == float(ref.t)


# --------------------------------------------------------------------- #
# Member-varying scalars ride as batched operands
# --------------------------------------------------------------------- #
def test_member_varying_diffusivity_operand():
    Ks = [0.5, 1.0, 2.0]
    es = EnsembleSolver(DiffusionSolver, _diff_cfg("xla"),
                        [{"diffusivity": k} for k in Ks])
    est = es.initial_state()
    out = es.run(est, 3)
    assert es.engaged_path()["operands"] == ["diffusivity"]
    assert es.engaged_path()["stepper"] == "ensemble-vmap[generic-xla]"
    for i, K in enumerate(Ks):
        ms = es.member_solver(i)
        assert ms.cfg.diffusivity == K
        ref = ms.run(ms.initial_state(), 3)
        # the member's own stability dt moved with K — times match
        # exactly; the field matches to ulp (traced vs constant-folded
        # scalar multiply)
        assert float(out.t[i]) == pytest.approx(float(ref.t), abs=0.0)
        np.testing.assert_allclose(
            np.asarray(out.u[i]), np.asarray(ref.u), rtol=0, atol=1e-5,
        )


def test_member_varying_diffusivity_under_pallas_impl():
    """Regression (caught by the verify drive): a Pallas-flavored impl
    plus a member-varying K used to push the traced operand into the
    per-axis Pallas laplacian, which rejects captured traced constants.
    The operand path must route that op to XLA and say so."""
    g = Grid.make(16, 12, 10, lengths=(1.6, 1.2, 1.0))
    cfg = DiffusionConfig(grid=g, diffusivity=1.0, dtype="float32",
                          impl="pallas_stage")
    es = EnsembleSolver(DiffusionSolver, cfg,
                        [{"diffusivity": 0.5}, {"diffusivity": 2.0}])
    out = es.run(es.initial_state(), 2)
    assert es.engaged_path()["stepper"] == "ensemble-vmap[generic-xla]"
    for i, K in enumerate((0.5, 2.0)):
        ms = es.member_solver(i)
        ref = ms.run(ms.initial_state(), 2)
        assert float(out.t[i]) == float(ref.t)
        np.testing.assert_allclose(
            np.asarray(out.u[i]), np.asarray(ref.u), rtol=0, atol=1e-5,
        )


def test_member_varying_cfl_and_riemann_states_burgers():
    cfg = BurgersConfig(grid=Grid.make(64), dtype="float32",
                        adaptive_dt=False, ic="riemann", impl="xla")
    members = [
        {"cfl": 0.3, "ic_params": (("left", 2.0), ("right", 1.0))},
        {"cfl": 0.4, "ic_params": (("left", 1.5), ("right", 0.5))},
        {"cfl": 0.2, "ic_params": (("left", 1.0), ("right", -1.0))},
    ]
    es = EnsembleSolver(BurgersSolver, cfg, members)
    out = es.run(es.initial_state(), 5)
    for i in range(3):
        ms = es.member_solver(i)
        ref = ms.run(ms.initial_state(), 5)
        assert float(out.t[i]) == pytest.approx(float(ref.t), rel=1e-6)
        np.testing.assert_allclose(
            np.asarray(out.u[i]), np.asarray(ref.u), rtol=0, atol=1e-5,
        )
    rows = es.member_summaries(out)
    assert [r["member"] for r in rows] == [0, 1, 2]
    assert all("mass_drift" in r for r in rows)
    assert rows[1]["overrides"]["cfl"] == 0.4


def test_advance_to_ensemble_lands_every_member():
    Ks = [0.5, 1.0, 2.0]
    cfg = _diff_cfg("xla")
    es = EnsembleSolver(DiffusionSolver, cfg,
                        [{"diffusivity": k} for k in Ks])
    est = es.initial_state()
    t_end = float(est.t[0]) + 0.002
    out = es.advance_to(est, t_end)
    its = np.asarray(out.it)
    assert np.allclose(np.asarray(out.t), t_end, atol=1e-6)
    # smaller K -> bigger stable dt -> fewer steps; counts are
    # per-member (finished members freeze in the vmapped while loop)
    assert its[0] < its[2], its


# --------------------------------------------------------------------- #
# Loud declines + member-attributed divergence
# --------------------------------------------------------------------- #
def test_slab_pin_rides_the_b_folded_grid():
    """Since the mesh-scale round the slab pin is ADMITTED: uniform-
    physics ensembles fold B into the whole-run slab grid instead of
    being declined (tests/test_ensemble_mesh.py proves bit-exactness);
    member-varying operands still decline the pin loudly — the fold
    bakes uniform physics."""
    es = EnsembleSolver(DiffusionSolver, _diff_cfg("pallas_slab"), 4)
    out = es.run(es.initial_state(), 2)
    assert es.engaged_path()["stepper"] == (
        "ensemble-fold[fused-whole-run-slab]"
    )
    assert out.members == 4
    with pytest.raises(ValueError, match="uniform physics"):
        es2 = EnsembleSolver(
            DiffusionSolver, _diff_cfg("pallas_slab"),
            [{"diffusivity": 0.5}, {"diffusivity": 2.0}],
        )
        es2.run(es2.initial_state(), 1)


def test_spatial_only_mesh_declines_batching_loudly(devices):
    """A mesh WITHOUT a members axis still declines loudly: a purely
    spatial mesh shards one member's grid — ensembles compose with a
    mesh through the 'members' axis (tests/test_ensemble_mesh.py)."""
    from multigpu_advectiondiffusion_tpu.parallel.mesh import (
        Decomposition,
        make_mesh,
    )

    mesh = make_mesh({"dz": 2}, devices=devices[:2])
    with pytest.raises(ValueError, match="members"):
        EnsembleSolver(DiffusionSolver, _diff_cfg("xla"), 4,
                       mesh=mesh, decomp=Decomposition.slab("dz"))


def test_unknown_member_override_rejected():
    with pytest.raises(ValueError, match="weno_order"):
        EnsembleSolver(BurgersSolver,
                       BurgersConfig(grid=Grid.make(32), impl="xla"),
                       [{"weno_order": 7}])


def test_diverging_member_names_index_others_unaffected():
    B = 6
    es = EnsembleSolver(DiffusionSolver, _diff_cfg("xla"), _members(B))
    est = es.initial_state()
    # poison member 3 in the evolving interior (wall cells would be
    # legitimately re-clamped by the Dirichlet post step)
    bad = est.u.at[3, 4, 5, 6].set(jnp.nan)
    est = EnsembleState(u=bad, t=est.t, it=est.it)
    out = es.run(est, 2)
    with pytest.raises(EnsembleMemberDivergedError) as exc:
        es.check_health(out)
    assert exc.value.members == [3]
    assert "member" in str(exc.value)
    # every healthy member is bit-exact against its looped single run
    for i in (0, 1, 2, 4, 5):
        ms = es.member_solver(i)
        ref = ms.run(ms.initial_state(), 2)
        np.testing.assert_array_equal(
            np.asarray(out.u[i]), np.asarray(ref.u),
            err_msg=f"healthy member {i} was poisoned by member 3",
        )


def test_ensemble_dispatch_event_schema(tmp_path):
    from multigpu_advectiondiffusion_tpu.telemetry import schema

    path = str(tmp_path / "ev.jsonl")
    es = EnsembleSolver(DiffusionSolver, _diff_cfg("xla"), 3)
    est = es.initial_state()
    with telemetry.capture(path):
        es.run(est, 2)
    evs = [json.loads(line) for line in open(path)]
    disp = [e for e in evs if e["kind"] == "ensemble"]
    assert disp and disp[0]["name"] == "dispatch"
    assert disp[0]["members"] == 3
    assert disp[0]["stepper"] == "ensemble-vmap[generic-xla]"
    for e in evs:
        assert schema.validate_event(e) == [], e


# --------------------------------------------------------------------- #
# Persistent AOT executable cache
# --------------------------------------------------------------------- #
def test_aot_cache_cold_store_warm_hit(tmp_path):
    aot_cache.configure(cache_dir=str(tmp_path / "aot"), enabled=True)
    cfg = _diff_cfg("xla")
    mpath = str(tmp_path / "cold.jsonl")
    es1 = EnsembleSolver(DiffusionSolver, cfg, 3)
    est = es1.initial_state()
    with telemetry.capture(mpath):
        cold = es1.run(est, 2)
    evs = [json.loads(line) for line in open(mpath)]
    stores = [e for e in evs if e["kind"] == "aot_cache"
              and e["name"] == "store"]
    assert stores and all(e["persisted"] for e in stores)
    assert not [e for e in evs if e["kind"] == "aot_cache"
                and e["name"] == "hit"]

    # a FRESH solver (new dispatch cache, same config) must load the
    # serialized executable instead of recompiling — and compute the
    # same answer
    wpath = str(tmp_path / "warm.jsonl")
    es2 = EnsembleSolver(DiffusionSolver, cfg, 3)
    with telemetry.capture(wpath):
        warm = es2.run(est, 2)
    evs = [json.loads(line) for line in open(wpath)]
    hits = [e for e in evs if e["kind"] == "aot_cache"
            and e["name"] == "hit"]
    assert hits, evs
    assert all(e["compile_seconds_saved"] > 0 for e in hits)
    assert not [e for e in evs if e["kind"] == "aot_cache"
                and e["name"] in ("miss", "store")]
    xla = [e for e in evs if e["kind"] == "xla" and e["name"] == "cost"]
    assert xla and all(e["aot"] == "hit" for e in xla)
    np.testing.assert_array_equal(np.asarray(cold.u), np.asarray(warm.u))


def test_aot_cache_key_separates_configs(tmp_path):
    """A (shape/dtype/impl/B)-different request never resolves to a
    stored executable — different keys, different entries."""
    aot_cache.configure(cache_dir=str(tmp_path / "aot"), enabled=True)
    cfg = _diff_cfg("xla")
    s1 = DiffusionSolver(cfg)
    s1.run(s1.initial_state(), 2)
    n_entries = len(os.listdir(str(tmp_path / "aot")))
    assert n_entries >= 1
    # same program key, different B -> distinct entries (the program
    # key carries B; the avals differ too)
    es = EnsembleSolver(DiffusionSolver, cfg, 2)
    es.run(es.initial_state(), 2)
    es2 = EnsembleSolver(DiffusionSolver, cfg, 4)
    es2.run(es2.initial_state(), 2)
    assert len(os.listdir(str(tmp_path / "aot"))) > n_entries + 1


def test_aot_cache_corrupt_and_stale_entries_are_misses(tmp_path):
    root = str(tmp_path / "aot")
    aot_cache.configure(cache_dir=root, enabled=True)
    cfg = _diff_cfg("xla")
    s1 = DiffusionSolver(cfg)
    st = s1.initial_state()
    s1.run(st, 2)
    entries = [os.path.join(root, n) for n in os.listdir(root)]
    assert entries
    # truncate every entry: the warm run must MISS (with a reason),
    # recompile, and still produce the right answer
    for p in entries:
        with open(p, "wb") as f:
            f.write(b"\x80corrupt")
    mpath = str(tmp_path / "ev.jsonl")
    s2 = DiffusionSolver(cfg)
    with telemetry.capture(mpath):
        out = s2.run(st, 2)
    evs = [json.loads(line) for line in open(mpath)]
    misses = [e for e in evs if e["kind"] == "aot_cache"
              and e["name"] == "miss"]
    assert misses and all(e["reason"] for e in misses)
    ref = DiffusionSolver(cfg).run(st, 2)
    np.testing.assert_array_equal(np.asarray(out.u), np.asarray(ref.u))


def test_aot_cache_disabled_by_default(tmp_path):
    mpath = str(tmp_path / "ev.jsonl")
    s = DiffusionSolver(_diff_cfg("xla"))
    with telemetry.capture(mpath):
        s.run(s.initial_state(), 1)
    evs = [json.loads(line) for line in open(mpath)]
    assert not [e for e in evs if e["kind"] == "aot_cache"]
    assert not aot_cache.enabled()


# --------------------------------------------------------------------- #
# CLI surface
# --------------------------------------------------------------------- #
def test_cli_ensemble_sweep(tmp_path):
    from multigpu_advectiondiffusion_tpu.cli.__main__ import main

    save = str(tmp_path / "out")
    mpath = str(tmp_path / "ev.jsonl")
    main([
        "diffusion3d", "--n", "12", "10", "8", "--iters", "3",
        "--ensemble", "3", "--sweep", "K=0.5:2.0",
        "--save", save, "--metrics", mpath,
    ])
    summary = json.load(open(os.path.join(save, "ensemble_summary.json")))
    assert summary["ensemble"] == 3
    assert len(summary["members"]) == 3
    ks = [m["overrides"]["diffusivity"] for m in summary["members"]]
    assert ks == pytest.approx([0.5, 1.25, 2.0])
    assert summary["mlups_members"] > 0
    assert summary["engaged"]["stepper"].startswith("ensemble-vmap")
    assert os.path.exists(os.path.join(save, "ensemble_result.bin"))
    evs = [json.loads(line) for line in open(mpath)]
    assert [e for e in evs if e["kind"] == "ensemble"]


def test_cli_ensemble_rejects_single_run_supervision(tmp_path):
    from multigpu_advectiondiffusion_tpu.cli.__main__ import main

    with pytest.raises(ValueError, match="checkpoint-every"):
        main([
            "diffusion3d", "--n", "12", "10", "8", "--iters", "2",
            "--ensemble", "2", "--checkpoint-every", "1",
            "--save", str(tmp_path),
        ])


def test_tuner_key_carries_ensemble_dimension(devices):
    """Satellite: a B=64 tuning decision can never be served to a B=1
    run — the ensemble member count is a first-class key dimension."""
    from multigpu_advectiondiffusion_tpu import tuning

    cfg = dataclasses.replace(_diff_cfg("xla"), impl="auto")
    k1 = tuning.make_key(DiffusionSolver, cfg, None, None, "cpu")
    k1b = tuning.make_key(DiffusionSolver, cfg, None, None, "cpu",
                          ensemble=1)
    k64 = tuning.make_key(DiffusionSolver, cfg, None, None, "cpu",
                          ensemble=64)
    assert k1 == k1b
    assert k64 != k1
    assert "ens=64" in k64 and "ens=1" in k1
