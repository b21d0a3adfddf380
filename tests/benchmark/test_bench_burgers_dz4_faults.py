"""``correct`` comes out false on the four-chip Burgers cell when the
halo exchange is left out, the step stalls or does nothing, or one cell
of the answer is altered (tiny grid, CPU)."""

from __future__ import annotations

import types

import jax
import pytest

from bench_testlib import run_cell
from test_bench_burgers_dz4 import CELL, tiny_cell_tree

WARMUP_CHUNKS = 2  # benchmark/traffic/march.json; one step a chunk here


def _halo_left_out(monkeypatch):
    """Each shard gets its own edge planes back instead of its
    neighbour's: the exchange between chips left out."""
    from multigpu_advectiondiffusion_tpu.parallel import halo

    lax = types.SimpleNamespace(**{k: getattr(jax.lax, k)
                                   for k in dir(jax.lax)
                                   if not k.startswith("_")})
    lax.ppermute = lambda x, axis_name, perm: x
    monkeypatch.setattr(halo, "lax", lax)


def _stalled(monkeypatch):
    """A step that returns its state unchanged once the warm-up's real
    steps are done."""
    from multigpu_advectiondiffusion_tpu.models import base

    real = base.SolverBase.run

    def run(self, state, n):
        if int(state.it) >= WARMUP_CHUNKS * n:
            return state
        return real(self, state, n)

    monkeypatch.setattr(base.SolverBase, "run", run)


def _unchanged(monkeypatch):
    """A step that returns its state unchanged from the first call."""
    from multigpu_advectiondiffusion_tpu.models import base

    monkeypatch.setattr(base.SolverBase, "run",
                        lambda self, state, n: state)


def _altered(monkeypatch):
    """One cell of the answer changed where it is produced."""
    from multigpu_advectiondiffusion_tpu.models import base

    real = base.SolverBase.run

    def run(self, state, n):
        out = real(self, state, n)
        i = tuple(k // 2 for k in out.u.shape)
        return out._replace(u=out.u.at[i].add(0.05))

    monkeypatch.setattr(base.SolverBase, "run", run)


@pytest.mark.parametrize(
    "fault", [_halo_left_out, _stalled, _unchanged, _altered],
    ids=["halo_left_out", "stalled", "unchanged", "altered"])
def test_a_fault_is_not_correct(tmp_path, monkeypatch, capsys, fault):
    fault(monkeypatch)
    rc, line, err = run_cell(tiny_cell_tree(tmp_path), CELL, monkeypatch,
                             capsys, seed=2**34 + 9, seconds=0.01)
    assert rc == 0, err
    assert line["correct"] is False and line["failed"] >= 1
    check = line["checks"]["max_rel_err"]
    assert check["value"] > check["limit"]
