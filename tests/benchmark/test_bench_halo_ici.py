"""``halo_ici_roofline``: the halo exchange's share of the interconnect
peak, on a made-up trace against a hand count, with each chip charged
the z-neighbours of the slab it holds, and on the small traces recorded
on v5e chips."""

from __future__ import annotations

import math
import types

import pytest

from bench_testlib import ROOT

from benchmark import trace as tr
from benchmark.spec import load_plugin

DATA = ROOT / "benchmark" / "testdata"
PEAKS = {"ici_bits_per_s": 1600e9}


def _metric():
    return load_plugin(str(ROOT), "layer_metrics", "halo_ici_roofline")


def _read(trace, cfg, steps):
    return _metric().read(types.SimpleNamespace(trace=trace, cfg=cfg,
                                                steps=steps, peaks=PEAKS))


def test_made_up_share():
    """Device 0 has two transfers in flight, 1-3 s and 2-4 s (3 s in
    all) and a collective that computes; device 1 has none in flight,
    so it is left out of the mean."""
    op = tr.Op
    t = tr.Trace(
        window=(0.0, 10.0),
        devices={0: [op(0, 10, "tpucfd.fused-stage"),
                     op(1, 3, "collective-permute-start", in_flight=True),
                     op(2, 4, "collective-permute-start", in_flight=True),
                     op(5, 6, "collective-permute-done"),
                     op(6, 9, "copy-start", in_flight=True)],
                 1: [op(0, 10, "tpucfd.fused-stage")]},
        host=[(0.0, 10.0, tr.WINDOW_SPAN)])
    cfg = {"family": "burgers3d", "grid_xyz": [10, 20, 8], "dtype": "float32",
           "stages": 3, "weno_order": 5, "mesh": {"dz": 2}}
    # 1000 steps x 3 stages x one z-neighbour (either chip of a chain
    # of two) x R = 3 planes of 20x10 cells x 4 B, over 3 s at 200 GB/s
    moved = 1000 * 3 * 1 * 3 * 200 * 4
    assert _read(t, cfg, 1000) == pytest.approx(100 * moved / (3 * 200e9))
    # the central O4 Laplacian exchanges R = 2 planes
    lap = dict(cfg, family="diffusion3d", order=4)
    del lap["weno_order"]
    assert _read(t, lap, 1000) == pytest.approx(100 * moved * 2 / 3
                                                / (3 * 200e9))
    assert _read(t, dict(cfg, mesh=None), 1000) is None
    assert _read(t, cfg, 0) is None
    quiet = tr.Trace(window=t.window, devices={0: t.devices[1],
                                               1: t.devices[1]},
                     host=t.host)
    assert _read(quiet, cfg, 1000) is None


def test_a_chip_is_charged_its_own_slabs_neighbours():
    """On a chain of four slabs placed as 0, 1, 3, 2 (a 2x2 host), the
    chips at the ends have one z-neighbour and the two inside have two:
    only the chips with collectives in flight count, each at its own."""
    metric = _metric()
    order = [0, 1, 3, 2]
    assert [metric.neighbours(order, d) for d in range(4)] == [1, 2, 1, 2]
    op = tr.Op
    busy = [op(0, 10, "tpucfd.fused-stage"),
            op(1, 3, "collective-permute-start", in_flight=True)]
    t = tr.Trace(window=(0.0, 10.0),
                 devices={0: busy, 1: [busy[0]], 2: [busy[0]], 3: busy},
                 host=[(0.0, 10.0, tr.WINDOW_SPAN)])
    cfg = {"family": "burgers3d", "grid_xyz": [10, 20, 32],
           "dtype": "float32", "stages": 3, "weno_order": 5,
           "mesh": {"dz": 4}}
    one = 100 * 1000 * 3 * 3 * 200 * 4 / (2 * 200e9)
    # device 0 holds an end slab, device 3 slab 2 of 0..3
    assert metric.share(t, cfg, 1000, PEAKS, order) == pytest.approx(
        (one + 2 * one) / 2)
    only0 = tr.Trace(window=t.window, devices={**t.devices, 3: busy[:1]},
                     host=t.host)
    assert metric.share(only0, cfg, 1000, PEAKS, order) == pytest.approx(one)
    assert metric.share(only0, cfg, 1000, PEAKS, [0]) is None


def test_the_slab_order_is_the_benchmarks_mesh(devices):
    """The reader places slabs with the benchmark's own mesh on this
    host's devices: four distinct ids, the first four devices."""
    order = _metric().slab_order({"mesh": {"dz": 4}})
    assert sorted(order) == sorted(d.id for d in devices[:4])


def test_recorded_traces():
    """The four-chip recording (a serialized-refresh diffusion march
    whose 64x72x256 padded shards hold 60 planes of at most 68x252
    interior cells, R = 2) reads a finite share of the peak, with only
    ``TPU:0``'s async line filled and that chip holding an end slab;
    the one-chip recording reads nothing."""
    t = tr.read(str(DATA / "diffusion_dz4.xplane.pb"))
    lo, hi = t.window
    stages = sum(1 for o in t.devices[0] if o.name == "tpucfd.fused-stage"
                 and lo <= o.start < hi)
    cfg = {"family": "diffusion3d", "grid_xyz": [252, 68, 240],
           "dtype": "float32", "stages": 3, "order": 4, "mesh": {"dz": 4}}
    share = _read(t, cfg, stages // 3)
    assert math.isfinite(share) and 0 < share <= 100
    in_flight = [d for d, ops in t.devices.items()
                 if any(o.in_flight for o in ops)]
    assert in_flight == [0]
    assert share == pytest.approx(
        _metric().share(t, cfg, stages // 3, PEAKS, [0, 1, 2, 3]))
    one = tr.read(str(DATA / "diffusion_1chip.xplane.pb"))
    assert _read(one, dict(cfg, mesh=None), 100) is None
