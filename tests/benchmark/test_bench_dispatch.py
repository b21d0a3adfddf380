"""The dispatch layer's readers (``dispatch_host_us``,
``dispatch_to_device_us``): known answers on a made-up trace, nothing
read where the program opens no top-level span, and the program's spans
as the harness reads them from a CPU run and from a recorded v5e trace."""

from __future__ import annotations

import glob
import types

import pytest

from bench_testlib import ROOT, run_cell, tiny_tree

from benchmark import dispatch_spans as ds
from benchmark import trace as tr
from benchmark.spec import load_plugin

DATA = ROOT / "benchmark" / "testdata"
READERS = ("dispatch_host_us", "dispatch_to_device_us")


def _metric(name):
    return load_plugin(str(ROOT), "layer_metrics", name)


def _read(t):
    return {n: _metric(n).read(types.SimpleNamespace(trace=t))
            for n in READERS}


def _top_span_stats(path) -> dict:
    """``(start, end) -> stats`` of the ``tpucfd.dispatch`` spans of the
    one ``.xplane.pb`` at or under ``path``, on :func:`trace.read`'s
    clock."""
    from jax.profiler import ProfileData

    if not str(path).endswith(".xplane.pb"):
        (path,) = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ds.TOP_SPAN:
                    out[(e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)] = dict(e.stats)
    return out


def _made_up(device_offset=0.0):
    """One chip in a 10 s window and three chunks, top-level spans at
    1-1.5, 4-4.4 and 7-7.3 s on the host's clock; a span nested in the
    second and one before the window are not chunks. The first chunk's
    program call returns at 1.2 s, the second's (inside its nested
    span) at 4.18 s, before two later calls that count for nothing, and
    the third has none; a call before the window counts for no chunk. The chip
    runs 0.5 s after each call on a device clock that reads
    ``device_offset`` seconds early, which no reader looks at."""
    ex = ds.EXECUTE_SPAN
    host = [(-1.0, -0.5, ds.TOP_SPAN), (-0.9, -0.8, ex),
            (0.0, 10.0, tr.WINDOW_SPAN),
            (1.0, 1.5, ds.TOP_SPAN), (1.1, 1.2, ex),
            (4.0, 4.4, ds.TOP_SPAN), (4.1, 4.2, ds.TOP_SPAN),
            (4.15, 4.18, ex), (4.25, 4.3, ex), (4.31, 4.35, ex),
            (7.0, 7.3, ds.TOP_SPAN)]
    ops = [tr.Op(t + 0.5 - device_offset, t + 1.0 - device_offset,
                 "tpucfd.fused-stage") for t in (1.2, 4.3)]
    return tr.Trace(window=(0.0, 10.0), devices={0: ops}, host=sorted(host))


def test_made_up_chunks():
    t = _made_up()
    assert ds.chunks(t) == [(1.0, 1.5), (4.0, 4.4), (7.0, 7.3)]
    assert ds.enqueued(t) == [1.2, 4.18, None]


@pytest.mark.parametrize("device_offset", [0.0, 1.5, -0.7])
def test_readers_on_the_made_up_trace(device_offset):
    """Known answers, whatever the device clock's offset from the
    host's: no reader mixes the two clocks."""
    got = _read(_made_up(device_offset))
    # spans 0.5, 0.4 and 0.3 s
    assert got["dispatch_host_us"] == pytest.approx(4e5)
    # first calls return 0.2 and 0.18 s into their chunks; none in the third
    assert got["dispatch_to_device_us"] == pytest.approx(1.9e5)


@pytest.mark.parametrize("name", ["diffusion_1chip.xplane.pb",
                                  "diffusion_dz4.xplane.pb"])
def test_nothing_to_read_without_program_spans(name):
    """Traces of a program that opens no ``tpucfd.dispatch`` span (these
    were recorded before it had one): every reader returns None."""
    t = tr.read(str(DATA / name))
    assert t.devices and not ds.chunks(t)
    assert _read(t) == dict.fromkeys(READERS)
    assert _read(None) == dict.fromkeys(READERS)


def test_harness_reads_one_program_span_per_chunk(tmp_path, monkeypatch,
                                                  capsys):
    """A traced CPU run of a cell: the window holds one top-level
    program span per chunk, each with the chunk's counts as stats, and
    the readers give their means (the CPU trace has no device plane, so
    one op over the whole window stands in)."""
    from benchmark import harness

    real, seen = tr.read, []

    def read(path):
        t = real(path)
        seen.append((t, _top_span_stats(path)))
        t.devices = {0: [tr.Op(*t.window, "f")]}
        return t

    monkeypatch.setattr(tr, "read", read)
    monkeypatch.setattr(harness, "_peaks", lambda root, kind: {
        "hbm_bytes_per_s": 819e9})
    rc, line, err = run_cell(tiny_tree(tmp_path, chunk_steps=3),
                             "diffusion3d_ref.march", monkeypatch, capsys,
                             trace=1)
    assert rc == 0, err
    ((t, stats),) = seen
    chunks = ds.chunks(t)
    assert len(chunks) == line["attempted"]
    for span in chunks:
        assert stats[span] == {"op": "run", "steps": 3, "programs": 1,
                               "cache": "hit"}
    queued = ds.enqueued(t)
    assert None not in queued
    host_us = line["metrics"]["dispatch_host_us"]
    assert host_us["unit"] == "us"
    assert host_us["value"] == pytest.approx(
        1e6 * sum(e - s for s, e in chunks) / len(chunks))
    to_device = line["metrics"]["dispatch_to_device_us"]
    assert to_device["unit"] == "us"
    assert 0 < to_device["value"] < host_us["value"]
    assert to_device["value"] == pytest.approx(
        1e6 * sum(q - s for (s, _), q in zip(chunks, queued)) / len(chunks))


# ``diffusion_1chip_dispatch.xplane.pb``: the diffusion cell at 128x64x48
# with 20-step chunks, recorded on a v5e by ``testdata/record.py`` with
# the program's dispatch spans and cut to 20 chunks by ``testdata/
# trim.py``; the readings as committed
DISPATCH_FIXTURE = {"chunks": 20, "dispatch_host_us": 880.1735,
                    "dispatch_to_device_us": 431.2525}


def test_recorded_trace_with_program_spans():
    """The readers on a trace recorded with the spans: every chunk is
    one ``run`` of 20 steps from the dispatch cache, each with its
    program call inside, and the wait for that call, summed over
    chunks, within the chip's idle seconds."""
    path = DATA / "diffusion_1chip_dispatch.xplane.pb"
    t = tr.read(str(path))
    stats = _top_span_stats(path)
    chunks = ds.chunks(t)
    assert len(chunks) == DISPATCH_FIXTURE["chunks"]
    for span in chunks:
        assert stats[span] == {"op": "run", "steps": 20, "programs": 1,
                               "cache": "hit"}
    got = _read(t)
    for name in READERS:
        assert got[name] == pytest.approx(DISPATCH_FIXTURE[name], rel=1e-6)
    waits = [q - s for (s, _), q in zip(chunks, ds.enqueued(t))]
    assert all(0 < w for w in waits)
    for d in t.devices:
        idle = t.window_s - tr.length(tr.busy(t, d))
        assert sum(waits) <= idle
