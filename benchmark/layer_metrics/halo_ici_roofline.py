"""The halo exchange's share of a chip's interconnect peak: the bytes the
exchange has to move into one chip in the traced window, over the
seconds the chip's collectives were in flight, at the chip's peak ICI
rate (``ici_bits_per_s`` in ``peaks.json``); mean over the chips whose
trace shows collectives in flight.

The bytes are counted from the configuration's shapes, so the count is
the same whatever implements the exchange: steps x RK stages x the
z-neighbours of the slab the chip holds (1 at either end of the
non-periodic chain, 2 inside) x the halo depth R (3 for WENO5, 4 for
WENO7, ``order / 2`` for the central Laplacian) x the interior
``ny x nx`` plane x the dtype's size. Which slab a chip holds is read
from the mesh the benchmark places the configuration on
(``systems/common.mesh_and_decomp``): trace plane ``/device:TPU:n`` is
the device whose id is ``n``. The seconds are the union of the chip's
collective ops on its async line (``trace.Op.in_flight``). Nothing to
read on one chip or on a trace with no collective in flight."""

from benchmark import trace as tr
from benchmark.work.common import chips, itemsize

WENO_HALO = {5: 3, 7: 4}


def halo_depth(cfg: dict) -> int:
    if "weno_order" in cfg:
        return WENO_HALO[int(cfg["weno_order"])]
    return int(cfg["order"]) // 2


def slab_order(cfg: dict) -> list:
    """Device ids in slab order along z, as the benchmark's mesh places
    them on this host's devices."""
    import jax

    from benchmark.systems.common import mesh_and_decomp

    mesh, _ = mesh_and_decomp(cfg, jax.devices()[:chips(cfg)])
    return [int(d.id) for d in mesh.devices.flat]


def neighbours(order: list, device: int) -> int:
    """Z-neighbours of the slab ``device`` holds in a non-periodic chain."""
    i = order.index(device)
    return (i > 0) + (i < len(order) - 1)


def share(trace, cfg: dict, steps: int, peaks: dict, order: list):
    """The reading, given the device ids in slab order."""
    if (trace is None or len(trace.devices) < 2 or not steps
            or len(order) < 2):
        return None
    plane = (float(steps) * int(cfg["stages"]) * halo_depth(cfg)
             * cfg["grid_xyz"][0] * cfg["grid_xyz"][1] * itemsize(cfg))
    peak = peaks["ici_bits_per_s"] / 8.0
    shares = []
    for device, ops in trace.devices.items():
        s = tr.length(tr.union([(o.start, o.end) for o in ops
                                if o.in_flight
                                and tr.COLLECTIVE.match(o.name)],
                               trace.window))
        if s > 0 and device in order:
            shares.append(neighbours(order, device) * plane / (s * peak))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)


def read(ctx):
    if ctx.trace is None or chips(ctx.cfg) < 2 or not ctx.steps:
        return None
    return share(ctx.trace, ctx.cfg, ctx.steps, ctx.peaks,
                 slab_order(ctx.cfg))
