"""Device meshes and domain decompositions.

The reference decomposes 1-D slabs along the last axis only, one MPI rank
per GPU (``MultiGPU/Diffusion3d_Baseline/main.c:69``,
``Util.cu:66-74`` ``AssignDevices``). Here a decomposition is a mapping
from grid axes to named ``jax.sharding.Mesh`` axes — 1-D slabs, 2-D pencils
or full 3-D blocks — and device placement is XLA's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Reserved mesh-axis name for the batched ensemble engine's member
# dimension (ROADMAP item 1: members x devices). The member axis shards
# the LEADING axis of a (B, *grid) batched state — members are
# embarrassingly parallel, so the axis is halo-free by construction and
# never appears in a spatial Decomposition (statically proven by
# analysis/halo_verify.verify_member_mesh).
MEMBER_AXIS = "members"


def member_extent(mesh) -> int:
    """Shard count of the ensemble member axis (1 when the mesh is
    ``None`` or carries no ``members`` axis)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(MEMBER_AXIS, 1))


def shard_map(*args, check: bool = True, **kwargs):
    """Project ``shard_map``. ``check=False`` disables the
    varying-across-mesh-axes/replication checker — needed only for
    programs containing ``pallas_call`` (whose output avals carry no
    ``vma`` typing); everything else keeps the checker on."""
    kwargs.setdefault("check_vma", check)
    return jax.shard_map(*args, **kwargs)


def make_mesh(
    axis_sizes: Dict[str, int], devices: Optional[Sequence] = None
) -> Mesh:
    """Build a mesh, e.g. ``make_mesh({'dz': 4, 'dy': 2})``.

    Axis order follows dict order; total size must divide the device count
    (or equal it when ``devices`` is None). The axes are ``Auto``: the
    program places data with explicit ``shard_map`` specs, and arrays
    stay free of sharding-in-types, so eager ops on a sharded state
    (``u.at[i].set(v)``) work as on any array.
    """
    names = tuple(axis_sizes)
    sizes = tuple(axis_sizes[n] for n in names)
    if devices is None:
        devices = jax.devices()
    need = math.prod(sizes)
    if need > len(devices):
        raise ValueError(f"mesh needs {need} devices, only {len(devices)} available")
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    return jax.make_mesh(sizes, names, axis_types=auto,
                         devices=tuple(devices[:need]))


def axis_extent(sizes, name) -> int:
    """Shard count of a mesh-axis spec: a single axis name, or a tuple of
    names (compound axis) whose extents multiply."""
    if isinstance(name, tuple):
        return math.prod(sizes[n] for n in name)
    return sizes[name]


def reduce_axis_names(decomp: "Decomposition", axis_sizes) -> Tuple[str, ...]:
    """The pmax/psum axis-name set of a decomposition under the given
    mesh extents: every individual mesh axis in use whose extent
    exceeds 1. The SINGLE source of the cross-shard reduction set —
    ``SolverBase.mesh_reduce_max``/``mesh_reduce_sum`` and the static
    sharding pass (``analysis/collective_verify``) both derive from
    here, so the reduction a step performs and the one the verifier
    proves cannot fork."""
    sizes = dict(axis_sizes)
    return tuple(
        n for n in decomp.mesh_axis_names() if sizes.get(n, 1) > 1
    )


@dataclasses.dataclass(frozen=True)
class Decomposition:
    """Maps array axes of the grid to mesh axes.

    ``axes[array_axis] = mesh_axis_name`` (axes not present are unsharded).
    The reference's slab split is ``Decomposition.slab(ndim)``: last-array-
    axis... i.e. z in 3-D, matching ``_Nz = Nz/np`` (``main.c:69``) — note
    the reference splits the *z* axis, which in this framework's
    ``(z, y, x)`` array order is axis 0.

    A mesh-axis entry may also be a *tuple* of mesh axis names — a
    compound axis splitting one grid axis over several mesh axes,
    outermost first. This is the multi-host layout: z over
    ``('dz_dcn', 'dz_ici')`` puts shard blocks on hosts (DCN hops between
    blocks) with consecutive shards inside each host riding ICI
    (:mod:`parallel.multihost`). ``ppermute``/``axis_index`` address the
    compound axis by its flattened row-major index, so the halo-exchange
    program is unchanged.
    """

    axes: Tuple[Tuple[int, object], ...]

    @staticmethod
    def of(mapping: Dict[int, object]) -> "Decomposition":
        norm = {
            ax: tuple(n) if isinstance(n, (list, tuple)) else n
            for ax, n in mapping.items()
        }
        return Decomposition(tuple(sorted(norm.items())))

    @staticmethod
    def slab(mesh_axis: str = "dz") -> "Decomposition":
        """Reference-style 1-D slab decomposition along z (array axis 0)."""
        return Decomposition.of({0: mesh_axis})

    @property
    def mapping(self) -> Dict[int, str]:
        return dict(self.axes)

    def mesh_axis(self, array_axis: int):
        return self.mapping.get(array_axis)

    def mesh_axis_names(self) -> Tuple[str, ...]:
        """All individual mesh axis names in use (compound axes flattened)."""
        out = []
        for _, name in self.axes:
            out.extend(name if isinstance(name, tuple) else (name,))
        return tuple(out)

    def partition_spec(self, ndim: int) -> PartitionSpec:
        return PartitionSpec(*[self.mapping.get(ax) for ax in range(ndim)])

    def sharding(self, mesh: Mesh, ndim: int) -> NamedSharding:
        return NamedSharding(mesh, self.partition_spec(ndim))

    def validate(self, mesh: Mesh, global_shape: Sequence[int]) -> None:
        """Startup topology assertions (the reference's ``MPIDeviceCheck``
        analog, ``Util.cu:43-61``) — every sharded axis must divide evenly
        and leave at least one stencil-halo worth of cells per shard."""
        for ax, name in self.axes:
            for n in name if isinstance(name, tuple) else (name,):
                if n not in mesh.shape:
                    raise ValueError(f"mesh has no axis {n!r}")
            parts = axis_extent(mesh.shape, name)
            if global_shape[ax] % parts:
                raise ValueError(
                    f"axis {ax} size {global_shape[ax]} not divisible by "
                    f"mesh axis {name!r} ({parts} shards)"
                )

    def local_shape(self, mesh: Mesh, global_shape: Sequence[int]) -> Tuple[int, ...]:
        out = list(global_shape)
        for ax, name in self.axes:
            out[ax] //= axis_extent(mesh.shape, name)
        return tuple(out)
