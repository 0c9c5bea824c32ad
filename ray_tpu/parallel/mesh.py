"""Device-mesh management: the TPU-native replacement for NCCL process groups.

In the reference, distributed tensor communication is a *runtime library*
(ray.util.collective NCCLGroup, python/ray/util/collective/collective_group/
nccl_collective_group.py:127, and torch.distributed in
python/ray/train/torch/config.py:113).  On TPU, collectives are *compiled into
the XLA program* and ride ICI; what remains at runtime is (a) describing the
mesh, (b) bootstrapping every host process into the same multi-host XLA
computation, and (c) mapping logical parallelism axes (data/fsdp/tensor/seq/
expert) onto physical mesh axes.  This module owns (a) and (c); bootstrap.py
owns (b).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

# Canonical logical axis order.  Physical layout: the innermost axes ("tensor",
# "seq") change fastest so they land on the tightest ICI loops when the mesh is
# built from a pod topology; "data" is outermost so data-parallel replicas may
# span DCN between slices.
AXIS_ORDER: Tuple[str, ...] = ("data", "fsdp", "expert", "pipeline", "seq", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape over named parallelism axes.

    Sizes of -1 mean "absorb remaining devices" (at most one axis may be -1).
    Axes of size 1 are still materialized so sharding rules can always refer to
    every canonical axis name.
    """

    data: int = -1
    fsdp: int = 1
    expert: int = 1
    pipeline: int = 1
    seq: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return MeshSpec(**sizes)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return AXIS_ORDER

    def shape(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    def size(self) -> int:
        return math.prod(s for s in self.shape() if s > 0)


def remesh_spec(spec: MeshSpec, n_devices: int) -> MeshSpec:
    """Re-resolve a mesh spec after an elastic re-mesh changed the device
    count (host lost → shrink, replacement host returned → grow).

    A spec with a -1 wildcard re-absorbs the new count directly.  A fully
    fixed spec re-shapes along its "data" axis (the DCN-spanning axis —
    replicas are what a host-count change adds or removes; ICI-bound axes
    like tensor/fsdp would change the compiled program's communication
    pattern) and fails with an actionable error when that isn't possible.
    """
    sizes = {a: getattr(spec, a) for a in AXIS_ORDER}
    if any(s == -1 for s in sizes.values()):
        return spec.resolve(n_devices)
    other = math.prod(s for a, s in sizes.items() if a != "data")
    if other <= 0 or n_devices % other != 0:
        raise ValueError(
            f"cannot re-mesh {sizes} onto {n_devices} devices: the non-data "
            f"axes need a multiple of {other}; use data=-1 for elastic "
            "training or resize the gang to a compatible host count"
        )
    sizes["data"] = n_devices // other
    return MeshSpec(**sizes)


def build_mesh(
    spec: Optional[MeshSpec] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Materialize a jax.sharding.Mesh from a MeshSpec.

    Uses mesh_utils.create_device_mesh so the physical device order respects
    ICI topology (nearest-neighbor rings per axis) on real TPU slices; for
    CPU devices (virtual device testing) that function is a plain reshape.
    A topology it cannot lay out raises: a reshape in its place would run,
    slowly, with neighbours that are not neighbours.
    """
    from jax.experimental import mesh_utils

    devices = list(devices if devices is not None else jax.devices())
    spec = (spec or MeshSpec()).resolve(len(devices))
    dev_array = mesh_utils.create_device_mesh(spec.shape(), devices=devices)
    return Mesh(dev_array, spec.axis_names)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return build_mesh(MeshSpec(data=1), devices=[device])


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
