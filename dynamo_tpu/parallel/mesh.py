"""Device mesh construction and pytree sharding.

The engine's parallelism is expressed entirely as a ``jax.sharding.Mesh``
with named axes + PartitionSpecs; XLA emits the collectives over ICI/DCN
(replaces the reference's delegation to NCCL inside engines —
SURVEY.md §2.5).

Axes (any may be 1): ``dp`` data, ``pp`` pipeline stage, ``tp`` tensor,
``ep`` expert, ``sp`` sequence/context.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AXIS_ORDER = ("dp", "pp", "ep", "tp", "sp")


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    pp: int = 1
    ep: int = 1
    tp: int = 1
    sp: int = 1
    # first device index this mesh claims — lets two engines in one process
    # own DISJOINT partitions of the device set (disaggregated prefill and
    # decode engines each on their own sub-mesh)
    device_offset: int = 0

    def total(self) -> int:
        return self.dp * self.pp * self.ep * self.tp * self.sp

    def axis_sizes(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @classmethod
    def tp_only(cls, tp: int) -> "MeshConfig":
        return cls(tp=tp)


def make_mesh(config: MeshConfig | None = None, devices=None) -> Mesh:
    """Build a named mesh.  Defaults: all local devices on the ``tp`` axis.

    Axis order puts ``tp``/``sp`` innermost so tensor-parallel collectives
    ride the fastest ICI links (outer axes land on DCN for multi-host).
    """
    devices = devices if devices is not None else jax.devices()
    if config is None:
        config = MeshConfig(tp=len(devices))
    n = config.total()
    off = config.device_offset
    if off < 0 or off + n > len(devices):
        raise ValueError(
            f"mesh needs devices [{off}, {off + n}), have {len(devices)}"
        )
    device_array = np.asarray(devices[off : off + n]).reshape(
        [config.axis_sizes()[a] for a in AXIS_ORDER]
    )
    return Mesh(device_array, AXIS_ORDER)


def device_summary() -> dict:
    """What jax runs on, as every result and log line names it."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_pytree(tree, specs, mesh: Mesh):
    """Place a pytree on the mesh according to a matching specs pytree."""
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        tree, specs,
    )


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
