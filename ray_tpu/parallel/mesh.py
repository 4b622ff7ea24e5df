"""Device mesh construction over ICI × DCN.

The mesh is the TPU-native replacement for the reference's process-group
bootstrap (``train/torch/config.py`` ``_setup_torch_process_group``): instead
of a NCCL rendezvous, parallelism is declared as named mesh axes and XLA
compiles the collectives onto the interconnect.

Axis vocabulary (outermost first, SURVEY.md §7.6):

- ``dp``   — pure data parallelism (gradient allreduce)
- ``fsdp`` — data parallelism with sharded parameters/optimizer state
            (reduce-scatter + all-gather)
- ``pp``   — pipeline stages
- ``sp``   — sequence/context parallelism (ring attention / Ulysses)
- ``tp``   — tensor parallelism (megatron-style sharded matmuls)
- ``ep``   — expert parallelism over an axis of its OWN
            (``sharding.EP_RULES``; GSPMD chooses the collectives; no
            step of the training path builds a mesh with ``ep > 1``).
            The training path's expert parallelism ALIASES ``fsdp``
            instead: ``sharding.FSDP_EP_RULES`` lays the ``expert``
            logical axis over the ``fsdp`` MESH axis, the one that also
            splits the batch and shards every other parameter, so the
            chips along ``fsdp`` are the group that shares each layer,
            and ``parallel/expert.py`` runs their exchange (an
            all-gather of the rows, a reduce-scatter of the parts) under
            ``shard_map`` over that axis

Multi-host placement: axes listed in ``dcn_axes`` are laid out across
slice boundaries (DCN); everything else stays inside a slice where
collectives ride ICI.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "tp", "ep")


@dataclass
class MeshConfig:
    """Declarative parallelism layout (the ScalingConfig analog for
    intra-program parallelism)."""

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1
    #: axes that cross slice/host boundaries (DCN); outermost in layout
    dcn_axes: Tuple[str, ...] = ("dp", "pp")
    #: -1 in any field means "absorb remaining devices"

    def axis_sizes(self) -> Dict[str, int]:
        return {"dp": self.dp, "fsdp": self.fsdp, "pp": self.pp,
                "sp": self.sp, "tp": self.tp, "ep": self.ep}

    def resolved(self, n_devices: int) -> "MeshConfig":
        sizes = self.axis_sizes()
        wildcard = [k for k, v in sizes.items() if v == -1]
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if len(wildcard) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if wildcard:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return MeshConfig(**sizes, dcn_axes=self.dcn_axes)

    @property
    def num_devices(self) -> int:
        return math.prod(self.axis_sizes().values())


def mesh_shape_for(n_devices: int, *, tp: int = 1, sp: int = 1,
                   pp: int = 1, fsdp: bool = True) -> MeshConfig:
    """Convenience: fill the data axis with whatever devices remain."""
    cfg = MeshConfig(dp=1 if fsdp else -1, fsdp=-1 if fsdp else 1,
                     pp=pp, sp=sp, tp=tp)
    return cfg.resolved(n_devices)


def build_mesh(config: Optional[MeshConfig] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with all six named axes.

    Device order: jax returns devices ordered so that adjacent ids share
    ICI links; we lay the innermost axes (tp, sp) over adjacent devices so
    their (latency-bound) collectives get the shortest paths, and the
    outermost axes (dp, pp) over slice boundaries where only
    bandwidth-bound gradient reductions travel.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    config = (config or MeshConfig(dp=-1)).resolved(n)
    sizes = config.axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


_global_mesh: Optional[Mesh] = None


def set_global_mesh(mesh: Optional[Mesh]) -> None:
    """Install the process-wide mesh used by model-internal shard_map
    blocks (e.g. ring attention inside GPT2 under plain jit/GSPMD)."""
    global _global_mesh
    _global_mesh = mesh


def get_global_mesh() -> Optional[Mesh]:
    return _global_mesh


class use_mesh:
    """Context manager form of :func:`set_global_mesh`."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._prev: Optional[Mesh] = None

    def __enter__(self) -> Mesh:
        self._prev = get_global_mesh()
        set_global_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc) -> None:
        set_global_mesh(self._prev)


def local_mesh_summary(mesh: Mesh) -> Dict[str, int]:
    return dict(mesh.shape)
