"""Logical-axis sharding rules and GSPMD presets.

The pattern (from public JAX scaling practice): model code annotates
arrays with *logical* axis names ("batch", "seq", "embed", "mlp",
"heads", "kv", "vocab", "layers", "expert"); a :class:`ShardingRules`
table maps logical names to mesh axes per parallelism style.  XLA then
inserts the collectives.  This replaces the reference's per-backend
process-group wiring with declarative sharding.  (One exchange is
written out and not left to XLA: a routed layer's, over the mesh axis
its experts lie on under :data:`FSDP_EP_RULES`, ``parallel/expert.py``.)

A table says where PARAMETERS lie (:meth:`ShardingRules.spec`).  Where
an ACTIVATION lies follows from it (:meth:`ShardingRules.
activation_spec`): its batch is split as the table says, and no other
axis of it may use a mesh axis the batch is split over, so under FSDP
an activation is whole along ``embed`` while the weight it meets is
sharded there: the weight is gathered for its use and its gradient is
reduced and scattered back, instead of the activation being exchanged.
Model code states this with :func:`constrain_activation`.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.core import telemetry
from ray_tpu.parallel.mesh import get_global_mesh

MeshAxis = Union[None, str, Tuple[str, ...]]


def _axes(axis: MeshAxis) -> Tuple[str, ...]:
    if axis is None:
        return ()
    return (axis,) if isinstance(axis, str) else tuple(axis)


def spec_axes(spec: P) -> Tuple[str, ...]:
    """Every mesh axis a PartitionSpec names, in order."""
    return tuple(a for axis in spec for a in _axes(axis))


@dataclass
class ShardingRules:
    """logical axis -> mesh axis (or tuple of axes, or None=replicated)."""

    rules: Dict[str, MeshAxis] = field(default_factory=dict)

    def spec(self, *logical_axes: Optional[str]) -> P:
        """Where a PARAMETER with these logical axes lies.  A mesh axis
        is named once in a spec: the FIRST logical axis that maps to it
        is split over it and a later one stays whole (under
        :data:`FSDP_EP_RULES` an expert's ``embed`` beside its
        ``expert``)."""
        taken: set = set()
        out = []
        for logical in logical_axes:
            axis = self.rules.get(logical) if logical is not None else None
            if taken.intersection(_axes(axis)):
                axis = None
            taken.update(_axes(axis))
            out.append(axis)
        return P(*out)

    def activation_spec(self, *logical_axes: Optional[str],
                        mesh: Optional[Mesh] = None,
                        shape: Optional[Sequence[int]] = None) -> P:
        """Where an ACTIVATION with these logical axes lies: ``batch``
        as the table says, every other axis as the table says less the
        mesh axes the batch is split over.  With ``mesh``, only axes
        the mesh has with more than one device are named; with
        ``shape`` too, a dimension that its mesh axes do not divide
        stays whole."""
        data = _axes(self.rules.get("batch"))
        out = []
        for i, logical in enumerate(logical_axes):
            names = _axes(self.rules.get(logical))
            if logical != "batch":
                names = tuple(a for a in names if a not in data)
            if mesh is not None:
                names = tuple(a for a in names
                              if mesh.shape.get(a, 1) > 1)
                if shape is not None and shape[i] % math.prod(
                        mesh.shape[a] for a in names):
                    names = ()
            out.append(names[0] if len(names) == 1 else names or None)
        return P(*out)

    def merged(self, **updates: MeshAxis) -> "ShardingRules":
        out = dict(self.rules)
        out.update(updates)
        return ShardingRules(out)


#: Fully-replicated parameters, batch split over data axes (DP).
DP_RULES = ShardingRules({
    "batch": ("dp", "fsdp"),
    "seq": None, "embed": None, "mlp": None, "heads": None,
    "kv": None, "vocab": None, "layers": None, "expert": None,
})

#: FSDP: parameters sharded over the fsdp axis on their largest dim.
FSDP_RULES = ShardingRules({
    "batch": ("dp", "fsdp"),
    "embed": "fsdp",
    "seq": None, "mlp": None, "heads": None, "kv": None,
    "vocab": None, "layers": None, "expert": None,
})

#: Megatron-style TP on top of (F)SDP: hidden/heads over tp.
TP_RULES = ShardingRules({
    "batch": ("dp", "fsdp"),
    "embed": "fsdp",
    "mlp": "tp",
    "heads": "tp",
    "kv": "tp",
    "vocab": "tp",
    "seq": None, "layers": None, "expert": None,
})

#: Sequence/context parallelism: activations split on seq over sp.
SP_RULES = TP_RULES.merged(seq="sp")

#: Expert parallelism over a mesh axis of its OWN: experts over ``ep``
#: beside TP's placement of everything else.  What GSPMD makes of a
#: layer whose experts lie so is its own choice of collectives
#: (``models/moe.py``: capacity-bounded einsums); no step of the
#: training path builds a mesh with ``ep > 1``.
EP_RULES = TP_RULES.merged(expert="ep")

#: Expert parallelism ALIASING fsdp, the layout of the training path:
#: :data:`FSDP_RULES` with ``expert`` over the SAME mesh axis that
#: shards everything else.  A parameter with an ``expert`` axis is split
#: over the chips BY EXPERT and by nothing else (an expert's matrices,
#: their gradients and AdamW's moments whole on the chip that owns it:
#: :meth:`ShardingRules.spec` names a mesh axis once); every other
#: parameter lies as under FSDP, ``embed`` over ``fsdp``, gathered for
#: use.  The batch is split over the same axis, so the chips of the axis
#: are one group whose tokens meet each other's experts through the
#: exchange of ``parallel/expert.py``.
FSDP_EP_RULES = FSDP_RULES.merged(expert="fsdp")

#: Every preset at once.  Each preset is this table with the axes it
#: does not use left whole, and a mesh built for a preset has one
#: device along those axes: so on any preset's mesh this table places
#: parameters and activations as the preset does, and model code that
#: is handed a mesh and no rules (:func:`constrain_activation`) reads
#: it.
MESH_RULES = TP_RULES.merged(seq="sp", expert="ep")

PRESETS: Dict[str, ShardingRules] = {
    "dp": DP_RULES,
    "fsdp": FSDP_RULES,
    "tp": TP_RULES,
    "sp": SP_RULES,
    "ep": EP_RULES,
    "fsdp_ep": FSDP_EP_RULES,
}


def logical_to_mesh(rules: ShardingRules, logical_specs: Any) -> Any:
    """Map a pytree of logical-axis tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(*axes)
        if isinstance(axes, (tuple, list)) else P(),
        logical_specs,
        is_leaf=lambda x: isinstance(x, (tuple, list)),
    )


def shard_params(params: Any, logical_specs: Any, rules: ShardingRules,
                 mesh: Mesh) -> Any:
    """Device-put a parameter pytree according to logical specs."""
    specs = logical_to_mesh(rules, logical_specs)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


def constrain_activation(x: jax.Array, *logical_axes: Optional[str],
                         mesh: Optional[Mesh] = None) -> jax.Array:
    """Say where activation ``x`` lies on ``mesh`` (default: the global
    mesh, as models ask for it).  With no mesh, or one device, ``x``
    comes back as it is and nothing is added to the trace."""
    mesh = get_global_mesh() if mesh is None else mesh
    if mesh is None or mesh.size == 1:
        return x
    spec = MESH_RULES.activation_spec(*logical_axes, mesh=mesh,
                                      shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def fsdp_plan(params: Any, logical_axes: Callable[[], Any], *,
              passes: int, mesh: Optional[Mesh] = None):
    """``with fsdp_plan(...):`` around the trace of a step: the span
    ``parallel:fsdp.plan`` that says what the step asks of the mesh.
    ``params`` is the step's parameter tree (shapes and dtypes are
    read), ``logical_axes()`` the same tree of logical-axis tuples
    (asked for only under a mesh), ``passes`` how often a step uses a weight (forward, a recomputed
    forward, backward).  A leaf is ``sharded`` when the preset that
    goes with the mesh (:data:`MESH_RULES`) splits it: it is gathered
    ``passes`` times a step (``gather_bytes``, as stored) and its
    gradient is reduced and scattered once (``scatter_bytes``).  With no
    mesh, or one device: no span."""
    mesh = get_global_mesh() if mesh is None else mesh
    if mesh is None or mesh.size == 1:
        return contextlib.nullcontext()
    live = {a for a, n in mesh.shape.items() if n > 1}
    sharded = whole = split_bytes = 0
    for leaf, names in zip(
            jax.tree.leaves(params),
            jax.tree.leaves(logical_axes(),
                            is_leaf=lambda x: isinstance(x, tuple))):
        if live.intersection(spec_axes(MESH_RULES.spec(*names))):
            sharded += 1
            split_bytes += leaf.size * leaf.dtype.itemsize
        else:
            whole += 1
    return telemetry.span(
        "parallel", "fsdp.plan",
        mesh=",".join(f"{a}={n}" for a, n in mesh.shape.items() if n > 1),
        leaves_sharded=sharded, leaves_whole=whole,
        gather_bytes=passes * split_bytes, scatter_bytes=split_bytes,
        act_spec=str(MESH_RULES.activation_spec(
            "batch", "seq", "embed", mesh=mesh)))


def named_sharding(mesh: Mesh, *axes: MeshAxis) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def flax_sharding(boxed_params: Any, rules: ShardingRules
                  ) -> Tuple[Any, Any]:
    """Split a flax ``nn.with_partitioning``-boxed param tree into
    (plain arrays, PartitionSpec tree) using the logical->mesh rules."""

    def is_boxed(x):
        return hasattr(x, "unbox") and hasattr(x, "names")

    specs = jax.tree.map(
        lambda x: rules.spec(*x.names) if is_boxed(x) else P(),
        boxed_params, is_leaf=is_boxed)
    plain = jax.tree.map(
        lambda x: x.unbox() if is_boxed(x) else x,
        boxed_params, is_leaf=is_boxed)
    return plain, specs


def place_flax_params(boxed_params: Any, rules: ShardingRules,
                      mesh: Mesh) -> Tuple[Any, Any]:
    """Unbox + device_put a flax param tree onto the mesh; returns
    (sharded plain params, spec tree)."""
    plain, specs = flax_sharding(boxed_params, rules)
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        plain, specs)
    return placed, specs
