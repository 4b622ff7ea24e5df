"""The exchange of an expert-parallel group: tokens to the chips that
hold their experts, the experts' partial results back and summed.

The group is the chips along ONE mesh axis, the axis the experts lie
over (``sharding.FSDP_EP_RULES``: the axis that also splits the batch
and shards everything else).  Each chip routes its own tokens over ALL
published experts; then, inside ``jit`` under ``shard_map`` over that
axis (``models/afmoe.py`` ``RoutedExperts``):

* :func:`gather_tokens` **all-gathers** the chips' rows with their
  choices and weights, so that every chip sees the group's tokens and
  runs dispatch, the grouped products and combine over them for the
  experts it OWNS: its part of every token's result;
* :func:`scatter_sums` **reduce-scatters** the parts, so that each chip
  is left with its own tokens' sums over all chips.

Dropless at static shapes, and an all-gather and a reduce-scatter, not
an all-to-all: with ``k`` choices over ``n`` chips a token's row is
wanted by ``(n - 1) (1 - ((n - 1) / n)^k)`` of the other chips on
average (2.7 of 3 at 8 over 4) and by all of them in the worst case,
which a buffer of static shape that drops nothing is sized for; an
all-to-all would move what the all-gather moves and add a sort by
destination.  The backward pass is the transposes: the gather's a
reduce-scatter of the rows' cotangents, the scatter's an all-gather.

Across hosts (an axis over DCN), for fewer choices than chips (where an
all-to-all moves less) and under a capacity that drops: not here.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh

from ray_tpu.core import telemetry
from ray_tpu.parallel.mesh import get_global_mesh

EXCHANGE = "all_gather+reduce_scatter"


def group_mesh(axis: Optional[str]) -> Optional[Mesh]:
    """The global mesh where it has MORE THAN ONE device along ``axis``,
    the axis a configuration says its experts lie over; ``None`` (no
    axis named, no mesh, one device along it): the layer runs without
    its exchange and nothing is added to the trace."""
    mesh = get_global_mesh() if axis else None
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        return None
    return mesh


def gather_tokens(x: jax.Array, axis: str) -> jax.Array:
    """``[T_local, ...]`` on every chip of ``axis`` -> ``[chips x
    T_local, ...]``, the chips' rows in the order of their index along
    the axis.  Inside ``shard_map``."""
    return jax.lax.all_gather(x, axis, axis=0, tiled=True)


def scatter_sums(x: jax.Array, axis: str) -> jax.Array:
    """Every chip's part ``[chips x T_local, ...]`` of the group's
    results -> this chip's own rows ``[T_local, ...]`` summed over the
    chips, in ``x``'s dtype.  Inside ``shard_map``."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)


def exchange_bytes(chips: int, tokens_local: int, embed: int, top_k: int,
                   itemsize: int) -> Dict[str, int]:
    """Bytes ONE chip receives in one gather (the other chips' rows, and
    their choices and float32 weights) and sends in one scatter (its
    part of the other chips' rows), a call of the layer's forward."""
    others = (chips - 1) * tokens_local
    return {"gather_bytes": others * (embed * itemsize + top_k * 8),
            "scatter_bytes": others * embed * itemsize}


def ep_plan(axis: Optional[str], *, experts: int, tokens_local: int,
            embed: int, top_k: int, itemsize: int):
    """``with ep_plan(...):`` around the trace of a stack of routed
    layers: the span ``parallel:ep.plan`` that says what exchange was
    compiled (beside ``parallel:fsdp.plan``).  ``gather_bytes`` and
    ``scatter_bytes``: of one call of a layer's forward on one chip
    (:func:`exchange_bytes`).  With no group: no span."""
    mesh = group_mesh(axis)
    if mesh is None:
        return contextlib.nullcontext()
    chips = mesh.shape[axis]
    args: Dict[str, Any] = dict(
        axis=axis, chips=chips, experts_held=experts // chips,
        tokens_local=tokens_local, tokens_group=chips * tokens_local,
        exchange=EXCHANGE,
        **exchange_bytes(chips, tokens_local, embed, top_k, itemsize))
    return telemetry.span("parallel", "ep.plan", **args)
