"""Pipeline parallelism over the ``pp`` mesh axis.

GPipe-style microbatch pipelining expressed as a ``shard_map`` +
``lax.scan`` over a rotating activation buffer: device *i* holds the
parameters of stage *i*; at schedule tick *t* it applies its stage to the
activation that arrived from stage *i-1* and forwards the result with
``ppermute``.  The schedule runs ``n_micro + n_stages - 1`` ticks (fill +
drain); everything is static-shaped so XLA can overlap the ppermute with
the next tick's compute.

The reference has no pipeline parallelism (SURVEY.md §2.5) — this is a
net-new capability of the TPU build.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_sharded(stage_params: Any, inputs: jax.Array,
                      stage_fn: Callable[[Any, jax.Array], jax.Array],
                      axis_name: str):
    """Inside shard_map: stage_params is this device's stage; inputs is
    the full microbatch stack [n_micro, ...] (replicated)."""
    n_stages = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = inputs.shape[0]
    total_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    state = jnp.zeros_like(inputs[0])
    outputs = jnp.zeros_like(inputs)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped; masked when out of range)
        mb = lax.dynamic_index_in_dim(
            inputs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        x = jnp.where(idx == 0, mb, state)
        active = (t - idx >= 0) & (t - idx < n_micro)
        y = stage_fn(stage_params, x)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # the last stage records its finished microbatch (t - n_stages + 1)
        out_slot = jnp.clip(t - n_stages + 1, 0, n_micro - 1)
        is_out = (idx == n_stages - 1) & (t - idx >= 0) & (t - idx < n_micro)
        outputs = lax.cond(
            is_out,
            lambda o: lax.dynamic_update_index_in_dim(o, y, out_slot, 0),
            lambda o: o,
            outputs)
        # rotate activations one hop forward
        state = lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state, outputs),
                               jnp.arange(total_ticks))
    # only the last stage ever writes `outputs` (others keep zeros), so a
    # psum over the axis broadcasts the real results to every device
    return lax.psum(outputs, axis_name)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, microbatches: jax.Array, *,
                   axis_name: str = "pp",
                   mesh: Optional[Mesh] = None) -> jax.Array:
    """Run ``stage_fn`` as a pipeline over the ``pp`` axis.

    - ``stage_params``: pytree whose leaves have a leading ``[n_stages]``
      dim (sharded one-stage-per-device when ``mesh`` is given).
    - ``microbatches``: ``[n_micro, micro_batch, ...]`` activations fed to
      stage 0; returns the same shape produced by the last stage.
    """
    if mesh is None:
        return _pipeline_sharded(stage_params, microbatches, stage_fn,
                                 axis_name)
    param_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = functools.partial(_pipeline_sharded, stage_fn=stage_fn,
                           axis_name=axis_name)

    def squeeze_stage(p):
        # shard_map gives each device [1, ...]; drop the stage dim
        return jax.tree.map(lambda x: x[0], p)

    def wrapped(params, inputs):
        return fn(squeeze_stage(params), inputs)

    return _shard_map(
        wrapped, mesh=mesh,
        in_specs=(param_spec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def stack_block_params(per_block_params: list) -> Any:
    """Stack N structurally-identical per-block param trees into one
    tree with a leading ``[n_stages]`` dim — the layout
    :func:`pipeline_apply` shards one-stage-per-device.  Use with a
    transformer's layer params (``params["h0"], params["h1"], ...``) to
    pipeline real models without restructuring them."""
    import numpy as np

    return jax.tree.map(lambda *leaves: jnp.stack(
        [jnp.asarray(np.asarray(x)) for x in leaves]),
        *per_block_params)
