"""Ulysses-style sequence parallelism: all-to-all head resharding.

Complement to ring attention: instead of rotating K/V, convert the
sequence sharding into a *head* sharding with one ``all_to_all`` (each
device then holds full sequences for H/n heads and runs ordinary local
attention), and convert back afterwards.  Cheaper than a ring when heads
divide evenly and the sequence fits per-device memory after resharding;
preferable on all-to-all-friendly topologies.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map


def _default_attn(q, k, v, causal: bool, scale: float):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _local_attn(q, k, v, causal: bool, scale: float, interpret: bool):
    """Post-all-to-all local attention: the pallas flash kernels on TPU
    (O(block) memory, custom-VJP backward) with the jnp reference as the
    CPU/awkward-shape fallback.  ``interpret=True`` ALWAYS runs the
    kernels (through the pallas interpreter) — a test asking for the
    kernel path must never silently compare the reference to itself."""
    from ray_tpu.ops.flash_attention import (_chunk_blocks,
                                             flash_attention,
                                             kernel_block_for)

    block_q, block_k = _chunk_blocks(q.shape[1], k.shape[1])
    if interpret:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=True)
    if jax.default_backend() == "tpu" \
            and kernel_block_for(q.shape[1]) is not None:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k)
    return _default_attn(q, k, v, causal, scale)


def _ulysses_sharded(q, k, v, axis_name: str, causal: bool, scale: float,
                     attn_fn: Optional[Callable], interpret: bool = False):
    # [B, T/n, H, D] -> all-to-all -> [B, T, H/n, D]
    def seq_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    fn = attn_fn or functools.partial(_local_attn, causal=causal,
                                      scale=scale, interpret=interpret)
    out = fn(qh, kh, vh)
    return heads_to_seq(out)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "sp", causal: bool = True,
                      scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      mesh: Optional[Mesh] = None,
                      interpret: bool = False) -> jax.Array:
    """All-to-all sequence parallel attention.

    The local attention after resharding defaults to the pallas flash
    kernels on TPU (jnp reference elsewhere); ``attn_fn(q, k, v)``
    overrides it, and ``interpret=True`` forces the kernels through the
    pallas interpreter on CPU (tests).  Heads must be divisible by the
    axis size.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mesh is None:
        return _ulysses_sharded(q, k, v, axis_name, causal, scale, attn_fn,
                                interpret)
    spec = P(None, axis_name, None, None)
    fn = functools.partial(_ulysses_sharded, axis_name=axis_name,
                           causal=causal, scale=scale, attn_fn=attn_fn,
                           interpret=interpret)
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
