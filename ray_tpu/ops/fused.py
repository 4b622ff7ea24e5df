"""Small fused ops: RMSNorm and softmax cross-entropy.

Pallas kernels for the memory-bound pieces XLA sometimes leaves on the
table; each has a jnp fallback used off-TPU (and as the autodiff rule —
the kernels are forward-only with ``custom_vjp`` recompute backward).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops._kernel import kernel_mode


def _rmsnorm_ref(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x, weight, eps, interpret):
    from jax.experimental import pallas as pl

    rows = x.shape[0] * (x.shape[1] if x.ndim == 3 else 1)
    flat = x.reshape(rows, x.shape[-1])
    # in and out tiles are double-buffered in VMEM next to the f32
    # working copy: 2 MiB per tile keeps the kernel inside the 16 MiB
    # scoped limit at Llama widths (512 x 4096 bf16 overflowed it)
    block = min(512, rows,
                max(8, (2 << 20) // (x.shape[-1] * x.dtype.itemsize)))
    if block < rows:
        # a width that is no power of two (2688) gives a bound that is
        # none either (390), and halving that ends at one row a tile
        block = 1 << (block.bit_length() - 1)
    while rows % block:
        block //= 2
    block = max(block, 1)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, x.shape[-1]), lambda i: (i, 0)),
            pl.BlockSpec((x.shape[-1],), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, x.shape[-1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat, weight)
    return out.reshape(x.shape)


def _rmsnorm_fwd(x, weight, eps, interpret):
    return _rmsnorm(x, weight, eps, interpret), (x, weight)


def _rmsnorm_bwd(eps, interpret, res, g):
    x, weight = res
    _, vjp = jax.vjp(lambda x_, w_: _rmsnorm_ref(x_, w_, eps), x, weight)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def fused_rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
                  interpret: Optional[bool] = None,
                  mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    """``mesh`` (models under a mesh pass ``get_global_mesh()``): GSPMD
    cannot partition a Mosaic kernel, so over several devices the
    kernel runs on each device's own rows of ``x [B, T, E]``, split as
    the activations are, with the whole ``weight``."""
    interpret = kernel_mode(interpret)
    if interpret is None:
        return _rmsnorm_ref(x, weight, eps)
    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel.sharding import MESH_RULES, P

        spec = MESH_RULES.activation_spec("batch", "seq", "embed",
                                          mesh=mesh, shape=x.shape)
        return jax.shard_map(
            lambda a, w: _rmsnorm(a, w, eps, interpret), mesh=mesh,
            in_specs=(spec, P()), out_specs=spec,
            check_vma=False)(x, weight)
    return _rmsnorm(x, weight, eps, interpret)


def fused_softmax_cross_entropy(logits: jax.Array,
                                labels: jax.Array) -> jax.Array:
    """Numerically-stable token cross entropy; relies on XLA fusion (the
    log-softmax + gather fuse into the producing matmul's epilogue)."""
    logits = logits.astype(jnp.float32)
    m = logits.max(axis=-1, keepdims=True)
    shifted = logits - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    label_logit = jnp.take_along_axis(
        shifted, labels[..., None], axis=-1)[..., 0]
    return lse - label_logit


def chunked_lm_loss(hidden: jax.Array, emb: jax.Array, labels: jax.Array,
                    *, chunk: int = 8192,
                    compute_dtype: Any = None,
                    logits_dtype: Any = None,
                    mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    """Mean next-token cross entropy with a chunked LM head.

    ``hidden`` [B,T,E] (f32), ``emb`` [V,E] (the output head's matrix:
    the embedding where a model ties the two, GPT-2's cells, an untied
    head elsewhere), ``labels`` [B,T].  Tokens are processed ``chunk`` at
    a time under
    ``jax.checkpoint``: the [chunk,V] logits block lives only inside one
    scan step (forward) and is recomputed in backward — HBM never holds
    [B,T,V], which at GPT-2-small scale is both the largest tensor and
    the dominant bandwidth cost of the naive head.

    ``mesh`` (models pass ``get_global_mesh()``): where its batch axes
    split the tokens, each device cuts the chunks inside its OWN tokens
    (``chunk`` stays the tokens of one scan step over the whole mesh, so
    a device's share of it), ``emb`` is gathered once for the scan, and
    the loss is the devices' sum over the global count.  Chunks of the
    flattened global ``[B x T]`` would mix devices, and every scan step
    would exchange its tokens.  A head sharded along the vocabulary is
    gathered too: correct, not Megatron's.
    """
    n = hidden.shape[0] * hidden.shape[1]
    axes = ()
    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel.sharding import MESH_RULES, P, spec_axes

        tokens = MESH_RULES.activation_spec(
            "batch", "seq", mesh=mesh, shape=hidden.shape[:2])
        axes = spec_axes(tokens)
    shards = math.prod(mesh.shape[a] for a in axes)
    local_sum = functools.partial(
        _lm_loss_sum, chunk=max(1, chunk // shards),
        compute_dtype=compute_dtype, logits_dtype=logits_dtype)
    if not axes:
        return local_sum(hidden, emb, labels) / n
    total = jax.shard_map(
        lambda h, e, y: jax.lax.psum(local_sum(h, e, y), axes),
        mesh=mesh, in_specs=(P(*tokens, None), P(), tokens),
        out_specs=P(), check_vma=False)(hidden, emb, labels)
    return total / n


def _lm_loss_sum(hidden, emb, labels, *, chunk, compute_dtype,
                 logits_dtype):
    """Summed cross entropy of ``chunked_lm_loss`` over the tokens it is
    given, ``chunk`` at a time."""
    B, T, E = hidden.shape
    V = emb.shape[0]
    flat_h = hidden.reshape(B * T, E).astype(jnp.float32)
    flat_y = labels.reshape(B * T)
    n = flat_h.shape[0]
    pad = (-n) % chunk
    if pad:
        flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
        flat_y = jnp.pad(flat_y, (0, pad))
    mask = (jnp.arange(flat_h.shape[0]) < n).astype(jnp.float32)
    n_chunks = flat_h.shape[0] // chunk
    h_c = flat_h.reshape(n_chunks, chunk, E)
    y_c = flat_y.reshape(n_chunks, chunk)
    m_c = mask.reshape(n_chunks, chunk)
    emb_f32 = emb.astype(jnp.float32)

    @jax.checkpoint
    def body(carry, xs):
        h, y, m = xs
        nll = _chunk_nll(h, y, emb_f32, compute_dtype, logits_dtype)
        return carry + jnp.sum(nll * m), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (h_c, y_c, m_c))
    return total


def _chunk_nll(h, y, emb_f32, compute_dtype, logits_dtype):
    """One scan step of the chunked head: the cross entropy ``[chunk]``
    of ``h [chunk, E]`` against ``y [chunk]``; the ``[chunk, V]`` logits
    live and die here."""
    if compute_dtype is not None:
        # MXU path: bf16 operands, f32 accumulation by default.
        # ``logits_dtype=bf16`` opts into storing the [chunk, V] block
        # (the step's largest HBM consumer, read several times per chunk
        # in fwd+bwd) in half width: logits then quantize at FULL
        # magnitude before the max-subtract, so the error grows with
        # logit scale (~0.06 per logit at |x|~16).  No training path asks
        # for it; the benchmark's ``lower_precision`` controls do
        # (``models/afmoe.py``, ``models/ouro.py``).
        logits = jax.lax.dot_general(
            h.astype(compute_dtype), emb_f32.astype(compute_dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=logits_dtype or jnp.float32)
    else:
        logits = h @ emb_f32.T  # [chunk, V]
    mx = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = (logits - mx).astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    label_logit = jnp.take_along_axis(shifted, y[:, None], axis=-1)[:, 0]
    return lse - label_logit


def chunked_token_loss(hidden: jax.Array, emb: jax.Array, labels: jax.Array,
                       *, chunk: int = 8192, compute_dtype: Any = None,
                       logits_dtype: Any = None) -> jax.Array:
    """The chunked LM head giving a loss a TOKEN: ``[B, T]`` float32
    next-token cross entropies of ``hidden [B, T, E]`` against ``labels
    [B, T]`` under the head ``emb [V, E]``, for a loss that weighs its
    tokens itself (``models/ouro.py``: every token's loss at four exits
    under its own exit distribution).  The scan step is
    ``chunked_lm_loss``'s: ``chunk`` tokens at a time under
    ``jax.checkpoint``, so the ``[chunk, V]`` logits live in one scan
    step and are recomputed in the backward pass, whose cotangent is a
    vector a token; HBM never holds ``[B, T, V]``.  One device (or
    replicated operands): no ``mesh``."""
    B, T, E = hidden.shape
    flat_h = hidden.reshape(B * T, E)   # cast a chunk at a time, below
    flat_y = labels.reshape(B * T)
    n = B * T
    pad = (-n) % chunk
    if pad:
        flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
        flat_y = jnp.pad(flat_y, (0, pad))
    emb_f32 = emb.astype(jnp.float32)

    @jax.checkpoint
    def body(_, xs):
        h, y = xs
        return None, _chunk_nll(h.astype(jnp.float32), y, emb_f32,
                                compute_dtype, logits_dtype)

    _, nll = jax.lax.scan(body, None, (flat_h.reshape(-1, chunk, E),
                                       flat_y.reshape(-1, chunk)))
    return nll.reshape(-1)[:n].reshape(B, T)
