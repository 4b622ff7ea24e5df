"""Small fused ops: RMSNorm, softmax cross-entropy and the chunked LM
head.

Pallas kernels for the memory-bound pieces XLA sometimes leaves on the
table; each has a jnp fallback used off-TPU (and as the autodiff rule —
the kernels are forward-only with ``custom_vjp`` recompute backward).
The chunked head is plain ``jnp`` under a ``custom_vjp`` of its own,
which recomputes nothing: loss and gradient come from one scan over the
``[chunk, V]`` logits (:func:`weighted_token_loss`).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops._kernel import kernel_mode


def _scale(weight, offset: float):
    """``offset + weight`` in float32; an offset of 0 adds nothing to the
    trace (the norms whose scale is the weight itself)."""
    weight = weight.astype(jnp.float32)
    return weight + offset if offset else weight


def _rmsnorm_ref(x, weight, eps, offset: float = 0.0):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * _scale(weight, offset)).astype(x.dtype)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, offset: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * _scale(w_ref[:], offset)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, weight, eps, interpret, offset=0.0):
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
        functools.partial(_rmsnorm_kernel, eps=eps, offset=offset),
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


def _rmsnorm_fwd(x, weight, eps, interpret, offset):
    return _rmsnorm(x, weight, eps, interpret, offset), (x, weight)


def _rmsnorm_bwd(eps, interpret, offset, res, g):
    x, weight = res
    _, vjp = jax.vjp(lambda x_, w_: _rmsnorm_ref(x_, w_, eps, offset), x,
                     weight)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def fused_rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
                  interpret: Optional[bool] = None,
                  mesh: Optional[jax.sharding.Mesh] = None,
                  offset: float = 0.0) -> jax.Array:
    """``offset``: the scale is ``offset + weight`` (1 for a norm whose
    weights are zero-centred, ``models/qwen3_next.py``); 0 traces the
    body as it is without the argument.

    ``mesh`` (models under a mesh pass ``get_global_mesh()``): GSPMD
    cannot partition a Mosaic kernel, so over several devices the
    kernel runs on each device's own rows of ``x [B, T, E]``, split as
    the activations are, with the whole ``weight``."""
    interpret = kernel_mode(interpret)
    if interpret is None:
        return _rmsnorm_ref(x, weight, eps, offset)
    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel.sharding import MESH_RULES, P

        spec = MESH_RULES.activation_spec("batch", "seq", "embed",
                                          mesh=mesh, shape=x.shape)
        return jax.shard_map(
            lambda a, w: _rmsnorm(a, w, eps, interpret, offset), mesh=mesh,
            in_specs=(spec, P()), out_specs=spec,
            check_vma=False)(x, weight)
    return _rmsnorm(x, weight, eps, interpret, offset)


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


#: what the step's plan span (``models/step.py``) says of the head a step
#: was built with: loss and gradient in the forward's one scan
HEAD_GRADIENT = "grad_in_forward"


def chunked_lm_loss(hidden: jax.Array, emb: jax.Array, labels: jax.Array,
                    *, chunk: int = 8192,
                    compute_dtype: Any = None,
                    logits_dtype: Any = None,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    weight: float = 1.0) -> jax.Array:
    """Mean next-token cross entropy with a chunked LM head, times
    ``weight`` (a second term of a loss, ``models/deepseek_v3.py``: the
    weight enters the gradient's products as it does under autodiff,
    before they round, and not behind them).

    ``hidden`` [B,T,E] (f32), ``emb`` [V,E] (the output head's matrix:
    the embedding where a model ties the two, GPT-2's cells, an untied
    head elsewhere), ``labels`` [B,T].  It is
    :func:`weighted_token_loss` with every token weighing ``weight / n``:
    tokens are processed ``chunk`` at a time, the [chunk,V] logits block
    lives only inside one scan step and is made ONCE, the gradient from
    it in the same step — HBM never holds [B,T,V], which at GPT-2-small
    scale is both the largest tensor and the dominant bandwidth cost of
    the naive head.

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

    def local_sum(h, e, y):
        flat = h.reshape(-1, h.shape[-1])
        return weighted_token_loss(
            flat, e, y.reshape(-1),
            jnp.full(flat.shape[:1], jnp.float32(weight) / n),
            chunk=max(1, chunk // shards), compute_dtype=compute_dtype,
            logits_dtype=logits_dtype)

    if not axes:
        return local_sum(hidden, emb, labels)
    return jax.shard_map(
        lambda h, e, y: jax.lax.psum(local_sum(h, e, y), axes),
        mesh=mesh, in_specs=(P(*tokens, None), P(), tokens),
        out_specs=P(), check_vma=False)(hidden, emb, labels)


def weighted_token_loss(hidden: jax.Array, emb: jax.Array, labels: jax.Array,
                        weights: jax.Array, *, chunk: int = 8192,
                        compute_dtype: Any = None,
                        logits_dtype: Any = None) -> jax.Array:
    """``sum_i weights_i x CE_i``, a float32 scalar: the cross entropy of
    ``hidden [N, E]`` against ``labels [N]`` under the head ``emb [V,
    E]``, every token under its own ``weights [N]`` (float32), through
    the chunked head.  ``chunk`` tokens a scan step, the ``[chunk, V]``
    logits alive in that step alone.

    Asked for a gradient (``jax.custom_vjp``), the SAME scan makes it:
    from a chunk's logits the step takes the loss and ``d = weights x
    (softmax - onehot)``, then ``d hidden = d @ emb`` and ``d emb += d^T
    @ hidden`` on a float32 carry, so the head costs three products a
    chunk and no logits are computed a second time.  The backward pass
    scales what the forward kept by its cotangent: ``d hidden``, ``d
    emb`` and, for ``weights``, ``CE`` itself (a loss that weighs its
    tokens by something that learns: ``models/ouro.py``'s exit gate).
    The head is the last thing a forward does and the first a backward
    does, so the rule needs no byte the recompute did not; how long the
    float32 ``d emb`` then waits for its weight's update is the
    compiler's schedule (PERF.md, PR 57: 0.75 GiB on one cell).  With no
    gradient asked the scan is the loss's alone, one product a chunk.

    Operands as autodiff of the plain form has them: under
    ``compute_dtype`` the products take ``hidden`` and ``emb`` rounded to
    it (``hidden`` a chunk at a time) and ``d`` as float32, accumulate in
    ``logits_dtype or float32``, and a chunk's ``d hidden`` and ``d emb``
    pass through ``compute_dtype`` once.  One device (or replicated
    operands): no ``mesh``; ``chunked_lm_loss`` brings one."""
    return _weighted_loss(hidden, emb, labels, weights.astype(jnp.float32),
                          chunk, compute_dtype, logits_dtype)


def _chunks(hidden, labels, weights, chunk):
    """The operands cut into scan steps of ``chunk`` tokens; the rows
    that fill the last step weigh nothing."""
    pad = (-hidden.shape[0]) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    return (hidden.reshape(-1, chunk, hidden.shape[-1]),
            labels.reshape(-1, chunk), weights.reshape(-1, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _weighted_loss(hidden, emb, labels, weights, chunk, compute_dtype,
                   logits_dtype):
    emb_f32 = emb.astype(jnp.float32)

    def body(total, xs):
        h, y, w = xs
        nll = _chunk_nll(h.astype(jnp.float32), y, emb_f32, compute_dtype,
                         logits_dtype)
        return total + jnp.sum(nll * w), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0),
                            _chunks(hidden, labels, weights, chunk))
    return total


def _weighted_loss_fwd(hidden, emb, labels, weights, chunk, compute_dtype,
                       logits_dtype):
    n, V = hidden.shape[0], emb.shape[0]
    emb_f32 = emb.astype(jnp.float32)
    out = _product_dtype(compute_dtype, logits_dtype)

    def body(carry, xs):
        total, d_emb = carry
        h, y, w = xs
        h, e = _operands(h.astype(jnp.float32), emb_f32, compute_dtype)
        nll, ex, row_sum = _chunk_terms(h, e, y, out)
        # as autodiff writes it: (w / sum) x exp, and -w at the label
        label = jax.lax.broadcasted_iota(jnp.int32, (chunk, V), 1) \
            == jnp.where(y < 0, y + V, y)[:, None]
        d = ((w / row_sum)[:, None] * ex
             + jnp.where(label, -w[:, None], 0.0)).astype(out)
        # a float32 ``d`` against a rounded operand, and the result
        # through the operand's type once: the transposes of the
        # forward's product, to the operand
        d_h = jax.lax.dot_general(d, e, (((1,), (0,)), ((), ())),
                                  preferred_element_type=out)
        d_e = jax.lax.dot_general(d, h, (((0,), (0,)), ((), ())),
                                  preferred_element_type=out)
        d_emb = d_emb + d_e.astype(e.dtype).astype(jnp.float32)
        return ((total + jnp.sum(nll * w), d_emb),
                (d_h.astype(h.dtype).astype(hidden.dtype), nll))

    (total, d_emb), (d_hidden, nll) = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros(emb.shape, jnp.float32)),
        _chunks(hidden, labels, weights, chunk))
    return total, (d_hidden.reshape(-1, hidden.shape[-1])[:n],
                   d_emb.astype(emb.dtype), nll.reshape(-1)[:n])


def _weighted_loss_bwd(chunk, compute_dtype, logits_dtype, kept, g):
    d_hidden, d_emb, nll = kept
    return ((g * d_hidden).astype(d_hidden.dtype),
            (g * d_emb).astype(d_emb.dtype), None, g * nll)


_weighted_loss.defvjp(_weighted_loss_fwd, _weighted_loss_bwd)


def _operands(h, emb_f32, compute_dtype):
    """What the head's products multiply: ``h [chunk, E]`` and the head,
    as they are or rounded to ``compute_dtype`` (the MXU path: bf16
    operands)."""
    if compute_dtype is None:
        return h, emb_f32
    return h.astype(compute_dtype), emb_f32.astype(compute_dtype)


def _product_dtype(compute_dtype, logits_dtype):
    """What the head's products accumulate in and give: float32, unless
    rounded operands come with a ``logits_dtype``.  ``logits_dtype=bf16``
    opts into storing the [chunk, V] block (the step's largest HBM
    consumer, read several times per chunk) in half width: logits then
    quantize at FULL magnitude before the max-subtract, so the error
    grows with logit scale (~0.06 per logit at |x|~16).  No training
    path asks for it; the benchmark's ``lower_precision`` controls do
    (``models/afmoe.py``, ``models/ouro.py``)."""
    if compute_dtype is None:
        return jnp.float32
    return logits_dtype or jnp.float32


def _chunk_nll(h, y, emb_f32, compute_dtype, logits_dtype):
    """One scan step of the chunked head: the cross entropy ``[chunk]``
    of ``h [chunk, E]`` against ``y [chunk]``; the ``[chunk, V]`` logits
    live and die here."""
    return _chunk_terms(*_operands(h, emb_f32, compute_dtype), y,
                        _product_dtype(compute_dtype, logits_dtype))[0]


def _chunk_terms(h, e, y, out):
    """``(nll [chunk], exp(logits - row max) [chunk, V], its row sum
    [chunk])`` of the operands ``h [chunk, E]`` and ``e [V, E]``, the
    logits accumulated and kept as ``out``: the loss, and what a gradient
    takes from the same logits."""
    logits = jax.lax.dot_general(h, e, (((1,), (1,)), ((), ())),
                                 preferred_element_type=out)  # [chunk, V]
    mx = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = (logits - mx).astype(jnp.float32)
    ex = jnp.exp(shifted)
    row_sum = jnp.sum(ex, axis=-1)
    label_logit = jnp.take_along_axis(shifted, y[:, None], axis=-1)[:, 0]
    return jnp.log(row_sum) - label_logit, ex, row_sum


def chunked_token_loss(hidden: jax.Array, emb: jax.Array, labels: jax.Array,
                       *, chunk: int = 8192, compute_dtype: Any = None,
                       logits_dtype: Any = None) -> jax.Array:
    """The chunked LM head giving a loss a TOKEN: ``[B, T]`` float32
    next-token cross entropies of ``hidden [B, T, E]`` against ``labels
    [B, T]`` under the head ``emb [V, E]``, for a READER of the losses
    and for a loss that weighs them in a way of its own
    (``models/ouro.py`` ``exit_terms``; the benchmark's controls).  A
    training loss that weighs its tokens is ``weighted_token_loss``.
    The scan step is that function's, ``chunk`` tokens at a time, but
    under ``jax.checkpoint``: here the cotangent is a vector a token,
    known only in the backward pass, so the ``[chunk, V]`` logits live
    in one scan step and ARE recomputed there; HBM never holds ``[B, T,
    V]``.  One device (or replicated operands): no ``mesh``."""
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
