"""Chunked scan of the GATED DELTA RULE (Gated DeltaNet, arXiv:2412.06464),
forward and backward.

One value head, state ``S`` in ``R^{d_k x d_v}``, ``S_0 = 0``; key ``k_t``
and query ``q_t`` in ``R^{d_k}`` (already l2-normalised and scaled by the
caller), value ``v_t`` in ``R^{d_v}``, a log decay ``g_t <= 0`` and a
step ``beta_t`` in ``(0, 1)``::

    S <- exp(g_t) S          d_t = beta_t (v_t - S^T k_t)
    S <- S + k_t d_t^T       o_t = S^T q_t

``H_k`` key heads serve ``H_v`` value heads: value head ``h`` reads q and
k of key head ``h // (H_v / H_k)``.  Where a state-space scan
(``ops/ssd.py``) lets the state DECAY and adds to it, here every token
also takes a rank-one correction out of it: inside a chunk the
corrections depend on one another, a triangular system a chunk.

:func:`gated_delta` computes the recurrence in chunks of ``C`` positions.
With ``gamma`` the running sum of ``g`` INSIDE a chunk (float32) and
``G_ij = exp(gamma_i - gamma_j)`` for ``i >= j``::

    A  = -strict_lower((beta K) K^T * G)            T = (I - A)^-1
    U  = T (beta V)                                 W = T (beta K exp(gamma))
    V' = U - W S                                    (the chunk's corrections)
    O  = (Q exp(gamma)) S + lower(Q K^T * G) V'
    S <- exp(gamma_C) S + (K exp(gamma_C - gamma))^T V'

Every decay is the exponential of a DIFFERENCE masked to ``i >= j``
BEFORE the ``exp`` (above the diagonal the difference is positive and
may overflow).  ``A`` is strictly lower triangular, so nilpotent (``A^C
= 0``), and ``T`` is EXACTLY the product ``(I + A)(I + A^2)(I + A^4) ..
(I + A^(C/2))``: ``log2 C`` squarings and as many products, which the
MXU takes, where forward substitution is ``C`` dependent steps
(:func:`unit_lower_inverse`; its backward is ``T^T dT T^T``, by a
``custom_vjp``, so no power of ``A`` is kept).

Three steps a call, and ONE statement of a chunk's algebra:

* :func:`_prepare_chunk`: what a chunk computes BEFORE its entering
  state is known (``A``, ``T``, ``U``, ``W``, ``K exp(gamma_C - gamma)``),
  for one key head and the value heads it serves, on 2-d values that fit
  VMEM;
* the carry: the state passes from chunk to chunk in a ``lax.scan``
  whose body, seen once a call by the compiler, is the two products
  ``W S`` and ``K^T V'`` (``carry`` = ``xla`` in the plan span);
* :func:`_read_out_chunk`: ``O`` from the state that entered the chunk
  and its corrections, the scores ``Q K^T * G`` made beside them.

On a TPU the first and the third are Pallas kernels, a few chunks of a
key head a grid step (``chunk_math`` = ``pallas``): the kernel reads its
blocks from the model's arrays viewed as ``[T, H * d]`` (a key head a
block of lanes) and the scan's own (``[chunks, B, H_k, r, ..]``), calls the
chunk's function on them and writes the results; nothing ``[.., C, C]``
reaches HBM.  Their backward is a kernel too, under ``jax.custom_vjp``:
it keeps the INPUTS only and calls ``jax.vjp`` of the same function in
its body, so ``T``, the decays and the scores are made again in VMEM.
Two things make the small algebra fit the chip (PERF.md, PR 59).  The
``C x C`` matrices of the value heads a key head serves lie SIDE BY SIDE
along the 128 lanes (a slab: two at a chunk of 64), and a product with
one of them is a product with all of them down the diagonal of a ``p C
x p C`` matrix (:func:`_diagonal`): whole registers and one pass of the
MXU where a head alone fills half of either.  And a grid step works
several chunks under ``jax.vmap`` (``ABREAST_BYTES``): a chunk's
products depend on one another six deep, and the MXU's latency, not its
rate, bounded a step that ran them chunk after chunk.
Elsewhere, and where the kernels cannot tile the shapes (a chunk that is
no whole sublane tiles, a head width that is no multiple of the 128
lanes), the same functions run under ``jax.vmap`` over batch, chunks and
key heads, plain ``jax.numpy`` under autodiff (``chunk_math`` = ``xla``):
what the kernels are tested against.

The carry and ``gamma`` and every ``exp`` are float32 whatever the
inputs; the products take their operands in ``v``'s dtype and accumulate
in float32.  Float32 operands are multiplied as float32
(``Precision.HIGHEST``: tests, the benchmark's scan probe), in the
kernels as outside them.

**Backward** of the carry: plain autodiff, the scan's body under
``jax.checkpoint``: the backward pass holds the float32 state that
ENTERED each chunk (``chunks x H_v x d_k x d_v``, the scan's stacked
carry, which the read-out reads too: 256 MiB a sequence of 8,192 at 32
heads of 128 x 128), the scan's operands ``U``, ``W``, ``K exp(gamma_C -
gamma)`` and the corrections ``V'`` it gave.

``ops:gated_delta.plan`` says what a call was traced as.
:func:`gated_delta_recurrence` is the definition, step by step, for
tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.ops._kernel import fit_block, kernel_mode, traced_once


#: the name every op of a call carries in a device trace, inside whatever
#: part of the step the caller stands in (``models/step.py``)
SCOPE = "gated_delta"
#: lanes of a vector register: a kernel's head is whole registers wide
LANES = 128
#: bytes of operands' elements a grid step of a kernel works abreast, a
#: chunk each: 8 chunks of bfloat16, 4 of float32 (the probe's; at 8 the
#: read-out's backward asks for 17 MB of the 16 MB of VMEM a kernel may
#: use).  A chunk's algebra is a chain of small DEPENDENT products (the
#: doubling's six deep), which the MXU's latency bounds and not its
#: rate; the chains of several chunks are independent.  On the chip a
#: forward call's ``prepare`` took 1.32 ms at 1 chunk a step, 0.52 at 4
#: and 0.54 at 8, its backward 2.18, 1.06 and 0.85 (PERF.md, PR 59)
ABREAST_BYTES = 16


class Plan(NamedTuple):
    """What was traced, for the ``ops:gated_delta.plan`` span."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    chunk: int
    seq: int

    @property
    def rep(self) -> int:
        """Value heads a key head serves: what a grid step works."""
        return self.value_heads // self.key_heads

    @property
    def pack(self) -> int:
        """Value heads to a slab: their ``C x C`` matrices lie side by
        side along the 128 lanes (two at a chunk of 64)."""
        return fit_block(self.rep, max(1, LANES // self.chunk))

    def chunks_a_step(self, dtype) -> int:
        return fit_block(self.seq // self.chunk,
                         ABREAST_BYTES // jnp.dtype(dtype).itemsize)

    def tiles(self, dtype) -> bool:
        """Whether the kernels can take these shapes: a chunk of whole
        sublane tiles of ``dtype`` (8 rows of 32 bits), heads of whole
        lanes."""
        rows = 8 * 4 // jnp.dtype(dtype).itemsize
        return not (self.chunk % rows or self.key_dim % LANES
                    or self.value_dim % LANES)

    def span_args(self, batch: int, kernels: bool, itemsize: int) -> dict:
        """``chunk_math``: where a chunk's algebra around the carry is
        computed (``pallas``: in VMEM, by kernels; ``xla``: batched, its
        ``C x C`` matrices arrays in HBM).  ``saved``: what the backward
        pass holds of the forward beside the inputs, ``saved_bytes`` of
        it a call: the float32 states that entered the chunks, and the
        carry's operands and corrections (``U``, ``W``, ``K exp(gamma_C -
        gamma)``, ``V'``) in the inputs' dtype; the ``xla`` form's
        autodiff keeps the ``C x C`` matrices besides, which are not
        counted."""
        chunks = self.seq // self.chunk
        heads = batch * self.value_heads
        return {**self._asdict(), "chunks": chunks,
                "inverse": "doubling", "carry": "xla",
                "chunk_math": "pallas" if kernels else "xla",
                "saved": "chunk_states,carry_operands",
                "saved_bytes": 4 * heads * chunks * self.key_dim
                * self.value_dim + 2 * itemsize * heads * self.seq
                * (self.key_dim + self.value_dim)}


def _plan(q, v, chunk: int) -> Plan:
    _, seq, key_heads, key_dim = q.shape
    value_heads, value_dim = v.shape[2:]
    if seq % chunk:
        raise ValueError(
            f"gated_delta: a sequence of {seq} positions is not whole "
            f"chunks of {chunk}; pad it to a multiple of the chunk")
    if chunk & (chunk - 1):
        raise ValueError(f"gated_delta: a chunk of {chunk} is no power of "
                         f"two (the inverse doubles)")
    if value_heads % key_heads:
        raise ValueError(f"gated_delta: {value_heads} value heads do not "
                         f"split over {key_heads} key heads")
    return Plan(key_heads, value_heads, key_dim, value_dim, chunk, seq)


def gated_delta_recurrence(q, k, v, g, beta):
    """The definition, step by step in float32: ``T`` sequential steps.
    ``q k [B, T, H_k, d_k]``, ``v [B, T, H_v, d_v]``, ``g beta [B, T,
    H_v]`` -> ``o [B, T, H_v, d_v]`` float32.  What every other path is
    tested against; not a training path."""
    f32 = jnp.float32
    rep = v.shape[2] // k.shape[2]

    def step(S, inp):
        qt, kt, vt, gt, bt = inp            # [B,Hk,dk] x2 [B,Hv,dv] [B,Hv] x2
        qt, kt = jnp.repeat(qt, rep, 1), jnp.repeat(kt, rep, 1)
        S = jnp.exp(gt)[..., None, None] * S
        d = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    first = jnp.zeros((v.shape[0], v.shape[2], k.shape[3], v.shape[3]), f32)
    swap = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)  # noqa: E731
    _, o = jax.lax.scan(step, first, tuple(map(swap, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# a chunk's algebra, on 2-d values: what the kernels' bodies call, and
# what ``jax.vmap`` batches off the TPU
# ---------------------------------------------------------------------------

#: the three 2-d products and, for each, the products that give the
#: cotangents of its operands from the result's ``g`` (``g`` takes the
#: place named ``"g"``): they are closed under differentiation, so a
#: kernel's backward multiplies on the array as its forward does and
#: transposes nothing
_CONTRACT = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}
_COTANGENTS = {"nn": (("nt", "g", "b"), ("tn", "a", "g")),
               "nt": (("nn", "g", "b"), ("tn", "g", "a")),
               "tn": (("nt", "b", "g"), ("nn", "a", "g"))}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dot(kind: str, dtype, a, b):
    """``a @ b`` (``nn``), ``a @ b^T`` (``nt``) or ``a^T @ b`` (``tn``)
    with float32 accumulation, the operands rounded to ``dtype`` HERE;
    float32 operands are multiplied as float32, not rounded to bfloat16
    first.  (The rounding is the product's own, not the caller's: a
    cotangent then stays float32 until the next product rounds it, where
    a caller's ``astype`` would round it on its way back too.  XLA drops
    such a round trip; a kernel does what is written.)"""
    exact = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (_CONTRACT[kind], ((), ())),
        precision=exact, preferred_element_type=jnp.float32)


def _dot_fwd(kind, dtype, a, b):
    return _dot(kind, dtype, a, b), (a, b)


def _dot_bwd(kind, dtype, saved, g):
    a, b = saved
    of = {"a": a, "b": b, "g": g}
    da, db = (_dot(how, dtype, of[x], of[y])
              for how, x, y in _COTANGENTS[kind])
    return da.astype(a.dtype), db.astype(b.dtype)


_dot.defvjp(_dot_fwd, _dot_bwd)


def _lane_head(shape, size: int):
    """Which head of a slab a lane belongs to, ``[*shape]`` int32."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // size


def _diagonal(x):
    """``[C, p C]``, a slab's heads' ``C x C`` matrices side by side ->
    ``[p C, p C]``, the same matrices down the diagonal and 0 beside
    them: ``y @ _diagonal(x)`` is then every head's own product ``y_h @
    x_h``, side by side, ONE product 128 lanes wide on the array."""
    size, width = x.shape
    if width == size:
        return x
    head = _lane_head(x.shape, size)
    return jnp.concatenate([jnp.where(head == h, x, 0.0)
                            for h in range(width // size)], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inverse(a, dtype):
    """:func:`unit_lower_inverse` of a slab's ``C x C`` float32 matrices
    ``[C, p C]``, its products' operands in ``dtype``."""
    size, width = a.shape
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) % size
    out, power, wide, reach = jnp.where(row == col, 1.0, a), a, \
        _diagonal(a), 2
    while reach < size:
        power = _dot("nn", dtype, power, wide)
        wide = _diagonal(power)
        out = out + _dot("nn", dtype, out, wide)
        reach *= 2
    return out


def _inverse_fwd(a, dtype):
    t = _inverse(a, dtype)
    return t, t


def _inverse_bwd(dtype, t, g):
    # d (I - a)^-1 = T da T, a head: T_h^T G_h is the h-th block of the
    # diagonal of T^T G
    size, width = t.shape
    tg = _dot("tn", dtype, t, g)
    if width != size:
        head = _lane_head(tg.shape, size)
        tg = sum(jnp.where(head == h, tg, 0.0)[h * size:(h + 1) * size]
                 for h in range(width // size))
    return (_dot("nt", dtype, tg, _diagonal(t)),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I - a)^-1`` for ``a [..., C, C]`` STRICTLY lower triangular
    (``C`` a power of two): ``a^C = 0``, so the Neumann series ends and
    is the product ``(I + a)(I + a^2)(I + a^4) ..``, exactly."""
    flat = a.reshape(-1, *a.shape[-2:])
    return jax.vmap(lambda one: _inverse(one, a.dtype))(flat).reshape(
        a.shape)


def _columns(row, size: int):
    """A slab's heads' vectors ``[1, p C]``, a head's chunk after the
    other along the lanes -> each head's as a column ``[C, 1]`` (a select
    and a sum along the lanes), and ``[C, p C]``: each head's column over
    that head's lanes."""
    shape = (size, row.shape[1])
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    cols = [jnp.sum(jnp.where(lane == h * size + i, row, 0.0), axis=1,
                    keepdims=True) for h in range(shape[1] // size)]
    wide = jnp.broadcast_to(cols[0], shape)
    for h, col in enumerate(cols[1:], 1):
        wide = jnp.where(lane // size == h, col, wide)
    return cols, wide


def _decay(gamma_cols, gamma_row):
    """``exp(gamma_i - gamma_j)`` for ``i >= j``, 0 above the diagonal,
    of a slab's heads side by side ``[C, p C]``: the difference is masked
    BEFORE the exponential."""
    i = jax.lax.broadcasted_iota(jnp.int32, gamma_cols.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, gamma_cols.shape, 1) \
        % gamma_cols.shape[0]
    return jnp.exp(jnp.where(i >= j, gamma_cols - gamma_row, -jnp.inf))


def _own_rows(x, h: int, heads: int):
    """``[C, d]`` of head ``h`` of a slab -> ``[p C, d]``, 0 at the
    other heads' rows: what a slab's ``[C, p C]`` multiplies to give
    head ``h``'s product alone."""
    zeros = jnp.zeros_like(x)
    return x if heads == 1 else jnp.concatenate(
        [x if i == h else zeros for i in range(heads)], axis=0)


def _prepare_chunk(k, v, beta, gamma):
    """What a chunk computes before its entering state: ``k [C, d_k]``
    of one key head; ``v [C, d_v]`` of each value head it serves; of
    each SLAB of ``p`` value heads the float32 rows ``beta``, ``gamma``
    ``[1, p C]`` -> ``(U, W, K exp(gamma_C - gamma))`` a value head, in
    ``v``'s dtype.  A slab's ``C x C`` matrices lie side by side along
    the lanes.  (Everything between the products is float32, the
    operands as well: a cotangent is rounded where a value is, at a
    product and at the results.)"""
    dtype, size = v[0].dtype, k.shape[0]
    pack = len(v) // len(gamma)
    k = k.astype(dtype).astype(jnp.float32)
    kk = _dot("nt", dtype, k, jnp.concatenate([k] * pack, axis=0))
    strict = jax.lax.broadcasted_iota(jnp.int32, kk.shape, 0) \
        > jax.lax.broadcasted_iota(jnp.int32, kk.shape, 1) % size
    us, ws, k_ends = [], [], []
    for s, (beta_row, gamma_row) in enumerate(zip(beta, gamma)):
        beta_h, beta_s = _columns(beta_row, size)
        gamma_h, gamma_s = _columns(gamma_row, size)
        a = -jnp.where(strict, beta_s * kk * _decay(gamma_s, gamma_row), 0.0)
        solve = _inverse(a, dtype)
        for h in range(pack):
            own = functools.partial(_own_rows, h=h, heads=pack)
            us.append(_dot("nn", dtype, solve,
                           own(beta_h[h] * v[s * pack + h])))
            ws.append(_dot("nn", dtype, solve,
                           own(beta_h[h] * jnp.exp(gamma_h[h]) * k)))
            k_ends.append(jnp.exp(gamma_h[h][size - 1:] - gamma_h[h]) * k)
    return tuple(tuple(x.astype(dtype) for x in xs)
                 for xs in (us, ws, k_ends))


def _read_out_chunk(q, k, gamma, state, fresh):
    """A chunk's results: ``q k [C, d_k]`` of one key head; of each
    value head it serves the float32 ``state [d_k, d_v]`` that entered
    and the corrections ``fresh [C, d_v]``; of each slab the float32 row
    ``gamma [1, p C]`` -> ``o [C, d_v]`` a value head, in ``fresh``'s
    dtype."""
    f32 = jnp.float32
    dtype, size = fresh[0].dtype, q.shape[0]
    pack = len(fresh) // len(gamma)
    q, k = (x.astype(dtype).astype(f32) for x in (q, k))
    qk = _dot("nt", dtype, q, jnp.concatenate([k] * pack, axis=0))
    out = []
    for s, gamma_row in enumerate(gamma):
        gamma_h, gamma_s = _columns(gamma_row, size)
        scores = qk * _decay(gamma_s, gamma_row)
        for h in range(pack):
            r = s * pack + h
            out.append((_dot("nn", dtype, jnp.exp(gamma_h[h]) * q, state[r])
                        + _dot("nn", dtype, scores, _own_rows(
                            fresh[r], h, pack))).astype(dtype))
    return (tuple(out),)


# ---------------------------------------------------------------------------
# a chunk's function over all chunks: a kernel's grid, or ``jax.vmap``
# ---------------------------------------------------------------------------

#: how the arrays of a call lie in HBM, by name: where batch, chunk and
#: key head are among an array's axes (``_LANES``: the key head is a
#: block of the LAST axis, which the positions count split in two)
_AXES = {
    # [B, chunks, C, H_k * d_k]: the model's [B, T, H_k, d_k], viewed
    "key": (0, 1, 3),
    # [B, chunks, C, H_k * r * d_v]: the model's [B, T, H_v, d_v]
    "value": (0, 1, 3),
    # [B, chunks, H_k, slabs, p C] float32: the chunks of a slab's p
    # heads, one after the other along the lanes
    "rows": (0, 1, 2),
    # [chunks, B, H_k, r, C or d_k, d]: what the carry's scan reads and
    # stacks, the chunk its leading axis
    "heads": (1, 0, 2),
}
_LANES = ("key", "value")

#: name -> (a chunk's function, the layouts of its arguments, of its
#: results)
_CHUNK_OPS = {
    "prepare": (_prepare_chunk, ("key", "value", "rows", "rows"),
                ("heads", "heads", "heads")),
    "read_out": (_read_out_chunk, ("key", "key", "rows", "heads", "heads"),
                 ("value",)),
}


def _indices(lay: str, block, rep: int, lead=()):
    """Where in ``block`` (a ref or an array, of one batch, chunk and key
    head under the leading index ``lead``) the parts are that a chunk's
    function takes apart: the value heads', the slabs' of ``rows``, the
    whole block of ``key``."""
    if lay == "key":
        return (lead + (...,),)
    if lay == "rows":
        return tuple(lead + (slice(s, s + 1),)
                     for s in range(block.shape[-2]))
    if lay == "heads":
        return tuple(lead + (r,) for r in range(rep))
    width = block.shape[-1] // rep
    return tuple(lead + (slice(None), slice(r * width, (r + 1) * width))
                 for r in range(rep))


def _parts(layouts, blocks, rep: int, lead=()):
    """The blocks as a chunk's function takes them (a ref is read)."""
    def cut(lay, x):
        found = tuple(x[i] for i in _indices(lay, x, rep, lead))
        return found[0] if lay == "key" else found
    return tuple(map(cut, layouts, blocks))


def _kernel(*refs, fn, ins, outs, rep: int, backward: bool):
    """A few chunks of one key head, each on its own, ``fn`` under
    ``jax.vmap`` over them: every operation of the algebra is then
    stated for all of them at once, and the chunks' chains of dependent
    products stand interleaved in the program as the scheduler should
    run them.  Forward: ``fn`` of the input blocks into the result
    blocks.  Backward: the input blocks and the results' cotangents into
    the inputs' cotangents, by ``jax.vjp`` of ``fn`` here in VMEM:
    nothing of the forward was kept."""
    every = (slice(None),)
    fn = jax.vmap(fn)
    args = _parts(ins, refs[:len(ins)], rep, every)
    if backward:
        given = _parts(outs, refs[len(ins):len(ins) + len(outs)], rep,
                       every)
        results, layouts = jax.vjp(fn, *args)[1](given), ins
    else:
        results, layouts = fn(*args), outs
    for lay, ref, parts in zip(layouts, refs[-len(layouts):], results):
        for i, part in zip(_indices(lay, ref, rep, every),
                           (parts,) if lay == "key" else parts):
            ref[i] = part.astype(ref.dtype)


def _result_shapes(op: str, plan: Plan, arrays):
    """``jax.ShapeDtypeStruct`` of ``op``'s results."""
    batch, chunks = arrays[0].shape[0], plan.seq // plan.chunk
    if op == "read_out":
        return [jax.ShapeDtypeStruct(
            (batch, chunks, plan.chunk, plan.value_heads * plan.value_dim),
            arrays[-1].dtype)]
    return [jax.ShapeDtypeStruct(
        (chunks, batch, plan.key_heads, plan.rep, plan.chunk, d),
        arrays[1].dtype)
        for d in (plan.value_dim, plan.key_dim, plan.key_dim)]


def _pallas(op: str, plan: Plan, interpret: bool, backward: bool, arrays,
            results):
    """``op``'s function (its ``jax.vjp`` with ``backward``) over the
    grid ``(batch, chunks / chunks a step, key heads)``: ``arrays`` in,
    ``results`` (shapes) out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    fn, ins, outs = _CHUNK_OPS[op]
    lay_in, lay_out = (ins + outs, ins) if backward else (ins, outs)
    abreast = plan.chunks_a_step(arrays[0].dtype)

    def spec(lay: str, shape):
        """A block: everything of one (batch, a step's chunks, key
        head), the chunks its leading axis."""
        axes = _AXES[lay][:2] + ((len(shape) - 1,) if lay in _LANES
                                 else _AXES[lay][2:])
        block = [None if i in axes else n for i, n in enumerate(shape)]
        block[_AXES[lay][1]] = abreast
        if lay in _LANES:
            block[-1] = shape[-1] // plan.key_heads

        def index(*step):
            at = [0] * len(shape)
            for axis, i in zip(axes, step):
                at[axis] = i
            return tuple(at)
        return pl.BlockSpec(tuple(block), index)

    return pl.pallas_call(
        functools.partial(_kernel, fn=fn, ins=ins, outs=outs, rep=plan.rep,
                          backward=backward),
        grid=(arrays[0].shape[0],
              plan.seq // plan.chunk // abreast, plan.key_heads),
        in_specs=[spec(lay, a.shape) for lay, a in zip(lay_in, arrays)],
        out_specs=[spec(lay, r.shape) for lay, r in zip(lay_out, results)],
        out_shape=results,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel")),
        interpret=interpret,
        name=f"gated_delta_{op}" + "_bwd" * backward)(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _in_vmem(op: str, plan: Plan, interpret: bool, *arrays):
    """``op`` of every chunk by a kernel; differentiated by a kernel."""
    return tuple(_pallas(op, plan, interpret, False, arrays,
                         _result_shapes(op, plan, arrays)))


def _in_vmem_fwd(op, plan, interpret, *arrays):
    return _in_vmem(op, plan, interpret, *arrays), arrays


def _in_vmem_bwd(op, plan, interpret, arrays, given):
    return tuple(_pallas(
        op, plan, interpret, True, (*arrays, *given),
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]))


_in_vmem.defvjp(_in_vmem_fwd, _in_vmem_bwd)


def _batched(op: str, plan: Plan, *arrays):
    """``op`` of every chunk by ``jax.vmap`` over batch, chunks and key
    heads of the same arrays: plain ``jnp`` under autodiff."""
    fn, ins, outs = _CHUNK_OPS[op]

    def lead(lay: str, x):
        """``[B, chunks, H_k, *block]``."""
        if lay in _LANES:
            x = x.reshape(*x.shape[:-1], plan.key_heads, -1)
        return jnp.moveaxis(x, _AXES[lay], (0, 1, 2))

    def back(lay: str, x):
        x = jnp.moveaxis(x, (0, 1, 2), _AXES[lay])
        return x.reshape(*x.shape[:-2], -1) if lay in _LANES else x

    def one(*blocks):
        return tuple(
            jnp.stack(parts) if lay == "heads"
            else jnp.concatenate(parts, axis=1)   # (``value``)
            for lay, parts in zip(outs, fn(*_parts(ins, blocks, plan.rep))))

    results = jax.vmap(jax.vmap(jax.vmap(one)))(*map(lead, ins, arrays))
    return tuple(map(back, outs, results))


def gated_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, *, chunk: int = 64,
                interpret: Optional[bool] = None) -> jax.Array:
    """``q k [B, T, H_k, d_k]`` (l2-normalised, ``q`` scaled), ``v [B, T,
    H_v, d_v]``, ``g [B, T, H_v]`` (log decays, ``<= 0``) and ``beta [B,
    T, H_v]`` -> ``o [B, T, H_v, d_v]`` in ``v``'s dtype.  ``T`` has to
    be whole chunks (a length that is not is refused, not padded).
    ``interpret``: ``ops/_kernel.py`` ``kernel_mode``; shapes the kernels
    cannot tile take the ``jnp`` form whatever it says."""
    plan = _plan(q, v, chunk)
    interpret = kernel_mode(interpret) if plan.tiles(v.dtype) else None
    with telemetry.span("ops", "gated_delta.plan", **plan.span_args(
            q.shape[0], interpret is not None, v.dtype.itemsize)), \
            jax.named_scope(SCOPE):
        return _gated_delta(q, k, v, g, beta, plan, interpret)


@traced_once("plan", "interpret")
def _gated_delta(q, k, v, g, beta, plan: Plan, interpret):
    """(Under an inner ``jit``: a model calls this once a layer and a
    sequence, and again under ``remat``, with the same shapes; the first
    call's jaxpr serves the others, ``ops/_kernel.py``.)"""
    f32 = jnp.float32
    b = v.shape[0]
    c, nc = plan.chunk, plan.seq // plan.chunk
    hk, rep = plan.key_heads, plan.rep
    dtype = v.dtype

    def chunk_op(op: str, *arrays):
        if interpret is None:
            return _batched(op, plan, *arrays)
        return _in_vmem(op, plan, interpret, *arrays)

    def rows(x, running: bool):
        """``[B, T, H_v]`` -> ``[B, chunks, H_v, C]`` float32, a head's
        chunk along the lanes, or its running sum: a product with the
        identity or with a triangle of ones, float32 to the last bit
        that six bfloat16 passes give (XLA's own transpose and ``cumsum``
        of an array whose minor axis is the heads took 0.4 ms a call on
        the chip, as long as the two forward kernels: PERF.md, PR 59;
        ``ops/ssd.py`` ``_cum_rows``)."""
        s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        return jnp.einsum(
            "bnsh,st->bnht", x.astype(f32).reshape(b, nc, c, hk * rep),
            (s <= t if running else s == t).astype(f32),
            precision=jax.lax.Precision.HIGHEST)

    def slabs(x):
        """-> ``[B, chunks, H_k, slabs, p C]``: a slab's heads' chunks
        one after the other."""
        return x.reshape(b, nc, hk, rep // plan.pack, plan.pack * c)

    # the model's layouts viewed in chunks, a key head a block of lanes
    # (free in row-major order; on the TPU ``[T, H, d]`` is tiled over
    # ``(H, d)`` and XLA lays the view out anew: PERF.md section 7)
    q, k, v = (x.reshape(b, nc, c, -1) for x in (q, k, v))
    gamma = rows(g, True)
    last = jnp.moveaxis(gamma[..., -1], 1, 0).reshape(nc, b, hk, rep)
    gamma = slabs(gamma)
    u, w, k_end = chunk_op("prepare", k, v, slabs(rows(beta, False)), gamma)

    def chunk_step(state, inp):
        """``state [b, h, r, dk, dv]`` float32, what ENTERS the chunk."""
        u_c, w_c, k_c, end = inp
        fresh = u_c.astype(f32) - jnp.einsum(
            "bhrik,bhrkv->bhriv", w_c, state.astype(dtype),
            preferred_element_type=f32)
        fresh = fresh.astype(dtype)
        new = jnp.exp(end)[..., None, None] * state + jnp.einsum(
            "bhrik,bhriv->bhrkv", k_c, fresh, preferred_element_type=f32)
        return new, (state, fresh)

    first = jnp.zeros((b, hk, rep, plan.key_dim, plan.value_dim), f32)
    _, (states, fresh) = jax.lax.scan(
        jax.checkpoint(chunk_step), first,
        (u, w, k_end, last))
    (out,) = chunk_op("read_out", q, k, gamma, states, fresh)
    return out.reshape(b, plan.seq, plan.value_heads, plan.value_dim)
