"""The passes over a token's residual lanes (``models/hyper.py``), each
ONE read of the lanes from a tile in VMEM.

``x [M, n C]``: ``M`` tokens, each ``n`` lanes of ``C`` columns side by
side.  A hyper-connection multiplies nothing worth the array: its cost
is how often the lanes cross HBM, and as plain ``jnp`` beside the
kernels of a step they cross it as float32 (the projection's operand, a
``[M, n C]`` float32 array written and read again) and once a term of
the backward.  Five kernels instead, a grid step a tile of whole tokens
(all ``n C`` columns of ``rows`` tokens), everything between the read
and the write on the tile:

* :func:`project` (``_lane_project``): ``x phi`` and ``sum(x^2)`` a
  token from one read of ``x`` as it lies.
* :func:`write` (``_lane_write``): ``x'_i = sum_j res[i, j] x_j +
  post_i y``, the four lanes in and the four out, float32 sums rounded
  once.
* :func:`write_bwd` (``_lane_write_bwd``): from ``x``, ``d x'`` and
  ``y``: ``d res[i, j] = <d x'_i, x_j>``, ``d post_i = <d x'_i, y>`` and
  ``d y = sum_i post_i d x'_i``.
* :func:`read_bwd` (``_lane_read_bwd``): ``d pre_i = <d u, x_i>``.
* :func:`open_bwd` (``_lane_open_bwd``): the ONE write of ``d x``::

      d x_j = sum_i res[i, j] d x'_i + pre_j d u
              + (phi g)_j + 2 d_ss x_j

  (``g [m, M]`` and ``d_ss [M]``: the cotangents of :func:`project`'s
  two results), and ``d phi = x^T g^T`` summed in float32 in VMEM over
  the token tiles of the same pass.

**The three-piece product, and why it is the float32 one.**  The
projection is stated in float32 (the configuration's ``hc_dtype``), and
XLA's float32 product at ``HIGHEST`` cuts BOTH operands into three
bfloat16 pieces (``hi + mid + lo``, 8 significant bits each, 24 in all:
a float32 exactly) and adds six of the nine partial products in float32.
But a bfloat16 ``x`` IS its own first piece: ``x_mid = x_lo = 0``, and of
the six products three are zero.  What is left, ``x phi_hi + x phi_mid +
x phi_lo`` with float32 accumulation, is every bit of ``x phi``: each
partial product of two 8-bit significands is exact in float32, and the
three pieces of ``phi`` add up to ``phi`` exactly.  So ``phi``'s pieces
lie side by side as ONE bfloat16 operand ``[n C, 3 m]`` (padded to 128
columns), ``x`` meets it in one MXU pass with no float32 ``x`` anywhere,
and the three column groups are added outside, smallest first.  ONE
piece is a bfloat16 product (off by 2^-9 of ``phi``) and is not this.
The pieces are cut with ``lax.reduce_precision``: a convert there and
back inside one program is dropped on the chip.  The same holds for the
backward's ``d phi`` (``g`` cut in three against ``x``); ``phi g``, which
is rounded to ``x``'s bfloat16 with the rest of ``d x``, takes the three
largest of the nine partial products (2^-16).  Where ``x`` is float32
nothing is cut and the kernels multiply float32 operands at ``HIGHEST``.

What a token's coefficients are made of (the gates, the sigmoids, the
norm's ``rsqrt``, the Sinkhorn steps) is no kernel's: the kernels take
and give them as ``[M, 128]`` float32 arrays, a token a row (``side``),
which ``hyper.py`` fills and reads.

:func:`mode` says which form runs: the kernels on a TPU (or through the
Pallas interpreter where a test asks), where a lane is whole 128-column
registers, the tokens divide into tiles, ``x`` is bfloat16 or float32
and the coefficients float32; else ``None``, and ``hyper.py`` runs its
``jnp`` forms.  Nothing but the shapes, the dtypes and the backend
decides.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops._kernel import kernel_mode, traced_once

#: the lanes of a vector register, and the width of a ``side`` array
UNIT = 128
#: rows worked at a time inside a tile (a packed bfloat16 register)
SUB = 16
#: columns of the contraction a product takes at a time
CHUNK = 2048
#: the most rows of a tile, and the bytes all blocks of a grid step may
#: take (each is held twice: one worked, one in flight)
ROWS = 256
BLOCK_BYTES = 20 << 20
VMEM_LIMIT = 100 << 20
HIGHEST = jax.lax.Precision.HIGHEST


class Tiles(NamedTuple):
    """The token tiles of the five kernels, by what each holds a token."""
    project: int
    write: int
    write_bwd: int
    read_bwd: int
    open_bwd: int


def _rows(tokens: int, token_bytes: int, unit: int) -> Optional[int]:
    """The largest tile of whole ``unit``-row groups that divides
    ``tokens`` and fits the blocks' budget; ``tokens`` itself where they
    fit as one tile."""
    most = min(ROWS, BLOCK_BYTES // token_bytes)
    for rows in range(most - most % unit, 0, -unit):
        if tokens % rows == 0:
            return rows
    return None


def tiles(tokens: int, n: int, width: int, dtype) -> Optional[Tiles]:
    """The tiles for ``tokens`` tokens of ``n`` lanes of ``width``
    columns, or ``None`` where the shapes are not whole tiles."""
    size = jnp.dtype(dtype).itemsize
    if n < 2 or 3 * n * (n + 2) >= UNIT or width % UNIT or not tokens \
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    lane = width * size
    # the backward's last pass gives ``g`` a tile of tokens LAST: whole
    # 128-lane registers of them, or all of them
    last = _rows(tokens, (3 * n + 1) * lane + 4 * n * width, UNIT) \
        or (tokens if tokens % SUB == 0 and tokens <= ROWS else None)
    held = (_rows(tokens, n * lane, SUB),
            _rows(tokens, (2 * n + 1) * lane, SUB),
            _rows(tokens, (2 * n + 2) * lane, SUB),
            _rows(tokens, (n + 1) * lane, SUB), last)
    return None if None in held else Tiles(*held)


def mode(tokens: int, n: int, width: int, dtype, coef_dtype,
         interpret: Optional[bool] = None) -> Optional[bool]:
    """``None``: the ``jnp`` forms; ``False``: the compiled kernels;
    ``True``: the kernels through the Pallas interpreter."""
    if jnp.dtype(coef_dtype) != jnp.float32 \
            or tiles(tokens, n, width, dtype) is None:
        return None
    return kernel_mode(interpret)


# ---------------------------------------------------------------------------
# the side arrays: a token's few numbers, a row of 128 float32
# ---------------------------------------------------------------------------

def side(*rows: jax.Array) -> jax.Array:
    """``[k, M]`` arrays (tokens last) stacked and turned: ``[M, 128]``
    float32, a token a row, zeros behind the ``sum k`` columns."""
    flat = jnp.concatenate([r.reshape(-1, r.shape[-1]).astype(jnp.float32)
                            for r in rows], axis=0)
    return jnp.pad(flat.T, ((0, 0), (0, UNIT - flat.shape[0])))


def unside(array: jax.Array, first: int, count: int) -> jax.Array:
    """Columns ``first .. first + count`` of a side array, tokens last:
    ``[count, M]``."""
    return array[:, first:first + count].T


def pieces(a: jax.Array):
    """A float32 array's three bfloat16 pieces, ``hi + mid + lo == a``
    to 24 bits, each still float32."""
    a = a.astype(jnp.float32)
    hi = jax.lax.reduce_precision(a, 8, 7)
    mid = jax.lax.reduce_precision(a - hi, 8, 7)
    return hi, mid, jax.lax.reduce_precision(a - hi - mid, 8, 7)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _over_rows(rows: int, body) -> None:
    """``body(slice)`` over a tile's ``SUB``-row groups."""
    from jax.experimental import pallas as pl

    def one(r, carry):
        body(pl.ds(pl.multiple_of(r * SUB, SUB), SUB))
        return carry

    jax.lax.fori_loop(0, rows // SUB, one, 0)


def _columns(cols, values) -> jax.Array:
    """``[SUB, 1]`` columns set into a ``[SUB, 128]`` row of a side
    array at the lanes ``cols``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (values[0].shape[0], UNIT), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for col, value in zip(cols, values):
        out = jnp.where(lane == col, value, out)
    return out


def _sum_lanes(x) -> jax.Array:
    return jnp.sum(x, axis=1, keepdims=True)


def _block(ref, here, lane: int, width: int, c: int) -> jax.Array:
    """Columns ``c .. c + 128`` of lane ``lane`` of the rows ``here``,
    widened to float32."""
    first = lane * width + c
    return ref[here, first:first + UNIT].astype(jnp.float32)


def _dot(a, b, exact: bool):
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=HIGHEST if exact else None)


def _project_kernel(x_ref, w_ref, out_ref, *, exact: bool):
    """``out [rows, 128]``: ``x w`` (the pieces' column groups), and the
    squares' sum a token in the LAST column."""
    rows, width = x_ref.shape
    out_ref[...] = sum(
        _dot(x_ref[:, c:min(c + CHUNK, width)],
             w_ref[c:min(c + CHUNK, width), :], exact)
        for c in range(0, width, CHUNK))

    def squares(here):
        part = jnp.zeros((SUB, UNIT), jnp.float32)
        for c in range(0, width, UNIT):
            x = _block(x_ref, here, 0, width, c)
            part = part + x * x
        lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, UNIT), 1)
        out_ref[here, :] = jnp.where(lane == UNIT - 1, _sum_lanes(part),
                                     out_ref[here, :])

    _over_rows(rows, squares)


def _write_kernel(x_ref, y_ref, side_ref, out_ref, *, n: int):
    """``side``: ``res[i, j]`` at column ``i n + j``, ``post_i`` at ``n n
    + i``."""
    rows, width = y_ref.shape

    def mix(here):
        s = side_ref[here, :]
        co = [s[:, k:k + 1] for k in range(n * n + n)]
        for c in range(0, width, UNIT):
            lanes = [_block(x_ref, here, j, width, c) for j in range(n)]
            y = _block(y_ref, here, 0, width, c)
            for i in range(n):
                out = co[i * n] * lanes[0]
                for j in range(1, n):
                    out = out + co[i * n + j] * lanes[j]
                out = out + co[n * n + i] * y
                out_ref[here, i * width + c:i * width + c + UNIT] = \
                    out.astype(out_ref.dtype)

    _over_rows(rows, mix)


def _write_bwd_kernel(x_ref, d_ref, y_ref, side_ref, dy_ref, dside_ref, *,
                      n: int):
    """``side``: ``post_i`` at column ``i``; ``dside``: ``d res[i, j]``
    at ``i n + j``, ``d post_i`` at ``n n + i``."""
    f32 = jnp.float32
    rows, width = y_ref.shape

    def sums(here):
        s = side_ref[here, :]
        post = [s[:, i:i + 1] for i in range(n)]
        acc = [jnp.zeros((SUB, UNIT), f32) for _ in range(n * n + n)]
        for c in range(0, width, UNIT):
            lanes = [_block(x_ref, here, j, width, c) for j in range(n)]
            y = _block(y_ref, here, 0, width, c)
            dy = None
            for i in range(n):
                d = _block(d_ref, here, i, width, c)
                dy = post[i] * d if dy is None else dy + post[i] * d
                for j in range(n):
                    acc[i * n + j] = acc[i * n + j] + d * lanes[j]
                acc[n * n + i] = acc[n * n + i] + d * y
            dy_ref[here, c:c + UNIT] = dy.astype(dy_ref.dtype)
        dside_ref[here, :] = _columns(range(n * n + n),
                                      [_sum_lanes(a) for a in acc])

    _over_rows(rows, sums)


def _read_bwd_kernel(x_ref, du_ref, dside_ref, *, n: int):
    """``d pre_i`` at column ``i``."""
    f32 = jnp.float32
    rows, width = du_ref.shape

    def sums(here):
        acc = [jnp.zeros((SUB, UNIT), f32) for _ in range(n)]
        for c in range(0, width, UNIT):
            du = _block(du_ref, here, 0, width, c)
            for i in range(n):
                acc[i] = acc[i] + du * _block(x_ref, here, i, width, c)
        dside_ref[here, :] = _columns(range(n), [_sum_lanes(a) for a in acc])

    _over_rows(rows, sums)


def _open_bwd_kernel(x_ref, d_ref, du_ref, side_ref, g_ref, gt_ref, w_ref,
                     dx_ref, dw_ref, p_ref, *, n: int, exact: bool):
    """``side``: ``res[i, j]`` at ``i n + j``, ``pre_j`` at ``n n + j``,
    ``2 d_ss`` at ``n n + n``.  ``g [rows, 128]`` against ``w [128, n
    C]`` is ``phi g`` a token (``p_ref``, float32 scratch); ``gt [R,
    rows]`` against ``x`` is this tile's term of ``d phi^T [R, n C]``."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    rows, width = du_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _start():
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)

    for c in range(0, n * width, CHUNK):
        cols = slice(c, min(c + CHUNK, n * width))
        p_ref[:, cols] = _dot(g_ref[...], w_ref[:, cols], exact)
        dw_ref[:, cols] += _dot(gt_ref[...], x_ref[:, cols], exact)

    def lanes_of(here):
        s = side_ref[here, :]
        co = [s[:, k:k + 1] for k in range(n * n + n + 1)]
        for c in range(0, width, UNIT):
            d = [_block(d_ref, here, i, width, c) for i in range(n)]
            du = _block(du_ref, here, 0, width, c)
            for j in range(n):
                cols = slice(j * width + c, j * width + c + UNIT)
                out = p_ref[here, cols] \
                    + co[n * n + n] * _block(x_ref, here, j, width, c) \
                    + co[n * n + j] * du
                for i in range(n):
                    out = out + co[i * n + j] * d[i]
                dx_ref[here, cols] = out.astype(dx_ref.dtype)

    _over_rows(rows, lanes_of)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------

def _call(kernel, name: str, tokens: int, rows: int, ins, outs, interpret,
          scratch=(), carried: bool = False, **static):
    """One kernel over the token tiles.  ``ins`` / ``outs``: ``(array or
    shape, block)`` pairs; a block is its columns where a tile of tokens
    goes down the rows, ``None`` where the whole array stays, or
    ``("last", rows_of_it)`` where the tokens are the last axis."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape, block):
        if block is None:
            return pl.BlockSpec(shape, lambda t: (0, 0))
        if isinstance(block, tuple):
            return pl.BlockSpec((block[1], rows), lambda t: (0, t))
        return pl.BlockSpec((rows, block), lambda t: (t, 0))

    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(tokens // rows,),
            in_specs=[spec(a.shape, b) for a, b in ins],
            out_specs=[spec(s.shape, b) for s, b in outs],
            scratch_shapes=list(scratch)),
        out_shape=[s for s, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if carried else "parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name)(*(a for a, _ in ins))


def _weights(phi: jax.Array, exact: bool) -> jax.Array:
    """``phi [n C, m]`` as the projection's operand ``[n C, 128]``: its
    three bfloat16 pieces side by side, or itself in float32."""
    w = phi.astype(jnp.float32) if exact else jnp.concatenate(
        pieces(phi), axis=1).astype(jnp.bfloat16)
    return jnp.pad(w, ((0, 0), (0, UNIT - w.shape[1])))


@traced_once("rows", "interpret")
def _lane_project(x, phi, rows: int, interpret: bool):
    f32 = jnp.float32
    tokens, m = x.shape[0], phi.shape[1]
    exact = x.dtype == f32
    out, = _call(
        _project_kernel, "lane_project", tokens, rows,
        [(x, x.shape[1]), (_weights(phi, exact), None)],
        [(jax.ShapeDtypeStruct((tokens, UNIT), f32), UNIT)],
        interpret, exact=exact)
    proj = out[:, :m] if exact else \
        (out[:, 2 * m:3 * m] + out[:, m:2 * m]) + out[:, :m]
    return proj.T, out[:, UNIT - 1]


@traced_once("n", "rows", "interpret")
def _lane_write(x, y, res, post, n: int, rows: int, interpret: bool):
    out, = _call(
        _write_kernel, "lane_write", x.shape[0], rows,
        [(x, x.shape[1]), (y, y.shape[1]), (side(res, post), UNIT)],
        [(jax.ShapeDtypeStruct(x.shape, x.dtype), x.shape[1])],
        interpret, n=n)
    return out


@traced_once("n", "rows", "interpret")
def _lane_write_bwd(x, d, y, post, n: int, rows: int, interpret: bool):
    f32 = jnp.float32
    tokens = x.shape[0]
    dy, dside = _call(
        _write_bwd_kernel, "lane_write_bwd", tokens, rows,
        [(x, x.shape[1]), (d, d.shape[1]), (y, y.shape[1]),
         (side(post), UNIT)],
        [(jax.ShapeDtypeStruct(y.shape, y.dtype), y.shape[1]),
         (jax.ShapeDtypeStruct((tokens, UNIT), f32), UNIT)],
        interpret, n=n)
    return dy, unside(dside, 0, n * n).reshape(n, n, tokens), \
        unside(dside, n * n, n)


@traced_once("n", "rows", "interpret")
def _lane_read_bwd(x, du, n: int, rows: int, interpret: bool):
    tokens = x.shape[0]
    dside, = _call(
        _read_bwd_kernel, "lane_read_bwd", tokens, rows,
        [(x, x.shape[1]), (du, du.shape[1])],
        [(jax.ShapeDtypeStruct((tokens, UNIT), jnp.float32), UNIT)],
        interpret, n=n)
    return unside(dside, 0, n)


@traced_once("n", "rows", "interpret")
def _lane_open_bwd(x, d, du, res, pre, phi, g, dss, n: int, rows: int,
                   interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    tokens, m = x.shape[0], phi.shape[1]
    exact = x.dtype == f32
    if exact:
        first, last, w = g.T, g, phi.astype(f32).T
    else:
        # ``phi g``: hi hi + hi mid + mid hi; ``d phi``: all of ``g``
        bf16 = jnp.bfloat16
        (g_hi, g_mid, g_lo), (p_hi, p_mid, _) = pieces(g), pieces(phi)
        first = jnp.concatenate([g_hi, g_hi, g_mid], axis=0).T.astype(bf16)
        last = jnp.concatenate([g_hi, g_mid, g_lo], axis=0).astype(bf16)
        w = jnp.concatenate([p_hi, p_mid, p_hi], axis=1).T.astype(bf16)
    held = -(-last.shape[0] // SUB) * SUB
    first = jnp.pad(first, ((0, 0), (0, UNIT - first.shape[1])))
    last = jnp.pad(last, ((0, held - last.shape[0]), (0, 0)))
    w = jnp.pad(w, ((0, UNIT - w.shape[0]), (0, 0)))
    dx, dw = _call(
        _open_bwd_kernel, "lane_open_bwd", tokens, rows,
        [(x, x.shape[1]), (d, d.shape[1]), (du, du.shape[1]),
         (side(res, pre, 2.0 * dss[None]), UNIT), (first, UNIT),
         (last, ("last", held)), (w, None)],
        [(jax.ShapeDtypeStruct(x.shape, x.dtype), x.shape[1]),
         (jax.ShapeDtypeStruct((held, x.shape[1]), f32), None)],
        interpret, scratch=[pltpu.VMEM((rows, x.shape[1]), f32)],
        carried=True, n=n, exact=exact)
    dphi = dw[:m] if exact else (dw[2 * m:3 * m] + dw[m:2 * m]) + dw[:m]
    return dx, dphi.T


# ---------------------------------------------------------------------------
# what ``hyper.py`` calls: ``x [M, n C]``, coefficients with tokens last
# ---------------------------------------------------------------------------

def _tiles(x, n: int) -> Tiles:
    return tiles(x.shape[0], n, x.shape[1] // n, x.dtype)


def project(x: jax.Array, phi: jax.Array, n: int, interpret: bool):
    """``(x phi)^T [m, M]`` and ``sum(x^2) [M]`` a token, float32."""
    return _lane_project(x, phi, _tiles(x, n).project, interpret)


def write(x: jax.Array, y: jax.Array, res: jax.Array, post: jax.Array,
          interpret: bool) -> jax.Array:
    """``x'_i = sum_j res[i, j] x_j + post_i y``: ``[M, n C]``."""
    n = post.shape[0]
    return _lane_write(x, y, res, post, n, _tiles(x, n).write, interpret)


def write_bwd(x: jax.Array, d: jax.Array, y: jax.Array, post: jax.Array,
              interpret: bool):
    """``d y [M, C]``, ``d res [n, n, M]``, ``d post [n, M]`` of
    :func:`write` under the cotangent ``d [M, n C]``; what ``d`` is to
    ``x`` is :func:`open_bwd`'s."""
    n = post.shape[0]
    return _lane_write_bwd(x, d, y, post, n, _tiles(x, n).write_bwd,
                           interpret)


def read_bwd(x: jax.Array, du: jax.Array, n: int, interpret: bool):
    """``d pre [n, M]`` of ``u = sum_i pre_i x_i`` under ``du [M, C]``."""
    return _lane_read_bwd(x, du, n, _tiles(x, n).read_bwd, interpret)


def open_bwd(x: jax.Array, d: jax.Array, du: jax.Array, res: jax.Array,
             pre: jax.Array, phi: jax.Array, g: jax.Array, dss: jax.Array,
             interpret: bool):
    """``d x [M, n C]`` whole (through ``res`` from ``d``, through
    ``pre`` from ``du``, through :func:`project` from ``g [m, M]`` and
    ``dss [M]``) and ``d phi [n C, m]`` float32."""
    n = pre.shape[0]
    return _lane_open_bwd(x, d, du, res, pre, phi, g, dss, n,
                          _tiles(x, n).open_bwd, interpret)
