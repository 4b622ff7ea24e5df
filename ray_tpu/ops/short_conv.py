"""A short causal depthwise convolution with bias and ``silu``, forward
and backward in one pass each over the channels.

``u [B, T, C]``, ``w [taps, C]``, ``bias [C]``, ``u`` zero before the
sequence::

    pre[t, c] = bias[c] + sum_j w[j, c] * u[t - (taps - 1) + j, c]
    out[t, c] = silu(pre[t, c])

A state-space mixer runs this over its scan's inputs (``models/
nemotron_h.py`` ``causal_conv``).  It multiplies nothing on the array
and is bound by bytes: ``u`` read once, ``out`` written once.  Written
as ``jnp`` it is a padded float32 copy of ``u``, ``taps`` slices shifted
by a row each (no shift is a whole sublane tile), ``silu`` as a pass,
and backward ``taps`` padded scatter-adds and ``taps`` reductions over
the sequence: ten times the bytes' bound on the chip (PERF.md, PR 45).
Two Pallas kernels instead, everything between the read and the write
on a tile in VMEM:

* :func:`_forward_kernel`: a grid step is a tile of ``rows`` positions x
  ``lanes`` channels in ``u``'s dtype, and beside it the ``HALO`` rows
  BEFORE the tile as a second small block of the same array (zeros
  before the sequence: the first tile masks what the clamped index
  read).  The tile is worked ``SUB`` rows x ``UNIT`` lanes at a time, so
  that a unit's chain stays in registers: the unit and the rows before
  it in float32, a shift by ``s`` rows as a rotation along the sublanes
  cut at the halo (what wraps around lands in the rows that are cut),
  taps, bias and ``silu`` in float32, ONE rounding on the write.  No
  padded copy of ``u`` and no float32 array in HBM;
* :func:`_backward_kernel`: reads ``u`` and the cotangent ``g``, forms
  ``pre`` again on the unit (storing it would be a float32 array the
  size of two ``u``), ``d pre = g * silu'(pre)``, and writes ``d u[t] =
  sum_j w[j] * d pre[t + (taps - 1) - j]``: the halo is now the rows
  AFTER the unit, for which ``pre`` is formed too (``AFTER`` rows, from
  the next tile's head as two more small blocks; zeros after the
  sequence).  ``d w`` and ``d bias`` are summed over a unit's rows eight
  sublanes at a time into float32 scratch along the batch and time axes
  of the grid and written once a block of channels.

Residuals are ``u``, ``w`` and ``bias`` alone.  The FIRST result of both
calls is 2-d ``[B T, C]``: the benchmark tells kernel calls apart by
their result shapes and files such a one with the fused norms.

Where the call sees no TPU, channels that are not whole 128-lane
registers or a sequence that is not whole ``SUB``-row units,
:func:`short_conv` is :func:`short_conv_jnp`, the plain form under
autodiff; ``interpret=True`` forces the kernels through the Pallas
interpreter.  Nothing but the shapes and the backend decides.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops._kernel import fold8, kernel_mode, traced_once

#: rows of the small blocks beside a tile: one packed bfloat16 register
HALO = 16
#: rows after a unit whose ``pre`` the backward forms again: one float32
#: register, and at least ``taps - 1``
AFTER = 8
#: rows x lanes worked at a time inside a tile (the lanes of a vector
#: register, or whole registers)
SUB = 64
UNIT = 128
#: the largest tile: rows (of two-byte elements; half for four) x lanes
ROWS = 1024
BLOCK_LANES = 512


class Tiles(NamedTuple):
    rows: int
    lanes: int


def tiles(u: jax.Array, taps: int) -> Optional[Tiles]:
    """The tile the kernels would work ``u [B, T, C]`` in, or ``None``
    where its shapes are not whole tiles."""
    seq, channels = u.shape[1:]
    if channels % UNIT or seq % SUB or not 1 <= taps - 1 <= AFTER \
            or u.dtype.itemsize not in (2, 4):
        return None
    most = ROWS * 2 // u.dtype.itemsize
    rows = max(r for r in range(SUB, max(most, SUB) + 1, SUB)
               if seq % r == 0)
    lanes = max(n for n in range(UNIT, BLOCK_LANES + 1, UNIT)
                if channels % n == 0)
    return Tiles(rows, lanes)


def short_conv_jnp(u: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """The plain form: shifted products over a padded float32 copy."""
    taps, seq = w.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(taps):
        out = out + w[j].astype(jnp.float32) * padded[:, j:j + seq]
    return jax.nn.silu(out).astype(u.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _shifted(ext, taps: int, rows: int):
    """``[s]``: ``ext`` moved down ``s`` rows and cut to ``rows`` rows
    behind the halo: row ``r`` holds ``ext[HALO + r - s]``."""
    from jax.experimental.pallas import tpu as pltpu

    return [ext[HALO:HALO + rows] if s == 0 else
            pltpu.roll(ext, s, 0)[HALO:HALO + rows] for s in range(taps)]


def _pre(shifted, w, bias):
    """``bias + sum_j w[j] u[t - (taps - 1) + j]``, float32."""
    taps = len(shifted)
    pre = bias + w[taps - 1:taps] * shifted[0]
    for s in range(1, taps):
        pre = pre + w[taps - 1 - s:taps - s] * shifted[s]
    return pre


def _forward_kernel(u_ref, before_ref, w_ref, bias_ref, out_ref, *,
                    taps: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    rows, lanes = out_ref.shape
    first = pl.program_id(2) == 0
    for lane in range(lanes // UNIT):
        cols = slice(lane * UNIT, (lane + 1) * UNIT)
        w, bias = w_ref[:, cols].astype(f32), bias_ref[:, cols].astype(f32)
        before = jnp.where(first, jnp.zeros_like(before_ref[:, cols]),
                           before_ref[:, cols])

        def unit(i, _, cols=cols, w=w, bias=bias, before=before):
            base = pl.multiple_of(i * SUB, SUB)
            lo = jnp.where(i == 0, before, u_ref[pl.ds(pl.multiple_of(
                jnp.maximum(base - HALO, 0), HALO), HALO), cols])
            ext = jnp.concatenate([lo, u_ref[pl.ds(base, SUB), cols]],
                                  axis=0).astype(f32)
            pre = _pre(_shifted(ext, taps, SUB), w, bias)
            out_ref[pl.ds(base, SUB), cols] = (
                pre * jax.nn.sigmoid(pre)).astype(out_ref.dtype)
            return 0

        jax.lax.fori_loop(0, rows // SUB, unit, 0)


def _backward_kernel(u_ref, g_ref, before_ref, after_ref, g_after_ref,
                     w_ref, bias_ref, du_ref, dw_ref, dbias_ref, sums_ref, *,
                     taps: int):
    """``sums_ref [taps + 1, 8, lanes]`` float32: ``d w`` and ``d bias``
    of this block of channels, eight sublanes wide until the last grid
    step of the block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, lanes = du_ref.shape
    units = rows // SUB
    b, t = pl.program_id(1), pl.program_id(2)
    first = t == 0
    last = t == pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(b == 0, first))
    def _start():
        sums_ref[...] = jnp.zeros(sums_ref.shape, f32)

    for lane in range(lanes // UNIT):
        cols = slice(lane * UNIT, (lane + 1) * UNIT)
        w, bias = w_ref[:, cols].astype(f32), bias_ref[:, cols].astype(f32)
        zero = jnp.zeros_like(before_ref[:, cols])
        before = jnp.where(first, zero, before_ref[:, cols])
        after = jnp.where(last, zero, after_ref[:, cols])
        g_after = jnp.where(last, jnp.zeros_like(g_after_ref[:, cols]),
                            g_after_ref[:, cols])

        def unit(i, sums, cols=cols, w=w, bias=bias, before=before,
                 after=after, g_after=g_after):
            base = pl.multiple_of(i * SUB, SUB)
            behind = pl.multiple_of(jnp.maximum(base - HALO, 0), HALO)
            ahead = pl.multiple_of(jnp.minimum(base + SUB, rows - HALO),
                                   HALO)
            lo = jnp.where(i == 0, before, u_ref[pl.ds(behind, HALO), cols])
            hi = jnp.where(i == units - 1, after,
                           u_ref[pl.ds(ahead, HALO), cols])
            g_hi = jnp.where(i == units - 1, g_after,
                             g_ref[pl.ds(ahead, HALO), cols])
            ext = jnp.concatenate([lo, u_ref[pl.ds(base, SUB), cols], hi],
                                  axis=0).astype(f32)
            g = jnp.concatenate([g_ref[pl.ds(base, SUB), cols], g_hi],
                                axis=0).astype(f32)[:SUB + AFTER]
            shifted = _shifted(ext, taps, SUB + AFTER)
            pre = _pre(shifted, w, bias)
            sig = jax.nn.sigmoid(pre)
            dpre = g * sig * (1.0 + pre * (1.0 - sig))
            # d u[t] = sum_k w[taps - 1 - k] d pre[t + k]
            du = w[taps - 1:taps] * dpre[:SUB]
            for k in range(1, taps):
                du = du + w[taps - 1 - k:taps - k] * pltpu.roll(
                    dpre, SUB + AFTER - k, 0)[:SUB]
            du_ref[pl.ds(base, SUB), cols] = du.astype(du_ref.dtype)
            own = dpre[:SUB]
            return tuple(
                [sums[j] + fold8(own * shifted[taps - 1 - j][:SUB])
                 for j in range(taps)] + [sums[taps] + fold8(own)])

        zeros = tuple(jnp.zeros((8, UNIT), f32) for _ in range(taps + 1))
        for j, s in enumerate(jax.lax.fori_loop(0, units, unit, zeros)):
            sums_ref[j, :, cols] += s

    @pl.when(jnp.logical_and(b == pl.num_programs(1) - 1, last))
    def _finish():
        for j in range(taps):
            dw_ref[j:j + 1, :] = jnp.sum(sums_ref[j], axis=0, keepdims=True)
        dbias_ref[...] = jnp.sum(sums_ref[taps], axis=0, keepdims=True)


def _specs(u, tile: Tiles):
    """The grid (blocks of channels, batch, tiles of rows: the sums of
    ``d w`` run along the last two) and the block specs both kernels
    share."""
    from jax.experimental import pallas as pl

    batch, seq, channels = u.shape
    rows, lanes = tile
    n, per = seq // rows, rows // HALO
    grid = (channels // lanes, batch, n)
    return grid, {
        # [B, T, C]: a tile, the rows before it, the rows after it
        "tile": pl.BlockSpec((None, rows, lanes), lambda c, b, t: (b, t, c)),
        "before": pl.BlockSpec(
            (None, HALO, lanes),
            lambda c, b, t: (b, jnp.maximum(t * per - 1, 0), c)),
        "after": pl.BlockSpec(
            (None, HALO, lanes),
            lambda c, b, t: (b, jnp.minimum((t + 1) * per,
                                            seq // HALO - 1), c)),
        # [B T, C]: 2-d results (see the module's text)
        "rows": pl.BlockSpec((rows, lanes), lambda c, b, t: (b * n + t, c)),
        # [taps or 1, C]
        "channel": lambda k: pl.BlockSpec((k, lanes),
                                          lambda c, b, t: (0, c)),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "arbitrary", "arbitrary"))


@traced_once("tile", "interpret")
def _forward(u, w, bias, tile: Tiles, interpret: bool):
    from jax.experimental import pallas as pl

    batch, seq, channels = u.shape
    taps = w.shape[0]
    grid, specs = _specs(u, tile)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, taps=taps), grid=grid,
        in_specs=[specs["tile"], specs["before"], specs["channel"](taps),
                  specs["channel"](1)],
        out_specs=specs["rows"],
        out_shape=jax.ShapeDtypeStruct((batch * seq, channels), u.dtype),
        compiler_params=_params(), interpret=interpret,
        name="short_conv")(u, u, w, bias.reshape(1, channels))
    return out.reshape(u.shape)


@traced_once("tile", "interpret")
def _backward(u, g, w, bias, tile: Tiles, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    batch, seq, channels = u.shape
    taps = w.shape[0]
    grid, specs = _specs(u, tile)
    du, dw, dbias = pl.pallas_call(
        functools.partial(_backward_kernel, taps=taps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid,
            in_specs=[specs["tile"], specs["tile"], specs["before"],
                      specs["after"], specs["after"],
                      specs["channel"](taps), specs["channel"](1)],
            out_specs=[specs["rows"], specs["channel"](taps),
                       specs["channel"](1)],
            scratch_shapes=[pltpu.VMEM((taps + 1, 8, tile.lanes), f32)]),
        out_shape=[jax.ShapeDtypeStruct((batch * seq, channels), u.dtype),
                   jax.ShapeDtypeStruct((taps, channels), f32),
                   jax.ShapeDtypeStruct((1, channels), f32)],
        compiler_params=_params(), interpret=interpret,
        name="short_conv_bwd")(u, g, u, u, g, w, bias.reshape(1, channels))
    return du.reshape(u.shape), dw, dbias.reshape(channels)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _short_conv(u, w, bias, tile: Tiles, interpret: bool):
    return _forward(u, w, bias, tile, interpret)


def _short_conv_fwd(u, w, bias, tile, interpret):
    return _forward(u, w, bias, tile, interpret), (u, w, bias)


def _short_conv_bwd(tile, interpret, res, g):
    u, w, bias = res
    du, dw, dbias = _backward(u, g, w, bias, tile, interpret)
    return du, dw.astype(w.dtype), dbias.astype(bias.dtype)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(u: jax.Array, w: jax.Array,
               bias: Optional[jax.Array] = None, *,
               interpret: Optional[bool] = None) -> jax.Array:
    """``silu(bias + sum_j w[j] * u[t - (taps - 1) + j])`` over ``u [B,
    T, C]`` in ``u``'s dtype: the float32 sum rounded once.  ``w [taps,
    C]`` and ``bias [C]``; taps, bias, ``silu`` and the gradients of
    ``w`` and ``bias`` are float32 whatever ``u`` is.  No ``bias``
    (``models/qwen3_next.py``): a constant zero that is no parameter,
    through the same kernels."""
    if bias is None:
        bias = jnp.zeros((u.shape[-1],), jnp.float32)
    tile = tiles(u, w.shape[0])
    interpret = kernel_mode(interpret)
    if tile is None or interpret is None:
        return short_conv_jnp(u, w, bias)
    return _short_conv(u, w, bias, tile, interpret)
