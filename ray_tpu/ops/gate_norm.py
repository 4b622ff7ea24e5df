"""A gated RMS norm in groups of the last axis, forward and backward in
one pass each over the rows.

``y`` and ``z [..., inner]``, ``scale [inner]``, ``inner`` cut into
``groups`` groups of ``width`` lanes::

    g[t, c]   = y[t, c] * silu(z[t, c])
    out[t, c] = g[t, c] * rsqrt(mean_group(g[t] ** 2) + eps) * scale[c]

A state-space mixer ends in this (``models/nemotron_h.py``
``gated_group_norm``: gate first, norm after).  It multiplies nothing on
the array and is bound by bytes: ``y`` and ``z`` read once, ``out``
written once.  Written as ``jnp`` beside a custom call it is float32
arrays ``[T, groups, width]`` in HBM for the group statistics and a
layout copy for each: five times the bytes' bound on the chip (PERF.md,
PR 49).  Two Pallas kernels instead, everything between the read and the
write on a tile in VMEM:

* :func:`_forward_kernel`: a grid step is a tile of ``rows`` rows x
  ``lanes`` lanes (whole groups) in the inputs' dtype, worked ``SUB``
  rows x one group at a time: gate and square in float32, the group's
  registers added elementwise and ONE reduction across lanes a row,
  ``rsqrt``, the scale, ONE rounding on the write.  No float32 array in
  HBM;
* :func:`_backward_kernel`: reads ``y``, ``z`` and the cotangent ``d``,
  forms ``g`` and the group's inverse RMS again on the unit (storing
  them would cost a layout and save nothing that matters)::

      n  = g * inv                       h = d * scale
      dg = inv * (h - n * mean_group(h * n))
      dy = dg * silu(z)                  dz = dg * y * silu'(z)

  ``d scale = sum_t d * n`` is summed over a unit's rows eight sublanes
  at a time into float32 scratch along the grid's row axis and written
  once a block of lanes.  ``dy`` is written over ``d``.

Residuals are ``y``, ``z`` and ``scale`` alone.  The FIRST result of both
calls is 2-d ``[rows, inner]``: the benchmark tells kernel calls apart by
their result shapes and files such a one with the fused norms.

Where the call sees no TPU, a group that is not whole 128-lane registers
or rows that are not whole ``SUB``-row units, :func:`gate_norm` is
:func:`gated_group_norm_jnp`, the plain form under autodiff;
``interpret=True`` forces the kernels through the Pallas interpreter.
Nothing but the shapes and the backend decides.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops._kernel import fold8, kernel_mode, traced_once
from ray_tpu.ops.fused import _rmsnorm_ref

#: rows worked at a time inside a tile
SUB = 64
#: the lanes of a vector register
UNIT = 128
#: the largest tile: rows (of two-byte elements; half for four) x lanes
#: (one group where a group is wider)
ROWS = 512
BLOCK_LANES = 512


class Tiles(NamedTuple):
    rows: int
    lanes: int


def tiles(rows: int, inner: int, groups: int, dtype) -> Optional[Tiles]:
    """The tile the kernels would work ``rows`` rows of ``inner`` lanes
    in ``groups`` groups in, or ``None`` where the shapes are not whole
    tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    if groups < 1 or inner % groups or (inner // groups) % UNIT \
            or rows % SUB or not rows or itemsize not in (2, 4):
        return None
    width = inner // groups
    most = max(ROWS * 2 // itemsize, SUB)
    tall = max(r for r in range(SUB, most + 1, SUB) if rows % r == 0)
    held = max(k for k in range(1, groups + 1) if groups % k == 0
               and (k == 1 or k * width <= BLOCK_LANES))
    return Tiles(tall, held * width)


def gated_group_norm_jnp(y: jax.Array, z: jax.Array, scale: jax.Array,
                         groups: int, eps: float) -> jax.Array:
    """The plain form: the group statistics over a reshaped float32
    ``y * silu(z)``."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(*g.shape[:-1], groups, -1)
    return _rmsnorm_ref(parts, scale.reshape(groups, -1), eps).reshape(
        g.shape).astype(y.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _group_mean(x):
    """``[SUB, width] -> [SUB, 1]``: the group's registers added
    elementwise, then one reduction across lanes a row."""
    width = x.shape[1]
    part = x[:, :UNIT]
    for lane in range(UNIT, width, UNIT):
        part = part + x[:, lane:lane + UNIT]
    return jnp.sum(part, axis=1, keepdims=True) * (1.0 / width)


def _units(rows: int, lanes: int, width: int):
    """The ref slices of a tile's units, group by group: a handful,
    written out one after the other (no loop: the chains of neighbouring
    units then overlap, and a call runs at the pace of its bytes)."""
    return [[(slice(r, r + SUB), slice(c, c + width))
             for r in range(0, rows, SUB)] for c in range(0, lanes, width)]


def _forward_kernel(y_ref, z_ref, scale_ref, out_ref, *, width: int,
                    eps: float):
    f32 = jnp.float32
    for group in _units(*out_ref.shape, width):
        scale = scale_ref[:, group[0][1]].astype(f32)
        for here in group:
            z = z_ref[here].astype(f32)
            g = y_ref[here].astype(f32) * (z * jax.nn.sigmoid(z))
            inv = jax.lax.rsqrt(_group_mean(g * g) + eps)
            out_ref[here] = (g * inv * scale).astype(out_ref.dtype)


def _backward_kernel(y_ref, z_ref, d_ref, scale_ref, dy_ref, dz_ref,
                     dscale_ref, sums_ref, *, width: int, eps: float):
    """``sums_ref [8, lanes]`` float32: ``d scale`` of this block of
    lanes, eight sublanes wide until the last grid step of the block."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _start():
        sums_ref[...] = jnp.zeros(sums_ref.shape, f32)

    for group in _units(*dy_ref.shape, width):
        cols = group[0][1]
        scale = scale_ref[:, cols].astype(f32)
        sums = sums_ref[:, cols]
        for here in group:
            y, z = y_ref[here].astype(f32), z_ref[here].astype(f32)
            d = d_ref[here].astype(f32)
            sig = jax.nn.sigmoid(z)
            gate = z * sig
            g = y * gate
            inv = jax.lax.rsqrt(_group_mean(g * g) + eps)
            n = g * inv
            h = d * scale
            dg = inv * (h - n * _group_mean(h * n))
            dy_ref[here] = (dg * gate).astype(dy_ref.dtype)
            dz_ref[here] = (dg * y * (sig * (1.0 + z * (1.0 - sig)))
                            ).astype(dz_ref.dtype)
            sums = sums + fold8(d * n)
        sums_ref[:, cols] = sums

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        dscale_ref[...] = jnp.sum(sums_ref[...], axis=0, keepdims=True)


def _specs(rows: int, inner: int, tile: Tiles):
    """The grid (blocks of lanes, tiles of rows: the sums of ``d scale``
    run along the last) and the block specs both kernels share."""
    from jax.experimental import pallas as pl

    grid = (inner // tile.lanes, rows // tile.rows)
    return grid, {
        "tile": pl.BlockSpec(tuple(tile), lambda c, t: (t, c)),
        "lane": pl.BlockSpec((1, tile.lanes), lambda c, t: (0, c)),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "arbitrary"))


@traced_once("groups", "eps", "tile", "interpret")
def _forward(y, z, scale, groups: int, eps: float, tile: Tiles,
             interpret: bool):
    from jax.experimental import pallas as pl

    inner = y.shape[-1]
    flat = (y.size // inner, inner)
    grid, specs = _specs(*flat, tile)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, width=inner // groups, eps=eps),
        grid=grid,
        in_specs=[specs["tile"], specs["tile"], specs["lane"]],
        out_specs=specs["tile"],
        out_shape=jax.ShapeDtypeStruct(flat, y.dtype),
        compiler_params=_params(), interpret=interpret,
        name="gate_norm")(y.reshape(flat), z.reshape(flat),
                          scale.reshape(1, inner))
    return out.reshape(y.shape)


@traced_once("groups", "eps", "tile", "interpret")
def _backward(y, z, d, scale, groups: int, eps: float, tile: Tiles,
              interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    inner = y.shape[-1]
    flat = (y.size // inner, inner)
    grid, specs = _specs(*flat, tile)
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_backward_kernel, width=inner // groups, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid,
            in_specs=[specs["tile"]] * 3 + [specs["lane"]],
            out_specs=[specs["tile"], specs["tile"], specs["lane"]],
            scratch_shapes=[pltpu.VMEM((8, tile.lanes), f32)]),
        out_shape=[jax.ShapeDtypeStruct(flat, y.dtype),
                   jax.ShapeDtypeStruct(flat, z.dtype),
                   jax.ShapeDtypeStruct((1, inner), f32)],
        # ``dy`` in the cotangent's bytes: it is dead after this call
        input_output_aliases={2: 0},
        compiler_params=_params(), interpret=interpret,
        name="gate_norm_bwd")(y.reshape(flat), z.reshape(flat),
                              d.reshape(flat), scale.reshape(1, inner))
    return dy.reshape(y.shape), dz.reshape(z.shape), dscale.reshape(inner)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gate_norm(y, z, scale, groups: int, eps: float, tile: Tiles,
               interpret: bool):
    return _forward(y, z, scale, groups, eps, tile, interpret)


def _gate_norm_fwd(y, z, scale, groups, eps, tile, interpret):
    return _forward(y, z, scale, groups, eps, tile, interpret), (y, z, scale)


def _gate_norm_bwd(groups, eps, tile, interpret, res, d):
    y, z, scale = res
    dy, dz, dscale = _backward(y, z, d, scale, groups, eps, tile, interpret)
    return dy, dz, dscale.astype(scale.dtype)


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def gate_norm(y: jax.Array, z: jax.Array, scale: jax.Array, groups: int,
              eps: float, *, interpret: Optional[bool] = None) -> jax.Array:
    """``RMSNorm(y * silu(z))`` in ``groups`` groups of the last axis
    under one learned ``scale [inner]``, in ``y``'s dtype: the float32
    result rounded once.  Gate, statistics, scale and the gradient of
    ``scale`` are float32 whatever ``y`` and ``z`` are."""
    inner = y.shape[-1]
    tile = tiles(y.size // inner, inner, groups, y.dtype) \
        if y.shape == z.shape and y.dtype == z.dtype else None
    interpret = kernel_mode(interpret)
    if tile is None or interpret is None:
        return gated_group_norm_jnp(y, z, scale, groups, eps)
    return _gate_norm(y, z, scale, groups, float(eps), tile, interpret)
