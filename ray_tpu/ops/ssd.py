"""Chunked scan of a selective state space (Mamba-2's SSD), forward and
backward.

One head, state ``S`` in ``R^{P x N}``, ``S_0 = 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T        y_t = S_t C_t + D xs_t

``H`` heads of ``P``, ``G`` groups of ``N``: head ``h`` reads ``B`` and
``C`` of group ``h // (H / G)``.  The time-step recurrence is ``T``
sequential steps; :func:`ssd` computes the same thing in chunks of ``Q``
positions.  With ``a_t = dt_t A`` and ``cum`` its running sum INSIDE a
chunk (float32, whatever the inputs)::

    y_t  = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s xs_s     (within)
         + exp(cum_t) S_in C_t                                       (read-out)
    S_c  = sum_s exp(cum_Q - cum_s) dt_s xs_s B_s^T                  (closing)
    S_in' = exp(cum_Q) S_in + S_c                                    (carry)

Every decay is the exponential of a DIFFERENCE of ``cum`` (never a ratio
of two exponentials: ``exp(-cum_s)`` overflows where ``exp(cum_t -
cum_s)`` is small).  Two Pallas kernels, forward and backward, each ONE
pass over the chunks with the carry in VMEM:

* :func:`_scan_kernel`: a grid step is one chunk of one group's heads
  (a whole group at 8 heads beat 4 and 2 on the chip: PERF.md, PR 35).
  It computes ``C B^T`` once for those heads, each
  head's ``Q x Q`` decayed scores ``(C B^T) * exp(cum_t - cum_s) * dt_s``
  and their product with ``xs``, the read-out of the incoming state, and
  the chunk's closing state, and carries ``S_in' = exp(cum_Q) S_in + S_c``
  in float32 scratch to the next grid step along the chunk axis (``carry``
  = ``kernel`` in the plan span).  The ``Q x Q`` matrices live in VMEM
  only; the state that ENTERED each chunk is written out once, float32,
  for the backward pass;
* :func:`_scan_bwd_kernel`: the same grid walked LAST CHUNK FIRST.  It
  recomputes the ``Q x Q`` matrices, carries the cotangent of the state
  that leaves a chunk in scratch (``dS_in = exp(cum_Q) dS_out + sum_t
  exp(cum_t) dy_t C_t^T``), and gives ``d xs``, ``d B``, ``d C`` (summed
  over a group's heads in the kernel) and the cotangents of ``cum`` and
  ``dt`` (a head's vectors; XLA folds them into ``d dt`` and ``d A``).

The first version of this file kept the carry in XLA (a kernel for the
chunk states, a ``lax.scan`` over them, a kernel for the rest): on the
chip the two ``lax.scan`` took 1.5 of a sequence's 5.35 ms forward and
backward (PERF.md, PR 35), so the carry moved into the kernels.

``B`` and ``C`` are read through the block index from their group: no
copy to ``H`` heads exists in HBM.  Heads narrower than the 128 lanes are
worked ``128 // P`` to a slab: a head's product with ``xs`` takes the slab
under that head's lane mask (the array is 128 wide whatever ``P`` is),
and the products with the state take the whole slab at once.

Off the TPU (tests, the CPU rehearsal) :func:`ssd` runs
:func:`ssd_einsum`, the plain ``jnp.einsum`` formulation of the same
chunked algebra under autodiff, unless ``interpret=True`` forces the
kernels through the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.ops._kernel import kernel_mode

#: lanes of a vector register: heads narrower than this share a slab
LANES = 128


class Plan(NamedTuple):
    """What was compiled, for the ``ops:ssd.plan`` span."""
    heads: int
    head_dim: int
    groups: int
    state: int
    chunk: int
    seq: int

    @property
    def heads_a_step(self) -> int:
        """A grid step works one group's heads (``C B^T`` once for them)."""
        return self.heads // self.groups

    @property
    def pack(self) -> int:
        """Heads to a 128-lane slab."""
        pack = max(1, LANES // self.head_dim)
        while self.heads_a_step % pack:
            pack //= 2
        return pack

    def span_args(self, kernels: bool) -> dict:
        """``carry``: where the state passes from chunk to chunk: in the
        kernels' scratch, or in XLA's ``lax.scan`` (the einsum
        formulation, off the TPU)."""
        return {**self._asdict(), "chunks": self.seq // self.chunk,
                "heads_a_step": self.heads_a_step,
                "carry": "kernel" if kernels else "xla"}


def _plan(xs, B, chunk) -> Plan:
    _, seq, heads, dim = xs.shape
    groups, state = B.shape[2:]
    if seq % chunk:
        raise ValueError(
            f"ssd: a sequence of {seq} positions is not whole chunks of "
            f"{chunk}; pad it to a multiple of the chunk")
    if heads % groups:
        raise ValueError(f"ssd: {heads} heads do not split over {groups} "
                         f"groups")
    return Plan(heads, dim, groups, state, chunk, seq)


# ---------------------------------------------------------------------------
# the time-step recurrence and the einsum formulation (plain jnp)
# ---------------------------------------------------------------------------

def ssd_recurrence(xs, dt, A, B, C, D):
    """The definition, step by step in float32: ``T`` sequential steps.
    What every other path is tested against; not a training path."""
    f32 = jnp.float32
    heads, groups = xs.shape[2], B.shape[2]
    rep = heads // groups

    def step(S, inp):
        x, d, b, c = inp                       # [B,H,P] [B,H] [B,G,N] x2
        b, c = jnp.repeat(b, rep, 1), jnp.repeat(c, rep, 1)
        S = jnp.exp(d * A)[..., None, None] * S + \
            (d[..., None] * x)[..., None] * b[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, c)

    first = jnp.zeros((xs.shape[0], heads, xs.shape[3], B.shape[3]), f32)
    swap = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)  # noqa: E731
    _, y = jax.lax.scan(step, first, (swap(xs), swap(dt), swap(B), swap(C)))
    return jnp.moveaxis(y, 0, 1) + D.astype(f32)[:, None] * xs.astype(f32)


def _chunk_cum(dt, A, chunk):
    """``cum [B, T, H]`` float32: the running sum of ``dt A`` inside each
    chunk."""
    b, t, h = dt.shape
    a = (dt.astype(jnp.float32) * A.astype(jnp.float32)).reshape(
        b, t // chunk, chunk, h)
    return jnp.cumsum(a, axis=2).reshape(b, t, h)


def _carry(states, decay, reverse=False):
    """``out[c]`` = the state that ENTERS chunk ``c`` (leaves it, seen
    from the end, with ``reverse``): ``S' = decay_c S + states_c``, float32.
    ``states [B, nc, ...]``, ``decay [B, nc, H]`` broadcast from the left
    of the trailing axes."""
    pad = (...,) + (None,) * (states.ndim - 3)

    def step(S, inp):
        s_c, f_c = inp
        return f_c[pad] * S + s_c, S

    swap = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    _, entering = jax.lax.scan(step, jnp.zeros_like(states[:, 0]),
                               (swap(states), swap(decay)), reverse=reverse)
    return swap(entering)


def ssd_einsum(xs, dt, A, B, C, D, *, chunk: int = 128):
    """The chunked algebra as plain ``jnp.einsum``, for autodiff: the
    ``Q x Q`` decays of every head are an array in HBM here.  The
    fallback off the TPU, and the number the kernels have to beat."""
    f32 = jnp.float32
    b, t, h, p = xs.shape
    g, n = B.shape[2:]
    nc, r = t // chunk, h // g
    cum = _chunk_cum(dt, A, chunk).reshape(b, nc, chunk, g, r)
    xdt = (xs * dt.astype(xs.dtype)[..., None]).reshape(b, nc, chunk, g, r, p)
    Bc, Cc = B.reshape(b, nc, chunk, g, n), C.reshape(b, nc, chunk, g, n)
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = cum[:, :, :, None] - cum[:, :, None, :]          # [b,c,t,s,g,r]
    decay = jnp.exp(jnp.where(keep[None, None, :, :, None, None], diff,
                              -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", Cc, Bc,
                    preferred_element_type=f32)
    scores = (cb[..., None] * decay).astype(xs.dtype)
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", scores, xdt,
                   preferred_element_type=f32)
    last = cum[:, :, -1]                                    # [b,c,g,r]
    to_end = jnp.exp(last[:, :, None] - cum).astype(xs.dtype)
    states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bc,
                        xdt * to_end[..., None], preferred_element_type=f32)
    entering = _carry(states.reshape(b, nc, h, p, n),
                      jnp.exp(last).reshape(b, nc, h))
    read = jnp.einsum("bctgn,bcgrpn->bctgrp", Cc,
                      entering.reshape(b, nc, g, r, p, n).astype(xs.dtype),
                      preferred_element_type=f32)
    y = y + jnp.exp(cum)[..., None] * read
    y = y.reshape(b, t, h, p) + D.astype(f32)[:, None] * xs.astype(f32)
    return y.astype(xs.dtype)


# ---------------------------------------------------------------------------
# layouts of the small per-head arrays
# ---------------------------------------------------------------------------

def _rows(v, plan: Plan):
    """``[B, T, H]`` -> ``[B, nc, G, H/G, Q]``: a head's chunk along the
    lanes."""
    b = v.shape[0]
    v = v.reshape(b, plan.seq // plan.chunk, plan.chunk, plan.groups,
                  plan.heads_a_step)
    return v.transpose(0, 1, 3, 4, 2)


def _from_rows(v, plan: Plan):
    return v.transpose(0, 1, 4, 2, 3).reshape(v.shape[0], plan.seq,
                                              plan.heads)


def _cols(rows):
    """``[.., step, Q]`` -> ``[.., Q, step]``: a head's chunk down the
    sublanes."""
    return jnp.swapaxes(rows, -1, -2)


def _cum_rows(dt_rows, A, plan: Plan):
    """The running sum of ``dt A`` inside each chunk, in the rows' layout,
    as a product with a triangle of ones (float32 to the last bit that
    six bfloat16 passes give): XLA's ``cumsum`` over 128 positions of an
    array whose minor axes are 8 heads took 1.9 ms a call on the chip,
    2.7 times the scan's forward kernel (PERF.md, PR 35)."""
    a = dt_rows * A.astype(jnp.float32).reshape(
        plan.groups, plan.heads_a_step)[..., None]
    s = jax.lax.broadcasted_iota(jnp.int32, (plan.chunk, plan.chunk), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (plan.chunk, plan.chunk), 1)
    return jnp.einsum("bcnhs,st->bcnht", a, (s <= t).astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _lane_head(rows: int, width: int, dim: int):
    """``[rows, width]`` int32: which head of the slab a lane belongs to."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // dim


def _spread(cols, first: int, pack: int, lane_head):
    """A slab-wide ``[Q, pack * P]`` array from the ``[Q, 1]`` columns of
    the slab's heads."""
    out = cols[:, first:first + 1]
    for k in range(1, pack):
        out = jnp.where(lane_head == k, cols[:, first + k:first + k + 1], out)
    return out


def _dot(a, b, contract):
    """A product on the array with float32 accumulation; float32 operands
    (tests, the benchmark's scan probe) are multiplied as float32, not
    rounded to bfloat16 first."""
    exact = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=exact,
                               preferred_element_type=jnp.float32)


def _nt(a, b):
    """``a @ b^T``."""
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):
    """``a^T @ b``."""
    return _dot(a, b, ((0,), (0,)))


def _nn(a, b):
    return _dot(a, b, ((1,), (0,)))


def _total(a):
    """The sum of a 2-D array as ``[1, 1]``."""
    return jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0, keepdims=True)


def _decays(cum_c, cum_r, h, chunk):
    """``exp(cum_t - cum_s)`` for ``s <= t``, 0 above the diagonal: the
    difference is masked BEFORE the exponential (above the diagonal it is
    positive and may overflow)."""
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = cum_c[:, h:h + 1] - cum_r[h:h + 1, :]
    return jnp.exp(jnp.where(t >= s, diff, -jnp.inf))


class _Slab(NamedTuple):
    """What a slab of ``pack`` heads needs of the per-head vectors,
    spread over its ``pack * P`` lanes (``[Q, pack * P]``) or down the
    rows of its state (``[pack * P, 1]``)."""
    enter: jax.Array     # exp(cum_t): what the incoming state decays by
    leave: jax.Array     # exp(cum_Q - cum_s): what a step adds to S_c
    dts: jax.Array       # dt_s
    whole: jax.Array     # exp(cum_Q) down the state's rows


def _slab(cum_c, dt_c, s: int, pack: int, dim: int, lane_head) -> _Slab:
    last = cum_c[-1:, :]
    row_head = jax.lax.broadcasted_iota(jnp.int32, (pack * dim, 1), 0) // dim
    whole = jnp.exp(last[:, s * pack:s * pack + 1])
    for k in range(1, pack):
        whole = jnp.where(row_head == k,
                          jnp.exp(last[:, s * pack + k:s * pack + k + 1]),
                          whole)
    return _Slab(jnp.exp(_spread(cum_c, s * pack, pack, lane_head)),
                 jnp.exp(_spread(last - cum_c, s * pack, pack, lane_head)),
                 _spread(dt_c, s * pack, pack, lane_head),
                 jnp.broadcast_to(whole, (pack * dim, 1)))


def _scan_kernel(x_ref, b_ref, c_ref, cum_c_ref, cum_r_ref, dt_c_ref,
                 dt_r_ref, y_ref, entering_ref, state_ref, *, plan: Plan):
    """One chunk of one group's heads: ``y``, the state
    that entered the chunk (kept for the backward pass), and the carry:
    the state in ``state_ref`` leaves the chunk for the next grid step
    along the chunk axis."""
    from jax.experimental import pallas as pl

    dim, chunk, pack = plan.head_dim, plan.chunk, plan.pack
    width, slabs = pack * dim, plan.heads_a_step // pack
    lane_head = _lane_head(chunk, width, dim)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    cum_c, cum_r = cum_c_ref[...], cum_r_ref[...]
    dt_c, dt_r = dt_c_ref[...], dt_r_ref[...]
    b, c = b_ref[...], c_ref[...]
    cb = _nt(c, b)                       # [Q, Q], once for the group
    for s in range(slabs):
        x = x_ref[:, s * width:(s + 1) * width]
        of = _slab(cum_c, dt_c, s, pack, dim, lane_head)
        state = state_ref[s]
        entering_ref[s] = state
        # the incoming state, read out for the slab's heads at once
        y = _nt(c, state.astype(c.dtype)) * of.enter
        for k in range(pack):
            h = s * pack + k
            scores = cb * _decays(cum_c, cum_r, h, chunk) * dt_r[h:h + 1, :]
            mine = x if pack == 1 else jnp.where(lane_head == k, x, 0)
            y = y + _nn(scores.astype(x.dtype), mine)
        y_ref[:, s * width:(s + 1) * width] = y.astype(y_ref.dtype)
        closing = _tn((x.astype(jnp.float32) * of.leave * of.dts).astype(
            x.dtype), b)
        state_ref[s] = of.whole * state + closing


def _scan_bwd_kernel(x_ref, dy_ref, b_ref, c_ref, s_ref, cum_c_ref,
                     cum_r_ref, dt_c_ref, dt_r_ref,
                     dx_ref, db_ref, dc_ref, dcum_c_ref, dcum_r_ref,
                     ddt_c_ref, ddt_r_ref, dstate_ref, *, plan: Plan):
    """The same chunk, walked LAST CHUNK FIRST: ``dstate_ref`` carries the
    cotangent of the state that leaves the chunk; the ``Q x Q`` matrices
    are recomputed."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    dim, chunk, pack = plan.head_dim, plan.chunk, plan.pack
    width, slabs = pack * dim, plan.heads_a_step // pack
    lane_head = _lane_head(chunk, width, dim)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate_ref[...] = jnp.zeros(dstate_ref.shape, f32)

    cum_c, cum_r = cum_c_ref[...], cum_r_ref[...]
    dt_c, dt_r = dt_c_ref[...], dt_r_ref[...]
    b, c = b_ref[...], c_ref[...]
    cd = b.dtype
    cb = _nt(c, b)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) \
        == chunk - 1

    dcb = jnp.zeros((chunk, chunk), f32)   # d(C B^T), over the group's heads
    db = jnp.zeros(b.shape, f32)
    dc = jnp.zeros(c.shape, f32)
    for s in range(slabs):
        cols = slice(s * width, (s + 1) * width)
        x, dy = x_ref[:, cols], dy_ref[:, cols]
        of = _slab(cum_c, dt_c, s, pack, dim, lane_head)
        state, dstate = s_ref[s], dstate_ref[s]           # [pack * P, N]
        # read-out: y_t += exp(cum_t) C_t S_in
        dy_in = (dy.astype(f32) * of.enter).astype(cd)
        dc = dc + _nn(dy_in, state.astype(cd))               # [Q, N]
        # closing state: S_c = sum_s leave_s dt_s xs_s B_s^T
        reach = _nt(b, dstate.astype(cd)) * of.leave         # [Q, pack*P]
        db = db + _nn((x.astype(f32) * of.leave * of.dts).astype(cd),
                      dstate.astype(cd))
        dx = reach * of.dts
        x_reach = x.astype(f32) * reach
        dy_read = dy.astype(f32) * of.enter * _nt(c, state.astype(cd))
        for k in range(pack):
            h = s * pack + k
            decay = _decays(cum_c, cum_r, h, chunk)
            mine = (lambda a: a) if pack == 1 else \
                (lambda a, k=k: jnp.where(lane_head == k, a, 0))
            # y_t += sum_s scores_ts xs_s, scores = cb * decay * dt_s
            through = _nt(mine(dy), x) * decay               # [Q, Q]
            scores = cb * decay * dt_r[h:h + 1, :]
            dcb = dcb + through * dt_r[h:h + 1, :]
            dx = dx + _tn(scores.astype(cd), mine(dy))
            moved = through * cb                 # d scores * scores / dt_s
            by_col = jnp.sum(moved, axis=0, keepdims=True)   # [1, Q]
            ddt_r_ref[h:h + 1, :] = by_col
            dcum_r_ref[h:h + 1, :] = -by_col * dt_r[h:h + 1, :]
            by_row = jnp.sum(moved * dt_r[h:h + 1, :], axis=1,
                             keepdims=True)                  # [Q, 1]
            # the state's parts, a head's lanes at a time
            v = jnp.sum(mine(x_reach), axis=1, keepdims=True)
            read = jnp.sum(mine(dy_read), axis=1, keepdims=True)
            dt_h = dt_c[:, h:h + 1]
            p0 = k * dim
            at_end = jnp.exp(cum_c[chunk - 1:chunk, h:h + 1]) * _total(
                dstate[p0:p0 + dim] * state[p0:p0 + dim]) + \
                _total(v * dt_h)
            ddt_c_ref[:, h:h + 1] = v
            dcum_c_ref[:, h:h + 1] = by_row + read - v * dt_h + \
                jnp.where(last_row, at_end, 0.0)
        dx_ref[:, cols] = dx.astype(dx_ref.dtype)
        # the carry's cotangent, for the chunk before this one
        dstate_ref[s] = of.whole * dstate + _tn(dy_in, c)
    dc_ref[...] = dc + _nn(dcb.astype(cd), b)
    db_ref[...] = db + _tn(dcb.astype(cd), c)


def _specs(plan: Plan, batch: int, reverse: bool):
    """The grid and the block specs the two kernels share: a step is one
    group's heads in one chunk; the chunk axis runs first chunk to last,
    or last to first with ``reverse``."""
    from jax.experimental import pallas as pl

    step, dim, chunk, n = (plan.heads_a_step, plan.head_dim, plan.chunk,
                           plan.state)
    chunks = plan.seq // chunk
    grid = (batch, plan.groups, chunks)
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    return grid, {
        # [B, nc, Q, H * P]
        "heads": pl.BlockSpec((None, None, chunk, step * dim),
                              lambda b, g, c: (b, at(c), 0, g)),
        # [B, nc, Q, G * N], through the block index from the group
        "group": pl.BlockSpec((None, None, chunk, n),
                              lambda b, g, c: (b, at(c), 0, g)),
        # [B, nc, H / pack, pack * P, N]
        "state": pl.BlockSpec(
            (None, None, step // plan.pack, plan.pack * dim, n),
            lambda b, g, c: (b, at(c), g, 0, 0)),
        # [B, nc, G, H / G, Q] and [.., Q, H / G]
        "rows": pl.BlockSpec((None, None, None, step, chunk),
                             lambda b, g, c: (b, at(c), g, 0, 0)),
        "cols": pl.BlockSpec((None, None, None, chunk, step),
                             lambda b, g, c: (b, at(c), g, 0, 0)),
    }


def _call(kernel, name, plan, batch, reverse, inputs, outputs, interpret):
    """``inputs``: ``(spec name, array)`` pairs; ``outputs``: ``(spec
    name, shape, dtype)``.  Scratch: the state of a group's heads, which
    the chunk axis carries from one grid step to the next."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, specs = _specs(plan, batch, reverse)
    carried = pltpu.VMEM((plan.heads_a_step // plan.pack,
                          plan.pack * plan.head_dim, plan.state),
                         jnp.float32)
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid,
            in_specs=[specs[kind] for kind, _ in inputs],
            out_specs=[specs[kind] for kind, _, _ in outputs],
            scratch_shapes=[carried]),
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                   for _, shape, dtype in outputs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)(*[a for _, a in inputs])


def _flat(a, plan: Plan):
    """``[B, T, heads or groups, width]`` -> ``[B, nc, Q, all of them]``
    (no result of a kernel here is 2-d or 3-d: the benchmark tells
    kernels apart by their result shapes)."""
    return a.reshape(a.shape[0], plan.seq // plan.chunk, plan.chunk, -1)


def _state_shape(plan: Plan, batch: int):
    return (batch, plan.seq // plan.chunk, plan.heads // plan.pack,
            plan.pack * plan.head_dim, plan.state)


def _small(cum_rows, dt_rows):
    """The per-head vectors in both layouts, as the kernels take them."""
    return [("cols", _cols(cum_rows)), ("rows", cum_rows),
            ("cols", _cols(dt_rows)), ("rows", dt_rows)]


def _chunk_scan(xs, dt_rows, cum_rows, B, C, plan: Plan, interpret):
    """``y`` without the skip term, and the state entering every chunk
    ``[B, nc, H / pack, pack * P, N]`` float32."""
    batch = xs.shape[0]
    x = _flat(xs, plan)
    y, entering = _call(
        _scan_kernel, "ssd_chunk_scan", plan, batch, False,
        [("heads", x), ("group", _flat(B, plan)), ("group", _flat(C, plan)),
         *_small(cum_rows, dt_rows)],
        [("heads", x.shape, xs.dtype),
         ("state", _state_shape(plan, batch), jnp.float32)], interpret)
    return y.reshape(xs.shape), entering


def _chunk_scan_bwd(xs, dy, dt_rows, cum_rows, B, C, entering, plan: Plan,
                    interpret):
    """``d xs``, ``d B``, ``d C``, and the cotangents of ``cum`` and of
    ``dt`` (its direct part) in the rows' layout."""
    f32 = jnp.float32
    batch = xs.shape[0]
    x, group = _flat(xs, plan), _flat(B, plan)
    small = _small(cum_rows, dt_rows)
    (_, cols), (_, rows) = small[:2]
    dx, db, dc, dcum_c, dcum_r, ddt_c, ddt_r = _call(
        _scan_bwd_kernel, "ssd_chunk_scan_bwd", plan, batch, True,
        [("heads", x), ("heads", _flat(dy, plan)), ("group", group),
         ("group", _flat(C, plan)), ("state", entering), *small],
        [("heads", x.shape, xs.dtype), ("group", group.shape, f32),
         ("group", group.shape, f32), ("cols", cols.shape, f32),
         ("rows", rows.shape, f32), ("cols", cols.shape, f32),
         ("rows", rows.shape, f32)], interpret)
    return (dx.reshape(xs.shape), db.reshape(B.shape).astype(B.dtype),
            dc.reshape(C.shape).astype(C.dtype), dcum_r + _cols(dcum_c),
            ddt_r + _cols(ddt_c))


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(xs, dt, A, B, C, D, plan: Plan, interpret):
    return _ssd_fwd(xs, dt, A, B, C, D, plan, interpret)[0]


def _ssd_fwd(xs, dt, A, B, C, D, plan: Plan, interpret):
    dt_rows = _rows(dt.astype(jnp.float32), plan)
    y, entering = _chunk_scan(xs, dt_rows, _cum_rows(dt_rows, A, plan), B, C,
                              plan, interpret)
    y = y + (D.astype(jnp.float32)[:, None] * xs).astype(y.dtype)
    return y, (xs, dt, A, B, C, D, entering)


def _ssd_bwd(plan: Plan, interpret, res, dy):
    f32 = jnp.float32
    xs, dt, A, B, C, D, entering = res
    dt_rows = _rows(dt.astype(f32), plan)
    cum_rows, cum_vjp = jax.vjp(lambda d, a: _cum_rows(d, a, plan), dt_rows,
                                A.astype(f32))
    dx, dB, dC, dcum, ddt = _chunk_scan_bwd(xs, dy, dt_rows, cum_rows, B, C,
                                            entering, plan, interpret)
    ddt_cum, dA = cum_vjp(dcum)
    dx = dx + (D.astype(f32)[:, None] * dy).astype(dx.dtype)
    dD = jnp.einsum("bthp,bthp->h", dy.astype(f32), xs.astype(f32))
    return (dx, _from_rows(ddt + ddt_cum, plan).astype(dt.dtype),
            dA.astype(A.dtype), dB, dC, dD.astype(D.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(xs: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, *, chunk: int = 128,
        interpret: Optional[bool] = None) -> jax.Array:
    """``xs [B, T, H, P]``, ``dt [B, T, H]`` (positive: after its
    softplus), ``A [H]`` (negative), ``B C [B, T, G, N]``, ``D [H]`` ->
    ``y [B, T, H, P]`` in ``xs``'s dtype.  ``T`` has to be whole chunks.
    ``cum``, the chunk states and the carry are float32 whatever the
    inputs; the products take their operands in ``xs``'s dtype and
    accumulate in float32."""
    plan = _plan(xs, B, chunk)
    interpret = kernel_mode(interpret)
    with telemetry.span("ops", "ssd.plan",
                        **plan.span_args(interpret is not None)):
        if interpret is None:
            return ssd_einsum(xs, dt, A, B, C, D, chunk=chunk)
        return _ssd(xs, dt, A, B, C, D, plan, interpret)
