"""Grouped matrix products for a routed expert layer that drops nothing.

A layer that holds ``E`` experts gets ``P = tokens x choices`` (token,
choice) pairs, of which those that chose one of ITS experts "land
here".  :func:`plan_rows` lays the landed pairs out as rows sorted by
expert, each expert's group padded to whole row tiles of ``block_m``,
inside a buffer of ``row_bound`` pairs: by default the worst case, every
pair lands (``models/afmoe.py`` takes it), so no imbalance ever drops a
row.  A caller that sizes the buffer for less reads ``fits`` and must not
combine a plan that does not.  Every row tile then belongs to ONE expert,
which makes the grouped product a plain tiled matmul whose weight block
is chosen per row tile through a scalar-prefetched table:

* :func:`grouped_matmul`  ``out[rows of e] = lhs[rows of e] @ rhs[e]``
* its backward: the same kernel against ``rhs[e]^T`` for ``d lhs``, and
  :func:`_tgmm` (``d rhs[e] = lhs[rows of e]^T @ d out[rows of e]``,
  accumulated over the expert's row tiles in VMEM scratch).

**The buffer is the worst case's in ADDRESSES only: nothing in the
program reads or writes a dead row.**  Only the ``n_live`` leading row
tiles hold rows, and all work in row space is done a live tile at a
time, in one of two forms:

* inside the kernels.  The grid still spans the buffer, but a dead
  tile's step computes nothing and its index maps repeat the last live
  tile's blocks, so Pallas elides its DMAs.  What stood between the
  products as passes of XLA over all ``M`` rows is work on a tile in
  VMEM (:func:`expert_products`, one ``custom_vjp`` over an expert's
  two or three products): the down product forms ``silu(gate) * up``
  (or ``relu(up)^2``) on its lhs tile, float32 from the bfloat16 tiles,
  and so does its ``d rhs``; its ``d lhs`` ends in the activation's
  derivative against the ``gate`` and ``up`` tiles and writes ``d gate``
  and ``d up``; the second ``d lhs`` onto the rows (up's, after gate's)
  adds the first's tile before it writes.  The activation's result is
  never an array.
* under a reach the plan bounds (:func:`_rows_of_tokens`, the one
  gather ``rows <- tokens`` of :func:`dispatch` and of :func:`combine`'s
  backward): one gather of the buffer's leading eighth, quarter, half or
  whole (:data:`REACHES`), the least that holds the ``n_live`` live
  tiles, written into a buffer that was allocated and never initialised
  (``lax.empty``); at most twice the live rows (or the buffer's eighth)
  are written, the dead ones among them read token 0.  (A ``fori_loop``
  of ``n_live``-bounded trips, chunk after chunk, touched fewer rows and
  was slower a row, and a step with 24 such loops was scheduled into a
  gibibyte more memory: PERF.md, PR 36.)

The rows of dead tiles hold whatever the memory held; nothing adds
them.  The three sums ``tokens <- rows`` (:func:`combine`, its ``d_w``
and :func:`dispatch`'s backward; a TPU scatter-add of 10^5 rows
serializes, so they pull) WALK THE PAIRS THAT LANDED: a kernel over
tiles of tokens (:func:`_walk_pallas`) that reads a tile's ``[k, tile]``
table of the pairs' rows from SMEM, lists the landed ones in pair order
without a branch, fetches each one's row by DMA into a ring in VMEM and
adds it in float32, a token's pairs in the order of its choices.  It
reads ``landed_share x pairs`` rows where a gather a choice read
``pairs`` (7 of 8 of which, 15 of 16 at a sixteenth held, were masked
after the read).  What a DMA can address is a whole tile of the array in
HBM, 8 rows (Mosaic refuses a slice of a tiled dimension that is not
whole tiles): the walk fetches the row's GROUP and adds the one row out
of it, two-byte rows as the 32-bit words they share with their
neighbour.  No list, no sort and no field of the plan is made for it.
Off the TPU, and for shapes no tile of the walk fits
(:func:`walk_tile`), the same sums are :func:`_gather_sum` and
:func:`_gather_dots`: a gather of ``T`` rows a choice, the pairs that
did not land masked after the read; the walk is held against them
(``tests/test_routed_walk.py``).  The walk's results are the TOKENS'
rows (``[T, D]``, ``[T, k]``) and never the row buffer's: the
benchmark's readers tell kernel families apart by result shape.

**How a tile is chosen** (:func:`gmm_tiles`, :func:`tgmm_tiles`: from
the shapes alone, one rule for every model).  A product takes the
contraction whole and tiles the result ``block_m x block_n``, the
width's tiles outermost, so every tile of the width is one more sweep
over the rows: each sweep fetches every live row tile again (and forms
the activation on it again), and walks the buffer's dead steps again.
So the result's width is ONE tile wherever the blocks fit
:data:`VMEM_BUDGET`: the grid is ``(1, row tiles)``, a row tile crosses
HBM once, an expert's whole matrix stays resident over its consecutive
row tiles, the result is written once.  Where the whole width does not
fit, the widest divisor that is whole 128-lane registers; last
:func:`lane_block`'s tile.  ``d rhs`` takes an expert's whole ``[k,
n]`` block likewise, and where that does not fit (the float32
accumulator and the product added to it are the block's size each) the
blocks under which the rows cross HBM the fewest times.  The kernels
ask Mosaic for the VMEM their blocks come to (it scopes a kernel to 16
MiB otherwise).  A caller's ``block_n`` is a CAP on the tile (tests
hold the kernels to narrow tiles with it); models pass none.  On a v5e
(PERF.md, PR 52) Mellum's nine products of 2304 x 896 over 136 live
row tiles of 528 took 8.5 ms where tiles of 128 and 384 lanes (seven
and six sweeps) took 20.2, bit for bit the same results: every element
is one float32 ``dot_general`` over the whole contraction whatever the
tile's width.

A width no tile divides (1856 = 2^6 x 29: its largest divisor under 512
is 464, which is neither whole 128-lane registers nor the whole width,
and Mosaic refuses such a block) is taken AS IT LIES in HBM: whole, or
(under a cap, or where the whole does not fit) under a masked last tile
(:func:`lane_block`): the grid rounds up, the columns a block reads
past the edge reach only columns of the result that are past the edge
too, and those are never written.  That holds for the activation formed
on a tile: it is elementwise, and ``d rhs`` contracts over ROWS, never
over the width.  No weight is padded.

On other backends (tests) the products fall back to plain ``jnp`` unless
``interpret=True`` forces the kernels through the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops._kernel import fit_block, kernel_mode, traced_once


def lane_block(width: int, block: int) -> int:
    """The tile of a dimension of ``width`` that lies along the lanes (or
    is contracted over whole): the largest divisor up to ``block`` where
    that is whole 128-lane registers or the whole width; else the
    multiple of 128 up to ``block`` that overhangs the edge least, the
    last tile masked."""
    fit = fit_block(width, block)
    tiles = range(128, block + 1, 128)   # none under 128 (tests' tiles)
    if fit % 128 == 0 or fit == width or not tiles:
        return fit
    return min(tiles, key=lambda b: (-(-width // b) * b, -b))


#: bytes of VMEM the blocks of one grouped product may take, and what
#: the kernels ask Mosaic for (it scopes a kernel to 16 MiB unless told):
#: half of a v5e core's 128 MiB.  The widest product of ``models/`` is
#: Nemotron's, 2688 x 1856 in bfloat16 under 256 rows: an expert's whole
#: matrix is 10.0 MB, and :func:`_gmm_vmem` counts 2 x (10.0 + 2 row
#: tiles of 1.4 + 4 result tiles of 1.0) + 14 of float32 values = 47 MB,
#: which fits; its ``d rhs`` whole is a 20 MB float32 accumulator, the
#: product's 20 MB value and 2 x 10 MB of result, 76 MB, which does not,
#: and is split three ways along the rows' width.
VMEM_BUDGET = 64 * 2 ** 20
#: what Mosaic scopes a kernel to by default: a kernel asks for no less
MOSAIC_VMEM = 16 * 2 ** 20


def lane_tiles(width: int, cap: Optional[int] = None) -> List[int]:
    """The tiles a dimension of ``width`` along the lanes may take,
    widest first: the whole width, then its divisors that are whole
    128-lane registers, last :func:`lane_block`'s (masked where nothing
    divides); none above ``cap`` where one is given."""
    top = width if cap is None else min(cap, width)
    tiles = [b for b in range(top, 0, -1)
             if width % b == 0 and (b == width or b % 128 == 0)]
    last = lane_block(width, 512 if cap is None else cap)
    return tiles if last in tiles else tiles + [last]


def _gmm_vmem(block_m: int, k: int, block_n: int, itemsize: int) -> int:
    """An upper bound of the bytes :func:`_gmm_pallas` holds in VMEM at
    a result tile ``[block_m, block_n]``, whichever of its forms runs:
    two buffers each of two lhs tiles, the weight block and four tiles of
    the result's shape (the activation's inputs and its two cotangents),
    and float32 values: three of the result's shape, three of the lhs
    tile's.  What the kernel asks Mosaic for."""
    blocks = 2 * block_m * k + k * block_n + 4 * block_m * block_n
    return max(2 * itemsize * blocks + 4 * 3 * block_m * (block_n + k),
               MOSAIC_VMEM)


def _tgmm_vmem(block_m: int, block_k: int, block_n: int, itemsize: int
               ) -> int:
    """The same of :func:`_tgmm_pallas` at a result block ``[block_k,
    block_n]``: two buffers each of two lhs tiles, the cotangent's tile
    and the result's block; in float32 the accumulator, the product
    added to it, and the activation's values on the lhs tile."""
    blocks = 2 * block_m * block_k + block_m * block_n + block_k * block_n
    return max(2 * itemsize * blocks + 4 * (2 * block_k * block_n
                                            + 3 * block_m * block_k),
               MOSAIC_VMEM)


def gmm_tiles(block_m: int, k: int, n: int, itemsize: int,
              cap: Optional[int] = None) -> Tuple[int, int]:
    """``(block_n, VMEM bytes)`` of a product ``[M, k] x [k, n]``: the
    widest of :func:`lane_tiles` whose blocks fit :data:`VMEM_BUDGET`
    (the narrowest where none does).  At the whole width every operand
    crosses HBM once: a sweep over the rows for every tile of ``n``
    fetches every live row tile again."""
    sized = [(b, _gmm_vmem(block_m, k, b, itemsize))
             for b in lane_tiles(n, cap)]
    return next((t for t in sized if t[1] <= VMEM_BUDGET), sized[-1])


def tgmm_tiles(block_m: int, k: int, n: int, itemsize: int,
               cap: Optional[int] = None) -> Tuple[int, int, int]:
    """``(block_k, block_n, VMEM bytes)`` of ``d rhs [k, n]``: of the
    blocks that fit :data:`VMEM_BUDGET`, the one under which the rows
    cross HBM the fewest times (an lhs tile once a tile of ``n``, a
    cotangent's tile once a tile of ``k``); of equals the one with whole
    lanes, ``k`` split.  ``cap`` bounds ``block_n`` alone."""
    sized = [(bk, bn, _tgmm_vmem(block_m, bk, bn, itemsize))
             for bn in lane_tiles(n, cap) for bk in lane_tiles(k)]
    fits = [t for t in sized if t[2] <= VMEM_BUDGET] or sized[-1:]
    return min(fits, key=lambda t: -(-n // t[1]) * k + -(-k // t[0]) * n)


def product_tiles(block_m: int, embed: int, width: int, itemsize: int,
                  cap: Optional[int] = None) -> Dict[str, Any]:
    """What :func:`expert_products` compiles at these shapes, for a
    ``moe.plan`` span: the weight block (``k x tile``) and, after the
    colon, the sweeps over the rows, of the products onto the experts'
    width (``up``: gate's, up's and the down product's ``d lhs``), of
    those back onto the rows' (``down``, and gate's and up's ``d lhs``)
    and of ``d rhs`` (gate's and up's; ``drhs_down``: the down
    product's); and the most VMEM any of the kernels asks for."""
    up = gmm_tiles(block_m, embed, width, itemsize, cap)
    down = gmm_tiles(block_m, width, embed, itemsize, cap)
    d_up = tgmm_tiles(block_m, embed, width, itemsize, cap)
    d_down = tgmm_tiles(block_m, width, embed, itemsize, cap)
    return {
        "product_tiles": ", ".join([
            f"up {embed}x{up[0]}:{-(-width // up[0])}",
            f"down {width}x{down[0]}:{-(-embed // down[0])}",
            f"drhs {d_up[0]}x{d_up[1]}:"
            f"{-(-embed // d_up[0])}x{-(-width // d_up[1])}",
            f"drhs_down {d_down[0]}x{d_down[1]}:"
            f"{-(-width // d_down[0])}x{-(-embed // d_down[1])}"]),
        "product_vmem_bytes": max(up[1], down[1], d_up[2], d_down[2])}


class RowPlan(NamedTuple):
    """Where every landed (token, choice) pair sits, and back."""
    row_pair: jax.Array     # [M]  flat pair id (token * k + choice) of a row
    row_valid: jax.Array    # [M]  the row holds a pair
    pair_row: jax.Array     # [T, k]  row of a pair
    pair_valid: jax.Array   # [T, k]  the pair landed here (and was kept)
    tile_expert: jax.Array  # [M / block_m]  local expert of a row tile
    n_live: jax.Array       # [1]  leading row tiles that hold rows
    sizes: jax.Array        # [E]  pairs that chose each held expert
    fits: jax.Array         # []   every landed pair has its row


def plan_rows(expert_idx: jax.Array, first: int, held: int, *,
              block_m: int, row_bound: Optional[int] = None) -> RowPlan:
    """``expert_idx [T, k]``: the experts (of all published ones) each
    token chose.  Pairs that chose ``first .. first + held - 1`` land
    here, sorted by expert then by pair id.  ``row_bound``: the most
    pairs the buffer is sized for; the default ``T * k`` is the worst
    case, so every plan fits.  With a smaller bound a plan may not
    (``fits`` False): the pairs past the buffer have no row, and a caller
    that combined such a plan would DROP them."""
    tokens, k = expert_idx.shape
    pairs = tokens * k
    bound = pairs if row_bound is None else row_bound
    m_tiles = -(-bound // block_m) + held  # each group pads < one tile
    m = m_tiles * block_m

    local = expert_idx.reshape(pairs).astype(jnp.int32) - first
    here = jnp.logical_and(local >= 0, local < held)
    key = jnp.where(here, local, held)
    onehot = (key[:, None] == jnp.arange(held)[None]).astype(jnp.int32)
    sizes = onehot.sum(0)                                      # [E]
    tiles_per = (sizes + block_m - 1) // block_m
    tile_ends = jnp.cumsum(tiles_per)
    tile_starts = tile_ends - tiles_per
    starts = jnp.cumsum(sizes) - sizes
    n_live = jnp.minimum(tile_ends[-1], m_tiles)

    # pair -> row: rank among the earlier pairs of the same expert
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot,
        jnp.minimum(key, held - 1)[:, None], axis=1)[:, 0]
    pair_row = tile_starts[jnp.minimum(key, held - 1)] * block_m + rank
    pair_valid = jnp.logical_and(here, pair_row < m)
    pair_row = jnp.where(pair_valid, pair_row, 0)

    # row -> pair: the stable sort's order, read through the padding
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    tile = jnp.arange(m_tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_ends, tile, side="right"),
        held - 1).astype(jnp.int32)
    row_expert = jnp.repeat(tile_expert, block_m)
    row = jnp.arange(m, dtype=jnp.int32)
    row_rank = row - tile_starts[row_expert] * block_m
    row_valid = jnp.logical_and(
        row < n_live * block_m,
        jnp.logical_and(row_rank >= 0, row_rank < sizes[row_expert]))
    src = jnp.clip(starts[row_expert] + row_rank, 0, pairs - 1)
    row_pair = jnp.where(row_valid, order[src], 0)
    return RowPlan(row_pair, row_valid, pair_row.reshape(tokens, k),
                   pair_valid.reshape(tokens, k), tile_expert,
                   n_live.reshape(1).astype(jnp.int32), sizes,
                   tile_ends[-1] <= m_tiles)


# ---------------------------------------------------------------------------
# rows <- tokens (as far as the live rows reach)
# ---------------------------------------------------------------------------

#: how far a gather ``rows <- tokens`` may reach, as divisors of the
#: buffer's tiles: the least reach that holds the live tiles is taken
REACHES = (8, 4, 2, 1)


def _rows_of_tokens(x: jax.Array, plan: RowPlan, weights=None) -> jax.Array:
    """``x [T, D]`` -> rows ``[M, D]``: each row of a live tile its
    pair's token, times the pair's weight where ``weights [T, k]`` are
    given (a row that holds no pair: token 0, weight 0).  ONE gather of
    the buffer's leading ``S`` rows, written into a buffer nobody
    initialised, ``S`` the least of the buffer's eighth, quarter, half
    and whole (in whole tiles) that holds the ``n_live`` live tiles,
    chosen under a ``lax.switch``; the rows past ``S`` are not touched,
    the dead ones before it read token 0."""
    k = plan.pair_row.shape[1]
    m, tiles = plan.row_pair.shape[0], plan.tile_expert.shape[0]
    block_m = m // tiles
    flat_w = None if weights is None else weights.reshape(-1)
    reaches = [-(-tiles // share) * block_m for share in REACHES]

    def reach(rows):
        def gather():
            pair = plan.row_pair[:rows]
            part = x[pair // k]
            if flat_w is not None:
                part = part * jnp.where(plan.row_valid[:rows], flat_w[pair],
                                        0.0)[:, None]
            part = part.astype(x.dtype)
            if rows == m:
                return part
            return jax.lax.dynamic_update_slice(
                jax.lax.empty((m, x.shape[1]), x.dtype), part, (0, 0))
        return gather

    live = plan.n_live[0] * block_m
    shorter = sum((live > rows).astype(jnp.int32) for rows in reaches[:-1])
    return jax.lax.switch(shorter, [reach(rows) for rows in reaches])


def dispatch(x: jax.Array, plan: RowPlan, *,
             interpret: Optional[bool] = None) -> jax.Array:
    """``x [T, D]`` -> rows ``[M, D]``: each row its pair's token (rows
    of live tiles that hold no pair read token 0 and are never combined;
    rows of dead tiles are not written).  Backward: every token the sum
    of its landed pairs' rows (:func:`_token_sums`)."""
    return _dispatch(x, plan, kernel_mode(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, plan, interpret):
    return _rows_of_tokens(x, plan)


def _dispatch_fwd(x, plan, interpret):
    return _dispatch(x, plan, interpret), plan


def _dispatch_bwd(interpret, plan, g):
    return _token_sums(g, plan, None, g.dtype, interpret), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def combine(rows: jax.Array, weights: jax.Array, plan: RowPlan, *,
            dtype=jnp.float32, interpret: Optional[bool] = None
            ) -> jax.Array:
    """Rows ``[M, D]`` and the pairs' weights ``[T, k]`` (f32) ->
    ``[T, D]``: every token the weighted sum of its landed pairs' rows,
    float32, in the order of its choices; rounded ONCE to ``dtype``
    where that is another (the float32 sums of a call are twice the
    rows')."""
    return _combine(rows, weights, plan, jnp.dtype(dtype),
                    kernel_mode(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(rows, weights, plan, dtype, interpret):
    return _token_sums(rows, plan, weights, dtype, interpret)


def _combine_fwd(rows, weights, plan, dtype, interpret):
    return (_combine(rows, weights, plan, dtype, interpret),
            (rows, weights, plan))


def _combine_bwd(dtype, interpret, res, g):
    rows, weights, plan = res
    # gathered in the rows' dtype (the float32 cotangent of a row is
    # twice the row), each row weighted as it is gathered
    d_rows = _rows_of_tokens(g.astype(rows.dtype), plan, weights)
    d_w = _pair_dots(rows, plan, g, interpret)
    return d_rows, d_w.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# tokens <- rows: the sums over a token's landed pairs
# ---------------------------------------------------------------------------

def _gather_sum(rows, plan: RowPlan, weights):
    """``out[t] = sum_c [valid] w[t, c] * rows[pair_row[t, c]]`` in f32,
    one choice at a time (``[T, k, D]`` at once is the worst-case
    buffer again): a gather of ``T`` rows a CHOICE, the pairs that did
    not land masked after the read.  The path off the TPU, and what the
    walk is held against."""
    tokens, k = plan.pair_row.shape
    out = jnp.zeros((tokens, rows.shape[1]), jnp.float32)
    for c in range(k):
        part = rows[plan.pair_row[:, c]].astype(jnp.float32)
        if weights is not None:
            part = part * weights[:, c, None]
        out = out + jnp.where(plan.pair_valid[:, c, None], part, 0.0)
    return out


def _gather_dots(rows, plan: RowPlan, g):
    """``out[t, c] = [valid] sum_d rows[pair_row[t, c], d] * g[t, d]``
    in f32, :func:`_gather_sum`'s way."""
    return jnp.stack([
        jnp.where(plan.pair_valid[:, c],
                  jnp.sum(rows[plan.pair_row[:, c]].astype(jnp.float32)
                          * g, axis=-1), 0.0)
        for c in range(plan.pair_row.shape[1])], axis=1)


#: rows of one tile of an array in HBM, of two- and of four-byte
#: elements alike: the least a DMA can address (Mosaic refuses a slice
#: of a tiled dimension that is not whole tiles)
ROW_GROUP = 8
#: row groups in flight a token tile
WALK_RING = 16
#: a landed pair in the kernel's list: ``token * _CHOICES + choice``
_CHOICES = 16


def walk_tile(tokens: int, width: int) -> Optional[int]:
    """Tokens a step of the walk's grid: whole 128-lane registers of the
    tables in SMEM (or all the tokens there are), as many as keep the
    result's block (and the cotangent's beside it) within VMEM; None:
    no such tile, the gathers stay."""
    fits = [t for t in (512, 256, 128)
            if tokens % t == 0 and t * width * 4 <= 2 ** 21]
    if fits:
        return fits[0]
    return tokens if tokens % ROW_GROUP == 0 and tokens <= 1024 else None


def _walks(rows, plan: RowPlan, interpret) -> bool:
    """Whether the sums over tokens walk the landed pairs (a kernel) or
    gather a row a pair: the kernels' switch, and shapes a DMA can
    address."""
    tokens = plan.pair_row.shape[0]
    return interpret is not None \
        and rows.dtype in (jnp.bfloat16, jnp.float32) \
        and rows.shape[0] % ROW_GROUP == 0 \
        and plan.pair_row.shape[1] <= _CHOICES \
        and walk_tile(tokens, rows.shape[1]) is not None


def _token_sums(rows, plan: RowPlan, weights, dtype, interpret):
    """``[T, D]``: every token the sum of its landed pairs' rows (times
    ``weights [T, k]`` where given), float32 in the order of its
    choices, rounded once to ``dtype``."""
    if not _walks(rows, plan, interpret):
        return _gather_sum(rows, plan, weights).astype(dtype)
    return _walk_pallas(rows, plan, weights, None, dtype, interpret)


def _pair_dots(rows, plan: RowPlan, g, interpret):
    """``[T, k]`` f32: every landed pair's row against its token's
    ``g [T, D]``, in float32; a pair that did not land 0."""
    if not _walks(rows, plan, interpret) or g.dtype not in (
            jnp.bfloat16, jnp.float32):
        return _gather_dots(rows, plan, g.astype(jnp.float32))
    return _walk_pallas(rows, plan, None, g, jnp.float32, interpret)


def _walk_kernel(some, tab, *refs, k: int, tile: int, weighted: bool,
                 dots: bool):
    """One tile of tokens.  ``some [1, tile]`` (SMEM): a token's landed
    choices as bits; ``tab [k, tile]`` (SMEM): the row of a pair; then
    ``w [k, tile]`` (SMEM, where ``weighted``), ``g [tile, D]`` (where
    ``dots``), the rows (HBM), the result's block, and scratch: the
    tile's landed pairs in pair order (SMEM), the ring of row groups,
    its semaphores and (a result that is not float32) the sums.  Every
    loop is rolled over a count read from SMEM; a landed pair costs one
    DMA of its row's group and one register row of work."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    g_ref = refs.pop(0) if dots else None
    rows_ref, out_ref, landed, ring, sem, *acc = refs
    acc_ref = acc[0] if acc else out_ref
    # two-byte elements as the 32-bit words they lie in (a word: the
    # same column of an even row and the next): half the rows, and no
    # packed row is ever indexed
    words = rows_ref.dtype.itemsize == 2
    src = rows_ref.bitcast(jnp.uint32) if words else rows_ref
    per = ring.shape[1]

    def one_row(ref, row):
        """Row ``row`` of ``ref`` as ``[1, D]`` float32: of a word, the
        row's half shifted to the top IS the float32."""
        if ref.dtype == jnp.float32:
            return ref[pl.ds(row, 1), :]
        if ref.dtype != jnp.uint32:
            ref = ref.bitcast(jnp.uint32)
        word = ref[pl.ds(row // 2, 1), :]
        return jax.lax.bitcast_convert_type(
            (word >> (row % 2 * 16).astype(jnp.uint32)) << 16, jnp.float32)

    def find(t, n):
        chose = some[0, t]      # bit c: the token's choice c landed

        def its_pairs(n):
            for c in range(k):  # no branch: written always, kept if landed
                landed[n] = t * _CHOICES + c
                n = n + ((chose >> c) & 1)
            return n
        return jax.lax.cond(chose != 0, its_pairs, lambda n: n, n)
    n = jax.lax.fori_loop(0, tile, find, 0)

    def group(j, row=0):
        slot = j % WALK_RING
        return pltpu.make_async_copy(
            src.at[pl.ds(pl.multiple_of(row // ROW_GROUP * per, per), per)],
            ring.at[slot], sem.at[slot])

    def ask(j):
        at = landed[j]
        group(j, tab[at % _CHOICES, at // _CHOICES]).start()

    def each(do):   # a loop's body that carries nothing
        return lambda j, carry: (do(j), carry)[1]

    jax.lax.fori_loop(0, jnp.minimum(n, WALK_RING), each(ask), 0)
    some_rows = 16 if tile % 16 == 0 else tile

    def some_of(i):
        return pl.ds(pl.multiple_of(i * some_rows, some_rows), some_rows)

    def zero(i):
        acc_ref[some_of(i), :] = jnp.zeros((some_rows, acc_ref.shape[1]),
                                           jnp.float32)
    jax.lax.fori_loop(0, tile // some_rows, each(zero), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def add(j):
        group(j).wait()         # any group's bytes: the slot's own
        at = landed[j]
        t, c = at // _CHOICES, at % _CHOICES
        got = one_row(ring.at[j % WALK_RING], tab[c, t] % ROW_GROUP)
        of = pl.ds(t, 1)
        if dots:
            dot = jnp.sum(got * one_row(g_ref, t), axis=1, keepdims=True)
            acc_ref[of, :] = jnp.where(lane == c, dot, acc_ref[of, :])
        else:
            if weighted:
                got = got * w_ref[c, t]
            acc_ref[of, :] = acc_ref[of, :] + got

    # two straight bodies, no branch in either: while groups remain to
    # be asked for, a slot is filled again as soon as its row is added
    def add_and_ask(j):
        add(j)
        ask(j + WALK_RING)

    ahead = jnp.maximum(n - WALK_RING, 0)
    jax.lax.fori_loop(0, ahead, each(add_and_ask), 0)
    jax.lax.fori_loop(ahead, n, each(add), 0)

    if acc:     # the sums, rounded once
        def rounded(i):
            out_ref[some_of(i), :] = acc_ref[some_of(i), :].astype(
                out_ref.dtype)
        jax.lax.fori_loop(0, tile // some_rows, each(rounded), 0)


@traced_once("dtype", "interpret")
def _walk_pallas(rows, plan: RowPlan, weights, g, dtype, interpret):
    """The walk: ``weights`` (or neither) the sums ``[T, D]`` in
    ``dtype``, ``g`` the dots ``[T, k]``; summed in float32.  Traced and
    lowered once for all the layer-calls of a step that run it on the
    same shapes (a kernel's lowering is a quarter of a second, and a
    step holds 15 to 32 calls)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, k = plan.pair_row.shape
    width = rows.shape[1]
    tile = walk_tile(tokens, width)
    words = rows.dtype.itemsize == 2
    weighted, dots = weights is not None, g is not None
    some = jnp.sum(jnp.where(plan.pair_valid, 1 << jnp.arange(k), 0), axis=1,
                   dtype=jnp.int32)
    in_smem = pl.BlockSpec((k, tile), lambda i: (0, i),
                           memory_space=pltpu.SMEM)
    specs = [pl.BlockSpec((1, tile), lambda i: (0, i),
                          memory_space=pltpu.SMEM), in_smem]
    args = [some[None], plan.pair_row.astype(jnp.int32).T]
    if weighted:
        specs.append(in_smem)
        args.append(weights.astype(jnp.float32).T)
    if dots:
        specs.append(pl.BlockSpec((tile, width), lambda i: (i, 0)))
        args.append(g)
    out_width = k if dots else width
    scratch = [
        pltpu.SMEM((tile * k,), jnp.int32),
        pltpu.VMEM((WALK_RING, ROW_GROUP // 2 if words else ROW_GROUP,
                    width), jnp.uint32 if words else rows.dtype),
        pltpu.SemaphoreType.DMA((WALK_RING,))]
    if jnp.dtype(dtype) != jnp.float32:
        scratch.append(pltpu.VMEM((tile, out_width), jnp.float32))
    return pl.pallas_call(
        functools.partial(_walk_kernel, k=k, tile=tile, weighted=weighted,
                          dots=dots),
        grid=(tokens // tile,),
        in_specs=specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, out_width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tokens, out_width), dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="landed_rows_dot" if dots else "landed_rows_sum",
    )(*args, rows)


# ---------------------------------------------------------------------------
# an expert's activation, on a tile in VMEM or (off the TPU) on an array
# ---------------------------------------------------------------------------

def _act(hidden) -> jax.Array:
    """``(gate, up)`` -> ``silu(gate) * up``; ``(up,)`` -> ``relu(up)^2``;
    in float32."""
    h = [a.astype(jnp.float32) for a in hidden]
    if len(h) == 2:
        return h[0] * jax.nn.sigmoid(h[0]) * h[1]
    return jnp.square(jnp.maximum(h[0], 0.0))


def _act_bwd(hidden, d_mid: jax.Array):
    """The cotangents of ``hidden`` (float32) under ``d_mid`` of
    :func:`_act`'s result."""
    h = [a.astype(jnp.float32) for a in hidden]
    if len(h) == 2:
        gate, up = h
        s = jax.nn.sigmoid(gate)
        return (d_mid * up * s * (1.0 + gate * (1.0 - s)),
                d_mid * gate * s)
    return (d_mid * 2.0 * jnp.maximum(h[0], 0.0),)


def _lhs_of(lhs, act: bool) -> jax.Array:
    """The product's left operand from its arrays (or tiles): the one
    there is, or the activation of the one or two."""
    return _act(lhs).astype(lhs[0].dtype) if act else lhs[0]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _live_tile(m, n_live_ref):
    """Row-tile index for the index maps: a dead tile repeats the last
    live one, so its blocks are already resident and nothing is copied."""
    return jnp.minimum(m, jnp.maximum(n_live_ref[0] - 1, 0))


def _gmm_kernel(te_ref, n_live_ref, *refs, n_lhs: int, act: bool,
                add: bool, n_hidden: int, transpose_rhs: bool):
    """``refs``: the lhs tiles (``act``: the activation's inputs), the
    weight block, the tile to add (``add``), the ``n_hidden`` tiles the
    activation's derivative is taken against, then the results: one, or
    ``n_hidden``."""
    from jax.experimental import pallas as pl

    lhs_refs, rhs_ref = refs[:n_lhs], refs[n_lhs]
    rest = refs[n_lhs + 1:]
    add_ref = rest[0] if add else None
    rest = rest[int(add):]
    hidden_refs, out_refs = rest[:n_hidden], rest[n_hidden:]

    @pl.when(pl.program_id(1) < n_live_ref[0])
    def _compute():
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        acc = jax.lax.dot_general(
            _lhs_of([r[:] for r in lhs_refs], act), rhs_ref[:], contract,
            preferred_element_type=jnp.float32)
        if add:
            acc = acc + add_ref[:].astype(jnp.float32)
        outs = _act_bwd([r[:] for r in hidden_refs], acc) if n_hidden \
            else (acc,)
        for out_ref, out in zip(out_refs, outs):
            out_ref[:] = out.astype(out_ref.dtype)


def _gmm_pallas(lhs, rhs, tile_expert, n_live, block_m, block_n,
                transpose_rhs, interpret, act=False, add=None, hidden=()):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs[0].shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    block_n, vmem = gmm_tiles(block_m, k, n, lhs[0].dtype.itemsize, block_n)
    if transpose_rhs:   # rhs [E, N, K]: rows of the block are outputs
        rhs_spec = pl.BlockSpec(
            (None, block_n, k),
            lambda j, i, te, nl: (te[_live_tile(i, nl)], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, block_n),
            lambda j, i, te, nl: (te[_live_tile(i, nl)], 0, j))
    lhs_spec = pl.BlockSpec((block_m, k),
                            lambda j, i, te, nl: (_live_tile(i, nl), 0))
    out_spec = pl.BlockSpec((block_m, block_n),
                            lambda j, i, te, nl: (_live_tile(i, nl), j))
    adds = () if add is None else (add,)
    n_out = max(len(hidden), 1)
    out_shape = [jax.ShapeDtypeStruct((m, n), lhs[0].dtype)] * n_out
    name = "grouped_matmul" + ("_t" if transpose_rhs else "") \
        + ("_act" if act or hidden else "") + ("_add" if adds else "")
    # n outermost (one tile of it wherever the budget allows):
    # consecutive row tiles of one expert keep its weight block resident
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_lhs=len(lhs), act=act,
                          add=bool(adds), n_hidden=len(hidden),
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-n // block_n), m // block_m),
            in_specs=[lhs_spec] * len(lhs) + [rhs_spec]
            + [out_spec] * (len(adds) + len(hidden)),
            out_specs=[out_spec] * n_out,
        ),
        out_shape=out_shape,
        # the tile to add is the result's own: the sum takes its place
        input_output_aliases={2 + len(lhs) + 1: 0} if adds else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(tile_expert, n_live, *lhs, rhs, *adds, *hidden)
    return tuple(out) if hidden else out[0]


def _tgmm_kernel(te_ref, n_live_ref, *refs, act: bool):
    from jax.experimental import pallas as pl

    *lhs_refs, dout_ref, out_ref, acc_ref = refs
    i = pl.program_id(2)
    last = pl.num_programs(2) - 1
    n_live = n_live_ref[0]
    here = te_ref[i]
    opens = jnp.logical_or(i == 0, te_ref[jnp.maximum(i - 1, 0)] != here)
    closes = jnp.logical_or(i == n_live - 1,
                            te_ref[jnp.minimum(i + 1, last)] != here)

    @pl.when(i < n_live)
    def _compute():
        @pl.when(opens)
        def _zero():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            _lhs_of([r[:] for r in lhs_refs], act), dout_ref[:],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(closes)
        def _store():
            out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _tgmm_pallas(lhs, dout, tile_expert, n_live, experts, block_m, block_n,
                 interpret, act=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs[0].shape
    n = dout.shape[1]
    block_k, block_n, vmem = tgmm_tiles(block_m, k, n, lhs[0].dtype.itemsize,
                                        block_n)
    lhs_spec = pl.BlockSpec((block_m, block_k),
                            lambda a, b, i, te, nl: (_live_tile(i, nl), a))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-k // block_k), -(-n // block_n), m // block_m),
            in_specs=[lhs_spec] * len(lhs) + [
                pl.BlockSpec((block_m, block_n),
                             lambda a, b, i, te, nl: (_live_tile(i, nl), b)),
            ],
            out_specs=pl.BlockSpec(
                (None, block_k, block_n),
                lambda a, b, i, te, nl: (te[_live_tile(i, nl)], a, b)),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((experts, k, n), lhs[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="grouped_matmul_drhs" + ("_act" if act else ""),
    )(tile_expert, n_live, *lhs, dout)


# ---------------------------------------------------------------------------
# plain jnp (off the TPU), tile by tile as the kernels see the rows
# ---------------------------------------------------------------------------

def _tiles_live(tile_expert, n_live):
    return jnp.arange(tile_expert.shape[0]) < n_live[0]


def _gmm_ref(lhs, rhs, tile_expert, n_live, block_m, transpose_rhs):
    """Float32; a dead tile's rows zero."""
    tiles = lhs.reshape(tile_expert.shape[0], block_m, lhs.shape[1])
    out = jnp.einsum("tmn,tkn->tmk" if transpose_rhs else "tmk,tkn->tmn",
                     tiles, rhs[tile_expert],
                     preferred_element_type=jnp.float32)
    out = jnp.where(_tiles_live(tile_expert, n_live)[:, None, None],
                    out, 0.0)
    return out.reshape(lhs.shape[0], -1)


def _tgmm_ref(lhs, dout, tile_expert, n_live, experts, block_m):
    t = tile_expert.shape[0]
    per_tile = jnp.einsum("tmk,tmn->tkn",
                          lhs.reshape(t, block_m, -1),
                          dout.reshape(t, block_m, -1),
                          preferred_element_type=jnp.float32)
    live = _tiles_live(tile_expert, n_live)
    per_tile = jnp.where(live[:, None, None], per_tile, 0.0)
    onehot = (tile_expert[:, None] == jnp.arange(experts)[None]) & \
        live[:, None]
    return jnp.einsum("te,tkn->ekn", onehot.astype(jnp.float32), per_tile)


# ---------------------------------------------------------------------------
# the differentiable products
# ---------------------------------------------------------------------------

def _product(lhs, rhs, tile_expert, n_live, block_m, block_n,
             transpose_rhs, interpret, act=False, add=None, hidden=()):
    """``lhs``: a tuple, the left operand or (``act``) what its
    activation is formed from; ``add [M, N]``: added to the product;
    ``hidden``: the product is the cotangent of their activation, the
    results theirs."""
    if interpret is not None:
        return _gmm_pallas(lhs, rhs, tile_expert, n_live, block_m, block_n,
                           transpose_rhs, interpret, act, add, hidden)
    dtype = lhs[0].dtype
    out = _gmm_ref(_lhs_of(lhs, act), rhs, tile_expert, n_live, block_m,
                   transpose_rhs)
    if add is not None:
        out = out + add.astype(jnp.float32)
    if hidden:
        return tuple(d.astype(dtype) for d in _act_bwd(hidden, out))
    return out.astype(dtype)


def _d_rhs(lhs, dout, rhs, tile_expert, n_live, block_m, block_n, interpret,
           act=False):
    """``d rhs[e] = lhs[rows of e]^T @ dout[rows of e]``, in ``rhs``'s
    dtype; ``act`` as :func:`_product`'s."""
    experts = rhs.shape[0]
    if interpret is None:
        return _tgmm_ref(_lhs_of(lhs, act), dout, tile_expert, n_live,
                         experts, block_m).astype(rhs.dtype)
    d_rhs = _tgmm_pallas(lhs, dout, tile_expert, n_live, experts, block_m,
                         block_n, interpret, act)
    # an expert with no rows was never visited: its block is whatever
    # the buffer held
    seen = jnp.any(
        (tile_expert[:, None] == jnp.arange(experts)[None])
        & _tiles_live(tile_expert, n_live)[:, None], axis=0)
    return jnp.where(seen[:, None, None], d_rhs, 0).astype(rhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm(lhs, rhs, tile_expert, n_live, block_m, block_n, interpret):
    return _product((lhs,), rhs, tile_expert, n_live, block_m, block_n,
                    False, interpret)


def _gmm_fwd(lhs, rhs, tile_expert, n_live, block_m, block_n, interpret):
    out = _gmm(lhs, rhs, tile_expert, n_live, block_m, block_n, interpret)
    return out, (lhs, rhs, tile_expert, n_live)


def _gmm_bwd(block_m, block_n, interpret, res, g):
    lhs, rhs, tile_expert, n_live = res
    plan = (tile_expert, n_live, block_m, block_n)
    d_lhs = _product((g,), rhs, *plan, True, interpret)
    return d_lhs, _d_rhs((lhs,), g, rhs, *plan, interpret), None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, plan: RowPlan, *,
                   block_n: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``lhs [M, K]`` rows laid out by ``plan``, ``rhs [E, K, N]`` ->
    ``[M, N]``: every live row tile times its expert's matrix, f32
    accumulation, output in ``lhs``'s dtype.  Rows of dead tiles are not
    written.  ``block_n``: a cap on the result's tile along ``N``; left
    open, the tile is :func:`gmm_tiles`' (the whole width where VMEM
    holds it)."""
    block_m = lhs.shape[0] // plan.tile_expert.shape[0]
    return _gmm(lhs, rhs, plan.tile_expert, plan.n_live, block_m, block_n,
                kernel_mode(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _experts(rows, weights, tile_expert, n_live, block_m, block_n,
             interpret):
    return _experts_fwd(rows, weights, tile_expert, n_live, block_m,
                        block_n, interpret)[0]


def _experts_fwd(rows, weights, tile_expert, n_live, block_m, block_n,
                 interpret):
    plan = (tile_expert, n_live, block_m, block_n)
    hidden = tuple(_product((rows,), w, *plan, False, interpret)
                   for w in weights[:-1])
    out = _product(hidden, weights[-1], *plan, False, interpret, act=True)
    return out, (rows, hidden, weights, tile_expert, n_live)


def _experts_bwd(block_m, block_n, interpret, res, g):
    rows, hidden, weights, tile_expert, n_live = res
    plan = (tile_expert, n_live, block_m, block_n)
    d_down = _d_rhs(hidden, g, weights[-1], *plan, interpret, act=True)
    d_hidden = _product((g,), weights[-1], *plan, True, interpret,
                        hidden=hidden)
    d_rows, d_weights = None, []
    for d, w in zip(d_hidden, weights[:-1]):
        d_weights.append(_d_rhs((rows,), d, w, *plan, interpret))
        d_rows = _product((d,), w, *plan, True, interpret, add=d_rows)
    return d_rows, (*d_weights, d_down), None, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def expert_products(rows: jax.Array, weights, plan: RowPlan, *,
                    block_n: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Every live row through its expert: ``rows [M, D]`` laid out by
    ``plan``; ``weights`` the experts' matrices, ``(gate, up, down)``
    (``down(silu(gate r) * up r)``) or ``(up, down)`` (``down(relu(up
    r)^2)``), ``[E, D, F]`` and ``down [E, F, D]`` -> ``[M, D]``.  One
    kernel call a product forward (``gate r`` and ``up r`` kept for the
    backward in ``rows``' dtype, the activation formed on the down
    product's tile) and two backward; rows of dead tiles are neither
    read nor written.  ``block_n`` as :func:`grouped_matmul`'s."""
    block_m = rows.shape[0] // plan.tile_expert.shape[0]
    return _experts(rows, tuple(weights), plan.tile_expert, plan.n_live,
                    block_m, block_n, kernel_mode(interpret))
