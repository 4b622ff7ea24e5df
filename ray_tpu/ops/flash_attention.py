"""Flash attention: fused blockwise attention for the MXU.

Forward and backward are pallas kernels (FlashAttention-2 style).  All
three kernels use the same structure: a 4-d grid whose last axis is
sequential ("arbitrary" dimension semantics) streaming K/V (forward,
dQ) or Q (dK/dV) tiles while the online-softmax statistics / gradient
accumulators live in VMEM scratch across its iterations.  VMEM usage
is therefore O(block), independent of sequence length; beyond one
chip, ``ray_tpu.parallel.ring_attention`` composes with this kernel
per shard.

Two kernel families, chosen from the shapes alone (``_nl_eligible``):

* native layout: q, k and v of ONE head width, 64 or 128, heads filling
  whole 128-lane slabs (any count of 128-wide heads, an even count of
  64-wide ones); K/V may carry fewer heads than q where the width is 128
  (one head a slab).  Reads ``[B, T, H, D]`` as it lies, so nothing is
  transposed around the calls.  20 heads of 64 (GPT-2 large), 32 on 4
  heads of 128 (Trinity-Mini);
* head-major (``[B, H, T, D]``, transposes around the calls): any head
  count and width, K/V heads any divisor of the query heads; q, k and v
  of one width.  25 heads of 64 (GPT-2 XL) cannot pack two to a slab.
  And its latent-attention variant (``k_rope=``): the key in two parts,
  each head's own ``[B, T, H, nope]`` beside ONE rotary head ``[B, T, 1,
  rope]`` that every query head shares, q ``nope + rope`` wide, v and the
  output of their own width ``dv``; equal head counts, no window.  32
  heads of 128 + 64 against v of 128 (Kanana-2-30B-A3B): a 192-wide head
  fills no whole slabs.

Both families stay because the benchmark has cells on each side
(``PERF.md`` section 4).

Matmul operands stay in the input dtype (bf16 on TPU) with f32
accumulation via ``preferred_element_type`` — the MXU's native mode.

A tile the mask cuts is not multiplied as one square and then half
thrown away.  On square tiles the diagonal runs corner to corner
through the tiles ``iq == ik``; such a tile is worked in ``n`` static
blocks (``_cut_parts``): row-block ``b`` of the Q tile against keys
``[0, (b + 1) * sub)`` of the K/V tile already in VMEM (forward, dQ),
column-block ``b`` of the K tile against queries ``[b * sub, block)``
(dK/dV), each block updating ITS rows of the statistics or
accumulators, and only the ``sub x sub`` corner on the diagonal pays for
a mask: ``(n + 1) / (2n)`` of the square's products.  The tile on the
lower edge of a ``window`` of whole tiles keeps its strict upper
triangle and is worked mirrored.  The grid, the block specs, the DMAs
and the pairs that contribute are those of the uncut kernels; ``n`` is
``CUT_BLOCKS`` for dK/dV and dQ and ``CUT_BLOCKS_FORWARD`` for the
forward, which takes a cut tile's blocks stage by stage so that the
row maximum and the row sum are reduced across lanes once for all the
tile's rows (``_row_max``).  Any other straddling tile (unequal blocks, a window that
is no whole number of tiles or shorter than one) is multiplied whole
under its mask, and one block traces exactly that body.  What a call
works for what counts is in its ``ops:flash.plan`` span.

Two static extras of the equal-width kernels, both off by default (the
default traces the kernels body for body as before they existed):

* ``window``: position ``t`` sees keys ``t - window + 1 .. t``.  Tiles
  wholly outside the window are skipped the way tiles above the
  diagonal are (compute gated off, index maps clamped so the DMA is
  elided); tiles that straddle its edge are masked, or worked in blocks
  as above.
* grouped heads: ``k``/``v`` may carry fewer heads than ``q``.  Query
  head ``h`` reads K/V head ``h // group`` through the block index — no
  copies of K and V in HBM — and the dK/dV kernel walks the ``group``
  query heads of its K/V head along its sequential axis.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.core import telemetry
from ray_tpu.ops._kernel import fit_block, kernel_mode, traced_once

NEG_INF = -1e30

#: block size along both sequence axes when the caller names none
DEFAULT_BLOCK = 1024

#: blocks a tile cut by the mask is worked in (``_cut_parts``), each at
#: least ``CUT_MIN`` rows and keys: whole 128-lane vregs of scores.
#: Chosen on a v5e (PERF.md section 6, PR 34): dK/dV and dQ gain up to
#: four blocks and nothing beyond; the forward, which takes a cut tile's
#: blocks stage by stage (``_row_max``), gains up to eight.
CUT_BLOCKS = 4
CUT_BLOCKS_FORWARD = 8
CUT_MIN = 128

#: the two pieces of a step's part ``attn`` that are made HERE
#: (``models/step.py`` ``ATTN_PIECES``; ``ops/`` imports no model, and a
#: test holds the two names to that list).  ``attn.kernel``: the kernel
#: calls, or the plain ``jnp`` form where that stands for them;
#: ``attn.layout``: what is done around the calls that is no kernel.  A
#: function under ``traced_once`` is traced once a family, so these cost
#: a handful of ``with`` statements a step, not a layer
_LAYOUT, _KERNEL = "attn.layout", "attn.kernel"


def _clamp_k_tile(j, i, block_q: int, block_k: int,
                  window: Optional[int] = None):
    """Causal DMA elision: clamp streaming K-tile index ``j`` to the last
    tile intersecting Q-tile ``i``'s causal triangle — fully-masked grid
    steps then revisit the previous block and pallas skips the copy."""
    last = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
    if window is None:
        return last
    # ... and from below to the first tile inside the window of the
    # tile's earliest query
    return jnp.maximum(
        last, jnp.maximum(i * block_q - (window - 1), 0) // block_k)


def _clamp_q_tile(j, i, block_q: int, block_k: int,
                  window: Optional[int] = None):
    """Causal DMA elision, reversed grid: clamp streaming Q-tile index
    ``j`` to the first tile intersecting K-tile ``i``'s causal triangle."""
    jmin = -((block_q - 1 - i * block_k) // block_q)
    first = jnp.maximum(j, jnp.maximum(jmin, 0))
    if window is None:
        return first
    # ... and from above to the last Q tile that still sees the K
    # tile's last key through the window
    return jnp.minimum(
        first, (i * block_k + block_k - 1 + window - 1) // block_q)


def _keep_mask(q_offset, k_offset, block_q: int, block_k: int,
               window: Optional[int] = None):
    """[block_q, block_k] bool: key visible to query (causal, and inside
    the window when there is one)."""
    q_pos = q_offset + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_offset + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return keep


def _tile_live(causal: bool, q_offset, k_offset, block_q: int,
               block_k: int, window: Optional[int] = None):
    """Does the (Q tile, K tile) pair hold any visible (query, key)?
    Not above the diagonal, and not wholly before the window of the
    tile's earliest query."""
    live = jnp.logical_or(not causal, k_offset <= q_offset + block_q - 1)
    if window is not None:
        live = jnp.logical_and(
            live, k_offset + block_k - 1 >= q_offset - (window - 1))
    return live


class _Part(NamedTuple):
    """One product of a (Q tile, K tile) pair: ``rows`` of the Q tile
    against ``cols`` of the K tile (slices of the blocks in VMEM), the
    product's side lengths, and its mask.  ``keep`` builds the mask when
    the kernel body asks (``None``: every pair is visible); it covers the
    whole product, or, for a part of a cut tile, only the ``sub x sub``
    ``corner`` the mask's edge runs through: (axis of the scores, at its
    end?), the rest of such a part being visible whole."""
    rows: slice
    cols: slice
    n_rows: int
    n_cols: int
    keep: Optional[Callable[[], jax.Array]] = None
    corner: Optional[Tuple[int, bool]] = None


def _part_keep(part: _Part):
    return None if part.keep is None else part.keep()


def _hide(s, keep, part: _Part):
    """Scores ``[n_rows, n_cols]`` with the part's hidden pairs at
    ``NEG_INF`` (``keep`` is what ``_part_keep`` gave)."""
    if keep is None:
        return s
    if keep.shape == s.shape:  # the whole tile's mask, or a part all corner
        return jnp.where(keep, s, NEG_INF)
    # only the corner pays for the select; the cut falls on whole vregs
    axis, at_end = part.corner
    size, sub = s.shape[axis], keep.shape[axis]
    split = size - sub if at_end else sub
    a = lax.slice_in_dim(s, 0, split, axis=axis)
    b = lax.slice_in_dim(s, split, size, axis=axis)
    if at_end:
        b = jnp.where(keep, b, NEG_INF)
    else:
        a = jnp.where(keep, a, NEG_INF)
    return jnp.concatenate([a, b], axis=axis)


def _lanes(parts, op):
    """``[rows_i, cols_i]`` each ``-> [sum(rows_i), 128]``: ``op``
    folded over the whole vregs along each row, nothing moved across
    lanes (narrower than 128 only where a test cuts smaller blocks)."""
    width = math.gcd(128, *[x.shape[1] for x in parts])
    return jnp.concatenate([
        functools.reduce(op, [x[:, i:i + width]
                              for i in range(0, x.shape[1], width)])
        for x in parts], axis=0)


def _row_max(scores):
    """``[block_q, 1]`` row maxima of a tile from the scores of its
    parts (row-blocks, in order).  A forward kernel takes a cut tile
    stage by stage: every block's scores, ONE maximum over all the
    tile's rows, every block's ``exp`` and ``p v``, ONE sum
    (``_row_sum``).  A block of its own would pay the reduction across
    lanes in a loop of 128 rows, too short to hide its latency: that
    way the forward gained nothing from the fewer products (PERF.md
    section 6, PR 34).  So each block folds its vregs elementwise and
    the tile's rows are reduced across lanes at once."""
    if len(scores) == 1:
        return scores[0].max(axis=-1)[:, None]
    return _lanes(scores, jnp.maximum).max(axis=-1)[:, None]


def _row_sum(probs):
    """``[block_q, 1]`` row sums, as ``_row_max``."""
    if len(probs) == 1:
        return probs[0].sum(axis=-1)[:, None]
    return _lanes(probs, jnp.add).sum(axis=-1)[:, None]


def _rows_of(x, part: _Part, parts):
    """A tile's row statistic ``[block_q, 1]`` at the rows of one of its
    parts."""
    return x if len(parts) == 1 else x[part.rows]


def _cut_blocks(block_q: int, block_k: int, forward: bool = False) -> int:
    """Into how many blocks a tile that the mask cuts is split (1: it is
    worked whole under its mask): ``CUT_BLOCKS``, or in a forward kernel
    ``CUT_BLOCKS_FORWARD``, halved until a block is whole ``CUT_MIN``
    rows; square tiles only."""
    if block_q != block_k:
        return 1
    n = CUT_BLOCKS_FORWARD if forward else CUT_BLOCKS
    while n > 1 and (block_q % n or (block_q // n) % CUT_MIN):
        n //= 2
    return n


def _cut_parts(edge: bool, walk: str, block: int, n: int):
    """The ``n`` products a cut ``block x block`` tile is worked as.

    The diagonal tile keeps its lower triangle (``edge=False``), the
    tile the window's lower edge cuts its strict upper one.  ``walk``
    says whose blocks the kernel's accumulators follow: ``"q"`` (forward
    and dQ: row-block ``b`` of the Q tile against the keys it can see),
    ``"k"`` (dK/dV: column-block ``b`` of the K tile against the queries
    that can see it).  What a block sees is a static slice of the other
    tile, and only the ``sub x sub`` corner on the mask's edge is
    masked."""
    sub = block // n

    def corner_keep():
        i = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        j = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        return i < j if edge else i >= j

    # the lower triangle's rows see keys from the tile's start, its
    # columns are seen by queries up to the tile's end; the upper
    # triangle mirrors both
    from_start = edge != (walk == "q")
    parts = []
    for b in range(n):
        own = slice(b * sub, (b + 1) * sub)
        lo, hi = (0, (b + 1) * sub) if from_start else (b * sub, block)
        if walk == "q":
            parts.append(_Part(own, slice(lo, hi), sub, hi - lo,
                               corner_keep, (1, from_start)))
        else:
            parts.append(_Part(slice(lo, hi), own, hi - lo, sub,
                               corner_keep, (0, from_start)))
    return parts


def _cut_kinds(block_q: int, block_k: int, window: Optional[int],
               forward: bool = False):
    """(blocks a cut tile is split in, is the diagonal tile cut so, is
    the window's edge tile): static, from the tile and the window.  On
    square tiles the diagonal runs corner to corner through the tiles
    ``iq == ik`` and through no other; that tile is purely causal unless
    the window is shorter than it.  The window's edge does the same
    through the tiles ``iq - ik == window / block`` when the window is
    whole tiles, and through two tiles a row otherwise."""
    n = _cut_blocks(block_q, block_k, forward)
    diag = n > 1 and (window is None or window >= block_q)
    edge = diag and window is not None and window % block_k == 0
    return n, diag, edge


def _causal_dispatch(causal: bool, q_offset, k_offset, block_q: int,
                     block_k: int, tile, window: Optional[int] = None,
                     walk: str = "q", forward: bool = False):
    """Run ``tile(part)`` (a forward kernel: ``tile(parts)``, a tile's
    parts together) under the causal tile classification:
    fully-visible tiles run whole with no mask, fully-masked tiles run
    nothing, and a tile the mask cuts is worked in blocks over what
    each block can see (``_cut_parts``) where the cut is the diagonal's
    or a whole-tile window's on square tiles; any other straddling tile
    gets the (iota + compare + select) mask over the whole tile.  The
    predicates are mutually exclusive and their union equals the old
    "not fully masked" gate, so no tile is dropped or run twice."""
    from jax.experimental import pallas as pl

    def whole(keep=None):
        part = _Part(slice(None), slice(None), block_q, block_k, keep)
        tile([part] if forward else part)

    def masked():
        whole(lambda: _keep_mask(q_offset, k_offset, block_q, block_k,
                                 window))

    def cut(edge: bool):
        def run():
            parts = _cut_parts(edge, walk, block_q, n)
            if forward:
                tile(parts)
            else:
                for part in parts:
                    tile(part)
        return run

    if not causal:
        whole()
        return
    straddles = jnp.logical_and(k_offset <= q_offset + block_q - 1,
                                k_offset + block_k - 1 > q_offset)
    fully_visible = k_offset + block_k - 1 <= q_offset
    if window is not None:
        # the window's lower edge cuts through the tile when its first
        # key lies before the window of the tile's LAST query
        live = _tile_live(True, q_offset, k_offset, block_q, block_k,
                          window)
        edge = k_offset < q_offset + block_q - window
        straddles = jnp.logical_and(
            live, jnp.logical_or(straddles,
                                 jnp.logical_and(fully_visible, edge)))
        fully_visible = jnp.logical_and(
            live, jnp.logical_and(fully_visible, jnp.logical_not(edge)))
    n, cut_diag, cut_edge = _cut_kinds(block_q, block_k, window, forward)
    if cut_diag:
        on_diag = q_offset == k_offset
        pl.when(on_diag)(cut(False))
        if cut_edge:
            pl.when(q_offset - k_offset == window)(cut(True))
        elif window is not None:
            pl.when(jnp.logical_and(straddles,
                                    jnp.logical_not(on_diag)))(masked)
    else:
        pl.when(straddles)(masked)
    pl.when(fully_visible)(whole)


def _plan_args(family: str, q, seq_k: int, block_q: int, block_k: int,
               causal: bool, window: Optional[int]) -> Dict[str, Any]:
    """What a call's kernels were compiled to do, for the ``flash.plan``
    span: counts from shapes, for one (batch, head), by the rules of
    ``_causal_dispatch``.  ``tiles_live`` (Q tile, K tile) pairs hold a
    visible (query, key); ``tiles_cut`` of them are worked in blocks of
    ``sub`` rows by dK/dV and dQ and of ``sub_forward`` by the forward
    (the diagonal's and the window's edge's, where they are cut so);
    ``pairs_worked`` (query, key) pairs are multiplied by a backward
    kernel, ``pairs_worked_forward`` by the forward, for the
    ``pairs_visible`` that count: their quotient is what the cutting is
    for."""
    seq_q = q.shape[1]
    block_q, block_k = min(block_q, seq_q), min(block_k, seq_k)
    # (blocks, diagonal tile cut, edge tile cut) of the backward kernels
    # and of the forward
    kinds = [_cut_kinds(block_q, block_k, window, forward) if causal
             else (1, False, False) for forward in (False, True)]
    in_cut = [sum(p.n_rows * p.n_cols
                  for p in _cut_parts(False, "q", block_q, n))
              for n, _, _ in kinds]
    live = cut = 0
    worked = [0, 0]
    for q0 in range(0, seq_q, block_q):
        for k0 in range(0, seq_k, block_k):
            if causal and (k0 > q0 + block_q - 1 or (
                    window is not None
                    and k0 + block_k - 1 < q0 - (window - 1))):
                continue
            live += 1
            in_blocks = [(diag and q0 == k0)
                         or (edge and q0 - k0 == window)
                         for _, diag, edge in kinds]
            cut += in_blocks[0]
            for i, yes in enumerate(in_blocks):
                worked[i] += in_cut[i] if yes else block_q * block_k
    if causal:
        last = np.minimum(np.arange(seq_q), seq_k - 1)  # a query's last key
        first = np.zeros_like(last) if window is None \
            else np.maximum(last - (window - 1), 0)
        visible = int((last - first + 1).sum())
    else:
        visible = seq_q * seq_k
    return {"family": family, "heads": q.shape[2], "width": q.shape[3],
            "seq": seq_q,
            "block": block_q if block_q == block_k
            else f"{block_q}x{block_k}",
            "window": window or 0, "sub": block_q // kinds[0][0],
            "sub_forward": block_q // kinds[1][0], "tiles_live": live,
            "tiles_cut": cut, "pairs_worked": worked[0],
            "pairs_worked_forward": worked[1], "pairs_visible": visible}


def _attention_reference(q, k, v, causal: bool, scale: float,
                         window: Optional[int] = None) -> jax.Array:
    with jax.named_scope(_KERNEL):  # it stands where the kernels would
        group = q.shape[2] // k.shape[2]
        if group > 1:  # query head h reads K/V head h // group
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            tq, tk = q.shape[1], k.shape[1]
            mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
            if window is not None:
                mask = jnp.logical_and(
                    mask, jnp.triu(jnp.ones((tq, tk), bool),
                                   tk - tq - (window - 1)))
            s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        return out.astype(q.dtype)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
               acc_ref, *, scale: float, causal: bool, block_q: int,
               block_k: int, window: Optional[int] = None):
    """Forward tile program: grid (B, H, q_tiles, k_tiles); the k axis
    is sequential ("arbitrary"), so the online-softmax stats live in
    VMEM scratch across its iterations.  Only one K/V tile is resident
    per step — VMEM stays O(block) at any sequence length."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_offset = iq * block_q
    k_offset = ik * block_k

    # causal: tiles entirely above the diagonal contribute nothing — the
    # compute is gated off here, and the K/V index maps clamp those grid
    # steps to the diagonal tile so their DMAs are skipped too (pallas
    # elides the copy when consecutive steps map to the same block)
    def _tile(parts):
        scores = []
        for part in parts:
            s = jax.lax.dot_general(
                q_ref[part.rows], k_ref[part.cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            scores.append(_hide(s, _part_keep(part), part))
        m = m_ref[:]            # [bq, 1]
        m_new = jnp.maximum(m, _row_max(scores))
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        # masked entries: exp(-1e30 - safe_m) underflows to exactly 0.0,
        # so no [bq, bk] guard select is needed
        probs = [jnp.exp(s - _rows_of(safe_m, part, parts))
                 for part, s in zip(parts, scores)]
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
        l_ref[:] = l_ref[:] * corr + _row_sum(probs)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr + jnp.concatenate(
            [jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[part.cols],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
             for part, p in zip(parts, probs)], axis=0)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window, forward=True)

    @pl.when(ik == n_k - 1)
    def _finish():
        m = m_ref[:][:, 0]
        l = l_ref[:][:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[:] = jnp.where(
            m <= NEG_INF / 2, NEG_INF, m + jnp.log(l_safe)
        ).astype(jnp.float32)[:, None]


def _kv_head(h, group: int):
    """Block index of the K/V head that query head ``h`` reads."""
    return h if group == 1 else h // group


@traced_once("causal", "scale", "block_q", "block_k", "interpret",
              "out_dtype", "window")
def _flash_forward(q, k, v, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool,
                   out_dtype=None, window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k = k.shape[1]
    group = heads // k.shape[2]
    # pallas layout: [B, H, T, D]
    with jax.named_scope(_LAYOUT):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)

    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, (
        f"sequence lengths ({seq_q}, {seq_k}) must divide into blocks "
        f"({block_q}, {block_k})")

    grid = (batch, heads, seq_q // block_q, seq_k // block_k)
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               window=window)

    if causal:
        # above-diagonal K/V tiles are fully masked — causal touches
        # ~half the tiles' bandwidth instead of all of them
        def kv_idx(b, h, i, j):
            return (b, _kv_head(h, group),
                    _clamp_k_tile(j, i, block_q, block_k, window), 0)
    else:
        def kv_idx(b, h, i, j):
            return (b, _kv_head(h, group), j, 0)

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, dim), kv_idx),
            pl.BlockSpec((None, None, block_k, dim), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, dim), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        out, lse = call(qt, kt, vt)
    with jax.named_scope(_LAYOUT):
        return out.transpose(0, 2, 1, 3), lse


def _fa_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                        causal: bool, block_q: int, block_k: int,
                        window: Optional[int] = None, q_tiles: int = 0):
    """dK/dV: grid (B, Hkv, k_tiles, group * q_tiles); the last axis is
    sequential — the Q tiles of each query head of the K/V head's group
    in turn (``q_tiles`` is given when the group has more than one) —
    with the dK/dV accumulators in scratch."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)
    iq = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k_offset = ik * block_k
    q_offset = (iq % q_tiles if q_tiles else iq) * block_q

    def _tile(part: _Part):
        rows, cols = part.rows, part.cols
        k = k_ref[cols]
        v = v_ref[cols]
        q = q_ref[rows]
        do = do_ref[rows]
        lse = lse_ref[rows][:, 0]
        delta = delta_ref[rows][:, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _hide(s, _part_keep(part), part)
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # clamp: keeps
        p = jnp.exp(s - lse[:, None])  # fully-masked rows at p == 0
        dv_acc[cols] = dv_acc[cols] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[cols] = dk_acc[cols] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window, walk="k")

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *, scale: float, causal: bool,
                      block_q: int, block_k: int,
                      window: Optional[int] = None):
    """dQ: grid (B, H, q_tiles, k_tiles); k sequential, dQ in scratch."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_offset = iq * block_q
    k_offset = ik * block_k

    def _tile(part: _Part):
        rows = part.rows
        q = q_ref[rows]
        do = do_ref[rows]
        lse = lse_ref[rows][:, 0]
        delta = delta_ref[rows][:, 0]
        k = k_ref[part.cols]
        v = v_ref[part.cols]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _hide(s, _part_keep(part), part)
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # clamp: keeps
        p = jnp.exp(s - lse[:, None])  # fully-masked rows at p == 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[rows] = dq_acc[rows] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


@traced_once("causal", "scale", "block_q", "block_k", "interpret",
              "grad_dtype", "window")
def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret, grad_dtype=None, delta=None,
                    window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k = k.shape[1]
    kv_heads = k.shape[2]
    group = heads // kv_heads
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    n_q = seq_q // block_q
    with jax.named_scope(_LAYOUT):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        dot = g.transpose(0, 2, 1, 3)
        if delta is None:
            # delta_i = rowsum(dO_i * O_i) (FlashAttention-2 eq. for
            # dS); [B,H,S,1] like lse (TPU blocks need >=2 trailing dims)
            delta = jnp.sum(dot.astype(jnp.float32)
                            * out.transpose(0, 2, 1, 3).astype(jnp.float32),
                            axis=-1, keepdims=True)

    seq_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    def q_of(h, j):
        """dK/dV grid -> (query head, Q tile): K/V head ``h`` walks its
        group's query heads one after the other along ``j``."""
        return (h, j) if group == 1 else (h * group + j // n_q, j % n_q)

    # causal DMA elision (same trick as the forward)
    if causal:
        def q_idx_rev(b, h, i, j):  # dK/dV grid: i = k tile, j = q tile
            hq, jq = q_of(h, j)
            return (b, hq,
                    _clamp_q_tile(jq, i, block_q, block_k, window), 0)

        def kv_idx_fwd(b, h, i, j):  # dQ grid: i = q tile, j = k tile
            return (b, _kv_head(h, group),
                    _clamp_k_tile(j, i, block_q, block_k, window), 0)
    else:
        def q_idx_rev(b, h, i, j):
            hq, jq = q_of(h, j)
            return (b, hq, jq, 0)

        def kv_idx_fwd(b, h, i, j):
            return (b, _kv_head(h, group), j, 0)

    tile_q = pl.BlockSpec((None, None, block_q, dim), q_idx_rev)
    tile_k_rev = pl.BlockSpec((None, None, block_k, dim),
                              lambda b, h, i, j: (b, h, i, 0))
    rows_q_rev = pl.BlockSpec((None, None, block_q, 1), q_idx_rev)
    dkdv = functools.partial(_fa_bwd_dkdv_kernel, scale=scale,
                             causal=causal, block_q=block_q,
                             block_k=block_k, window=window,
                             q_tiles=0 if group == 1 else n_q)
    dkdv_call = pl.pallas_call(
        dkdv,
        grid=(batch, kv_heads, seq_k // block_k, group * n_q),
        in_specs=[tile_q, tile_k_rev, tile_k_rev, tile_q, rows_q_rev,
                  rows_q_rev],
        out_specs=[tile_k_rev, tile_k_rev],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, grad_dtype or k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, grad_dtype or v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dim), jnp.float32),
                        pltpu.VMEM((block_k, dim), jnp.float32)],
        compiler_params=seq_params,
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        dk, dv = dkdv_call(qt, kt, vt, dot, lse, delta)

    tile_q_fwd = pl.BlockSpec((None, None, block_q, dim),
                              lambda b, h, i, j: (b, h, i, 0))
    tile_k_fwd = pl.BlockSpec((None, None, block_k, dim), kv_idx_fwd)
    rows_q_fwd = pl.BlockSpec((None, None, block_q, 1),
                              lambda b, h, i, j: (b, h, i, 0))
    dq_kernel = functools.partial(_fa_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, window=window)
    dq_call = pl.pallas_call(
        dq_kernel,
        grid=(batch, heads, seq_q // block_q, seq_k // block_k),
        in_specs=[tile_q_fwd, tile_k_fwd, tile_k_fwd, tile_q_fwd,
                  rows_q_fwd, rows_q_fwd],
        out_specs=tile_q_fwd,
        out_shape=jax.ShapeDtypeStruct(qt.shape, grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
        compiler_params=seq_params,
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        dq = dq_call(qt, kt, vt, dot, lse, delta)

    with jax.named_scope(_LAYOUT):
        return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
                dv.transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# Native-layout ("NL") kernels: consume [B, T, H, D] directly.
#
# The kernels above want [B, H, T, D]; XLA materializes layout transposes
# around the custom-calls to provide it.  Consuming [B,T,H,D] with the
# head inside the block does not tile: (H=12, D=64) trailing dims pad to
# (16, 128), a 2.7x VMEM inflation that overflows scoped vmem at useful
# block sizes.
#
# The NL kernels sidestep the padding instead of fighting it: collapse
# the two minor dims with a free reshape [B,T,H,D] -> [B,T,H*D] and tile
# [block, 128] slabs whose lane slice at h2*128 is tile-aligned — each
# 128-lane slab packs ``pack = 128//D`` heads side by side (2 for D=64,
# 1 for D=128).  Per-head score separation inside a packed slab needs no
# cross-lane shuffles:
#
#   s_h  = dot(q * lane_mask_h, k)   contracting all 128 lanes
#   o_h  = dot(p_h, v) * lane_mask_h ditto for dv/dk/dq contributions
#
# The masked full-width contractions cost the MXU nothing vs the
# per-head kernels above: a K=64 contraction only half-fills the
# 128-deep systolic array, so two masked K=128 matmuls == two K=64
# matmuls in wall-clock, and the lane masks are VPU broadcast
# multiplies.  Softmax statistics ride in per-head [block_q, 1] scratch
# (sublane vectors — lane-broadcastable with no per-iteration relayout);
# LSE/delta travel between forward and backward as [B, H2, T, pack]
# (T in sublanes for the same reason).
#
# Reference anchor: net-new TPU territory (SURVEY §2.5) — the reference's
# flash attention is a CUDA kernel with its own layout constraints.
# ---------------------------------------------------------------------------


def _lane_mask(h: int, pack: int, dim: int, rows: int, dtype):
    """[rows, 128] mask selecting head ``h``'s lanes within a packed slab."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, pack * dim), 1)
    return jnp.logical_and(lane >= h * dim, lane < (h + 1) * dim).astype(dtype)


def _head_sel(pack: int, dim: int, rows: int):
    """[rows, pack*dim] bool: True on head 0's lanes (pack==2 only)."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, pack * dim), 1)
    return lane < dim


def _fa_nl_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                  scale: float, causal: bool, block_q: int,
                  block_k: int, pack: int, dim: int,
                  window: Optional[int] = None):
    """Native-layout forward: grid (B, H2, q_tiles, k_tiles), k sequential.

    Refs are [block, pack*dim] slabs; head ``h`` of the slab lives in
    lanes [h*dim, (h+1)*dim).  Per-head online-softmax stats are [bq, 1]
    sublane vectors (m_h, l_h) — the layout the VPU broadcasts along
    lanes for free, so nothing relayouts per k-iteration.
    """
    from jax.experimental import pallas as pl

    m_refs = scratch[:pack]
    l_refs = scratch[pack:2 * pack]
    acc_ref = scratch[2 * pack]

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        for h in range(pack):
            m_refs[h][:] = jnp.full_like(m_refs[h], NEG_INF)
            l_refs[h][:] = jnp.zeros_like(l_refs[h])
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_offset = iq * block_q
    k_offset = ik * block_k

    def _tile(parts):
        qs = [q_ref[part.rows] for part in parts]
        ks = [k_ref[part.cols] for part in parts]
        vs = [v_ref[part.cols] for part in parts]
        keeps = [_part_keep(part) for part in parts]
        corrs = []
        pvs = []
        for h in range(pack):
            scores = []
            for part, q, k, keep in zip(parts, qs, ks, keeps):
                qh = q * _lane_mask(h, pack, dim, part.n_rows, q.dtype) if pack > 1 else q
                s = jax.lax.dot_general(
                    qh, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                scores.append(_hide(s, keep, part))
            m = m_refs[h][:]            # [bq, 1]
            l = l_refs[h][:]
            m_new = jnp.maximum(m, _row_max(scores))
            safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            # masked entries: exp(-1e30 - safe_m) underflows to exactly
            # 0.0, so no [bq, bk] guard select is needed
            probs = [jnp.exp(s - _rows_of(safe_m, part, parts))
                     for part, s in zip(parts, scores)]
            corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
            l_refs[h][:] = l * corr + _row_sum(probs)
            m_refs[h][:] = m_new
            pv = [jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                for p, v in zip(probs, vs)]
            corrs.append(corr)
            pvs.append(pv[0] if len(pv) == 1
                       else jnp.concatenate(pv, axis=0))
        if pack == 1:
            acc_ref[:] = acc_ref[:] * corrs[0] + pvs[0]
        else:
            sel = _head_sel(pack, dim, block_q)
            acc_ref[:] = (acc_ref[:] * jnp.where(sel, corrs[0], corrs[1])
                          + jnp.where(sel, pvs[0], pvs[1]))

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window, forward=True)

    @pl.when(ik == n_k - 1)
    def _finish():
        divs = []
        lses = []
        for h in range(pack):
            l = l_refs[h][:]
            m = m_refs[h][:]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            divs.append(l_safe)
            lses.append(jnp.where(m <= NEG_INF / 2, NEG_INF,
                                  m + jnp.log(l_safe)))
        if pack == 1:
            o_ref[:] = (acc_ref[:] / divs[0]).astype(o_ref.dtype)
            lse_ref[:] = lses[0].astype(jnp.float32)
        else:
            sel = _head_sel(pack, dim, block_q)
            o_ref[:] = (acc_ref[:] /
                        jnp.where(sel, divs[0], divs[1])).astype(o_ref.dtype)
            lse_ref[:] = jnp.concatenate(lses, axis=1).astype(jnp.float32)


@traced_once("causal", "scale", "block_q", "block_k", "interpret",
              "out_dtype", "window")
def _flash_nl_forward(q, k, v, causal: bool, scale: float,
                      block_q: int, block_k: int, interpret: bool,
                      out_dtype=None, window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k = k.shape[1]
    pack = 128 // dim
    h2 = heads // pack
    # grouped heads (one head a slab only, ``_nl_eligible``): query
    # slab h reads K/V slab h // group
    group = heads // k.shape[2]
    # free reshapes: collapse the contiguous minor dims
    with jax.named_scope(_LAYOUT):
        qr = q.reshape(batch, seq_q, h2 * pack * dim)
        kr = k.reshape(batch, seq_k, k.shape[2] * dim)
        vr = v.reshape(batch, seq_k, k.shape[2] * dim)

    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, (
        f"sequence lengths ({seq_q}, {seq_k}) must divide into blocks "
        f"({block_q}, {block_k})")

    grid = (batch, h2, seq_q // block_q, seq_k // block_k)
    kernel = functools.partial(_fa_nl_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               pack=pack, dim=dim, window=window)

    if causal:
        def kv_idx(b, h, i, j):
            return (b, _clamp_k_tile(j, i, block_q, block_k, window),
                    _kv_head(h, group))
    else:
        def kv_idx(b, h, i, j):
            return (b, j, _kv_head(h, group))

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, pack * dim),
                         lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((None, block_k, pack * dim), kv_idx),
            pl.BlockSpec((None, block_k, pack * dim), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, pack * dim),
                         lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((None, None, block_q, pack),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qr.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct((batch, h2, seq_q, pack), jnp.float32),
        ],
        scratch_shapes=(
            [pltpu.VMEM((block_q, 1), jnp.float32)] * pack     # running max
            + [pltpu.VMEM((block_q, 1), jnp.float32)] * pack   # running sum
            + [pltpu.VMEM((block_q, pack * dim), jnp.float32)]  # accumulator
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        out, lse = call(qr, kr, vr)
    with jax.named_scope(_LAYOUT):
        return out.reshape(q.shape), lse


def _fa_nl_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                           causal: bool, block_q: int, block_k: int,
                           pack: int, dim: int,
                           window: Optional[int] = None, q_tiles: int = 0):
    """NL dK/dV: grid (B, Hkv2, k_tiles, group * q_tiles); the last axis
    sequential: the Q tiles of each query slab of the group in turn
    (``q_tiles`` is given when the group has more than one)."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)
    iq = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k_offset = ik * block_k
    q_offset = (iq % q_tiles if q_tiles else iq) * block_q

    def _tile(part: _Part):
        rows, cols = part.rows, part.cols
        q = q_ref[rows]
        k = k_ref[cols]
        v = v_ref[cols]
        do = do_ref[rows]
        keep = _part_keep(part)
        pdos = []
        dsqs = []
        for h in range(pack):
            mask_q = (_lane_mask(h, pack, dim, part.n_rows, q.dtype)
                      if pack > 1 else None)
            qh = q * mask_q if pack > 1 else q
            doh = do * mask_q if pack > 1 else do
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = _hide(s, keep, part)
            lse = lse_ref[rows][:, h:h + 1]     # [rows, 1]
            delta = delta_ref[rows][:, h:h + 1]
            lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # [bq, 1]
            p = jnp.exp(s - lse)  # clamp keeps fully-masked rows at p == 0
            pdo = jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                doh, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dsq = jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pdos.append(pdo)
            dsqs.append(dsq)
        if pack == 1:
            dv_acc[cols] = dv_acc[cols] + pdos[0]
            dk_acc[cols] = dk_acc[cols] + dsqs[0]
        else:
            sel = _head_sel(pack, dim, part.n_cols)
            dv_acc[cols] = dv_acc[cols] + jnp.where(sel, pdos[0], pdos[1])
            dk_acc[cols] = dk_acc[cols] + jnp.where(sel, dsqs[0], dsqs[1])

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window, walk="k")

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _fa_nl_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, scale: float, causal: bool,
                         block_q: int, block_k: int, pack: int, dim: int,
                         window: Optional[int] = None):
    """NL dQ: grid (B, H2, q_tiles, k_tiles); k sequential."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_offset = iq * block_q
    k_offset = ik * block_k

    def _tile(part: _Part):
        rows = part.rows
        q = q_ref[rows]
        k = k_ref[part.cols]
        v = v_ref[part.cols]
        do = do_ref[rows]
        keep = _part_keep(part)
        dsks = []
        for h in range(pack):
            mask_q = (_lane_mask(h, pack, dim, part.n_rows, q.dtype)
                      if pack > 1 else None)
            qh = q * mask_q if pack > 1 else q
            doh = do * mask_q if pack > 1 else do
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = _hide(s, keep, part)
            lse = lse_ref[rows][:, h:h + 1]     # [rows, 1]
            delta = delta_ref[rows][:, h:h + 1]
            lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # [bq, 1]
            p = jnp.exp(s - lse)  # clamp keeps fully-masked rows at p == 0
            dp = jax.lax.dot_general(
                doh, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dsk = jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dsks.append(dsk)
        if pack == 1:
            dq_acc[rows] = dq_acc[rows] + dsks[0]
        else:
            sel = _head_sel(pack, dim, part.n_rows)
            dq_acc[rows] = dq_acc[rows] + jnp.where(sel, dsks[0], dsks[1])

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


@traced_once("causal", "scale", "block_q", "block_k", "interpret",
              "grad_dtype", "window")
def _flash_nl_backward(q, k, v, out, lse, g, causal, scale, block_q,
                       block_k, interpret, grad_dtype=None, delta=None,
                       window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k = k.shape[1]
    pack = 128 // dim
    h2 = heads // pack
    kv_heads = k.shape[2]
    group = heads // kv_heads
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    n_q = seq_q // block_q
    with jax.named_scope(_LAYOUT):
        qr = q.reshape(batch, seq_q, heads * dim)
        kr = k.reshape(batch, seq_k, kv_heads * dim)
        vr = v.reshape(batch, seq_k, kv_heads * dim)
        gr = g.reshape(batch, seq_q, heads * dim)
        if delta is None:
            # delta_i = rowsum(dO_i * O_i), laid out [B, H2, T, pack]
            # like lse (T in sublanes so per-head columns broadcast along
            # lanes without relayout); XLA fuses the product+reduce
            delta = (jnp.sum(g.astype(jnp.float32)
                             * out.astype(jnp.float32),
                             axis=-1)                  # [B, T, H]
                     .reshape(batch, seq_q, h2, pack)
                     .transpose(0, 2, 1, 3))           # [B, H2, T, pack]

    seq_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    def q_of(h, j):
        """dK/dV grid -> (query slab, Q tile): K/V slab ``h`` walks its
        group's query slabs one after the other along ``j``."""
        return (h, j) if group == 1 else (h * group + j // n_q, j % n_q)

    if causal:
        def q_idx_rev(b, h, i, j):  # dK/dV grid: i = k tile, j = q tile
            hq, jq = q_of(h, j)
            return (b, _clamp_q_tile(jq, i, block_q, block_k, window), hq)

        def rows_idx_rev(b, h, i, j):
            hq, jq = q_of(h, j)
            return (b, hq,
                    _clamp_q_tile(jq, i, block_q, block_k, window), 0)

        def kv_idx_fwd(b, h, i, j):  # dQ grid: i = q tile, j = k tile
            return (b, _clamp_k_tile(j, i, block_q, block_k, window),
                    _kv_head(h, group))
    else:
        def q_idx_rev(b, h, i, j):
            hq, jq = q_of(h, j)
            return (b, jq, hq)

        def rows_idx_rev(b, h, i, j):
            hq, jq = q_of(h, j)
            return (b, hq, jq, 0)

        def kv_idx_fwd(b, h, i, j):
            return (b, j, _kv_head(h, group))

    slab = pack * dim
    tile_q = pl.BlockSpec((None, block_q, slab), q_idx_rev)
    tile_k_rev = pl.BlockSpec((None, block_k, slab),
                              lambda b, h, i, j: (b, i, h))
    rows_q_rev = pl.BlockSpec((None, None, block_q, pack), rows_idx_rev)
    dkdv = functools.partial(_fa_nl_bwd_dkdv_kernel, scale=scale,
                             causal=causal, block_q=block_q,
                             block_k=block_k, pack=pack, dim=dim,
                             window=window,
                             q_tiles=0 if group == 1 else n_q)
    dkdv_call = pl.pallas_call(
        dkdv,
        grid=(batch, kv_heads // pack, seq_k // block_k, group * n_q),
        in_specs=[tile_q, tile_k_rev, tile_k_rev, tile_q, rows_q_rev,
                  rows_q_rev],
        out_specs=[tile_k_rev, tile_k_rev],
        out_shape=[jax.ShapeDtypeStruct(kr.shape, grad_dtype or k.dtype),
                   jax.ShapeDtypeStruct(vr.shape, grad_dtype or v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, slab), jnp.float32),
                        pltpu.VMEM((block_k, slab), jnp.float32)],
        compiler_params=seq_params,
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        dk, dv = dkdv_call(qr, kr, vr, gr, lse, delta)

    tile_q_fwd = pl.BlockSpec((None, block_q, slab),
                              lambda b, h, i, j: (b, i, h))
    tile_k_fwd = pl.BlockSpec((None, block_k, slab), kv_idx_fwd)
    rows_q_fwd = pl.BlockSpec((None, None, block_q, pack),
                              lambda b, h, i, j: (b, h, i, 0))
    dq_kernel = functools.partial(_fa_nl_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, pack=pack, dim=dim,
                                  window=window)
    dq_call = pl.pallas_call(
        dq_kernel,
        grid=(batch, h2, seq_q // block_q, seq_k // block_k),
        in_specs=[tile_q_fwd, tile_k_fwd, tile_k_fwd, tile_q_fwd,
                  rows_q_fwd, rows_q_fwd],
        out_specs=tile_q_fwd,
        out_shape=jax.ShapeDtypeStruct(qr.shape, grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, slab), jnp.float32)],
        compiler_params=seq_params,
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        dq = dq_call(qr, kr, vr, gr, lse, delta)

    with jax.named_scope(_LAYOUT):
        return (dq.reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape))


# ---------------------------------------------------------------------------
# Latent attention (MLA) kernels: the key in two parts, v narrower than q.
#
# ``q [B, T, H, nope + rope]`` scores against a key that is, per head,
# ``k [B, T, H, nope]`` (up-projected from the latent) beside ONE rotary
# key head ``k_rope [B, T, 1, rope]`` that all ``H`` query heads share;
# ``v [B, T, H, dv]`` and the output are ``dv`` wide.  Nothing is padded
# or copied in HBM: v and o keep their own width, and the shared rotary
# head is read through the block index (head 0 for every query head, the
# grouped-head idiom with a group of ``H`` on that one operand).  Inside
# a tile the two key parts are joined along lanes, so the score is one
# ``nope + rope``-deep product and dQ/dK one ``nope + rope``-wide one.
#
# Head-major like the kernels at the top (a 192-wide head is no whole
# number of 128-lane slabs), with the tile classification of the
# native-layout ones (``_causal_dispatch``: only diagonal tiles pay for
# the mask).  dK/dV walks ALL query heads of a K tile along its
# sequential axis: the per-head dK/dV accumulators are flushed at each
# head's last Q tile, the shared rotary key's gradient accumulates over
# every head and is written once.
# ---------------------------------------------------------------------------


def _mla_key(k_ref, r_ref, cols: slice):
    """The whole key ``[cols, nope + rope]`` of those rows of the K
    tile: the head's own part beside the shared rotary part."""
    return jnp.concatenate([k_ref[cols], r_ref[cols]], axis=1)


def _mla_scores(q_ref, k_ref, r_ref, part: _Part):
    """``[part.n_rows, part.n_cols]`` float32, unscaled: ONE product as
    deep as the whole key (two products, one a key part, summed before
    the softmax measured 1.8% slower a layer on a v5e: PERF.md, PR 33)."""
    return jax.lax.dot_general(
        q_ref[part.rows], _mla_key(k_ref, r_ref, part.cols),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _fa_mla_kernel(q_ref, k_ref, r_ref, v_ref, o_ref, lse_ref, m_ref,
                   l_ref, acc_ref, *, scale: float, causal: bool,
                   block_q: int, block_k: int):
    """MLA forward: grid (B, H, q_tiles, k_tiles), k sequential."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_offset = iq * block_q
    k_offset = ik * block_k

    def _tile(parts):
        vs = [v_ref[part.cols] for part in parts]
        scores = []
        for part in parts:
            s = _mla_scores(q_ref, k_ref, r_ref, part) * scale
            scores.append(_hide(s, _part_keep(part), part))
        m = m_ref[:]            # [bq, 1]
        m_new = jnp.maximum(m, _row_max(scores))
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        # masked entries underflow to exactly 0
        probs = [jnp.exp(s - _rows_of(safe_m, part, parts))
                 for part, s in zip(parts, scores)]
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
        l_ref[:] = l_ref[:] * corr + _row_sum(probs)
        m_ref[:] = m_new
        acc = acc_ref[:] * corr
        pv = [jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for p, v in zip(probs, vs)]
        acc_ref[:] = acc + (pv[0] if len(pv) == 1
                            else jnp.concatenate(pv, axis=0))

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     forward=True)

    @pl.when(ik == n_k - 1)
    def _finish():
        m = m_ref[:]
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = jnp.where(m <= NEG_INF / 2, NEG_INF,
                               m + jnp.log(l_safe)).astype(jnp.float32)


def _mla_blocks(q, k, block_q: int, block_k: int):
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, (
        f"sequence lengths ({seq_q}, {seq_k}) must divide into blocks "
        f"({block_q}, {block_k})")
    return block_q, block_k


@traced_once("causal", "scale", "block_q", "block_k", "interpret")
def _flash_mla_forward(q, k, k_rope, v, causal: bool, scale: float,
                       block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k, nope, rope, dim_v = (k.shape[1], k.shape[3], k_rope.shape[3],
                                v.shape[3])
    block_q, block_k = _mla_blocks(q, k, block_q, block_k)
    # pallas layout: [B, H, T, D]
    with jax.named_scope(_LAYOUT):
        qt, kt, rt, vt = (x.transpose(0, 2, 1, 3)
                          for x in (q, k, k_rope, v))

    def k_tile(j, i):
        return _clamp_k_tile(j, i, block_q, block_k) if causal else j

    rows_q = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    call = pl.pallas_call(
        functools.partial(_fa_mla_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(batch, heads, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, dim), rows_q),
            pl.BlockSpec((None, None, block_k, nope),
                         lambda b, h, i, j: (b, h, k_tile(j, i), 0)),
            pl.BlockSpec((None, None, block_k, rope),
                         lambda b, h, i, j: (b, 0, k_tile(j, i), 0)),
            pl.BlockSpec((None, None, block_k, dim_v),
                         lambda b, h, i, j: (b, h, k_tile(j, i), 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, dim_v), rows_q),
            pl.BlockSpec((None, None, block_q, 1), rows_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq_q, dim_v), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum
            pltpu.VMEM((block_q, dim_v), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        out, lse = call(qt, kt, rt, vt)
    with jax.named_scope(_LAYOUT):
        return out.transpose(0, 2, 1, 3), lse


def _mla_probs(q_ref, k_ref, r_ref, lse_ref, scale, keep, part: _Part):
    """``p [part.n_rows, part.n_cols]`` from the forward's row
    statistics."""
    s = _mla_scores(q_ref, k_ref, r_ref, part) * scale
    s = _hide(s, keep, part)
    lse = lse_ref[part.rows]    # [rows, 1]
    lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # clamp: keeps
    return jnp.exp(s - lse)     # fully-masked rows at p == 0


def _fa_mla_bwd_dkdv_kernel(q_ref, k_ref, r_ref, v_ref, do_ref, lse_ref,
                            delta_ref, dk_ref, dr_ref, dv_ref, dk_acc,
                            dv_acc, *, scale: float, causal: bool,
                            block_q: int, block_k: int, q_tiles: int):
    """MLA dK/dV: grid (B, k_tiles, H * q_tiles); the last axis is
    sequential: every query head in turn, its Q tiles one after the
    other.  ``dk_acc`` is ``nope + rope`` wide: its first ``nope`` lanes
    are the head's own dK, zeroed at each head's first Q tile and written
    at its last; the rest is the shared rotary key's gradient, which
    accumulates over all heads and is written at the very end."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    iq = j % q_tiles
    nope = k_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)

    @pl.when(iq == 0)
    def _init_head():
        dk_acc[:, :nope] = jnp.zeros((block_k, nope), jnp.float32)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k_offset = ik * block_k
    q_offset = iq * block_q

    def _tile(part: _Part):
        rows, cols = part.rows, part.cols
        q = q_ref[rows]
        do = do_ref[rows]
        keep = _part_keep(part)
        p = _mla_probs(q_ref, k_ref, r_ref, lse_ref, scale, keep, part)
        dv_acc[cols] = dv_acc[cols] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[rows]) * scale
        dk_acc[cols] = dk_acc[cols] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     walk="k")

    @pl.when(iq == q_tiles - 1)
    def _finish_head():
        dk_ref[:] = dk_acc[:, :nope].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(j == n_j - 1)
    def _finish():
        dr_ref[:] = dk_acc[:, nope:].astype(dr_ref.dtype)


def _fa_mla_bwd_dq_kernel(q_ref, k_ref, r_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dq_ref, dq_acc, *, scale: float,
                          causal: bool, block_q: int, block_k: int):
    """MLA dQ: grid (B, H, q_tiles, k_tiles); k sequential."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_offset = iq * block_q
    k_offset = ik * block_k

    def _tile(part: _Part):
        rows, cols = part.rows, part.cols
        keep = _part_keep(part)
        p = _mla_probs(q_ref, k_ref, r_ref, lse_ref, scale, keep, part)
        dp = jax.lax.dot_general(
            do_ref[rows], v_ref[cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[rows]) * scale
        dq_acc[rows] = dq_acc[rows] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), _mla_key(k_ref, r_ref, cols),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


@traced_once("causal", "scale", "block_q", "block_k", "interpret")
def _flash_mla_backward(q, k, k_rope, v, out, lse, g, causal, scale,
                        block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k, nope, rope, dim_v = (k.shape[1], k.shape[3], k_rope.shape[3],
                                v.shape[3])
    block_q, block_k = _mla_blocks(q, k, block_q, block_k)
    n_q = seq_q // block_q
    with jax.named_scope(_LAYOUT):
        qt, kt, rt, vt, dot = (x.transpose(0, 2, 1, 3)
                               for x in (q, k, k_rope, v, g))
        # delta_i = rowsum(dO_i * O_i), [B, H, T, 1] like lse
        delta = jnp.sum(dot.astype(jnp.float32)
                        * out.transpose(0, 2, 1, 3).astype(jnp.float32),
                        axis=-1, keepdims=True)

    def q_tile(j, i):  # dK/dV grid: i = k tile, j = head * n_q + q tile
        return _clamp_q_tile(j % n_q, i, block_q, block_k) if causal \
            else j % n_q

    def k_tile(j, i):  # dQ grid: i = q tile, j = k tile
        return _clamp_k_tile(j, i, block_q, block_k) if causal else j

    # dK/dV: the head rides the sequential axis
    walk_q = lambda b, i, j: (b, j // n_q, q_tile(j, i), 0)  # noqa: E731
    head_k = lambda b, i, j: (b, j // n_q, i, 0)             # noqa: E731
    shared_k = lambda b, i, j: (b, 0, i, 0)                  # noqa: E731
    dkdv_call = pl.pallas_call(
        functools.partial(_fa_mla_bwd_dkdv_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          q_tiles=n_q),
        grid=(batch, seq_k // block_k, heads * n_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, dim), walk_q),
            pl.BlockSpec((None, None, block_k, nope), head_k),
            pl.BlockSpec((None, None, block_k, rope), shared_k),
            pl.BlockSpec((None, None, block_k, dim_v), head_k),
            pl.BlockSpec((None, None, block_q, dim_v), walk_q),
            pl.BlockSpec((None, None, block_q, 1), walk_q),
            pl.BlockSpec((None, None, block_q, 1), walk_q),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, nope), head_k),
            pl.BlockSpec((None, None, block_k, rope), shared_k),
            pl.BlockSpec((None, None, block_k, dim_v), head_k),
        ],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(rt.shape, k_rope.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dim), jnp.float32),
                        pltpu.VMEM((block_k, dim_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        dk, dr, dv = dkdv_call(qt, kt, rt, vt, dot, lse, delta)

    rows_q = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    dq_call = pl.pallas_call(
        functools.partial(_fa_mla_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(batch, heads, n_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, dim), rows_q),
            pl.BlockSpec((None, None, block_k, nope),
                         lambda b, h, i, j: (b, h, k_tile(j, i), 0)),
            pl.BlockSpec((None, None, block_k, rope),
                         lambda b, h, i, j: (b, 0, k_tile(j, i), 0)),
            pl.BlockSpec((None, None, block_k, dim_v),
                         lambda b, h, i, j: (b, h, k_tile(j, i), 0)),
            pl.BlockSpec((None, None, block_q, dim_v), rows_q),
            pl.BlockSpec((None, None, block_q, 1), rows_q),
            pl.BlockSpec((None, None, block_q, 1), rows_q),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, dim), rows_q),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(_KERNEL):
        dq = dq_call(qt, kt, rt, vt, dot, lse, delta)

    with jax.named_scope(_LAYOUT):
        return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk, dr, dv))


def _chunk_blocks(seq_q: int, seq_k: int):
    """Ring-chunk block sizes: the default, shrunk to divisors of the
    (arbitrary) chunk lengths."""
    return fit_block(seq_q, DEFAULT_BLOCK), fit_block(seq_k, DEFAULT_BLOCK)


def _flash_chunk_fwd(q, k, v, causal: bool, scale: float,
                     interpret: bool = False):
    """Forward-only chunk attention for partial-softmax composition
    (ring attention): returns ``(out, lse)`` with ``out`` the f32
    chunk-normalized output and ``lse [B, T, H]`` the chunk's
    log-sum-exp — the pair downstream code merges across chunks with the
    standard rescaling identity.  f32 out keeps the cross-chunk
    accumulation at one rounding total (the per-tile VMEM accumulators
    are f32 already).  Kernel-dispatched like ``flash_attention`` (the
    family from the shapes, the default block) but with no autodiff
    rule: callers own the backward (the ring builds it from
    ``_flash_chunk_bwd``)."""
    batch, seq_q, heads, dim = q.shape
    block_q, block_k = _chunk_blocks(seq_q, k.shape[1])
    if _nl_eligible(q, k, v):
        out, lse = _flash_nl_forward(q, k, v, causal, scale, block_q,
                                     block_k, interpret,
                                     out_dtype=jnp.float32)
        # [B, H2, T, pack] -> [B, T, H]  (head index = h2 * pack + h)
        with jax.named_scope(_LAYOUT):
            lse = lse.transpose(0, 2, 1, 3).reshape(batch, seq_q, heads)
    else:
        out, lse = _flash_forward(q, k, v, causal, scale, block_q,
                                  block_k, interpret,
                                  out_dtype=jnp.float32)
        with jax.named_scope(_LAYOUT):
            lse = lse[..., 0].transpose(0, 2, 1)
    return out, lse


def _flash_chunk_bwd(q, k, v, out, lse, g, causal: bool, scale: float,
                     interpret: bool = False, delta=None):
    """Backward of one (Q-chunk, KV-chunk) pair given the GLOBAL row
    statistics: ``lse [B, T, H]`` must be the final merged log-sum-exp,
    ``out``/``g`` the final output / its cotangent for the Q chunk, and
    ``delta [B, T, H]`` (optional, recomputed when absent) their
    rowsum product — that is exactly what makes per-chunk backwards sum
    to the global gradient.  Returns f32 ``(dq, dk, dv)`` for exact
    cross-chunk accumulation."""
    batch, seq_q, heads, dim = q.shape
    block_q, block_k = _chunk_blocks(seq_q, k.shape[1])
    if _nl_eligible(q, k, v):
        pack = 128 // dim
        h2 = heads // pack

        def to_nl(x):
            with jax.named_scope(_LAYOUT):
                return x.reshape(batch, seq_q, h2, pack) \
                    .transpose(0, 2, 1, 3)

        return _flash_nl_backward(q, k, v, out, to_nl(lse), g, causal,
                                  scale, block_q, block_k, interpret,
                                  grad_dtype=jnp.float32,
                                  delta=None if delta is None
                                  else to_nl(delta))
    with jax.named_scope(_LAYOUT):
        lse = lse.transpose(0, 2, 1)[..., None]
        if delta is not None:
            delta = delta.transpose(0, 2, 1)[..., None]
    return _flash_backward(q, k, v, out, lse, g, causal, scale, block_q,
                           block_k, interpret, grad_dtype=jnp.float32,
                           delta=delta)


def kernel_block_for(seq: int, block: int = DEFAULT_BLOCK):
    """Fitted block size when ``seq`` divides into sublane-aligned tiles
    big enough for the flash kernels to pay off, else ``None`` — the
    shared eligibility test for sequence-parallel dispatch (ring and
    Ulysses both gate on it)."""
    fit = fit_block(seq, block)
    return fit if fit >= 128 and fit % 8 == 0 else None


def _nl_eligible(q, k, v) -> bool:
    """The NL kernels handle head_dim in {64, 128} with the head count a
    multiple of the per-slab packing factor."""
    dim = q.shape[-1]
    if dim not in (64, 128):
        return False
    pack = 128 // dim
    if q.shape[2] != k.shape[2] and pack != 1:
        # grouped heads ride the slab index: one head a slab only
        return False
    return q.shape[2] % pack == 0 and k.shape[2] % pack == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_nl(q, k, v, causal, scale, block_q, block_k, interpret,
              window=None):
    out, _ = _flash_nl_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret, window=window)
    return out


def _flash_nl_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                  window):
    out, lse = _flash_nl_forward(q, k, v, causal, scale, block_q, block_k,
                                 interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_nl_bwd(causal, scale, block_q, block_k, interpret, window, res,
                  g):
    q, k, v, out, lse = res
    return _flash_nl_backward(q, k, v, out, lse, g, causal, scale,
                              block_q, block_k, interpret, window=window)


_flash_nl.defvjp(_flash_nl_fwd, _flash_nl_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret,
           window=None):
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            interpret, window=window)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res,
               g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, scale,
                           block_q, block_k, interpret, window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_mla(q, k, k_rope, v, causal, scale, block_q, block_k,
               interpret):
    out, _ = _flash_mla_forward(q, k, k_rope, v, causal, scale, block_q,
                                block_k, interpret)
    return out


def _flash_mla_fwd(q, k, k_rope, v, causal, scale, block_q, block_k,
                   interpret):
    out, lse = _flash_mla_forward(q, k, k_rope, v, causal, scale, block_q,
                                  block_k, interpret)
    return out, (q, k, k_rope, v, out, lse)


def _flash_mla_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, k_rope, v, out, lse = res
    return _flash_mla_backward(q, k, k_rope, v, out, lse, g, causal, scale,
                               block_q, block_k, interpret)


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def _latent_attention(q, k, k_rope, v, causal, scale, block_q, block_k,
                      interpret, native, mesh, window):
    """``flash_attention`` with the key in two parts (``k_rope``)."""
    heads, rope = q.shape[2], k_rope.shape[-1]
    if (k.shape[2], v.shape[2], k_rope.shape[2]) != (heads, heads, 1) \
            or q.shape[-1] != k.shape[-1] + rope:
        raise ValueError(
            f"k_rope= takes q [.., H, nope + rope], k [.., H, nope], "
            f"k_rope [.., 1, rope] and v [.., H, dv]; got {q.shape}, "
            f"{k.shape}, {k_rope.shape}, {v.shape}")
    if window is not None or native or (mesh is not None and mesh.size > 1):
        raise ValueError("k_rope= runs the head-major kernels on one "
                         "device, with no window")
    interpret = kernel_mode(interpret)
    if interpret is None:
        with jax.named_scope(_KERNEL):  # a tile's two key parts, joined
            whole = jnp.concatenate(
                [k, jnp.broadcast_to(k_rope, (*k.shape[:3], rope))], -1)
        return _attention_reference(q, whole, v, causal, scale)
    block_q = DEFAULT_BLOCK if block_q is None else block_q
    block_k = DEFAULT_BLOCK if block_k is None else block_k
    with telemetry.span("ops", "flash.plan", **_plan_args(
            "latent", q, k.shape[1], block_q, block_k, causal, None)):
        return _flash_mla(q, k, k_rope, v, causal, scale, block_q, block_k,
                          interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    native: Optional[bool] = None,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    window: Optional[int] = None,
                    k_rope: Optional[jax.Array] = None) -> jax.Array:
    """Fused attention. Shapes ``[batch, seq, heads, head_dim]``; ``k``
    and ``v`` may carry fewer heads than ``q`` (a divisor of its count:
    query head ``h`` reads K/V head ``h // group``, inside the kernel).

    ``k_rope`` (latent attention): the key's rotary part as its ONE head,
    ``[batch, seq, 1, rope]``, shared by every query head.  ``k`` then
    holds each head's own part alone (``q`` is ``k``'s width plus
    ``rope``, the rotary part last) and ``v``, and with it the result,
    may be narrower than ``q``: ``q [.., 32, 192]``, ``k [.., 32, 128]``,
    ``k_rope [.., 1, 64]``, ``v [.., 32, 128]`` is DeepSeek-V3's layer.
    Equal head counts, causal or not, no window, one device a call.

    ``window`` (causal only): position ``t`` sees keys ``t - window + 1
    .. t``; a window that covers the sequence is no window.

    ``mesh``: under plain jit/GSPMD over several devices pass the mesh
    (models pass ``get_global_mesh()``): a Mosaic kernel cannot be
    partitioned automatically, so the kernel then runs per shard of a
    ``shard_map`` — batch over ``dp``/``fsdp``, heads over ``tp``,
    attention being independent in both.  Inside a user ``shard_map``
    the axes are already bound: pass nothing.

    On TPU runs the pallas kernels, forward and backward
    (FlashAttention-2 dK/dV and dQ kernels, O(T) memory); on other
    backends (tests) falls back to the jnp reference unless
    ``interpret=True`` forces the kernels through the pallas
    interpreter.  The grid streams K/V tiles with VMEM-scratch
    accumulators, so memory stays O(block) at any sequence length.
    ``block_q``/``block_k`` default to ``DEFAULT_BLOCK``: the tile must
    be large enough to amortize the f32 softmax work on the VPU per MXU
    matmul.

    ``native``: which kernel family runs follows from the shapes
    (``_nl_eligible``: q, k and v of one head_dim, 64 or 128, and whole
    128-lane slabs of heads take the native-layout kernels, which read
    ``[B, T, H, D]`` with no transposes around the calls; anything else,
    25 heads of 64 among them, takes the head-major kernels, and so does
    the two-part key of ``k_rope``, whose 192-wide heads fill no whole
    slabs).  A test passes ``True`` or ``False`` to hold one family
    against the other; both agree to f32-ulp level (test_ops.py).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k_rope is not None:
        return _latent_attention(q, k, k_rope, v, causal, scale, block_q,
                                 block_k, interpret, native, mesh, window)
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(
            f"query heads ({q.shape[2]}) must be a multiple of the K/V "
            f"heads ({k.shape[2]}, {v.shape[2]})")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("window= needs causal=True and window >= 1")
        if window >= k.shape[1]:
            window = None  # every key is inside it
    if native and not _nl_eligible(q, k, v):
        # validate BEFORE any backend fallback so CPU-tested code fails
        # the same way it would on the chip
        raise ValueError(
            f"native-layout flash attention needs head_dim in (64, 128) "
            f"and heads divisible by 128//head_dim; got {q.shape}")
    interpret = kernel_mode(interpret)
    if interpret is None:
        return _attention_reference(q, k, v, causal, scale, window)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        spec = P(tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names),
                 None, "tp" if "tp" in mesh.axis_names else None, None)
        per_shard = functools.partial(
            flash_attention, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret, native=native,
            window=window)
        # (what a shard runs names its own pieces inside this one: a
        # reader takes the innermost)
        with jax.named_scope(_LAYOUT):
            return jax.shard_map(per_shard, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False)(q, k, v)
    block_q = DEFAULT_BLOCK if block_q is None else block_q
    if block_k is None:
        # a head wider than one 128-lane slab (256: two): inside a step
        # the dK/dV kernel's tiles at 1024 x 1024 pass its 16 MiB of
        # scoped VMEM (by 20 KB at width 256); the forward and dQ stream
        # K/V along this axis, so their traffic stays what it is
        block_k = DEFAULT_BLOCK if q.shape[-1] <= 128 else \
            DEFAULT_BLOCK // 2
    if native is None:
        native = _nl_eligible(q, k, v)
    with telemetry.span("ops", "flash.plan", **_plan_args(
            "native" if native else "head_major", q, k.shape[1], block_q,
            block_k, causal, window)):
        kernels = _flash_nl if native else _flash
        return kernels(q, k, v, causal, scale, block_q, block_k, interpret,
                       window)
