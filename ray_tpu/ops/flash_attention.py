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

Two static extras of the equal-width kernels, both off by default (the
default traces the kernels body for body as before they existed):

* ``window``: position ``t`` sees keys ``t - window + 1 .. t``.  Tiles
  wholly outside the window are skipped the way tiles above the
  diagonal are (compute gated off, index maps clamped so the DMA is
  elided); tiles that straddle its edge are masked.
* grouped heads: ``k``/``v`` may carry fewer heads than ``q``.  Query
  head ``h`` reads K/V head ``h // group`` through the block index — no
  copies of K and V in HBM — and the dK/dV kernel walks the ``group``
  query heads of its K/V head along its sequential axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30

#: block size along both sequence axes when the caller names none
DEFAULT_BLOCK = 1024


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


def _causal_dispatch(causal: bool, q_offset, k_offset, block_q: int,
                     block_k: int, tile, window: Optional[int] = None):
    """Run ``tile(apply_mask)`` under the causal tile classification:
    diagonal-straddling tiles get the (iota + compare + select) causal
    mask, fully-visible tiles skip it, fully-masked tiles run nothing.
    The two predicates are mutually exclusive and their union equals the
    old "not fully masked" gate, so no tile is dropped or run twice."""
    from jax.experimental import pallas as pl

    if not causal:
        tile(False)
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
    pl.when(straddles)(lambda: tile(True))
    pl.when(fully_visible)(lambda: tile(False))


def _attention_reference(q, k, v, causal: bool, scale: float,
                         window: Optional[int] = None) -> jax.Array:
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
    @pl.when(_tile_live(causal, q_offset, k_offset, block_q, block_k,
                        window))
    def _compute():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_keep_mask(q_offset, k_offset, block_q, block_k,
                                     window), s, NEG_INF)
        m = m_ref[:][:, 0]
        l = l_ref[:][:, 0]
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        # masked entries: exp(-1e30 - safe_m) underflows to exactly 0.0,
        # so no [bq, bk] guard select is needed
        p = jnp.exp(s - safe_m[:, None])
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
        l_new = l * corr + p.sum(axis=-1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new[:, None]
        l_ref[:] = l_new[:, None]

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


def _flash_forward(q, k, v, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool,
                   out_dtype=None, window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k = k.shape[1]
    group = heads // k.shape[2]
    # pallas layout: [B, H, T, D]
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

    out, lse = pl.pallas_call(
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
    )(qt, kt, vt)
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
    live = jnp.logical_or(not causal, q_offset + block_q - 1 >= k_offset)
    if window is not None:
        live = _tile_live(causal, q_offset, k_offset, block_q, block_k,
                          window)

    @pl.when(live)
    def _compute():
        k = k_ref[:]
        v = v_ref[:]
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:][:, 0]
        delta = delta_ref[:][:, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_keep_mask(q_offset, k_offset, block_q, block_k,
                                     window), s, NEG_INF)
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # [bq] clamp: keeps
        p = jnp.exp(s - lse[:, None])  # fully-masked rows at p == 0
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

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

    @pl.when(_tile_live(causal, q_offset, k_offset, block_q, block_k,
                        window))
    def _compute():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:][:, 0]
        delta = delta_ref[:][:, 0]
        k = k_ref[:]
        v = v_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_keep_mask(q_offset, k_offset, block_q, block_k,
                                     window), s, NEG_INF)
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)  # [bq] clamp: keeps
        p = jnp.exp(s - lse[:, None])  # fully-masked rows at p == 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


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
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    if delta is None:
        # delta_i = rowsum(dO_i * O_i) (FlashAttention-2 eq. for dS);
        # [B,H,S,1] like lse (TPU blocks need >=2 trailing dims)
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
    dk, dv = pl.pallas_call(
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
    )(qt, kt, vt, dot, lse, delta)

    tile_q_fwd = pl.BlockSpec((None, None, block_q, dim),
                              lambda b, h, i, j: (b, h, i, 0))
    tile_k_fwd = pl.BlockSpec((None, None, block_k, dim), kv_idx_fwd)
    rows_q_fwd = pl.BlockSpec((None, None, block_q, 1),
                              lambda b, h, i, j: (b, h, i, 0))
    dq_kernel = functools.partial(_fa_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, window=window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(batch, heads, seq_q // block_q, seq_k // block_k),
        in_specs=[tile_q_fwd, tile_k_fwd, tile_k_fwd, tile_q_fwd,
                  rows_q_fwd, rows_q_fwd],
        out_specs=tile_q_fwd,
        out_shape=jax.ShapeDtypeStruct(qt.shape, grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
        compiler_params=seq_params,
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

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

    def _tile(apply_mask: bool):
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        if apply_mask:
            causal_keep = _keep_mask(q_offset, k_offset, block_q, block_k,
                                     window)
        corrs = []
        pvs = []
        for h in range(pack):
            qh = q * _lane_mask(h, pack, dim, block_q, q.dtype) if pack > 1 else q
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if apply_mask:
                s = jnp.where(causal_keep, s, NEG_INF)
            m = m_refs[h][:]            # [bq, 1]
            l = l_refs[h][:]
            m_new = jnp.maximum(m, s.max(axis=-1)[:, None])
            safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            # masked entries: exp(-1e30 - safe_m) underflows to exactly
            # 0.0, so no [bq, bk] guard select is needed
            p = jnp.exp(s - safe_m)
            corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
            l_refs[h][:] = l * corr + p.sum(axis=-1)[:, None]
            m_refs[h][:] = m_new
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            corrs.append(corr)
            pvs.append(pv)
        if pack == 1:
            acc_ref[:] = acc_ref[:] * corrs[0] + pvs[0]
        else:
            sel = _head_sel(pack, dim, block_q)
            acc_ref[:] = (acc_ref[:] * jnp.where(sel, corrs[0], corrs[1])
                          + jnp.where(sel, pvs[0], pvs[1]))

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window)

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

    out, lse = pl.pallas_call(
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
    )(qr, kr, vr)
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

    def _tile(apply_mask: bool):
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        if apply_mask:
            causal_keep = _keep_mask(q_offset, k_offset, block_q, block_k,
                                     window)
        pdos = []
        dsqs = []
        for h in range(pack):
            mask_q = (_lane_mask(h, pack, dim, block_q, q.dtype)
                      if pack > 1 else None)
            qh = q * mask_q if pack > 1 else q
            doh = do * mask_q if pack > 1 else do
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if apply_mask:
                s = jnp.where(causal_keep, s, NEG_INF)
            lse = lse_ref[:][:, h:h + 1]     # [bq, 1]
            delta = delta_ref[:][:, h:h + 1]
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
            dv_acc[:] = dv_acc[:] + pdos[0]
            dk_acc[:] = dk_acc[:] + dsqs[0]
        else:
            sel = _head_sel(pack, dim, block_k)
            dv_acc[:] = dv_acc[:] + jnp.where(sel, pdos[0], pdos[1])
            dk_acc[:] = dk_acc[:] + jnp.where(sel, dsqs[0], dsqs[1])

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window)

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

    def _tile(apply_mask: bool):
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        if apply_mask:
            causal_keep = _keep_mask(q_offset, k_offset, block_q, block_k,
                                     window)
        dsks = []
        for h in range(pack):
            mask_q = (_lane_mask(h, pack, dim, block_q, q.dtype)
                      if pack > 1 else None)
            qh = q * mask_q if pack > 1 else q
            doh = do * mask_q if pack > 1 else do
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if apply_mask:
                s = jnp.where(causal_keep, s, NEG_INF)
            lse = lse_ref[:][:, h:h + 1]     # [bq, 1]
            delta = delta_ref[:][:, h:h + 1]
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
            dq_acc[:] = dq_acc[:] + dsks[0]
        else:
            sel = _head_sel(pack, dim, block_q)
            dq_acc[:] = dq_acc[:] + jnp.where(sel, dsks[0], dsks[1])

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile,
                     window)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


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
    qr = q.reshape(batch, seq_q, heads * dim)
    kr = k.reshape(batch, seq_k, kv_heads * dim)
    vr = v.reshape(batch, seq_k, kv_heads * dim)
    gr = g.reshape(batch, seq_q, heads * dim)
    if delta is None:
        # delta_i = rowsum(dO_i * O_i), laid out [B, H2, T, pack] like
        # lse (T in sublanes so per-head columns broadcast along lanes
        # without relayout); XLA fuses the product+reduce
        delta = (jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
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
    dk, dv = pl.pallas_call(
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
    )(qr, kr, vr, gr, lse, delta)

    tile_q_fwd = pl.BlockSpec((None, block_q, slab),
                              lambda b, h, i, j: (b, i, h))
    tile_k_fwd = pl.BlockSpec((None, block_k, slab), kv_idx_fwd)
    rows_q_fwd = pl.BlockSpec((None, None, block_q, pack),
                              lambda b, h, i, j: (b, h, i, 0))
    dq_kernel = functools.partial(_fa_nl_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, pack=pack, dim=dim,
                                  window=window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(batch, h2, seq_q // block_q, seq_k // block_k),
        in_specs=[tile_q_fwd, tile_k_fwd, tile_k_fwd, tile_q_fwd,
                  rows_q_fwd, rows_q_fwd],
        out_specs=tile_q_fwd,
        out_shape=jax.ShapeDtypeStruct(qr.shape, grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, slab), jnp.float32)],
        compiler_params=seq_params,
        interpret=interpret,
    )(qr, kr, vr, gr, lse, delta)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


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


def _mla_key(k_ref, r_ref):
    """The tile's whole key ``[block_k, nope + rope]``: the head's own
    part beside the shared rotary part."""
    return jnp.concatenate([k_ref[:], r_ref[:]], axis=1)


def _mla_scores(q_ref, k_ref, r_ref):
    """``[block_q, block_k]`` float32, unscaled: ONE product as deep as
    the whole key (two products, one a key part, summed before the
    softmax measured 1.8% slower a layer on a v5e: PERF.md, PR 33)."""
    return jax.lax.dot_general(
        q_ref[:], _mla_key(k_ref, r_ref), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


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

    def _tile(apply_mask: bool):
        v = v_ref[:]
        s = _mla_scores(q_ref, k_ref, r_ref) * scale
        if apply_mask:
            s = jnp.where(_keep_mask(q_offset, k_offset, block_q, block_k),
                          s, NEG_INF)
        m = m_ref[:]            # [bq, 1]
        m_new = jnp.maximum(m, s.max(axis=-1)[:, None])
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe_m)  # masked entries underflow to exactly 0
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
        l_ref[:] = l_ref[:] * corr + p.sum(axis=-1)[:, None]
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile)

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


def _flash_mla_forward(q, k, k_rope, v, causal: bool, scale: float,
                       block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k, nope, rope, dim_v = (k.shape[1], k.shape[3], k_rope.shape[3],
                                v.shape[3])
    block_q, block_k = _mla_blocks(q, k, block_q, block_k)
    # pallas layout: [B, H, T, D]
    qt, kt, rt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, k_rope, v))

    def k_tile(j, i):
        return _clamp_k_tile(j, i, block_q, block_k) if causal else j

    rows_q = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    out, lse = pl.pallas_call(
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
    )(qt, kt, rt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _mla_probs(q_ref, k_ref, r_ref, lse_ref, scale, keep):
    """``p [bq, bk]`` of a tile from the forward's row statistics."""
    s = _mla_scores(q_ref, k_ref, r_ref) * scale
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    lse = lse_ref[:]            # [bq, 1]
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

    def _tile(apply_mask: bool):
        q = q_ref[:]
        do = do_ref[:]
        keep = _keep_mask(q_offset, k_offset, block_q, block_k) \
            if apply_mask else None
        p = _mla_probs(q_ref, k_ref, r_ref, lse_ref, scale, keep)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:]) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile)

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

    def _tile(apply_mask: bool):
        keep = _keep_mask(q_offset, k_offset, block_q, block_k) \
            if apply_mask else None
        p = _mla_probs(q_ref, k_ref, r_ref, lse_ref, scale, keep)
        dp = jax.lax.dot_general(
            do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:]) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), _mla_key(k_ref, r_ref),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _causal_dispatch(causal, q_offset, k_offset, block_q, block_k, _tile)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_mla_backward(q, k, k_rope, v, out, lse, g, causal, scale,
                        block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, heads, dim = q.shape
    seq_k, nope, rope, dim_v = (k.shape[1], k.shape[3], k_rope.shape[3],
                                v.shape[3])
    block_q, block_k = _mla_blocks(q, k, block_q, block_k)
    n_q = seq_q // block_q
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
    dk, dr, dv = pl.pallas_call(
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
    )(qt, kt, rt, vt, dot, lse, delta)

    rows_q = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    dq = pl.pallas_call(
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
    )(qt, kt, rt, vt, dot, lse, delta)

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
        lse = lse.transpose(0, 2, 1, 3).reshape(batch, seq_q, heads)
    else:
        out, lse = _flash_forward(q, k, v, causal, scale, block_q,
                                  block_k, interpret,
                                  out_dtype=jnp.float32)
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
            return x.reshape(batch, seq_q, h2, pack).transpose(0, 2, 1, 3)

        return _flash_nl_backward(q, k, v, out, to_nl(lse), g, causal,
                                  scale, block_q, block_k, interpret,
                                  grad_dtype=jnp.float32,
                                  delta=None if delta is None
                                  else to_nl(delta))
    return _flash_backward(q, k, v, out,
                           lse.transpose(0, 2, 1)[..., None], g, causal,
                           scale, block_q, block_k, interpret,
                           grad_dtype=jnp.float32,
                           delta=None if delta is None
                           else delta.transpose(0, 2, 1)[..., None])


def fit_block(seq: int, block: int) -> int:
    """Largest divisor of ``seq`` that is <= ``block`` (the pallas grids
    need the sequence to divide into whole tiles)."""
    for d in range(min(block, seq), 0, -1):
        if seq % d == 0:
            return d
    return 1


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
    if interpret is None:
        if jax.default_backend() != "tpu":
            whole = jnp.concatenate(
                [k, jnp.broadcast_to(k_rope, (*k.shape[:3], rope))], -1)
            return _attention_reference(q, whole, v, causal, scale)
        interpret = False
    return _flash_mla(q, k, k_rope, v, causal, scale,
                      DEFAULT_BLOCK if block_q is None else block_q,
                      DEFAULT_BLOCK if block_k is None else block_k,
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
    backend = jax.default_backend()
    if interpret is None:
        if backend != "tpu":
            return _attention_reference(q, k, v, causal, scale, window)
        interpret = False
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        spec = P(tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names),
                 None, "tp" if "tp" in mesh.axis_names else None, None)
        per_shard = functools.partial(
            flash_attention, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret, native=native,
            window=window)
        return jax.shard_map(per_shard, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)(q, k, v)
    block_q = DEFAULT_BLOCK if block_q is None else block_q
    block_k = DEFAULT_BLOCK if block_k is None else block_k
    if native is None:
        native = _nl_eligible(q, k, v)
    if native:
        return _flash_nl(q, k, v, causal, scale, block_q, block_k,
                         interpret, window)
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret,
                  window)
