"""Ring attention: exact attention over sequence shards with a ring of
``ppermute`` K/V rotations.

Sequence parallelism is absent from the reference (SURVEY.md §2.5); this
is the TPU-native construction: each device along the ``sp`` mesh axis
holds a contiguous sequence chunk of Q, K, V.  Over ``sp``-many steps,
every device computes blockwise attention of its Q chunk against the K/V
chunk currently resident, maintaining an online-softmax accumulator
(running max ``m``, normalizer ``l``, weighted values ``o``), then rotates
K/V one hop around the ring.  Communication overlaps compute on ICI and
peak memory stays O(T/n) per device.

Causal masking is exact.  On TPU the default path runs each chunk pair
through the pallas flash kernels (O(block) VMEM, bf16 MXU operands) and
merges normalized log-sum-exp partials; chunks entirely in a Q chunk's
future are skipped outright via ``lax.switch``.  The jnp reference path
(CPU/tests/fallback) masks per element and lets XLA schedule every pair.

Usable two ways:
- inside an existing ``shard_map``: call with ``axis_name="sp"``;
- standalone: pass ``mesh=``; inputs are globally-shaped arrays and the
  function applies ``shard_map`` itself.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map

NEG_INF = -1e30


def _block_attn(q, k, v, m, l, o, q_offset, k_offset, causal, scale):
    """One blockwise online-softmax update.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]
    m, l: [B, H, Tq] running max / normalizer; o: [B, Tq, H, D]
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (m_new == NEG_INF) against NaNs
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    correction = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - safe_m)
    correction = jnp.where(m <= NEG_INF / 2, 0.0, correction)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool,
                            scale: float):
    """jnp reference ring (autodiff-differentiable): the CPU/test path
    and the fallback for shapes the flash kernels do not cover."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    batch, tq, heads, dim = q.shape
    qf = q.astype(jnp.float32)

    m0 = jnp.full((batch, heads, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, heads, tq), jnp.float32)
    o0 = jnp.zeros((batch, tq, heads, dim), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, s):
        m, l, o, k_cur, v_cur = carry
        # K/V chunk at ring step s originated on device (my_idx - s) mod n
        k_idx = (my_idx - s) % axis_size
        q_offset = my_idx * tq
        k_offset = k_idx * k_cur.shape[1]
        m, l, o = _block_attn(qf, k_cur, v_cur, m, l, o,
                              q_offset, k_offset, causal, scale)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, l, o, k_nxt, v_nxt), None

    (m, l, o, _, _), _ = lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(axis_size))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-kernel ring: per-chunk pallas attention + log-sum-exp merging.
#
# The reference path above materializes the [B, H, Tq, Tk] f32 score
# tensor of every chunk pair — O((T/n)^2) memory per device and f32
# einsums on the MXU.  This path runs each (Q-chunk, KV-chunk) pair
# through the O(block)-memory flash kernels (bf16 operands, f32
# accumulation) and merges the normalized per-chunk partials with the
# standard rescaling identity:
#
#   out = (out_a * e^(lse_a - m) + out_b * e^(lse_b - m)) / (e^.. + e^..)
#
# Causality becomes chunk classification instead of per-element masks:
# with equal contiguous chunks, a KV chunk is entirely in a Q chunk's
# past (plain non-causal kernel), the diagonal (causal kernel), or the
# future — which lax.switch SKIPS outright, the block-skipping the
# reference path's docstring deferred.
#
# The backward rides the same ring a second time: dK/dV accumulators
# travel WITH their K/V chunk (one extra ppermute pair per step) and
# land home after the full loop, while each stop adds that device's
# per-chunk flash backward — computed against the GLOBAL merged lse and
# final-output delta, which is what makes per-chunk gradients sum
# exactly to the global gradient.
# ---------------------------------------------------------------------------


def _merge_partials(out_a, lse_a, out_b, lse_b):
    """Merge two normalized partial-attention results ([B,T,H,D] f32,
    [B,T,H] f32 log-sum-exp); fully-masked partials carry lse=-inf and
    drop out via the guards."""
    m = jnp.maximum(lse_a, lse_b)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    wa = jnp.where(lse_a <= NEG_INF / 2, 0.0, jnp.exp(lse_a - safe_m))
    wb = jnp.where(lse_b <= NEG_INF / 2, 0.0, jnp.exp(lse_b - safe_m))
    tot = wa + wb
    tot_safe = jnp.where(tot == 0.0, 1.0, tot)
    out = (out_a * wa[..., None] + out_b * wb[..., None]) / tot_safe[..., None]
    lse = jnp.where(tot == 0.0, NEG_INF, safe_m + jnp.log(tot_safe))
    return out, lse


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale, interpret):
    from ray_tpu.ops.flash_attention import _flash_chunk_fwd

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    batch, tq, heads, dim = q.shape

    out0 = jnp.zeros((batch, tq, heads, dim), jnp.float32)
    lse0 = jnp.full((batch, tq, heads), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def chunk(k_cur, v_cur, use_causal):
        # per-chunk out is already f32 (one rounding total across the
        # whole ring, matching the single-device kernel's f32 scratch)
        return _flash_chunk_fwd(q, k_cur, v_cur, use_causal, scale,
                                interpret)

    def step(carry, s):
        out, lse, k_cur, v_cur = carry
        k_idx = (my_idx - s) % axis_size
        if causal:
            # 0 = diagonal chunk (causal kernel), 1 = past (plain
            # kernel), 2 = future (skipped outright)
            case = jnp.where(k_idx == my_idx, 0,
                             jnp.where(k_idx < my_idx, 1, 2))
            o_s, lse_s = lax.switch(case, [
                lambda: chunk(k_cur, v_cur, True),
                lambda: chunk(k_cur, v_cur, False),
                lambda: (out0, lse0),
            ])
        else:
            o_s, lse_s = chunk(k_cur, v_cur, False)
        out, lse = _merge_partials(out, lse, o_s, lse_s)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (out, lse, k_nxt, v_nxt), None

    (out, lse, _, _), _ = lax.scan(
        step, (out0, lse0, k, v), jnp.arange(axis_size))
    return out.astype(q.dtype), lse


def _ring_flash_bwd_pass(q, k, v, out, lse, g, axis_name, causal, scale,
                         interpret):
    from ray_tpu.ops.flash_attention import _flash_chunk_bwd

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    dq0 = jnp.zeros(q.shape, jnp.float32)
    zeros_kv = (jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32))
    # delta = rowsum(g * out) is loop-invariant: compute once, not per
    # ring step
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def chunk_bwd(k_cur, v_cur, use_causal):
        return _flash_chunk_bwd(q, k_cur, v_cur, out, lse, g, use_causal,
                                scale, interpret, delta=delta)

    def step(carry, s):
        dq, k_cur, v_cur, dk, dv = carry
        k_idx = (my_idx - s) % axis_size
        if causal:
            case = jnp.where(k_idx == my_idx, 0,
                             jnp.where(k_idx < my_idx, 1, 2))
            dq_c, dk_c, dv_c = lax.switch(case, [
                lambda: chunk_bwd(k_cur, v_cur, True),
                lambda: chunk_bwd(k_cur, v_cur, False),
                lambda: (dq0,) + zeros_kv,
            ])
        else:
            dq_c, dk_c, dv_c = chunk_bwd(k_cur, v_cur, False)
        dq = dq + dq_c
        dk = dk + dk_c
        dv = dv + dv_c
        # the accumulators travel WITH their chunk; after axis_size hops
        # the packet is home with every device's contribution on board
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk, axis_name, perm)
        dv_nxt = lax.ppermute(dv, axis_name, perm)
        return (dq, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v) + zeros_kv, jnp.arange(axis_size))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, scale, interpret):
    out, _ = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale,
                                  interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret):
    out, lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale,
                                    interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    return _ring_flash_bwd_pass(q, k, v, out, lse, g, axis_name, causal,
                                scale, interpret)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   mesh: Optional[Mesh] = None,
                   impl: str = "auto",
                   interpret: bool = False) -> jax.Array:
    """Exact (flash-equivalent) attention over a sequence-sharded mesh
    axis.

    Args shapes: ``[batch, seq, heads, head_dim]`` — the seq dim sharded
    over ``axis_name`` (shard-local when called inside shard_map, global
    when ``mesh`` is given).

    ``impl``: "kernel" runs each chunk pair through the pallas flash
    kernels and merges log-sum-exp partials (O(block) memory per device,
    bf16 MXU operands, future chunks skipped outright; custom-VJP ring
    backward) — the TPU path; "reference" is the jnp online-softmax scan
    (differentiable via autodiff; materializes per-chunk-pair score
    blocks); "auto" picks by backend.  ``interpret=True`` with
    impl="kernel" exercises the kernel ring through the pallas
    interpreter on CPU (tests).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl == "auto":
        from ray_tpu.ops.flash_attention import kernel_block_for
        tq_local = (q.shape[1] // mesh.shape[axis_name]
                    if mesh is not None else q.shape[1])
        # awkward chunk lengths fall back to the reference scan
        impl = ("kernel"
                if jax.default_backend() == "tpu"
                and kernel_block_for(tq_local) is not None
                else "reference")
    if impl == "kernel":
        def fn(q_, k_, v_):
            return _ring_flash(q_, k_, v_, axis_name, causal, scale,
                               interpret)
    elif impl == "reference":
        fn = functools.partial(_ring_attention_sharded,
                               axis_name=axis_name, causal=causal,
                               scale=scale)
    else:
        raise ValueError(f"impl must be auto|kernel|reference, got {impl!r}")
    if mesh is None:
        return fn(q, k, v)

    spec = P(None, axis_name, None, None)
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
