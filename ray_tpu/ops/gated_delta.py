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

What does not depend on the entering state (``A``, ``T``, ``U``, ``W``,
the scores ``Q K^T * G``) is computed for ALL chunks at once, batched;
the state passes from chunk to chunk in a ``lax.scan`` whose body, seen
once a call by the compiler, is the two products ``W S`` and ``K^T V'``;
the read-out ``O`` is batched again over the states that entered each
chunk.  The carry and ``gamma`` and every ``exp`` are float32 whatever
the inputs; the products take their operands in ``v``'s dtype and
accumulate in float32 (the inverse's in float32: under
``jax.default_matmul_precision`` they follow it).

**Backward**: plain autodiff of the above, the scan's body under
``jax.checkpoint``: the backward pass holds the float32 state that
ENTERED each chunk (``chunks x H_v x d_k x d_v``, the scan's stacked
carry, which the read-out reads too: 256 MiB a sequence of 8,192 at 32
heads of 128 x 128) and recomputes ``V'`` inside a chunk.

Plain ``jax.numpy`` under XLA on every backend; ``ops:gated_delta.plan``
says what a call was traced as.  :func:`gated_delta_recurrence` is the
definition, step by step, for tests.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.core import telemetry
from ray_tpu.ops._kernel import traced_once


#: the name every op of a call carries in a device trace, inside whatever
#: part of the step the caller stands in (``models/step.py``)
SCOPE = "gated_delta"


class Plan(NamedTuple):
    """What was traced, for the ``ops:gated_delta.plan`` span."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    chunk: int
    seq: int

    def span_args(self, batch: int) -> dict:
        """``saved``: what the backward pass holds of the forward beside
        the inputs; ``saved_bytes`` of it a call."""
        chunks = self.seq // self.chunk
        return {**self._asdict(), "chunks": chunks,
                "inverse": "doubling", "carry": "xla",
                "saved": "chunk_states",
                "saved_bytes": 4 * batch * chunks * self.value_heads
                * self.key_dim * self.value_dim}


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


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I - a)^-1`` for ``a [..., C, C]`` STRICTLY lower triangular
    (``C`` a power of two): ``a^C = 0``, so the Neumann series ends and
    is the product ``(I + a)(I + a^2)(I + a^4) ..``, exactly."""
    size = a.shape[-1]
    eye = jnp.eye(size, dtype=a.dtype)
    out, power, reach = eye + a, a, 2
    while reach < size:
        power = power @ power
        out = out + out @ power
        reach *= 2
    return out


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, g):
    # d (I - a)^-1 = T da T
    tt = jnp.swapaxes(t, -1, -2)
    return (tt @ g @ tt,)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, *, chunk: int = 64) -> jax.Array:
    """``q k [B, T, H_k, d_k]`` (l2-normalised, ``q`` scaled), ``v [B, T,
    H_v, d_v]``, ``g [B, T, H_v]`` (log decays, ``<= 0``) and ``beta [B,
    T, H_v]`` -> ``o [B, T, H_v, d_v]`` in ``v``'s dtype.  ``T`` has to
    be whole chunks (a length that is not is refused, not padded)."""
    plan = _plan(q, v, chunk)
    with telemetry.span("ops", "gated_delta.plan",
                        **plan.span_args(q.shape[0])), \
            jax.named_scope(SCOPE):
        return _gated_delta(q, k, v, g, beta, plan)


@traced_once("plan")
def _gated_delta(q, k, v, g, beta, plan: Plan):
    """(Under an inner ``jit``: a model calls this once a layer and a
    sequence, and again under ``remat``, with the same shapes; the first
    call's jaxpr serves the others, ``ops/_kernel.py``.)"""
    f32 = jnp.float32
    b, t = v.shape[:2]
    c, nc = plan.chunk, plan.seq // plan.chunk
    hk, rep = plan.key_heads, plan.value_heads // plan.key_heads
    dk, dv = plan.key_dim, plan.value_dim
    dtype = v.dtype

    # a value head is (its key head h, one of r)
    q = jnp.moveaxis(q.reshape(b, nc, c, hk, dk), 2, 3)       # [b,n,h,c,dk]
    k = jnp.moveaxis(k.reshape(b, nc, c, hk, dk), 2, 3)
    v = jnp.moveaxis(v.reshape(b, nc, c, hk, rep, dv), 2, 4)  # [b,n,h,r,c,dv]
    beta = jnp.moveaxis(beta.astype(f32).reshape(b, nc, c, hk, rep), 2, 4)
    gamma = jnp.cumsum(jnp.moveaxis(
        g.astype(f32).reshape(b, nc, c, hk, rep), 2, 4), axis=-1)

    lower = jnp.tril(jnp.ones((c, c), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]          # [b,n,h,r,i,j]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    kk = jnp.einsum("bnhid,bnhjd->bnhij", k, k, preferred_element_type=f32)
    qk = jnp.einsum("bnhid,bnhjd->bnhij", q, k, preferred_element_type=f32)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a = -jnp.where(strict, beta[..., None] * kk[:, :, :, None] * decay, 0.0)
    solve = unit_lower_inverse(a).astype(dtype)               # [b,n,h,r,i,j]
    scores = (qk[:, :, :, None] * decay).astype(dtype)

    e_gamma = jnp.exp(gamma)[..., None]                       # [b,n,h,r,c,1]
    k_f32 = k[:, :, :, None].astype(f32)                      # [b,n,h,1,c,dk]
    beta_v = (beta[..., None] * v).astype(dtype)
    beta_k = (beta[..., None] * e_gamma * k_f32).astype(dtype)
    u = jnp.einsum("bnhrij,bnhrjd->bnhrid", solve, beta_v,
                   preferred_element_type=f32).astype(dtype)
    w = jnp.einsum("bnhrij,bnhrjd->bnhrid", solve, beta_k,
                   preferred_element_type=f32).astype(dtype)
    last = gamma[..., -1]                                     # [b,n,h,r]
    k_end = (jnp.exp(last[..., None] - gamma)[..., None] * k_f32
             ).astype(dtype)

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

    swap = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    first = jnp.zeros((b, hk, rep, dk, dv), f32)
    _, (states, fresh) = jax.lax.scan(
        jax.checkpoint(chunk_step), first,
        (swap(u), swap(w), swap(k_end), swap(last)))
    states, fresh = swap(states), swap(fresh)

    q_in = (e_gamma * q[:, :, :, None].astype(f32)).astype(dtype)
    out = jnp.einsum("bnhrik,bnhrkv->bnhriv", q_in, states.astype(dtype),
                     preferred_element_type=f32) \
        + jnp.einsum("bnhrij,bnhrjv->bnhriv", scores, fresh,
                     preferred_element_type=f32)
    # [b, n, h, r, c, dv] -> [b, n, c, h, r, dv]
    return jnp.moveaxis(out.astype(dtype), 4, 2).reshape(b, t, hk * rep, dv)
