"""A residual stream of several lanes mixed by manifold-constrained
hyper-connections (mHC: DeepSeek-AI, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606), for the training path.

A model that carries ONE lane adds a sub-layer's result to its input,
``x + F(N(x))``.  Here a token carries ``n`` lanes of the model's width
and every sub-layer ``F`` has a connection of its own, which for each
token makes three sets of coefficients from all ``n x C`` elements of
its lanes (:func:`coefficients`), reads the sub-layer's input as a
weighted sum of the lanes (:func:`read`) and writes its result back
through a doubly-stochastic ``n x n`` matrix over the lanes and a gain a
lane (:func:`write`)::

    r        = vec(X[t]) / sqrt(mean(vec(X[t])^2) + 1e-6)      (no scale)
    [p|q|S]  = r phi                     phi [n C, n + n + n n]
    H_pre    = sigmoid(a_pre p + b_pre)                    [n]
    H_post   = 2 sigmoid(a_post q + b_post)                [n]
    H_res    = sinkhorn(exp(clip(a_res S + b_res, lo, hi)))    [n, n]
    u[t]     = sum_i H_pre[i] X[t, i]
    X'[t, i] = sum_j H_res[i, j] X[t, j] + H_post[i] F(N(u))[t]

The coefficients are float32 whatever the model computes in, as a
router's scores are.  With ``H_pre = H_post = H_res = 1`` on one lane
this is the plain residual.

**Where the lanes lie.**  ``X`` is ``[B, T, n x C]`` in the model's
dtype: a token's lanes side by side along the LAST axis, lane ``i`` the
columns ``i C .. (i + 1) C``, which are the bytes of a row-major ``[B,
T, n, C]``.  A TPU lays the last two axes of an array out in tiles of
16 x 128 (bfloat16), so an axis of 4 before the width would be padded
to 16 and a part's saved input with it; side by side a lane starts at a
multiple of 128 columns and nothing is padded.  The coefficients lie
the other way round, the tokens LAST (``[n, T]``, ``[n, n, T]``): the
twenty normalisations then run over whole 128-lane rows of tokens, and
what their backward keeps of each is ``n x n`` rows of ``T`` and not
``T`` tiles of 8 x 128 for sixteen numbers.

**What runs where.**  The arithmetic on a token's ``n (n + 2)``
coefficients (the gates, the sigmoids, the norm's ``rsqrt``, the clamp,
the twenty Sinkhorn steps: ONE ``lax.scan`` a call, which the compiler
sees once and whose backward is taken through) is plain ``jax.numpy``
here, whatever runs the passes over the lanes.  Those passes (the
projection with the squares' sum, :func:`write`, and the backward of the
lanes) are the kernels of ``ops/lane_mix.py`` on a TPU, where a lane is
whole 128-column registers, the tokens divide into tiles and the
coefficients are float32 (``lane_mix.mode``); anywhere else they are the
``jnp`` forms below.  On the kernels' path a connection is two calls
(:func:`_open`, :func:`_close`) with the sub-layer between them, each
with a backward of its own, so that the lanes are read once a pass and
``d X`` is written once: see :func:`_open`.  :func:`read` stays XLA's
on both (one fusion that reads four lanes and writes one).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models import step
from ray_tpu.ops import lane_mix

#: ``H_post = POST_GAIN x sigmoid(.)``: 1 where the sigmoid is at rest
POST_GAIN = 2.0
#: the epsilon of the norm over a token's lanes (the paper's)
LANE_EPS = 1e-6
#: the Sinkhorn-Knopp iteration (the published ``hc_sinkhorn_iters``,
#: ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``: the paper's own)
SINKHORN_ITERS = 20
SINKHORN_EPS = 1e-6
CLAMP = (-30.0, 30.0)
#: what the coefficients are computed in: float32, as a router's scores
COEF_DTYPE = jnp.float32


class Coefficients(NamedTuple):
    """A connection's coefficients for ``T`` tokens, float32, tokens
    last: ``pre [n, T]``, ``post [n, T]``, ``res [n, n, T]`` (``res[i,
    j]``: what lane ``i`` takes of lane ``j``)."""
    pre: jax.Array
    post: jax.Array
    res: jax.Array


def lanes_of(x: jax.Array, n: int) -> jax.Array:
    """One lane ``[B, T, C]`` copied to ``n``: ``[B, T, n x C]``."""
    return jnp.tile(x, (1, 1, n))


def _lanes(x: jax.Array, n: int):
    """The ``n`` lanes of ``x [B, T, n C]``, each ``[B, T, C]`` widened
    to float32."""
    width = x.shape[-1] // n
    return [x[..., i * width:(i + 1) * width].astype(jnp.float32)
            for i in range(n)]


def collapse(x: jax.Array, n: int) -> jax.Array:
    """The lanes' sum ``[B, T, C]``, added in float32 and rounded once."""
    return sum(_lanes(x, n)).astype(x.dtype)


def lane_scale(x: jax.Array, dtype: Any) -> jax.Array:
    """``1 / sqrt(mean(vec(X[t])^2) + eps)`` over all ``n x C`` elements
    of a token, ``[B, T]``.  ``x [M, 1]`` (no lane is one column wide)
    IS the mean square a token, where a kernel has taken it with the
    projection: what turns it into the scale is still this."""
    x = x.astype(dtype)
    mean_square = x[..., 0] if x.shape[-1] == 1 \
        else jnp.mean(x * x, axis=-1)
    return jax.lax.rsqrt(mean_square + LANE_EPS)


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``exp(logits) [n, n, T]`` made doubly stochastic a token, the
    paper's ``T_r(T_c(.))`` ``iters`` times: every column divided by its
    sum (over ``i``, axis 0), then every row by its (over ``j``, axis
    1), each sum with ``eps`` added.  Rows sum to 1 up to ``eps`` after
    the last step; columns as closely as the iteration has converged."""
    def one(m, _):
        m = m / (m.sum(0, keepdims=True) + eps)
        return m / (m.sum(1, keepdims=True) + eps), None

    return jax.lax.scan(one, jnp.exp(logits), None, length=iters)[0]


def coefficients(x: jax.Array, phi: jax.Array, bias: jax.Array,
                 gates: jax.Array, n: int) -> Coefficients:
    """The coefficients of the tokens of ``x [B, T, n C]``, ``B x T`` of
    them in that order (``phi [n C, n (n + 2)]``, ``bias [n (n + 2)]``,
    ``gates [3]``: pre, post, res), all of it in :data:`COEF_DTYPE`.
    ``r phi`` is taken as ``(X phi) / rms``, the scale a token: the
    normalised lanes are never written out."""
    dtype = COEF_DTYPE
    rows = x.reshape(-1, x.shape[-1])
    proj = jax.lax.dot_general(                                  # [m, T]
        phi.astype(dtype), rows.astype(dtype), (((0,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return _of_projection(proj, lane_scale(rows, dtype), bias, gates, n)


def _of_projection(proj: jax.Array, scale: jax.Array, bias: jax.Array,
                   gates: jax.Array, n: int) -> Coefficients:
    """The coefficients from ``proj [m, T]`` (``phi^T X^T``, tokens last)
    and the lanes' ``scale [T]``: all that is a token's own numbers."""
    dtype = COEF_DTYPE
    proj = proj.astype(dtype) * scale[None]
    gate = jnp.concatenate([
        jnp.broadcast_to(gates[k].astype(dtype), (count,))
        for k, count in enumerate((n, n, n * n))])
    z = gate[:, None] * proj + bias.astype(dtype)[:, None]
    pre = jax.nn.sigmoid(z[:n])
    post = POST_GAIN * jax.nn.sigmoid(z[n:2 * n])
    res = sinkhorn(jnp.clip(z[2 * n:], *CLAMP).reshape(n, n, -1),
                   SINKHORN_ITERS, SINKHORN_EPS)
    return Coefficients(*(c.astype(jnp.float32) for c in (pre, post, res)))


def _a_token(c: jax.Array, x: jax.Array) -> jax.Array:
    """A coefficient a token ``[B x T]`` against ``x [B, T, C]``."""
    return c.reshape(*x.shape[:-1], 1)


def read(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``u[t] = sum_i pre[i, t] X[t, i]``: ``[B, T, n C] -> [B, T, C]``,
    summed in float32, rounded once to ``x``'s dtype."""
    lanes = _lanes(x, pre.shape[0])
    return sum(_a_token(pre[i], lane) * lane
               for i, lane in enumerate(lanes)).astype(x.dtype)


def write(x: jax.Array, y: jax.Array, coef: Coefficients) -> jax.Array:
    """``X'[t, i] = sum_j res[i, j, t] X[t, j] + post[i, t] y[t]``:
    ``[B, T, n C]``, every lane summed in float32 and rounded once."""
    n = coef.post.shape[0]
    lanes, y = _lanes(x, n), y.astype(jnp.float32)
    return jnp.concatenate([
        (sum(_a_token(coef.res[i, j], y) * lanes[j] for j in range(n))
         + _a_token(coef.post[i], y) * y).astype(x.dtype)
        for i in range(n)], axis=-1)


# ---------------------------------------------------------------------------
# the kernels' path: a connection as two calls around its sub-layer
# ---------------------------------------------------------------------------

def _small(n: int, width: int, proj, sumsq, bias, gates) -> Coefficients:
    """The coefficients from the kernel's two results: ``proj [m, M]``
    unscaled and the squares' sum a token ``[M]`` over ``width``
    elements.  Traced anew every call (a ``custom_vjp`` keeps no trace),
    so the module's names are read as :func:`coefficients` reads them."""
    mean_square = (sumsq / width).astype(COEF_DTYPE)[:, None]
    return _of_projection(proj, lane_scale(mean_square, COEF_DTYPE), bias,
                          gates, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _open(x, phi, bias, gates, n: int, interpret: bool):
    """A connection before its sub-layer, ``x [M, n C]``: ``(coef, u,
    held)``, the coefficients, what the sub-layer reads, and the lanes
    again for :func:`_close` ALONE.  ``held`` is ``x``; what comes back
    for it is not its cotangent but ``d X'`` as :func:`_close` got it,
    still to be carried through ``res``: this call's backward does that
    in the one pass that writes ``d X``, with the terms through ``pre``,
    the projection and the norm (``lane_mix.open_bwd``), so the four
    are never arrays of their own."""
    return _open_fwd(x, phi, bias, gates, n, interpret)[0]


def _open_fwd(x, phi, bias, gates, n, interpret):
    with step.scope("hc.coef"):
        # (kept under ``step.remat``: the recompute starts from these
        # 25 numbers a token, not from another pass over the lanes)
        proj, sumsq = step.keep("projection", lane_mix.project(
            x, phi, n, interpret))
        coef, pull = jax.vjp(functools.partial(_small, n, x.shape[-1]),
                             proj, sumsq, bias, gates)
    with step.scope("hc.mix"):
        u = read(x, coef.pre)
    return (coef, u, x), (x, phi, coef, pull)


def _open_bwd(n, interpret, kept, cotangents):
    x, phi, coef, pull = kept
    dcoef, du, d = cotangents
    with step.scope("hc.mix"):
        dpre = lane_mix.read_bwd(x, du, n, interpret)
    with step.scope("hc.coef"):
        g, dsumsq, dbias, dgates = pull(dcoef._replace(
            pre=dcoef.pre + dpre))
    with step.scope("hc.mix"):
        dx, dphi = lane_mix.open_bwd(x, d, du, coef.res, coef.pre, phi, g,
                                     dsumsq, interpret)
    return dx, dphi.astype(phi.dtype), dbias, dgates


_open.defvjp(_open_fwd, _open_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _close(held, y, res, post, interpret: bool):
    """A connection after its sub-layer: ``X'`` from ``held`` (of
    :func:`_open`), the sub-layer's ``y [M, C]`` and the coefficients."""
    return _close_fwd(held, y, res, post, interpret)[0]


def _close_fwd(held, y, res, post, interpret):
    with step.scope("hc.mix"):
        return lane_mix.write(held, y, res, post, interpret), \
            (held, y, post)


def _close_bwd(interpret, kept, d):
    held, y, post = kept
    with step.scope("hc.mix"):
        dy, dres, dpost = lane_mix.write_bwd(held, d, y, post, interpret)
    # ``d`` for ``held``: see :func:`_open`
    return d, dy, dres, dpost


_close.defvjp(_close_fwd, _close_bwd)


def stats_of(coef: Coefficients) -> Dict[str, jax.Array]:
    """What a connection made of a sequence, for its operator:
    ``offdiag_mass`` the mean of ``1 - trace(H_res) / n`` (0: the plain
    residual, every lane keeps to itself), ``doubly_stochastic_error``
    the largest ``|column sum - 1|`` the iteration left, ``pre_entropy``
    the mean entropy of ``H_pre`` normalised over the lanes (``log n``:
    a sub-layer reads all lanes alike)."""
    n = coef.post.shape[0]
    trace = sum(coef.res[i, i] for i in range(n))
    p = coef.pre / coef.pre.sum(0, keepdims=True)
    return {"offdiag_mass": jnp.mean(1.0 - trace / n),
            "doubly_stochastic_error":
                jnp.max(jnp.abs(coef.res.sum(0) - 1.0)),
            "pre_entropy": jnp.mean(-jnp.sum(p * jnp.log(p + 1e-30), 0))}


def _bias_init(n: int, off_diagonal: float = -8.0):
    """``H_pre = 1 / n`` a lane, ``H_post = 1``, ``H_res`` all but the
    identity: the plain residual over ``n`` equal lanes at the start."""
    def init(key, shape, dtype):
        del key
        res = off_diagonal * (1.0 - jnp.eye(n))
        return jnp.concatenate([
            jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
            res.reshape(-1)]).astype(dtype).reshape(shape)
    return init


class Connection(nn.Module):
    """One sub-layer's connection: its parameters (``phi``, ``b``,
    ``gates``) and the coefficients of a sequence under the step's part
    ``hc.coef``.  ``config``: any dataclass with ``hc_mult`` and
    ``param_dtype``."""
    config: Any

    @nn.compact
    def connect(self, x: jax.Array) -> "Mix":
        """The connection of ``x [B, T, n C]`` up to its sub-layer."""
        cfg = self.config
        n = cfg.hc_mult
        m = n * (n + 2)
        phi = self.param(
            "phi", nn.with_partitioning(nn.initializers.normal(0.02),
                                        ("embed", None)),
            (x.shape[-1], m), cfg.param_dtype)
        bias = self.param("b", nn.with_partitioning(_bias_init(n), (None,)),
                          (m,), cfg.param_dtype)
        gates = self.param(
            "gates", nn.with_partitioning(nn.initializers.constant(0.01),
                                          (None,)), (3,), cfg.param_dtype)
        kernels = _kernels(x, n)
        if kernels is None:
            with step.scope("hc.coef"):
                coef = coefficients(x, phi, bias, gates, n)
            mixed = Mix(x, coef)
        else:
            coef, u, held = _open(x.reshape(-1, x.shape[-1]), phi, bias,
                                  gates, n, kernels)
            mixed = Mix(x, coef, u=u.reshape(*x.shape[:-1], -1),
                        close=lambda y: _close(
                            held, y.reshape(-1, y.shape[-1]), coef.res,
                            coef.post, kernels).reshape(x.shape))
        # for an operator who asks (``hc_stats``): a step collects
        # nothing, and what nothing collects is not traced
        if self.is_mutable_collection("intermediates"):
            with step.scope("hc.coef"):
                for name, value in stats_of(coef).items():
                    self.sow("intermediates", name, value)
        return mixed

    def __call__(self, x: jax.Array) -> Coefficients:
        return self.connect(x).coef


class Sum:
    """How a part's result joins a stream of ONE lane: ``u``, what the
    sub-layer reads, is the stream; :meth:`add` takes a term of its
    result as it comes (``x + y``, in the part's own scope); :meth:`out`
    is the stream after it."""

    def __init__(self, x: jax.Array):
        self.u = self.x = x

    def add(self, y: jax.Array) -> None:
        self.x = self.x + y

    def out(self) -> jax.Array:
        return self.x


class Mix:
    """The same three on several lanes under a connection's ``coef``:
    ``u`` is :func:`read`, the terms are summed, and :meth:`out` is
    :func:`write`; both under the step's part ``hc.mix``, so :meth:`out`
    is called outside the sub-layer's own scope.  On the kernels' path
    the connection hands in ``u`` as :func:`_open` read it and ``close``,
    its :func:`_close`."""

    def __init__(self, x: jax.Array, coef: Coefficients, u=None,
                 close=None):
        self.x, self.coef, self.y, self.close = x, coef, None, close
        if u is None:
            with step.scope("hc.mix"):
                u = read(x, coef.pre)
        self.u = u

    def add(self, y: jax.Array) -> None:
        self.y = y if self.y is None else self.y + y

    def out(self) -> jax.Array:
        if self.close is not None:
            return self.close(self.y)
        with step.scope("hc.mix"):
            return write(self.x, self.y, self.coef)


def residual(cfg, x: jax.Array):
    """The residual strategy of a part whose input is ``x``: :class:`Sum`
    where the configuration carries one lane, else :class:`Mix` under a
    :class:`Connection` of the part's own (named ``hc``; call inside the
    part's ``__call__``)."""
    if cfg.hc_mult == 1:
        return Sum(x)
    return Connection(cfg, name="hc").connect(x)


def _kernels(x: jax.Array, n: int) -> Optional[bool]:
    """How the passes over the lanes of ``x [..., T, n C]`` run
    (``lane_mix.mode``): ``None`` the ``jnp`` forms."""
    tokens = math.prod(x.shape[:-1])
    return lane_mix.mode(tokens, n, x.shape[-1] // n, x.dtype, COEF_DTYPE)


def plan_args(cfg, batch: int, tokens: int) -> Dict[str, Any]:
    """What was compiled for a step of ``batch`` sequences of ``tokens``
    (a connection sees one sequence a call), for the ``hc.plan`` span:
    ``impl`` says how the passes over the lanes run; on the kernels'
    path ``x_reads`` says how often a connection's forward (project,
    ``read``, write), recompute (``read``: the projection is kept) and
    backward (``lane_mix``'s three) read ``X``, and ``kernel_calls``
    counts the lanes' kernel calls of a step (five a connection)."""
    lanes = jax.ShapeDtypeStruct((tokens, cfg.hc_mult * cfg.embed_dim),
                                 getattr(cfg, "dtype", cfg.param_dtype))
    kernels = _kernels(lanes, cfg.hc_mult) is not None
    connections = 2 * batch * sum(getattr(cfg, name, 0) for name in (
        "num_dense_layers", "num_layers", "num_mtp_layers"))
    recomputed = 1 if getattr(cfg, "remat", "") else 0
    return {"lanes": cfg.hc_mult, "iters": SINKHORN_ITERS,
            "clamp": ",".join(f"{c:g}" for c in CLAMP),
            "eps": SINKHORN_EPS, "width": cfg.embed_dim, "seq": tokens,
            "coef_dtype": jnp.dtype(COEF_DTYPE).name,
            "impl": "pallas" if kernels else "jnp",
            "x_reads": f"3,{recomputed},3" if kernels else "",
            "kernel_calls": 5 * connections if kernels else 0}
