"""The many-lane residual stream (``ray_tpu/models/hyper.py``): the
Sinkhorn steps, the coefficients against the equations written out, the
lanes' sums, the plain residual as the one-lane case, and what a
connection tells its operator."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hyper


def _logits(n, tokens, seed=0, spread=2.0):
    return spread * jax.random.normal(jax.random.PRNGKey(seed),
                                      (n, n, tokens))


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("iters", [1, 20])
def test_sinkhorn_rows_sum_to_one_and_columns_converge(n, iters):
    """After the last step every row sums to 1 up to ``eps``; the columns
    as closely as the iteration has converged: ``stats_of`` reads that as
    the gauge's error, and twenty steps leave less than one."""
    m = hyper.sinkhorn(_logits(n, 64, spread=0.5), iters, 1e-6)
    assert m.shape == (n, n, 64) and float(m.min()) > 0
    np.testing.assert_allclose(np.asarray(m.sum(1)), 1.0, atol=1e-3)
    coef = hyper.Coefficients(jnp.full((n, 64), 0.5), jnp.ones((n, 64)), m)
    error = float(hyper.stats_of(coef)["doubly_stochastic_error"])
    assert error == pytest.approx(float(jnp.abs(m.sum(0) - 1).max()))
    assert error < (1e-4 if iters == 20 else 1.0)
    if iters == 1:
        assert error > 1e-3   # one step has not converged at this spread


def test_sinkhorn_is_column_then_row():
    """The paper's ``T_r(T_c(.))``: one step by hand."""
    logits = _logits(4, 8, seed=1)
    m = jnp.exp(logits)
    m = m / (m.sum(0, keepdims=True) + 1e-6)
    m = m / (m.sum(1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(hyper.sinkhorn(logits, 1, 1e-6)),
                               np.asarray(m), rtol=1e-6)


@pytest.mark.parametrize("iters", [3, 20])
def test_sinkhorn_gradient_matches_finite_differences(iters):
    """The backward is taken THROUGH the steps (a ``lax.scan``)."""
    from jax.test_util import check_grads

    weights = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 5))
    with jax.enable_x64(True):
        logits = jnp.asarray(_logits(3, 5, seed=3, spread=1.0), jnp.float64)
        check_grads(lambda z: (hyper.sinkhorn(z, iters, 1e-6)
                               * weights.astype(jnp.float64)).sum(),
                    (logits,), order=1, modes=("rev",), atol=1e-6,
                    rtol=1e-6)


def _connection(n=4, width=16, tokens=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (1, tokens, n * width))
    phi = 0.2 * jax.random.normal(ks[1], (n * width, n * (n + 2)))
    bias = jax.random.normal(ks[2], (n * (n + 2),))
    gates = jnp.array([0.5, 0.7, 0.9])
    return x, phi, bias, gates


def test_coefficients_are_the_equations_written_out():
    """``[p | q | S] = r phi`` over the normalised lanes, the three
    gates, the sigmoids, the clamp and the Sinkhorn steps, token by
    token in plain numpy-like code; the program takes ``r phi`` as ``(X
    phi) / rms`` and lays the tokens last."""
    n, width = 4, 16
    x, phi, bias, gates = _connection(n, width)
    coef = hyper.coefficients(x, phi, bias, gates, n)
    vec = x[0]
    r = vec / jnp.sqrt(jnp.mean(vec * vec, -1, keepdims=True) + 1e-6)
    z = jnp.dot(r, phi, precision="highest")
    pre = jax.nn.sigmoid(gates[0] * z[:, :n] + bias[:n])
    post = 2 * jax.nn.sigmoid(gates[1] * z[:, n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(gates[2] * z[:, 2 * n:] + bias[2 * n:], -30, 30)
                ).reshape(-1, n, n)
    for _ in range(20):
        m = m / (m.sum(1, keepdims=True) + 1e-6)    # columns: over rows i
        m = m / (m.sum(2, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(coef.pre.T), np.asarray(pre),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(coef.post.T), np.asarray(post),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(coef.res, -1, 0)),
                               np.asarray(m), rtol=1e-4, atol=1e-6)
    assert all(c.dtype == jnp.float32 for c in coef)


def test_the_clamp_bounds_the_logits_before_exp():
    x, phi, bias, gates = _connection()
    wild = hyper.coefficients(x, 1e4 * phi, bias, gates, 4)
    assert bool(jnp.isfinite(wild.res).all())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_read_and_write_are_the_sums_over_the_lanes(dtype):
    n, width = 4, 16
    x, phi, bias, gates = _connection(n, width)
    coef = hyper.coefficients(x, phi, bias, gates, n)
    y = jax.random.normal(jax.random.PRNGKey(9), (1, x.shape[1], width))
    lanes = x.astype(dtype).astype(jnp.float32).reshape(1, -1, n, width)
    yy = y.astype(dtype).astype(jnp.float32)
    u = jnp.einsum("it,btic->btc", coef.pre, lanes)
    out = jnp.einsum("ijt,btjc->btic", coef.res, lanes) \
        + jnp.moveaxis(coef.post, 0, -1)[None, :, :, None] \
        * yy[:, :, None, :]
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    got_u = hyper.read(x.astype(dtype), coef.pre)
    got = hyper.write(x.astype(dtype), y.astype(dtype), coef)
    assert got_u.dtype == dtype and got.dtype == dtype
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got_u, np.float32),
                               np.asarray(u), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(out.reshape(x.shape)), rtol=tol,
                               atol=tol)


def test_one_lane_with_unit_coefficients_is_the_plain_residual():
    """``H_pre = H_post = H_res = 1`` on one lane: ``u = x`` and ``X' =
    x + y``: :class:`hyper.Mix` is then :class:`hyper.Sum`."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 8))
    ones = hyper.Coefficients(jnp.ones((1, 12)), jnp.ones((1, 12)),
                              jnp.ones((1, 1, 12)))
    plain, mixed = hyper.Sum(x), hyper.Mix(x, ones)
    np.testing.assert_array_equal(np.asarray(plain.u), np.asarray(mixed.u))
    for res in (plain, mixed):
        res.add(y)
    np.testing.assert_allclose(np.asarray(plain.out()),
                               np.asarray(mixed.out()), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(plain.out()),
                                  np.asarray(x + y))


def test_lanes_of_and_collapse():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8))
    lanes = hyper.lanes_of(x, 4)
    assert lanes.shape == (2, 6, 32)
    np.testing.assert_allclose(np.asarray(hyper.collapse(lanes, 4)),
                               np.asarray(4 * x), rtol=1e-6)


def test_the_identity_like_start():
    """The program's own init: ``H_pre`` a quarter a lane, ``H_post`` 1,
    ``H_res`` all but the identity, so equal lanes stay equal and the
    stream is the plain residual's."""
    cfg = types.SimpleNamespace(hc_mult=4, param_dtype=jnp.float32,
                                embed_dim=8)
    x = hyper.lanes_of(jax.random.normal(jax.random.PRNGKey(0), (1, 16, 8)),
                       4)
    conn = hyper.Connection(cfg)
    from flax.core import meta

    params = meta.unbox(conn.init(jax.random.PRNGKey(1), x)["params"])
    assert {k: v.shape for k, v in params.items()} == {
        "phi": (32, 24), "b": (24,), "gates": (3,)}
    coef, state = conn.apply({"params": params}, x,
                             mutable=["intermediates"])
    np.testing.assert_allclose(np.asarray(coef.pre), 0.25, atol=0.02)
    np.testing.assert_allclose(np.asarray(coef.post), 1.0, atol=0.05)
    sown = state["intermediates"]
    assert float(sown["offdiag_mass"][0]) < 0.01
    assert float(sown["doubly_stochastic_error"][0]) < 1e-4
    assert float(sown["pre_entropy"][0]) == pytest.approx(np.log(4),
                                                          abs=1e-3)
    assert hyper.plan_args(cfg, 2, 16) == {
        "lanes": 4, "iters": 20, "clamp": "-30,30", "eps": 1e-6,
        "width": 8, "seq": 16, "coef_dtype": "float32", "impl": "jnp",
        "x_reads": "", "kernel_calls": 0}


@pytest.mark.parametrize("remat,reads,calls", [("full", "3,1,3", 5),
                                               ("", "3,0,3", 5)])
def test_the_plan_says_what_the_kernels_read(remat, reads, calls,
                                             monkeypatch):
    """Where the lanes' kernels run (a TPU; here the decision steered as
    a chip would answer) the plan says so: how often a connection's
    forward, recompute and backward read ``X`` and a step's kernel
    calls, two connections a layer and a call a sequence."""
    from ray_tpu.ops import lane_mix

    monkeypatch.setattr(lane_mix, "kernel_mode", lambda interpret: False)
    cfg = types.SimpleNamespace(
        hc_mult=4, param_dtype=jnp.float32, dtype=jnp.bfloat16,
        embed_dim=256, remat=remat, num_dense_layers=1, num_layers=4,
        num_mtp_layers=0)
    args = hyper.plan_args(cfg, 4, 2048)
    assert (args["impl"], args["x_reads"]) == ("pallas", reads)
    assert args["kernel_calls"] == 5 * 2 * 4 * calls
    # shapes the kernels cannot tile, or bfloat16 coefficients: ``jnp``
    assert hyper.plan_args(cfg, 4, 2040)["impl"] == "jnp"
    monkeypatch.setattr(hyper, "COEF_DTYPE", jnp.bfloat16)
    assert hyper.plan_args(cfg, 4, 2048)["kernel_calls"] == 0
