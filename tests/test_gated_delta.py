"""The chunked scan of the gated delta rule (``ray_tpu/ops/gated_delta.py``)
against its definition, the recurrence step by step: values and
gradients at small sizes on the CPU, the triangular inverse by doubling,
what a call says of itself, and what it refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core import telemetry
from ray_tpu.ops import gated_delta as gd


def _inputs(key, b=2, t=64, hk=2, hv=4, d=8, decay=3.0, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, t, hk, d))
    k = jax.random.normal(ks[1], (b, t, hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hv, d))
    g = -jax.random.uniform(ks[3], (b, t, hv), minval=0.0, maxval=decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("chunk", [1, 8, 16, 64])
@pytest.mark.parametrize("heads", [(2, 4), (4, 4)])
def test_the_chunked_form_is_the_recurrence(chunk, heads):
    args = _inputs(jax.random.PRNGKey(0), hk=heads[0], hv=heads[1])
    with jax.default_matmul_precision("highest"):
        want = gd.gated_delta_recurrence(*args)
        got = gd.gated_delta(*args, chunk=chunk)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("chunk", [8, 32])
def test_its_gradients_are_the_recurrence_s(chunk):
    args = _inputs(jax.random.PRNGKey(1))
    weigh = jax.random.normal(jax.random.PRNGKey(2), args[2].shape)
    every = tuple(range(5))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: (gd.gated_delta_recurrence(*a)
                                    * weigh).sum(), every)(*args)
        got = jax.grad(lambda *a: (gd.gated_delta(*a, chunk=chunk)
                                   * weigh).sum(), every)(*args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(g - w)) <= 2e-5 * scale, name


def test_a_strong_decay_overflows_nowhere():
    """Decays of 20 a token: ``exp(gamma_i - gamma_j)`` above the diagonal
    would be ``exp(+1260)``; it is masked before the ``exp``."""
    args = _inputs(jax.random.PRNGKey(3), decay=20.0)
    weigh = jax.random.normal(jax.random.PRNGKey(4), args[2].shape)
    with jax.default_matmul_precision("highest"):
        want = gd.gated_delta_recurrence(*args)
        got, grads = jax.value_and_grad(
            lambda *a: (gd.gated_delta(*a, chunk=64) * weigh).sum(),
            (0, 1, 2, 3, 4))(*args)
        out = gd.gated_delta(*args, chunk=64)
    assert np.isfinite(float(got))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_bfloat16_operands_keep_a_float32_carry():
    args = _inputs(jax.random.PRNGKey(5), t=128, dtype=jnp.bfloat16)
    want = gd.gated_delta_recurrence(*args)
    got = gd.gated_delta(*args, chunk=16)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    assert float(err) < 2e-2
    jaxpr = str(jax.make_jaxpr(lambda *a: gd.gated_delta(*a, chunk=16))(
        *args))
    # the scan's carry: [b, hk, r, dk, dv] float32
    assert "f32[2,2,2,8,8]" in jaxpr


@pytest.mark.parametrize("size", [1, 2, 8, 64])
def test_the_inverse_by_doubling_is_exact_and_its_backward_keeps_no_power(
        size):
    # entries of the size a chunk's are (unit keys, beta under 1): at
    # unit entries the inverse of a 64 x 64 runs to 1e7 and float32 ends
    a = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(6),
                                         (3, size, size)), -1)
    eye = jnp.eye(size)
    with jax.default_matmul_precision("highest"):
        got = gd.unit_lower_inverse(a)
        want = jnp.linalg.inv(eye - a)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        weigh = jax.random.normal(jax.random.PRNGKey(7), a.shape)
        g = jax.grad(lambda a: (gd.unit_lower_inverse(a) * weigh).sum())(a)
        w = jax.grad(lambda a: (jnp.linalg.inv(eye - a) * weigh).sum())(a)
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3,
                               atol=1e-3)


def test_a_call_says_what_it_was_traced_as():
    args = _inputs(jax.random.PRNGKey(8))
    telemetry.drain_spans("test")
    jax.eval_shape(lambda *a: gd.gated_delta(*a, chunk=16), *args)
    rows = [r for r in telemetry.drain_spans("test")
            if (r["cat"], r["name"]) == ("ops", "gated_delta.plan")]
    assert len(rows) == 1
    assert rows[0]["args"] == {
        "key_heads": 2, "value_heads": 4, "key_dim": 8, "value_dim": 8,
        "chunk": 16, "seq": 64, "chunks": 4, "inverse": "doubling",
        "carry": "xla", "saved": "chunk_states",
        "saved_bytes": 4 * 2 * 4 * 4 * 8 * 8}


@pytest.mark.parametrize("kw,match", [
    (dict(t=60, chunk=16), "not whole chunks"),
    (dict(t=48, chunk=12), "no power of two"),
    (dict(hk=3, hv=4, chunk=16), "do not split"),
])
def test_what_it_cannot_run_is_refused_not_padded(kw, match):
    chunk = kw.pop("chunk")
    args = _inputs(jax.random.PRNGKey(9), **kw)
    with pytest.raises(ValueError, match=match):
        gd.gated_delta(*args, chunk=chunk)
