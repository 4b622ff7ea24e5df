"""The short causal convolution's kernels (``ray_tpu/ops/short_conv.py``)
through the Pallas interpreter on the CPU, against the plain ``jnp`` form
and the benchmark's reference.  Results only: nothing here is a speed."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops import short_conv as sc

CHANNELS = 256


@pytest.fixture(autouse=True)
def tiles_of_64_rows(monkeypatch):
    """A sequence of ``n x 64`` rows is then ``n`` row tiles of one
    unit; the channels two blocks of one register."""
    monkeypatch.setattr(sc, "ROWS", 64)
    monkeypatch.setattr(sc, "BLOCK_LANES", 128)


def _inputs(batch, seq, taps, dtype, channels=CHANNELS):
    k = jax.random.split(jax.random.PRNGKey(seq + taps), 4)
    u = jax.random.normal(k[0], (batch, seq, channels)).astype(dtype)
    w = 0.5 * jax.random.normal(k[1], (taps, channels))
    bias = jax.random.normal(k[2], (channels,))
    ct = jax.random.normal(k[3], (batch, seq, channels))
    return u, w, bias, ct


def _kernels(u, w, bias):
    return sc.short_conv(u, w, bias, interpret=True)


def _grads(conv, u, w, bias, ct):
    return jax.grad(lambda u, w, bias: (
        conv(u, w, bias).astype(jnp.float32) * ct).sum(), (0, 1, 2))(
            u, w, bias)


def _reference(u, w, bias):
    """``benchmarks/reference/nemotron_h.py``'s, a sequence at a time in
    float32."""
    return jnp.stack([ref.causal_conv(s.astype(jnp.float32), w, bias)
                      for s in u])


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("row_tiles", [2, 3])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 4e-3)])
def test_forward_and_the_three_gradients(dtype, tol, row_tiles, taps):
    u, w, bias, ct = _inputs(2, 64 * row_tiles, taps, dtype)
    assert sc.tiles(u, taps) == (64, 128)
    got = _kernels(u, w, bias)
    assert got.dtype == dtype and got.shape == u.shape
    _close(got, sc.short_conv_jnp(u, w, bias), tol)
    _close(got, _reference(u, w, bias), tol)
    mine = _grads(_kernels, u, w, bias, ct)
    assert [g.dtype for g in mine] == [dtype, jnp.float32, jnp.float32]
    for form in (sc.short_conv_jnp,
                 lambda *a: _reference(*a).astype(dtype)):
        for g, want in zip(mine, _grads(form, u, w, bias, ct)):
            assert g.shape == want.shape
            _close(g, want, 2 * tol)


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("row_tiles", [2, 3])
def test_a_spike_crosses_the_tiles_edge_by_taps_less_one_rows(row_tiles,
                                                              taps):
    seq, edge = 64 * row_tiles, 64 * (row_tiles - 1)
    w = jnp.ones((taps, CHANNELS))
    bias = jnp.zeros((CHANNELS,))
    # forward: the last row of the tile before the edge
    u = jnp.zeros((1, seq, CHANNELS)).at[0, edge - 1].set(1.0)
    out = np.asarray(_kernels(u, w, bias))
    rows = np.flatnonzero(np.abs(out[0]).max(-1))
    assert rows.tolist() == list(range(edge - 1, edge - 1 + taps))
    # backward: a cotangent in the first row behind the edge
    ct = jnp.zeros((1, seq, CHANNELS)).at[0, edge].set(1.0)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, seq, CHANNELS))
    du = np.asarray(_grads(_kernels, u, w, bias, ct)[0])
    rows = np.flatnonzero(np.abs(du[0]).max(-1))
    assert rows.tolist() == list(range(edge - (taps - 1), edge + 1))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_past_holds_bit_for_bit_when_the_future_changes(dtype):
    u, w, bias, _ = _inputs(1, 192, 4, dtype)
    got = np.asarray(_kernels(u, w, bias), np.float32)
    for t in (1, 63, 64, 65, 130):
        later = u.at[:, t:].set(7.0)
        again = np.asarray(_kernels(later, w, bias), np.float32)
        assert (again[:, :t] == got[:, :t]).all()
        assert (again[:, t] != got[:, t]).any()


@pytest.mark.parametrize("batch", [1, 2])
def test_a_sequence_starts_from_zeros(batch):
    """Not from the call before it, nor from the sequence before it in
    the batch: the head of every sequence is that of a call of its own."""
    u, w, bias, ct = _inputs(batch, 128, 4, jnp.float32)
    _kernels(100.0 + u, w, bias).block_until_ready()
    got = _kernels(u, w, bias)
    by_hand = jax.nn.silu(bias + w[3] * u[:, 0])   # t = 0 sees itself
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(by_hand),
                               rtol=1e-6, atol=1e-6)
    each = jnp.concatenate([_kernels(u[i:i + 1], w, bias)
                            for i in range(batch)])
    assert (np.asarray(got) == np.asarray(each)).all()
    whole = _grads(_kernels, u, w, bias, ct)
    parts = [_grads(_kernels, u[i:i + 1], w, bias, ct[i:i + 1])
             for i in range(batch)]
    assert (np.asarray(whole[0]) == np.asarray(
        jnp.concatenate([p[0] for p in parts]))).all()
    for k in (1, 2):   # d w and d bias are sums over the batch
        np.testing.assert_allclose(np.asarray(whole[k]), np.asarray(
            sum(p[k] for p in parts)), rtol=1e-5, atol=1e-5)


def _kernel_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(sub)


def test_the_first_result_of_both_calls_is_two_dimensional():
    """The benchmark's readers tell kernel calls apart by result shapes
    (``benchmarks/reduce/kernels.py``): 2-d ``[batch x seq, channels]``
    is filed with the fused norms; 3-d would read as a flash call,
    ``[taps, C]`` first as a grouped product."""
    u, w, bias, ct = _inputs(2, 128, 4, jnp.bfloat16)
    calls = list(_kernel_calls(jax.make_jaxpr(
        lambda *a: _grads(_kernels, *a, ct))(u, w, bias).jaxpr))
    assert len(calls) == 2
    for call in calls:
        assert call.outvars[0].aval.shape == (2 * 128, CHANNELS)
        assert call.outvars[0].aval.dtype == jnp.bfloat16
    assert [v.aval.shape for v in calls[1].outvars[1:]] == \
        [(4, CHANNELS), (1, CHANNELS)]
    assert all(v.aval.dtype == jnp.float32 for v in calls[1].outvars[1:])


@pytest.mark.parametrize("shape,taps", [
    ((1, 12, 6), 4),        # tests/test_nemotron_h.py's
    ((1, 128, 192), 4),     # channels: a register and a half
    ((1, 100, 256), 4),     # rows: no whole unit
    ((2, 128, 256), 1),     # no tap before the row itself
    ((2, 128, 256), 10),    # more rows back than the halo is formed for
])
def test_shapes_the_kernels_refuse_take_the_plain_form(shape, taps):
    u = jax.random.normal(jax.random.PRNGKey(0), shape)
    w = jax.random.normal(jax.random.PRNGKey(1), (taps, shape[-1]))
    bias = jnp.zeros((shape[-1],))
    assert sc.tiles(u, taps) is None
    text = str(jax.make_jaxpr(_kernels)(u, w, bias))
    assert "pallas_call" not in text
    np.testing.assert_allclose(
        np.asarray(_kernels(u, w, bias)),
        np.asarray(_reference(u, w, bias)), rtol=1e-5, atol=1e-6)


def test_the_model_s_convolution_is_this_one_and_the_backend_decides():
    u, w, bias, _ = _inputs(1, 128, 4, jnp.bfloat16)
    off_chip = str(jax.make_jaxpr(nh.causal_conv)(u, w, bias))
    assert "pallas_call" not in off_chip      # this is the CPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        on_chip = str(jax.make_jaxpr(   # a trace of its own
            lambda *a: nh.causal_conv(*a))(u, w, bias))
    assert on_chip.count("pallas_call") == 1 and "short_conv" in on_chip
    assert nh.causal_conv(u, w, bias).dtype == jnp.bfloat16
    assert nh.causal_conv(u.astype(jnp.float32), w, bias).dtype == \
        jnp.float32
