"""The gated group norm's kernels (``ray_tpu/ops/gate_norm.py``) through
the Pallas interpreter on the CPU, against the plain ``jnp`` form and the
norm written out by hand.  Results only: nothing here is a speed."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops import gate_norm as gn

EPS = 1e-5
#: (groups, width): the cell's, the narrowest a kernel takes (two groups
#: a block of lanes), one group of two registers
GROUPS = [(8, 512), (2, 128), (1, 256)]


@pytest.fixture(autouse=True)
def tiles_of_64_rows(monkeypatch):
    """``n x 64`` rows are then ``n`` row tiles of one unit."""
    monkeypatch.setattr(gn, "ROWS", 64)


def _rows(row_tiles):
    return 64 * row_tiles


def _inputs(rows, groups, width, dtype):
    k = jax.random.split(jax.random.PRNGKey(rows + width), 4)
    inner = groups * width
    y = jax.random.normal(k[0], (1, rows, inner)).astype(dtype)
    z = jax.random.normal(k[1], (1, rows, inner)).astype(dtype)
    scale = 1.0 + 0.5 * jax.random.normal(k[2], (inner,))
    ct = jax.random.normal(k[3], (1, rows, inner))
    return y, z, scale, ct


def _kernels(groups):
    return lambda y, z, scale: gn.gate_norm(y, z, scale, groups, EPS,
                                            interpret=True)


def _plain(groups):
    return lambda y, z, scale: gn.gated_group_norm_jnp(y, z, scale, groups,
                                                       EPS)


def _by_hand(y, z, scale, groups):
    """As ``benchmarks/reference/nemotron_h.py``'s mixer ends: float32,
    a square root and a division."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(*g.shape[:-1], groups, -1)
    parts = parts / jnp.sqrt((parts * parts).mean(-1, keepdims=True) + EPS)
    return parts.reshape(g.shape) * scale


def _grads(norm, y, z, scale, ct):
    return jax.grad(lambda y, z, scale: (
        norm(y, z, scale).astype(jnp.float32) * ct).sum(), (0, 1, 2))(
            y, z, scale)


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _cases(f):
    f = pytest.mark.parametrize("groups,width", GROUPS)(f)
    f = pytest.mark.parametrize("row_tiles", [1, 3])(f)
    return pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                                 (jnp.bfloat16, 4e-3)])(f)


@_cases
def test_forward(dtype, tol, row_tiles, groups, width):
    rows = _rows(row_tiles)
    y, z, scale, _ = _inputs(rows, groups, width, dtype)
    assert gn.tiles(rows, groups * width, groups, dtype) == \
        (rows // row_tiles, max(width, 256))
    got = _kernels(groups)(y, z, scale)
    assert got.dtype == dtype and got.shape == y.shape
    _close(got, _plain(groups)(y, z, scale), tol)
    _close(got, _by_hand(y, z, scale, groups), tol)


@_cases
def test_the_three_gradients(dtype, tol, row_tiles, groups, width):
    y, z, scale, ct = _inputs(_rows(row_tiles), groups, width, dtype)
    mine = _grads(_kernels(groups), y, z, scale, ct)
    assert [g.dtype for g in mine] == [dtype, dtype, jnp.float32]
    for g, want in zip(mine, _grads(_plain(groups), y, z, scale, ct)):
        _close(g, want, 2 * tol)


@pytest.mark.parametrize("groups,width", GROUPS)
def test_a_row_and_a_group_stand_alone(groups, width):
    """A row's result depends on its own row alone and a group's on its
    own lanes alone, bit for bit."""
    y, z, scale, _ = _inputs(128, groups, width, jnp.float32)
    got = np.asarray(_kernels(groups)(y, z, scale))
    other = y.at[:, 70, width - 1].set(9.0)
    again = np.asarray(_kernels(groups)(other, z, scale))
    changed = np.argwhere(again != got)
    assert set(changed[:, 1]) == {70}
    assert changed[:, 2].max() < width and len(changed) == width


def _kernel_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(sub)


@pytest.mark.parametrize("groups,width", GROUPS)
def test_the_first_result_of_both_calls_is_two_dimensional(groups, width):
    """The benchmark's readers tell kernel calls apart by result shapes
    (``benchmarks/reduce/kernels.py``): 2-d ``[tokens, inner]`` is filed
    with the fused norms; 3-d ``[1, tokens, inner]`` would read as a
    flash call, 3-d with the groups first as a grouped product."""
    y, z, scale, ct = _inputs(64, groups, width, jnp.bfloat16)
    inner = groups * width
    calls = list(_kernel_calls(jax.make_jaxpr(
        lambda *a: _grads(_kernels(groups), *a, ct))(y, z, scale).jaxpr))
    assert len(calls) == 2
    assert [[v.aval.shape for v in call.outvars] for call in calls] == \
        [[(64, inner)], [(64, inner), (64, inner), (1, inner)]]
    assert [v.aval.dtype for call in calls for v in call.outvars] == \
        [jnp.bfloat16] * 3 + [jnp.float32]


@pytest.mark.parametrize("rows,groups,width,dtype", [
    (64, 2, 32, jnp.float32),      # ``tiny``: a group of 32 lanes
    (64, 2, 192, jnp.float32),     # a register and a half a group
    (40, 2, 128, jnp.float32),     # rows: no whole unit
    (5, 2, 4, jnp.float32),        # tests/test_nemotron_h.py's
    (64, 2, 128, jnp.float16),     # y and z of two kinds (z float32)
])
def test_shapes_the_kernels_refuse_take_the_plain_form(rows, groups, width,
                                                       dtype):
    y, z, scale, _ = _inputs(rows, groups, width, dtype)
    if dtype == jnp.float16:
        z = z.astype(jnp.float32)
    else:
        assert gn.tiles(rows, groups * width, groups, dtype) is None
    text = str(jax.make_jaxpr(_kernels(groups))(y, z, scale))
    assert "pallas_call" not in text
    np.testing.assert_allclose(
        np.asarray(_kernels(groups)(y, z, scale), jnp.float32),
        np.asarray(_by_hand(y, z, scale, groups)),
        rtol=2e-3 if dtype == jnp.float16 else 1e-5, atol=1e-6)


def test_the_model_s_norm_is_this_one_and_the_backend_decides():
    y, z, scale, _ = _inputs(64, 2, 128, jnp.bfloat16)
    off_chip = str(jax.make_jaxpr(
        lambda *a: nh.gated_group_norm(*a, 2, EPS))(y, z, scale))
    assert "pallas_call" not in off_chip      # this is the CPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        on_chip = str(jax.make_jaxpr(
            lambda *a: nh.gated_group_norm(*a, 2, EPS))(y, z, scale))
    assert on_chip.count("pallas_call") == 1 and "gate_norm" in on_chip
    assert nh.gated_group_norm(y, z, scale, 2, EPS).dtype == jnp.bfloat16
    f32 = jnp.float32
    assert nh.gated_group_norm(y.astype(f32), z.astype(f32), scale, 2,
                               EPS).dtype == f32
