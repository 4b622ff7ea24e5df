"""The kernels of the residual lanes (``ray_tpu/ops/lane_mix.py``) and
the path ``ray_tpu/models/hyper.py`` takes through them, against
``hyper.py``'s own ``jnp`` forms: on the CPU, the kernels through the
Pallas interpreter.  Values and gradients only: nothing here is a speed.

The cell's gradient tolerance cannot tell a bfloat16 projection from the
float32 one the configuration states (PERF.md section 6, PR 54:
``coef_bf16`` passes): :func:`test_the_projection_is_the_float32_product`
is what does.
"""

import contextlib
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from ray_tpu.models import hyper
from ray_tpu.ops import lane_mix

HIGHEST = jax.lax.Precision.HIGHEST
#: tokens, lanes, a lane's columns: tiny (two tiles of tokens where the
#: kernels cut them by 16), and ONE tile at the published width (``n C``
#: = 14,336 and 24 coefficients)
TINY = (32, 4, 128)
PUBLISHED = (16, 4, 3584)
SHAPES = {"tiny": TINY, "published": PUBLISHED}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def _arrays(shape, dtype, seed=0):
    tokens, n, width = shape
    m = n * (n + 2)
    k = jax.random.split(jax.random.PRNGKey(seed), 10)
    normal = jax.random.normal
    return types.SimpleNamespace(
        n=n, m=m, width=width,
        x=normal(k[0], (tokens, n * width), jnp.float32).astype(dtype),
        d=normal(k[1], (tokens, n * width), jnp.float32).astype(dtype),
        y=normal(k[2], (tokens, width), jnp.float32).astype(dtype),
        du=normal(k[3], (tokens, width), jnp.float32).astype(dtype),
        phi=0.02 * normal(k[4], (n * width, m), jnp.float32),
        res=jax.random.uniform(k[5], (n, n, tokens)),
        pre=jax.random.uniform(k[6], (n, tokens)),
        post=2.0 * jax.random.uniform(k[7], (n, tokens)),
        g=normal(k[8], (m, tokens)), dss=normal(k[9], (tokens,)))


def _close(got, want, dtype):
    """Within float32's noise; a bfloat16 result within ONE rounding of
    the float32 sum (the ``jnp`` backward rounds every term)."""
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(np.abs(want).max(), 1e-6))


def _f32(a):
    return a.astype(jnp.float32)


# ---------------------------------------------------------------------------
# each kernel against the jnp form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_project_is_the_product_and_the_squares_sum(shape, dtype):
    a = _arrays(shape, dtype)
    proj, sumsq = lane_mix.project(a.x, a.phi, a.n, True)
    want = jnp.dot(a.phi.T, _f32(a.x).T, precision=HIGHEST)
    assert proj.shape == want.shape and proj.dtype == jnp.float32
    err = np.linalg.norm(np.asarray(proj - want)) / np.linalg.norm(want)
    assert err < 2e-5, err
    np.testing.assert_allclose(np.asarray(sumsq),
                               np.asarray((_f32(a.x) ** 2).sum(-1)),
                               rtol=1e-5)


@pytest.mark.parametrize("kept", [3, 1], ids=["three_pieces", "one_piece"])
def test_the_projection_is_the_float32_product(kept, monkeypatch):
    """At the published width, against the float32 ``HIGHEST`` product
    of the ``jnp`` form: the kernel's three bfloat16 pieces of ``phi``
    are inside 2e-5 of its norm (they read 4.4e-7 here).  The line is
    float32's own noise with room: two orders of summing the same 14,336
    float32 terms of one coefficient (numpy's pairwise ``sum`` against a
    running ``cumsum``) differ by 6e-8 of the terms' norm here.  ONE
    piece of ``phi`` (a bfloat16 product, which the cell's ``GRAD_RTOL``
    cannot refuse) is off by 1.6e-3: eighty times over the line."""
    a = _arrays(PUBLISHED, jnp.bfloat16)
    want = np.asarray(jnp.dot(a.phi.T, _f32(a.x).T, precision=HIGHEST))
    terms = np.asarray(a.phi)[:, 0] * np.asarray(_f32(a.x))[0]
    orders = abs(float(terms.sum()) - float(np.cumsum(terms)[-1]))
    assert orders < 2e-5 * np.linalg.norm(terms)
    if kept == 1:
        monkeypatch.setattr(lane_mix, "pieces", lambda p: (
            jax.lax.reduce_precision(p.astype(jnp.float32), 8, 7),
            jnp.zeros(p.shape, jnp.float32),
            jnp.zeros(p.shape, jnp.float32)))
        # the builder keeps its trace of these shapes: build afresh
        build = lane_mix._lane_project.__wrapped__
        monkeypatch.setattr(lane_mix, "_lane_project", lane_mix.traced_once(
            "rows", "interpret")(lambda x, phi, rows, interpret: build(
                x, phi, rows, interpret)))
    proj, _ = lane_mix.project(a.x, a.phi, a.n, True)
    err = np.linalg.norm(np.asarray(proj) - want) / np.linalg.norm(want)
    assert err < 2e-5 if kept == 3 else err > 1e-3, err


def test_float32_lanes_take_the_unsplit_path(monkeypatch):
    """Float32 ``x`` is multiplied as it is: nothing is cut in pieces."""
    def never(_):
        raise AssertionError("a float32 operand was cut in pieces")

    monkeypatch.setattr(lane_mix, "pieces", never)
    a = _arrays((16, 2, 128), jnp.float32, seed=3)
    proj, _ = lane_mix.project(a.x, a.phi, a.n, True)
    dx, dphi = lane_mix.open_bwd(a.x, a.d, a.du, a.res, a.pre, a.phi, a.g,
                                 a.dss, True)
    assert proj.shape == (a.m, 16) and dx.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(dphi),
        np.asarray(jnp.dot(a.x.T, a.g.T, precision=HIGHEST)), rtol=2e-5,
        atol=1e-4)


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_write_is_hypers_write(shape, dtype):
    a = _arrays(shape, dtype)
    got = lane_mix.write(a.x, a.y, a.res, a.post, True)
    want = hyper.write(a.x[None], a.y[None],
                       hyper.Coefficients(a.pre, a.post, a.res))[0]
    assert got.dtype == dtype
    # the same float32 sums in the same order, rounded once (a compiler
    # that contracts a product and a sum moves a tie by one bfloat16 ulp)
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(_f32(got)), np.asarray(_f32(want)),
                               rtol=tol, atol=1e-6)
    if dtype == jnp.bfloat16:
        assert np.mean(np.asarray(got) != np.asarray(want)) < 1e-3


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_write_bwd_is_the_backward_of_hypers_write(shape, dtype):
    a = _arrays(shape, dtype)
    _, pull = jax.vjp(
        lambda y, res, post: hyper.write(
            _f32(a.x)[None], y[None],
            hyper.Coefficients(a.pre, post, res))[0],
        _f32(a.y), a.res, a.post)
    dy, dres, dpost = pull(_f32(a.d))
    got = lane_mix.write_bwd(a.x, a.d, a.y, a.post, True)
    assert got[0].dtype == dtype
    _close(got[0], dy, dtype)
    _close(got[1], dres, jnp.float32)
    _close(got[2], dpost, jnp.float32)


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_read_bwd_is_the_backward_of_hypers_read(shape, dtype):
    a = _arrays(shape, dtype)
    _, pull = jax.vjp(lambda pre: hyper.read(_f32(a.x)[None], pre)[0], a.pre)
    _close(lane_mix.read_bwd(a.x, a.du, a.n, True), pull(_f32(a.du))[0],
           jnp.float32)


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_open_bwd_writes_the_whole_of_dx_and_dphi(shape, dtype):
    """``d x`` through ``res`` from ``d``, through ``pre`` from ``du``,
    through the projection from ``g`` and through the squares' sum from
    ``dss``: the backward of the four ``jnp`` forms added up."""
    a = _arrays(shape, dtype)
    x = _f32(a.x)

    def through(x, phi):
        out = hyper.write(x[None], jnp.zeros_like(_f32(a.y))[None],
                          hyper.Coefficients(a.pre, a.post, a.res))[0]
        return (out, hyper.read(x[None], a.pre)[0],
                jnp.dot(phi.T, x.T, precision=HIGHEST), (x * x).sum(-1))

    _, pull = jax.vjp(through, x, a.phi)
    dx, dphi = pull((_f32(a.d), _f32(a.du), a.g, a.dss))
    got = lane_mix.open_bwd(a.x, a.d, a.du, a.res, a.pre, a.phi, a.g,
                            a.dss, True)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    _close(got[0], dx, dtype)
    _close(got[1], dphi, jnp.float32)


# ---------------------------------------------------------------------------
# a connection around a sub-layer: hyper.py's two paths
# ---------------------------------------------------------------------------

def _connection(shape, dtype, kernels, patches=()):
    """Loss, ``X'`` and the gradients (``phi``, ``b``, ``gates``; ``X``;
    the sub-layer's weight, which ``y`` reaches the loss through) of one
    connection around ``y = tanh(u w)``, on the ``jnp`` path (``kernels``
    ``None``) or through the interpreted kernels (``True``)."""
    tokens, n, width = shape
    cfg = types.SimpleNamespace(hc_mult=n, param_dtype=jnp.float32,
                                embed_dim=width)
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(k[0], (1, tokens, n * width),
                          jnp.float32).astype(dtype)
    w = (jax.random.normal(k[1], (width, width)) / width ** 0.5).astype(
        dtype)
    conn = hyper.Connection(cfg)
    params = meta.unbox(conn.init(k[2], x)["params"])
    # away from the identity-like start, so every term carries weight
    params = dict(params, phi=5.0 * params["phi"],
                  b=params["b"] + 0.5 * jax.random.normal(
                      k[3], params["b"].shape),
                  gates=jnp.array([0.5, 0.7, 0.9]))

    def loss(params, x, w):
        mix = conn.apply({"params": params}, x, method="connect")
        mix.add(jnp.tanh(mix.u @ w))
        out = mix.out()
        return 0.01 * (_f32(out) ** 2).sum(), out

    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            lane_mix, "kernel_mode", lambda interpret: kernels))
        for name, stand_in in patches:
            stack.enter_context(mock.patch.object(hyper, name, stand_in))
        (value, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(params, x, w)
    return value, out, grads


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_a_connection_through_the_kernels_is_the_jnp_connection(shape,
                                                                dtype):
    want = _connection(shape, dtype, None)
    got = _connection(shape, dtype, True)
    exact = dtype == jnp.float32
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    np.testing.assert_allclose(np.asarray(_f32(got[1])),
                               np.asarray(_f32(want[1])), rtol=1e-5,
                               atol=1e-5 if exact else 2.0 ** -6)
    (p_got, x_got, w_got), (p_want, x_want, w_want) = got[2], want[2]
    # the connection's own leaves are float32 sums on both paths; the
    # bfloat16 ``d X`` is rounded once here and a term at a time there
    for name in ("phi", "b", "gates"):
        err = np.linalg.norm(np.asarray(p_got[name] - p_want[name])) \
            / np.linalg.norm(np.asarray(p_want[name]))
        assert err < (2e-5 if exact else 2e-3), (name, err)
    for a, b in ((x_got, x_want), (w_got, w_want)):
        err = np.linalg.norm(np.asarray(_f32(a) - _f32(b))) \
            / np.linalg.norm(np.asarray(_f32(b)))
        assert err < (2e-5 if exact else 2.0 ** -7), err


def test_both_parts_keep_their_names_on_the_kernel_path():
    """``hc_roofline`` reads the device time under ``hc.coef`` AND
    ``hc.mix``: the projection and the coefficients' arithmetic (forward
    and backward) stand under the first, ``read``, ``write`` and the
    lanes' backward under the second, by the reader the benchmark uses
    (an op is of the OUTERMOST part in its name: a module method called
    ``mix`` once put all of it under ``hc.mix``)."""
    import re

    from benchmarks.reduce import scopes
    from ray_tpu.models import step

    tokens, n, width = TINY
    cfg = types.SimpleNamespace(hc_mult=n, param_dtype=jnp.float32,
                                embed_dim=width)
    x = jnp.ones((1, tokens, n * width), jnp.bfloat16)

    def loss(params, x):
        mix = hyper.Connection(cfg, name="hc").apply(
            {"params": params}, x, method="connect")
        mix.add(jnp.tanh(mix.u))
        return _f32(mix.out()).sum()

    with mock.patch.object(lane_mix, "kernel_mode", lambda interpret: True):
        params = meta.unbox(hyper.Connection(cfg, name="hc").init(
            jax.random.PRNGKey(0), x)["params"])
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, x).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    of = {part: [name for name in names
                 if scopes.part(name, step.PARTS) == part]
          for part in ("hc.coef", "hc.mix")}
    for part, phase in (("hc.coef", "jvp("), ("hc.coef", "transpose("),
                        ("hc.mix", "jvp("), ("hc.mix", "transpose(")):
        assert [name for name in of[part] if phase in name], (part, phase)
    # the kernels' calls: each under the part PERF.md says
    for kernel, part in (("_lane_project", "hc.coef"),
                         ("_lane_write", "hc.mix"),
                         ("_lane_write_bwd", "hc.mix"),
                         ("_lane_read_bwd", "hc.mix"),
                         ("_lane_open_bwd", "hc.mix")):
        under = {scopes.part(name, step.PARTS) for name in names
                 if f"jit({kernel})" in name}
        assert under == {part}, (kernel, under)


def _ones(x, dtype):
    return jnp.ones(x.shape[:-1], dtype)


def _rows_alone(logits, iters, eps):
    # (the controls' rows-first order converges to what columns-first
    # does: twenty steps leave nothing of the order to see)
    m = jnp.exp(logits)
    return m / (m.sum(1, keepdims=True) + eps)


CONTROLS = {"lane_scale": _ones, "sinkhorn": _rows_alone,
            "SINKHORN_ITERS": 1, "POST_GAIN": 1.0}


@pytest.mark.parametrize("name", CONTROLS)
def test_a_patched_name_changes_the_kernel_path(name):
    """``benchmarks/controls/xing.py`` breaks the program by patching
    these while it is traced: on the kernels' path each is still read
    at trace time (after a sound trace of the same shapes), and what it
    breaks is what it breaks on the ``jnp`` path."""
    sound = _connection(TINY, jnp.bfloat16, True)
    patch = [(name, CONTROLS[name])]
    broken = _connection(TINY, jnp.bfloat16, True, patch)
    assert abs(float(broken[0]) - float(sound[0])) > 1e-3 * float(sound[0])
    assert float(broken[0]) == pytest.approx(
        float(_connection(TINY, jnp.bfloat16, None, patch)[0]), rel=1e-5)


# ---------------------------------------------------------------------------
# which path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tokens,n,width,dtype,coef,interpret,want", [
    (2048, 4, 3584, jnp.bfloat16, jnp.float32, True, True),
    (2048, 4, 1024, jnp.float32, jnp.float32, False, False),
    (2048, 4, 3584, jnp.float32, jnp.float32, True, None),     # no tile
    (2048, 4, 3584, jnp.bfloat16, jnp.float32, None, None),    # no TPU
    (2048, 4, 3584, jnp.bfloat16, jnp.bfloat16, True, None),   # COEF_DTYPE
    (2048, 4, 3584, jnp.float16, jnp.float32, True, None),
    (2048, 4, 3500, jnp.bfloat16, jnp.float32, True, None),    # no register
    (2040, 4, 3584, jnp.bfloat16, jnp.float32, True, None),    # no tile
    (2048, 1, 3584, jnp.bfloat16, jnp.float32, True, None),    # one lane
    (2048, 6, 128, jnp.bfloat16, jnp.float32, True, None),     # 144 columns
    (16, 4, 8, jnp.float32, jnp.float32, True, None),
])
def test_mode(tokens, n, width, dtype, coef, interpret, want):
    assert lane_mix.mode(tokens, n, width, dtype, coef, interpret) is want


@pytest.mark.parametrize("why", ["no_tile", "coef_bf16", "no_tpu"])
def test_a_connection_falls_back_to_jnp(why, monkeypatch):
    """Shapes no tile fits, bfloat16 coefficients and a backend that is
    no TPU run ``hyper.py``'s ``jnp`` forms: no kernel is built."""
    def never(*_, **__):
        raise AssertionError("a kernel was built")

    for name in ("project", "write", "write_bwd", "read_bwd", "open_bwd"):
        monkeypatch.setattr(lane_mix, name, never)
    shape, kernels, patches = TINY, True, ()
    if why == "no_tile":
        shape = (24, 4, 128)
    elif why == "coef_bf16":
        patches = [("COEF_DTYPE", jnp.bfloat16)]
    else:
        kernels = lane_mix.kernel_mode(None)
        assert kernels is None
    value, out, _ = _connection(shape, jnp.bfloat16, kernels, patches)
    assert np.isfinite(float(value)) and out.shape == (1, shape[0], 4 * 128)


def test_the_tiles_at_the_published_size():
    """A sequence of the cell: whole tiles, the backward's last pass 128
    tokens (its ``g`` lies tokens last), all blocks of a grid step twice
    inside the kernels' VMEM limit."""
    tiles = lane_mix.tiles(2048, 4, 3584, jnp.bfloat16)
    assert tiles == (256, 256, 256, 256, 128)
    lane = 3584 * 2
    held = {"project": 4, "write": 9, "write_bwd": 10, "read_bwd": 5,
            "open_bwd": 13}
    for name, rows in tiles._asdict().items():
        assert 2 * rows * held[name] * lane < lane_mix.VMEM_LIMIT
