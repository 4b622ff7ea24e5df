"""The chunked scan of the gated delta rule (``ray_tpu/ops/gated_delta.py``)
against its definition, the recurrence step by step: values and
gradients at small sizes on the CPU, the triangular inverse by doubling,
what a call says of itself, and what it refuses.  A case with a head
width of 128 runs the KERNELS through the Pallas interpreter
(``interpret=True``); a width of 8, or a chunk of 1, is a shape they
cannot tile and runs the ``jnp`` form."""

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


#: the kernels' cases: (chunk, (H_k, H_v)) at one sequence of 128, heads
#: of 128, through the interpreter: a key head's two value heads side by
#: side in a slab of 32 lanes and of 128 (the benchmark cell's), and a
#: value head alone
KERNELS = [(16, (2, 4)), (64, (2, 4)), (64, (4, 4))]
_KERNEL_SHAPE = dict(b=1, t=128, d=128)


def _rel(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


@pytest.mark.parametrize("chunk,heads,kernels", [
    *[(c, h, False) for h in ((2, 4), (4, 4)) for c in (1, 8, 16, 64)],
    *[(c, h, True) for c, h in KERNELS], (16, (4, 4), True)])
def test_the_chunked_form_is_the_recurrence(chunk, heads, kernels):
    args = _inputs(jax.random.PRNGKey(0), hk=heads[0], hv=heads[1],
                   **(_KERNEL_SHAPE if kernels else {}))
    with jax.default_matmul_precision("highest"):
        want = gd.gated_delta_recurrence(*args)
        got = gd.gated_delta(*args, chunk=chunk, interpret=kernels or None)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("chunk,heads,kernels", [
    (8, (2, 4), False), (32, (2, 4), False),
    *[(c, h, True) for c, h in KERNELS]])
def test_its_gradients_are_the_recurrence_s(chunk, heads, kernels):
    args = _inputs(jax.random.PRNGKey(1), hk=heads[0], hv=heads[1],
                   **(_KERNEL_SHAPE if kernels else {}))
    weigh = jax.random.normal(jax.random.PRNGKey(2), args[2].shape)
    every = tuple(range(5))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: (gd.gated_delta_recurrence(*a)
                                    * weigh).sum(), every)(*args)
        got = jax.grad(lambda *a: (gd.gated_delta(
            *a, chunk=chunk, interpret=kernels or None) * weigh).sum(),
            every)(*args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(g - w)) <= 2e-5 * scale, name


@pytest.mark.parametrize("kernels", [False, True])
def test_a_strong_decay_overflows_nowhere(kernels):
    """Decays of 20 a token: ``exp(gamma_i - gamma_j)`` above the diagonal
    would be ``exp(+1260)``; it is masked before the ``exp``."""
    args = _inputs(jax.random.PRNGKey(3), decay=20.0,
                   **(_KERNEL_SHAPE if kernels else {}))
    weigh = jax.random.normal(jax.random.PRNGKey(4), args[2].shape)
    def weighed(*a):
        out = gd.gated_delta(*a, chunk=64, interpret=kernels or None)
        return (out * weigh).sum(), out

    with jax.default_matmul_precision("highest"):
        want = gd.gated_delta_recurrence(*args)
        (got, out), grads = jax.value_and_grad(
            weighed, (0, 1, 2, 3, 4), has_aux=True)(*args)
    assert np.isfinite(float(got))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk,heads,kernels", [
    (16, (2, 4), False), *[(c, h, True) for c, h in KERNELS]])
def test_bfloat16_operands_keep_a_float32_carry(chunk, heads, kernels):
    """Values against the recurrence; the kernels' gradients against the
    ``jnp`` form's on the same bfloat16 operands (the same products on
    the same operand widths: what differs is the order of float32
    sums)."""
    shape = _KERNEL_SHAPE if kernels else dict(t=128)
    args = _inputs(jax.random.PRNGKey(5), hk=heads[0], hv=heads[1],
                   dtype=jnp.bfloat16, **shape)
    want = gd.gated_delta_recurrence(*args)
    op = lambda *a, **kw: gd.gated_delta(  # noqa: E731
        *a, chunk=chunk, **kw)
    got = op(*args, interpret=kernels or None)
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 2e-2
    jaxpr = str(jax.make_jaxpr(lambda *a: op(
        *a, interpret=kernels or None))(*args))
    # the scan's carry: [b, hk, r, dk, dv] float32
    b, d = args[2].shape[0], args[2].shape[3]
    hk, hv = heads
    assert f"f32[{b},{hk},{hv // hk},{d},{d}]" in jaxpr
    if kernels:
        weigh = jax.random.normal(jax.random.PRNGKey(6), args[2].shape)
        grads = [jax.grad(lambda *a: (op(*a, interpret=mode).astype(
            jnp.float32) * weigh).sum(), tuple(range(5)))(*args)
            for mode in (None, True)]
        for name, w, g in zip("q k v g beta".split(), *grads):
            assert _rel(g, w.astype(jnp.float32)) < 5e-3, name


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


def _plan_rows(d, chunk, interpret, dtype=jnp.float32):
    args = _inputs(jax.random.PRNGKey(8), d=d, dtype=dtype)
    telemetry.drain_spans("test")
    jax.eval_shape(lambda *a: gd.gated_delta(
        *a, chunk=chunk, interpret=interpret), *args)
    return [r["args"] for r in telemetry.drain_spans("test")
            if (r["cat"], r["name"]) == ("ops", "gated_delta.plan")]


def test_a_call_says_what_it_was_traced_as():
    (row,) = _plan_rows(8, 16, None)
    assert row == {
        "key_heads": 2, "value_heads": 4, "key_dim": 8, "value_dim": 8,
        "chunk": 16, "seq": 64, "chunks": 4, "inverse": "doubling",
        "carry": "xla", "chunk_math": "xla",
        "saved": "chunk_states,carry_operands",
        # the float32 states that entered, and U, W, K exp(..), V'
        "saved_bytes": 4 * 2 * 4 * 4 * 8 * 8 + 4 * 4 * 2 * 4 * 64 * 8}


@pytest.mark.parametrize("d,chunk,interpret,dtype,said", [
    (128, 16, True, jnp.float32, "pallas"),
    (128, 16, True, jnp.bfloat16, "pallas"),
    (128, 16, None, jnp.float32, "xla"),      # off the TPU, left open
    (8, 16, True, jnp.float32, "xla"),        # a head of 8 lanes
    (128, 4, True, jnp.float32, "xla"),       # a chunk under a tile
    (128, 8, True, jnp.bfloat16, "xla"),      # 8 rows of 16 bits: half one
])
def test_the_plan_says_where_the_chunk_math_runs(d, chunk, interpret, dtype,
                                                 said):
    """By the shapes, never by a name: what the kernels cannot tile takes
    the ``jnp`` form whatever ``interpret`` says."""
    (row,) = _plan_rows(d, chunk, interpret, dtype)
    assert (row["chunk_math"], row["carry"]) == (said, "xla")
    assert row["saved_bytes"] == 4 * 2 * 4 * (64 // chunk) * d * d \
        + 4 * jnp.dtype(dtype).itemsize * 2 * 4 * 64 * d


def _scans(jaxpr, outside=""):
    """The name stacks of the ``scan`` equations of ``jaxpr`` that are
    NOT inside a kernel's body (an inner ``jit``'s equations carry their
    names relative to its own)."""
    found = []
    for eqn in jaxpr.eqns:
        name = f"{outside}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "scan":
            found.append(name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scans(sub, name)
    return found


def _kernel_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub)
    return found


def test_the_carry_stays_one_scan_a_pass_under_the_ops_name():
    """What ``gdn_roofline``'s reader and the benchmark's controls stand
    on (``benchmarks/layer_metrics/gdn_roofline.py``,
    ``benchmarks/controls/qwen3_next.py``): a kernel-form call holds ONE
    ``lax.scan`` outside the kernels' bodies, its gradient that one and
    its backward, each under the name ``gated_delta``; the chunk algebra
    around them is kernel calls."""
    args = _inputs(jax.random.PRNGKey(10), **_KERNEL_SHAPE,
                   dtype=jnp.bfloat16)
    op = lambda *a: gd.gated_delta(*a, chunk=64, interpret=True)  # noqa: E731
    forward = jax.make_jaxpr(op)(*args).jaxpr
    (carry,) = _scans(forward)
    assert gd.SCOPE in carry
    assert _kernel_calls(forward) == ["gated_delta_prepare",
                                      "gated_delta_read_out"]
    grad = jax.make_jaxpr(jax.grad(
        lambda *a: op(*a).astype(jnp.float32).sum(), tuple(range(5))))(
            *args).jaxpr
    loops = _scans(grad)
    assert len(loops) == 2 and all(gd.SCOPE in name for name in loops)
    assert sorted(_kernel_calls(grad)) == [
        "gated_delta_prepare", "gated_delta_prepare_bwd",
        "gated_delta_read_out", "gated_delta_read_out_bwd"]
    # the seam the controls patch: the op calls ``lax.scan`` by that name
    assert gd.jax.lax.scan is jax.lax.scan
    assert callable(gd._gated_delta.__wrapped__)


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
