"""Kernel correctness tests (pallas interpret mode on CPU)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import fused_rmsnorm, fused_softmax_cross_entropy
from ray_tpu.ops import gate_norm as gn
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import short_conv as sc
from ray_tpu.ops import ssd
from ray_tpu.ops._kernel import kernel_mode
from ray_tpu.ops.flash_attention import (
    _attention_reference,
    flash_attention,
)
from ray_tpu.ops.fused import _rmsnorm_ref


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_kernel_matches_reference(causal):
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 256, 2, 64
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _attention_reference(q, k, v, causal, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_gradients():
    rng = np.random.default_rng(1)
    b, t, h, d = 1, 128, 2, 32
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    def loss_ref(q, k, v):
        return _attention_reference(q, k, v, True, d ** -0.5).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(2)
    b, t, h, d = 1, 128, 2, 64
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _attention_reference(q, q, q, True, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("shape", [(2, 256, 4, 64),   # pack=2 slabs
                                   (1, 256, 3, 128)])  # pack=1 slabs
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_native_layout_matches_head_major(shape, causal):
    """The native-layout kernels (no transposes around the custom-call)
    compute the same blockwise online-softmax in the same order as the
    head-major kernels; only the memory layout differs.  Tolerance is
    ulp-level rather than exact: the NL kernels skip the causal select
    on fully-visible tiles (the head-major path applies an all-true
    mask there), and XLA compiles the two exp() patterns into slightly
    different vectorized code."""
    rng = np.random.default_rng(4)
    b, t, h, d = shape
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    def run(native):
        return jax.vjp(
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=causal, block_q=128, block_k=128,
                interpret=True, native=native), q, k, v)

    out_hm, vjp_hm = run(False)
    out_nl, vjp_nl = run(True)
    np.testing.assert_allclose(np.asarray(out_hm), np.asarray(out_nl),
                               atol=1e-6, rtol=0)
    for a, b_ in zip(vjp_hm(g), vjp_nl(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=0)


def test_flash_attention_native_layout_eligibility():
    from ray_tpu.ops.flash_attention import _nl_eligible

    rng = np.random.default_rng(5)

    def arr(h, d):
        return jnp.asarray(rng.standard_normal((1, 128, h, d)), jnp.float32)

    assert _nl_eligible(arr(4, 64), arr(4, 64), arr(4, 64))
    assert _nl_eligible(arr(3, 128), arr(3, 128), arr(3, 128))
    assert not _nl_eligible(arr(3, 64), arr(3, 64), arr(3, 64))  # odd pack
    assert not _nl_eligible(arr(4, 32), arr(4, 32), arr(4, 32))  # small dim
    with pytest.raises(ValueError):
        flash_attention(arr(4, 32), arr(4, 32), arr(4, 32),
                        interpret=True, native=True)


@pytest.mark.parametrize("q_heads,k_heads,dim,native,env", [
    (20, 20, 64, True, {}),    # gpt2-large: two heads to a 128-lane slab
    (25, 25, 64, False, {}),   # gpt2-xl: 25 heads cannot pack two to a slab
    (32, 4, 128, True, {}),    # trinity-mini: one head a slab, grouped K/V
    (8, 4, 64, False, {}),     # grouped heads cannot share a slab
    (20, 20, 64, True, {"RAY_TPU_FLASH_NATIVE": "0",
                        "RAY_TPU_FLASH_BLOCK_Q": "128"}),
], ids=["gpt2-large", "gpt2-xl", "trinity-mini", "grouped-64", "env-dead"])
def test_flash_family_follows_the_shapes(q_heads, k_heads, dim, native, env,
                                         monkeypatch):
    """The shapes alone pick the kernel family and the default block: the
    environment has no say (the ``RAY_TPU_FLASH_*`` variables are gone)."""
    q = jnp.zeros((1, 2048, q_heads, dim), jnp.bfloat16)
    kv = jnp.zeros((1, 2048, k_heads, dim), jnp.bfloat16)

    def traced():
        return str(jax.make_jaxpr(
            lambda q, k, v: flash_attention(q, k, v, interpret=False)
        )(q, kv, kv))

    text = traced()
    family = "_flash_nl" if native else "_flash"
    # the custom_vjp, and inside it the jitted builder of its kernel call
    assert re.findall(r"name=(_flash\w*)", text) == [
        family, family + "_forward"]
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert traced() == text


def test_rmsnorm_matches_reference():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 64, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
    out = fused_rmsnorm(x, w, interpret=True)
    var = np.mean(np.square(np.asarray(x)), axis=-1, keepdims=True)
    ref = np.asarray(x) / np.sqrt(var + 1e-6) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


def test_cross_entropy():
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((8, 100)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 100, (8,)), jnp.int32)
    loss = fused_softmax_cross_entropy(logits, labels)
    ref = -jax.nn.log_softmax(logits)[jnp.arange(8), labels]
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("interpret,backend,mode", [
    (None, None, None),     # left open off the TPU: the reference form
    (None, "tpu", False),   # left open on the chip: the compiled kernels
    (False, None, False),
    (True, None, True),     # the kernels through the interpreter
])
def test_kernel_mode(interpret, backend, mode, monkeypatch):
    if backend:
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernel_mode(interpret) is mode


def _normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _flash_case():
    args = tuple(_normal(i, (1, 128, 2, 64)) for i in range(3))
    return (flash_attention, lambda q, k, v: _attention_reference(
        q, k, v, True, 64 ** -0.5, None), args)


def _rmsnorm_case():
    args = _normal(0, (64, 256), jnp.bfloat16), _normal(1, (256,))
    return fused_rmsnorm, lambda x, w: _rmsnorm_ref(x, w, 1e-6), args


def _grouped_matmul_case():
    def plan_of(picked):
        return gm.plan_rows(picked, 0, 4, block_m=8)

    def plain(picked, lhs, rhs):
        plan = plan_of(picked)
        return gm._gmm_ref(lhs, rhs, plan.tile_expert, plan.n_live, 8, False)

    picked = jax.random.randint(jax.random.PRNGKey(0), (32, 2), 0, 4)
    rows = jax.eval_shape(plan_of, picked).row_pair.shape[0]
    return (lambda picked, lhs, rhs: gm.grouped_matmul(
        lhs, rhs, plan_of(picked)), plain,
        (picked, _normal(1, (rows, 16)), _normal(2, (4, 16, 24))))


def _short_conv_case():
    u = _normal(0, (1, 128, 128), jnp.bfloat16)
    assert sc.tiles(u, 4) is not None   # a shape the kernels take
    return (sc.short_conv, sc.short_conv_jnp,
            (u, _normal(1, (4, 128)), _normal(2, (128,))))


def _gate_norm_case():
    y, z = (_normal(i, (1, 64, 256), jnp.bfloat16) for i in range(2))
    assert gn.tiles(64, 256, 2, y.dtype) is not None
    return (lambda y, z, scale: gn.gate_norm(y, z, scale, 2, 1e-5),
            lambda y, z, scale: gn.gated_group_norm_jnp(y, z, scale, 2, 1e-5),
            (y, z, _normal(2, (256,))))


def _ssd_case():
    xs = _normal(0, (1, 32, 2, 8))
    dt = jax.nn.softplus(_normal(1, (1, 32, 2)))
    a, d = -jnp.exp(_normal(2, (2,))), _normal(3, (2,))
    b, c = (_normal(i, (1, 32, 1, 16)) for i in (4, 5))
    return (functools.partial(ssd.ssd, chunk=16),
            functools.partial(ssd.ssd_einsum, chunk=16),
            (xs, dt, a, b, c, d))


#: ``flash_attention(k_rope=)`` has this case already:
#: test_flash_mla.py::test_without_a_chip_the_entry_takes_the_reference_on_the_whole_key
@pytest.mark.parametrize("case", [
    _rmsnorm_case, _flash_case, _grouped_matmul_case, _short_conv_case,
    _gate_norm_case, _ssd_case], ids=lambda f: f.__name__[1:-5])
def test_left_open_off_the_tpu_an_entry_is_its_reference_form(case):
    """``interpret=None`` with no TPU: bit for bit the plain form, on a
    shape the kernels would take (both under ``jit``: one compile a
    side)."""
    assert jax.default_backend() != "tpu"
    entry, plain, args = case()
    got, want = jax.jit(entry)(*args), jax.jit(plain)(*args)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
