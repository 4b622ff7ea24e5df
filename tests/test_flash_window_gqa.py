"""The flash kernels with ``window=`` and with fewer K/V heads than
query heads: forward, dK/dV and dQ through the Pallas interpreter
against ``_attention_reference`` given the same mask; and the defaults,
which must trace GPT-2's kernels as they were before either existed."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("ray_tpu.ops.flash_attention")


def _qkv(seq, heads, kv_heads, dim, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, seq, heads, dim), jnp.float32)
    k = jax.random.normal(ks[1], (2, seq, kv_heads, dim), jnp.float32)
    v = jax.random.normal(ks[2], (2, seq, kv_heads, dim), jnp.float32)
    g = jax.random.normal(ks[3], (2, seq, heads, dim), jnp.float32)
    return q, k, v, g


def _both(q, k, v, g, window, native, block):
    scale = q.shape[-1] ** -0.5

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  interpret=True, native=native,
                                  block_q=block, block_k=block)

    def plain(q, k, v):
        return fa._attention_reference(q, k, v, True, scale, window)

    out, vjp = jax.vjp(kernel, q, k, v)
    ref, ref_vjp = jax.vjp(plain, q, k, v)
    return (out, *vjp(g)), (ref, *ref_vjp(g))


#: family, heads, K/V heads, head size, sequence, tile, window
CASES = [
    ("native", 4, 4, 128, 256, 64, 100),    # window not a multiple of the tile
    ("native", 4, 4, 128, 256, 64, 64),     # exactly one tile
    ("native", 4, 4, 128, 256, 64, 1),      # each position sees itself alone
    ("native", 4, 4, 128, 128, 64, 300),    # sequence shorter than the window
    ("native", 8, 2, 128, 256, 64, None),   # grouped heads, full attention
    ("native", 8, 2, 128, 256, 64, 100),    # both
    ("native", 4, 4, 64, 256, 64, 100),     # two heads a slab, windowed
    ("head_major", 6, 2, 32, 256, 64, 100),  # both, head-major family
    ("head_major", 6, 3, 32, 256, 64, None),
    ("head_major", 5, 5, 64, 256, 128, 130),
]


@pytest.mark.parametrize("family,heads,kv,dim,seq,block,window", CASES)
def test_window_and_grouped_heads_match_the_reference(
        family, heads, kv, dim, seq, block, window):
    q, k, v, g = _qkv(seq, heads, kv, dim)
    got, want = _both(q, k, v, g, window, family == "native", block)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_the_kernel_functions_take_a_window_wider_than_the_sequence():
    """``flash_attention`` drops such a window; the kernels themselves
    must also be right with it (every tile live, nothing masked by it)."""
    q, k, v, g = _qkv(128, 2, 2, 128)
    scale = 128 ** -0.5
    out, vjp = jax.vjp(lambda *a: fa._flash_nl(
        *a, True, scale, 64, 64, True, 1000), q, k, v)
    ref, ref_vjp = jax.vjp(lambda *a: fa._attention_reference(
        *a, True, scale), q, k, v)
    for a, b in zip((out, *vjp(g)), (ref, *ref_vjp(g))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_grouped_heads_need_one_head_a_slab_in_the_native_family():
    q, k, v, _ = _qkv(128, 4, 2, 64)
    assert not fa._nl_eligible(q, k, v)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, native=True, interpret=True)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k[:, :, :1].repeat(3, 2), v, interpret=True)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, causal=False, window=8, interpret=True)


def _clamped(fn, i, n, *args):
    return sorted({int(fn(jnp.int32(j), jnp.int32(i), *args))
                   for j in range(n)})


def test_index_maps_visit_the_live_tiles_and_no_other():
    """8 tiles of 128, window 300: query tile 5 (rows 640..767) sees keys
    341..767, so K tiles 2..5; key tile 2 (keys 256..383) is seen by
    queries 256..682, so Q tiles 2..5."""
    assert _clamped(fa._clamp_k_tile, 5, 8, 128, 128, 300) == [2, 3, 4, 5]
    assert _clamped(fa._clamp_q_tile, 2, 8, 128, 128, 300) == [2, 3, 4, 5]
    assert _clamped(fa._clamp_k_tile, 5, 8, 128, 128) == [0, 1, 2, 3, 4, 5]
    assert _clamped(fa._clamp_q_tile, 2, 8, 128, 128) == [2, 3, 4, 5, 6, 7]


#: sha256 of the jaxpr text of d(sum(flash_attention(q, k, v)))/d(q,k,v)
#: at GPT-2's call (equal heads of 64, causal, no window) on tiles of
#: 128, too small to be cut in blocks.  Re-pinned in PR 34: the
#: functions that build the kernel calls are jitted (``traced_once``),
#: so the text gained their ``pjit`` frames (``native`` was 1b0b7461...
#: since before ``window`` and grouped heads existed, and its KERNELS
#: are still those: ``tests/test_flash_cut_tiles.py`` holds one block to
#: the parent's); the ``head_major`` kernels besides took
#: ``_causal_dispatch`` (a tile below the diagonal is no longer masked;
#: 54e59604... before)
GOLDEN = {
    "native": ((2, 256, 4, 64),
               "2aa38b1ed09acaa9c33911849a9062f9b407819da730550d4b35046b9d047b13"),
    "head_major": ((2, 256, 5, 64),
                   "bb7ab3dc5118669532adc48986b278d959cbcb6075cd017ad1b3137480c5a601"),
}


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_defaults_trace_the_kernels_gpt2_ran_before(family):
    shape, digest = GOLDEN[family]
    q = jnp.zeros(shape, jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False,
            native=family == "native", block_q=128,
            block_k=128).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert hashlib.sha256(text.encode()).hexdigest() == digest
