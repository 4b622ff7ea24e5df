"""The flash kernels with the key in two parts (``k_rope=``: latent
attention): v narrower than q/k and ONE rotary key head shared by every
query head.  Forward, dK/dV and dQ through the Pallas interpreter
against ``_attention_reference`` on the key written out whole; and the
calls the benchmark's other configurations make, which must trace the
kernels they traced before this existed."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("ray_tpu.ops.flash_attention")


def _operands(seq, heads, nope, rope, dim_v, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (2, seq, heads, nope + rope), jnp.float32)
    k = jax.random.normal(ks[1], (2, seq, heads, nope), jnp.float32)
    r = jax.random.normal(ks[2], (2, seq, 1, rope), jnp.float32)
    v = jax.random.normal(ks[3], (2, seq, heads, dim_v), jnp.float32)
    g = jax.random.normal(ks[4], (2, seq, heads, dim_v), jnp.float32)
    return q, k, r, v, g


def _whole_key(k, r):
    return jnp.concatenate(
        [k, jnp.broadcast_to(r, (*k.shape[:3], r.shape[-1]))], -1)


#: heads, nope, rope, value width, sequence, tile, causal
CASES = [
    (4, 16, 8, 16, 64, 64, True),      # the sequence is one tile
    (4, 16, 8, 16, 256, 64, True),     # several: skipped, diagonal, whole
    (3, 128, 64, 128, 256, 128, True),  # the published widths, odd heads
    (2, 32, 16, 24, 128, 32, True),    # v narrower than the key's own part
    (2, 16, 8, 16, 128, 64, False),    # not causal: every tile whole
]


@pytest.mark.parametrize("heads,nope,rope,dim_v,seq,block,causal", CASES)
def test_the_two_part_key_matches_the_reference_on_the_whole_key(
        heads, nope, rope, dim_v, seq, block, causal):
    q, k, r, v, g = _operands(seq, heads, nope, rope, dim_v)
    scale = (nope + rope) ** -0.5

    def kernel(q, k, r, v):
        return fa.flash_attention(q, k, v, k_rope=r, causal=causal,
                                  interpret=True, block_q=block,
                                  block_k=block)

    def plain(q, k, r, v):
        return fa._attention_reference(q, _whole_key(k, r), v, causal,
                                       scale)

    out, vjp = jax.vjp(kernel, q, k, r, v)
    ref, ref_vjp = jax.vjp(plain, q, k, r, v)
    assert out.shape == (2, seq, heads, dim_v)
    for name, a, b in zip(("out", "dq", "dk", "dk_rope", "dv"),
                          (out, *vjp(g)), (ref, *ref_vjp(g))):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_without_a_chip_the_entry_takes_the_reference_on_the_whole_key():
    q, k, r, v, _ = _operands(64, 4, 16, 8, 16)
    got = fa.flash_attention(q, k, v, k_rope=r)
    want = fa._attention_reference(q, _whole_key(k, r), v, True, 24 ** -0.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_what_the_two_part_key_does_not_take_is_refused():
    q, k, r, v, _ = _operands(64, 4, 16, 8, 16)
    bad = [dict(k_rope=jnp.concatenate([r, r], 2)),      # two rotary heads
           dict(k_rope=r[..., :4]),                      # widths do not add up
           dict(k_rope=r, window=8), dict(k_rope=r, native=True)]
    for kw in bad:
        with pytest.raises(ValueError):
            fa.flash_attention(q, k, v, interpret=True, **kw)
    with pytest.raises(ValueError):    # grouped heads beside a shared one
        fa.flash_attention(q, k[:, :, :2], v[:, :, :2], k_rope=r,
                           interpret=True)


def test_the_kernels_read_one_rotary_head_and_write_v_s_width():
    """What the calls' operands are in HBM: the rotary key ``[B, 1, T,
    rope]`` (never 32 copies), v and the output ``dv`` wide (never
    padded to the key's width), dQ and dK as wide as what they
    differentiate."""
    heads, nope, rope, dim_v, seq = 32, 128, 64, 128, 2048
    q = jnp.zeros((1, seq, heads, nope + rope), jnp.bfloat16)
    k = jnp.zeros((1, seq, heads, nope), jnp.bfloat16)
    r = jnp.zeros((1, seq, 1, rope), jnp.bfloat16)
    v = jnp.zeros((1, seq, heads, dim_v), jnp.bfloat16)

    def loss(q, k, r, v):
        return fa.flash_attention(
            q, k, v, k_rope=r, interpret=False).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(q, k, r, v)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"] + [
        e for outer in jaxpr.eqns for sub in jax.core.jaxprs_in_params(
            outer.params) for e in sub.eqns
        if e.primitive.name == "pallas_call"]
    shapes = [([tuple(a.aval.shape) for a in e.invars],
               [tuple(a.aval.shape) for a in e.outvars]) for e in calls]
    hm = lambda h, d: (1, h, seq, d)  # noqa: E731
    fwd_in = [hm(32, 192), hm(32, 128), hm(1, 64), hm(32, 128)]
    bwd_in = fwd_in + [hm(32, 128), hm(32, 1), hm(32, 1)]
    assert sorted(shapes) == sorted([
        (fwd_in, [hm(32, 128), hm(32, 1)]),
        (bwd_in, [hm(32, 128), hm(1, 64), hm(32, 128)]),
        (bwd_in, [hm(32, 192)])])


#: sha256 of the jaxpr text of d(sum(flash_attention(q, k, v)))/d(q,k,v)
#: at the calls the benchmark's other configurations make: as PR 34 left
#: them, when a tile the mask cuts came to be worked in blocks and the
#: functions that build the kernel calls came under an inner ``jit``
#: (the kernels from before ``k_rope`` existed, commit 4a98c94, are what
#: ``tests/test_flash_cut_tiles.py`` holds one block to)
BEFORE = {
    "trinity-mini.sliding": ((1, 8192, 32, 128), (1, 8192, 4, 128), 2048,
        "be5eedee4f89553da3a870d134a4e0c98344c8e0e0a9751a3a1d382529ca6179"),
    "trinity-mini.full": ((1, 8192, 32, 128), (1, 8192, 4, 128), None,
        "b882149e1eec844d96c46fa47bd58a3a17922b8bc124108c1ad4ae23878c2118"),
    "gpt2-large": ((8, 1024, 20, 64), (8, 1024, 20, 64), None,
        "3072be4dd5757e8bd01d5b9e0a56e224f1cf2a34d208bd3cad5238cc0d1bd5c6"),
    "gpt2-xl": ((8, 1024, 25, 64), (8, 1024, 25, 64), None,
        "c084d8f29f36d053fe3cf7f1390b6e0e0597bc2dce6604bac355b8acb4b5b6fb"),
}


@pytest.mark.parametrize("call", sorted(BEFORE))
def test_the_other_configurations_trace_the_kernels_they_traced(call):
    q_shape, kv_shape, window, digest = BEFORE[call]
    q = jnp.zeros(q_shape, jnp.bfloat16)
    kv = jnp.zeros(kv_shape, jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False,
            window=window).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))
    assert hashlib.sha256(text.encode()).hexdigest() == digest
