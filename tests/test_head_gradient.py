"""The chunked head makes its gradient in the forward's own scan
(``ops/fused.py`` ``weighted_token_loss``): three products a chunk, the
``[chunk, V]`` logits made once.  Values and all three gradients against
the plain unchunked form in float32 and, in bfloat16 compute, against the
head as it stood BEFORE (a scan step under ``jax.checkpoint``, autodiff's
gradient: kept here as the oracle, to the operand); and the PROGRAM: what
products, scans and checkpoints the traced gradient of a model's loss
holds.  Tiny sizes on the CPU: counts and correctness, no speed.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2, ouro
from ray_tpu.ops import fused
from ray_tpu.parallel import MeshConfig, build_mesh

N, E, V = 200, 48, 130
BF16 = jnp.bfloat16


def _operands(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (N, E)),
            0.3 * jax.random.normal(k[1], (V, E)),
            jax.random.randint(k[2], (N,), 0, V),
            jax.random.uniform(k[3], (N,)) / N)


def plain(h, e, y, w, **_):
    """The unchunked form: every token's logits at once."""
    return jnp.sum(w * fused.fused_softmax_cross_entropy(h @ e.T, y))


def before(h, e, y, w, *, chunk, compute_dtype=None, logits_dtype=None):
    """The head as it stood before: the scan step under
    ``jax.checkpoint``, its gradient autodiff's, the logits recomputed in
    the backward scan."""
    pad = (-h.shape[0]) % chunk
    h, y, w = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
               for a in (h, y, w))
    e = e.astype(jnp.float32)

    @jax.checkpoint
    def body(total, xs):
        hc, yc, wc = xs
        nll = fused._chunk_nll(hc.astype(jnp.float32), yc, e,
                               compute_dtype, logits_dtype)
        return total + jnp.sum(nll * wc), None

    return jax.lax.scan(body, jnp.float32(0.0), (
        h.reshape(-1, chunk, h.shape[-1]), y.reshape(-1, chunk),
        w.reshape(-1, chunk)))[0]


#: name -> (oracle, keywords of the head, cotangent, rows that weigh
#: nothing, (rtol of the value, tolerance of a gradient over its scale))
CASES = {
    "f32_chunk_divides": (plain, dict(chunk=40), 1.0, 0, (1e-6, 1e-6)),
    "f32_padded_tail": (plain, dict(chunk=64), 1.0, 0, (1e-6, 1e-6)),
    "f32_one_chunk_over_all": (plain, dict(chunk=8192), 1.0, 0,
                               (1e-6, 1e-6)),
    "f32_cotangent_of_three": (plain, dict(chunk=64), 3.0, 0, (1e-6, 1e-6)),
    "f32_rows_that_weigh_nothing": (plain, dict(chunk=64), 1.0, 37,
                                    (1e-6, 1e-6)),
    # bfloat16 operands: the products' operands are the parent's, so a
    # cotangent of one gives its gradients but for the order of the
    # float32 sums of ``d emb`` (the parent's scan ran backwards)
    "bf16_chunk_divides": (before, dict(chunk=40, compute_dtype=BF16), 1.0,
                           0, (1e-7, 1e-6)),
    "bf16_padded_tail": (before, dict(chunk=64, compute_dtype=BF16), 1.0,
                         0, (1e-7, 1e-6)),
    "bf16_rows_that_weigh_nothing": (
        before, dict(chunk=64, compute_dtype=BF16), 1.0, 37, (1e-7, 1e-6)),
    "bf16_logits_in_bf16": (
        before, dict(chunk=64, compute_dtype=BF16, logits_dtype=BF16), 1.0,
        0, (1e-7, 1e-6)),
    # any other cotangent scales BEHIND the products where autodiff
    # scales ``d`` before they round: one rounding of ``d`` either way
    "bf16_cotangent_of_three": (
        before, dict(chunk=64, compute_dtype=BF16), 3.0, 0, (1e-7, 1e-2)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_value_and_all_three_gradients(case):
    oracle, kw, cotangent, idle, (rtol, gtol) = CASES[case]
    h, e, y, w = _operands()
    if idle:
        w = w.at[-idle:].set(0.0)

    def both(f):
        return jax.jit(jax.value_and_grad(
            lambda h, e, w: cotangent * f(h, e, y, w, **kw),
            argnums=(0, 1, 2)))(h, e, w)

    got, got_g = both(fused.weighted_token_loss)
    want, want_g = both(oracle)
    np.testing.assert_allclose(got, want, rtol=rtol)
    # with no gradient asked the scan is the loss's alone: one value
    np.testing.assert_allclose(
        cotangent * fused.weighted_token_loss(h, e, y, w, **kw), got,
        rtol=1e-6)
    for name, a, b in zip(("hidden", "emb", "weights"), got_g, want_g):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=gtol, atol=gtol * scale,
                                   err_msg=name)
    if idle:    # a row that weighs nothing moves nothing
        assert not np.asarray(got_g[0][-idle:]).any()
    if oracle is before and cotangent == 1.0:
        # ``d hidden`` has no sum across chunks: the parent's, bit for bit
        np.testing.assert_array_equal(got_g[0], want_g[0])


def test_logits_in_bf16_still_read_differently():
    """The ``bf16_head_logits`` controls need a loss and a gradient that
    differ from the float32 logits' by more than rounding noise."""
    h, e, y, w = _operands()
    h = 4.0 * h    # logits of some size, as a trained head's

    def both(logits_dtype):
        return jax.value_and_grad(
            lambda h, e: fused.weighted_token_loss(
                h, e, y, w, chunk=64, compute_dtype=BF16,
                logits_dtype=logits_dtype), argnums=(0, 1))(h, e)

    (full, (full_h, _)), (half, (half_h, _)) = both(None), both(BF16)
    assert abs(float(half) - float(full)) > 1e-4 * abs(float(full))
    assert float(jnp.abs(half_h - full_h).max()) \
        > 1e-3 * float(jnp.abs(full_h).max())


@pytest.mark.parametrize("chunk", [64, 8192],
                         ids=["padded_tail", "chunk_over_local_tokens"])
@pytest.mark.parametrize("cotangent", [1.0, 3.0])
def test_under_a_two_device_mesh_it_is_the_plain_mean(chunk, cotangent):
    """8 sequences of 25 tokens over fsdp=2: a device cuts its chunks
    inside its own 100 tokens, the head enters whole, and ``d emb`` is
    the devices' sum."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    hidden = jax.random.normal(k[0], (8, 25, E))
    emb = 0.3 * jax.random.normal(k[1], (V, E))
    labels = jax.random.randint(k[2], (8, 25), 0, V)
    mesh = build_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2])
    want, want_g = jax.value_and_grad(
        lambda h, e: cotangent * fused.fused_softmax_cross_entropy(
            jnp.einsum("bte,ve->btv", h, e), labels).mean(),
        argnums=(0, 1))(hidden, emb)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda h, e: cotangent * fused.chunked_lm_loss(
            h, e, labels, chunk=chunk, mesh=mesh), argnums=(0, 1)))(
        jax.device_put(hidden, NamedSharding(mesh, P("fsdp"))),
        jax.device_put(emb, NamedSharding(mesh, P(None, "fsdp"))))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=2e-6 * float(jnp.abs(b).max()))


def _walk(jaxpr, prefix=""):
    """``(equation, name)`` of every equation, an inner jaxpr's under the
    outer equation's name stack."""
    for eqn in jaxpr.eqns:
        name = "/".join(x for x in (
            prefix, str(eqn.source_info.name_stack)) if x)
        yield eqn, name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(getattr(sub, "jaxpr", sub), name)


def _head_program(jaxpr, vocab):
    """What the traced program holds under the part ``head``: the
    products whose shapes carry the vocabulary, the scans, and the
    checkpoints."""
    found = {"products": 0, "scans": 0, "checkpoints": 0}
    for eqn, name in _walk(jaxpr.jaxpr):
        if "head" not in name.replace("heads", ""):
            continue
        prim = eqn.primitive.name
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        if prim == "dot_general" and any(vocab in s for s in shapes):
            found["products"] += 1
        found["scans"] += prim == "scan"
        found["checkpoints"] += prim in ("remat", "remat2", "checkpoint")
    return found


def _gpt2_loss():
    # a vocabulary no other axis of the step has
    cfg = gpt2.GPT2Config.tiny(vocab_size=384, remat="full")
    model = gpt2.GPT2(cfg)
    params = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32)
    return functools.partial(gpt2.loss_fn, model, head_chunk=64), \
        params, tokens, cfg.vocab_size


def _ouro_loss():
    cfg = ouro.OuroConfig.tiny(remat="full")
    model = ouro.Ouro(cfg)
    params = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32)
    return functools.partial(ouro.loss_fn, model, head_chunk=32), \
        params, tokens, cfg.vocab_size


@pytest.mark.parametrize("model", ["gpt2", "ouro"])
def test_the_gradient_s_program_multiplies_three_times_a_chunk(model):
    loss, params, tokens, vocab = {"gpt2": _gpt2_loss,
                                   "ouro": _ouro_loss}[model]()
    widths = {s for a in jax.tree.leaves(params) for s in a.shape}
    assert vocab not in widths - {vocab} and vocab not in tokens.shape
    alone = _head_program(jax.make_jaxpr(loss)(params, tokens), vocab)
    assert alone == {"products": 1, "scans": 1, "checkpoints": 0}
    graded = _head_program(
        jax.make_jaxpr(jax.grad(loss))(params, tokens), vocab)
    assert graded == {"products": 3, "scans": 1, "checkpoints": 0}


def test_the_gradient_s_ops_are_filed_forward_and_the_scaling_backward():
    """``benchmarks/reduce/scopes.py`` files an op by ``jvp(`` /
    ``transpose(`` in its name: the scan with its three products is the
    forward's, and what the backward pass has left of the head is no
    product."""
    from benchmarks.reduce import scopes

    loss, params, tokens, vocab = _gpt2_loss()
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, tokens)
    phases = {}
    for eqn, name in _walk(jaxpr.jaxpr):
        if scopes.part(name, ["head"]) != "head":
            continue
        phases.setdefault(scopes.phase(name), set()).add(eqn.primitive.name)
    assert "scan" in phases["forward"] \
        and "dot_general" in phases["forward"]
    assert not {"scan", "dot_general"} & phases.get("backward", set())
    assert "recompute" not in phases
