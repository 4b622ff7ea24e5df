"""The plain reference against the program at a tiny size on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmarks.reference import gpt2 as ref
from ray_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn


def _setup(dtype, heads, wte_scale=1.0):
    cfg = GPT2Config.tiny(dtype=dtype, num_heads=heads,
                          embed_dim=32 * heads, remat="full")
    model = GPT2(cfg)
    boxed = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=1))
    params = ref.init_like(meta.unbox(boxed), jax.random.PRNGKey(1))
    params["wte"] = params["wte"] * wte_scale
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, cfg.max_seq_len), dtype=np.int32))
    sizes = dict(n_layer=cfg.num_layers, n_head=cfg.num_heads, ln_eps=1e-6)
    return model, params, tokens, sizes


def _both(model, params, tokens, sizes, **program_kw):
    lp, gp = jax.value_and_grad(
        lambda p: loss_fn(model, p, tokens, **program_kw))(params)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, **sizes))(params)
    return (abs(float(lp) - float(lr)) / float(lr),
            float(ref.grad_error(gp, gr)))


@pytest.mark.parametrize("heads", [2, 5], ids=["even_heads", "odd_heads"])
def test_float32_program_is_the_reference_to_rounding(heads):
    """Same arithmetic, so the only gap is summation order."""
    loss_gap, grad_gap = _both(*_setup(jnp.float32, heads))
    assert loss_gap < 1e-6 and grad_gap < 1e-5


@pytest.mark.parametrize("heads", [2, 5], ids=["even_heads", "odd_heads"])
@pytest.mark.parametrize("wte_scale", [1.0, 30.0],
                         ids=["initial", "trained_like_logits"])
def test_bf16_program_is_inside_the_tolerances(heads, wte_scale):
    """The program as the cells run it: bf16 compute, f32 logits."""
    loss_gap, grad_gap = _both(*_setup(jnp.bfloat16, heads, wte_scale))
    assert loss_gap <= ref.LOSS_RTOL
    assert grad_gap <= ref.GRAD_RTOL


def test_a_dropped_term_fails_both_tolerances():
    model, params, tokens, sizes = _setup(jnp.bfloat16, 2)
    broken = jax.tree.map(lambda a: a, params)
    broken["wpe"] = jnp.zeros_like(params["wpe"])  # no position embedding
    lp, gp = jax.value_and_grad(lambda p: loss_fn(model, p, tokens))(broken)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, **sizes))(params)
    assert abs(float(lp) - float(lr)) / float(lr) > ref.LOSS_RTOL
    assert float(ref.grad_error(gp, gr)) > ref.GRAD_RTOL


def test_init_like_follows_the_published_initialisation():
    _, params, _, _ = _setup(jnp.float32, 2)
    assert float(jnp.std(params["wte"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(params["wpe"])) == pytest.approx(0.01, rel=0.1)
    assert float(jnp.abs(params["h0"]["attn_qkv"]["bias"]).max()) == 0.0
    assert float(params["h1"]["ln_2"]["scale"].min()) == 1.0
    assert float(jnp.std(params["h1"]["mlp_up"]["kernel"])) == \
        pytest.approx(0.02, rel=0.1)
