"""GPT-2's forward pass and next-token loss in plain ``jax.numpy``:
float32 throughout, ``default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes), no kernels, no chunked
head, no remat, full ``[B, T, V]`` logits.  It follows the published
model (Radford et al. 2019; the public ``gpt2*`` ``config.json``):
learned position embeddings, pre-LN blocks, fused qkv projection split
q|k|v, causal softmax attention scaled by ``head_dim ** -0.5``,
``gelu_new`` (tanh) MLP of width 4E, final LN, head tied to ``wte``.

Departures, each on purpose:

* the layers run under ONE ``lax.scan`` over stacked parameters, so 36
  or 48 layers trace and compile once — same arithmetic, layer by layer;
* ``ln_eps`` is an argument: the published value is 1e-5, the program
  under test uses flax's default 1e-6, and the reference is given the
  program's so that the comparison checks the program's arithmetic
  (PERF.md lists the departure for a program PR).

It takes the program's own parameter tree (``wte``, ``wpe``, ``h<i>`` with
``ln_1 attn_qkv attn_proj ln_2 mlp_up mlp_down``, ``ln_f``) and nothing
else from the program.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

#: |program loss - reference loss| / reference loss allowed on one
#: batch.  The program multiplies in bf16 (8 mantissa bits) with f32
#: accumulation: per-logit rounding is ~2^-9 of |logit| and nearly
#: zero-mean, so the mean over thousands of tokens moves by 1e-6..1e-5
#: of the loss at initial weights and up to 6e-5 at trained-like logit
#: scales (tests/test_reference.py; 1.2e-6 at gpt2-large on the chip).
#: A dropped term (a bias, a residual, the position embedding), a wrong
#: scale or a wrong label shift moves it by 1e-3 or more.  What it can
#: NOT see: logits stored in bf16 (``head_logits_dtype``) shift the mean
#: loss no more than the bf16 matmuls already do (measured 6e-6..1e-4
#: against 9e-6..6e-5 at three logit scales), so no loss check tells
#: them apart; the benchmark builds its step without that shortcut.
LOSS_RTOL = 1e-4
#: ||g_program - g_reference|| / ||g_reference|| over the whole tree:
#: bf16 activations carry ~2^-8 relative error per matmul through the
#: layers, so a right gradient sits at about 1e-2 (measured: 0.0098 to
#: 0.0107 at the tiny size); a missing backward term or a wrong
#: reduction is of order 1.
GRAD_RTOL = 3e-2


def init_like(shapes: Any, key: jax.Array) -> Any:
    """Random weights for a tree of shapes, by GPT-2's published
    initialisation: N(0, 0.02) for every matrix and the token embedding,
    N(0, 0.01) for the position embedding, zero biases, unit LN scales.
    Leaves are told apart by their name in the program's tree; leaves
    alike in name and shape (one per layer) are drawn as ONE stacked
    array, so the program has a dozen random draws and not hundreds."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: Dict[Any, list] = {}
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        groups.setdefault((name, leaf.shape, leaf.dtype), []).append(i)
    out: list = [None] * len(flat)
    for n, ((name, shape, dtype), where) in enumerate(groups.items()):
        if name == "scale":
            block = jnp.ones((len(where), *shape), dtype)
        elif name == "bias":
            block = jnp.zeros((len(where), *shape), dtype)
        else:
            std = 0.01 if name == "wpe" else 0.02
            block = std * jax.random.normal(
                jax.random.fold_in(key, n), (len(where), *shape), dtype)
        for j, i in enumerate(where):
            out[i] = block[j]
    return jax.tree_util.tree_unflatten(treedef, out)


def expand_layers(tree: Dict[str, Any], n_layer: int) -> Dict[str, Any]:
    """A depth-1 parameter tree with layer ``h0`` standing for every
    layer: ``h0`` .. ``h<n_layer-1>`` all alike, the rest as it is."""
    out = {k: v for k, v in tree.items() if k != "h0"}
    out.update({f"h{i}": tree["h0"] for i in range(n_layer)})
    return out


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, eps):
    b, t, e = x.shape
    d = e // n_head
    q, k, v = jnp.split(_dense(_layer_norm(x, p["ln_1"], eps),
                               p["attn_qkv"]), 3, axis=-1)
    q, k, v = (a.reshape(b, t, n_head, d) for a in (q, k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    x = x + _dense(o.reshape(b, t, e), p["attn_proj"])
    h = _gelu_new(_dense(_layer_norm(x, p["ln_2"], eps), p["mlp_up"]))
    return x + _dense(h, p["mlp_down"])


def logits(params: Dict[str, Any], tokens: jax.Array, *, n_layer: int,
           n_head: int, ln_eps: float) -> jax.Array:
    """``[B, T, V]`` float32 logits."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[params[f"h{i}"] for i in range(n_layer)])
        t = tokens.shape[1]
        x = params["wte"][tokens] + params["wpe"][None, :t]
        x, _ = jax.lax.scan(
            lambda x, p: (_block(x, p, n_head, ln_eps), None), x, stacked)
        return _layer_norm(x, params["ln_f"], ln_eps) @ params["wte"].T


def loss_sum(params, tokens, **sizes) -> jax.Array:
    """Sum over the batch of next-token negative log likelihoods
    (labels are the tokens shifted left; the last position has none)."""
    logp = jax.nn.log_softmax(logits(params, tokens, **sizes)[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.sum()


def loss(params, tokens, **sizes) -> jax.Array:
    """Mean next-token cross entropy, as the program's ``loss_fn``."""
    b, t = tokens.shape
    return loss_sum(params, tokens, **sizes) / (b * (t - 1))


def grad_error(g_program, g_reference) -> jax.Array:
    """Relative L2 distance between two gradient trees (jittable)."""
    num = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b))
              for a, b in zip(jax.tree.leaves(g_program),
                              jax.tree.leaves(g_reference)))
    den = sum(jnp.sum(jnp.square(b)) for b in jax.tree.leaves(g_reference))
    return jnp.sqrt(num / den)
