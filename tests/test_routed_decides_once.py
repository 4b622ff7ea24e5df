"""A routed call decides once (``models/step.py`` ``remat`` / ``keep``,
``models/afmoe.py`` ``route`` and ``_kept``): under
``remat="full"`` the backward pass replays the forward's choices and row
plan and makes neither again.  The four routed models' tiny
configurations on the CPU, Mellum's on the four-device mesh of
``tests/test_mellum.py`` and through the plain ``loss_fn`` with no
``intermediates`` collected, which is how the train step traces it (and
where a kept value that left the ``shard_map`` body unread would fail
the trace)."""

import functools
import hashlib
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.sharding import NamedSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ray_tpu.models import afmoe, step  # noqa: E402
from ray_tpu.parallel import MeshConfig, build_mesh  # noqa: E402
from ray_tpu.parallel.mesh import use_mesh  # noqa: E402
from ray_tpu.parallel.sharding import (  # noqa: E402
    FSDP_EP_RULES,
    flax_sharding,
)

#: module, model, configuration, what the tiny configuration is made
#: with beyond ``remat``, and the seed of the tokens: one at which the
#: tree before took another ``top_k`` in its recompute than the loss ran
#: (bfloat16 models; :data:`NEAR`)
MODELS = {
    "afmoe": ("AFMoE", "AFMoEConfig", {}, 1),
    "deepseek_v3": ("DeepseekV3", "DeepseekV3Config", {}, 2),
    "nemotron_h": ("NemotronH", "NemotronHConfig", {}, 2),
    # the last two layers of a period, in float32, 32 of a sequence's 64
    # tokens a routed call: ``tests/test_mellum.py``'s four-device case
    "mellum": ("Mellum", "MellumConfig", dict(
        num_layers=2, layer_stop=4, dtype=jnp.float32, routed_tokens=32), 1),
}

#: routed layer-calls of a step: 2 expert layers x 2 sequences on one
#: device; on the mesh 2 layers x 2 sequences a device x 2 pieces
LAYER_CALLS = {"afmoe": 4, "deepseek_v3": 4, "nemotron_h": 4, "mellum": 8}


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Row tiles of 8, not 256: the groups then span several tiles."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


def _config(name, remat):
    mod = importlib.import_module("ray_tpu.models." + name)
    model, config, kw, _ = MODELS[name]
    cfg = getattr(mod, config).tiny(remat=remat, **kw)
    return mod, cfg, getattr(mod, model)(cfg)


@functools.lru_cache(maxsize=None)
def _gradient(name, remat):
    """``(compiled text, loss, gradients)`` of the model's plain
    ``loss_fn`` at ``remat``; Mellum over four devices, laid out as its
    cell's preset lays it."""
    mod, cfg, model = _config(name, remat)
    on_mesh = name == "mellum"
    batch = 8 if on_mesh else 2
    params = meta.unbox(model.init_params(jax.random.PRNGKey(0),
                                          batch=1 if on_mesh else batch))
    tokens = jax.random.randint(jax.random.PRNGKey(MODELS[name][3]),
                                (batch, cfg.max_seq_len), 0, cfg.vocab_size)

    def run(params, tokens):
        fn = jax.jit(jax.value_and_grad(
            lambda p: mod.loss_fn(model, p, tokens)))
        compiled = fn.lower(params).compile()
        loss, grads = compiled(params)
        return compiled.as_text(), loss, jax.device_get(grads)

    if not on_mesh:
        return run(params, tokens)
    mesh = build_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])
    with use_mesh(mesh):
        boxed = jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0), batch=4))
        _, specs = flax_sharding(boxed, FSDP_EP_RULES)
        placed = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs))
        return run(placed, jax.device_put(tokens, NamedSharding(
            mesh, FSDP_EP_RULES.spec("batch", None))))


def _instructions(text):
    """``(what the instruction is, its op_name)`` of every instruction of
    a compiled module's text that has a name."""
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name:
            yield line.split("metadata=")[0], name.group(1)


def _is_sort(what):
    # XLA's CPU backend lowers ``top_k`` to a sort or to a call named so
    return re.search(r"\bsort\(|top_?k", what, re.IGNORECASE) is not None


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_recompute_makes_no_plan_and_takes_no_top_k(name):
    text = _gradient(name, "full")[0]
    ops = list(_instructions(text))
    remade = [n for _, n in ops if "rematted_computation" in n]
    assert remade, "nothing is recomputed: is remat on?"
    assert [n for n in remade if "moe.plan" in n] == []
    assert [n for what, n in ops if "rematted_computation" in n
            and "moe.route" in n and _is_sort(what)] == []
    # the router's scores ARE recomputed: the weights' backward is theirs
    assert [n for n in remade if "moe.route" in n]
    sorts = [n for what, n in ops if "moe.plan" in n
             and re.search(r"\bsort\(", what)]
    assert len(sorts) == LAYER_CALLS[name]


#: (loss as a hex float, sha256 of every gradient leaf's bytes in the
#: tree's order) at ``remat=""``, computed by :func:`_digest` on the tree
#: BEFORE the weights were taken at the ids (commit 6eb95be, whose
#: ``route`` took ``top_k``'s values).  PR 57: Mellum's LOSS moved by one
#: unit in the last place (``...310`` -> ``...30e``: the head weighs a
#: token ``1 / n`` inside its scan where it divided the sum by ``n``);
#: every gradient leaf of all four is to the byte what it was
PINNED = {
    "afmoe": ("0x1.62e31a0000000p+2", "9b0116038d5cb0df"),
    "deepseek_v3": ("0x1.62e84e0000000p+2", "e8121434fa8dd02d"),
    "nemotron_h": ("0x1.63a4060000000p+2", "3b1e922cf5a65d0e"),
    "mellum": ("0x1.63430e0000000p+2", "396968ca4e0e6398"),
}

#: the most a leaf of the ``remat="full"`` gradients may lie from the
#: ``remat=""`` ones, as the largest difference over the leaf's largest
#: entry: between what the tree before read (its recompute took another
#: ``top_k`` than the loss ran, and the backward was of that routing:
#: AFMoE 0.2386 on ``h0/mlp/moe/experts_down``, DeepSeek-V3 0.4046 on
#: ``h0/mlp/moe/experts_up``, Nemotron-H 0.3708 on ``h1/mlp/moe/
#: experts_up``) and what is left now, bfloat16's rounding under another
#: fusion (0.0166, 0.0127, 0.0264).  Mellum's is float32 and read 3.0e-7
#: before and now: its limit only holds what is there.
NEAR = {"afmoe": 0.06, "deepseek_v3": 0.06, "nemotron_h": 0.08,
        "mellum": 1e-5}


def _digest(grads):
    sha = hashlib.sha256()
    for leaf in jax.tree.leaves(grads):
        sha.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return sha.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_gradients_are_the_stored_ones_and_the_recompute_s_lie_near(name):
    _, loss, stored = _gradient(name, "")
    assert (float(loss).hex(), _digest(stored)) == PINNED[name]
    _, again, remade = _gradient(name, "full")
    assert abs(float(again) - float(loss)) <= 2e-5 * float(loss)
    far = {}
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(stored)[0],
                            jax.tree.leaves(remade)):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        far[jax.tree_util.keystr(path)] = float(
            np.abs(a - b).max() / np.abs(a).max())
    worst = max(far, key=far.get)
    assert far[worst] <= NEAR[name], (worst, far[worst])


@pytest.mark.parametrize("name", sorted(MODELS))
def test_route_returns_what_it_returned(name):
    """Against the lines ``route`` was before: ``top_k``'s values for the
    router's own choice, the scores at ``chosen`` for a replay; every
    result in every bit, for the sigmoid routers and Mellum's softmax."""
    _, cfg, _ = _config(name, "")
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(keys[0], (48, cfg.embed_dim), cfg.dtype)
    w_router = 0.5 * jax.random.normal(
        keys[1], (cfg.embed_dim, cfg.num_experts), cfg.param_dtype)
    chosen = jax.random.randint(keys[2], (48, cfg.top_k), 0,
                                cfg.num_experts)

    def before(h, w_router, chosen=None):
        logits = jnp.dot(h.astype(cfg.router_dtype),
                         w_router.astype(cfg.router_dtype),
                         precision=jax.lax.Precision.HIGHEST)
        scores = afmoe._SCORES[getattr(cfg, "score_func", "sigmoid")](logits)
        top, own = jax.lax.top_k(scores, cfg.top_k)
        idx = own
        if chosen is not None:
            idx, top = chosen, jnp.take_along_axis(scores, chosen, axis=1)
        top = top.astype(jnp.float32)
        return idx, cfg.route_scale * top / top.sum(-1, keepdims=True), own

    for args in ((h, w_router), (h, w_router, chosen)):
        got = jax.jit(functools.partial(afmoe.route, cfg))(*args)
        want = jax.jit(before)(*args)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert (np.asarray(a) == np.asarray(b)).all()
        # and the weights' gradient to the router
        d_got, d_want = (jax.jit(jax.grad(
            lambda w, f=f: (f(args[0], w, *args[2:])[1] ** 2).sum()))(
                w_router) for f in (functools.partial(afmoe.route, cfg),
                                    before))
        assert (np.asarray(d_got) == np.asarray(d_want)).all()
    assert (np.asarray(got[0]) == np.asarray(chosen)).all()


#: the parts of the four models that name nothing: (module, class,
#: what it is made with after the configuration)
UNNAMED = {
    "afmoe.attn": ("afmoe", "AttentionPart", ("sliding",)),
    "deepseek_v3.attn": ("deepseek_v3", "AttentionPart", ()),
    "mellum.attn": ("mellum", "AttentionPart", ("full",)),
    "nemotron_h.attn": ("nemotron_h", "AttentionPart", ()),
    "nemotron_h.mixer": ("nemotron_h", "MixerPart", ()),
}


@pytest.mark.parametrize("what", sorted(UNNAMED))
def test_a_part_that_names_nothing_compiles_as_under_a_bare_remat(what):
    """``step.remat``'s policy finds no name in an attention part or a
    mixer and saves nothing: the gradient's compiled text is the text
    under ``nn.remat`` alone, which is what these parts had."""
    import flax.linen as nn

    name, part, args = UNNAMED[what]
    mod, cfg, _ = _config(name.split(".")[0], "full")
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (1, cfg.max_seq_len, cfg.embed_dim), cfg.dtype)
    plain = getattr(mod, part)(cfg, *args)
    params = plain.init(jax.random.PRNGKey(3), x)

    def text(wrap):
        module = wrap(getattr(mod, part))(cfg, *args)
        return jax.jit(jax.grad(lambda p, x: module.apply(p, x).astype(
            jnp.float32).sum(), argnums=(0, 1))).lower(
                params, x).compile().as_text()

    # (one call site: the text holds the frames it was traced from)
    bare, kept = (text(wrap) for wrap in (nn.remat, step.remat))
    assert "rematted_computation" in bare
    assert kept == bare


def test_keep_takes_the_names_remat_keeps_alone():
    tree = {"a": jnp.arange(3), "b": (jnp.ones(2),)}
    for name in step.KEPT:
        kept = step.keep(name, tree)
        assert jax.tree.structure(kept) == jax.tree.structure(tree)
        assert (np.asarray(kept["a"]) == np.arange(3)).all()
    with pytest.raises(ValueError, match="not kept"):
        step.keep("scores", tree)
