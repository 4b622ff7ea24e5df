"""Parallelism library tests on the virtual 8-device CPU mesh."""

import functools
import hashlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import (
    MeshConfig,
    build_mesh,
    mesh_shape_for,
    pipeline_apply,
    ring_attention,
    ulysses_attention,
)
from ray_tpu.core import telemetry
from ray_tpu.parallel import sharding
from ray_tpu.parallel.mesh import use_mesh
from ray_tpu.parallel.sharding import (
    FSDP_RULES,
    TP_RULES,
    logical_to_mesh,
    shard_params,
)


def reference_attention(q, k, v, causal=True):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def test_device_count():
    assert len(jax.devices()) == 8


def test_mesh_config_resolution():
    cfg = MeshConfig(dp=-1, tp=2).resolved(8)
    assert cfg.dp == 4 and cfg.tp == 2
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=2).resolved(8)
    with pytest.raises(ValueError):
        MeshConfig(dp=-1, tp=-1).resolved(8)


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    assert mesh.shape["dp"] == 2
    assert mesh.shape["sp"] == 2
    assert mesh.shape["tp"] == 2
    assert mesh.shape["fsdp"] == 1


def test_mesh_shape_for():
    cfg = mesh_shape_for(8, tp=2)
    assert cfg.fsdp == 4 and cfg.tp == 2


def test_sharding_rules():
    specs = logical_to_mesh(TP_RULES, {"w": ("embed", "mlp"),
                                       "b": ("mlp",)})
    assert specs["w"] == P("fsdp", "tp")
    assert specs["b"] == P("tp")


def test_shard_params_places_on_mesh():
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2))
    params = {"w": jnp.ones((16, 32)), "b": jnp.zeros((32,))}
    sharded = shard_params(params, {"w": ("embed", "mlp"), "b": ("mlp",)},
                           TP_RULES, mesh)
    assert sharded["w"].sharding.spec == P("fsdp", "tp")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh(MeshConfig(sp=8))
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 64, 4, 16
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    out = ring_attention(q, k, v, causal=causal, mesh=mesh)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_kernel_impl_matches_reference(causal):
    """The flash-kernel ring (per-chunk pallas attention + log-sum-exp
    partial merging, future chunks skipped) through the pallas
    interpreter — the path real TPU meshes take."""
    mesh = build_mesh(MeshConfig(sp=8))
    rng = np.random.default_rng(3)
    b, t, h, d = 1, 512, 2, 64  # d=64 -> NL kernels; chunk = 128 rows
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    out = ring_attention(q, k, v, causal=causal, mesh=mesh,
                         impl="kernel", interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_kernel_impl_gradients(causal):
    """The custom-VJP ring backward (dK/dV accumulators traveling with
    their chunk) must match autodiff through the reference ring — both
    the lax.switch causal classification and the no-switch plain path."""
    mesh = build_mesh(MeshConfig(sp=8))
    rng = np.random.default_rng(5)
    b, t, h, d = 1, 256, 2, 64
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    def loss(impl):
        def f(q_, k_, v_):
            out = ring_attention(q_, k_, v_, causal=causal, mesh=mesh,
                                 impl=impl, interpret=(impl == "kernel"))
            return (out.astype(jnp.float32) ** 2).sum()
        return f

    g_kernel = jax.grad(loss("kernel"), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(causal):
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    rng = np.random.default_rng(1)
    b, t, h, d = 2, 32, 8, 16
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    out = ulysses_attention(q, k, v, causal=causal, mesh=mesh)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_kernel_impl_matches_reference(causal):
    """Ulysses with its TPU-default local attention (the flash kernels)
    through the pallas interpreter, forward and gradients."""
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    rng = np.random.default_rng(7)
    b, t, h, d = 1, 512, 4, 64  # post-all-to-all: full T, h/4 heads
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    def run(**kw):
        return jax.vjp(
            lambda q_, k_, v_: ulysses_attention(
                q_, k_, v_, causal=causal, mesh=mesh, **kw), q, k, v)

    out, vjp = run(interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    out_ref, vjp_ref = run()  # jnp reference local attention
    for a, b_ in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_ring_attention_inside_jit_with_sharded_inputs():
    mesh = build_mesh(MeshConfig(sp=8))
    b, t, h, d = 1, 128, 2, 8
    q = jnp.ones((b, t, h, d))
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    q = jax.device_put(q, sharding)

    @jax.jit
    def fn(q):
        return ring_attention(q, q, q, causal=True, mesh=mesh)

    out = fn(q)
    assert out.shape == (b, t, h, d)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_pipeline_matches_sequential():
    mesh = build_mesh(MeshConfig(pp=4, dp=2))
    n_stages, n_micro, mb, dim = 4, 8, 2, 16
    rng = np.random.default_rng(2)
    ws = jnp.asarray(rng.standard_normal((n_stages, dim, dim)) * 0.1,
                     jnp.float32)
    xs = jnp.asarray(rng.standard_normal((n_micro, mb, dim)), jnp.float32)

    def stage(w, x):
        return jnp.tanh(x @ w)

    out = pipeline_apply(stage, ws, xs, mesh=mesh)

    expected = xs
    seq = []
    for i in range(n_micro):
        y = xs[i]
        for s in range(n_stages):
            y = stage(ws[s], y)
        seq.append(y)
    expected = jnp.stack(seq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_under_jit():
    mesh = build_mesh(MeshConfig(pp=8))
    ws = jnp.ones((8, 4, 4)) * 0.1
    xs = jnp.ones((16, 2, 4))

    @jax.jit
    def run(ws, xs):
        return pipeline_apply(lambda w, x: x @ w, ws, xs, mesh=mesh)

    out = run(ws, xs)
    assert out.shape == xs.shape


def test_pipeline_real_transformer_blocks():
    """Model-level PP: GPT-2 blocks pipelined over pp=4 match the
    sequential forward, and the pipelined step differentiates."""
    import flax
    import numpy as np

    from ray_tpu.models.gpt2 import Block, GPT2Config
    from ray_tpu.parallel.pipeline import (pipeline_apply,
                                           stack_block_params)

    cfg = GPT2Config.tiny(dtype=jnp.float32, num_layers=4,
                          attn_impl="reference")
    rng = jax.random.PRNGKey(0)
    D = cfg.embed_dim
    x = jax.random.normal(rng, (8, 2, 16, D))  # [n_micro, mb, T, D]

    block = Block(cfg)
    per_layer = []
    for i in range(cfg.num_layers):
        p = block.init(jax.random.PRNGKey(i), x[0])["params"]
        per_layer.append(flax.core.unfreeze(
            jax.tree.map(lambda v: v.unbox() if hasattr(v, "unbox")
                         else v, p,
                         is_leaf=lambda v: hasattr(v, "unbox"))))
    stacked = stack_block_params(per_layer)

    def stage_fn(params, act):
        return block.apply({"params": params}, act)

    # sequential reference
    want = x
    out_parts = []
    for m in range(x.shape[0]):
        act = x[m]
        for p in per_layer:
            act = stage_fn(p, act)
        out_parts.append(act)
    want = jnp.stack(out_parts)

    mesh = build_mesh(MeshConfig(pp=4), devices=jax.devices()[:4])
    got = jax.jit(lambda s, xs: pipeline_apply(
        stage_fn, s, xs, mesh=mesh))(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    # gradients flow through the schedule
    grads = jax.jit(jax.grad(lambda s: pipeline_apply(
        stage_fn, s, x, mesh=mesh).mean()))(stacked)
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))


# -- where activations lie (PR 30) ---------------------------------------

#: a mesh for each preset: the axes the preset does not name hold one
#: device
PRESET_MESHES = {
    "dp": MeshConfig(dp=8),
    "fsdp": MeshConfig(fsdp=8),
    "tp": MeshConfig(fsdp=4, tp=2),
    "sp": MeshConfig(fsdp=2, sp=2, tp=2),
    "ep": MeshConfig(fsdp=2, tp=2, ep=2),
}


def test_parameter_rules_are_what_they_were():
    """The benchmark reads these as they are."""
    assert FSDP_RULES.spec("vocab", "embed") == P(None, "fsdp")
    assert FSDP_RULES.spec("embed", "mlp") == P("fsdp", None)
    assert FSDP_RULES.spec("batch", None) == P(("dp", "fsdp"), None)
    assert TP_RULES.spec("embed", "heads") == P("fsdp", "tp")


@pytest.mark.parametrize("preset", sorted(PRESET_MESHES))
def test_activation_rules_name_only_axes_of_the_presets_mesh(preset):
    rules = sharding.PRESETS[preset]
    mesh = build_mesh(PRESET_MESHES[preset])
    live = {a for a, n in mesh.shape.items() if n > 1}
    data = set(sharding.spec_axes(rules.spec("batch")))
    for logical in rules.rules:
        named = set(sharding.spec_axes(
            rules.activation_spec(logical, mesh=mesh)))
        assert named <= live, (logical, named)
        if logical != "batch":
            # an activation has a batch dimension, and a mesh axis is
            # used once a spec
            assert not named & data, (logical, named)
        # the model is handed a mesh and no rules: on the preset's own
        # mesh that is the same placement
        assert sharding.MESH_RULES.activation_spec(logical, mesh=mesh) \
            == rules.activation_spec(logical, mesh=mesh), logical
        assert set(sharding.spec_axes(sharding.MESH_RULES.spec(logical))) \
            & live == set(sharding.spec_axes(rules.spec(logical))) & live
    assert rules.activation_spec("batch", "seq", "embed", mesh=mesh)[2] \
        is None


def test_activation_spec_keeps_whole_what_the_mesh_does_not_divide():
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2))
    spec = functools.partial(TP_RULES.activation_spec,
                             "batch", "seq", "heads", mesh=mesh)
    assert spec() == P("fsdp", None, "tp")
    assert spec(shape=(8, 16, 6)) == P("fsdp", None, "tp")
    assert spec(shape=(6, 16, 5)) == P(None, None, None)
    # without a mesh: the table's own names
    assert FSDP_RULES.activation_spec("batch", "seq", "embed") \
        == P(("dp", "fsdp"), None, None)


def _tiny_gpt2(**kw):
    import optax

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, make_train_step

    model = GPT2(GPT2Config.tiny(dtype=jnp.float32, remat="full", **kw))
    # a large epsilon: AdamW divides by the root of the second moment,
    # which turns rounding in a gradient near zero into a whole step
    tx = optax.adamw(1e-2, eps=1e-3, weight_decay=0.01)
    boxed = model.init_params(jax.random.PRNGKey(0), batch=1)
    tokens = np.random.default_rng(0).integers(
        0, model.config.vocab_size, (3, 8, model.config.max_seq_len),
        dtype=np.int32)
    return model, tx, boxed, tokens, lambda: make_train_step(model, tx)


def _same_placement(a, b):
    return jax.tree.all(jax.tree.map(
        lambda x, y: x.sharding.is_equivalent_to(y.sharding, x.ndim), a, b))


@pytest.mark.parametrize("layout", [{"fsdp": 4}, {"dp": 2, "fsdp": 2}],
                         ids=["fsdp4", "dp2_fsdp2"])
def test_gpt2_step_under_fsdp_is_the_one_device_step(layout):
    model, tx, boxed, tokens, make_step = _tiny_gpt2()
    plain, _ = sharding.flax_sharding(boxed, FSDP_RULES)
    # the step donates its arguments: each side gets its own copy
    want_p = jax.tree.map(jnp.array, plain)
    want_o = tx.init(want_p)
    step = make_step()
    want = []
    for batch in tokens:
        want_p, want_o, loss = step(want_p, want_o, batch)
        want.append(float(loss))

    mesh = build_mesh(MeshConfig(**layout), devices=jax.devices()[:4])
    with use_mesh(mesh):
        params, specs = sharding.place_flax_params(boxed, FSDP_RULES, mesh)
        assert params["h0"]["mlp_up"]["kernel"].sharding.spec \
            == P("fsdp", None)
        # zeros_like keeps a moment's placement; AdamW's scalar count is
        # the caller's to place
        opt = jax.tree.map(
            lambda x: x if x.ndim else jax.device_put(
                x, NamedSharding(mesh, P())), tx.init(params))
        step = make_step()
        telemetry.drain_spans("test")
        for batch, loss_one in zip(tokens, want):
            was_p, was_o = params, opt
            was = jax.tree.map(lambda x: x.sharding, (params, opt))
            params, opt, loss = step(params, opt, jax.device_put(
                batch, NamedSharding(mesh, FSDP_RULES.spec("batch", None))))
            np.testing.assert_allclose(float(loss), loss_one, rtol=2e-6)
            # state leaves as it came: donation holds, nothing recompiles
            now = jax.tree.map(lambda x: x.sharding, (params, opt))
            assert jax.tree.all(jax.tree.map(
                lambda a, b, x: a.is_equivalent_to(b, x.ndim),
                was, now, (params, opt)))
            assert all(x.is_deleted() for x in jax.tree.leaves(was_p))
        # one trace served the three steps: one plan span
        assert len([r for r in telemetry.drain_spans("test")
                    if r["name"] == "fsdp.plan"]) == 1
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        params, want_p)


@pytest.mark.parametrize("chunk", [64, 8192],
                         ids=["padded_tail", "chunk_over_local_tokens"])
def test_chunked_lm_loss_under_the_mesh_is_the_unsharded_call(chunk):
    """8 sequences of 37 tokens over fsdp=4: a device holds 74 tokens,
    neither a multiple of its 16-token share of ``chunk=64`` nor as many
    as its share of the default."""
    from ray_tpu.ops.fused import chunked_lm_loss

    rng = np.random.default_rng(1)
    hidden = jnp.asarray(rng.normal(size=(8, 37, 32)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(101, 32)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 101, (8, 37)), jnp.int32)
    mesh = build_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])

    def both(mesh):
        return jax.jit(jax.value_and_grad(
            lambda h, e: chunked_lm_loss(h, e, labels, chunk=chunk,
                                         mesh=mesh), argnums=(0, 1)))

    want, (want_h, want_e) = both(None)(hidden, emb)
    got, (got_h, got_e) = both(mesh)(
        jax.device_put(hidden, NamedSharding(mesh, P("fsdp"))),
        jax.device_put(emb, NamedSharding(mesh, P(None, "fsdp"))))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got_e, want_e, rtol=1e-5, atol=1e-6)
    text = str(jax.make_jaxpr(both(mesh))(hidden, emb))
    assert "shard_map" in text
    assert "shard_map" not in str(jax.make_jaxpr(both(None))(hidden, emb))


#: sha256 of the jaxpr text of the GPT-2 train step on ``GPT2Config.tiny``
#: with no mesh, the flash kernels traced (not the CPU's reference), as
#: the file traced it BEFORE it said anything about activations; re-pinned
#: in PR 34 for what ``ops/flash_attention.py`` changed under it on purpose
#: (the functions that build the kernel calls under an inner ``jit``, the
#: head-major kernels' tile classification; 09b991c8... and 4882aa21...
#: before), and in PR 57 for the chunked head (its scan makes the
#: gradient with the loss, ``ops/fused.py``; 19bff496... and cb0e2585...
#: before): what the step says about placement is as it was, nothing
STEP_BEFORE = {
    "": "8b2f3a7d5c8af7ff3512381f40cd7d645f8baca91176a9a03f946dd5e81f2bfb",
    "full": "c881340e3daa18159cdf80040b0c7920438ea541782d99981db7ac46d978f085",
}


def _kernel_calls(jaxpr) -> int:
    """Kernel calls the program makes: a jaxpr shared by several call
    sites (the inner ``jit`` around a kernel call) counts at each."""
    return sum(1 if e.primitive.name == "pallas_call" else sum(
        _kernel_calls(sub) for sub in jax.core.jaxprs_in_params(e.params))
        for e in jaxpr.eqns)


def _step_jaxpr(remat, mesh=None):
    import optax
    from flax.core import meta

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, make_train_step

    model = GPT2(GPT2Config.tiny(remat=remat))
    tx = optax.adamw(1e-3)
    params = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0))))
    args = (params, jax.eval_shape(tx.init, params),
            jax.ShapeDtypeStruct((4, 128), jnp.int32))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            use_mesh(mesh):
        return jax.make_jaxpr(make_train_step(model, tx))(*args), params


@pytest.mark.parametrize("remat", sorted(STEP_BEFORE))
def test_with_no_mesh_the_step_traces_as_it_did(remat):
    telemetry.drain_spans("test")
    jaxpr, _ = _step_jaxpr(remat)
    text = str(jaxpr)
    assert "sharding_constraint" not in text and "shard_map" not in text
    assert _kernel_calls(jaxpr.jaxpr) == (8 if remat else 6)
    assert hashlib.sha256(text.encode()).hexdigest() == STEP_BEFORE[remat]
    assert not [r for r in telemetry.drain_spans("test")
                if r["name"] == "fsdp.plan"]


def test_under_a_mesh_the_step_says_its_plan_once_a_trace():
    mesh = build_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])
    telemetry.drain_spans("test")
    jaxpr, params = _step_jaxpr("full", mesh)
    text = str(jaxpr)
    rows = [r for r in telemetry.drain_spans("test")
            if r["name"] == "fsdp.plan"]
    assert len(rows) == 1 and rows[0]["cat"] == "parallel"
    # the embedding's output, ln_f's, and each block's input and output,
    # forward and recomputed; their cotangents
    assert text.count("sharding_constraint") >= 2 + 2 * 2 * 2
    # reckoned from the tree: what FSDP_RULES splits over fsdp
    from ray_tpu.models.gpt2 import GPT2, GPT2Config

    boxed = jax.eval_shape(lambda: GPT2(GPT2Config.tiny()).init_params(
        jax.random.PRNGKey(0)))
    plain, specs = sharding.flax_sharding(boxed, FSDP_RULES)
    split = [x.size * x.dtype.itemsize for x, s in zip(
        jax.tree.leaves(plain),
        jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)))
        if "fsdp" in s]
    whole = len(jax.tree.leaves(plain)) - len(split)
    assert len(split) == 2 + 2 * 10 + 2 and whole == 2 * 2
    assert rows[0]["args"] == {
        "mesh": "fsdp=4", "leaves_sharded": len(split),
        "leaves_whole": whole, "gather_bytes": 3 * sum(split),
        "scatter_bytes": sum(split),
        "act_spec": "PartitionSpec('fsdp', None, None)"}
    assert jax.tree.structure(params) == jax.tree.structure(plain)
