"""The exchange of an expert-parallel group (``ray_tpu/parallel/expert.py``,
``models/afmoe.py`` ``RoutedExperts._exchanged``) and the preset that
places it (``parallel/sharding.py`` ``FSDP_EP_RULES``) at tiny sizes on
the CPU's devices: the routed layer over four devices with its exchange
against the same layer on one device holding all experts, value and
every gradient; the four chips' parts before the scatter adding up to
the uncut reference's layer; nothing dropped at the worst imbalance; and
nothing of it in the trace of a layer that does not cross chips.  The
model that runs it: ``tests/test_mellum.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import mellum as ref  # noqa: E402
from ray_tpu.models import afmoe, step  # noqa: E402
from ray_tpu.models import mellum as ml  # noqa: E402
from ray_tpu.ops import grouped_matmul as gm  # noqa: E402
from ray_tpu.parallel import MeshConfig, build_mesh, expert  # noqa: E402
from ray_tpu.parallel.mesh import use_mesh  # noqa: E402
from ray_tpu.parallel.sharding import (  # noqa: E402
    EP_RULES,
    FSDP_EP_RULES,
    FSDP_RULES,
    flax_sharding,
)


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Row tiles of 8, not 256: at these sizes the groups then span
    several tiles and pad unevenly."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


@pytest.fixture(scope="module")
def mesh4():
    return build_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])


def _arch(cfg):
    return dict(top_k=cfg.top_k)


def _spec(array):
    """An array's PartitionSpec, padded to its rank."""
    spec = tuple(array.sharding.spec)
    return spec + (None,) * (array.ndim - len(spec))


def _layer_params(cfg, key):
    e, w, n = cfg.embed_dim, cfg.expert_dim, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {"router": 0.5 * jax.random.normal(ks[0], (e, n)),
            "experts_gate": 0.2 * jax.random.normal(ks[1], (n, e, w)),
            "experts_up": 0.2 * jax.random.normal(ks[2], (n, e, w)),
            "experts_down": 0.2 * jax.random.normal(ks[3], (n, w, e))}


def _routed(cfg, params, h):
    """``sum(layer(h) * probe)`` and the layer's value: a scalar whose
    gradients reach every parameter, the router's through the weights."""
    out = afmoe.RoutedExperts(cfg).apply({"params": params}, h)
    probe = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
        out.shape)
    return (out * probe).sum(), out


@pytest.mark.parametrize("top_k", [2, 5])
def test_four_devices_with_the_exchange_are_one_device_with_all_experts(
        mesh4, top_k):
    """Value and EVERY gradient (the router's too, and the tokens'),
    float32.  ``top_k`` 5 of 8 experts over 4 chips: most tokens are
    wanted by every chip, as in the cell (8 of 64 over 4)."""
    cfg = ml.MellumConfig.tiny(dtype=jnp.float32, top_k=top_k)
    params = _layer_params(cfg, jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(7), (4, 24, cfg.embed_dim))
    grad = jax.value_and_grad(lambda p, x: _routed(cfg, p, x),
                              argnums=(0, 1), has_aux=True)
    (_, want), (want_p, want_h) = jax.jit(grad)(params, h)
    with use_mesh(mesh4):
        placed = jax.device_put(params, {
            k: NamedSharding(mesh4, P("fsdp") if k != "router" else P())
            for k in params})
        split = jax.device_put(h, NamedSharding(mesh4, P("fsdp")))
        crossed = jax.jit(grad).lower(placed, split).compile()
        (_, got), (got_p, got_h) = crossed(placed, split)
        text = crossed.as_text()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-5)
    for name in params:
        np.testing.assert_allclose(
            np.asarray(got_p[name]), np.asarray(want_p[name]),
            rtol=1e-4, atol=1e-5, err_msg=name)
    assert float(jnp.abs(want_p["router"]).max()) > 1e-3
    # each device's gradient of the experts is of its OWN experts alone
    assert _spec(got_p["experts_gate"]) == ("fsdp", None, None)
    assert " all-to-all(" not in text and " all-gather(" in text


def test_the_chips_parts_before_the_scatter_add_up_to_the_uncut_layer(
        mesh4):
    """The test that ties a share to the model, here an identity of the
    program: chip ``c``'s part (its experts ``2c, 2c+1`` over the
    GROUP's tokens, what the reduce-scatter sums) is the one-chip layer
    told it holds ``(2c, 2)``; the four add up to the uncut reference's
    layer, and so does the exchanged layer."""
    cfg = ml.MellumConfig.tiny(dtype=jnp.float32)
    full = _layer_params(cfg, jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(7), (4, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    with jax.default_matmul_precision("highest"):
        w_all, (picked, _) = ref.expert_weights(flat, full, _arch(cfg))
        uncut = ref.experts_under_mask(flat, w_all, full)
        # a token's weights over all experts sum to 1: nothing scales
        np.testing.assert_allclose(np.asarray(w_all.sum(-1)), 1.0,
                                   rtol=1e-6)
        parts = []
        for chip in range(4):
            held = ml.MellumConfig.tiny(
                dtype=jnp.float32, expert_axis=None,
                experts_held=(2 * chip, 2))
            share = {k: v if k == "router" else v[2 * chip:2 * chip + 2]
                     for k, v in full.items()}
            parts.append(afmoe.RoutedExperts(held).apply(
                {"params": share}, h).reshape(flat.shape))
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2
    with use_mesh(mesh4):
        crossed = jax.jit(lambda p, x: afmoe.RoutedExperts(cfg).apply(
            {"params": p}, x))(full, jax.device_put(
                h, NamedSharding(mesh4, P("fsdp"))))
    np.testing.assert_allclose(np.asarray(crossed).reshape(flat.shape),
                               np.asarray(uncut), rtol=1e-4, atol=1e-5)


def test_the_scatter_s_sum_left_out_is_not_the_layer(mesh4, monkeypatch):
    """The control ``no_scatter_sum``: each chip keeping its own
    experts' part of its own tokens is caught at any size."""
    cfg = ml.MellumConfig.tiny(dtype=jnp.float32)
    full = _layer_params(cfg, jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(7), (4, 24, cfg.embed_dim))
    want = afmoe.RoutedExperts(cfg).apply({"params": full}, h)

    def own_part(x, axis):
        rows = x.shape[0] // jax.lax.axis_size(axis)
        return jax.lax.dynamic_slice_in_dim(
            x, rows * jax.lax.axis_index(axis), rows)

    monkeypatch.setattr(expert, "scatter_sums", own_part)
    with use_mesh(mesh4):
        got = jax.jit(lambda p, x: afmoe.RoutedExperts(cfg).apply(
            {"params": p}, x))(full, jax.device_put(
                h, NamedSharding(mesh4, P("fsdp"))))
    assert float(jnp.abs(got - want).max()) > 1e-2


def test_nothing_is_dropped_when_every_token_picks_one_chip_s_experts(
        mesh4):
    """Every token alike, so all 96 pick the same 2 experts, both on chip
    1: the worst imbalance there is, and its buffer holds them all."""
    cfg = ml.MellumConfig.tiny(dtype=jnp.float32)
    row = jax.random.normal(jax.random.PRNGKey(9), (cfg.embed_dim,))
    full = _layer_params(cfg, jax.random.PRNGKey(8))
    router = full["router"]
    for n, e in enumerate((2, 3)):
        router = router.at[:, e].set((12.0 - 2 * n) * row / (row @ row))
    full = dict(full, router=router)
    h = jnp.broadcast_to(row, (4, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    with jax.default_matmul_precision("highest"):
        w_all, (picked, _) = ref.expert_weights(flat, full, _arch(cfg))
        want = ref.experts_under_mask(flat, w_all, full)
    assert (jnp.sort(picked, -1) == jnp.array([2, 3])).all()
    with use_mesh(mesh4):
        got, state = jax.jit(lambda p, x: afmoe.RoutedExperts(cfg).apply(
            {"params": p}, x, mutable=["intermediates"]))(
                full, jax.device_put(h, NamedSharding(mesh4, P("fsdp"))))
    np.testing.assert_allclose(np.asarray(got).reshape(flat.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    load = np.asarray(state["intermediates"]["expert_load"][0])
    assert load.tolist() == [0, 0, 96, 96, 0, 0, 0, 0]
    # chip 1's buffer is sized for all 192 pairs of the group
    plan = gm.plan_rows(picked, 2, 2, block_m=afmoe.BLOCK_ROWS)
    assert bool(plan.fits) and int(plan.sizes.sum()) == 192


def test_one_device_along_the_axis_adds_nothing_to_the_trace():
    """``expert_axis`` names an axis; with no mesh, or one device along
    it, the layer is the one-chip layer: no ``shard_map``, no part
    ``moe.exchange``."""
    cfg = ml.MellumConfig.tiny(dtype=jnp.float32)
    params = _layer_params(cfg, jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.embed_dim))

    def layer(p, x):
        return afmoe.RoutedExperts(cfg).apply({"params": p}, x)

    plain = str(jax.make_jaxpr(layer)(params, h))
    assert "shard_map" not in plain and "all_gather" not in plain
    one = build_mesh(MeshConfig(fsdp=1), devices=jax.devices()[:1])
    with use_mesh(one):
        assert expert.group_mesh("fsdp") is None
        assert str(jax.make_jaxpr(layer)(params, h)) == plain
    assert expert.group_mesh(None) is None
    assert "moe.exchange" in step.PARTS
    assert step.PARTS.index("moe.route") < step.PARTS.index("moe.exchange") \
        < step.PARTS.index("moe.plan")



def test_the_preset_splits_experts_by_expert_and_the_rest_as_fsdp():
    cfg = ml.MellumConfig.tiny()
    boxed = jax.eval_shape(lambda: ml.Mellum(cfg).init_params(
        jax.random.PRNGKey(0), batch=1))
    _, specs = flax_sharding(boxed, FSDP_EP_RULES)
    _, fsdp = flax_sharding(boxed, FSDP_RULES)
    moe = specs["h0"]["mlp"]["moe"]
    for name in ("experts_gate", "experts_up", "experts_down"):
        # by expert and by nothing else: a mesh axis is named once
        assert moe[name] == P("fsdp", None, None)
    assert fsdp["h0"]["mlp"]["moe"]["experts_gate"] == P(None, "fsdp", None)
    same = jax.tree.map(lambda a, b: a == b, specs, fsdp,
                        is_leaf=lambda s: isinstance(s, P))
    differ = [k for k, v in jax.tree_util.tree_leaves_with_path(same)
              if not v]
    assert len(differ) == 3 * cfg.num_layers
    # the presets before it are what they were
    assert FSDP_RULES.rules["expert"] is None
    assert EP_RULES.spec("expert", "embed", "mlp") == P("ep", "fsdp", "tp")
    assert FSDP_EP_RULES is not FSDP_RULES
