"""The Mellum block (``ray_tpu/models/mellum.py``) and the exchange of an
expert-parallel group (``ray_tpu/parallel/expert.py``, ``models/afmoe.py``
``RoutedExperts._exchanged``) at tiny sizes on the CPU: the program
against the plain reference (``benchmarks/reference/mellum.py``) on one
device and on a mesh of four, YaRN's table against its closed form, the
softmax router, the plan spans and gauges, and the three one-chip routed
models' tiny steps bit for bit what they were before the layer could
cross chips.  The layer's exchange by itself:
``tests/test_expert_exchange.py``."""

import math
import os
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

from benchmarks.reference import mellum as ref  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import afmoe  # noqa: E402
from ray_tpu.models import mellum as ml  # noqa: E402
from ray_tpu.parallel import MeshConfig, build_mesh, expert  # noqa: E402
from ray_tpu.parallel.mesh import use_mesh  # noqa: E402
from ray_tpu.parallel.sharding import (  # noqa: E402
    FSDP_EP_RULES,
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
    return dict(window=cfg.window, rope_theta=cfg.rope_theta, yarn=dict(
        rope_theta=cfg.rope_theta, factor=cfg.yarn_factor,
        original_max_position_embeddings=cfg.yarn_original_max,
        beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow,
        attention_factor=cfg.yarn_attention_factor),
        top_k=cfg.top_k, global_every=cfg.global_every,
        layer_stop=cfg.layer_stop or cfg.num_layers)


def _setup(batch=4, **kw):
    """4 query on 2 K/V heads of 16, 8 experts top-2 of width 16, window
    24 of 64, YaRN over an original context of 32; the last two layers
    of a period (sliding, full): both kinds, half the compile."""
    cfg = ml.MellumConfig.tiny(**{"num_layers": 2, "layer_stop": 4, **kw})
    model = ml.Mellum(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=1)))
    params = ref.init_like(shapes, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, cfg.max_seq_len), 0, cfg.vocab_size)
    sizes = dict(n_layer=cfg.num_layers, n_head=cfg.num_heads,
                 ln_eps=cfg.rms_eps, arch=_arch(cfg), query_block=16,
                 token_chunk=32)
    return cfg, model, params, tokens, sizes


def _spec(array):
    """An array's PartitionSpec, padded to its rank."""
    spec = tuple(array.sharding.spec)
    return spec + (None,) * (array.ndim - len(spec))


def _on_mesh(mesh, model, params, tokens):
    """``params`` and ``tokens`` placed as the cell's preset places
    them."""
    boxed = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=4))
    _, specs = flax_sharding(boxed, FSDP_EP_RULES)
    placed = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))
    return placed, jax.device_put(tokens, NamedSharding(
        mesh, FSDP_EP_RULES.spec("batch", None)))


def test_the_tiny_model_is_a_whole_period_of_the_published_pattern():
    cfg = ml.MellumConfig.tiny()
    assert cfg.layer_kinds() == ["sliding", "sliding", "sliding", "full"]
    assert _setup()[0].layer_kinds() == ["sliding", "full"]
    stage = ml.MellumConfig.mellum2_12b_a2_5b_stage()
    assert stage.layer_kinds() == ["sliding", "sliding", "sliding", "full"]
    assert ml.MellumConfig.mellum2_12b_a2_5b().layer_kinds() \
        == 7 * ["sliding", "sliding", "sliding", "full"]
    assert (stage.embed_dim, stage.num_heads, stage.num_kv_heads,
            stage.head_dim, stage.expert_dim, stage.num_experts, stage.top_k,
            stage.vocab_size, stage.window, stage.experts_held) == (
        2304, 32, 4, 128, 896, 64, 8, 98304, 1024, (0, 64))


# ---------------------------------------------------------------------------
# the program against the reference, on one device and over four
# ---------------------------------------------------------------------------

#: float32: the two are the same arithmetic in another order.  bfloat16
#: at width 32: every matmul rounds to 8 bits, and the reference is
#: given the program's choices, since a near tie may flip
@pytest.mark.parametrize("dtype,loss_rtol,grad_rtol,pieces", [
    (jnp.float32, 1e-6, 2e-5, 32),
    (jnp.bfloat16, 3e-4, 0.1, None),
])
def test_program_matches_reference_on_one_device(dtype, loss_rtol,
                                                 grad_rtol, pieces):
    cfg, model, params, tokens, sizes = _setup(
        batch=2, dtype=dtype, remat="full", routed_tokens=pieces)
    loss, grads = jax.value_and_grad(
        lambda p: ml.loss_fn(model, p, tokens))(params)
    choices = ml.router_choices(model, params, tokens)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, choices=choices, **sizes))(params)
    assert abs(float(loss) - float(want)) <= loss_rtol * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= grad_rtol
    if dtype == jnp.float32:   # then the reference chooses the same
        own = ref.forward(params, tokens, **sizes)[1]
        for a, b in zip(choices, own):
            assert (jnp.sort(a, -1) == jnp.sort(b, -1)).all()


def test_program_over_four_devices_matches_reference_and_one_device(
        mesh4, pieces=32):
    """The cell's layout at a tiny size: experts over ``fsdp`` by
    expert, two sequences a device, the routed layers WITH their
    exchange; against the uncut reference (no exchange, no mesh) and
    against the same program on one device."""
    cfg, model, params, tokens, sizes = _setup(
        batch=8, dtype=jnp.float32, remat="full", routed_tokens=pieces)
    alone, alone_grads = jax.jit(jax.value_and_grad(
        lambda p: ml.loss_fn(model, p, tokens)))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, expert_blocks=1, **sizes))(params)
    with use_mesh(mesh4):
        placed, split = _on_mesh(mesh4, model, params, tokens)
        step_fn = jax.jit(jax.value_and_grad(
            lambda p: ml.loss_fn(model, p, split, with_choices=True),
            has_aux=True))
        compiled = step_fn.lower(placed).compile()
        (loss, chose), grads = compiled(placed)
        text = compiled.as_text()
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= 2e-5
    assert abs(float(loss) - float(alone)) <= 1e-6 * float(alone)
    assert float(ref.grad_error(grads, alone_grads)) <= 2e-5
    # the routers' own choices come back in batch order
    for a, b in zip(chose, ml.router_choices(model, params, tokens)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert " all-to-all(" not in text
    assert " all-gather(" in text and "reduce-scatter" in text


def test_the_harness_pairs_both_gradients_at_the_reference_s_routing(mesh4):
    """``entry.loss_fn`` of the cell's configuration over the four
    devices, the reference beside it under the same mesh (its experts a
    block a chip)."""
    from benchmarks.reference import mellum_paired as paired

    cfg, model, params, tokens, sizes = _setup(batch=4, dtype=jnp.float32,
                                               remat="full")
    with use_mesh(mesh4):
        placed, _ = _on_mesh(mesh4, model, params, tokens)
        (loss, misrouted), got = jax.jit(jax.value_and_grad(
            lambda p: paired.program_loss(model, p, tokens, arch=_arch(cfg),
                                          with_misrouted=True),
            has_aux=True))(placed)
        want = jax.jit(jax.grad(
            lambda p: ref.loss(p, tokens, **sizes)))(placed)
    assert float(loss) > 1.0 and float(misrouted) <= paired.MISROUTED_MAX
    assert float(ref.grad_error(got, want)) <= 2e-5


def test_init_like_places_what_it_makes_as_the_preset_does(mesh4):
    """The harness's gradient check makes its weights with no sharding
    of its own: the reference puts them on the global mesh."""
    cfg, model, *_ = _setup()
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=1)))
    with use_mesh(mesh4):
        made = jax.jit(lambda k: ref.init_like(shapes, k))(
            jax.random.PRNGKey(1))
    moe = made["h0"]["mlp"]["moe"]
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert _spec(moe[name]) == ("fsdp", None, None)
        assert moe[name].addressable_shards[0].data.shape[0] == 2
    assert _spec(made["h0"]["attn"]["wq"]["kernel"]) == ("fsdp", None)
    assert _spec(made["h0"]["attn"]["wo"]["kernel"]) == (None, "fsdp")
    assert _spec(made["embed"]) == (None, "fsdp")
    assert float(made["embed"].std()) == pytest.approx(ref.EMBED_STD,
                                                       rel=0.05)
    assert float(made["final_norm"]["scale"].min()) == 1.0


# ---------------------------------------------------------------------------
# rotation, router, placement, spans and gauges
# ---------------------------------------------------------------------------

def test_yarn_table_against_the_closed_form_at_the_published_numbers():
    cfg = ml.MellumConfig.mellum2_12b_a2_5b()
    d, b, L = 128, 500000.0, 8192
    low = d * math.log(L / (32 * 2 * math.pi)) / (2 * math.log(b))
    high = d * math.log(L / (1 * 2 * math.pi)) / (2 * math.log(b))
    assert (round(low, 2), round(high, 2)) == (18.08, 34.98)
    inv, got_low, got_high = ml.yarn_inv_freq(
        cfg.head_dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max,
        cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    assert (got_low, got_high) == (18, 35)
    plain = [b ** (-2 * j / d) for j in range(64)]
    # extrapolated as trained, blended half way, interpolated by 16
    assert float(inv[10]) == pytest.approx(plain[10], rel=1e-6)
    ramp = (27 - 18) / (35 - 18)
    assert float(inv[27]) == pytest.approx(
        (1 - ramp) * plain[27] + ramp * plain[27] / 16, rel=1e-5)
    assert float(inv[50]) == pytest.approx(plain[50] / 16, rel=1e-6)
    assert 0.1 * math.log(16) + 1 == pytest.approx(
        cfg.yarn_attention_factor, rel=1e-12)
    cos, sin = ml.rope_table(cfg, "full", 16)
    assert float(cos[0, 0]) == pytest.approx(cfg.yarn_attention_factor)
    np.testing.assert_allclose(
        np.asarray(cos ** 2 + sin ** 2), cfg.yarn_attention_factor ** 2,
        rtol=1e-5)
    # sliding layers: another table, no factor
    cos_s, sin_s = ml.rope_table(cfg, "sliding", 16)
    np.testing.assert_allclose(np.asarray(cos_s ** 2 + sin_s ** 2), 1.0,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin_s[5]), np.sin(
        5 * np.asarray(plain, np.float32)), rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(sin[5, 50] - sin_s[5, 50])) > 1e-6
    # and the reference's table is the same one
    want, low_r, high_r = ref.yarn_inv_freq(128, _arch(cfg)["yarn"])
    assert (low_r, high_r) == (18, 35)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(want), rtol=1e-6)


def test_rotation_is_afmoe_s_convention_with_the_table_s_factor():
    cfg = ml.MellumConfig.tiny()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 3, 16))
    got = ml.rotate(x, *ml.rope_table(cfg, "sliding", 12))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(afmoe._rope(x, cfg.rope_theta)),
        rtol=1e-5, atol=1e-6)
    full = ml.rotate(x, *ml.rope_table(cfg, "full", 12))
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(full, axis=-1)),
        cfg.yarn_attention_factor * np.asarray(jnp.linalg.norm(x, axis=-1)),
        rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ref._rotate(x[:, 4:], ref._rotation(
            16, "full", _arch(cfg)), start=4)),
        np.asarray(full[:, 4:]), rtol=1e-4, atol=1e-5)


def test_the_softmax_router_s_weights_sum_to_one_and_match_the_reference():
    cfg = ml.MellumConfig.tiny(dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.embed_dim))
    w_router = 0.5 * jax.random.normal(jax.random.PRNGKey(5),
                                       (cfg.embed_dim, cfg.num_experts))
    idx, weights, own = afmoe.route(cfg, h, w_router)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    picked, scores = ref.route(h, w_router, cfg.top_k)
    assert (jnp.sort(idx, -1) == jnp.sort(picked, -1)).all()
    np.testing.assert_allclose(np.asarray(scores.sum(-1)), 1.0, rtol=1e-6)
    dense, _ = ref.expert_weights(h, {"router": w_router}, _arch(cfg))
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(dense, idx, axis=1)),
        np.asarray(weights), rtol=1e-5)
    # a sigmoid router (Trinity's) weighs the same choice otherwise
    sig = afmoe.route(afmoe.AFMoEConfig.tiny(
        route_scale=1.0, dtype=jnp.float32), h, w_router)
    assert (jnp.sort(sig[0], -1) == jnp.sort(idx, -1)).all()
    assert float(jnp.abs(sig[1] - weights).max()) > 1e-3


def test_plan_spans_say_what_exchange_was_compiled(mesh4):
    cfg, model, params, tokens, _ = _setup(batch=4, dtype=jnp.float32,
                                           routed_tokens=32)
    telemetry.drain_spans("test")
    with use_mesh(mesh4):
        placed, split = _on_mesh(mesh4, model, params, tokens)
        jax.eval_shape(lambda p: ml.loss_fn(model, p, split), placed)
    rows = {r["name"]: r for r in telemetry.drain_spans("test")}
    plan = rows["ep.plan"]["args"]
    assert rows["ep.plan"]["cat"] == "parallel"
    assert plan == {
        "axis": "fsdp", "chips": 4, "experts_held": 2, "tokens_local": 32,
        "tokens_group": 128, "exchange": "all_gather+reduce_scatter",
        "gather_bytes": 3 * 32 * (32 * 4 + 2 * 8),
        "scatter_bytes": 3 * 32 * 32 * 4}
    moe = rows["moe.plan"]["args"]
    assert (moe["experts"], moe["held"], moe["top_k"], moe["router"],
            moe["pairs"], moe["row_bound"]) == (8, 2, 2, "softmax", 256, 256)
    # kept for the recompute, a call: a chip's OWN choices [32, 2] and
    # its plan over the group's 256 pairs (256 / 8 + 2 tiles of 8 rows)
    assert (moe["kept"], moe["kept_bytes"]) == (
        "choices,plan",
        32 * 2 * 4 + 272 * (4 + 1) + 256 * (4 + 1) + 34 * 4 + 4)
    # no mesh: no exchange to speak of
    jax.eval_shape(lambda p: ml.loss_fn(model, p, tokens), params)
    rows = {r["name"]: r for r in telemetry.drain_spans("test")}
    assert "ep.plan" not in rows and rows["moe.plan"]["args"]["held"] == 8


def test_group_stats_and_gauges_under_this_model_s_name():
    cfg, model, params, tokens, sizes = _setup(batch=4, dtype=jnp.float32)
    stats = ml.group_stats(model, ml.router_stats(model, params, tokens),
                           chips=4, tokens=tokens.size)
    choices = ref.forward(params, tokens, **sizes)[1]
    for layer, picked in enumerate(choices):
        picked = np.asarray(picked)
        by_chip = [int(((picked // 2) == c).sum()) for c in range(4)]
        assert float(stats["landed_share"][layer]) == pytest.approx(
            max(by_chip) / picked.size)
        assert 0.25 <= float(stats["landed_share"][layer]) < 0.6
    one = expert.exchange_bytes(4, cfg.max_seq_len, cfg.embed_dim,
                                cfg.top_k, 4)
    assert stats["exchange_bytes"] == cfg.num_layers * (
        one["gather_bytes"] + one["scatter_bytes"])
    flat = ml.report_router_stats(stats)
    assert flat["moe/exchange_bytes"] == stats["exchange_bytes"]
    assert ("mellum", 0, None) in telemetry._moe_keys


# ---------------------------------------------------------------------------
# what may not move
# ---------------------------------------------------------------------------

#: loss and the gradients' summed magnitudes of a tiny step, as hex
#: floats, computed on the tree BEFORE this layer could cross chips
#: (commit 8ed1763) by this very function.  PR 57: the head weighs every
#: token ``1 / n`` inside its scan where it divided the sum by ``n``, so
#: two of the three LOSSES moved by one unit in the last place
#: (``...37a`` -> ``...378``, ``...4d6`` -> ``...4d4``); the gradients'
#: sums are to the bit what they were
PINNED = {
    "afmoe": ("0x1.62e3780000000p+2", "0x1.53ad880000000p+8"),
    "deepseek_v3": ("0x1.63dc8e0000000p+2", "0x1.b335400000000p+6"),
    "nemotron_h": ("0x1.63d4d40000000p+2", "0x1.dcbf360000000p+6"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_one_chip_routed_models_tiny_steps_are_bit_for_bit_what_they_were(
        name, monkeypatch):
    import importlib

    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 256)
    mod = importlib.import_module("ray_tpu.models." + name)
    Model, Config = {"afmoe": ("AFMoE", "AFMoEConfig"),
                     "deepseek_v3": ("DeepseekV3", "DeepseekV3Config"),
                     "nemotron_h": ("NemotronH", "NemotronHConfig")}[name]
    cfg = getattr(mod, Config).tiny()
    model = getattr(mod, Model)(cfg)
    params = meta.unbox(model.init_params(jax.random.PRNGKey(0), batch=2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq_len),
                                0, cfg.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: mod.loss_fn(model, p, tokens)))(params)
    total = sum(jnp.abs(g.astype(jnp.float32)).sum()
                for g in jax.tree.leaves(grads))
    assert (float(loss).hex(), float(total).hex()) == PINNED[name]
