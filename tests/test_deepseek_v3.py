"""The DeepSeek-V3 block as Kanana-2-30B-A3B configures it
(``ray_tpu/models/deepseek_v3.py``): latent attention, and AFMoE's
routed expert layer at this model's numbers, against the plain reference
(``benchmarks/reference/deepseek_v3.py``) at tiny sizes on the CPU: loss
and gradients, the shares of the experts adding up to the uncut layer,
nothing dropped at any imbalance, interleaved RoPE, what the routers
tell their operator, and what the ``mla.plan`` span says was compiled."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import afmoe as afmoe_ref  # noqa: E402
from benchmarks.reference import deepseek_v3 as ref  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import afmoe  # noqa: E402
from ray_tpu.models import deepseek_v3 as ds  # noqa: E402
from ray_tpu.ops import grouped_matmul as gm  # noqa: E402


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Row tiles of 8, not 256: at these sizes the groups then span
    several tiles, pad unevenly, and overflow their buffers."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


def _arch(cfg):
    return dict(rope_theta=cfg.rope_theta, route_scale=cfg.route_scale,
                top_k=cfg.top_k, first_held=cfg.experts_held[0])


def _setup(**kw):
    """4 heads, nope 16 + rope 8 against value 16, latent 32, 8 experts
    top-2 with 2 shared, one dense and two expert layers."""
    cfg = ds.DeepseekV3Config.tiny(**kw)
    model = ds.DeepseekV3(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq_len),
                                0, cfg.vocab_size)
    sizes = dict(n_layer=cfg.num_layers, n_head=cfg.num_heads,
                 ln_eps=cfg.rms_eps, arch=_arch(cfg), query_block=16,
                 token_chunk=32)
    return cfg, model, params, tokens, sizes


def test_the_tiny_model_is_the_one_the_issue_names():
    cfg = ds.DeepseekV3Config.tiny()
    assert (cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.num_experts, cfg.top_k,
            cfg.num_shared_experts, cfg.num_dense_layers, cfg.num_layers
            ) == (4, 16, 8, 16, 32, 8, 2, 2, 1, 2)


#: float32: the two are the same arithmetic in another order.  bfloat16
#: at width 32: every matmul rounds to 8 bits and nothing averages out,
#: and the reference is given the program's choices, since a near tie of
#: two scores may flip between the precisions (counted on the chip)
@pytest.mark.parametrize("dtype,loss_rtol,grad_rtol,held", [
    (jnp.float32, 1e-6, 1e-5, (0, 8)),
    (jnp.float32, 1e-6, 1e-5, (2, 4)),
    (jnp.bfloat16, 2e-4, 0.1, (2, 4)),
])
def test_program_matches_reference_on_loss_and_gradients(
        dtype, loss_rtol, grad_rtol, held):
    cfg, model, params, tokens, sizes = _setup(dtype=dtype,
                                               experts_held=held)
    loss, grads = jax.value_and_grad(
        lambda p: ds.loss_fn(model, p, tokens))(params)
    choices = ds.router_choices(model, params, tokens)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, choices=choices, **sizes))(params)
    assert abs(float(loss) - float(want)) <= loss_rtol * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= grad_rtol
    if dtype == jnp.float32:   # then the reference chooses the same
        own = ref.forward(params, tokens, **sizes)[1]
        for a, b in zip(choices, own):
            assert (jnp.sort(a, -1) == jnp.sort(b, -1)).all()


@pytest.mark.parametrize("dtype,grad_rtol", [(jnp.float32, 1e-5),
                                              (jnp.bfloat16, 0.1)])
def test_the_harness_pairs_both_gradients_at_the_reference_s_routing(
        dtype, grad_rtol):
    """``entry.loss_fn`` of the cell's configuration: the program's loss
    at the experts the reference chose."""
    from benchmarks.reference import deepseek_v3_paired as paired

    cfg, model, params, tokens, sizes = _setup(dtype=dtype,
                                               experts_held=(2, 4))
    (loss, misrouted), got = jax.value_and_grad(
        lambda p: paired.program_loss(model, p, tokens, arch=_arch(cfg),
                                      with_misrouted=True),
        has_aux=True)(params)
    want = jax.grad(lambda p: ref.loss(p, tokens, **sizes))(params)
    assert float(loss) > 1.0 and float(misrouted) <= paired.MISROUTED_MAX
    assert float(ref.grad_error(got, want)) <= grad_rtol


def test_the_reference_runs_a_batch_as_its_sequences_one_at_a_time():
    """``hidden`` maps over the batch: two sequences together are each
    alone, replayed choices included."""
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.float32)
    both = ref.loss_sum(params, tokens, **sizes)
    each = sum(ref.loss_sum(params, tokens[i:i + 1], **sizes)
               for i in range(2))
    assert float(both) == pytest.approx(float(each), rel=1e-6)
    own = ref.forward(params, tokens, **sizes)[1]
    assert own[0].shape == (2 * cfg.max_seq_len, cfg.top_k)
    replay = ref.loss_sum(params, tokens, choices=own, **sizes)
    assert float(replay) == pytest.approx(float(both), rel=1e-6)


def test_interleaved_rope_is_a_rotation_of_complex_pairs():
    """Elements ``2i, 2i+1`` as one complex number times ``exp(i t
    theta^(-2i/D))``; program and reference alike, and the reference
    from any start."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 3, 8))
    theta = 1e6
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    freq = theta ** (-np.arange(0, 8, 2) / 8)
    turned = z * np.exp(1j * np.arange(12)[None, :, None, None] * freq)
    want = np.stack([turned.real, turned.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(ds.rope_interleaved(x, theta)),
                               want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.rope_interleaved(x, theta)),
                               want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.rope_interleaved(x[:, 4:], theta, start=4)),
        want[:, 4:], rtol=1e-5, atol=1e-5)
    # and it is NOT the rotate-half pairing of the same angles
    assert float(jnp.abs(afmoe._rope(x, theta) - want).max()) > 0.1


def _layer(cfg, h, params):
    return afmoe.RoutedExperts(cfg).apply({"params": params}, h)


def _layer_params(cfg, key):
    e, w, n = cfg.embed_dim, cfg.expert_dim, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {"router": 0.5 * jax.random.normal(ks[0], (e, n)),
            "experts_gate": 0.2 * jax.random.normal(ks[1], (n, e, w)),
            "experts_up": 0.2 * jax.random.normal(ks[2], (n, e, w)),
            "experts_down": 0.2 * jax.random.normal(ks[3], (n, w, e))}


def _share(params, first, count):
    return {k: v if k == "router" else v[first:first + count]
            for k, v in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2, this model's numbers (top-2, scale
    2.448, width 16): the routed parts that all the shares give, plus
    the shared experts (ONE SwiGLU of twice the width) counted ONCE,
    equal the uncut layer of the uncut reference."""
    cfg = ds.DeepseekV3Config.tiny(dtype=jnp.float32)
    full = _layer_params(cfg, jax.random.PRNGKey(5))
    wide = cfg.num_shared_experts * cfg.expert_dim
    shared = {k: 0.2 * jax.random.normal(
        jax.random.PRNGKey(6 + i), s) for i, (k, s) in enumerate((
            ("gate", (cfg.embed_dim, wide)), ("up", (cfg.embed_dim, wide)),
            ("down", (wide, cfg.embed_dim))))}
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    with jax.default_matmul_precision("highest"):
        once = afmoe_ref._swiglu(flat, shared["gate"], shared["up"],
                                 shared["down"])
        parts = [
            _layer(ds.DeepseekV3Config.tiny(dtype=jnp.float32,
                                            experts_held=(first, 2)),
                   h, _share(full, first, 2)).reshape(flat.shape)
            for first in (0, 2, 4, 6)]
        w_all, _ = ref.held_weights(flat, full,
                                    dict(_arch(cfg), first_held=0))
        uncut = ref.experts_under_mask(flat, w_all, full)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    # every token's weights over all experts sum to the scaling factor
    np.testing.assert_allclose(np.asarray(w_all.sum(-1)), cfg.route_scale,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(once + sum(parts)),
                               np.asarray(once + uncut),
                               rtol=1e-4, atol=1e-5)
    # and a share alone is NOT the layer: nothing stands in for the rest
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2


@pytest.mark.parametrize("held", [(0, 8), (0, 2), (6, 2)])
def test_no_row_is_dropped_when_every_token_picks_the_same_experts(held):
    """Trinity's test on this model's layer: every token alike, so all
    48 pick the same 2 of 8 experts, the worst imbalance there is."""
    cfg = ds.DeepseekV3Config.tiny(dtype=jnp.float32, experts_held=held)
    row = jax.random.normal(jax.random.PRNGKey(9), (cfg.embed_dim,))
    full = _layer_params(cfg, jax.random.PRNGKey(8))
    router = full["router"]
    for n, e in enumerate((0, 1)):   # logits 12, 10 on those two
        router = router.at[:, e].set((12.0 - 2 * n) * row / (row @ row))
    full = dict(full, router=router)
    h = jnp.broadcast_to(row, (2, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    got = _layer(cfg, h, _share(full, *held)).reshape(flat.shape)
    with jax.default_matmul_precision("highest"):
        w_held, (picked, _) = ref.held_weights(
            flat, _share(full, *held), dict(_arch(cfg), first_held=held[0]))
        want = ref.experts_under_mask(flat, w_held, _share(full, *held))
    assert (jnp.sort(picked, -1) == jnp.array([0, 1])).all()
    here = 0 if held == (6, 2) else 2
    plan = gm.plan_rows(picked, held[0], held[1], block_m=afmoe.BLOCK_ROWS)
    assert bool(plan.fits)
    assert int(plan.row_valid.sum()) == 48 * here == int(plan.sizes.sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_router_stats_against_counts_made_by_hand():
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.float32,
                                               experts_held=(2, 4))
    stats = ds.router_stats(model, params, tokens)
    choices = ref.forward(params, tokens, **sizes)[1]
    assert len(choices) == cfg.num_layers == 2
    for layer, picked in enumerate(choices):
        picked = np.asarray(picked)
        load = [int((picked == e).sum()) for e in range(2, 6)]
        assert [int(x) for x in stats["load"][layer]] == load
        assert float(stats["landed_share"][layer]) == pytest.approx(
            sum(load) / picked.size)
        assert float(stats["imbalance"][layer]) == pytest.approx(
            max(load) / (sum(load) / 4))
    flat = ds.report_router_stats(stats)
    assert set(flat) == {f"moe/h{i}/{k}" for i in range(2) for k in (
        "landed_share", "imbalance", "live_share")}
    # the gauges carry THIS model's name, Trinity's stay its own
    assert ds.report_router_stats.keywords == {"model_name": "deepseek_v3"}
    assert ("deepseek_v3", 0, None) in telemetry._moe_keys
    afmoe.report_router_stats(stats)
    assert ("afmoe", 0, None) in telemetry._moe_keys


def test_the_plan_spans_say_what_was_compiled():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32,
                                           experts_held=(2, 4))
    telemetry.drain_spans("test")
    jax.eval_shape(lambda p: ds.loss_fn(model, p, tokens), params)
    rows = {r["name"]: r for r in telemetry.drain_spans("test")
            if r["cat"] == "model"}
    assert set(rows) == {"mla.plan", "moe.plan"}
    assert rows["mla.plan"]["args"] == {
        "heads": 4, "nope": 16, "rope": 8, "value": 16, "latent": 32,
        "seq": 64, "family": "head_major", "score": "concat"}
    assert rows["moe.plan"]["args"] == {
        "experts": 8, "held_first": 2, "held": 4, "top_k": 2,
        "row_bound": 64 * 2, "block_rows": 8, "buffer_passes": 0,
        "row_gather": "reach", "gather_reaches": "1/8,1/4,1/2,1/1",
        "walk_tile": 64, "pairs": 64 * 2, "walked": "table",
        # what a recompute keeps of the call (``step.remat``): the choices
        # [64, 2] int32 and the plan's tables over 128 / 8 + 4 tiles of 8
        # rows (``row_pair`` int32 and ``row_valid``, ``pair_row`` int32
        # and ``pair_valid``, a tile's expert, the live tiles' count)
        "kept": "choices,plan",
        "kept_bytes": 64 * 2 * 4 + 160 * (4 + 1) + 128 * (4 + 1) + 20 * 4
                      + 4,
        "product_tiles": "up 32x16:1, down 16x32:1, drhs 32x16:1x1, "
                         "drhs_down 16x32:1x1",
        "product_vmem_bytes": gm.product_tiles(8, 32, 16, 4)[
            "product_vmem_bytes"]}


def test_the_scopes_name_the_kernel_call_and_the_up_projection():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32)
    text = jax.jit(lambda p: ds.loss_fn(model, p, tokens)).lower(
        params).as_text(debug_info=True)
    assert "attn.mla" in text and "mla.kv_up" in text
    assert "moe.route" in text and "moe.experts" in text


def test_the_cut_configuration_is_the_file_s():
    """One chip's share of eight, as ``benchmarks/configs/
    kanana-2-30b-a3b.json`` states it: widths as published."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        conf = json.load(f)
    cfg = ds.DeepseekV3Config.kanana_2_30b_a3b_share()
    pub = conf["published"]
    assert (cfg.embed_dim, cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.qk_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
            cfg.dense_dim, cfg.expert_dim, cfg.num_shared_experts,
            cfg.num_experts, cfg.top_k, cfg.route_scale, cfg.rms_eps,
            cfg.rope_theta, cfg.num_dense_layers) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["qk_head_dim"], pub["v_head_dim"], pub["kv_lora_rank"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_shared_experts"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["rms_norm_eps"], pub["rope_theta"],
        pub["first_k_dense_replace"])
    assert list(cfg.experts_held) == conf["as_run"]["experts_held"]
    assert cfg.experts_held[1] == conf["n_routed_experts"] == 16
    assert (cfg.num_layers + cfg.num_dense_layers, cfg.vocab_size,
            cfg.max_seq_len, cfg.num_layers) == (
        conf["num_hidden_layers"], conf["vocab_size"],
        conf["n_positions"], conf["n_layer"])
    full = ds.DeepseekV3Config.kanana_2_30b_a3b()
    assert (full.num_layers + full.num_dense_layers, full.vocab_size,
            full.experts_held) == (pub["num_hidden_layers"],
                                   pub["vocab_size"], (0, 128))
    shapes = jax.eval_shape(lambda: ds.DeepseekV3(cfg).init_params(
        jax.random.PRNGKey(0), seq=128))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(
        meta.unbox(shapes))) == conf["as_run"]["parameters"]
