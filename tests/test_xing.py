"""The DeepSeek-V3 block as Xing4.0-29B-A4B configures it
(``ray_tpu/models/deepseek_v3.py`` with a query latent, YaRN, four lanes
under hyper-connections and a multi-token module) against the plain
reference (``benchmarks/reference/xing.py``) at tiny sizes on the CPU:
loss and gradients with and without each mechanism, the one-lane case
against Kanana's block, the shares adding up through ``write`` to the
uncut layer, the step's parts, the plan span and the gauges."""

import dataclasses
import json
import os
import re
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
from benchmarks.reference import xing as ref  # noqa: E402
from benchmarks.reference import xing_paired as paired  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import afmoe, hyper, step  # noqa: E402
from ray_tpu.models import deepseek_v3 as ds  # noqa: E402

#: the tiny Xing: Kanana's tiny sizes with this model's mechanisms
XING = dict(q_lora_rank=16, yarn_factor=64.0, rope_theta=1e4, hc_mult=4,
            num_shared_experts=1, route_scale=2.0)


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


def _arch(cfg):
    return dict(rope_theta=cfg.rope_theta, route_scale=cfg.route_scale,
                top_k=cfg.top_k, first_held=cfg.experts_held[0],
                lanes=cfg.hc_mult, sinkhorn_iters=hyper.SINKHORN_ITERS,
                yarn_factor=cfg.yarn_factor,
                yarn_original=ds.YARN_ORIGINAL_MAX,
                yarn_mscale=ds.YARN_MSCALE,
                yarn_mscale_all_dim=ds.YARN_MSCALE_ALL_DIM,
                mtp_weight=ds.MTP_WEIGHT)


def _setup(**kw):
    cfg = ds.DeepseekV3Config.tiny(**{**XING, **kw})
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


#: one dense and one expert layer at 32 positions: the reference's
#: gradient is what these tests wait for
SMALL = dict(num_layers=1, max_seq_len=32)


@pytest.mark.parametrize("dtype,loss_rtol,grad_rtol,mtp,q_rank,dense", [
    (jnp.float32, 1e-6, 2e-5, 0, 16, 1),
    (jnp.float32, 1e-6, 2e-5, 1, 16, 0),
    (jnp.float32, 1e-6, 2e-5, 0, None, 0),
    (jnp.bfloat16, 3e-4, 0.1, 1, 16, 0),
])
def test_program_matches_reference_on_loss_and_gradients(
        dtype, loss_rtol, grad_rtol, mtp, q_rank, dense):
    """With and without the multi-token module, with and without the
    query latent, with and without a leading dense layer; the reference
    is given the program's choices, since a near tie may flip in
    bfloat16."""
    cfg, model, params, tokens, sizes = _setup(
        dtype=dtype, experts_held=(2, 4), num_mtp_layers=mtp, remat="full",
        q_lora_rank=q_rank, num_dense_layers=dense, **SMALL)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ds.loss_fn(model, p, tokens)))(params)
    choices = ds.router_choices(model, params, tokens)
    assert len(choices) == cfg.num_layers + mtp
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, choices=choices, **sizes)))(params)
    assert abs(float(loss) - float(want)) <= loss_rtol * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= grad_rtol
    if dtype == jnp.float32:   # then the reference chooses the same
        own = ref.forward(params, tokens, **sizes)[1]
        for a, b in zip(choices, own):
            assert (jnp.sort(a, -1) == jnp.sort(b, -1)).all()


def test_the_harness_pairs_both_gradients_at_the_reference_s_routing():
    """``entry.loss_fn`` of the cell's configuration, the multi-token
    module's routed layer replayed like any other."""
    cfg, model, params, tokens, sizes = _setup(
        dtype=jnp.float32, experts_held=(2, 4), num_mtp_layers=1,
        num_dense_layers=0, **SMALL)
    (loss, misrouted), got = jax.jit(jax.value_and_grad(
        lambda p: paired.program_loss(model, p, tokens, arch=_arch(cfg),
                                      with_misrouted=True),
        has_aux=True))(params)
    want = jax.jit(jax.grad(
        lambda p: ref.loss(p, tokens, **sizes)))(params)
    assert float(loss) > 1.0 and float(misrouted) <= paired.MISROUTED_MAX
    assert float(ref.grad_error(got, want)) <= 2e-5


def test_the_multi_token_term_is_the_second_term_of_the_loss():
    """``L = L_main + 0.3 L_mtp``: the model with the module gives the
    main term where the weight is 0, and more where it is 0.3; the
    reference's ``loss_sum`` over ``B (T - 1)`` is the same mean."""
    cfg, model, params, tokens, sizes = _setup(
        dtype=jnp.float32, num_mtp_layers=1, num_dense_layers=0, **SMALL)
    both = float(ds.loss_fn(model, params, tokens))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(ds, "MTP_WEIGHT", 0.0)
        alone = float(ds.loss_fn(model, params, tokens))
    without = {k: v for k, v in params.items() if k != "mtp"}
    plain = float(ds.loss_fn(ds.DeepseekV3(dataclasses.replace(
        cfg, num_mtp_layers=0)), without, tokens))
    assert alone == pytest.approx(plain, rel=1e-6)
    l_mtp = (both - alone) / ds.MTP_WEIGHT
    assert 0.5 * alone < l_mtp < 2.0 * alone
    each = sum(float(ref.loss_sum(params, tokens[i:i + 1], **sizes))
               for i in range(2)) / (2 * (cfg.max_seq_len - 1))
    assert each == pytest.approx(both, rel=1e-5)


@pytest.mark.parametrize("query_latent", [False, True])
@pytest.mark.parametrize("yarn", [False, True])
def test_one_lane_is_kanana_s_block_with_or_without_each_mechanism(
        query_latent, yarn):
    """``hc_mult`` 1: the stream is the plain residual, and the model
    agrees with KANANA's reference where it has neither a query latent
    nor YaRN (the fields' defaults), and with this model's reference on
    one lane (``H_pre = H_post = H_res = 1``: the connection written as
    its one-lane case) where it has either."""
    from benchmarks.reference import deepseek_v3 as kanana_ref

    kw = dict(XING, hc_mult=1, dtype=jnp.float32)
    if not query_latent:
        kw["q_lora_rank"] = None
    if not yarn:
        kw["yarn_factor"] = None
    cfg = ds.DeepseekV3Config.tiny(**kw)
    model = ds.DeepseekV3(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    params = kanana_ref.init_like(shapes, jax.random.PRNGKey(3))
    assert ("wq" in params["h0"]["attn"]) == (not query_latent)
    assert "hc" not in params["h0"]["attn"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    loss = float(ds.loss_fn(model, params, tokens))
    if not query_latent and not yarn:
        want = float(kanana_ref.loss(
            params, tokens, n_layer=2, n_head=4, ln_eps=cfg.rms_eps,
            arch=dict(rope_theta=cfg.rope_theta, top_k=2, first_held=0,
                      route_scale=cfg.route_scale),
            query_block=16, token_chunk=32))
        assert loss == pytest.approx(want, rel=1e-6)
    else:   # this model's reference on ONE lane: H_pre = H_post = H_res = 1
        want = _one_lane_reference_loss(cfg, params, tokens)
        assert loss == pytest.approx(want, rel=1e-5)


def _one_lane_reference_loss(cfg, params, tokens):
    """``benchmarks/reference/xing.py`` with every connection the
    one-lane case: a tree given ``hc`` entries whose coefficients come
    out as 1 (``phi`` 0, biases large; ``H_post = 2 sigmoid(0) = 1``)."""
    hc = {"phi": jnp.zeros((cfg.embed_dim, 3)),
          "b": jnp.array([40.0, 0.0, 0.0]), "gates": jnp.zeros((3,))}

    def with_hc(tree):
        if not isinstance(tree, dict):
            return tree
        out = {k: with_hc(v) for k, v in tree.items()}
        if "attn_norm" in out or "mlp_norm" in out:
            out["hc"] = hc
        return out

    arch = dict(_arch(cfg), lanes=1)
    if cfg.yarn_factor is None:
        arch.update(yarn_factor=1.0)
    return float(ref.loss(with_hc(params), tokens, n_layer=cfg.num_layers,
                          n_head=cfg.num_heads, ln_eps=cfg.rms_eps,
                          arch=arch, query_block=16, token_chunk=32))


def test_unit_coefficients_on_equal_lanes_give_kanana_s_block(monkeypatch):
    """Four lanes whose connections are forced to the plain residual's
    (``H_pre`` a quarter a lane of EQUAL lanes, ``H_post`` 1, ``H_res``
    the identity): every lane is the one-lane stream, so the block's
    result is Kanana's on every lane and the loss is the one-lane
    model's with the embedding scaled by the lanes' sum."""
    n = 4

    def plain(x, *_, **__):
        tokens = x.shape[0] * x.shape[1]
        eye = jnp.broadcast_to(jnp.eye(n)[:, :, None], (n, n, tokens))
        return hyper.Coefficients(jnp.full((n, tokens), 1.0 / n),
                                  jnp.ones((n, tokens)), eye)

    monkeypatch.setattr(hyper, "coefficients", plain)
    kw = dict(XING, dtype=jnp.float32)
    many = ds.DeepseekV3(ds.DeepseekV3Config.tiny(**kw))
    one = ds.DeepseekV3(ds.DeepseekV3Config.tiny(**dict(kw, hc_mult=1)))
    shapes = meta.unbox(jax.eval_shape(
        lambda: many.init_params(jax.random.PRNGKey(0), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(3))

    def without_hc(tree):
        return {k: without_hc(v) for k, v in tree.items() if k != "hc"} \
            if isinstance(tree, dict) else tree

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    x_many, _ = many.apply({"params": params}, tokens,
                           method=ds.DeepseekV3.hidden)
    x_one, _ = one.apply({"params": without_hc(params)}, tokens,
                         method=ds.DeepseekV3.hidden)
    # the final norm takes the lanes' sum, n times the one-lane stream:
    # an RMS norm does not see the factor
    np.testing.assert_allclose(np.asarray(x_many), np.asarray(x_one),
                               rtol=2e-4, atol=2e-5)


def _layer_params(cfg, key):
    e, w, n = cfg.embed_dim, cfg.expert_dim, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {"router": 0.5 * jax.random.normal(ks[0], (e, n)),
            "experts_gate": 0.2 * jax.random.normal(ks[1], (n, e, w)),
            "experts_up": 0.2 * jax.random.normal(ks[2], (n, e, w)),
            "experts_down": 0.2 * jax.random.normal(ks[3], (n, w, e))}


def test_the_shares_add_up_through_write_to_the_uncut_layer():
    """The share test of the guide, with lanes: 8 experts in 4 shares of
    2.  Every chip computes the connection, the shared expert and the
    ``H_res`` term alike; its routed part alone differs.  ``write`` is
    linear in ``y``, so the chips' results less the common part (``write``
    of the shared expert's result, counted ONCE) add up to ``write`` of
    the UNCUT reference layer's routed sum with no lanes kept (``x``
    zero)."""
    cfg = ds.DeepseekV3Config.tiny(**dict(XING, dtype=jnp.float32))
    n, width = cfg.hc_mult, cfg.embed_dim
    full = _layer_params(cfg, jax.random.PRNGKey(5))
    shared = {k: 0.2 * jax.random.normal(jax.random.PRNGKey(6 + i), s)
              for i, (k, s) in enumerate((
                  ("gate", (width, cfg.expert_dim)),
                  ("up", (width, cfg.expert_dim)),
                  ("down", (cfg.expert_dim, width))))}
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, n * width))
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    coef = hyper.coefficients(
        x, 0.1 * jax.random.normal(ks[0], (n * width, n * (n + 2))),
        jax.random.normal(ks[1], (n * (n + 2),)), jnp.full((3,), 0.5), n)
    with jax.default_matmul_precision("highest"):
        h = hyper.read(x, coef.pre)
        flat = h.reshape(-1, width)
        once = afmoe_ref._swiglu(flat, shared["gate"], shared["up"],
                                 shared["down"]).reshape(h.shape)

        def chip(first):
            held = ds.DeepseekV3Config.tiny(**dict(
                XING, dtype=jnp.float32, experts_held=(first, 2)))
            part = afmoe.RoutedExperts(held).apply(
                {"params": {k: v if k == "router" else v[first:first + 2]
                            for k, v in full.items()}}, h)
            return hyper.write(x, once + part, coef)

        chips = [chip(first) for first in (0, 2, 4, 6)]
        common = hyper.write(x, once, coef)
        w_all, _ = ref.held_weights(flat, full, dict(
            _arch(cfg), first_held=0))
        uncut = ref.experts_under_mask(flat, w_all, full).reshape(h.shape)
        whole = hyper.write(x, once + uncut, coef)
    got = common + sum(c - common for c in chips)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(chips[0] - whole).max()) > 1e-2


def test_yarn_frequencies_and_the_softmax_scale_are_the_published_ones():
    cfg = ds.DeepseekV3Config.xing4_0_29b_a4b_share()
    inv, factor = cfg.rope_table()
    assert factor == 1.0 and inv.shape == (32,)
    plain = 1e4 ** (-2.0 * np.arange(32) / 64)
    # pairs up to 10 as trained, from 23 on a 64th, a ramp between
    np.testing.assert_allclose(np.asarray(inv[:11]), plain[:11], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[23:]), plain[23:] / 64,
                               rtol=1e-6)
    assert (np.asarray(inv[11:23]) < plain[11:23]).all()
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 2.00474,
                                              rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(64, ref.ARCH)), np.asarray(inv),
        rtol=1e-6)
    kanana = ds.DeepseekV3Config.kanana_2_30b_a3b_share()
    assert kanana.softmax_scale is None and kanana.rope_table() is None


def test_the_plan_span_the_parts_and_the_scopes():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32,
                                           num_mtp_layers=1)
    for part in ("hc.coef", "hc.mix", "mtp"):
        assert part in step.PARTS
    assert step.PARTS.index("embed") < step.PARTS.index("hc.coef") \
        < step.PARTS.index("attn")
    assert step.PARTS.index("moe.combine") < step.PARTS.index("mtp") \
        < step.PARTS.index("head")
    telemetry.drain_spans("test")
    text = jax.jit(lambda p: ds.loss_fn(model, p, tokens)).lower(
        params).as_text(debug_info=True)
    rows = {r["name"]: r for r in telemetry.drain_spans("test")
            if r["cat"] == "model"}
    assert set(rows) == {"mla.plan", "moe.plan", "hc.plan"}
    assert rows["hc.plan"]["args"] == {
        "lanes": 4, "iters": 20, "clamp": "-30,30", "eps": 1e-6,
        "width": 32, "seq": 64, "coef_dtype": "float32", "impl": "jnp",
        "x_reads": "", "kernel_calls": 0}
    assert rows["mla.plan"]["args"]["q_latent"] == 16
    assert rows["mla.plan"]["args"]["yarn_factor"] == 64.0
    for name in ("hc.coef", "hc.mix", "mla.q_up", "mla.kv_up", "attn.mla",
                 "moe.route", "mtp"):
        assert name in text, name
    # a connection's work stands BESIDE the sub-layer's part, not in it
    names = set(re.findall(r'loc\("([^"]*)"', text))
    assert not [n for n in names if re.search(r"/attn/.*hc\.(coef|mix)", n)
                and "/mtp/" not in n]
    assert not [n for n in names if "/mlp/hc." in n and "/mtp/" not in n]


def test_hc_stats_reach_the_gauges():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32)
    stats = ds.hc_stats(model, params, tokens)
    connections = 2 * (cfg.num_dense_layers + cfg.num_layers)
    assert all(v.shape == (connections,) for v in stats.values())
    assert all(0.1 < float(m) < 0.95 for m in stats["offdiag_mass"])
    assert all(float(e) < 1e-2
               for e in stats["doubly_stochastic_error"])
    assert all(0 < float(h) <= np.log(4) + 1e-6
               for h in stats["pre_entropy"])
    flat = ds.report_hc_stats(stats)
    assert set(flat) == {f"hc/c{i}/{k}" for i in range(connections)
                         for k in ("offdiag_mass", "pre_entropy",
                                   "doubly_stochastic_error")}
    for name, stat in (("ray_tpu_hc_offdiag_mass", "offdiag_mass"),
                       ("ray_tpu_hc_pre_entropy", "pre_entropy"),
                       ("ray_tpu_hc_doubly_stochastic_error",
                        "doubly_stochastic_error")):
        gauge = telemetry._gauge(name, "")
        assert gauge.tag_keys == ("model", "connection")
        for i in range(connections):
            key = (("model", "deepseek_v3"), ("connection", str(i)))
            assert gauge._values[key] == pytest.approx(
                float(stats[stat][i]))


def test_the_cut_configuration_is_the_file_s():
    """One chip's share of eight, as ``benchmarks/configs/
    xing4.0-29b-a4b.json`` states it: widths as published."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        conf = json.load(f)
    cfg = ds.DeepseekV3Config.xing4_0_29b_a4b_share()
    pub, yarn = conf["published"], conf["published"]["rope_scaling"]
    assert (cfg.embed_dim, cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim, cfg.kv_lora_rank, cfg.q_lora_rank,
            cfg.dense_dim, cfg.expert_dim, cfg.num_shared_experts,
            cfg.num_experts, cfg.top_k, cfg.route_scale, cfg.rms_eps,
            cfg.rope_theta, cfg.hc_mult, hyper.SINKHORN_ITERS,
            hyper.SINKHORN_EPS, hyper.CLAMP) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["kv_lora_rank"], pub["q_lora_rank"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_shared_experts"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["rms_norm_eps"], pub["rope_theta"], pub["hc_mult"],
        pub["hc_sinkhorn_iters"], pub["hc_eps"],
        (pub["mhc_h_res_clamp_min"], pub["mhc_h_res_clamp_max"]))
    assert (cfg.yarn_factor, ds.YARN_ORIGINAL_MAX, ds.YARN_BETA_FAST,
            ds.YARN_BETA_SLOW, ds.YARN_MSCALE, ds.YARN_MSCALE_ALL_DIM
            ) == (yarn["factor"], yarn["original_max_position_embeddings"],
                  yarn["beta_fast"], yarn["beta_slow"], yarn["mscale"],
                  yarn["mscale_all_dim"])
    assert conf["rope_scaling"] == yarn
    assert list(cfg.experts_held) == conf["as_run"]["experts_held"]
    assert cfg.experts_held[1] == conf["n_routed_experts"] == 8
    assert (cfg.num_layers + cfg.num_dense_layers, cfg.num_dense_layers,
            cfg.vocab_size, cfg.max_seq_len, cfg.num_layers,
            cfg.num_mtp_layers) == (
        conf["num_hidden_layers"], conf["first_k_dense_replace"],
        conf["vocab_size"], conf["n_positions"], conf["n_layer"],
        conf["num_nextn_predict_layers"])
    assert ds.MTP_WEIGHT == conf["assumed"]["mtp_weight"]
    full = ds.DeepseekV3Config.xing4_0_29b_a4b()
    assert (full.num_layers + full.num_dense_layers, full.num_dense_layers,
            full.vocab_size, full.experts_held, full.num_mtp_layers) == (
        pub["num_hidden_layers"], pub["first_k_dense_replace"],
        pub["vocab_size"], (0, 64), pub["num_nextn_predict_layers"])

    def count(c):
        shapes = jax.eval_shape(lambda: ds.DeepseekV3(c).init_params(
            jax.random.PRNGKey(0), seq=128))
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(meta.unbox(shapes)))

    assert count(cfg) == conf["as_run"]["parameters"] == 759_346_190
    assert count(dataclasses.replace(cfg, num_mtp_layers=1)) \
        - count(cfg) == 154_127_158
