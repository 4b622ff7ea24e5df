"""The Nemotron-H stack as Nemotron-3-Nano-30B-A3B configures it
(``ray_tpu/models/nemotron_h.py``): a pattern of layers that are each a
mixer, an attention or an expert MLP alone, against the plain reference
(``benchmarks/reference/nemotron_h.py``: the recurrence step by step) at
tiny sizes on the CPU: loss and gradients, every layer kind apart, the
sixteen shares of the experts adding up to the uncut layer, relu^2
experts of a width no tile divides, what the routers tell their
operator, what the plan spans say was compiled; and that the two routed
models before it still trace the programs they traced."""

import hashlib
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

from benchmarks.reference import nemotron_h as ref  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import afmoe  # noqa: E402
from ray_tpu.models import deepseek_v3 as ds  # noqa: E402
from ray_tpu.models import nemotron_h as nh  # noqa: E402
from ray_tpu.ops import grouped_matmul as gm  # noqa: E402


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Row tiles of 8, not 256: at these sizes the groups then span
    several tiles and pad unevenly."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


def _arch(cfg, **kw):
    return dict(route_scale=cfg.route_scale, top_k=cfg.top_k,
                first_held=cfg.experts_held[0], ssm_heads=cfg.ssm_heads,
                ssm_groups=cfg.ssm_groups, ssm_state=cfg.ssm_state,
                head_dim=cfg.head_dim, pattern=cfg.pattern, **kw)


def _setup(**kw):
    """``EMEM*``: 8 mixer heads of 8 in 2 groups of state 16, chunks of
    16; 4 query heads on 2 K/V heads of 16; 8 experts of 24, top-2, a
    shared one of 48."""
    cfg = nh.NemotronHConfig.tiny(**kw)
    model = nh.NemotronH(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq_len),
                                0, cfg.vocab_size)
    sizes = dict(n_layer=cfg.num_layers, n_head=cfg.num_heads,
                 ln_eps=cfg.rms_eps, arch=_arch(cfg), query_block=16,
                 token_chunk=32, scan_segment=16)
    return cfg, model, params, tokens, sizes


def test_the_published_model_and_its_share_are_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        conf = json.load(f)
    pub = conf["published"]
    full = nh.NemotronHConfig.nemotron_3_nano_30b_a3b()
    assert full.layer_kinds() == pub["hybrid_override_pattern"]
    assert len(full.layer_kinds()) == pub["num_hidden_layers"] == 52
    assert [full.layer_kinds().count(k) for k in "ME*"] == [23, 23, 6]
    share = nh.NemotronHConfig.nemotron_3_nano_30b_a3b_share()
    assert share.layer_kinds() == conf["as_run"]["pattern"] == "EMEMEMEM*"
    assert pub["hybrid_override_pattern"][34:43] == share.layer_kinds()
    for cfg in (full, share):
        assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                cfg.ssm_state, cfg.conv, cfg.chunk, cfg.expert_dim,
                cfg.shared_dim, cfg.num_experts, cfg.top_k, cfg.route_scale,
                cfg.rms_eps) == (
            pub["hidden_size"], pub["num_attention_heads"],
            pub["num_key_value_heads"], pub["head_dim"],
            pub["mamba_num_heads"], pub["mamba_head_dim"], pub["n_groups"],
            pub["ssm_state_size"], pub["conv_kernel"], pub["chunk_size"],
            pub["moe_intermediate_size"],
            pub["moe_shared_expert_intermediate_size"],
            pub["n_routed_experts"], pub["num_experts_per_tok"],
            pub["routed_scaling_factor"], pub["layer_norm_epsilon"])
    assert (share.num_layers, share.experts_held[1], share.vocab_size,
            share.max_seq_len) == (conf["n_layer"], conf["n_routed_experts"],
                                   conf["vocab_size"], conf["n_positions"])
    shapes = jax.eval_shape(lambda: nh.NemotronH(share).init_params(
        jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(meta.unbox(shapes))) \
        == conf["as_run"]["parameters"] == 666_962_944
    with pytest.raises(ValueError, match="letters M, E and"):
        nh.NemotronHConfig(pattern="ME-E", num_layers=2)


def test_depth_one_holds_a_layer_of_every_kind_and_expands_to_the_share():
    """The harness builds the tree at depth 1 and expands it."""
    one = nh.NemotronH(nh.NemotronHConfig.tiny(num_layers=1))
    tree = meta.unbox(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0))))
    assert set(tree) == {"embed", "head", "final_norm", "h0", "m0", "a0"}
    cfg, model, params, _, _ = _setup()
    grown = ref.expand_layers(tree, cfg.num_layers)
    assert jax.tree.map(lambda a: a.shape, grown) == jax.tree.map(
        lambda a: a.shape, params)


def test_init_like_follows_the_source_s_initialisers():
    cfg, _, params, _, _ = _setup()
    mixer = params["m0"]["mixer"]
    assert (mixer["D"] == 1).all() and (mixer["gate_norm"]["scale"] == 1).all()
    step = jax.nn.softplus(mixer["dt_bias"])
    assert float(step.min()) >= 1e-4 and float(step.max()) <= 0.1 + 1e-6
    a = jnp.exp(mixer["A_log"])
    assert float(a.min()) >= 1 and float(a.max()) <= 16
    # projections into the residual stream: 0.02 / sqrt(52)
    wide = params["h0"]["mlp"]["moe"]
    assert float(wide["experts_down"].std()) == pytest.approx(
        0.02 / 52 ** 0.5, rel=0.1)
    assert float(wide["experts_up"].std()) == pytest.approx(0.02, rel=0.1)
    assert float(params["embed"].std()) == pytest.approx(ref.EMBED_STD,
                                                         rel=0.1)


#: float32: the two are the same arithmetic in another order (the
#: program's chunked einsum against the reference's recurrence).
#: bfloat16 at width 32: every matmul rounds to 8 bits and nothing
#: averages out, and the reference is given the program's choices
@pytest.mark.parametrize("dtype,loss_rtol,grad_rtol,held", [
    (jnp.float32, 1e-6, 2e-5, (0, 8)),
    (jnp.float32, 1e-6, 2e-5, (2, 4)),
    (jnp.bfloat16, 3e-4, 0.1, (2, 4)),
])
def test_program_matches_reference_on_loss_and_gradients(
        dtype, loss_rtol, grad_rtol, held):
    cfg, model, params, tokens, sizes = _setup(dtype=dtype, remat="full",
                                               experts_held=held)
    loss, grads = jax.value_and_grad(
        lambda p: nh.loss_fn(model, p, tokens))(params)
    choices = nh.router_choices(model, params, tokens)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, choices=choices, **sizes))(params)
    assert abs(float(loss) - float(want)) <= loss_rtol * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= grad_rtol
    if dtype == jnp.float32:   # then the reference chooses the same
        own = ref.forward(params, tokens, **sizes)[1]
        for a, b in zip(choices, own):
            assert (jnp.sort(a, -1) == jnp.sort(b, -1)).all()


@pytest.mark.parametrize("pattern,layers", [("M", 0), ("*", 0), ("E", 1),
                                            ("MM*E", 1), ("EM*ME", 2)])
def test_every_layer_kind_apart_and_any_pattern(pattern, layers):
    """A stack of one kind alone, and patterns the share does not use:
    the leaf-by-leaf gradients, so that a small leaf (``A_log``,
    ``dt_bias``, the convolution) is held to the reference on its own
    scale."""
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, pattern=pattern,
                                  num_layers=layers)
    model = nh.NemotronH(cfg)
    params = ref.init_like(meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2))),
        jax.random.PRNGKey(4))
    # weights large enough that every part moves the loss
    params = jax.tree.map(
        lambda a: 8.0 * a if a.ndim >= 2 and a.shape[0] != 256 else a, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    sizes = dict(n_layer=layers, n_head=cfg.num_heads, ln_eps=cfg.rms_eps,
                 arch=_arch(cfg), query_block=16, token_chunk=32,
                 scan_segment=16)
    loss, grads = jax.value_and_grad(
        lambda p: nh.loss_fn(model, p, tokens))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, **sizes))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) <= 2e-4 * scale, \
            jax.tree_util.keystr(path)


def test_the_mixer_s_pieces_against_the_reference_s():
    """The convolution reads the past alone, the gated norm multiplies
    first; both against the reference's own lines."""
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    got = nh.causal_conv(u, w, bias)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(
        ref.causal_conv(u[0], w, bias)), rtol=1e-6, atol=1e-6)
    later = u.at[:, 7:].set(0.0)   # the future changed: the past holds
    np.testing.assert_allclose(np.asarray(nh.causal_conv(later, w, bias)
                                          [:, :7]), np.asarray(got[:, :7]))
    by_hand = jax.nn.silu(bias + w[3] * u[0, 0])   # t = 0 sees itself alone
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(by_hand),
                               rtol=1e-6)
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 8))
    z = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 8))
    g = (y * jax.nn.silu(z)).reshape(1, 5, 2, 4)
    want = (g / jnp.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
            ).reshape(1, 5, 8) * 2.0
    np.testing.assert_allclose(np.asarray(nh.gated_group_norm(
        y, z, 2.0 * jnp.ones((8,)), 2, 1e-5)), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("dtype,grad_rtol", [(jnp.float32, 2e-5),
                                              (jnp.bfloat16, 0.1)])
def test_the_harness_pairs_both_gradients_at_the_reference_s_routing(
        dtype, grad_rtol):
    """``entry.loss_fn`` of the cell's configuration: the program's loss
    at the experts the reference chose, given that the routing is the
    reference's up to near ties and the scan is the recurrence's."""
    from benchmarks.reference import nemotron_h_paired as paired

    cfg, model, params, tokens, sizes = _setup(dtype=dtype,
                                               experts_held=(2, 4))
    (loss, misrouted), got = jax.value_and_grad(
        lambda p: paired.program_loss(model, p, tokens, arch=_arch(cfg),
                                      with_misrouted=True),
        has_aux=True)(params)
    want = jax.grad(lambda p: ref.loss(p, tokens, **sizes))(params)
    assert float(loss) > 1.0 and float(misrouted) <= paired.MISROUTED_MAX
    assert float(ref.grad_error(got, want)) <= grad_rtol
    assert float(paired.scan_error(model, params, tokens, _arch(cfg))) \
        <= paired.SCAN_RTOL


def test_a_scan_that_loses_its_carry_zeroes_the_paired_loss(monkeypatch):
    from benchmarks.reference import nemotron_h_paired as paired
    from ray_tpu.ops import ssd as scan

    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32)
    monkeypatch.setattr(scan, "_carry",
                        lambda states, decay, reverse=False:
                        jnp.zeros_like(states))
    assert float(paired.scan_error(model, params, tokens, _arch(cfg))) \
        > 100 * paired.SCAN_RTOL
    assert float(paired.program_loss(model, params, tokens,
                                     arch=_arch(cfg))) == 0.0


def test_the_reference_runs_a_batch_as_its_sequences_one_at_a_time():
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.float32)
    both = ref.loss_sum(params, tokens, **sizes)
    each = sum(ref.loss_sum(params, tokens[i:i + 1], **sizes)
               for i in range(2))
    assert float(both) == pytest.approx(float(each), rel=1e-6)
    own = ref.forward(params, tokens, **sizes)[1]
    assert own[0].shape == (2 * cfg.max_seq_len, cfg.top_k)
    replay = ref.loss_sum(params, tokens, choices=own, **sizes)
    assert float(replay) == pytest.approx(float(both), rel=1e-6)


# ---------------------------------------------------------------------------
# the routed layer in its second form
# ---------------------------------------------------------------------------

def _layer(cfg, h, params):
    return afmoe.RoutedExperts(cfg).apply({"params": params}, h)


def _layer_params(cfg, key, experts):
    e, w = cfg.embed_dim, cfg.expert_dim
    ks = jax.random.split(key, 3)
    return {"router": 0.5 * jax.random.normal(ks[0], (e, cfg.num_experts)),
            "experts_up": 0.2 * jax.random.normal(ks[1], (experts, e, w)),
            "experts_down": 0.2 * jax.random.normal(ks[2], (experts, w, e))}


def _share(params, first, count):
    return {k: v if k == "router" else v[first:first + count]
            for k, v in params.items()}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The share test the guide asks for, at the deployment's own
    division: 32 experts in SIXTEEN shares of 2 (top-6, scale 2.5, two
    matrices an expert): the routed parts that all the shares give,
    plus the shared expert counted ONCE, equal the uncut layer of the
    uncut reference."""
    kw = dict(dtype=jnp.float32, num_experts=32, top_k=6)
    cfg = nh.NemotronHConfig.tiny(experts_held=(0, 32), **kw)
    full = _layer_params(cfg, jax.random.PRNGKey(5), 32)
    shared = {"shared_up": {"kernel": 0.2 * jax.random.normal(
        jax.random.PRNGKey(6), (cfg.embed_dim, cfg.shared_dim))},
        "shared_down": {"kernel": 0.2 * jax.random.normal(
            jax.random.PRNGKey(7), (cfg.shared_dim, cfg.embed_dim))}}
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    arch = dict(_arch(cfg), first_held=0)
    with jax.default_matmul_precision("highest"):
        once = ref._relu2(flat, shared, "shared_")
        parts = [_layer(nh.NemotronHConfig.tiny(experts_held=(first, 2),
                                                **kw),
                        h, _share(full, first, 2)).reshape(flat.shape)
                 for first in range(0, 32, 2)]
        w_all, _ = ref.held_weights(flat, full, arch)
        uncut = ref.experts_under_mask(flat, w_all, full)
    assert len(parts) == 16
    assert sum(float(jnp.abs(p).max()) > 0 for p in parts) >= 12
    # every token's weights over all experts sum to the scaling factor
    np.testing.assert_allclose(np.asarray(w_all.sum(-1)), 2.5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(once + sum(parts)),
                               np.asarray(once + uncut),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2


def test_one_routed_layer_serves_both_expert_forms():
    """The same module, the same routing and dispatch: three matrices
    under ``gated``, two under ``relu2``; no third layer in ``models/``."""
    assert nh.RoutedExperts is afmoe.RoutedExperts is ds.RoutedExperts
    h = jnp.zeros((1, 8, 32))
    shapes = {form: jax.eval_shape(lambda cfg=cfg: afmoe.RoutedExperts(
        cfg).init(jax.random.PRNGKey(0), h))["params"] for form, cfg in (
            ("gated", afmoe.AFMoEConfig.tiny()),
            ("relu2", nh.NemotronHConfig.tiny()))}
    assert set(shapes["gated"]) == {"router", "experts_gate", "experts_up",
                                    "experts_down"}
    assert set(shapes["relu2"]) == {"router", "experts_up", "experts_down"}


# 1856 = 29 x 64 is the published expert width; 232 = 29 x 8 is its like
# at a tile of 128: neither a multiple of the tile nor the whole of it
@pytest.mark.parametrize("k,n", [(96, 29 * 8), (29 * 8, 96)])
def test_grouped_products_take_a_width_no_tile_divides(k, n):
    """The kernels (interpret mode) against plain jnp at a width of 29 x
    8 under tiles of 128: forward, d lhs and d rhs; the masked last tile
    writes nothing past the edge and reads nothing into the result."""
    idx = jax.random.randint(jax.random.PRNGKey(0), (64, 2), 0, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, k))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (4, k, n))

    def loss(x, w, interpret):
        plan = gm.plan_rows(idx, 2, 4, block_m=8)
        out = gm.grouped_matmul(gm.dispatch(x, plan), w, plan, block_n=128,
                                interpret=interpret)
        assert out.shape == (plan.row_valid.shape[0], n)
        return jnp.sum(jnp.where(plan.row_valid[:, None], out, 0.0) ** 2)

    want, want_grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w, None)
    got, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w, True)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, wg in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("width,block,want", [
    (1856, 512, 384), (1856, 1024, 640), (2688, 512, 384),
    (2688, 1024, 896), (1024, 512, 512), (768, 512, 384), (2048, 1024, 1024),
    (96, 512, 96), (232, 128, 128),
    # Mellum's: 896 = 7 x 128 and 7 is prime, so under any cap below the
    # whole width the only whole-lane divisor is one register
    (896, 512, 128), (896, 768, 128), (2304, 512, 384), (2304, 1024, 768)])
def test_lane_block_keeps_whole_tiles_and_masks_only_what_it_must(
        width, block, want):
    assert gm.lane_block(width, block) == want
    assert want == width or want % 128 == 0
    fit = gm.fit_block(width, block)
    if fit % 128 == 0 or fit == width:
        assert want == fit   # what the tiling chose before: unchanged
    # under a cap the rule's tile is that one: the widest it may take
    # (blocks of 1,024 lanes and under always fit the budget)
    assert gm.lane_tiles(width, block)[0] == want
    assert gm.gmm_tiles(256, 2048, width, 2, block)[0] == want
    assert gm.tgmm_tiles(256, 2048, width, 2, block)[1] == want
    # with no cap the whole width comes first, then whole-lane divisors;
    # a masked tile is the last resort, where nothing divides
    free = gm.lane_tiles(width)
    assert free[0] == width and gm.lane_block(width, 512) in free
    assert all(width % b == 0 for b in free[:-1])
    assert width % free[-1] == 0 or free[-1] % 128 == 0


@pytest.mark.parametrize("k,n,want,d_rhs", [
    # the four configurations' products, bfloat16 under 256 rows: an
    # expert's whole matrix one block, the width as it lies in HBM
    (2048, 1024, 1024, (2048, 1024)), (1024, 2048, 2048, (1024, 2048)),
    (2048, 768, 768, (2048, 768)), (768, 2048, 2048, (768, 2048)),
    (2304, 896, 896, (2304, 896)), (896, 2304, 2304, (896, 2304)),
    # 1856 whole forward; its d rhs whole is 76 MB of blocks and is split
    # where the rows cross HBM least: 2688 three ways (whole lanes) and
    # never 1856, which nothing divides
    (2688, 1856, 1856, (896, 1856)), (1856, 2688, 2688, (1856, 896)),
    # what the budget refuses, by whole-lane divisors, widest first; d
    # rhs in the blocks under which the rows cross HBM least (4 x 4096 +
    # 2 x 8192 lanes a row)
    (4096, 8192, 1024, (2048, 2048)),
    # under 128 lanes (tests' shapes): whole
    (32, 32, 32, (32, 32))])
def test_the_rule_takes_the_widest_tile_the_budget_holds(k, n, want, d_rhs):
    """``gmm_tiles`` and ``tgmm_tiles``, no cap: the whole width first,
    then whole-lane divisors, by the blocks' own bytes against the
    module's VMEM budget."""
    tile, vmem = gm.gmm_tiles(256, k, n, 2)
    assert tile == want and vmem == gm._gmm_vmem(256, k, want, 2)
    assert vmem <= gm.VMEM_BUDGET
    wider = [b for b in gm.lane_tiles(n) if b > tile]
    assert all(gm._gmm_vmem(256, k, b, 2) > gm.VMEM_BUDGET for b in wider)
    *blocks, vmem = gm.tgmm_tiles(256, k, n, 2)
    assert tuple(blocks) == d_rhs and vmem <= gm.VMEM_BUDGET
    if d_rhs != (k, n):
        assert gm._tgmm_vmem(256, k, n, 2) > gm.VMEM_BUDGET


# ---------------------------------------------------------------------------
# what the operator sees
# ---------------------------------------------------------------------------

def test_router_stats_against_counts_made_by_hand():
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.float32,
                                               experts_held=(2, 4))
    stats = nh.router_stats(model, params, tokens)
    choices = ref.forward(params, tokens, **sizes)[1]
    assert len(choices) == cfg.num_layers == 2
    for layer, picked in enumerate(choices):
        picked = np.asarray(picked)
        load = [int((picked == e).sum()) for e in range(2, 6)]
        assert [int(x) for x in stats["load"][layer]] == load
        assert float(stats["landed_share"][layer]) == pytest.approx(
            sum(load) / picked.size)
    flat = nh.report_router_stats(stats)
    assert set(flat) == {f"moe/h{i}/{k}" for i in range(2) for k in (
        "landed_share", "imbalance", "live_share")}
    assert nh.report_router_stats.keywords == {"model_name": "nemotron_h"}
    assert ("nemotron_h", 0, None) in telemetry._moe_keys


def test_the_plan_spans_say_what_was_compiled():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32,
                                           experts_held=(2, 4))
    telemetry.drain_spans("test")
    jax.eval_shape(lambda p: nh.loss_fn(model, p, tokens), params)
    spans = telemetry.drain_spans("test")
    rows = {r["name"]: r for r in spans if r["cat"] == "model"}
    # the scan says its own, once a traced call: 2 mixers x 2 sequences
    scans = [r["args"] for r in spans if (r["cat"], r["name"]) == (
        "ops", "ssd.plan")]
    assert len(scans) == 4 and all(a == {
        "heads": 8, "head_dim": 8, "groups": 2, "state": 16, "chunk": 16,
        "seq": 64, "chunks": 4, "heads_a_step": 4,
        "carry": "xla"}     # off the TPU: the einsum formulation
        for a in scans)
    assert set(rows) == {"hybrid.plan", "moe.plan"}
    assert rows["hybrid.plan"]["args"] == {
        "pattern": "EMEM*", "mixers": 2, "experts": 2, "attention": 1,
        "conv": 4, "norm_group": 32, "gate_norm": "jnp",
        "expert_form": "relu2", "experts_held": 4}
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
        "product_tiles": "up 32x24:1, down 24x32:1, drhs 32x24:1x1, "
                         "drhs_down 24x32:1x1",
        "product_vmem_bytes": gm.product_tiles(8, 32, 24, 4)[
            "product_vmem_bytes"],
        "form": "relu2"}


@pytest.mark.parametrize("config,kw,form", [
    ("tiny", {}, "jnp"),                        # a group of 32 lanes
    ("nemotron_3_nano_30b_a3b_share", {}, "kernel"),   # 8 groups of 512
    ("nemotron_3_nano_30b_a3b_share", {"dtype": jnp.float32}, "kernel"),
    ("nemotron_3_nano_30b_a3b_share", {"max_seq_len": 8200}, "jnp"),
])
def test_the_plan_names_the_gate_norm_s_form_by_the_shapes(config, kw, form):
    cfg = getattr(nh.NemotronHConfig, config)(**kw)
    assert cfg.plan_args()["gate_norm"] == form


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)])
def test_in_proj_is_one_weight_and_a_product_a_part(dtype, tol):
    """``_SplitDense``: ``nn.Dense``'s one ``kernel [embed, z + u + dt]``
    in the tree, each part its own product of the kernel's columns, equal
    to the parts cut out of the one product, and no array of all the
    columns traced for the activations."""
    cfg = nh.NemotronHConfig.tiny(dtype=dtype)
    widths = (cfg.ssm_inner, cfg.conv_dim, cfg.ssm_heads)
    part = nh._SplitDense(cfg, widths)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 16, cfg.embed_dim)
                          ).astype(dtype)
    params = part.init(jax.random.PRNGKey(1), h)["params"]
    kernel = meta.unbox(params)["kernel"]
    assert list(params) == ["kernel"]
    assert kernel.shape == (cfg.embed_dim, sum(widths))
    assert kernel.dtype == cfg.param_dtype
    assert params["kernel"].names == ("embed", "mlp")
    got = part.apply({"params": params}, h)
    assert [g.shape[-1] for g in got] == list(widths)
    assert all(g.dtype == dtype for g in got)
    whole = (h @ kernel.astype(dtype)).astype(jnp.float32)
    want = jnp.split(whole, [widths[0], widths[0] + widths[1]], axis=-1)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                                   rtol=tol, atol=tol)
    traced = str(jax.make_jaxpr(lambda p: part.apply({"params": p}, h))(
        params))
    assert f"16,{sum(widths)}]" not in traced


def test_the_scopes_name_the_mixer_s_parts():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32)
    text = jax.jit(lambda p: nh.loss_fn(model, p, tokens)).lower(
        params).as_text(debug_info=True)
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                  "ssm.out_proj", "attn.full", "moe.route", "moe.experts"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# the two routed models before this one trace what they traced
# ---------------------------------------------------------------------------

def _digest(text):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()
                          ).hexdigest()


def _step_text(module, model):
    cfg = model.config
    tokens = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
    params = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    return str(jax.make_jaxpr(lambda p, t: jax.value_and_grad(
        lambda q: module.loss_fn(model, q, t))(p))(params, tokens))


def _gmm_text(k, n):
    idx = jnp.zeros((512, 2), jnp.int32)

    def loss(x, w):
        plan = gm.plan_rows(idx, 0, 4, block_m=256)
        return gm.grouped_matmul(gm.dispatch(x, plan), w, plan,
                                 interpret=False).astype(jnp.float32).sum()

    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        jnp.zeros((512, k), jnp.bfloat16), jnp.zeros((4, k, n),
                                                     jnp.bfloat16)))


#: sha256 of the jaxpr text (addresses blanked) of the loss and its
#: gradients of the tiny Trinity and Kanana-2 models under
#: ``remat="full"``, and of the grouped products' kernels (forward, d
#: lhs, d rhs) at their cells' widths (the row tile is 256 here: the
#: fixture's 8 is undone below).  Taken on PR 36's final tree, the child
#: of 7b234df, which changes all six ON PURPOSE: the routed layer keeps
#: to its live rows (the activation, its derivative and the sum of the
#: two ``d rows`` inside the grouped kernels, the gathers under a
#: ``lax.switch`` of reaches), so a kernel takes its operands as tuples
#: and ``dispatch`` is no plain index.  They pin what PR 36 traces: a
#: later PR that means to leave the routed models alone keeps them.  That
#: the GPT-2 step traces what it traced is ``tests/test_parallel.py``'s
#: ``STEP_BEFORE``, which PR 36 does not edit.  Taken again on PR 48's
#: tree, which changes all six ON PURPOSE too: ``dispatch`` and
#: ``combine`` carry the kernels' switch to their backward passes (on a
#: TPU the sums over tokens walk the landed pairs) and ``combine``
#: rounds its sums itself; off the TPU the arithmetic is what it was.
#: And on PR 53's, the two steps: a routed call's choices and plan carry
#: the names a recompute keeps them by and ``route`` takes the weights at
#: the ids (``tests/test_routed_decides_once.py``); the products' four
#: are what they were.  And on PR 57's, the two steps again: the chunked
#: head's scan makes the gradient with the loss (``ops/fused.py``
#: ``weighted_token_loss``), so its ``checkpoint`` and backward scan are
#: gone from the text; the products' four are what they were
GOLDEN = {
    "afmoe":
        "0046aaa9e22f131b5e458e377054218c17b7f284854baf81f2b9b1e601f28cc6",
    "deepseek_v3":
        "5c6263f5be2fafe67636918616d3e8cb0dafa74d8959224be861368fe02e0961",
    "gmm_1024_2048":
        "4ab5cd396489efe5707466e78a6868beed355142b351f4a7f5853f9812edf078",
    "gmm_2048_1024":
        "a4d348a5b4129d0b32e8fc62b51abf97729913ef9cad79665a33706ad4c50f88",
    "gmm_2048_768":
        "7955d35bb3377fa255af5c60cb7f64246cfca54696524c1fcc9f7f3fa36920a9",
    "gmm_768_2048":
        "f4023cff2bb787fd33436965271a632a584267dffdcd946d793f108d4f39de68",
}


@pytest.mark.parametrize("what", sorted(GOLDEN))
def test_trinity_s_and_kanana_s_steps_trace_the_programs_they_traced(
        what, monkeypatch):
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 256)
    if what == "afmoe":
        text = _step_text(afmoe, afmoe.AFMoE(
            afmoe.AFMoEConfig.tiny(remat="full")))
    elif what == "deepseek_v3":
        text = _step_text(ds, ds.DeepseekV3(
            ds.DeepseekV3Config.tiny(remat="full")))
    else:
        text = _gmm_text(*map(int, what.split("_")[1:]))
    assert _digest(text) == GOLDEN[what]
