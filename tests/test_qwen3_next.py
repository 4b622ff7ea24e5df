"""The Qwen3-Next stack as Qwen3-Next-80B-A3B-Instruct configures it
(``ray_tpu/models/qwen3_next.py``): three Gated DeltaNet layers to one
gated full-attention layer, every layer with its expert MLP, against the
plain reference (``benchmarks/reference/qwen3_next.py``: the recurrence
step by step) at tiny sizes on the CPU: loss and gradients, either kind
of layer apart, the pattern ``(i + 1) % 4``, the depth argument, the
sixteen shares of the experts adding up to the uncut layer, what the
routers tell their operator and what the plan spans say was compiled."""

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

from benchmarks.reference import qwen3_next as ref  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import afmoe  # noqa: E402
from ray_tpu.models import qwen3_next as qn  # noqa: E402
from ray_tpu.ops import fused  # noqa: E402
from ray_tpu.ops import gated_delta as gd  # noqa: E402
from ray_tpu.ops import short_conv as sc  # noqa: E402


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Row tiles of 8, not 256: at these sizes the groups then span
    several tiles and pad unevenly."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


def _arch(cfg, **kw):
    return dict(top_k=cfg.top_k, first_held=cfg.experts_held[0],
                head_dim=cfg.head_dim, rotary_dim=cfg.rotary_dim,
                rope_theta=cfg.rope_theta, key_heads=cfg.lin_key_heads,
                value_heads=cfg.lin_value_heads, pattern=cfg.pattern, **kw)


def _setup(**kw):
    """``LLF``: 2 key and 4 value heads of 8, chunks of 16; 4 query heads
    on 2 K/V heads of 16, 4 elements rotated; 8 experts of 24, top-2, a
    gated shared one of 24."""
    cfg = qn.Qwen3NextConfig.tiny(**kw)
    model = qn.Qwen3Next(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq_len),
                                0, cfg.vocab_size)
    sizes = dict(n_layer=cfg.num_layers, n_head=cfg.num_heads,
                 ln_eps=cfg.rms_eps, arch=_arch(cfg), query_block=16,
                 token_chunk=32, scan_segment=16)
    return cfg, model, params, tokens, sizes


def _stirred(params, key):
    """Weights at which every part moves the loss: the zero-centred norm
    weights off zero, the matrices larger."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for n, (path, a) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "weight":
            a = 0.3 * jax.random.normal(jax.random.fold_in(key, n), a.shape)
        elif a.ndim >= 2 and a.shape[0] != 256:
            a = 6.0 * a
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def test_the_published_model_and_its_share_are_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        conf = json.load(f)
    pub = conf["published"]
    full = qn.Qwen3NextConfig.qwen3_next_80b_a3b()
    assert full.layer_kinds() == qn.published_pattern(
        pub["num_hidden_layers"], pub["full_attention_interval"]) \
        == ref.published_pattern(48, 4) == "LLLF" * 12
    assert [full.layer_kinds().count(k) for k in "LF"] == [36, 12]
    share = qn.Qwen3NextConfig.qwen3_next_80b_a3b_share()
    assert share.layer_kinds() == conf["as_run"]["pattern"] == "LLLF" \
        == full.layer_kinds()[:4]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    for cfg in (full, share):
        assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                cfg.rotary_dim, cfg.rope_theta, cfg.lin_key_heads,
                cfg.lin_value_heads, cfg.lin_key_dim, cfg.lin_value_dim,
                cfg.conv, cfg.expert_dim, cfg.shared_dim, cfg.num_experts,
                cfg.top_k, cfg.rms_eps, cfg.score_func, cfg.route_scale) == (
            pub["hidden_size"], pub["num_attention_heads"],
            pub["num_key_value_heads"], pub["head_dim"],
            pub["head_dim"] * pub["partial_rotary_factor"],
            pub["rope_theta"], pub["linear_num_key_heads"],
            pub["linear_num_value_heads"], pub["linear_key_head_dim"],
            pub["linear_value_head_dim"], pub["linear_conv_kernel_dim"],
            pub["moe_intermediate_size"],
            pub["shared_expert_intermediate_size"], pub["num_experts"],
            pub["num_experts_per_tok"], pub["rms_norm_eps"], "softmax", 1.0)
    assert (share.num_layers, share.experts_held[1], share.vocab_size,
            share.max_seq_len, len(share.layer_kinds())) == (
        conf["n_layer"], conf["num_experts"], conf["vocab_size"],
        conf["n_positions"], conf["num_hidden_layers"])
    assert conf["vocab_size"] * 8 == pub["vocab_size"]
    assert conf["num_experts"] * 16 == pub["num_experts"]
    shapes = meta.unbox(jax.eval_shape(
        lambda: qn.Qwen3Next(share).init_params(jax.random.PRNGKey(0))))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == conf["as_run"]["parameters"] == 625_667_136
    assert conf["as_run"]["state_bytes"] == 16 * 625_667_136
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["h0"]["mixer"]) == 33_718_464 + 2_048   # + its norm
    assert count(shapes["h3"]["attn"]) == 27_263_488 + 2_048
    assert count(shapes["h0"]["mlp"]) == 100_663_296 + 4_200_448 - 2_048
    with pytest.raises(ValueError, match="letters L and F"):
        qn.Qwen3NextConfig(pattern="LLMF", num_layers=2)


@pytest.mark.parametrize("layers,interval", [(48, 4), (8, 4), (6, 3), (5, 1)])
def test_a_layer_is_full_where_its_index_plus_one_divides(layers, interval):
    kinds = qn.published_pattern(layers, interval)
    assert len(kinds) == layers
    for i, kind in enumerate(kinds):
        assert (kind == "F") == ((i + 1) % interval == 0)
    cfg = qn.Qwen3NextConfig.tiny(pattern=kinds,
                                  num_layers=kinds.count("L"))
    tree = jax.eval_shape(lambda: qn.Qwen3Next(cfg).init_params(
        jax.random.PRNGKey(0)))
    for i, kind in enumerate(kinds):
        assert ("attn" in tree[f"h{i}"]) == (kind == "F")
        assert ("mixer" in tree[f"h{i}"]) == (kind == "L")
        assert "moe" in tree[f"h{i}"]["mlp"]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_the_depth_argument_counts_linear_layers_before_the_full_one(depth):
    """The harness builds the tree at depth 1, ``L F``, and expands it;
    its gradient check runs depth 2, ``L L F``: both kinds of mixer."""
    cfg = qn.Qwen3NextConfig.tiny(num_layers=depth)
    assert cfg.layer_kinds() == "L" * depth + "F"
    assert cfg.num_expert_layers == depth + 1
    one = qn.Qwen3Next(qn.Qwen3NextConfig.tiny(num_layers=1))
    tree = meta.unbox(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0))))
    assert set(tree) == {"embed", "head", "final_norm", "h0", "h1"}
    want = meta.unbox(jax.eval_shape(lambda: qn.Qwen3Next(cfg).init_params(
        jax.random.PRNGKey(0))))
    grown = ref.expand_layers(tree, depth)
    assert jax.tree.map(lambda a: a.shape, grown) == jax.tree.map(
        lambda a: a.shape, want)
    assert "attn" in grown[f"h{depth}"] and "mixer" in grown["h0"]


def test_init_like_follows_the_source_s_initialisers():
    cfg, _, params, _, _ = _setup()
    mixer = params["h0"]["mixer"]
    assert (mixer["dt_bias"] == 1).all() and (mixer["gate_norm"] == 1).all()
    a = jnp.exp(mixer["A_log"])
    assert float(a.min()) > 0 and float(a.max()) <= 16
    for norm in (mixer["norm"], params["h2"]["attn"]["q_norm"],
                 params["h0"]["mlp"]["mlp_norm"], params["final_norm"]):
        assert (norm["weight"] == 0).all()
    assert float(params["h0"]["mlp"]["moe"]["experts_up"].std()) \
        == pytest.approx(0.02, rel=0.1)
    assert float(params["embed"].std()) == pytest.approx(ref.EMBED_STD,
                                                         rel=0.1)
    # the program's own initialisers agree on the constants
    own = meta.unbox(jax.jit(lambda: qn.Qwen3Next(cfg).init_params(
        jax.random.PRNGKey(0), batch=1))())
    assert (own["h0"]["mixer"]["dt_bias"] == 1).all()
    assert (own["h0"]["mixer"]["norm"]["weight"] == 0).all()


#: float32: the two are the same arithmetic in another order (the
#: program's chunked scan against the reference's recurrence).
#: bfloat16 at width 32: every matmul rounds to 8 bits and nothing
#: averages out, and the reference is given the program's choices
@pytest.mark.parametrize("dtype,loss_rtol,grad_rtol,held", [
    (jnp.float32, 2e-6, 5e-5, (2, 4)),
    (jnp.bfloat16, 5e-4, 0.1, (2, 4)),
])
def test_program_matches_reference_on_loss_and_gradients(
        dtype, loss_rtol, grad_rtol, held):
    cfg, model, params, tokens, sizes = _setup(dtype=dtype, remat="full",
                                               experts_held=held)
    params = _stirred(params, jax.random.PRNGKey(5))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: qn.loss_fn(model, p, tokens)))(params)
    choices = qn.router_choices(model, params, tokens)
    assert len(choices) == 3 and choices[0].shape == (2 * 64, cfg.top_k)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, choices=choices, **sizes)))(params)
    assert abs(float(loss) - float(want)) <= loss_rtol * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= grad_rtol
    if dtype == jnp.float32:   # then the reference chooses the same
        own = ref.forward(params, tokens, **sizes)[1]
        for a, b in zip(choices, own):
            assert (jnp.sort(a, -1) == jnp.sort(b, -1)).all()


@pytest.mark.parametrize("pattern,layers", [("L", 1), ("F", 0), ("FL", 1)])
def test_either_layer_kind_apart_and_any_pattern(pattern, layers):
    """A stack of one kind alone, and patterns the share does not use:
    the leaf-by-leaf gradients, so that a small leaf (``A_log``,
    ``dt_bias``, the convolution, a norm's weight, the shared expert's
    gate) is held to the reference on its own scale."""
    cfg = qn.Qwen3NextConfig.tiny(dtype=jnp.float32, pattern=pattern,
                                  num_layers=layers)
    model = qn.Qwen3Next(cfg)
    params = _stirred(ref.init_like(meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2))),
        jax.random.PRNGKey(4)), jax.random.PRNGKey(6))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    sizes = dict(n_layer=layers, n_head=cfg.num_heads, ln_eps=cfg.rms_eps,
                 arch=_arch(cfg), query_block=16, token_chunk=32,
                 scan_segment=16)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: qn.loss_fn(model, p, tokens)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, **sizes)))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) <= 5e-4 * scale, \
            jax.tree_util.keystr(path)


def test_the_mixer_s_pieces_against_the_reference_s():
    """The convolution reads the past alone and has no bias, the gated
    norm normalises first, the rotation touches the first elements
    alone, the norms scale by ``1 + w``."""
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    got = sc.short_conv(u, w)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(
        ref.causal_conv(u[0], w)), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        sc.short_conv(u, w, jnp.zeros((6,)))))
    later = u.at[:, 7:].set(0.0)   # the future changed: the past holds
    np.testing.assert_allclose(np.asarray(sc.short_conv(later, w)[:, :7]),
                               np.asarray(got[:, :7]))
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(
        jax.nn.silu(w[3] * u[0, 0])), rtol=1e-6)   # t = 0 sees itself alone
    o = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 2, 4))
    z = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 2, 4))
    scale = jnp.arange(1.0, 5.0)
    want = scale * o / jnp.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) \
        * jax.nn.silu(z)
    np.testing.assert_allclose(np.asarray(qn.norm_then_gate(
        o, z, scale, 1e-6)), np.asarray(want), rtol=1e-5)
    gate_first = (o * jax.nn.silu(z))
    gate_first = scale * gate_first / jnp.sqrt(
        (gate_first ** 2).mean(-1, keepdims=True) + 1e-6)
    assert float(jnp.abs(gate_first - want).max()) > 0.1
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 2, 16))
    turned = qn.partial_rope(x, 4, 1e7)
    np.testing.assert_allclose(np.asarray(turned[..., 4:]),
                               np.asarray(x[..., 4:]))
    np.testing.assert_allclose(np.asarray(turned[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)   # position 0 turns by nothing
    assert float(jnp.abs(turned[:, 1:, :, :4] - x[:, 1:, :, :4]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(turned), np.asarray(
        ref._rotate_first(x, {"rotary_dim": 4, "rope_theta": 1e7})),
        rtol=1e-5, atol=1e-6)
    unit = qn.l2_normalised(x, 0.5)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(unit, axis=-1)),
                               0.5, rtol=1e-5)
    rows = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 32))
    wn = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (32,))
    np.testing.assert_allclose(
        np.asarray(fused.fused_rmsnorm(rows, wn, eps=1e-6, offset=1.0)),
        np.asarray(ref._norm(rows, wn, 1e-6)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(fused.fused_rmsnorm(rows, wn, eps=1e-6, offset=1.0,
                                       interpret=True)),
        np.asarray(ref._norm(rows, wn, 1e-6)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(   # and with no offset it is what it was
        np.asarray(fused.fused_rmsnorm(rows, 1.0 + wn, eps=1e-6,
                                       interpret=True)),
        np.asarray(ref._norm(rows, wn, 1e-6)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,grad_rtol", [(jnp.float32, 5e-5),
                                              (jnp.bfloat16, 0.1)])
def test_the_harness_pairs_both_gradients_at_the_reference_s_routing(
        dtype, grad_rtol):
    """``entry.loss_fn`` of the cell's configuration: the program's loss
    at the experts the reference chose, given that the routing is the
    reference's up to near ties and the scan is the recurrence's."""
    from benchmarks.reference import qwen3_next_paired as paired

    cfg, model, params, tokens, sizes = _setup(dtype=dtype,
                                               experts_held=(2, 4))
    (loss, misrouted), got = jax.jit(jax.value_and_grad(
        lambda p: paired.program_loss(model, p, tokens, arch=_arch(cfg),
                                      with_misrouted=True),
        has_aux=True))(params)
    want = jax.jit(jax.grad(lambda p: ref.loss(p, tokens, **sizes)))(params)
    assert float(loss) > 1.0 and float(misrouted) <= paired.MISROUTED_MAX
    assert float(ref.grad_error(got, want)) <= grad_rtol
    assert float(paired.scan_error(model, params, tokens, _arch(cfg))) \
        <= paired.SCAN_RTOL


def test_a_scan_that_loses_its_carry_zeroes_the_paired_loss(monkeypatch):
    from benchmarks.reference import qwen3_next_paired as paired

    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32)
    scan = jax.lax.scan

    def forgetful(body, init, xs, **kw):
        if isinstance(xs, tuple) and len(xs) == 4:   # the chunks' carry
            return scan(lambda s, x: body(jnp.zeros_like(s), x), init, xs,
                        **kw)
        return scan(body, init, xs, **kw)

    monkeypatch.setattr(gd.jax.lax, "scan", forgetful)
    # (outside the op's inner ``jit``, whose cache may hold a sound trace)
    monkeypatch.setattr(gd, "_gated_delta", gd._gated_delta.__wrapped__)
    assert float(paired.scan_error(model, params, tokens, _arch(cfg))) \
        > 100 * paired.SCAN_RTOL
    assert float(paired.program_loss(model, params, tokens,
                                     arch=_arch(cfg))) == 0.0


def test_the_reference_runs_a_batch_as_its_sequences_one_at_a_time():
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.float32)
    loss_sum = jax.jit(lambda t, c=None: ref.loss_sum(params, t, choices=c,
                                                      **sizes))
    both = loss_sum(tokens)
    each = sum(loss_sum(tokens[i:i + 1]) for i in range(2))
    assert float(both) == pytest.approx(float(each), rel=1e-6)
    own = ref.forward(params, tokens, **sizes)[1]
    assert len(own) == 3 and own[0].shape == (2 * cfg.max_seq_len, cfg.top_k)
    replay = loss_sum(tokens, own)
    assert float(replay) == pytest.approx(float(both), rel=1e-6)


# ---------------------------------------------------------------------------
# the routed layer under a softmax, sixteen shares
# ---------------------------------------------------------------------------

def _layer_params(cfg, key, experts):
    e, w = cfg.embed_dim, cfg.expert_dim
    ks = jax.random.split(key, 4)
    return {"router": 0.5 * jax.random.normal(ks[0], (e, cfg.num_experts)),
            "experts_gate": 0.2 * jax.random.normal(ks[1], (experts, e, w)),
            "experts_up": 0.2 * jax.random.normal(ks[2], (experts, e, w)),
            "experts_down": 0.2 * jax.random.normal(ks[3], (experts, w, e))}


def _share(params, first, count):
    return {k: v if k == "router" else v[first:first + count]
            for k, v in params.items()}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The share test the guide asks for, at the deployment's own
    division: 32 experts in SIXTEEN shares of 2 (a softmax over all 32,
    top-10, weights divided by their sum): the routed parts that all the
    shares give, plus the gated shared expert counted ONCE, equal the
    uncut layer of the uncut reference."""
    kw = dict(dtype=jnp.float32, num_experts=32, top_k=10)
    cfg = qn.Qwen3NextConfig.tiny(experts_held=(0, 32), **kw)
    full = _layer_params(cfg, jax.random.PRNGKey(5), 32)
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    e, w = cfg.embed_dim, cfg.shared_dim
    shared = {name: {"kernel": 0.2 * jax.random.normal(k, shape)}
              for name, k, shape in (
                  ("shared_gate", keys[0], (e, w)),
                  ("shared_up", keys[1], (e, w)),
                  ("shared_down", keys[2], (w, e)),
                  ("shared_expert_gate", keys[3], (e, 1)))}
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 24, e))
    flat = h.reshape(-1, e)
    arch = dict(_arch(cfg), first_held=0)
    with jax.default_matmul_precision("highest"):
        once = jax.nn.sigmoid(flat @ shared["shared_expert_gate"]["kernel"]) \
            * ref._swiglu(flat, *(shared[n]["kernel"] for n in (
                "shared_gate", "shared_up", "shared_down")))
        parts = [afmoe.RoutedExperts(qn.Qwen3NextConfig.tiny(
            experts_held=(first, 2), **kw)).apply(
                {"params": _share(full, first, 2)}, h).reshape(flat.shape)
            for first in range(0, 32, 2)]
        w_all, (own, scores) = ref.held_weights(flat, full, arch)
        uncut = ref.experts_under_mask(flat, w_all, full)
    assert len(parts) == 16
    assert sum(float(jnp.abs(p).max()) > 0 for p in parts) >= 12
    # a softmax over ALL experts; a token's weights over its ten sum to 1
    np.testing.assert_allclose(np.asarray(scores.sum(-1)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w_all.sum(-1)), 1.0, rtol=1e-5)
    assert ((w_all > 0).sum(-1) == 10).all()
    np.testing.assert_allclose(np.asarray(once + sum(parts)),
                               np.asarray(once + uncut),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2


def test_the_routers_tell_their_operator_under_this_model_s_name():
    cfg, model, params, tokens, _ = _setup(experts_held=(2, 4))
    stats = qn.router_stats(model, params, tokens)
    assert stats["load"].shape == (3, 4)
    flat = qn.report_router_stats(stats)
    assert set(flat) == {f"moe/h{i}/{k}" for i in range(3) for k in (
        "landed_share", "imbalance", "live_share")}
    assert all(0 <= flat[f"moe/h{i}/landed_share"] <= 1 for i in range(3))
    assert qn.report_router_stats.keywords == {"model_name": "qwen3_next"}


def test_the_plan_spans_say_what_was_compiled():
    cfg, model, params, tokens, _ = _setup(remat="full")
    telemetry.drain_spans("test")
    jax.eval_shape(lambda p: qn.loss_fn(model, p, tokens), params)
    rows = {}
    for r in telemetry.drain_spans("test"):
        rows.setdefault((r["cat"], r["name"]), []).append(r["args"])
    (hybrid,) = rows["model", "hybrid.plan"]
    assert hybrid["pattern"] == "LLF" and hybrid["mixers"] == 2
    assert hybrid["attention"] == 1 and hybrid["experts"] == 3
    assert hybrid["scan"] == "gated_delta" and hybrid["experts_held"] == 8
    (moe,) = rows["model", "moe.plan"]
    assert moe["experts"] == 8 and moe["top_k"] == 2
    assert moe["router"] == "softmax"
    scans = rows["ops", "gated_delta.plan"]
    # two mixers x two sequences (the recompute is traced with the grads)
    assert len(scans) == 4
    assert scans[0]["chunk"] == 16 and scans[0]["chunks"] == 4
    assert scans[0]["key_heads"] == 2 and scans[0]["value_heads"] == 4
    assert scans[0]["saved"] == "chunk_states,carry_operands"
    # heads of 8 lanes: a shape the kernels cannot tile
    assert (scans[0]["chunk_math"], scans[0]["carry"]) == ("xla", "xla")
