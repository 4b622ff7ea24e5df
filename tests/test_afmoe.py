"""The AFMoE block (``ray_tpu/models/afmoe.py``) and its routed expert
layer against the plain reference (``benchmarks/reference/afmoe.py``),
at tiny sizes on the CPU: loss and gradients, the shares of the experts
adding up to the uncut layer, nothing dropped at any imbalance, the
grouped products through the Pallas interpreter, what the router tells
its operator, and what the ``moe.plan`` span says was compiled."""

import functools
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

from benchmarks.reference import afmoe as ref  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import afmoe  # noqa: E402
from ray_tpu.ops import grouped_matmul as gm  # noqa: E402


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Row tiles of 8, not 256: at these sizes the groups then span
    several tiles, pad unevenly, and overflow their buffers."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)


def _arch(cfg):
    return dict(window=cfg.window, rope_theta=cfg.rope_theta,
                route_scale=cfg.route_scale, top_k=cfg.top_k,
                global_every=cfg.global_every, mup=cfg.mup,
                first_held=cfg.experts_held[0],
                expert_layer_start=cfg.expert_layer_start)


def _setup(**kw):
    """2 K/V heads under 4 query heads, window 24 < sequence 64, 8
    experts top-2, one dense and two expert layers (sliding, full)."""
    cfg = afmoe.AFMoEConfig.tiny(**kw)
    model = afmoe.AFMoE(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq_len),
                                0, cfg.vocab_size)
    sizes = dict(n_layer=cfg.num_layers, n_head=cfg.num_heads,
                 ln_eps=cfg.rms_eps, arch=_arch(cfg), query_block=16,
                 token_chunk=32)
    return cfg, model, params, tokens, sizes


#: float32: the two are the same arithmetic in another order.  bfloat16
#: at width 32: every matmul rounds to 8 bits and nothing averages out
#: (0.055 measured; 0.0175 at width 512, 0.01 expected at 2048), and the
#: reference is given the program's choices, since a near tie of two
#: scores may flip between the precisions (counted on the chip, PERF.md)
@pytest.mark.parametrize("dtype,loss_rtol,grad_rtol,held", [
    (jnp.float32, 1e-6, 1e-5, (0, 8)),
    (jnp.float32, 1e-6, 1e-5, (2, 4)),
    (jnp.bfloat16, 1e-4, 0.1, (2, 4)),
])
def test_program_matches_reference_on_loss_and_gradients(
        dtype, loss_rtol, grad_rtol, held):
    cfg, model, params, tokens, sizes = _setup(dtype=dtype,
                                               experts_held=held)
    assert cfg.layer_kinds() == ["sliding", "sliding", "full"]
    loss, grads = jax.value_and_grad(
        lambda p: afmoe.loss_fn(model, p, tokens))(params)
    choices = afmoe.router_choices(model, params, tokens)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, choices=choices, **sizes))(params)
    assert abs(float(loss) - float(want)) <= loss_rtol * float(want)
    assert float(ref.grad_error(grads, want_grads)) <= grad_rtol
    if dtype == jnp.float32:   # then the reference chooses the same
        own = ref.forward(params, tokens, **sizes)[1]
        for a, b in zip(choices, own):
            assert (jnp.sort(a, -1) == jnp.sort(b, -1)).all()


def test_a_recorded_routing_replays_to_the_same_loss():
    """``loss_fn(choices=)`` with the routers' own choices is the loss
    without them, to the bit; with other choices it is another loss."""
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32,
                                           experts_held=(2, 4))
    own = afmoe.router_choices(model, params, tokens)
    loss = afmoe.loss_fn(model, params, tokens)
    assert float(afmoe.loss_fn(model, params, tokens, choices=own)) \
        == float(loss)
    other = [(c + 1) % cfg.num_experts for c in own]
    assert float(afmoe.loss_fn(model, params, tokens, choices=other)) \
        != float(loss)


@pytest.mark.parametrize("dtype,grad_rtol", [(jnp.float32, 1e-5),
                                              (jnp.bfloat16, 0.1)])
def test_the_harness_pairs_both_gradients_at_the_reference_s_routing(
        dtype, grad_rtol):
    """``entry.loss_fn`` of the cell's configuration: the program's loss
    at the experts the reference chose, so that the harness's
    ``grad_error(grad(program), grad(reference.loss))`` compares
    arithmetic and not near ties."""
    from benchmarks.reference import afmoe_paired

    cfg, model, params, tokens, sizes = _setup(dtype=dtype,
                                               experts_held=(2, 4))
    got = jax.grad(lambda p: afmoe_paired.program_loss(
        model, p, tokens, arch=_arch(cfg)))(params)
    want = jax.grad(lambda p: ref.loss(p, tokens, **sizes))(params)
    assert float(ref.grad_error(got, want)) <= grad_rtol


def test_the_pairing_refuses_a_routing_that_is_not_the_reference_s():
    """The program's own routers against the reference's scores: the
    sound program misroutes (almost) no token and gets its loss; one
    whose routers see another input misroutes many, gets the constant 0
    and so a gradient of zero, which the harness reads as an error of 1."""
    from benchmarks.reference import afmoe_paired

    cfg, model, params, tokens, _ = _setup(dtype=jnp.bfloat16,
                                           experts_held=(2, 4))
    loss, misrouted = afmoe_paired.program_loss(
        model, params, tokens, arch=_arch(cfg), with_misrouted=True)
    assert float(misrouted) <= 0.02 and float(loss) > 1.0

    class Deaf(afmoe.AFMoE):   # its routers hear half of each token
        def apply(self, variables, *a, **kw):
            p = dict(variables["params"])
            for name in ("h0", "h1"):
                moe = dict(p[name]["mlp"]["moe"])
                moe["router"] = moe["router"].at[::2].set(0.0)
                p[name] = {**p[name], "mlp": {**p[name]["mlp"], "moe": moe}}
            return super().apply({"params": p}, *a, **kw)

    deaf = Deaf(cfg)
    (loss, misrouted), grads = jax.value_and_grad(
        lambda p: afmoe_paired.program_loss(
            deaf, p, tokens, arch=_arch(cfg), with_misrouted=True),
        has_aux=True)(params)
    assert float(misrouted) > afmoe_paired.MISROUTED_MAX
    assert float(loss) == 0.0
    assert all(float(jnp.abs(g).max()) == 0.0
               for g in jax.tree.leaves(grads))


def test_where_the_two_routings_differ_the_scores_nearly_tie():
    """bfloat16 program against float32 reference: a token whose chosen
    sets differ swapped experts whose reference scores are close."""
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.bfloat16)
    mine = afmoe.router_choices(model, params, tokens)
    theirs = ref.forward(params, tokens, **sizes)[1]
    for a, b, (differ, gap) in zip(
            mine, theirs, ref.flip_gaps(params, tokens, mine, **sizes)):
        assert (differ == (jnp.sort(a, -1) != jnp.sort(b, -1)).any(-1)).all()
        assert 0 < float(differ.mean()) < 0.25
        assert 0 <= float(gap.min()) and float(gap.max()) < 0.05
        assert float(jnp.where(differ, 0.0, gap).max()) == 0.0
    # a routing that is simply wrong reads large
    wrong = [(c + 3) % cfg.num_experts for c in theirs]
    assert all(float(gap.max()) > 0.05 for _, gap in
               ref.flip_gaps(params, tokens, wrong, **sizes))


def _layer(cfg, h, params):
    return afmoe.RoutedExperts(cfg).apply({"params": params}, h)


def _layer_params(cfg, key):
    e, w, n = cfg.embed_dim, cfg.expert_dim, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {"router": 0.5 * jax.random.normal(ks[0], (e, n)),
            "experts_gate": 0.2 * jax.random.normal(ks[1], (n, e, w)),
            "experts_up": 0.2 * jax.random.normal(ks[2], (n, e, w)),
            "experts_down": 0.2 * jax.random.normal(ks[3], (n, w, e))}


def _all_pick(params, row, experts):
    """A router under which every token equal to ``row`` picks
    ``experts``: logits 12, 10, .. on those, |logit| < 9 elsewhere."""
    router = params["router"]
    for n, e in enumerate(experts):
        router = router.at[:, e].set((12.0 - 2 * n) * row / (row @ row))
    return dict(params, router=router)


def _share(params, first, count):
    return {k: v if k == "router" else v[first:first + count]
            for k, v in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the routed parts that all the shares
    give, plus the shared expert counted ONCE, equal the uncut layer of
    the uncut reference."""
    cfg = afmoe.AFMoEConfig.tiny(dtype=jnp.float32)
    full = _layer_params(cfg, jax.random.PRNGKey(5))
    shared = {k: 0.2 * jax.random.normal(
        jax.random.PRNGKey(6 + i), s) for i, (k, s) in enumerate((
            ("gate", (cfg.embed_dim, cfg.expert_dim)),
            ("up", (cfg.embed_dim, cfg.expert_dim)),
            ("down", (cfg.expert_dim, cfg.embed_dim))))}
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    with jax.default_matmul_precision("highest"):
        once = ref._swiglu(flat, shared["gate"], shared["up"],
                           shared["down"])
        parts = [
            _layer(afmoe.AFMoEConfig.tiny(dtype=jnp.float32,
                                          experts_held=(first, 2)),
                   h, _share(full, first, 2)).reshape(flat.shape)
            for first in (0, 2, 4, 6)]
        uncut, _ = ref._routed(flat, full, dict(_arch(cfg), first_held=0),
                               None, 16)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(np.asarray(once + sum(parts)),
                               np.asarray(once + uncut),
                               rtol=1e-4, atol=1e-5)
    # and a share alone is NOT the layer: nothing stands in for the rest
    assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2


@pytest.mark.parametrize("held,picks,kernels", [
    ((0, 8), (0, 1), None), ((0, 2), (0, 1), None), ((6, 2), (0, 1), None),
    # a replayed routing may name ONE expert twice: every pair of every
    # token on expert 0, the gathers' loop at its longest, and through
    # the kernels (the interpreter's dead rows are NaN)
    ((0, 1), (0, 0), True), ((0, 2), (0, 0), True), ((0, 2), (0, 1), True),
])
def test_no_row_is_dropped_when_every_token_picks_the_same_experts(
        held, picks, kernels, monkeypatch):
    """Every token alike, so all 48 pick the same 2 of 8 experts: the
    worst imbalance there is.  The layer's result is the reference's for
    every token, whether the picked experts are held here (all 96 pairs
    then land on 2 experts, or on one, four and eight times what an even
    router lands) or not: the buffers are the worst case's."""
    monkeypatch.setattr(gm, "kernel_mode", lambda interpret: kernels)
    cfg = afmoe.AFMoEConfig.tiny(dtype=jnp.float32, experts_held=held)
    row = jax.random.normal(jax.random.PRNGKey(9), (cfg.embed_dim,))
    full = _all_pick(_layer_params(cfg, jax.random.PRNGKey(8)), row, (0, 1))
    h = jnp.broadcast_to(row, (2, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    picked, _ = ref.route(flat, full["router"], cfg.top_k, cfg.route_scale)
    assert (jnp.sort(picked, -1) == jnp.array([0, 1])).all()
    chosen = None
    if picks != (0, 1):
        chosen = picked = jnp.broadcast_to(jnp.array(picks), picked.shape)
    got = afmoe.RoutedExperts(cfg).apply(
        {"params": _share(full, *held)}, h, chosen).reshape(flat.shape)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._routed(flat, _share(full, *held),
                              dict(_arch(cfg), first_held=held[0]),
                              chosen, 16)
    here = sum(int(held[0] <= int(e) < sum(held)) for e in picked[0])
    plan = gm.plan_rows(picked, held[0], held[1],
                        block_m=afmoe.BLOCK_ROWS)
    assert bool(plan.fits)   # sized for the worst case: always
    assert int(plan.pair_valid.sum()) == 48 * here == int(plan.sizes.sum())
    assert int(plan.row_valid.sum()) == 48 * here
    assert here == {(6, 2): 0, (0, 1): 2 if picks == (0, 0) else 1}.get(
        held, 2)
    # the rows fill their tiles: 6 tiles of 8 a choice, of 12 + held
    assert int(plan.n_live[0]) == 6 * here
    assert plan.tile_expert.shape[0] == 12 + held[1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_a_plan_past_its_buffers_says_so_and_dropping_it_is_wrong():
    """Buffers sized for fewer pairs than land: the plan says it does
    not fit.  The layer sizes its own for the worst case and is exact;
    its steps run by hand on the short plan anyway (what the benchmark's
    ``drops`` control does) lose rows and are far from the reference."""
    picked = jnp.zeros((48, 2), jnp.int32).at[:, 1].set(1)
    full = gm.plan_rows(picked, 0, 2, block_m=8)
    short = gm.plan_rows(picked, 0, 2, block_m=8, row_bound=32)
    assert bool(full.fits) and int(full.pair_valid.sum()) == 96
    assert not bool(short.fits) and int(short.pair_valid.sum()) == 48

    cfg = afmoe.AFMoEConfig.tiny(dtype=jnp.float32, experts_held=(0, 2))
    row = jax.random.normal(jax.random.PRNGKey(9), (cfg.embed_dim,))
    p = _share(_all_pick(_layer_params(cfg, jax.random.PRNGKey(8)), row,
                         (0, 1)), 0, 2)
    h = jnp.broadcast_to(row, (2, 24, cfg.embed_dim))
    flat = h.reshape(-1, cfg.embed_dim)
    exact = _layer(cfg, h, p)
    idx, weights, _ = afmoe.route(cfg, flat, p["router"])
    rows = gm.dispatch(flat, short)
    act = jax.nn.silu(gm.grouped_matmul(rows, p["experts_gate"], short)) \
        * gm.grouped_matmul(rows, p["experts_up"], short)
    dropped = gm.combine(gm.grouped_matmul(act, p["experts_down"], short),
                         weights, short)
    with jax.default_matmul_precision("highest"):
        want, picked = ref._routed(flat, p, dict(_arch(cfg), first_held=0),
                                   None, 16)
    assert (jnp.sort(idx, -1) == jnp.sort(picked, -1)).all()
    assert int(((picked >= 0) & (picked < 2)).sum()) == 96 > 32
    np.testing.assert_allclose(np.asarray(exact).reshape(want.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(dropped - want).max()) > 1e-3


def _plain_layer(cfg, p, h, chosen=None):
    """The routed layer unfused, in plain ``jnp`` under plain autodiff:
    every held expert applied to every token, under the routing's mask."""
    flat = h.reshape(-1, h.shape[-1])
    idx, w, _ = afmoe.route(cfg, flat, p["router"], chosen)
    ids = cfg.experts_held[0] + jnp.arange(cfg.experts_held[1])
    w_held = jnp.einsum("tk,tke->te", w, (
        idx[:, :, None] == ids[None, None]).astype(jnp.float32))
    up = jnp.einsum("td,edf->etf", flat, p["experts_up"])
    if "experts_gate" in p:
        mid = jax.nn.silu(jnp.einsum("td,edf->etf", flat,
                                     p["experts_gate"])) * up
    else:
        mid = jnp.square(jax.nn.relu(up))
    out = jnp.einsum("etf,efd->etd", mid, p["experts_down"])
    return jnp.einsum("te,etd->td", w_held, out).reshape(h.shape)


class _Relu2(afmoe.AFMoEConfig):
    expert_form = "relu2"


def _nan_rows(monkeypatch, block_n):
    """The kernels through the interpreter, whose unwritten results and
    whose blocks past an array's edge are NaN, tiles of ``block_n``; and
    the gathers' buffer NaN before the gather writes as far as it
    reaches: every dead row of every buffer is poison."""
    seen = []

    def empty(shape, dtype):
        seen.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    monkeypatch.setattr(jax.lax, "empty", empty)
    monkeypatch.setattr(gm, "expert_products", functools.partial(
        gm.expert_products, block_n=block_n, interpret=True))
    return seen


# 464 = 29 x 16: under tiles of 128 (and of 256, d rhs's) the last tile
# is masked, as the published 1856 under 384 and 640
@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("width,block_n", [(32, 512), (464, 128)])
def test_the_fused_layer_is_the_unfused_one_with_its_dead_rows_poisoned(
        form, width, block_n, monkeypatch):
    """Output and every gradient (``h``, router, gate, up, down) of the
    layer as it runs (the activation on the down product's tile, its
    derivative at the end of ``d lhs``, ``d rows`` summed in the second
    kernel, the gathers no further than they must reach) against plain
    ``jnp``, with
    every dead row NaN; expert 3 (held, the second of four) gets no
    row."""
    kinds = {"gated": afmoe.AFMoEConfig, "relu2": _Relu2}
    cfg = kinds[form].tiny(dtype=jnp.float32, experts_held=(2, 4),
                           expert_dim=width)
    p = _share(_layer_params(cfg, jax.random.PRNGKey(5)), 2, 4)
    if form == "relu2":
        del p["experts_gate"]
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 40, cfg.embed_dim))
    chosen = afmoe.route(cfg, h[0], p["router"])[0]
    chosen = jnp.where(chosen == 3, 7, chosen)     # nobody picks expert 3
    cot = jax.random.normal(jax.random.PRNGKey(11), h.shape)

    def loss(layer):
        return lambda h, p: (layer(h, p) * cot).sum()

    want, want_grads = jax.value_and_grad(loss(
        lambda h, p: _plain_layer(cfg, p, h, chosen)), argnums=(0, 1))(h, p)
    buffers = _nan_rows(monkeypatch, block_n)
    got, grads = jax.value_and_grad(loss(
        lambda h, p: afmoe.RoutedExperts(cfg).apply({"params": p}, h,
                                                    chosen)),
        argnums=(0, 1))(h, p)
    # dispatch's rows and combine's d rows: both through the one
    # gather, which is traced for each of its three shorter reaches
    plan = gm.plan_rows(chosen, 2, 4, block_m=afmoe.BLOCK_ROWS)
    assert buffers == [(plan.row_valid.shape[0], cfg.embed_dim)] * 6
    assert 0 < int(plan.n_live[0]) < plan.tile_expert.shape[0] - 2
    assert int(plan.sizes[1]) == 0 and int(plan.sizes.min(initial=99,
                                           where=plan.sizes > 0)) > 0
    rows = gm.dispatch(h[0], plan)
    # 14 tiles of 8 rows: an eighth is 2 tiles, a quarter 4, a half 7
    reach = next(r for r in (16, 32, 56, 112)
                 if r >= int(plan.n_live[0]) * afmoe.BLOCK_ROWS)
    assert reach == 56 and plan.tile_expert.shape[0] == 14
    assert np.isnan(np.asarray(rows)[reach:]).all()
    assert np.isfinite(np.asarray(rows)[:reach]).all()
    up = np.asarray(gm.grouped_matmul(rows, p["experts_up"], plan,
                                      interpret=True))
    live = int(plan.n_live[0]) * afmoe.BLOCK_ROWS
    assert np.isnan(up[live:]).all() and np.isfinite(up[:live]).all()
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat, want_flat = (jax.tree.leaves_with_path(g) for g in
                       (grads, want_grads))
    assert len(flat) == (6 if form == "gated" else 5) - 1
    for (path, g), (_, w) in zip(flat, want_flat):
        assert np.isfinite(np.asarray(g)).all(), path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5, err_msg=str(path))
    assert float(jnp.abs(grads[1]["experts_down"][1]).max()) == 0.0


# 896 = 7 x 128 and 7 is prime (Mellum's width): under the narrowest cap
# seven tiles, under the rule one; 464 = 29 x 16: no tile divides it,
# masked under the cap and whole under the rule
@pytest.mark.parametrize("matrices", [3, 2], ids=["gated", "relu2"])
@pytest.mark.parametrize("width", [896, 464])
def test_the_rule_s_tiles_give_the_narrowest_cap_s_results(matrices, width):
    """``expert_products`` forward, ``d rows`` and every ``d weights``
    through the Pallas interpreter at the narrowest tile (``block_n=128``:
    seven sweeps over the rows at 896, a masked fourth tile at 464) and
    at the tiles the rule picks (the whole width, one sweep): every
    element is one float32 ``dot_general`` over the whole contraction
    either way and ``d rhs`` adds the row tiles in order, so the results
    are the same to a float32 rounding, and the same BITS where the
    backend sums a contraction in one order whatever the result's width
    (the test prints which it found: this sandbox's CPU does not, its
    ``dot`` blocks a [8, 64] x [64, 128] product otherwise than a [8,
    64] x [64, 896] one; the chip does, PERF.md, PR 52)."""
    embed, held, block_m = 64, 4, 8
    assert gm.gmm_tiles(block_m, embed, width, 4)[0] == width
    assert gm.gmm_tiles(block_m, embed, width, 4, 128)[0] == 128
    assert gm.tgmm_tiles(block_m, embed, width, 4)[:2] == (embed, width)
    idx = jax.random.randint(jax.random.PRNGKey(0), (40, 2), 0, 8)
    plan = gm.plan_rows(idx, 2, held, block_m=block_m)
    live = int(plan.n_live[0]) * block_m
    assert 0 < live < plan.row_valid.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    rows = gm.dispatch(jax.random.normal(keys[0], (40, embed)), plan)
    rows = jnp.where(jnp.arange(rows.shape[0])[:, None] < live, rows, 0.0)
    weights = tuple(0.1 * jax.random.normal(k, (held, embed, width))
                    for k in keys[1:matrices]) + (
        0.1 * jax.random.normal(keys[3], (held, width, embed)),)
    cot = jax.random.normal(keys[4], rows.shape)

    def through(block_n):
        out, vjp = jax.vjp(lambda r, w: gm.expert_products(
            r, w, plan, block_n=block_n, interpret=True), rows, weights)
        d_rows, d_weights = vjp(cot)
        return [np.asarray(a) for a in
                (out[:live], d_rows[:live], *d_weights)]

    narrow, ruled = through(128), through(None)
    assert len(narrow) == 2 + matrices
    assert all(np.isfinite(a).all() for a in narrow + ruled)
    same = all(np.array_equal(a, b) for a, b in zip(narrow, ruled))
    print("the rule's tiles against the narrowest cap:",
          "bit for bit" if same else "to a float32 rounding")
    for a, b in zip(narrow, ruled):   # a rounding at the sums' scale
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   atol=1e-6 * np.abs(b).max())


def _by_loop(lhs, rhs, groups, block_m):
    """Each expert's rows times its matrix, one expert at a time."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    row = 0
    for e, n in enumerate(groups):
        out[row:row + n] = np.asarray(lhs[row:row + n]) @ np.asarray(rhs[e])
        row += -(-n // block_m) * block_m
    return out


@pytest.mark.parametrize("groups", [
    (5, 0, 19, 8, 0, 1),    # empty and uneven groups
    (0, 0, 0, 0, 0, 40),    # everything on the last expert
    (0, 0, 0, 0, 0, 0),     # nothing lands here
])
def test_grouped_product_matches_a_loop_over_experts(groups):
    """Forward, d lhs and d rhs kernels through the Pallas interpreter,
    on rows laid out by ``plan_rows``."""
    block_m, k, n, experts = 8, 16, 24, len(groups)
    picked = jnp.asarray(
        [e for e, c in enumerate(groups) for _ in range(c)]
        + [experts + 3] * 7, jnp.int32)[:, None]      # 7 pairs elsewhere
    plan = gm.plan_rows(picked, 0, experts, block_m=block_m)
    assert tuple(int(s) for s in plan.sizes) == groups
    rows = plan.row_valid.shape[0]
    lhs = jax.random.normal(jax.random.PRNGKey(0), (rows, k))
    lhs = jnp.where(plan.row_valid[:, None], lhs, 0.0)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (experts, k, n))
    cot = jnp.where(plan.row_valid[:, None], jax.random.normal(
        jax.random.PRNGKey(2), (rows, n)), 0.0)
    live = np.asarray(jnp.repeat(
        jnp.arange(plan.tile_expert.shape[0]) < plan.n_live[0], block_m))

    def through(interpret):
        out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, plan, block_n=8, interpret=interpret), lhs, rhs)
        return (out, *vjp(cot))

    want = _by_loop(lhs, rhs, groups, block_m)
    for interpret in (True, None):   # the kernels, and the jnp fallback
        out, d_lhs, d_rhs = through(interpret)
        np.testing.assert_allclose(np.asarray(out)[live], want[live],
                                   rtol=1e-5, atol=1e-5)
        want_dl = _by_loop(cot, jnp.swapaxes(rhs, 1, 2), groups, block_m)
        np.testing.assert_allclose(np.asarray(d_lhs)[live], want_dl[live],
                                   rtol=1e-5, atol=1e-5)
        want_dr = np.zeros(rhs.shape, np.float32)
        row = 0
        for e, c in enumerate(groups):
            want_dr[e] = np.asarray(lhs[row:row + c]).T @ np.asarray(
                cot[row:row + c])
            row += -(-c // block_m) * block_m
        np.testing.assert_allclose(np.asarray(d_rhs), want_dr,
                                   rtol=1e-5, atol=1e-5)


def test_dispatch_and_combine_are_each_others_transpose():
    picked = jax.random.randint(jax.random.PRNGKey(0), (40, 2), 0, 8)
    plan = gm.plan_rows(picked, 2, 4, block_m=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 6))
    w = jax.random.uniform(jax.random.PRNGKey(2), (40, 2))
    rows = gm.dispatch(x, plan)
    # each landed pair's row holds its token
    for t in range(40):
        for c in range(2):
            if plan.pair_valid[t, c]:
                assert (rows[plan.pair_row[t, c]] == x[t]).all()
    got = gm.combine(rows, w, plan)
    here = (picked >= 2) & (picked < 6)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x * (w * here).sum(1, keepdims=True)),
        rtol=1e-6, atol=1e-6)
    # gradients against plain autodiff of the same gathers
    def plain(x, w):
        rows = x[plan.row_pair // 2]
        return sum(jnp.where(plan.pair_valid[:, c, None],
                             rows[plan.pair_row[:, c]] * w[:, c, None], 0.0)
                   for c in range(2))
    g = jax.grad(lambda x, w: (gm.combine(gm.dispatch(x, plan), w, plan)
                               ** 2).sum(), argnums=(0, 1))(x, w)
    g_plain = jax.grad(lambda x, w: (plain(x, w) ** 2).sum(),
                       argnums=(0, 1))(x, w)
    for a, b in zip(g, g_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_router_stats_against_counts_made_by_hand():
    cfg, model, params, tokens, sizes = _setup(dtype=jnp.float32,
                                               experts_held=(2, 4))
    stats = afmoe.router_stats(model, params, tokens)
    choices = ref.forward(params, tokens, **sizes)[1]
    for layer, picked in enumerate(choices):
        picked = np.asarray(picked)
        load = [int((picked == e).sum()) for e in range(2, 6)]
        assert [int(x) for x in stats["load"][layer]] == load
        assert float(stats["landed_share"][layer]) == pytest.approx(
            sum(load) / picked.size)
        assert float(stats["imbalance"][layer]) == pytest.approx(
            max(load) / (sum(load) / 4))
        # a call sees one sequence of 64: its groups padded to tiles of
        # 8, in buffers of 64 x 2 / 8 tiles and one more an expert
        live = sum(-(-int((picked[s * 64:(s + 1) * 64] == e).sum()) // 8)
                   for s in range(2) for e in range(2, 6))
        assert int(stats["live_tiles"][layer]) == live
        assert int(stats["buffer_tiles"][layer]) == 2 * (16 + 4)
    flat = afmoe.report_router_stats(stats)
    assert set(flat) == {f"moe/h{i}/{k}" for i in range(2) for k in (
        "landed_share", "imbalance", "live_share")}
    assert flat["moe/h1/live_share"] == pytest.approx(live / 40)
    gauge = telemetry._gauge("ray_tpu_moe_live_share", "")
    assert gauge.tag_keys == ("model", "layer")
    assert gauge._values[telemetry._moekey("afmoe", 1)] \
        == pytest.approx(live / 40)


def test_moe_plan_span_says_what_was_compiled():
    cfg, model, params, tokens, _ = _setup(dtype=jnp.float32,
                                           experts_held=(2, 4))
    telemetry.drain_spans("test")
    jax.eval_shape(lambda p: afmoe.loss_fn(model, p, tokens), params)
    rows = [r for r in telemetry.drain_spans("test")
            if r["name"] == "moe.plan"]
    assert len(rows) == 1 and rows[0]["cat"] == "model"
    assert rows[0]["args"] == {
        "experts": 8, "held_first": 2, "held": 4, "top_k": 2,
        "row_bound": 64 * 2,   # every pair of a sequence
        "block_rows": 8,
        # what of that buffer a layer-call still passes over whole, and
        # how the gathers keep to the live rows
        "buffer_passes": 0, "row_gather": "reach",
        "gather_reaches": "1/8,1/4,1/2,1/1",
        # the sums over tokens: a sequence of 64 is one tile of the walk
        "walk_tile": 64, "pairs": 64 * 2, "walked": "table",
        # what a recompute keeps of the call (``step.remat``): the choices
        # [64, 2] int32 and the plan's tables over 128 / 8 + 4 tiles of 8
        # rows (``row_pair`` int32 and ``row_valid``, ``pair_row`` int32
        # and ``pair_valid``, a tile's expert, the live tiles' count)
        "kept": "choices,plan",
        "kept_bytes": 64 * 2 * 4 + 160 * (4 + 1) + 128 * (4 + 1) + 20 * 4
                      + 4,
        # the grouped products: an expert's whole matrix a block (k x
        # tile), one sweep over the rows each
        "product_tiles": "up 32x32:1, down 32x32:1, drhs 32x32:1x1, "
                         "drhs_down 32x32:1x1",
        "product_vmem_bytes": gm.product_tiles(8, 32, 32, 4)[
            "product_vmem_bytes"],
        "window": 24, "heads": 4, "kv_heads": 2, "layers": "s,s,f"}


def _kernel_calls(jaxpr):
    """Every ``pallas_call`` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns") and eqn.primitive.name != "pallas_call":
                yield from _kernel_calls(inner)


#: the four routed configurations as their cells run them: the span's
#: ``product_tiles`` at the published widths
PRODUCT_TILES = {
    "trinity": "up 2048x1024:1, down 1024x2048:1, drhs 2048x1024:1x1, "
               "drhs_down 1024x2048:1x1",
    "kanana": "up 2048x768:1, down 768x2048:1, drhs 2048x768:1x1, "
              "drhs_down 768x2048:1x1",
    # an expert's d rhs whole is 76 MB of blocks: three blocks of it
    "nemotron": "up 2688x1856:1, down 1856x2688:1, drhs 896x1856:3x1, "
                "drhs_down 1856x896:1x3",
    # where the parent made seven sweeps (896 under 128) and six
    "mellum": "up 2304x896:1, down 896x2304:1, drhs 2304x896:1x1, "
              "drhs_down 896x2304:1x1",
}


def _published(name):
    from ray_tpu.models import deepseek_v3, mellum, nemotron_h

    return {
        "trinity": afmoe.AFMoEConfig.trinity_mini_share,
        "kanana": deepseek_v3.DeepseekV3Config.kanana_2_30b_a3b_share,
        "nemotron":
            nemotron_h.NemotronHConfig.nemotron_3_nano_30b_a3b_share,
        "mellum": mellum.MellumConfig.mellum2_12b_a2_5b_stage,
    }[name]()


@pytest.mark.parametrize("name", sorted(PRODUCT_TILES))
def test_moe_plan_span_names_the_tiles_the_kernels_are_built_with(
        name, monkeypatch):
    """``product_tiles`` and ``product_vmem_bytes`` of the ``moe.plan``
    span at a cell's published widths, and the kernels traced at those
    widths (compiled form, not run): every grouped product's grid makes
    the sweeps over the rows the span says, its weight block is the
    span's, and no kernel asks for more VMEM than the span's bytes.  One
    function (``gm.gmm_tiles`` / ``gm.tgmm_tiles``), two callers."""
    import re

    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 256)   # the cells'
    cfg = _published(name)
    args = afmoe.routed_plan_args(cfg, 8192)
    assert args["product_tiles"] == PRODUCT_TILES[name]
    assert args["block_rows"] == 256
    assert 16 * 2 ** 20 < args["product_vmem_bytes"] <= gm.VMEM_BUDGET
    said = {kind: tuple(int(x) for x in rest if x)
            for kind, *rest in re.findall(
                r"(\w+) (\d+)x(\d+):(\d+)(?:x(\d+))?",
                args["product_tiles"])}
    embed, width = cfg.embed_dim, cfg.expert_dim
    gated = getattr(cfg, "expert_form", "gated") == "gated"
    tiles, held = 3, 2

    def shape(*dims, dtype=cfg.dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    def grads(rows, weights, tile_expert, n_live):
        out, vjp = jax.vjp(lambda r, w: gm._experts(
            r, w, tile_expert, n_live, 256, None, False), rows, weights)
        return out, vjp(out)

    weights = (shape(held, embed, width),) * (2 if gated else 1) \
        + (shape(held, width, embed),)
    calls = list(_kernel_calls(jax.make_jaxpr(grads)(
        shape(tiles * 256, embed), weights, shape(tiles, dtype=jnp.int32),
        shape(1, dtype=jnp.int32)).jaxpr))
    assert len(calls) == (9 if gated else 6)
    for eqn in calls:
        grid = eqn.params["grid_mapping"].grid
        blocks = [tuple(getattr(b, "block_size", None) for b in m.block_shape)
                  for m in eqn.params["grid_mapping"].block_mappings]
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert 16 * 2 ** 20 <= limit <= args["product_vmem_bytes"]
        kernel = eqn.params["name"]
        result = eqn.params["out_avals"][0].shape
        if "drhs" in kernel:
            k, n = result[1:]
            block_k, block_n, over_k, over_n = said[
                "drhs" if k == embed else "drhs_down"]
            assert grid == (over_k, over_n, tiles), kernel
            assert (None, block_k, block_n) in blocks, kernel
            continue
        # onto the experts' width (gate, up, the down product's d lhs)
        # or back onto the rows' (down, gate's and up's d lhs)
        k, tile, sweeps = said["up" if result[1] == width else "down"]
        assert grid == (sweeps, tiles), kernel
        assert k + result[1] == embed + width
        assert ((None, tile, k) if "_t" in kernel else (None, k, tile)) \
            in blocks, kernel


def test_the_cut_configuration_is_the_file_s():
    """One chip's share of eight, as ``benchmarks/configs/
    trinity-mini.json`` states it: widths as published."""
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        conf = json.load(f)
    cfg = afmoe.AFMoEConfig.trinity_mini_share()
    pub = conf["published"]
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.dense_dim, cfg.expert_dim, cfg.num_experts, cfg.top_k,
            cfg.window, cfg.route_scale, cfg.rms_eps, cfg.rope_theta) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["sliding_window"], pub["route_scale"], pub["rms_norm_eps"],
        pub["rope_theta"])
    assert cfg.layer_kinds() == conf["as_run"]["layer_kinds"]
    assert list(cfg.experts_held) == conf["as_run"]["experts_held"]
    assert cfg.experts_held[1] == conf["num_experts"] == 16
    assert (cfg.num_layers + cfg.num_dense_layers, cfg.vocab_size,
            cfg.max_seq_len) == (conf["num_hidden_layers"],
                                 conf["vocab_size"], conf["n_positions"])
    assert afmoe.AFMoEConfig.trinity_mini().layer_kinds() == [
        k.split("_")[0] for k in pub["layer_types"]]
