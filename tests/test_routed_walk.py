"""The routed layer's sums over tokens as a walk of the pairs that
landed (``ops/grouped_matmul.py`` ``_walk_pallas``), through the Pallas
interpreter, against the gathers it replaces on a TPU and which stay
the path everywhere else (``_gather_sum``, ``_gather_dots``).

How equal: the plain sum (``dispatch``'s backward) adds the same
float32 numbers in the same order, a token's landed pairs by choice, so
it is equal to the last bit.  The weighted sum multiplies and adds in
that order too, but the CPU's compiled code is free to fuse a multiply
into the add that follows it (one rounding, not two) in either program,
so it is held to 2 ulp of float32.  ``d_w`` sums a row's products over
the width in another order than XLA's reduction: 1e-5 of the largest.
``d_rows`` is the parent's gather, untouched: equal.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe
from ray_tpu.ops import grouped_matmul as gm

#: the three routed cells' (choices a token, held of published experts)
#: at small sizes; the width's class: a power of two (2,048) or whole
#: 128-lane registers that are none (2,688 = 21 x 128)
CELLS = {
    "trinity-mini": (8, 16, 128, 256),
    "kanana-2-30b-a3b": (6, 16, 128, 256),
    "nemotron-3-nano-30b-a3b": (6, 8, 128, 384),
}
ULP = float(np.finfo(np.float32).eps)


def _case(cell, tokens=128, dtype=jnp.bfloat16, idx=None, width=None):
    """A plan, the rows of ``x`` under it with every row that holds no
    pair NaN (dead tiles hold whatever the memory held), weights and a
    cotangent."""
    k, held, experts, cell_width = CELLS[cell]
    width = width or cell_width
    key = jax.random.PRNGKey(len(cell))
    if idx is None:
        idx = jax.vmap(lambda q: jax.random.permutation(q, experts)[:k])(
            jax.random.split(key, tokens)).astype(jnp.int32)
    plan = gm.plan_rows(idx, 0, held, block_m=8)
    w = jax.random.uniform(jax.random.fold_in(key, 1), idx.shape,
                           jnp.float32, 0.1, 1.0)
    x = jax.random.normal(jax.random.fold_in(key, 2),
                          (idx.shape[0], width), jnp.float32).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 3), x.shape, jnp.float32)
    rows = jnp.where(plan.row_valid[:, None], gm.dispatch(x, plan),
                     jnp.nan).astype(dtype)
    return plan, rows, w, x, g


def _close(got, want, ulps=2):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=ulps * ULP,
                               atol=ulps * ULP * np.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_combine_walks_to_the_gathers_sum(cell, dtype):
    plan, rows, w, _, _ = _case(cell, dtype=dtype)
    assert 0 < int(plan.pair_valid.sum()) < plan.pair_valid.size
    assert gm._walks(rows, plan, True) and not gm._walks(rows, plan, None)
    _close(gm.combine(rows, w, plan, interpret=True),
           gm.combine(rows, w, plan))
    # a weight of one is no multiply: the plain walk, to the last bit
    np.testing.assert_array_equal(
        np.asarray(gm._token_sums(rows, plan, None, jnp.float32, True)),
        np.asarray(gm._gather_sum(rows, plan, None)))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_combines_backward_walks_for_d_w_and_gathers_d_rows(cell):
    plan, rows, w, _, g = _case(cell)

    def grads(interpret):
        return jax.vjp(lambda r, w: gm.combine(r, w, plan,
                                               interpret=interpret),
                       rows, w)[1](g)

    (d_rows, d_w), (want_rows, want_w) = grads(True), grads(None)
    assert d_rows.dtype == rows.dtype and d_w.dtype == w.dtype
    np.testing.assert_array_equal(np.asarray(d_rows, np.float32),
                                  np.asarray(want_rows, np.float32))
    assert np.isfinite(np.asarray(d_w)).all()
    np.testing.assert_allclose(
        np.asarray(d_w), np.asarray(want_w), rtol=1e-5,
        atol=1e-5 * float(jnp.abs(want_w).max()))
    assert (np.asarray(d_w)[~np.asarray(plan.pair_valid)] == 0).all()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sums_rounded_once_and_a_cotangent_of_two_bytes(cell):
    """As ``RoutedExperts`` calls it: the sums leave the kernel in the
    model's dtype, rounded once from float32 (where the float32 sums are
    2 ulp apart that is at most one step of bfloat16), and the
    cotangent comes back in it: ``d_w`` reads its rows as the words
    they lie in, ``d_rows`` gathers it as it is."""
    plan, rows, w, _, g = _case(cell)
    g = g.astype(jnp.bfloat16)

    def both(interpret):
        out, vjp = jax.vjp(lambda r, w: gm.combine(
            r, w, plan, dtype=jnp.bfloat16, interpret=interpret), rows, w)
        return (out, *vjp(g))

    (out, d_rows, d_w), (want, want_rows, want_w) = both(True), both(None)
    assert out.dtype == want.dtype == jnp.bfloat16
    got32, want32 = (np.asarray(a, np.float32) for a in (out, want))
    assert np.isfinite(got32).all()
    assert (np.abs(got32 - want32) <= 2.0 ** -7 * np.abs(want32)).all()
    assert (got32 == want32).mean() > 0.999
    np.testing.assert_array_equal(np.asarray(d_rows, np.float32),
                                  np.asarray(want_rows, np.float32))
    np.testing.assert_allclose(
        np.asarray(d_w), np.asarray(want_w), rtol=1e-5,
        atol=1e-5 * float(jnp.abs(want_w).max()))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_dispatchs_backward_walks_to_the_last_bit(cell):
    plan, rows, _, x, _ = _case(cell)

    def d_x(interpret):
        return jax.vjp(lambda x: gm.dispatch(x, plan, interpret=interpret),
                       x)[1](rows)[0]

    got, want = d_x(True), d_x(None)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_token_all_of_whose_pairs_land_and_one_none_of_whose_do(cell):
    """The worst case the buffer is sized for, a token at a time: token
    0 chose ``k`` held experts, token 1 none; the rest as they fell."""
    k, held, experts, _ = CELLS[cell]
    key = jax.random.PRNGKey(7)
    idx = jax.vmap(lambda q: jax.random.permutation(q, experts)[:k])(
        jax.random.split(key, 128)).astype(jnp.int32)
    idx = idx.at[0].set(jnp.arange(k)[::-1]) \
        .at[1].set(held + jnp.arange(k))
    plan, rows, w, _, g = _case(cell, idx=idx)
    assert bool(plan.pair_valid[0].all()) and not bool(
        plan.pair_valid[1].any())
    got = gm.combine(rows, w, plan, interpret=True)
    _close(got, gm.combine(rows, w, plan))
    assert float(jnp.abs(got[1]).max()) == 0.0
    d_w = gm._pair_dots(rows, plan, g, True)
    assert float(jnp.abs(d_w[1]).max()) == 0.0
    assert float(jnp.abs(d_w[0]).min()) > 0.0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_pair_lands_and_the_ring_goes_round(cell):
    """Every token's every choice held here: ``tokens x k`` landed pairs
    a tile, many times the groups the ring holds."""
    k, _, _, _ = CELLS[cell]
    idx = jax.vmap(lambda q: jax.random.permutation(q, k))(
        jax.random.split(jax.random.PRNGKey(3), 128)).astype(jnp.int32)
    key = jax.random.PRNGKey(5)
    plan = gm.plan_rows(idx, 0, k, block_m=8)
    assert bool(plan.pair_valid.all()) and 128 * k > 4 * gm.WALK_RING
    rows = jax.random.normal(key, (plan.row_pair.shape[0], 256),
                             jnp.float32).astype(jnp.bfloat16)
    w = jax.random.uniform(jax.random.fold_in(key, 1), idx.shape)
    _close(gm.combine(rows, w, plan, interpret=True),
           gm.combine(rows, w, plan))
    np.testing.assert_array_equal(
        np.asarray(gm._token_sums(rows, plan, None, jnp.float32, True)),
        np.asarray(gm._gather_sum(rows, plan, None)))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_plan_with_no_landed_pair_sums_to_zero(cell):
    k, held, experts, _ = CELLS[cell]
    idx = held + jax.vmap(
        lambda q: jax.random.permutation(q, experts - held)[:k])(
        jax.random.split(jax.random.PRNGKey(2), 128)).astype(jnp.int32)
    plan, rows, w, _, g = _case(cell, idx=idx)
    assert int(plan.n_live[0]) == 0 and not bool(plan.pair_valid.any())
    assert np.isnan(np.asarray(rows, np.float32)).all()
    assert float(jnp.abs(gm.combine(rows, w, plan,
                                    interpret=True)).max()) == 0.0
    assert float(jnp.abs(gm._pair_dots(rows, plan, g, True)).max()) == 0.0


def test_a_width_that_is_no_whole_lane_registers_and_a_tile_of_all_tokens():
    """1,856 = 14.5 x 128: the walk takes a row's whole width, so no
    tile has to divide it; 40 tokens are no 128: one tile of them all."""
    plan, rows, w, x, g = _case("nemotron-3-nano-30b-a3b", tokens=40,
                                width=1856)
    assert gm.walk_tile(40, 1856) == 40
    _close(gm.combine(rows, w, plan, interpret=True),
           gm.combine(rows, w, plan))
    np.testing.assert_allclose(
        np.asarray(gm._pair_dots(rows, plan, g, True)),
        np.asarray(gm._gather_dots(rows, plan, g)), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("tokens,width,tile", [
    (8192, 2048, 256), (16384, 2048, 256), (8192, 2688, 128),
    (8192, 1 << 16, None), (64, 32, 64), (4100, 2048, None)])
def test_the_tile_follows_from_the_shapes(tokens, width, tile):
    """Whole 128-lane registers of the tables, a result block of at most
    2 MiB; where no tile is to be had the gathers stay."""
    assert gm.walk_tile(tokens, width) == tile
    plan = gm.plan_rows(jnp.zeros((tokens, 2), jnp.int32), 0, 1, block_m=8)
    rows = jax.ShapeDtypeStruct((plan.row_pair.shape[0], width),
                                jnp.bfloat16)
    assert gm._walks(rows, plan, False) == (tile is not None)
    assert not gm._walks(rows, plan, None)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_layer_under_checkpoint_has_the_gathers_gradients(
        cell, monkeypatch):
    """``jax.grad`` through one ``RoutedExperts`` under
    ``jax.checkpoint`` (forward, recomputed forward, backward): every
    kernel through the interpreter against the path off the TPU."""
    monkeypatch.setattr(afmoe, "BLOCK_ROWS", 8)
    k, held, _, _ = CELLS[cell]
    cfg = afmoe.AFMoEConfig.tiny(dtype=jnp.float32, num_experts=32,
                                 top_k=k, experts_held=(4, held),
                                 embed_dim=128, expert_dim=32)
    layer = afmoe.RoutedExperts(cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.embed_dim))
    cot = jax.random.normal(jax.random.PRNGKey(2), h.shape)
    params = layer.init(jax.random.PRNGKey(3), h)["params"]
    from flax.core import meta
    params = meta.unbox(params)

    def grads(kernels):
        monkeypatch.setattr(gm, "kernel_mode", lambda interpret: kernels)
        return jax.grad(lambda p, h: (jax.checkpoint(
            lambda p, h: layer.apply({"params": p}, h))(p, h) * cot).sum(),
            argnums=(0, 1))(params, h)

    got, want = grads(True), grads(None)
    for (path, a), (_, b) in zip(jax.tree.leaves_with_path(got),
                                 jax.tree.leaves_with_path(want)):
        assert np.isfinite(np.asarray(a)).all(), path
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4,
            atol=2e-5 * float(jnp.abs(b).max() + 1e-9), err_msg=str(path))


def test_the_walks_jaxpr_holds_a_kernel_a_sum_and_no_gather_of_rows():
    """On a TPU (``interpret=False``) each of the three sums is one
    kernel call and gathers nothing; ``combine``'s backward keeps the
    one gather ``rows <- tokens`` under its four reaches for ``d_rows``."""
    plan, rows, w, x, g = _case("trinity-mini")
    f32 = jnp.dtype(jnp.float32)
    text = str(jax.make_jaxpr(functools.partial(
        gm.combine, plan=plan, interpret=False))(rows, w))
    assert text.count("landed_rows_sum") == 1 and "gather" not in text
    text = str(jax.make_jaxpr(lambda g: gm._dispatch_bwd(False, plan, g))(
        rows))
    assert text.count("landed_rows_sum") == 1 and "gather" not in text
    text = str(jax.make_jaxpr(lambda g: gm._combine_bwd(
        f32, False, (rows, w, plan), g))(g))
    assert text.count("landed_rows_dot") == 1
    assert "landed_rows_sum" not in text
    # a reach gathers a row for its pair, and the pair's weight
    assert text.count("gather[") == 2 * len(gm.REACHES)
