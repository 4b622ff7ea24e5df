"""A tile that the mask cuts is worked in blocks over what each block
can see (``_cut_parts``): the diagonal tile in row-blocks against the
keys to their left, the tile on a whole-tile window's edge mirrored,
dK/dV in column-blocks; four blocks in the backward kernels, eight in
the forward, which takes them stage by stage (one row maximum and one
row sum over the tile's rows).  Every kernel of every family through the
Pallas interpreter against ``_attention_reference``; one block traces
the parent's kernels; and what the ``ops:flash.plan`` span counts
against a count made from the mask."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core import telemetry

fa = importlib.import_module("ray_tpu.ops.flash_attention")


@pytest.fixture
def cut_constants(monkeypatch):
    """``set(CUT_MIN=16)``: the module's constants for one test.  The
    functions that build the kernel calls are jitted and key on shapes,
    so what they traced under other constants is dropped first, and
    again when the constants go back."""
    def set_(**values):
        for name, value in values.items():
            monkeypatch.setattr(fa, name, value)
        jax.clear_caches()

    yield set_
    monkeypatch.undo()
    jax.clear_caches()


@pytest.fixture
def small_blocks(cut_constants):
    """Blocks of 16 rows, so that tiles of 64 are cut in four: the
    interpreter has no vregs to align to."""
    cut_constants(CUT_MIN=16)


def _operands(seq, heads, kv_heads, dim, dim_v=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    dim_v = dim_v or dim
    q = jax.random.normal(ks[0], (2, seq, heads, dim), jnp.float32)
    k = jax.random.normal(ks[1], (2, seq, kv_heads, dim), jnp.float32)
    v = jax.random.normal(ks[2], (2, seq, kv_heads, dim_v), jnp.float32)
    g = jax.random.normal(ks[3], (2, seq, heads, dim_v), jnp.float32)
    return q, k, v, g


def _assert_close(got, want, names):
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def _against_the_reference(family, heads, kv, dim, seq, block_q, block_k,
                           window):
    q, k, v, g = _operands(seq, heads, kv, dim)
    scale = dim ** -0.5

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  interpret=True, native=family == "native",
                                  block_q=block_q, block_k=block_k)

    def plain(q, k, v):
        return fa._attention_reference(q, k, v, True, scale, window)

    out, vjp = jax.vjp(kernel, q, k, v)
    ref, ref_vjp = jax.vjp(plain, q, k, v)
    _assert_close((out, *vjp(g)), (ref, *ref_vjp(g)),
                  ("out", "dq", "dk", "dv"))


#: family, heads, K/V heads, head size
SHAPES = {
    "native-2-a-slab": ("native", 2, 2, 64),
    "native-grouped": ("native", 4, 2, 128),
    "head-major": ("head_major", 3, 3, 64),
    "head-major-grouped": ("head_major", 4, 2, 32),
    "head-major-wide": ("head_major", 2, 1, 128),
}
#: sequence (tiles of 64), window -> (the diagonal tile is cut in
#: blocks, the window's edge tile is)
MASKS = {
    "1-tile": (64, None, (True, False)),
    "2-tiles": (128, None, (True, False)),
    "4-tiles": (256, None, (True, False)),
    "window-1-tile": (256, 64, (True, True)),
    "window-2-tiles": (256, 128, (True, True)),
    "window-100": (256, 100, (True, False)),   # its edge: the whole mask
    "window-32": (256, 32, (False, False)),    # shorter than a tile
}
#: the same on tiles of 128: eight blocks of 16 rows in the forward
MASKS_128 = {
    "2-tiles": (256, None), "window-1-tile": (384, 128),
    "window-200": (384, 200),
}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_cut_tiles_match_the_reference(small_blocks, shape, mask):
    family, heads, kv, dim = SHAPES[shape]
    seq, window, kinds = MASKS[mask]
    # eight blocks of a tile of 64 would be 8 rows each: four, forward too
    assert fa._cut_kinds(64, 64, window) == (4, *kinds)
    assert fa._cut_kinds(64, 64, window, forward=True) == (4, *kinds)
    _against_the_reference(family, heads, kv, dim, seq, 64, 64, window)


@pytest.mark.parametrize("mask", sorted(MASKS_128))
@pytest.mark.parametrize("shape", ["native-2-a-slab", "native-grouped",
                                   "head-major"])
def test_the_forward_takes_eight_blocks_together(small_blocks, shape, mask):
    family, heads, kv, dim = SHAPES[shape]
    seq, window = MASKS_128[mask]
    kinds = (True, window is not None and window % 128 == 0)
    assert fa._cut_kinds(128, 128, window) == (4, *kinds)
    assert fa._cut_kinds(128, 128, window, forward=True) == (8, *kinds)
    _against_the_reference(family, heads, kv, dim, seq, 128, 128, window)


@pytest.mark.parametrize("family,heads,dim,seq,block,window,blocks", [
    ("native", 2, 64, 512, 256, 256, (2, 2)),     # sub 128: two blocks
    ("native", 1, 128, 512, 512, None, (4, 4)),   # one tile, cut in four
    ("head_major", 1, 64, 1024, 512, 512, (4, 4)),
    ("native", 2, 64, 1024, 1024, None, (4, 8)),  # GPT-2's one tile
])
def test_cut_tiles_at_the_width_the_chip_is_given(family, heads, dim, seq,
                                                  block, window, blocks):
    """No patched constant: blocks of whole 128-row vregs, as many as
    the tile has room for (backward, forward)."""
    kinds = (True, window is not None)
    assert fa._cut_kinds(block, block, window) == (blocks[0], *kinds)
    assert fa._cut_kinds(block, block, window, forward=True) == (
        blocks[1], *kinds)
    _against_the_reference(family, heads, heads, dim, seq, block, block,
                           window)


@pytest.mark.parametrize("family", ["native", "head_major"])
@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64)])
def test_unequal_blocks_take_the_whole_tile_mask(small_blocks, family,
                                                 block_q, block_k):
    assert fa._cut_kinds(block_q, block_k, None) == (1, False, False)
    assert fa._cut_kinds(block_q, block_k, 128) == (1, False, False)
    _against_the_reference(family, 2, 2, 64, 256, block_q, block_k, 128)


@pytest.mark.parametrize("nope,rope,dim_v", [(16, 8, 16), (96, 32, 64)],
                         ids=["narrow", "wide"])
@pytest.mark.parametrize("seq,block_q,block_k", [
    (64, 64, 64), (128, 64, 64), (256, 64, 64), (256, 64, 128),
    (256, 128, 128)])
def test_cut_tiles_of_the_two_part_key_match_the_reference(
        small_blocks, seq, block_q, block_k, nope, rope, dim_v):
    heads = 2
    q, k, v, g = _operands(seq, heads, heads, nope, dim_v)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    q = jax.random.normal(ks[0], (2, seq, heads, nope + rope), jnp.float32)
    r = jax.random.normal(ks[1], (2, seq, 1, rope), jnp.float32)
    scale = (nope + rope) ** -0.5
    assert (fa._cut_blocks(block_q, block_k),
            fa._cut_blocks(block_q, block_k, forward=True)) == {
                (64, 64): (4, 4), (128, 128): (4, 8),
                (64, 128): (1, 1)}[block_q, block_k]

    def kernel(q, k, r, v):
        return fa.flash_attention(q, k, v, k_rope=r, interpret=True,
                                  block_q=block_q, block_k=block_k)

    def plain(q, k, r, v):
        whole = jnp.concatenate(
            [k, jnp.broadcast_to(r, (*k.shape[:3], rope))], -1)
        return fa._attention_reference(q, whole, v, True, scale)

    out, vjp = jax.vjp(kernel, q, k, r, v)
    ref, ref_vjp = jax.vjp(plain, q, k, r, v)
    _assert_close((out, *vjp(g)), (ref, *ref_vjp(g)),
                  ("out", "dq", "dk", "dk_rope", "dv"))


def test_the_parts_of_a_cut_tile_cover_its_kept_pairs_once():
    """Rows against columns of every part, and the corner's mask where
    the part has one, laid over the tile: the kept triangle exactly, for
    both walks and both cuts."""
    block, n = 64, 4
    i, j = np.indices((block, block))
    for edge, want in ((False, i >= j), (True, i < j)):
        for walk in ("q", "k"):
            seen = np.zeros((block, block), int)
            for part in fa._cut_parts(edge, walk, block, n):
                rows, cols = part.rows, part.cols
                assert (part.n_rows, part.n_cols) == (
                    rows.stop - rows.start, cols.stop - cols.start)
                s = jnp.zeros((part.n_rows, part.n_cols), jnp.float32)
                kept = np.asarray(fa._hide(s, part.keep(), part)) == 0.0
                seen[rows, cols] += kept
            np.testing.assert_array_equal(seen, want.astype(int))


def _kernel_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _kernel_calls(sub)


#: sha256 over the three kernels (grid mapping and body, as jaxpr text)
#: of d(sum(flash_attention(...)))/d(operands) at the cells' calls,
#: traced from the parent's file (commit 9bc562e), where a cut tile was
#: multiplied whole under its mask
PARENT = {
    "gpt2-large": ((8, 1024, 20, 64), (8, 1024, 20, 64), None,
        "8ac4ddf96eeeffcaee78d82b4a24cba6003f5b1966624ee702fa77d00381e90e"),
    "trinity-mini.sliding": ((1, 8192, 32, 128), (1, 8192, 4, 128), 2048,
        "88a375e5007ec0dbbe9fd07a42cad31faf7e297ac76a5501f5b8bba9724a7ff3"),
    "trinity-mini.full": ((1, 8192, 32, 128), (1, 8192, 4, 128), None,
        "3d7f520c4085ae85bd446f647fef28bcbc5668ac73f27ab49d479d2b6140076c"),
    "kanana-2-30b-a3b": ((1, 16384, 32, 192), (1, 16384, 32, 128), "latent",
        "06e3f50b06e87fc361b9181c1fea868a5652c66664b8a52d5336eea860cc5f3d"),
    # the head-major kernels mask only the tiles the mask cuts since
    # this PR (``_causal_dispatch``) and their forward keeps its
    # statistics as columns, so one block is no longer the parent's
    # 8f03b255...: pinned as it is now
    "gpt2-xl": ((8, 1024, 25, 64), (8, 1024, 25, 64), None,
        "b218c62cc5dcc1827103257c0708d009da0908b56773ef46eb1e5ceddc18ffa3"),
}


def _kernels_digest(q_shape, kv_shape, window):
    q = jnp.zeros(q_shape, jnp.bfloat16)
    kv = jnp.zeros(kv_shape, jnp.bfloat16)
    if window == "latent":
        r = jnp.zeros((*kv_shape[:2], 1, q_shape[3] - kv_shape[3]),
                      jnp.bfloat16)

        def loss(q, k, r, v):
            return fa.flash_attention(
                q, k, v, k_rope=r,
                interpret=False).astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
            q, kv, r, kv)
    else:
        def loss(q, k, v):
            return fa.flash_attention(
                q, k, v, causal=True, interpret=False,
                window=window).astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    calls = list(_kernel_calls(jaxpr.jaxpr))
    assert len(calls) == 3  # forward, dK/dV, dQ
    text = "\n".join(f"{e.params['grid_mapping']}\n{e.params['jaxpr']}"
                     for e in calls)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("call", sorted(PARENT))
def test_one_block_traces_the_kernels_as_they_were(call, cut_constants):
    """One block is no second path: the tile bodies, given the whole
    tile as their one part, trace the parent's kernels."""
    q_shape, kv_shape, window, digest = PARENT[call]
    assert _kernels_digest(q_shape, kv_shape, window) != digest
    cut_constants(CUT_BLOCKS=1, CUT_BLOCKS_FORWARD=1)
    assert _kernels_digest(q_shape, kv_shape, window) == digest


def _count_from_the_mask(seq, block, window, n):
    """(tiles live, tiles cut, pairs worked, pairs visible) for one
    (batch, head), from the ``[seq, seq]`` mask itself: a tile is cut in
    blocks when what it keeps is a whole triangle, and a row-block then
    meets the ``sub``-aligned span of keys its rows see."""
    i, j = np.indices((seq, seq))
    mask = i >= j
    if window is not None:
        mask &= i - j < window
    sub = block // n
    ti, tj = np.indices((block, block))
    live = cut = worked = 0
    for q0 in range(0, seq, block):
        for k0 in range(0, seq, block):
            tile = mask[q0:q0 + block, k0:k0 + block]
            if not tile.any():
                continue
            live += 1
            if n > 1 and (np.array_equal(tile, ti >= tj)
                          or np.array_equal(tile, ti < tj)):
                cut += 1
                for r0 in range(0, block, sub):
                    cols = np.flatnonzero(tile[r0:r0 + sub].any(axis=0))
                    lo = cols.min() // sub * sub
                    hi = -(-(cols.max() + 1) // sub) * sub
                    worked += sub * (hi - lo)
            else:
                worked += block * block
    return live, cut, worked, int(mask.sum())


#: family, q's head width, sequence, tile, window (the two-part key
#: takes none)
PLANS = [(family, width, seq, block, window)
         for family, width in (("native", 64), ("native", 128),
                               ("head_major", 64), ("latent", 96))
         for seq, block, window in (
             (64, 64, None), (256, 64, None), (256, 64, 64), (256, 64, 128),
             (256, 64, 100), (256, 64, 32), (256, 128, None), (256, 32, 96))
         if window is None or family != "latent"]


@pytest.mark.parametrize("family,width,seq,block,window", PLANS)
def test_the_plan_span_counts_what_the_mask_says(small_blocks, family,
                                                 width, seq, block, window):
    heads = 4 if family == "native" else 3
    q = jnp.zeros((2, seq, heads, width), jnp.float32)
    kv = q
    extra = {}
    if family == "latent":
        kv = jnp.zeros((2, seq, heads, width - 32), jnp.float32)
        extra["k_rope"] = jnp.zeros((2, seq, 1, 32), jnp.float32)
    else:
        extra.update(window=window, native=family == "native")
    telemetry.drain_spans("test")
    jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=True, block_q=block, block_k=block, **extra))(
            q, kv, kv)
    rows = [r for r in telemetry.drain_spans("test")
            if (r["cat"], r["name"]) == ("ops", "flash.plan")]
    assert len(rows) == 1  # once a traced call
    n = fa._cut_blocks(block, block)
    n_fwd = fa._cut_blocks(block, block, forward=True)
    assert (n, n_fwd) == (min(4, block // 16), min(8, block // 16))
    live, cut, worked, visible = _count_from_the_mask(seq, block, window, n)
    *_, worked_fwd, _ = _count_from_the_mask(seq, block, window, n_fwd)
    assert rows[0]["args"] == {
        "family": family, "heads": heads, "width": width, "seq": seq,
        "block": block, "window": window or 0, "sub": block // n,
        "sub_forward": block // n_fwd, "tiles_live": live, "tiles_cut": cut,
        "pairs_worked": worked, "pairs_worked_forward": worked_fwd,
        "pairs_visible": visible}


def test_the_plan_span_at_the_cells_shapes():
    """What the four steady cells' calls work for what counts, in the
    backward kernels and in the forward: GPT-2's one tile went from half
    to four fifths in dK/dV and dQ and to eight ninths in the forward."""
    shapes = {
        "gpt2-large": ((8, 1024, 20, 64), (8, 1024, 20, 64), {}),
        "gpt2-xl": ((8, 1024, 25, 64), (8, 1024, 25, 64), {}),
        "trinity.sliding": ((1, 8192, 32, 128), (1, 8192, 4, 128),
                            {"window": 2048}),
        "trinity.full": ((1, 8192, 32, 128), (1, 8192, 4, 128), {}),
    }
    telemetry.drain_spans("test")
    for q_shape, kv_shape, extra in shapes.values():
        q = jnp.zeros(q_shape, jnp.bfloat16)
        kv = jnp.zeros(kv_shape, jnp.bfloat16)
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
            q, k, v, interpret=False, **extra))(q, kv, kv)
    q = jnp.zeros((1, 16384, 32, 192), jnp.bfloat16)
    kv = jnp.zeros((1, 16384, 32, 128), jnp.bfloat16)
    jax.make_jaxpr(lambda q, k, r, v: fa.flash_attention(
        q, k, v, k_rope=r, interpret=False))(
            q, kv, jnp.zeros((1, 16384, 1, 64), jnp.bfloat16), kv)
    rows = [r["args"] for r in telemetry.drain_spans("test")
            if r["name"] == "flash.plan"]
    got = [(r["family"], r["tiles_live"], r["tiles_cut"], r["sub"],
            round(r["pairs_visible"] / r["pairs_worked"], 3),
            r["sub_forward"],
            round(r["pairs_visible"] / r["pairs_worked_forward"], 3))
           for r in rows]
    assert got == [("native", 1, 1, 256, 0.801, 128, 0.89),
                   ("head_major", 1, 1, 256, 0.801, 128, 0.89),
                   ("native", 21, 14, 256, 0.889, 128, 0.941),
                   ("native", 36, 8, 256, 0.970, 128, 0.985),
                   ("latent", 136, 16, 256, 0.985, 128, 0.992)]
