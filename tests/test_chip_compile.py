"""The chip's compiler, asked from the CPU sandbox.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``): what it
refuses here — a kernel over its fast-memory limit, an unaligned slice,
a Mosaic kernel GSPMD cannot partition, a step that does not fit 16 GB —
it would refuse on the chip.  Nothing runs, so these say nothing about
results or times.

All such compiles live in THIS file, and the topology is described
inside a module-scoped fixture: only one process may load libtpu, so a
second file (another xdist worker) or an import-time call would make
the suite's workers disagree.  The kernel entry points are called
directly with ``interpret=False`` — the public wrappers ask
``jax.default_backend()``, which is the CPU here, and would take the
jnp reference.
"""

import dataclasses
import importlib
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

fa = importlib.import_module("ray_tpu.ops.flash_attention")
fused = importlib.import_module("ray_tpu.ops.fused")

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attn_grads(family: str):
    """d(sum(attention))/d(q, k, v): the forward and both backward
    kernels of one family, default 1024 blocks, compiled not interpreted."""
    def grads(q, k, v):
        scale = q.shape[-1] ** -0.5

        def loss(q, k, v):
            kernels = fa._flash_nl if family == "native" else fa._flash
            out = kernels(q, k, v, True, scale, fa.DEFAULT_BLOCK,
                          fa.DEFAULT_BLOCK, False)
            return out.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return grads


@pytest.mark.parametrize("family", ["native", "head_major"])
@pytest.mark.parametrize("shape", [(32, 1024, 12, 64), (1, 32768, 12, 64)],
                         ids=["gpt2_batch32", "long_context_32k"])
def test_flash_kernels_compile_for_v5e(one_chip, family, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(_attn_grads(family)).lower(x, x, x).compile()
    # forward, dK/dV and dQ: three Mosaic kernels, none interpreted
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("family,heads", [("native", 20),
                                          ("head_major", 25)],
                         ids=["gpt2-large", "gpt2-xl"])
def test_cut_tile_flash_compiles_at_gpt2_cells_shapes(one_chip, family,
                                                      heads):
    """8 sequences of 1,024 a device, 20 heads of 64 (two a slab) and 25
    (head-major): ONE tile a head, and the diagonal cuts it, so dK/dV
    and dQ work it in four blocks of 256 (``_cut_parts``: slices of the
    tiles in VMEM at multiples of 256 rows, scores of 256 to 1,024
    lanes) and the forward in eight of 128, stage by stage."""
    assert fa._cut_kinds(1024, 1024, None) == (4, True, False)
    assert fa._cut_kinds(1024, 1024, None, forward=True) == (8, True, False)
    x = jax.ShapeDtypeStruct((8, 1024, heads, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(_attn_grads(family)).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("window", [2048, None], ids=["sliding", "full"])
def test_windowed_grouped_head_flash_compiles_at_afmoe_shapes(one_chip,
                                                              window):
    """One sequence of 8,192, 32 query heads on 4 K/V heads of 128, as
    ``models/afmoe.py`` calls it: forward, dK/dV and dQ.  The diagonal
    tile, and the tile on the edge of a window of two tiles, are worked
    in blocks: four in the backward kernels, eight in the forward."""
    assert fa._cut_kinds(1024, 1024, window) == (4, True, window is not None)
    assert fa._cut_kinds(1024, 1024, window, forward=True)[0] == 8
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16,
                              sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: fa._flash_nl(
            q, k, v, True, 128 ** -0.5, 1024, 1024, False, window
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_latent_attention_flash_compiles_at_deepseek_v3_shapes(one_chip):
    """One sequence of 16,384, 32 heads, q/k of 128 + 64 against v of
    128, the rotary key ONE head, as ``models/deepseek_v3.py`` calls it:
    forward, dK/dV and dQ at the default 1024 blocks, the 16 diagonal
    tiles a head worked in blocks; and what lies in HBM around them: v
    and the output 128 wide, the rotary key never 32 times."""
    assert fa._cut_blocks(1024, 1024) == 4
    assert fa._cut_blocks(1024, 1024, forward=True) == 8
    def shape(heads, width):
        return jax.ShapeDtypeStruct((1, 16384, heads, width), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, r, v):
        return jax.grad(lambda q, k, r, v: fa._flash_mla(
            q, k, r, v, True, 192 ** -0.5, fa.DEFAULT_BLOCK,
            fa.DEFAULT_BLOCK, False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))(q, k, r, v)

    compiled = jax.jit(grads).lower(
        shape(32, 192), shape(32, 128), shape(1, 64),
        shape(32, 128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert all("bf16[1,1,16384,64]" in line for line in calls)
    assert not any("bf16[1,32,16384,64]" in line for line in calls)
    assert any(re.search(r"= \(bf16\[1,32,16384,128\]\S*, "
                         r"f32\[1,32,16384,1\]", line) for line in calls)


def test_latent_attention_flash_compiles_at_xing_shapes_with_its_scale(
        one_chip):
    """One sequence of 2,048 (and of 4,096, the model's own length), 32
    heads, q/k of 128 + 64 against v of 128, the rotary key ONE head, the
    softmax scale GIVEN (``192^-0.5 x 2.00474``: YaRN's ``mscale``
    squared), as ``models/deepseek_v3.py`` calls it for Xing4.0:
    forward, dK/dV and dQ."""
    ds = importlib.import_module("ray_tpu.models.deepseek_v3")
    scale = ds.DeepseekV3Config.xing4_0_29b_a4b_share().softmax_scale
    assert scale == pytest.approx(192 ** -0.5 * 2.00474, rel=1e-5)

    def grads(q, k, r, v):
        return jax.grad(lambda q, k, r, v: fa._flash_mla(
            q, k, r, v, True, scale, fa.DEFAULT_BLOCK, fa.DEFAULT_BLOCK,
            False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))(q, k, r, v)

    for seq in (2048, 4096):
        def shape(heads, width):
            return jax.ShapeDtypeStruct((1, seq, heads, width),
                                        jnp.bfloat16, sharding=one_chip)

        text = jax.jit(grads).lower(
            shape(32, 192), shape(32, 128), shape(1, 64),
            shape(32, 128)).compile().as_text()
        assert text.count("tpu_custom_call") == 3
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and " custom-call(" in line]
        assert all(f"bf16[1,1,{seq},64]" in line for line in calls)


def test_grouped_products_compile_at_afmoe_widths(one_chip):
    """The worst-case row buffer of one sequence (8 x 8,192 pairs and a
    tile of padding for each of 16 experts) times 16 experts' matrices
    of 2048 x 1024: the forward kernel, d lhs and d rhs, at the tiles
    the rule picks (``gm.gmm_tiles``: an expert's whole matrix) under
    the VMEM it states."""
    gm = importlib.import_module("ray_tpu.ops.grouped_matmul")
    tiles = 8 * 8192 // 256 + 16

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def grads(lhs, rhs, tile_expert, n_live):
        out, vjp = jax.vjp(lambda a, b: gm._gmm(
            a, b, tile_expert, n_live, 256, None, False), lhs, rhs)
        return out, vjp(out)

    compiled = jax.jit(grads).lower(
        shape((tiles * 256, 2048)), shape((16, 2048, 1024)),
        shape((tiles,), jnp.int32), shape((1,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


#: the five routed cells: experts held, hidden x expert width, choices
#: a token, tokens a call (Mellum: the group's), matrices an expert
#: (three: gated; two: relu2)
ROUTED_CELLS = {
    "trinity-mini": (16, 2048, 1024, 8, 8192, 3),
    "kanana-2-30b-a3b": (16, 2048, 768, 6, 16384, 3),
    "nemotron-3-nano-30b-a3b": (8, 2688, 1856, 6, 8192, 2),
    "mellum2-12b-a2.5b": (16, 2304, 896, 8, 16384, 3),
    "xing4.0-29b-a4b": (8, 3584, 1024, 4, 2048, 3),
}



@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_fused_expert_products_compile_at_the_cells_widths(one_chip, cell):
    """An expert's products over the worst-case row buffer of one call,
    as ``RoutedExperts`` runs them: forward a kernel a product, the down
    product forming the activation on its lhs tile (1856 wide: the whole
    width, contracted); backward the down product's ``d rhs`` with the
    same prologue, its ``d lhs`` ending in the activation's derivative
    (two results, gated), and for gate and up a ``d rhs`` and a ``d
    lhs``, the second adding the first's tile in place.  Every product
    at the tiles the rule picks from the shapes (``gm.product_tiles``:
    an expert's whole matrix a block, 1856 as it lies; Nemotron's ``d
    rhs`` in three blocks), compiled under the VMEM limit the kernels
    state: what Mosaic refuses for fast memory it refuses here."""
    gm = importlib.import_module("ray_tpu.ops.grouped_matmul")
    held, hidden, width, top_k, tokens, matrices = ROUTED_CELLS[cell]
    tiles = top_k * tokens // 256 + held

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def grads(rows, weights, tile_expert, n_live):
        out, vjp = jax.vjp(lambda r, w: gm._experts(
            r, w, tile_expert, n_live, 256, None, False), rows, weights)
        return out, vjp(out)

    weights = (shape((held, hidden, width)),) * (matrices - 1) \
        + (shape((held, width, hidden)),)
    text = jax.jit(grads).lower(
        shape((tiles * 256, hidden)), weights, shape((tiles,), jnp.int32),
        shape((1,), jnp.int32)).compile().as_text()
    calls = [re.search(r"grouped_matmul[a-z_]*", line.split(" = ")[0]
                       ).group().rstrip("_")
             for line in _kernel_calls(text)]
    into = matrices - 1
    assert sorted(calls) == sorted(
        ["grouped_matmul"] * into + ["grouped_matmul_act"]
        + ["grouped_matmul_drhs_act", "grouped_matmul_t_act"]
        + ["grouped_matmul_drhs"] * into + ["grouped_matmul_t"]
        + ["grouped_matmul_t_add"] * (into - 1))
    # the sum of the two d lhs is the second kernel's own result: XLA
    # adds no row buffers.  (Xing's buffer of 10,240 rows is small enough
    # for XLA to lay the down product's result in its faster memory
    # space, ``S(1)``; being this function's output it is then moved out
    # to HBM by an asynchronous copy of the compiler's own, no pass of
    # the program's.  A copy within one space is still caught.)
    assert _not_moves_between_memory_spaces(
        _passes_over_the_row_buffer(text, tiles * 256), text) == []


#: the five cells' routers: experts published, the scores' function,
#: tokens a chip routes a call (Mellum: its own quarter of the group's)
ROUTERS = {
    "trinity-mini": (128, "sigmoid", 8192),
    "kanana-2-30b-a3b": (128, "sigmoid", 16384),
    "nemotron-3-nano-30b-a3b": (128, "sigmoid", 8192),
    "mellum2-12b-a2.5b": (64, "softmax", 4096),
    "xing4.0-29b-a4b": (64, "sigmoid", 2048),
}


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_a_routers_weights_are_made_apart_from_their_sum(one_chip, cell):
    """``afmoe.route`` for a described v5e at the cells' shapes: the
    scores at the ids (``afmoe._scores_at``: a select against the ids and
    a sum over the experts) are ONE fusion's one result ``[T, k]``, and
    the sum over a token's chosen that normalises them is another's.
    Fused into one reduce (which is what this compiler does without the
    barrier ``_scores_at`` ends in: a second result ``[T]`` of the same
    fusion), a token's weights are added in another order than after
    ``top_k``, and on the chip a quarter of them leave the parent's last
    bit and the first loss with them (PERF.md section 6, PR 53).  The
    CPU's tests cannot see that; this holds the compiled text to it."""
    import types

    afmoe = importlib.import_module("ray_tpu.models.afmoe")
    _, hidden, _, top_k, _, _ = ROUTED_CELLS[cell]
    experts, score, tokens = ROUTERS[cell]
    cfg = types.SimpleNamespace(router_dtype=jnp.float32, top_k=top_k,
                                route_scale=2.5, score_func=score)
    text = jax.jit(lambda h, w: afmoe.route(cfg, h, w)[:2]).lower(
        jax.ShapeDtypeStruct((tokens, hidden), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((hidden, experts), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    results = [line.split(" fusion(")[0].split(" = ", 1)[1]
               for line in text.splitlines()
               if " fusion(" in line and "reduce_sum" in line
               and f"f32[{tokens},{top_k}]" in line.split(" fusion(")[0]]
    assert results and all(r.startswith("f32[") for r in results), results


#: tokens a step of the walks, by the hidden width
WALK_TILES = {2048: 256, 2304: 128, 2688: 128, 3584: 128}


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_the_walks_compile_at_the_cells_shapes(one_chip, cell):
    """The routed layer's three sums over tokens as ``RoutedExperts``
    runs them on a TPU: ``combine`` (the weighted walk), its backward
    (``d_w``: the walk's dots; ``d_rows`` stays a gather under a reach)
    and ``dispatch``'s backward (the plain walk), over the worst-case
    row buffer of one call.  Three kernel calls, each with a result of
    the TOKENS' rows: the benchmark's readers file such a call with the
    norms, not with the grouped products."""
    gm = importlib.import_module("ray_tpu.ops.grouped_matmul")
    held, hidden, _, top_k, tokens, _ = ROUTED_CELLS[cell]

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    plan = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda i: gm.plan_rows(i, 0, held, block_m=256),
                       shape((tokens, top_k), jnp.int32)))
    rows = plan.row_pair.shape[0]
    assert rows == top_k * tokens + held * 256
    assert gm.walk_tile(tokens, hidden) == WALK_TILES[hidden]

    def sums(r, w, p, x):   # the sums leave in the model's dtype
        out, vjp = jax.vjp(lambda r, w: gm._combine(
            r, w, p, jnp.dtype(jnp.bfloat16), False), r, w)
        return out, vjp(out), jax.vjp(
            lambda x: gm._dispatch(x, p, False), x)[1](r)

    text = jax.jit(sums).lower(
        shape((rows, hidden)), shape((tokens, top_k), jnp.float32), plan,
        shape((tokens, hidden))).compile().as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 3
    assert sum("landed_rows_sum" in _called(line) for line in calls) == 2
    assert sum("landed_rows_dot" in _called(line) for line in calls) == 1
    assert _kernel_results_of_rows(calls, rows) == []


def test_fused_rmsnorm_compiles_at_llama_width(one_chip):
    x = jax.ShapeDtypeStruct((8, 2048, 4096), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda x, w: fused._rmsnorm(x, w, 1e-6, False)).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _gpt2_step_shapes(place, cfg=None):
    """The shared GPT-2 train step (124M unless ``cfg`` says otherwise)
    with abstract arguments; ``place(shape_struct, partition_spec)``
    attaches the sharding."""
    import optax

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import make_train_step
    from ray_tpu.parallel.sharding import FSDP_RULES, flax_sharding

    cfg = cfg or GPT2Config.gpt2_small()
    model = GPT2(cfg)
    tx = optax.adamw(6e-4, weight_decay=0.01)
    boxed = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=1))
    plain, specs = flax_sharding(boxed, FSDP_RULES)
    params = jax.tree.map(place, plain, specs)
    opt_state = jax.eval_shape(tx.init, params)
    tokens = place(jax.ShapeDtypeStruct((32, cfg.max_seq_len), jnp.int32),
                   P(("dp", "fsdp"), None))
    return make_train_step(model, tx), (params, opt_state, tokens)


def _lower_as_on_tpu(step, args):
    # the model's flash dispatch asks jax.default_backend(); steer it in
    # the test, as the chip would answer
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return step.lower(*args)


def test_gpt2_124m_train_step_fits_one_v5e(one_chip):
    def place(a, _spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    step, args = _gpt2_step_shapes(place)
    lowered = _lower_as_on_tpu(step, args)
    # with no mesh the step says nothing about placement
    assert "sharding_constraint" not in lowered.as_text()
    assert "@Sharding" not in lowered.as_text()
    compiled = lowered.compile()
    # 12 layers x (forward, dK/dV, dQ)
    assert compiled.as_text().count("tpu_custom_call") == 36
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 0.9 * V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB"


#: the same compile of the step as it was before it said where
#: activations lie (PR 28's tree, this sandbox): what GSPMD made of one
#: table for parameters and activations (and 10 all-to-alls)
XL_DEPTH2_BEFORE = {"all-reduce": 33, "temp_bytes": 4_911_000_000,
                    "tpu_custom_call": 8}


def test_gpt2_fsdp_step_partitions_over_four_v5e(topo):
    """Mosaic kernels cannot be partitioned by GSPMD: under a mesh the
    model has to run them per shard (flash_attention(mesh=...)).  And
    the step is FSDP: activations stay on their batch shard, whole along
    embed; weights are gathered for use, gradients scattered back.
    GPT-2 XL's published widths, 8 sequences a device, depth cut to 2:
    partitioning does not depend on depth, and the full-depth compile
    costs minutes (made by hand: PERF.md, PR 30)."""
    from ray_tpu.models import GPT2Config
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.mesh import use_mesh

    mesh = build_mesh(MeshConfig(fsdp=4), devices=topo.devices)

    def place(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    step, args = _gpt2_step_shapes(
        place, dataclasses.replace(GPT2Config.gpt2_xl(remat="full"),
                                   num_layers=2))
    with use_mesh(mesh):
        compiled = _lower_as_on_tpu(step, args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") \
        == XL_DEPTH2_BEFORE["tpu_custom_call"]
    assert "all-gather" in text and "reduce-scatter" in text
    assert text.count(" all-to-all(") <= 2
    assert text.count(" all-reduce(") < XL_DEPTH2_BEFORE["all-reduce"]
    # no activation lies along a width shard (1600 / 4), the embedding
    # lookup's neither: the rounded table is gathered for it
    assert not re.search(r"\[\d+,1024,400\]", text)
    mem = compiled.memory_analysis()  # bytes on each device
    assert mem.temp_size_in_bytes < XL_DEPTH2_BEFORE["temp_bytes"]
    assert mem.temp_size_in_bytes < 0.9 * V5E_HBM_BYTES
    # parameters and AdamW's moments leave as they came: donation holds
    assert mem.alias_size_in_bytes >= mem.argument_size_in_bytes - 2 ** 20
    (params_in, _, _), _ = compiled.input_shardings
    assert jax.tree.all(jax.tree.map(
        lambda a, b, x: a.is_equivalent_to(b, x.ndim),
        params_in, compiled.output_shardings[0], args[0]))


# ---------------------------------------------------------------------------
# Nemotron-H (PR 35): the chunked scan, the step, the gradient check
# ---------------------------------------------------------------------------

def test_ssd_kernels_compile_at_the_nemotron_cells_shapes(one_chip):
    """One sequence of 8,192, 64 heads of 64, 8 groups of state 128,
    chunks of 128, a whole group a grid step: the forward kernel and the
    backward one (``_ssd``: the public wrapper asks the backend).  What
    lies in HBM around them: B and C by group, never 64 heads of them,
    and no ``Q x Q`` array a head."""
    ssd = importlib.import_module("ray_tpu.ops.ssd")

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    xs = shape((1, 8192, 64, 64))
    bc = shape((1, 8192, 8, 128))
    head = shape((64,), jnp.float32)
    plan = ssd._plan(xs, bc, 128)
    assert plan.heads_a_step == 8

    def grads(*a):
        def loss(*a):
            y = ssd._ssd(*a, plan, False).astype(jnp.float32)
            return (y * y).sum()
        return jax.grad(loss, argnums=tuple(range(6)))(*a)

    compiled = jax.jit(grads).lower(
        xs, shape((1, 8192, 64), jnp.float32), head, bc, bc, head).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "[1,8192,64,128]" not in text            # B or C a head
    assert not re.search(r"\[(1,)?64,64,128,128\]", text)   # Q x Q a head


@pytest.mark.parametrize("dtype,rows", [(jnp.bfloat16, 1024),
                                        (jnp.float32, 512)])
def test_short_conv_kernels_compile_at_the_nemotron_cells_shapes(
        one_chip, dtype, rows):
    """One sequence of 8,192 over the mixer's 6,144 channels, four taps:
    the forward kernel and the backward one (``_short_conv``: the public
    wrapper asks the backend), in the cell's bfloat16 and in the float32
    a check may run the mixer in (half the rows a tile, so that both fit
    the kernels' fast memory).  No float32 copy of ``u`` in HBM, padded
    or not, and every call's FIRST result 2-d."""
    conv = importlib.import_module("ray_tpu.ops.short_conv")

    def shape(dims, kind):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one_chip)

    u = shape((1, 8192, 6144), dtype)
    tile = conv.tiles(u, 4)
    assert tile == (rows, 512)

    def grads(*a):
        def loss(*a):
            y = conv._short_conv(*a, tile, False).astype(jnp.float32)
            return (y * y).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(*a)

    text = jax.jit(grads).lower(u, shape((4, 6144), jnp.float32),
                                shape((6144,), jnp.float32)
                                ).compile().as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 2
    kind = "bf16" if dtype == jnp.bfloat16 else "f32"
    for line in calls:
        assert re.search(rf"= \(?{kind}\[8192,6144\]", line), line[:200]
    assert "[1,8195,6144]" not in text and "[8195,6144]" not in text
    if dtype == jnp.bfloat16:
        assert "f32[1,8192,6144]" not in text


@pytest.mark.parametrize("dtype,rows", [(jnp.bfloat16, 512),
                                        (jnp.float32, 256)])
def test_gate_norm_kernels_compile_at_the_nemotron_cells_shapes(
        one_chip, dtype, rows):
    """One sequence of 8,192 over the mixer's 4,096 inner channels in 8
    groups of 512: the forward kernel and the backward one
    (``_gate_norm``: the public wrapper asks the backend), in the cell's
    bfloat16 and in float32 (half the rows a tile).  No array of the
    tokens by group in HBM, and the FIRST result of both calls 2-d."""
    norm = importlib.import_module("ray_tpu.ops.gate_norm")

    def shape(dims, kind):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one_chip)

    y = shape((1, 8192, 4096), dtype)
    tile = norm.tiles(8192, 4096, 8, dtype)
    assert tile == (rows, 512)

    def grads(*a):
        def loss(*a):
            g = norm._gate_norm(*a, 8, 1e-5, tile, False).astype(jnp.float32)
            return (g * g).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(*a)

    text = jax.jit(grads).lower(y, y, shape((4096,), jnp.float32)
                                ).compile().as_text()
    calls = _kernel_calls(text)
    assert [_called(line).count("gate_norm") for line in calls] == [1, 1]
    kind = "bf16" if dtype == jnp.bfloat16 else "f32"
    for line in calls:
        assert re.search(rf"= \(?{kind}\[8192,4096\]", line), line[:200]
    assert "[8192,8,512]" not in text


def _nemotron_share(**kw):
    nh = importlib.import_module("ray_tpu.models.nemotron_h")
    cfg = nh.NemotronHConfig.nemotron_3_nano_30b_a3b_share(remat="full",
                                                           **kw)
    return nh, cfg, nh.NemotronH(cfg)


def _abstract_params(model, one_chip, batch):
    from flax.core import meta

    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=batch)))
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)


#: bytes of the cell's training state: 666,962,944 parameters, f32
#: weights and AdamW's two moments (the gradients are temporaries)
NEMOTRON_STATE_BYTES = 666_962_944 * 12


def _compiled_step(module, model, batch, one_chip):
    """The donated train step of a routed model's share, compiled for
    the described chip: its text and its bytes on the device."""
    import optax

    params = _abstract_params(model, one_chip, batch)
    tx = optax.adamw(1e-5, weight_decay=0.01)
    opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(tx.init, params))
    tokens = jax.ShapeDtypeStruct((batch, model.config.max_seq_len),
                                  jnp.int32, sharding=one_chip)
    compiled = _lower_as_on_tpu(module.make_train_step(model, tx),
                                (params, opt_state, tokens)).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes >= mem.argument_size_in_bytes - 2 ** 20
    return compiled.as_text(), params, total


def _plans_remade(text):
    """Instructions of the backward pass's recompute that stand under
    ``moe.plan``: none, since a routed call's choices and row plan are
    kept from the forward (``models/step.py`` ``remat``, PR 53), and with
    them no second ``argsort`` a layer-call."""
    return [line[:120] for line in text.splitlines()
            if re.search(r'op_name="[^"]*rematted_computation[^"]*moe\.plan',
                         line)]


def _kernel_calls(text):
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]


def _called(line):
    """The instruction's own name, which holds the kernel's ``name=``
    (``%grouped_matmul_act.7``, ``%jvp_landed_rows_sum_.1``).  The rest
    of the line will not do: it names the operands too, and a walk reads
    what a grouped product wrote."""
    return line.split(" = ")[0].strip()


def _op_name(line):
    """The instruction's ``op_name``: the program's names on the way to
    it.  A flash call is known by this (``.../jit(_flash_nl_forward)/
    attn.kernel/pallas_call``): its own name is its innermost scope's,
    ``%attn.kernel.7`` since the pieces of ``attn`` (``models/step.py``)."""
    found = re.search(r'op_name="([^"]*)"', line)
    return found.group(1) if found else ""


def _kernel_results_of_rows(calls, rows):
    """Kernel calls other than the grouped products with a 2-d result of
    ``rows`` rows, the routed layer's row buffer: the benchmark's readers
    (``benchmarks/reduce/kernels.py``) tell kernel families apart by
    result shape and would count such a call as a grouped product, find
    "another number of calls" and let ``gmm_roofline*`` fall silent."""
    return [_called(line) for line in calls
            if "grouped_matmul" not in _called(line)
            and re.search(rf"(?:bf16|f32)\[{rows},\d+\]",
                          line.split(" = ", 1)[1].split(" custom-call(")[0])]


def _walks(calls, layer_calls, sums=2):
    """The walks of a step: three a layer-call (``combine`` forward, its
    ``d_w``, ``dispatch``'s backward: two sums and the dots; the
    recomputed forward ends before ``combine`` where no gradient needs
    its result), every one under the part it belongs to."""
    walks = [line for line in calls if "landed_rows" in _called(line)]
    dots = [line for line in walks if "landed_rows_dot" in _called(line)]
    assert (len(dots), len(walks)) == (layer_calls,
                                       (1 + sums) * layer_calls)
    for line in walks:
        part = "moe.combine" if line in dots else r"moe\.(combine|dispatch)"
        assert re.search(rf'op_name="[^"]*/{part}/', line), line[:300]
    assert sum("/moe.dispatch/" in line for line in walks) == layer_calls


def _not_moves_between_memory_spaces(found, text):
    """``found`` (:func:`_passes_over_the_row_buffer` of ``text``)
    without the compiler's own asynchronous moves of an array between
    HBM and its faster memory space: a ``copy-start`` (or a
    ``slice-start``: the same move a slice of the array at a time) of
    which exactly ONE of destination and source is laid out in ``S(1)``,
    and its ``-done``.  A copy that stays in one space is a pass over
    the buffer like any other and is kept, in every cell."""
    full = {m.group(1): line for line in text.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", line)] if m}

    def moves(line):
        name = line.split(" = ")[0]
        done = re.match(r"%(copy|slice)-done", name)
        if done:
            name = re.search(rf"{done.group(1)}-done\((%[\w.\-]+)\)",
                             full[name]).group(1)
        start = re.match(r"%(copy|slice)-start", name)
        if not start:
            return False
        ends = re.findall(r"(?:bf16|f32)\[[\d,]+\]\{[^}]*\}",
                          full[name].split(f" {start.group(1)}-start(")[0])[:2]
        return len(ends) == 2 and ("S(1)" in ends[0]) != ("S(1)" in ends[1])

    return [line for line in found if not moves(line)]


def _passes_over_the_row_buffer(text, rows):
    """Instructions of a compiled step that WRITE a float ``[rows,
    width]`` array, the routed layer's worst-case row buffer, other than
    a kernel call and the gathers' own (the conditional that chooses
    their reach, and what its branches hold): there should be none.
    Names for another instruction's result (tuples and their elements,
    bitcasts) and the program's arguments write nothing; an instruction inside a fusion is the
    fusion's."""
    inside = set(re.findall(r"calls=%([\w.\-]+)", text))
    for branches in re.findall(r"branch_computations=\{([^}]*)\}", text):
        inside.update(re.findall(r"%([\w.\-]+)", branches))
    found, computation = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            computation = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if computation in inside or not op or not re.search(
                rf"(?:bf16|f32)\[{rows},\d+\]", op.group(1)):
            continue
        if op.group(2) not in ("custom-call", "conditional", "tuple",
                               "get-tuple-element", "bitcast",
                               "parameter"):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("cell", ["trinity-mini", "kanana-2-30b-a3b"])
def test_gated_share_steps_count_their_kernels_and_pass_no_row_buffer(
        one_chip, cell):
    """``trinity-mini.steady``'s and ``kanana-2-30b-a3b.steady``'s steps
    at the published widths: 4 and 5 expert layers x 2 and 1 sequences x
    3 products x (2 forward, d lhs, d rhs) grouped kernels, which is what
    the benchmark's readers count, and around them no pass of XLA over a
    call's worst-case row buffer.  The recompute makes no plan: a call's
    choices and tables are kept from the forward, 0.94 MB x 8 calls and
    1.40 MB x 5 (the ``moe.plan`` span's ``kept_bytes``)."""
    if cell == "trinity-mini":
        module = importlib.import_module("ray_tpu.models.afmoe")
        model = module.AFMoE(module.AFMoEConfig.trinity_mini_share(
            remat="full"))
        batch, calls = 2, 96
    else:
        module = importlib.import_module("ray_tpu.models.deepseek_v3")
        model = module.DeepseekV3(
            module.DeepseekV3Config.kanana_2_30b_a3b_share(remat="full"))
        batch, calls = 1, 60
    text, _, total = _compiled_step(module, model, batch, one_chip)
    kernels = _kernel_calls(text)
    assert sum("grouped_matmul" in _called(line)
               for line in kernels) == calls
    # a layer and a sequence: two forward calls (remat), dK/dV, dQ
    assert sum("/jit(_flash_" in _op_name(line) for line in kernels) == \
        (40 if cell == "trinity-mini" else 24)
    held, _, _, top_k, tokens, _ = ROUTED_CELLS[cell]
    rows = top_k * tokens + held * 256
    assert any(f"[{rows}," in line for line in kernels)
    assert _passes_over_the_row_buffer(text, rows) == []
    # twelve grouped kernels a layer-call (Trinity's norm after the
    # layer wants the routed sums again: its combine is recomputed); and
    # the row buffer is no other kernel's result (the readers' trap,
    # PERF.md section 7)
    _walks(kernels, calls // 12, sums=3 if cell == "trinity-mini" else 2)
    assert _kernel_results_of_rows(kernels, rows) == []
    assert _plans_remade(text) == []
    assert "moe.plan" in text
    assert total < 0.9 * V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB"


def test_nemotron_share_train_step_fits_one_v5e(one_chip):
    """``nemotron-3-nano-30b-a3b.steady``'s step: EMEMEMEM* at the
    published widths, batch 2 x 8,192, donated state; in it the 0.70 MB
    x 8 calls of choices and row plans the recompute replays (PR 53).
    The line below had 38 MB of room (11.803 GiB on the parent); the
    step reads 11.832 with the weights selected at the ids
    (``afmoe._scores_at``), 8 MB under it."""
    nh, cfg, model = _nemotron_share()
    text, params, total = _compiled_step(nh, model, 2, one_chip)
    assert sum(a.size for a in jax.tree.leaves(params)) == 666_962_944
    calls = _kernel_calls(text)
    named = lambda name: sum(  # noqa: E731
        name in _called(line) for line in calls)
    # 4 mixers x 2 sequences: forward twice (remat), backward once
    assert named("ssd_chunk_scan_bwd") == 8
    assert named("ssd_chunk_scan") - named("ssd_chunk_scan_bwd") == 16
    # the mixers' convolution likewise (PR 45), and every call's FIRST
    # result 2-d [8192, channels]: the benchmark's readers tell kernel
    # calls apart by result shapes, and file such a one with the norms
    assert named("short_conv_bwd") == 8
    assert named("short_conv") - named("short_conv_bwd") == 16
    for line in calls:
        if "short_conv" in line:
            assert re.search(r"= \(?bf16\[8192,6144\]", line), line[:200]
    # no padded float32 copy of a mixer's ``u``
    assert not re.search(r"f32\[(1,)?8195,6144\]", text)
    # the mixers' gated norm likewise (PR 49), 2-d [8192, inner], and no
    # float32 array by group for its statistics
    assert named("gate_norm_bwd") == 8
    assert named("gate_norm") - named("gate_norm_bwd") == 16
    for line in calls:
        if "gate_norm" in _called(line):
            assert re.search(r"= \(?bf16\[8192,4096\]", line), line[:200]
    assert "f32[8192,8,512]" not in text
    # ``z``, ``u`` and the step sizes leave ``in_proj`` as arrays of their
    # own: no ``[8192, 4096 + 6144 + 64]`` one to copy them out of
    assert "8192,10304]" not in text
    # 4 expert layers x 2 sequences x 2 products x (2 forward, d lhs, d rhs)
    assert named("grouped_matmul") == 64
    assert _passes_over_the_row_buffer(text, 6 * 8192 + 8 * 256) == []
    _walks(calls, 8)
    assert _kernel_results_of_rows(calls, 6 * 8192 + 8 * 256) == []
    assert _plans_remade(text) == []
    # what it took before the routed layer kept to its live rows (PR 35:
    # 11.83 GiB), and a hundredth of a GiB
    assert total < 11.84 * 2 ** 30, f"{total / 2**30:.3f} GiB"
    print(f"nemotron step: {total / 2**30:.3f} GiB")


def test_nemotron_gradient_check_fits_beside_the_training_state(one_chip):
    """The harness's check (``benchmarks/kinds/train.py``
    ``gradient_check``): depth 2, ``EMEM*``, two sequences, the program's
    paired loss and the step-by-step reference, BOTH gradients in one
    program, while the training state is still on the chip."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    reference = importlib.import_module("benchmarks.reference.nemotron_h")
    paired = importlib.import_module(
        "benchmarks.reference.nemotron_h_paired")
    _, cfg, model = _nemotron_share(num_layers=2)
    params = _abstract_params(model, one_chip, 2)
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32,
                                  sharding=one_chip)
    sizes = {"n_layer": 2, "n_head": cfg.num_heads, "ln_eps": cfg.rms_eps}

    def error(p, t):
        return reference.grad_error(
            jax.grad(lambda q: paired.program_loss(model, q, t))(p),
            jax.grad(lambda q: reference.loss(q, t, **sizes))(p))

    compiled = _lower_as_on_tpu(jax.jit(error), (params, tokens)).compile()
    assert "ssd_chunk_scan_bwd" in compiled.as_text()
    assert "short_conv_bwd" in compiled.as_text()
    assert "gate_norm_bwd" in compiled.as_text()
    mem = compiled.memory_analysis()
    check = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert check + NEMOTRON_STATE_BYTES < 0.97 * V5E_HBM_BYTES, \
        f"{check / 2**30:.2f} GiB beside " \
        f"{NEMOTRON_STATE_BYTES / 2**30:.2f} GiB of state"
    print(f"nemotron gradient check: {check / 2**30:.3f} GiB")


def _xing_share(**kw):
    ds = importlib.import_module("ray_tpu.models.deepseek_v3")
    cfg = ds.DeepseekV3Config.xing4_0_29b_a4b_share(remat="full", **kw)
    return ds, cfg, ds.DeepseekV3(cfg)


#: bytes of the cell's training state: 759,346,190 parameters, f32
#: weights and AdamW's two moments (the gradients are temporaries)
XING_STATE_BYTES = 759_346_190 * 12


@pytest.mark.slow
def test_xing_share_train_step_fits_one_v5e(one_chip):
    """``xing4.0-29b-a4b.steady``'s step: one dense and four expert
    layers at the published widths, four lanes, batch 4 x 2,048, donated
    state.  8.49 GiB of state and 5.48 of temporaries: 13.97 GiB (14.11
    while the lanes were XLA's, PR 54).  The passes over the lanes are
    ``ops/lane_mix.py``'s kernels (PR 55): a connection's projection
    (kept for the recompute), ``write``, and the three of its backward;
    ``read`` and the arithmetic on the coefficients stay XLA's, and no
    float32 copy of a sequence's lanes is left in the step."""
    ds, cfg, model = _xing_share()
    text, params, total = _compiled_step(ds, model, 4, one_chip)
    assert sum(a.size for a in jax.tree.leaves(params)) == 759_346_190
    calls = _kernel_calls(text)
    named = lambda name: sum(  # noqa: E731
        name in _called(line) for line in calls)
    # 5 layers x 4 sequences x (2 forward, dK/dV, dQ)
    assert sum("/jit(_flash_" in _op_name(line) for line in calls) == 80
    # 4 expert layers x 4 sequences x 3 products x (2 forward, d lhs, d rhs)
    assert named("grouped_matmul") == 192
    # 5 layers x 2 connections x 4 sequences, each once: the recompute
    # starts from the projection the forward kept
    lanes = {name: named(name) for name in (
        "lane_project", "lane_write.", "lane_write_bwd", "lane_read_bwd",
        "lane_open_bwd")}
    assert lanes == {"lane_project": 40, "lane_write.": 40,
                     "lane_write_bwd": 40, "lane_read_bwd": 40,
                     "lane_open_bwd": 40}, lanes
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        plan = ds.hyper.plan_args(cfg, 4, cfg.max_seq_len)
    assert (plan["impl"], plan["x_reads"], plan["kernel_calls"]) == (
        "pallas", "3,1,3", sum(lanes.values()))
    assert not re.search(r"f32\[(?:1,)?2048,14336\]", text)
    rows = 4 * 2048 + 8 * 256
    assert _not_moves_between_memory_spaces(
        _passes_over_the_row_buffer(text, rows), text) == []
    # ``write``'s backward wants the sub-layer's result again (the gain's
    # gradient is its product with the cotangent): combine is recomputed
    _walks(calls, 16, sums=3)
    assert _kernel_results_of_rows(calls, rows) == []
    assert _plans_remade(text) == []
    for part in ("hc.coef", "hc.mix", "mla.q_up", "attn.mla"):
        assert part in text, part
    # the twenty Sinkhorn steps are a loop the compiler sees once a call
    assert total < 14.3 * 2 ** 30, f"{total / 2**30:.3f} GiB"
    print(f"xing step: {total / 2**30:.3f} GiB")


@pytest.mark.slow
def test_xing_gradient_check_fits_beside_the_training_state(one_chip):
    """The harness's check (``benchmarks/kinds/train.py``
    ``gradient_check``): depth 2 (the dense layer and two expert
    layers), two sequences of 2,048, the program's paired loss and the
    reference, BOTH float32 gradients in one program (502,493,602
    parameters: 5.62 GiB with the arguments), while the training state
    is still on the chip: ISSUE 54's line of 0.97 x 16 - 8.49 = 7.03
    GiB.  At 4,096 the program's gradient alone holds 1.90 GiB of
    activations beside those 5.62: why the cell runs 4 x 2,048."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    reference = importlib.import_module("benchmarks.reference.xing")
    paired = importlib.import_module("benchmarks.reference.xing_paired")
    _, cfg, model = _xing_share(num_layers=2)
    params = _abstract_params(model, one_chip, 2)
    assert sum(a.size for a in jax.tree.leaves(params)) == 502_493_602
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32,
                                  sharding=one_chip)
    sizes = {"n_layer": 2, "n_head": cfg.num_heads, "ln_eps": cfg.rms_eps}

    def error(p, t):
        return reference.grad_error(
            jax.grad(lambda q: paired.program_loss(model, q, t))(p),
            jax.grad(lambda q: reference.loss(q, t, **sizes))(p))

    compiled = _lower_as_on_tpu(jax.jit(error), (params, tokens)).compile()
    mem = compiled.memory_analysis()
    check = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert check + XING_STATE_BYTES < 0.97 * V5E_HBM_BYTES, \
        f"{check / 2**30:.2f} GiB beside " \
        f"{XING_STATE_BYTES / 2**30:.2f} GiB of state"
    print(f"xing gradient check: {check / 2**30:.3f} GiB")


def _ouro_stage(**kw):
    ouro = importlib.import_module("ray_tpu.models.ouro")
    cfg = ouro.OuroConfig.ouro_2_6b_stage(remat="full", **kw)
    return ouro, cfg, ouro.Ouro(cfg)


#: bytes of ``ouro-2.6b.steady``'s training state: 509,661,185
#: parameters, f32 weights and AdamW's two moments
OURO_STATE_BYTES = 509_661_185 * 12


def test_flash_compiles_at_the_ouro_cells_shapes(one_chip):
    """One sequence of 4,096, 16 query on 16 K/V heads of 128, as
    ``models/ouro.py`` calls it: the native-layout family (one head a
    128-lane slab), full causal: forward, dK/dV and dQ."""
    x = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    assert fa._nl_eligible(x, x, x)
    compiled = jax.jit(_attn_grads("native")).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.slow
def test_ouro_stage_train_step_fits_one_v5e(one_chip):
    """``ouro-2.6b.steady``'s step: 6 layers x 4 passes at the published
    widths, batch 2 x 4,096, donated state.  Marked slow: it takes two
    minutes of a file that is the suite's longest, and the benchmark
    reads the same number on the chip (``step_hbm_gib``, 10.75)."""
    ouro, _, model = _ouro_stage()
    text, params, total = _compiled_step(ouro, model, 2, one_chip)
    assert sum(a.size for a in jax.tree.leaves(params)) == 509_661_185
    # a call's ``op_name`` says what built it (a backward call also
    # READS a ``_flash_nl_forward`` result: count names, not mentions)
    calls = [_op_name(line) for line in _kernel_calls(text)]
    named = lambda name: sum(  # noqa: E731
        f"/jit({name})/" in call for call in calls)
    # 6 layers x 4 passes x 2 sequences: forward twice (remat), dK/dV
    # and dQ once
    assert named("_flash_nl_forward") == 96
    assert named("_flash_nl_backward") == 96
    assert not re.search(r"f32\[(2,4095|8190|8192|32760|32768),49152\]",
                         text)
    assert total < 0.9 * V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB"
    print(f"ouro step: {total / 2**30:.3f} GiB")


@pytest.mark.slow
def test_ouro_gradient_check_fits_beside_the_training_state(one_chip):
    """The harness's check (``benchmarks/kinds/train.py``
    ``gradient_check``): depth 2 (2 layers x 4 passes), two sequences,
    the program's loss and the reference's, BOTH gradients in one
    program, while the training state is still on the chip: what decided
    the cell's depth (a stage of 8 layers' state leaves it no room).
    Marked slow as the cell's own step above: 155 s of compiling in the
    file that alone decides how long the tier-1 run lasts (the driver's
    took 1,334 s of its 1,470 with it), and every traced run of the cell
    makes this check on the chip."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    reference = importlib.import_module("benchmarks.reference.ouro")
    ouro, cfg, model = _ouro_stage(num_layers=2)
    params = _abstract_params(model, one_chip, 2)
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32,
                                  sharding=one_chip)
    sizes = {"n_layer": 2, "n_head": cfg.num_heads, "ln_eps": cfg.rms_eps}

    def error(p, t):
        return reference.grad_error(
            jax.grad(lambda q: ouro.loss_fn(model, q, t))(p),
            jax.grad(lambda q: reference.loss(q, t, **sizes))(p))

    compiled = _lower_as_on_tpu(jax.jit(error), (params, tokens)).compile()
    text = compiled.as_text()
    # a call's ``op_name`` says what built it (a backward call also
    # READS a ``_flash_nl_forward`` result: count names, not mentions)
    calls = [_op_name(line) for line in _kernel_calls(text)]
    named = lambda name: sum(  # noqa: E731
        f"/jit({name})/" in call for call in calls)
    # the program's half: 2 layers x 4 passes x 2 sequences, forward
    # twice (remat), dK/dV and dQ once
    assert named("_flash_nl_forward") == 32
    assert named("_flash_nl_backward") == 32
    # no [tokens, vocabulary] logits on either side: a chunk at a time
    assert not re.search(r"f32\[(2,4095|8190|8192|32760|32768),49152\]",
                         text)
    assert "f32[1024,49152]" in text
    mem = compiled.memory_analysis()
    check = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert check + OURO_STATE_BYTES < 0.97 * V5E_HBM_BYTES, \
        f"{check / 2**30:.2f} GiB beside " \
        f"{OURO_STATE_BYTES / 2**30:.2f} GiB of state"
    print(f"ouro gradient check: {check / 2**30:.3f} GiB")


# ---------------------------------------------------------------------------
# Mellum (PR 51): the four chips that share each layer, and the exchange
# ---------------------------------------------------------------------------

#: bytes of ``mellum2-12b-a2.5b.ep4.steady``'s training state ON ONE CHIP:
#: a quarter of 2,123,976,960 parameters, f32 weights and AdamW's two
#: moments (each chip its own 16 experts a layer whole, and a quarter of
#: everything else)
MELLUM_STATE_BYTES_A_CHIP = 2_123_976_960 * 12 // 4


def _mellum_on_four(topo, depth):
    """The stage's model at ``depth`` layers, the 2 x 2 mesh, and
    ``place(shape, spec)``."""
    from ray_tpu.parallel import MeshConfig, build_mesh

    ml = importlib.import_module("ray_tpu.models.mellum")
    mesh = build_mesh(MeshConfig(fsdp=4), devices=topo.devices)
    cfg = ml.MellumConfig.mellum2_12b_a2_5b_stage(remat="full",
                                                  num_layers=depth)

    def place(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return ml, cfg, ml.Mellum(cfg), mesh, place


def _mellum_params(model, place):
    from ray_tpu.parallel.sharding import FSDP_EP_RULES, flax_sharding

    plain, specs = flax_sharding(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=4)),
        FSDP_EP_RULES)
    return jax.tree.map(place, plain, specs), specs


def _mellum_step(topo, depth):
    """``mellum2-12b-a2.5b.ep4.steady``'s donated step at ``depth``
    layers, global batch 8 x 8,192, compiled for the four described
    chips."""
    import optax

    from ray_tpu.parallel.mesh import use_mesh

    ml, cfg, model, mesh, place = _mellum_on_four(topo, depth)
    with use_mesh(mesh):
        params, specs = _mellum_params(model, place)
        tx = optax.adamw(1e-5, weight_decay=0.01)
        opt_state = jax.eval_shape(tx.init, params)
        tokens = place(jax.ShapeDtypeStruct((8, cfg.max_seq_len), jnp.int32),
                       P(("dp", "fsdp"), None))
        compiled = _lower_as_on_tpu(ml.make_train_step(model, tx), (
            params, opt_state, tokens)).compile()
    return compiled, params, specs


def _exchange_and_placement(compiled, params, specs, depth):
    """What holds of the step at any depth: the exchange is all-gathers
    of the group's rows and reduce-scatters of the parts, never an
    all-to-all; the experts lie by expert and the state is donated; the
    recompute makes no plan (a chip keeps its own choices and its plan
    over the group's pairs from the forward: 1.46 MB a call, PR 53)."""
    text = compiled.as_text()
    assert text.count(" all-to-all(") == 0
    assert _plans_remade(text) == [] and "moe.plan" in text
    # a call of a routed layer gathers the four chips' 4,096 rows of
    # 2304 (forward and recomputed forward), and the backward of the
    # scatter gathers as many: 2 sequences x 2 pieces a layer
    rows = re.findall(r"= \(?bf16\[(?:1,)?16384,2304\][^=]* all-gather", text)
    assert len(rows) >= depth * 2 * 2 * 3 >= 8, len(rows)
    # the TPU compiler writes a reduce-scatter as a fused all-reduce
    # and slice named ``all-reduce-scatter``: [16384, 2304] -> a chip's
    # 4,096 rows (padded to 4,224); computations alike are written once
    assert len(re.findall(
        r"all-reduce-scatter[.\d]* \(input[.\d]*: bf16\[16384,2304\]\)",
        text)) >= 4
    moe = specs["h0"]["mlp"]["moe"]
    assert moe["experts_gate"] == moe["experts_down"] == P("fsdp", None, None)
    (params_in, _, _), _ = compiled.input_shardings
    assert jax.tree.all(jax.tree.map(
        lambda a, b, x: a.is_equivalent_to(b, x.ndim),
        params_in, compiled.output_shardings[0], params))
    shard = params_in["h0"]["mlp"]["moe"]["experts_up"].shard_shape(
        params["h0"]["mlp"]["moe"]["experts_up"].shape)
    assert shard == (16, 2304, 896)   # each chip's AdamW updates these
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= mem.argument_size_in_bytes - 2 ** 20
    return text, mem


def test_mellum_step_exchanges_over_four_v5e(topo):
    """One sliding layer of the stage (the exchange and the placement
    do not depend on depth; the whole period is the slow test below)."""
    compiled, params, specs = _mellum_step(topo, 1)
    text, mem = _exchange_and_placement(compiled, params, specs, 1)
    # 2 sequences x (forward twice, dK/dV, dQ); 2 x 2 pieces x 12 products
    calls = _kernel_calls(text)
    assert sum("grouped_matmul" in _called(line) for line in calls) == 48
    assert mem.peak_memory_in_bytes < 0.9 * V5E_HBM_BYTES


@pytest.mark.slow
def test_mellum_stage_step_fits_four_v5e(topo):
    """The whole period at the cell's batch: 7.91 GiB of state a chip
    with the gradients, and the step's temporaries beside it, the 16
    calls' kept choices and plans (23 MB a chip, PR 53) among them."""
    compiled, params, specs = _mellum_step(topo, 4)
    assert sum(a.size for a in jax.tree.leaves(params)) == 2_123_976_960
    _, mem = _exchange_and_placement(compiled, params, specs, 4)
    assert mem.argument_size_in_bytes == pytest.approx(
        MELLUM_STATE_BYTES_A_CHIP, rel=1e-3)
    # what the compiler says the program holds at its fullest (my
    # compile, PR 51: 14.25 GiB of the chip's 15.75)
    assert mem.peak_memory_in_bytes < 14.5 * 2 ** 30, \
        f"{mem.peak_memory_in_bytes / 2**30:.2f} GiB"
    print(f"mellum step: peak {mem.peak_memory_in_bytes / 2**30:.3f} GiB")


@pytest.mark.slow
def test_mellum_gradient_check_fits_beside_the_training_state(topo):
    """The harness's check (``benchmarks/kinds/train.py``
    ``gradient_check``): depth 2, four sequences, the program's paired
    loss and the reference, BOTH float32 gradients in one program, over
    the four chips beside the training state (1.29B parameters x 12
    bytes = 15.5 GB: it fits only where it lies over the chips)."""
    import sys

    from ray_tpu.parallel.mesh import use_mesh

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    reference = importlib.import_module("benchmarks.reference.mellum")
    paired = importlib.import_module("benchmarks.reference.mellum_paired")
    _, cfg, model, mesh, place = _mellum_on_four(topo, 2)
    sizes = {"n_layer": 2, "n_head": cfg.num_heads, "ln_eps": cfg.rms_eps}

    def error(p, t):
        return reference.grad_error(
            jax.grad(lambda q: paired.program_loss(model, q, t))(p),
            jax.grad(lambda q: reference.loss(q, t, **sizes))(p))

    with use_mesh(mesh):
        params, _ = _mellum_params(model, place)
        tokens = place(jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32),
                       P())
        compiled = _lower_as_on_tpu(jax.jit(error),
                                    (params, tokens)).compile()
    assert compiled.as_text().count(" all-to-all(") == 0
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes + MELLUM_STATE_BYTES_A_CHIP \
        < 0.97 * V5E_HBM_BYTES, \
        f"{mem.peak_memory_in_bytes / 2**30:.2f} GiB beside " \
        f"{MELLUM_STATE_BYTES_A_CHIP / 2**30:.2f} GiB of state"
    print(f"mellum gradient check: peak "
          f"{mem.peak_memory_in_bytes / 2**30:.3f} GiB")


# --------------------------------------------------------------------------
# Qwen3-Next-80B-A3B: heads of 256, the gated delta rule's scan
# --------------------------------------------------------------------------

#: bytes of ``qwen3-next-80b-a3b.steady``'s training state: 625,667,136
#: parameters, f32 weights and AdamW's two moments
QWEN3_NEXT_STATE_BYTES = 625_667_136 * 12


def _qwen3_next_share(**kw):
    qn = importlib.import_module("ray_tpu.models.qwen3_next")
    cfg = qn.Qwen3NextConfig.qwen3_next_80b_a3b_share(remat="full", **kw)
    return qn, cfg, qn.Qwen3Next(cfg)


def test_flash_compiles_at_the_qwen3_next_cells_shapes(one_chip):
    """One sequence of 4,096 (and of 8,192), 16 query on 2 K/V heads of
    256, as ``models/qwen3_next.py`` calls it: the first head of two lane
    slabs, so the head-major family (the native one takes 64 and 128),
    a group of 8, full causal: forward, dK/dV and dQ, at the blocks
    ``flash_attention`` picks for a head wider than 128 (1024 queries x
    512 keys: inside the cell's step the dK/dV kernel's tiles at 1024 x
    1024 passed its 16 MiB of scoped VMEM by 20 KB)."""
    for seq in (4096, 8192):
        q = jax.ShapeDtypeStruct((1, seq, 16, 256), jnp.bfloat16,
                                 sharding=one_chip)
        k = jax.ShapeDtypeStruct((1, seq, 2, 256), jnp.bfloat16,
                                 sharding=one_chip)
        assert not fa._nl_eligible(q, k, k)

        def grads(q, k, v):
            with mock.patch.object(jax, "default_backend", lambda: "tpu"):
                return jax.grad(lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=True).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)

        text = jax.jit(grads).lower(q, k, k).compile().as_text()
        assert text.count("tpu_custom_call") == 3
        # the key block: dK/dV's grid walks seq / 512 tiles of keys
        assert f"bf16[1,2,{seq},256]" in text


@pytest.mark.parametrize("chunk_math", ["xla", "pallas"])
def test_the_gated_delta_scan_compiles_at_the_cells_shapes(one_chip,
                                                           chunk_math):
    """``ops/gated_delta.py`` forward and backward at one sequence of the
    cell: 16 key heads serving 32 value heads of 128, chunks of 64.  In
    both forms two loops over the 64 chunks (the carry, and its backward:
    what ``gdn_roofline``'s reader counts) and a float32 state.  ``xla``,
    the public wrapper's choice off the TPU: plain ``jnp``, no kernel,
    and the backward's temporaries (0.77 GiB here; 1.11 before PR 59,
    2.15 then at 8,192, which with the reference's own is what the
    gradient check had no room for).  ``pallas`` (``interpret=False``), what the chip runs: Mosaic
    accepts the kernels before and after the carry and their backward,
    no ``[.., 64, 64]`` float32 matrix of all chunks is left in the
    program, and the temporaries are 0.53 GiB."""
    gd = importlib.import_module("ray_tpu.ops.gated_delta")
    kernels = chunk_math == "pallas"
    q = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 4096, 32), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, g, b: gd.gated_delta(
            q, k, v, g, b, interpret=False if kernels else None).astype(
                jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))).lower(
                    q, q, v, g, g).compile()
    text = compiled.as_text()
    # the forward before the carry, and the two backward kernels (the
    # read-out's forward feeds nothing a gradient needs)
    assert text.count("tpu_custom_call") == (3 if kernels else 0)
    assert len(re.findall(r" while\(", text)) == 2
    assert "f32[64,1,16,2,128,128]" in text   # the states that entered
    # a slab's C x C matrices side by side, of all 64 chunks x 16 heads
    assert bool(re.search(r"f32\[(1,)?64,16,64,128\]", text)) != kernels
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (0.6 if kernels else 1.3) * 2 ** 30


@pytest.mark.slow
def test_qwen3_next_share_train_step_fits_one_v5e(one_chip):
    """``qwen3-next-80b-a3b.steady``'s step: L L L F at the published
    widths, batch 4 x 4,096, donated state: 12.03 GiB (14.17 at 2 x
    8,192).  Marked slow: three minutes of compiling in the suite's
    longest file, and the benchmark reads the same number on the chip
    (``step_hbm_gib``)."""
    qn, cfg, model = _qwen3_next_share()
    text, params, total = _compiled_step(qn, model, 4, one_chip)
    assert sum(a.size for a in jax.tree.leaves(params)) == 625_667_136
    calls = _kernel_calls(text)
    named = lambda name: sum(  # noqa: E731
        name in _called(line) for line in calls)
    # 3 mixers x 4 sequences: the convolution forward twice (remat),
    # backward once, with no bias to learn
    assert named("short_conv_bwd") == 12
    assert named("short_conv") - named("short_conv_bwd") == 24
    # 4 layers x 4 sequences x 3 products x (2 forward, d lhs, d rhs)
    assert named("grouped_matmul") == 192
    flash = [_op_name(line) for line in calls]
    assert sum("/jit(_flash_forward)/" in c for c in flash) == 8
    assert sum("/jit(_flash_backward)/" in c for c in flash) == 8
    assert _plans_remade(text) == []
    assert total < 12.2 * 2 ** 30, f"{total / 2**30:.3f} GiB"
    print(f"qwen3-next step: {total / 2**30:.3f} GiB")


@pytest.mark.slow
def test_qwen3_next_gradient_check_fits_beside_the_training_state(one_chip):
    """The harness's check: depth 2, ``L L F``, two sequences of 4,096,
    the program's paired loss and the step-by-step reference, BOTH
    gradients in one program, beside the training state: 7.75 GiB beside
    6.99.  At 8,192 it reads 12.25: why the cell runs 4 x 4,096."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    reference = importlib.import_module("benchmarks.reference.qwen3_next")
    paired = importlib.import_module(
        "benchmarks.reference.qwen3_next_paired")
    _, cfg, model = _qwen3_next_share(num_layers=2)
    params = _abstract_params(model, one_chip, 2)
    tokens = jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32,
                                  sharding=one_chip)
    sizes = {"n_layer": 2, "n_head": cfg.num_heads, "ln_eps": cfg.rms_eps}

    def error(p, t):
        return reference.grad_error(
            jax.grad(lambda q: paired.program_loss(model, q, t))(p),
            jax.grad(lambda q: reference.loss(q, t, **sizes))(p))

    compiled = _lower_as_on_tpu(jax.jit(error), (params, tokens)).compile()
    assert "short_conv_bwd" in compiled.as_text()
    mem = compiled.memory_analysis()
    check = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert check + QWEN3_NEXT_STATE_BYTES < 0.97 * V5E_HBM_BYTES, \
        f"{check / 2**30:.2f} GiB beside " \
        f"{QWEN3_NEXT_STATE_BYTES / 2**30:.2f} GiB of state"
    print(f"qwen3-next gradient check: {check / 2**30:.3f} GiB")
