"""Every op of a train step under the program's name.

A compiled step has no host spans inside it: the names the program puts
on its ops (``models/step.py``: ONE list of parts for every model) are
what a device trace is split by.  Here each of the four model files'
``make_train_step`` is compiled at its tiny size on the CPU and every
instruction's ``op_name`` read from the compiled text, with the reader
the benchmark uses on a chip's trace (``benchmarks/reduce/scopes.py``);
the list of parts comes from the ``model:step.scopes`` span, as there.
The part ``attn`` has a second level, its pieces
(``step.ATTN_PIECES``, reader ``benchmarks/reduce/pieces.py``): held
here in all six model files, on the step traced as for a TPU.
Names only: nothing here is a speed.
"""

import functools
import hashlib
import importlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import optax
import pytest
from flax.core import meta

from benchmarks.reduce import pieces, scopes
from ray_tpu.core import telemetry
from ray_tpu.models import afmoe, deepseek_v3, gpt2, mellum, nemotron_h, \
    ouro, qwen3_next, step
from ray_tpu.ops import fused
from ray_tpu.ops import grouped_matmul as gm

# (``ray_tpu.ops`` exports the function under the module's name)
fa = importlib.import_module("ray_tpu.ops.flash_attention")

MODELS = {
    "gpt2": (gpt2, gpt2.GPT2Config, gpt2.GPT2),
    "afmoe": (afmoe, afmoe.AFMoEConfig, afmoe.AFMoE),
    "deepseek_v3": (deepseek_v3, deepseek_v3.DeepseekV3Config,
                    deepseek_v3.DeepseekV3),
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig,
                   nemotron_h.NemotronH),
    "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextConfig,
                   qwen3_next.Qwen3Next),
}
ROUTED = ("moe.route", "moe.plan", "moe.dispatch", "moe.experts",
          "moe.combine")
SEEN = {
    "gpt2": {"embed", "attn", "mlp", "head", "optimizer"},
    "afmoe": {"embed", "attn", "mlp", "head", "optimizer", *ROUTED},
    "deepseek_v3": {"embed", "attn", "mlp", "head", "optimizer", *ROUTED},
    "nemotron_h": {"embed", "attn", "mlp", "head", "optimizer", *ROUTED,
                   "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                   "ssm.out_proj"},
    # a Gated DeltaNet mixer's work under the five parts a Mamba-2
    # mixer's stands under (PR 58): ``PARTS`` is what it was
    "qwen3_next": {"embed", "attn", "mlp", "head", "optimizer", *ROUTED,
                   "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                   "ssm.out_proj"},
}
#: sha256 of ``str(make_jaxpr(train_step))`` (kernels traced as for a
#: TPU) and of the parameter tree's paths, shapes and dtypes, taken at
#: the commit BEFORE the scopes (bef306c): a scope is a string in an
#: instruction's metadata, the program is what it was.  All eight
#: re-pinned at PR 57, which changed the program on purpose in ONE place:
#: the chunked head's scan makes the gradient with the loss
#: (``ops/fused.py`` ``weighted_token_loss``), so the ``checkpoint``
#: around its step and the backward scan are gone from the text; the
#: parameter trees are what they were
BEFORE = {
    ("gpt2", ""): (
        "2096c88896f593a3e667014deaf3b706c8336dd81aa8efea7067f74861e7eb0f",
        "8673451a825d006b3c71617386c7388d4450a1a51087cba9af55ae5eebf1757a"),
    ("gpt2", "full"): (
        "943f05855cc631cfbb60428ed28a8d24df78f31ca2c7d00073940124bf1fd6f2",
        "8673451a825d006b3c71617386c7388d4450a1a51087cba9af55ae5eebf1757a"),
    # the three routed models re-pinned at PR 48: on a TPU the routed
    # layer's sums over tokens are kernel calls (``ops/grouped_matmul.py``
    # ``_walk_pallas``) where they were a gather a choice, and
    # ``combine`` rounds its sums itself; the parameter trees are what
    # they were, and GPT-2 runs none of it; the three again at PR 52: the
    # grouped products' kernels take an expert's whole matrix a block
    # (their grids, blocks and ``vmem_limit_bytes`` are in the text); and
    # at PR 53: a routed call's choices and row plan carry the names a
    # recompute keeps them by (``step.keep``: a ``name`` equation each),
    # the parts' ``checkpoint`` has the policy that keeps them, and the
    # weights are the scores taken at the ids in both branches of
    # ``route``; GPT-2's two are what they were
    ("afmoe", ""): (
        "10389a86af7cdd81baa2802b6336b3ad1312787f48c30e20dfbb44cb8a589307",
        "73a54f273ca8d1cab30e4c5907da0f975cba6a8dcc30a4dda90aad118cc3b47d"),
    ("afmoe", "full"): (
        "80e81a3511bd8b5d9203c5d50298005051718b270761240418105243c5a3ca37",
        "73a54f273ca8d1cab30e4c5907da0f975cba6a8dcc30a4dda90aad118cc3b47d"),
    ("deepseek_v3", ""): (
        "15a9723576927e13ce4bd8d1fe920c829bce2841b2e3165a0dcdbd6a5973223a",
        "80259fb632cdea7eb743ab44b67d71fd30d782bcfb29589957d50eecca4b4839"),
    ("deepseek_v3", "full"): (
        "27b0a79e295b923cd28c1500222bfbc08f24885e7536dd00788576faa8fa2fbc",
        "80259fb632cdea7eb743ab44b67d71fd30d782bcfb29589957d50eecca4b4839"),
    # re-pinned at PR 45 too: the mixer's convolution is two kernel
    # calls (``ops/short_conv.py``) where it was XLA's passes; and at
    # PR 49: ``in_proj`` is a product a part of its one weight's columns
    # (``_SplitDense``), and the gated norm rounds its own result
    # (``ops/gate_norm.py``; ``tiny``'s groups of 32 take its ``jnp`` form)
    ("nemotron_h", ""): (
        "2846209a4d803512d7670d682c347d46405025590a4df004e1b1e50e5fb953c3",
        "dc97618ee45dda61ed25a95141a3b81a3c70d427ead3341d15b02ac03fd1409e"),
    ("nemotron_h", "full"): (
        "c6f1fbc9edb2fbbe41dc0ca5447cc0036d02f5091953c801ef59e336f2b5cfc2",
        "dc97618ee45dda61ed25a95141a3b81a3c70d427ead3341d15b02ac03fd1409e"),
    # Mellum and Xing (``TINY`` below) pinned at PR 58, taken at its
    # PARENT (f9d52de): that PR gave ``fused_rmsnorm`` an offset,
    # ``short_conv`` a call without bias, the routed models a count of
    # expert layers apart from their depth argument and the flash
    # kernels a narrower key block for heads wider than 128, and none of
    # it may reach a model that asks for none of it
    ("mellum", ""): (
        "38e915539769072f645e97eec15f7234ebdb02b5bc12600000fc555a9486b18b",
        "1d016bbb7db2f4c6809d6af7d6dc444d95d85988192687da423def682d25531a"),
    ("mellum", "full"): (
        "4a4d04a044c2a10d1c11a49695082c37cf61a6b3b8ef1f530408d9046ca192ed",
        "1d016bbb7db2f4c6809d6af7d6dc444d95d85988192687da423def682d25531a"),
    ("xing", ""): (
        "14988363e09b83234e21b9d070c46ea7bc3973f5d7bc287f6b30318c49d47f47",
        "161d9e86a1b94fa03254ce3a232d9e728bfeb11661576488b2e4d18d10d760f3"),
    ("xing", "full"): (
        "885210fcf12b9151bdc75b1b98fd7b41f55e912d4ae06cd99aa40a84d1ba769a",
        "161d9e86a1b94fa03254ce3a232d9e728bfeb11661576488b2e4d18d10d760f3"),
}

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(.*"
    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
#: what a backend puts in for itself under the name of whatever stood
#: there (the CPU rounds bfloat16 through float32 around every op):
#: no work of the program's
_PLUMBING = {"convert", "constant", "bitcast", "copy", "tuple",
             "get-tuple-element", "parameter"}


@functools.lru_cache(maxsize=None)
def compiled(name, walking=False):
    """``(instructions [(opcode, op_name)], the trace's step.scopes
    rows)`` of the model's tiny step under ``remat="full"``.
    ``walking``: the routed layer's sums over tokens as on a TPU, the
    walk of the landed pairs, its kernel through the interpreter (whose
    ops are then the program's own, under the names the trace gave)."""
    if walking:
        walk = gm._walk_pallas
        with mock.patch.object(gm, "_walks", lambda *_: True), \
                mock.patch.object(gm, "_walk_pallas", lambda *a:
                                  walk(*a[:-1], True)):
            return compiled.__wrapped__(name)
    module, config, model_cls = MODELS[name]
    cfg = config.tiny(remat="full")
    model = model_cls(cfg)
    params = meta.unbox(model.init_params(jax.random.PRNGKey(0), batch=2))
    tx = optax.adamw(1e-3)
    train_step = module.make_train_step(model, tx)
    tokens = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
    telemetry.drain_spans("test")
    text = train_step.lower(params, tx.init(params), tokens) \
        .compile().as_text()
    rows = [r for r in telemetry.drain_spans("test")
            if (r["cat"], r["name"]) == ("model", "step.scopes")]
    found = [m.groups() for m in map(_INSTRUCTION.match, text.splitlines())
             if m]
    # instructions of the step's own: a reduction's or a sort's little
    # computations carry bare names
    return [(op, n) for op, n in found if n.startswith("jit(")], rows


def parts_of(name):
    (row,) = compiled(name)[1]
    return row["args"]["parts"].split(",")


def all_parts(op_name, parts):
    """EVERY part among an op's components (the reader takes the
    outermost: an op has to sit under one)."""
    return {p for piece in scopes.components(op_name) for p in parts
            if piece == p or piece.startswith(p + ".")}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_span_says_the_list_once_a_trace(name):
    instructions, rows = compiled(name)
    assert len(rows) == 1
    assert rows[0]["args"] == {"parts": ",".join(step.PARTS),
                               "attn_pieces": ",".join(step.ATTN_PIECES),
                               "remat": "full",
                               # which head the step was built with
                               "head": fused.HEAD_GRADIENT}
    assert fused.HEAD_GRADIENT == "grad_in_forward"
    parts = parts_of(name)
    seen = {scopes.part(n, parts) for _, n in instructions} - {None}
    assert seen == SEEN[name]
    # the program's rule and the reader's are one rule
    for _, n in instructions:
        mine = [step.part_of(c) for c in scopes.components(n)]
        assert next((p for p in mine if p), None) == scopes.part(n, parts)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_product_sits_under_exactly_one_part(name):
    instructions, _ = compiled(name)
    parts = parts_of(name)
    products = [(op, n) for op, n in instructions
                if op in ("dot", "convolution", "custom-call")]
    assert len(products) >= 10
    astray = [(op, n) for op, n in products
              if len(all_parts(n, parts)) != 1]
    assert not astray, astray[:5]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_under_two_percent_of_the_ops_sit_under_no_part(name):
    instructions, _ = compiled(name)
    parts = parts_of(name)
    work = [n for op, n in instructions if op not in _PLUMBING]
    bare = [n for n in work if scopes.part(n, parts) is None]
    assert len(work) > 500
    assert len(bare) < 0.02 * len(work), (
        len(bare), len(work), sorted(set(bare))[:10])
    # and no op is under two: what the routed layer adds stands BESIDE
    # ``mlp``, ``moe.plan`` beside ``moe.route``
    assert not [n for n in work if len(all_parts(n, parts)) > 1]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_phases_are_told_by_jaxs_own_markers(name):
    instructions, _ = compiled(name)
    parts = parts_of(name)
    by_phase = {}
    for _, n in instructions:
        by_phase.setdefault(scopes.phase(n), []).append(n)
    assert set(by_phase) >= {"forward", "recompute", "backward",
                             "optimizer"}
    # the update is outside the gradient
    assert not [n for n in by_phase["optimizer"]
                if "jvp(" in n or "transpose(" in n]
    # the marker the reader keys on, under remat="full": the forward run
    # again is inside the backward pass, and the parts are told in it
    recomputed = by_phase["recompute"]
    assert all("transpose(" in n and "/rematted_computation/" in n
               for n in recomputed)
    # (a part's last op is not run again where no gradient needs it)
    # (nor is a routed call's plan: a decision is kept, ``step.remat``)
    # (nor the head: its gradient is made in the forward's own scan)
    assert {scopes.part(n, parts) for n in recomputed} >= \
        SEEN[name] - {"embed", "optimizer", "moe.combine", "ssm.out_proj",
                      "moe.plan", "head"}
    assert not {"moe.plan", "head"} & {scopes.part(n, parts)
                                       for n in recomputed}
    # the head's products are the forward's, all three
    head = {ph: [n for n in by_phase[ph] if scopes.part(n, parts) == "head"]
            for ph in ("forward", "backward")}
    assert any("while" in n or "dot_general" in n for n in head["forward"])
    assert not [n for n in head["backward"]
                if "while" in n or "dot_general" in n]
    # a backward op never passes for a forward one
    assert not [n for n in by_phase["forward"] if "transpose(" in n]


@pytest.mark.parametrize("name", sorted(set(MODELS) - {"gpt2"}))
def test_the_plan_is_not_under_the_router(name):
    instructions, _ = compiled(name)
    planned = [n for _, n in instructions if "moe.plan" in n]
    assert planned
    assert not [n for n in planned if "moe.route" in n]
    routed = [n for _, n in instructions if "moe.route" in n]
    # the router's product and its top-k stay the router's
    assert any(n.endswith("/dot_general") for n in routed)
    assert any("top_k" in n for n in routed)
    # a custom_vjp's backward lands under its forward's part
    parts = parts_of(name)
    assert [n for _, n in instructions if "transpose(" in n
            and "rematted_computation" not in n
            and scopes.part(n, parts) == "moe.dispatch"]


@pytest.mark.parametrize("name", sorted(set(MODELS) - {"gpt2"}))
def test_every_op_of_the_walk_sits_under_combine_or_dispatch(name):
    """The step whose sums over tokens walk the landed pairs (a TPU's)
    against the step that gathers: what the walk brings, the kernel's
    body and the table it is given, is all under ``moe.combine``
    (forward, and ``d_w`` in the backward pass) and ``moe.dispatch``
    (backward); under every other part, and under none, there is no op
    more."""
    parts = parts_of(name)

    def by_part(walking):
        counts = {}
        # (the gathers' program under the key the other tests hold it by)
        for op, n in (compiled(name, True) if walking else compiled(name))[0]:
            if op not in _PLUMBING:
                key = (scopes.part(n, parts), scopes.phase(n))
                counts[key] = counts.get(key, 0) + 1
        return counts

    gathers, walks = by_part(False), by_part(True)
    for key in (("moe.combine", "forward"), ("moe.combine", "backward"),
                ("moe.dispatch", "backward")):
        assert walks[key] > gathers[key] + 100, key
    # no part but those two gains an op (the CPU's own plumbing around
    # them may go: fewer, never more); under NO part a handful at most:
    # a fusion the CPU makes at a ``checkpoint``'s edge is named after
    # the edge (``h0/remat2``), two a layer where the edge's neighbour
    # became a kernel's result
    assert set(walks) == set(gathers)
    grew = {key for key in walks if walks[key] > gathers[key]}
    assert {part for part, _ in grew} - {None} == {"moe.combine",
                                                   "moe.dispatch"}
    bare = [sum(v for (part, _), v in c.items() if part is None)
            for c in (gathers, walks)]
    assert bare[1] <= bare[0] + 8 and bare[1] < 0.02 * sum(walks.values())
    work = [n for op, n in compiled(name, True)[0] if op not in _PLUMBING]
    assert not [n for n in work if len(all_parts(n, parts)) > 1]


@pytest.mark.parametrize("name,remat", sorted(BEFORE))
def test_the_program_is_what_it_was(name, remat):
    """Parameter paths and the jaxpr as before the scopes (GPT-2 at
    another size: ``tests/test_parallel.py`` ``STEP_BEFORE``)."""
    module, tiny, model_cls = TINY[name]
    cfg = tiny(remat=remat)
    model = model_cls(cfg)
    tx = optax.adamw(1e-3)
    params = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    args = (params, jax.eval_shape(tx.init, params),
            jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(module.make_train_step(model, tx))(*args))
    # (a ``checkpoint`` with a policy prints the function's address)
    text = re.sub(r" at 0x[0-9a-f]+>", ">", text)
    paths = "\n".join(sorted(
        jax.tree_util.keystr(k) + str(v.shape) + str(v.dtype)
        for k, v in jax.tree_util.tree_leaves_with_path(params)))
    assert (hashlib.sha256(text.encode()).hexdigest(),
            hashlib.sha256(paths.encode()).hexdigest()) == \
        BEFORE[name, remat]


# --------------------------------------------------------------------------
# the part ``attn``, one level down: its pieces
# --------------------------------------------------------------------------

#: every model file's tiny configuration: the four above, Mellum, Ouro,
#: and ``deepseek_v3`` as Xing runs it (a query latent, YaRN, four lanes)
TINY = {**{name: (module, config.tiny, cls)
           for name, (module, config, cls) in MODELS.items()},
        "mellum": (mellum, mellum.MellumConfig.tiny, mellum.Mellum),
        "ouro": (ouro, ouro.OuroConfig.tiny, ouro.Ouro),
        "xing": (deepseek_v3, functools.partial(
            deepseek_v3.DeepseekV3Config.tiny, q_lora_rank=16,
            yarn_factor=64.0, rope_theta=1e4, hc_mult=4,
            num_shared_experts=1, route_scale=2.0),
            deepseek_v3.DeepseekV3),
        # GPT-2 with two heads of 64: the native-layout kernels
        "gpt2_native": (gpt2, functools.partial(gpt2.GPT2Config.tiny,
                                                embed_dim=128), gpt2.GPT2)}
PIECED = sorted(set(TINY) - {"gpt2_native"})
#: a kernel family -> a tiny step that calls it
FAMILIES = {"native": "gpt2_native", "head_major": "gpt2",
            "latent": "deepseek_v3"}


def _equations(jaxpr, prefix=""):
    """``(primitive, name)`` of every equation, those of inner jaxprs
    under the names the lowering would give them: the outer equation's
    name stack before theirs, ``jit(<name>)`` at an inner ``jit``.  A
    kernel's body is the kernel's."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        name = "/".join(x for x in (
            prefix, str(eqn.source_info.name_stack)) if x)
        inner = [] if prim == "pallas_call" else \
            list(jax.core.jaxprs_in_params(eqn.params))
        if prim in ("jit", "pjit"):
            name += f"/jit({eqn.params['name']})"
        for sub in inner:
            yield from _equations(getattr(sub, "jaxpr", sub), name)
        if not inner:
            yield prim, f"{name}/{prim}"


@functools.lru_cache(maxsize=None)
def traced(name):
    """``(equations [(primitive, name)], the trace's step.scopes rows)``
    of the model's tiny step under ``remat="full"``, traced as for a TPU
    (the kernel calls there, not their ``jnp`` form).  The jaxpr, not
    its lowering: the Nemotron mixer's kernels do not lower at their
    tiny shapes, and a name is the trace's to give."""
    module, tiny, model_cls = TINY[name]
    cfg = tiny(remat="full")
    model = model_cls(cfg)
    tx = optax.adamw(1e-3)
    params = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    args = (params, jax.eval_shape(tx.init, params),
            jax.ShapeDtypeStruct((2, cfg.max_seq_len), jnp.int32))
    telemetry.drain_spans("test")
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jaxpr = jax.make_jaxpr(module.make_train_step(model, tx))(*args)
    rows = [r for r in telemetry.drain_spans("test")
            if (r["cat"], r["name"]) == ("model", "step.scopes")]
    return list(_equations(jaxpr.jaxpr)), rows


def all_pieces(op_name):
    """EVERY piece among an op's components (the reader takes the
    innermost: off a ``shard_map`` an op sits under one)."""
    return {c[len("attn."):] for c in scopes.components(op_name)
            if c.startswith("attn.")
            and c[len("attn."):] in step.ATTN_PIECES}


def under_attn(instructions):
    return [(op, n) for op, n in instructions
            if scopes.part(n, step.PARTS) == "attn"]


@pytest.mark.parametrize("name", PIECED)
def test_the_span_says_the_pieces_beside_the_parts(name):
    _, rows = traced(name)
    assert len(rows) == 1
    assert rows[0]["args"]["attn_pieces"] == ",".join(step.ATTN_PIECES)
    assert pieces.step_pieces(rows) == list(step.ATTN_PIECES)
    assert scopes.step_parts(rows) == list(step.PARTS)


@pytest.mark.parametrize("name", PIECED)
def test_every_op_under_attn_sits_under_one_piece(name):
    said = list(step.ATTN_PIECES)
    found = [traced(name)[0]]
    if name in MODELS:  # and in the step the CPU compiled (the jnp form)
        found.append([(op, n) for op, n in compiled(name)[0]
                      if op not in _PLUMBING])
    for instructions in found:
        work = under_attn(instructions)
        assert len(work) > 100
        assert not [n for _, n in work if len(all_pieces(n)) > 1]
        bare = [n for _, n in work if pieces.piece(n, said) is None]
        assert len(bare) < 0.02 * len(work), (
            len(bare), len(work), sorted(set(bare))[:10])
        seen = {pieces.piece(n, said) for _, n in work} - {None}
        assert seen >= {"norm", "proj", "kernel"}
        assert ("gate" in seen) == (name in ("afmoe", "qwen3_next"))
        assert ("pos" in seen) == (name not in ("gpt2", "nemotron_h"))
    # what the kernels' file does around its calls: where they are called
    assert "layout" in {pieces.piece(n, said)
                        for _, n in under_attn(found[0])}
    # no piece outside the part: ``hc.*`` stay beside ``attn``
    assert not [n for _, n in found[0] if all_pieces(n)
                and scopes.part(n, step.PARTS) != "attn"]


@pytest.mark.parametrize("name", PIECED)
def test_every_product_under_attn_is_a_projection_or_the_kernels(name):
    said = list(step.ATTN_PIECES)
    products = [n for op, n in under_attn(traced(name)[0])
                if op == "dot_general"]
    assert len(products) >= 10
    assert {pieces.piece(n, said) for n in products} == {"proj"}
    if name in MODELS:  # the jnp form stands for the kernels
        products = [n for op, n in under_attn(compiled(name)[0])
                    if op in ("dot", "convolution")]
        assert {pieces.piece(n, said) for n in products} <= {"proj",
                                                              "kernel"}
        assert [n for n in products if "/attn.proj/" in n]
    # a latent projection's plain name stands inside the piece
    latent = [n for _, n in traced(name)[0] if "/mla." in n]
    assert bool(latent) == (name in ("deepseek_v3", "xing"))
    assert {pieces.piece(n, said) for n in latent} <= {"proj"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_kernel_call_is_kernel_and_what_is_around_it_layout(family):
    assert (fa._LAYOUT, fa._KERNEL) == ("attn.layout", "attn.kernel")
    assert {"layout", "kernel"} <= set(step.ATTN_PIECES)
    said = list(step.ATTN_PIECES)
    made_here = [(op, n) for op, n in under_attn(
        traced(FAMILIES[family])[0]) if "/jit(_flash_" in n]
    builders = {c for _, n in made_here for c in scopes.components(n)
                if c.startswith("jit(_flash_")}
    assert builders == {
        "native": {"jit(_flash_nl_forward)", "jit(_flash_nl_backward)"},
        "head_major": {"jit(_flash_forward)", "jit(_flash_backward)"},
        "latent": {"jit(_flash_mla_forward)", "jit(_flash_mla_backward)"},
    }[family]
    calls = [n for op, n in made_here if op == "pallas_call"]
    # a call of a layer: forward, recomputed forward, dK/dV and dQ
    by_phase = {}
    for n in calls:
        by_phase[scopes.phase(n)] = by_phase.get(scopes.phase(n), 0) + 1
    forward = by_phase["forward"]
    assert forward >= 2 and by_phase == {
        "forward": forward, "recompute": forward, "backward": 2 * forward}
    assert {pieces.piece(n, said) for n in calls} == {"kernel"}
    moved = [n for op, n in made_here if op == "transpose"]
    assert bool(moved)
    assert {pieces.piece(n, said) for n in moved} == {"layout"}
    # and nothing made here is anything else
    assert {pieces.piece(n, said) for _, n in made_here} == {"layout",
                                                             "kernel"}
    # any other kernel call under the part is a fused norm's
    other = [n for op, n in under_attn(traced(FAMILIES[family])[0])
             if op == "pallas_call" and n not in calls]
    assert {pieces.piece(n, said) for n in other} <= {"norm"}


def test_a_scope_under_attn_is_a_piece_or_a_kind():
    with pytest.raises(ValueError):
        step.scope("attn.rotate")
    with step.scope("attn.pos"), step.scope("attn.mla"):
        pass
    for piece in step.ATTN_PIECES:
        assert step.part_of("attn." + piece) == "attn"
    assert not set(step.ATTN_PIECES) & set(step.ATTN_KINDS)


def test_a_scope_that_is_no_part_is_refused():
    with pytest.raises(ValueError):
        step.scope("attention")
    assert step.part_of("attn.mla") == "attn"
    assert step.part_of("mla.kv_up") is None and step.part_of("moe") is None
    with step.scope("moe.plan"):
        pass
