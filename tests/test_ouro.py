"""The looped decoder (``ray_tpu/models/ouro.py``) against the plain
reference (``benchmarks/reference/ouro.py``), at tiny sizes on the CPU:
loss and every gradient, the loop tied to a stack of four times the
layers with weights of their own, the exit distribution, the chunked
head that gives a loss a token, both ``remat`` forms, and what the
``loop.plan`` span and the ``ray_tpu_loop_*`` gauges say."""

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

from benchmarks.reference import ouro as ref  # noqa: E402
from ray_tpu.core import telemetry  # noqa: E402
from ray_tpu.models import ouro  # noqa: E402
from ray_tpu.ops import fused  # noqa: E402

REF_KW = {"query_block": 16, "token_chunk": 16}


def _setup(seed=3, **kw):
    cfg = ouro.OuroConfig.tiny(**{"dtype": jnp.float32, **kw})
    model = ouro.Ouro(cfg)
    shapes = meta.unbox(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), batch=2)))
    params = ref.init_like(shapes, jax.random.PRNGKey(seed))
    # a gate that has an opinion: the initial bias is zero
    params["exit_gate"]["bias"] = jnp.full((1,), 0.3)
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, cfg.max_seq_len), dtype=np.int32)
    sizes = {"n_layer": cfg.num_layers, "n_head": cfg.num_heads,
             "ln_eps": cfg.rms_eps,
             "arch": {"passes": cfg.passes, "rope_theta": cfg.rope_theta,
                      "exit_beta": cfg.exit_beta}}
    return cfg, model, params, tokens, sizes


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_the_tree_is_the_configuration_s_count():
    cfg = ouro.OuroConfig.ouro_2_6b_stage()
    shapes = jax.eval_shape(lambda: ouro.Ouro(cfg).init_params(
        jax.random.PRNGKey(0), seq=128))
    assert sum(a.size for a in jax.tree.leaves(meta.unbox(shapes))) \
        == 509_661_185
    # the published model's constructor takes 48: ISSUE 47's count of
    # its matrices and layer norms, the final norm's 2048 and the gate's
    # 2049 beside it, by the layer this stage holds six of
    layer = sum(a.size for a in jax.tree.leaves(meta.unbox(shapes)["h0"]))
    assert ouro.OuroConfig.ouro_2_6b().num_layers == 48
    assert 48 * layer + 2 * 49152 * 2048 + 2048 + 2049 \
        == 2_667_970_560 + 2048 + 2049


@pytest.mark.parametrize("remat", ["", "full"])
def test_loss_and_every_gradient_match_the_reference(remat):
    cfg, model, params, tokens, sizes = _setup(remat=remat)
    loss, grads = jax.value_and_grad(
        lambda p: ouro.loss_fn(model, p, tokens, head_chunk=32))(params)
    want, g_ref = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, **sizes, **REF_KW))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(ref.grad_error(grads, g_ref)) < 2e-5
    got, wanted = _leaves(grads), _leaves(g_ref)
    assert set(got) == set(wanted)
    for name, g in wanted.items():
        scale = float(jnp.abs(g).max())
        assert scale > 0, name   # every leaf takes a gradient, the gate's too
        np.testing.assert_allclose(got[name], g, atol=2e-4 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("leaf", ["kernel", "bias"])
@pytest.mark.parametrize("chunk", [32, 1024],
                         ids=["padded_tail", "one_chunk"])
def test_the_gate_learns_through_the_head_s_weights(leaf, chunk):
    """The head weighs every (exit, token) by ``p_t / n`` and hands the
    weights ``CE`` for a cotangent: the gate's gradient is the
    reference's, and the loss a reader puts together from
    ``exit_terms`` (the head that recomputes) is the training loss."""
    cfg, model, params, tokens, sizes = _setup()
    loss, got = jax.value_and_grad(
        lambda p: ouro.loss_fn(model, p, tokens, head_chunk=chunk))(params)
    want = jax.grad(
        lambda p: ref.loss(p, tokens, **sizes, **REF_KW))(params)
    g = want["exit_gate"][leaf]
    scale = float(jnp.abs(g).max())
    assert scale > 0
    np.testing.assert_allclose(got["exit_gate"][leaf], g, atol=2e-5 * scale)

    def by_terms(p):
        ce, gate = ouro.exit_terms(model, p, tokens, head_chunk=chunk)
        return ouro.exit_loss(ce, gate, cfg.exit_beta).mean()

    terms, g_terms = jax.value_and_grad(by_terms)(params)
    assert float(loss) == pytest.approx(float(terms), rel=2e-6)
    np.testing.assert_allclose(got["exit_gate"][leaf],
                               g_terms["exit_gate"][leaf],
                               atol=2e-5 * scale)


def test_both_remat_forms_give_one_value():
    values = []
    for remat in ("", "full"):
        cfg, model, params, tokens, _ = _setup(remat=remat)
        values.append(jax.value_and_grad(
            lambda p: ouro.loss_fn(model, p, tokens))(params))
    assert float(values[0][0]) == float(values[1][0])
    for a, b in zip(jax.tree.leaves(values[0][1]),
                    jax.tree.leaves(values[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


def test_the_loop_is_a_stack_of_four_times_the_layers_fed_copies():
    """The looped program at depth L against ``passes x L`` layers laid
    out one after the other, each with weights of its OWN that are
    copies: one loss, and a shared weight's gradient is the SUM of its
    copies' gradients."""
    cfg, model, params, tokens, sizes = _setup()
    L, R = cfg.num_layers, cfg.passes
    copies = [jax.tree.map(jnp.copy, params[f"h{n % L}"])
              for n in range(R * L)]

    def unrolled(copies, rest):
        with jax.default_matmul_precision("highest"):
            toks = jnp.asarray(tokens)
            states = ref.exits(rest, rest["embed"][toks], laid_out=copies,
                               **sizes,
                               **REF_KW)
            labels = jnp.concatenate(
                [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
            each = ref.token_losses(rest, states, labels, cfg.exit_beta, 16)
            return each[:, :-1].sum() \
                / (tokens.shape[0] * (tokens.shape[1] - 1))

    want, g_copies = jax.value_and_grad(unrolled)(copies, params)
    loss, grads = jax.value_and_grad(
        lambda p: ouro.loss_fn(model, p, tokens))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    for i in range(L):
        summed = jax.tree.map(lambda *g: sum(g),
                              *[g_copies[t * L + i] for t in range(R)])
        for (path, got), want_leaf in zip(
                jax.tree_util.tree_flatten_with_path(grads[f"h{i}"])[0],
                jax.tree.leaves(summed)):
            scale = float(jnp.abs(want_leaf).max())
            np.testing.assert_allclose(
                got, want_leaf, atol=2e-4 * scale,
                err_msg=f"h{i}{jax.tree_util.keystr(path)}")
        # and no single copy's gradient is the whole of it
        one = jax.tree.leaves(g_copies[i])[0]
        assert float(jnp.abs(one - jax.tree.leaves(summed)[0]).max()) > 0


def test_the_exit_distribution_sums_to_one_and_the_last_takes_the_rest():
    gate = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7)) * 3.0
    log_p = ouro.exit_log_p(gate)
    p = jnp.exp(log_p)
    assert log_p.shape == (4, 5, 7)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(gate)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    # (the plain product cancels in ``1 - lambda`` where the log does not)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-4,
        atol=1e-7)
    # a gate that never opens leaves everything to the last exit, one
    # that opens at once nothing
    np.testing.assert_allclose(
        jnp.exp(ouro.exit_log_p(jnp.full((3, 1), -40.0)))[:, 0],
        [0, 0, 0, 1], atol=1e-6)
    np.testing.assert_allclose(
        jnp.exp(ouro.exit_log_p(jnp.full((3, 1), 40.0)))[:, 0],
        [1, 0, 0, 0], atol=1e-6)


def test_the_loss_weighs_the_exits_and_pays_for_entropy():
    ce = jnp.array([[4.0], [3.0], [2.0], [1.0]])
    gate = jnp.zeros((3, 1))                       # lambda = 1/2
    p = np.array([0.5, 0.25, 0.125, 0.125])
    entropy = -(p * np.log(p)).sum()
    want = (p * ce[:, 0]).sum() - 0.05 * entropy
    assert float(ouro.exit_loss(ce, gate, 0.05)[0]) == pytest.approx(want)
    # the gate takes a gradient: towards the later, cheaper exits
    g = jax.grad(lambda g: ouro.exit_loss(ce, g, 0.05).sum())(gate)
    assert (np.asarray(g) > 0).all()


#: (tokens [B, T], width, vocabulary): the GPT-2 and the Trinity test
#: models' heads, and a count of tokens no chunk divides
HEADS = {"gpt2_tiny": ((2, 127), 64, 256), "afmoe_tiny": ((2, 63), 32, 256),
         "ragged": ((3, 50), 48, 130)}


@pytest.mark.parametrize("case", sorted(HEADS))
def test_token_losses_mean_is_the_chunked_head_s(case):
    shape, width, vocab = HEADS[case]
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    hidden = jax.random.normal(k[0], (*shape, width))
    emb = 0.3 * jax.random.normal(k[1], (vocab, width))
    labels = jax.random.randint(k[2], shape, 0, vocab)
    for compute in (None, jnp.bfloat16):
        each = fused.chunked_token_loss(hidden, emb, labels, chunk=32,
                                        compute_dtype=compute)
        assert each.shape == shape and each.dtype == jnp.float32
        mean = fused.chunked_lm_loss(hidden, emb, labels, chunk=32,
                                     compute_dtype=compute)
        assert float(each.mean()) == pytest.approx(float(mean), rel=2e-6)


@pytest.mark.parametrize("case", sorted(HEADS))
def test_token_losses_gradient_under_a_cotangent_a_token(case):
    shape, width, vocab = HEADS[case]
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    hidden = jax.random.normal(k[0], (*shape, width))
    emb = 0.3 * jax.random.normal(k[1], (vocab, width))
    labels = jax.random.randint(k[2], shape, 0, vocab)
    weight = jax.random.uniform(k[3], shape)       # a weight a token

    def plain(h, e):
        return fused.fused_softmax_cross_entropy(
            jnp.einsum("bte,ve->btv", h, e), labels)

    np.testing.assert_allclose(
        fused.chunked_token_loss(hidden, emb, labels, chunk=32),
        plain(hidden, emb), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda h, e: (weight * fused.chunked_token_loss(
        h, e, labels, chunk=32)).sum(), argnums=(0, 1))(hidden, emb)
    want = jax.grad(lambda h, e: (weight * plain(h, e)).sum(),
                    argnums=(0, 1))(hidden, emb)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_logits_block_lives_in_one_scan_step():
    hidden = jax.ShapeDtypeStruct((2, 64, 32), jnp.float32)
    emb = jax.ShapeDtypeStruct((4096, 32), jnp.float32)
    labels = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda h, e, y: fused.chunked_token_loss(h, e, y, chunk=16).sum(),
        argnums=(0, 1)))(hidden, emb, labels))
    assert "f32[16,4096]" in text          # a chunk's block
    assert "f32[128,4096]" not in text and "f32[2,64,4096]" not in text


def test_every_op_is_named_by_part_and_pass():
    cfg, model, params, tokens, _ = _setup(remat="full")
    from ray_tpu.models import step

    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: ouro.loss_fn(model, p, tokens)))(params)
    names = set()

    def walk(jp):
        for eqn in jp.eqns:
            names.add(str(eqn.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    flash = [n for n in names if "attn.full" in n]
    assert flash and all("attn/pass" in n for n in flash)
    for t in range(cfg.passes):
        assert any(f"h1/attn/pass{t}" in n for n in names)
        assert any(f"h0/mlp/pass{t}" in n for n in names)
        assert any(f"head/pass{t}" in n for n in names)
    assert any("exit/pass0" in n for n in names)
    assert not any("exit/pass3" in n for n in names)   # the last has no gate
    assert "exit" in step.PARTS
    assert step.PARTS.index("head") < step.PARTS.index("exit") \
        < step.PARTS.index("optimizer")


def test_loop_plan_span_says_what_was_compiled():
    cfg, model, params, tokens, _ = _setup(remat="full")
    telemetry.drain_spans("test")
    jax.eval_shape(lambda p: ouro.loss_fn(model, p, tokens), params)
    rows = [r for r in telemetry.drain_spans("test")
            if r["name"] == "loop.plan"]
    assert len(rows) == 1 and rows[0]["cat"] == "model"
    assert rows[0]["args"] == {
        "passes": 4, "layers": 2, "layer_calls": 8, "head_calls": 4,
        "remat": "part",
        # two parts a layer-call, a float32 state of 2 x 64 x 64 each
        "saved_bytes": 2 * 8 * 2 * 64 * 64 * 4}
    # the cell's: 48 states of 4,096 x 2,048 bf16 a sequence, two of them
    cell = ouro.OuroConfig.ouro_2_6b_stage(remat="full")
    assert cell.plan_args(2, 4096)["saved_bytes"] == 2 * 48 * 16_777_216


def test_exit_stats_reach_the_gauges():
    cfg, model, params, tokens, _ = _setup()
    stats = ouro.exit_stats(model, params, tokens)
    share = np.asarray(stats["exit_share"])
    assert share.shape == (4,) and share.sum() == pytest.approx(1.0)
    assert float(stats["expected_passes"]) == pytest.approx(
        (share * np.arange(1, 5)).sum(), rel=1e-6)
    assert 0 < float(stats["exit_entropy"]) <= np.log(4) + 1e-6
    flat = ouro.report_exit_stats(stats)
    assert set(flat) == {"loop/exit1/share", "loop/exit2/share",
                         "loop/exit3/share", "loop/exit4/share",
                         "loop/expected_passes", "loop/exit_entropy"}
    per = telemetry._gauge("ray_tpu_loop_exit_share", "")
    assert per.tag_keys == ("model", "exit")
    for t in range(4):
        assert per._values[(("model", "ouro"), ("exit", str(t + 1)))] \
            == pytest.approx(share[t])
    key = (("model", "ouro"),)
    assert telemetry._gauge("ray_tpu_loop_expected_passes", "")._values[
        key] == pytest.approx(flat["loop/expected_passes"])
    assert telemetry._gauge("ray_tpu_loop_exit_entropy", "")._values[
        key] == pytest.approx(flat["loop/exit_entropy"])


def test_the_cut_configuration_is_the_file_s():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        conf = json.load(f)
    cfg = ouro.OuroConfig.ouro_2_6b_stage(**conf["entry"]["config_args"])
    assert (cfg.embed_dim, cfg.num_layers, cfg.num_heads, cfg.max_seq_len,
            cfg.vocab_size) == tuple(conf[k] for k in (
                "n_embd", "n_layer", "n_head", "n_positions", "vocab_size"))
    assert (cfg.passes, cfg.head_dim, cfg.mlp_dim, cfg.rope_theta,
            cfg.rms_eps, cfg.exit_beta) == (
        conf["total_ut_steps"], conf["head_dim"], conf["intermediate_size"],
        conf["rope_theta"], conf["rms_norm_eps"],
        conf["assumed"]["exit_beta"])
    assert conf["published"]["num_hidden_layers"] == 48 \
        == ouro.OuroConfig.ouro_2_6b().num_layers
