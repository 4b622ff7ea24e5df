"""``benchmarks/costs_afmoe.py`` against counts made by hand, and
against the program's own parameter tree."""

import json
import os

import pytest

from benchmarks import costs_afmoe as ca

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def test_visible_pairs_by_hand():
    # 4 positions, window 2: (0,0) (1,0) (1,1) (2,1) (2,2) (3,2) (3,3)
    assert ca.visible_pairs(4, 2) == 7
    assert ca.visible_pairs(4, None) == 10 == ca.visible_pairs(4, 9)
    assert ca.visible_pairs(4, 1) == 4
    # the cell: four windows of 2048 in 8192 positions
    assert ca.visible_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048
    share = ca.visible_pairs(8192, 2048) / ca.visible_pairs(8192, None)
    assert 0.43 < share < 0.44  # a sliding layer's share of a full one's


def test_parameters_by_hand_and_as_the_file_states(conf):
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
    dense = attn + 3 * 2048 * 6144
    expert = attn + 3 * 2048 * 1024 + 2048 * 128 + 16 * 3 * 2048 * 1024
    total = dense + 4 * expert + 2 * 25024 * 2048 + 2048
    assert ca.attention_params(conf) == attn
    assert ca.num_params(conf) == total == conf["as_run"]["parameters"]
    assert total * 16 == conf["as_run"]["state_bytes"] >= 10e9


def test_parameters_are_the_program_s_tree(conf):
    jax = pytest.importorskip("jax")
    import dataclasses

    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import afmoe as ref

    entry = conf["entry"]
    cfg = resolve(entry["config"])(**entry["config_args"])
    one = resolve(entry["model"])(dataclasses.replace(
        cfg, **{entry["depth_arg"]: 1}))
    tree = meta.unbox(ref.expand_layers(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), batch=1)),
        conf["n_layer"]))
    count = sum(int(a.size) for a in jax.tree.leaves(tree))
    assert count == ca.num_params(conf)


def test_flops_a_token_by_hand(conf):
    proj = 2 * (3 * 2048 * 4096 + 2 * 2048 * 512)
    sliding = 4 * 4096 * ca.visible_pairs(8192, 2048) / 8192
    full = 4 * 4096 * ca.visible_pairs(8192, None) / 8192
    expert = 2 * (2048 * 128 + 3 * 2048 * 1024 * (1 + 8 * 16 / 128))
    dense = 2 * 3 * 2048 * 6144
    head = 2 * 25024 * 2048
    forward = 5 * proj + 4 * sliding + full + dense + 4 * expert + head
    assert ca.train_flops_per_token(conf, 8192) == pytest.approx(3 * forward)
    assert 29.3e6 < sliding < 29.5e6 and 67.1e6 < full < 67.2e6


def test_flash_call_cost_by_hand():
    # 1 sequence of 4, 2 query heads on 1 K/V head of 8, window 2
    c = ca.flash_call_cost("fwd", 1, 4, 2, 1, 8, 2)
    assert c["flops"] == 2 * 2.0 * 1 * 2 * 7 * 8
    # q, o: 2 x [1,4,2,8]; k, v: 2 x [1,4,1,8]; bf16; lse f32 [1,2,4]
    assert c["bytes"] == (2 * 64 + 2 * 32) * 2 + 8 * 4
    d = ca.flash_call_cost("dkdv", 1, 4, 2, 1, 8, None)
    assert d["flops"] == 4 * 2.0 * 2 * 10 * 8
    assert d["bytes"] == (2 * 64 + 4 * 32) * 2 + 2 * 8 * 4
    q = ca.flash_call_cost("dq", 1, 4, 2, 1, 8, None)
    assert q["flops"] == 3 * 2.0 * 2 * 10 * 8
    assert q["bytes"] == (3 * 64 + 2 * 32) * 2 + 2 * 8 * 4


def test_step_costs_count_the_calls_the_step_makes(conf):
    # the program runs a layer over one sequence at a time: a call a
    # sequence, each over half the batch's rows
    flash = ca.flash_step_cost(conf, 2, 8192, remat=True)
    assert flash["calls"] == 2 * 5 * 4
    one = sum(n * ca.flash_call_cost(k, 2, 8192, 32, 4, 128, 2048)["flops"]
              for k, n in (("fwd", 2), ("dkdv", 1), ("dq", 1)))
    full = sum(n * ca.flash_call_cost(k, 2, 8192, 32, 4, 128, None)["flops"]
               for k, n in (("fwd", 2), ("dkdv", 1), ("dq", 1)))
    assert flash["flops"] == pytest.approx(4 * one + full)
    gmm = ca.gmm_step_cost(conf, 2, 8192, remat=True)
    assert gmm["rows"] == 16384 and gmm["calls"] == 2 * 4 * 3 * 4
    assert gmm["flops"] == 48 * 2.0 * 16384 * 2048 * 1024
    call = ca.gmm_call_cost("fwd", 8192, 2048, 1024, 16)
    assert call["bytes"] == (8192 * 3072 + 16 * 2048 * 1024) * 2
    assert gmm["bytes"] == 96 * call["bytes"]
    # a call a sequence: a batch of one makes half the calls
    assert ca.gmm_step_cost(conf, 1, 8192, remat=True)["calls"] == 48
    assert ca.flash_step_cost(conf, 1, 8192, remat=True)["calls"] == 20
