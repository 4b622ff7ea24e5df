"""``benchmarks/costs_xing.py`` against counts made another way: the
parameters against the program's own tree and the issue's arithmetic,
the FLOPs a token against a sum written out by hand, the kernels' costs
against their siblings' at these widths, and the hyper-connections' least
bytes against the passes the docstring names."""

import json
import os

import pytest

from benchmarks import costs_afmoe, costs_deepseek_v3, costs_xing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_parameters_are_the_file_s_and_the_issue_s(conf):
    assert costs_xing.attention_matrices(conf) + 768 + 512 == 28_411_136
    assert costs_xing.hc_params(conf) == 344_091
    assert costs_xing._layer_params(conf, True) == 128_426_294
    assert costs_xing._layer_params(conf, False) == 128_196_918
    assert costs_xing.num_params(conf) == conf["as_run"]["parameters"] \
        == 759_346_190
    assert conf["as_run"]["state_bytes"] == 16 * 759_346_190
    with_mtp = dict(conf, num_nextn_predict_layers=1)
    assert costs_xing.num_params(with_mtp) - costs_xing.num_params(conf) \
        == 154_127_158


def test_parameters_are_the_program_s_tree(conf):
    import jax
    from flax.core import meta

    from ray_tpu.models import deepseek_v3 as ds

    cfg = ds.DeepseekV3Config.xing4_0_29b_a4b_share()
    shapes = meta.unbox(jax.eval_shape(
        lambda: ds.DeepseekV3(cfg).init_params(jax.random.PRNGKey(0),
                                               seq=128)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == costs_xing.num_params(conf)


def test_flops_a_token_are_the_sum_written_out(conf):
    seq = 4096
    e, heads = 3584, 32
    attn = 2 * (e * 768 + 768 * heads * 192 + e * 576
                + 512 * heads * 256 + heads * 128 * e)
    scores = 2 * heads * (192 + 128) * (seq + 1) / 2
    hc = 2 * 2 * (4 * e * 24 + 4 * e + 16 * e + 4 * e)
    dense = 2 * 3 * e * 9216
    expert = 2 * (e * 64 + 3 * e * 1024 + 3 * e * 1024 * 4 * 8 / 64)
    forward = 5 * (attn + scores + hc) + dense + 4 * expert \
        + 2 * 16384 * e
    assert costs_xing.train_flops_per_token(conf, seq) == pytest.approx(
        3 * forward, rel=1e-12)
    # the lanes' products are a small share of the FLOPs: their cost is
    # bytes
    assert 5 * hc / forward < 0.03


@pytest.mark.parametrize("batch,seq", [(2, 4096), (4, 2048)])
def test_the_kernels_costs_are_their_siblings_at_these_widths(
        conf, batch, seq):
    flash = costs_xing.mla_flash_step_cost(conf, batch, seq, remat=True)
    assert flash["calls"] == 5 * batch * 4
    one = costs_deepseek_v3.mla_flash_call_cost("fwd", 1, seq, 32, 128, 64,
                                                128)
    assert one["flops"] == 2.0 * 32 * (seq * (seq + 1) // 2) * 320
    gmm = costs_xing.gmm_step_cost(conf, batch, seq, remat=True)
    rows = seq * 4 * 8 // 64
    assert costs_xing.expected_live_rows(conf, seq) == rows
    assert gmm["calls"] == 4 * batch * 3 * 4 and gmm["rows"] == batch * rows
    call = costs_afmoe.gmm_call_cost("fwd", rows, 3584, 1024, 8)
    assert gmm["flops"] == pytest.approx(gmm["calls"] * call["flops"])
    # 8,192 tokens a step either way: the same FLOPs in the products
    assert gmm["flops"] == pytest.approx(costs_xing.gmm_step_cost(
        conf, 2, 4096, remat=True)["flops"])


@pytest.mark.parametrize("remat,lanes", [(True, 9 + 4 + 15),
                                         (False, 9 + 15)])
def test_the_connections_least_bytes_are_the_passes_named(conf, remat,
                                                          lanes):
    assert costs_xing.hc_call_lanes(4, remat) == lanes
    got = costs_xing.hc_step_bytes(conf, 2, 4096, remat)
    assert got["calls"] == 2 * 5 * 2
    assert got["bytes"] == 20 * lanes * 4096 * 3584 * 2
    # 8,192 tokens a step either way
    assert costs_xing.hc_step_bytes(conf, 4, 2048, remat)["bytes"] \
        == got["bytes"]
