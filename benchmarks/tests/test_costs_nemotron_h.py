"""``benchmarks/costs_nemotron_h.py`` against counts made by hand, and
against the program's own parameter tree; and the new entries of
``BENCHMARK.json`` against what ISSUE 35 fixes of them."""

import json
import os

import pytest

from benchmarks import costs_nemotron_h as cn

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nemotron-3-nano-30b-a3b.steady"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def test_parameters_by_hand_and_as_the_file_states(conf):
    w_in, w_out = 2688 * (4096 + 6144 + 64), 4096 * 2688
    assert cn.mixer_matrices(conf) == w_in + w_out == 38_707_200
    mixer = w_in + w_out + 5 * 6144 + 3 * 64 + 2688 + 4096
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    outside = 2688 * 128 + 2 * 2688 * 3712 + 2688
    one_expert = 2 * 2688 * 1856
    assert (mixer, attention, outside, one_expert) == (
        38_744_896, 23_399_040, 20_302_464, 9_977_856)
    total = (4 * mixer + attention + 4 * (outside + 8 * one_expert)
             + 2 * 16384 * 2688 + 2688)
    assert cn.num_params(conf) == total == conf["as_run"]["parameters"] \
        == 666_962_944
    # 10.67 GB of state: 67% of the chip, over the driver's floor of 25%
    assert total * 16 == conf["as_run"]["state_bytes"]
    assert 0.25 * 16e9 < total * 16 < 16e9


def test_parameters_are_the_program_s_tree(conf):
    jax = pytest.importorskip("jax")
    import dataclasses

    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import nemotron_h as ref

    entry = conf["entry"]
    cfg = resolve(entry["config"])(**entry["config_args"])
    one = resolve(entry["model"])(dataclasses.replace(
        cfg, **{entry["depth_arg"]: 1}))
    tree = meta.unbox(ref.expand_layers(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), batch=1, seq=128)),
        conf["n_layer"]))
    assert sum(int(a.size) for a in jax.tree.leaves(tree)) \
        == cn.num_params(conf)


def test_flops_a_token_by_hand(conf):
    scan = cn.scan_flops_per_token(conf)
    # the causal half of a chunk of 128, diagonal in: 64.5 pairs a position
    assert scan == {"cb": 2 * 8 * 64.5 * 128, "scores_x": 2 * 64 * 64.5 * 64,
                    "states": 2 * 64 * 64 * 128, "read_out": 2 * 64 * 64 * 128}
    mixer = 2 * 38_707_200 + sum(scan.values())
    pairs = (8192 + 1) / 2
    attention = 2 * (2 * 2688 * 4096 + 2 * 2688 * 256) + 2 * 32 * 256 * pairs
    expert = 2 * (2688 * 128 + 2 * 2688 * 3712 + 2 * 2688 * 1856 * 6 * 8 / 128)
    forward = 4 * mixer + attention + 4 * expert + 2 * 16384 * 2688
    assert cn.train_flops_per_token(conf, 8192) == pytest.approx(3 * forward)
    assert 3 * forward == pytest.approx(2.145e9, rel=1e-3)
    # the mixers are the largest part, as ISSUE 35 sized the cell
    assert 4 * mixer / forward == pytest.approx(0.45, abs=0.01)


def test_scan_call_costs_by_hand():
    kw = dict(batch=1, seq=8192, heads=64, dim=64, groups=8, state=128,
              chunk=128)
    fwd, bwd = cn.ssd_call_cost("fwd", **kw), cn.ssd_call_cost("bwd", **kw)
    group_sq = 8192 * 8 * 64.5 * 128
    head_sq = 8192 * 64 * 64.5 * 64
    head_pn = 8192 * 64 * 64 * 128
    assert fwd["flops"] == 2 * (group_sq + head_sq + 2 * head_pn)
    assert bwd["flops"] == 2 * (3 * group_sq + 2 * head_sq + 4 * head_pn)
    xs, bc, per_head = 8192 * 4096 * 2, 8192 * 1024 * 2, 8192 * 64 * 4
    assert fwd["bytes"] == 2 * xs + 2 * bc + per_head           # y written
    assert bwd["bytes"] == 3 * xs + 4 * bc + 3 * per_head
    # 20.7 KB a token a layer forward: bound by bytes on paper
    assert fwd["bytes"] / 8192 == pytest.approx(20.7e3, rel=0.01)
    assert fwd["flops"] / 197e12 < fwd["bytes"] / 819e9


def test_a_step_s_scan_calls_and_grouped_products(conf):
    scan = cn.ssd_step_cost(conf, 2, 8192, remat=True)
    assert (scan["fwd"], scan["bwd"], scan["calls"]) == (16, 8, 24)
    one = {k: cn.ssd_call_cost(k, 1, 8192, 64, 64, 8, 128, 128)
           for k in ("fwd", "bwd")}
    for what in ("flops", "bytes"):
        assert scan[what] == pytest.approx(
            16 * one["fwd"][what] + 8 * one["bwd"][what])
    assert cn.ssd_step_cost(conf, 2, 8192, remat=False)["calls"] == 16
    assert conf["as_run"]["ssd_calls_a_step"] == {
        "scan": scan["fwd"], "scan_bwd": scan["bwd"]}
    # a held expert meets 768 tokens a step: 6,144 rows a layer, half a call
    assert cn.expected_live_rows(conf, 8192) == 768 * 8 // 2 == 3072
    gmm = cn.gmm_step_cost(conf, 2, 8192, remat=True)
    # 4 layers x 2 sequences x 2 products x (2 forward, d lhs, d rhs)
    assert gmm["calls"] == 64 and gmm["rows"] == 6144
    assert gmm["flops"] == 64 * 2.0 * 3072 * 2688 * 1856   # 1856: unpadded


def test_the_benchmark_s_new_entries_are_issue_35_s(conf):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == conf["name"]
    assert bench["configs"][-1]["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "steady", 1)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "ssd_ms", "ssd_roofline", "gmm_roofline.nemotron_h",
        "model_flops_util.nemotron_h"]
    assert all(m["moves"] == "tokens_per_s_per_chip" for m in new)
    shared = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", []) and len(m["workloads"]) > 1}
    assert shared == {"tokens_per_s_per_chip", "step_ms_p90", "data_wait_ms",
                      "step_ms_median", "dispatch_ms", "step_hbm_gib",
                      "device_idle_share", "gmm_ms",
                      # the one attention layer's eight flash calls a step
                      # (REVIEW, PR 35): the reader takes every flash call
                      "swa_flash_ms"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


@pytest.mark.parametrize("key", ["hidden_size", "mamba_num_heads",
                                 "mamba_head_dim", "n_groups",
                                 "ssm_state_size", "conv_kernel",
                                 "chunk_size", "num_attention_heads",
                                 "num_key_value_heads", "head_dim",
                                 "moe_intermediate_size",
                                 "moe_shared_expert_intermediate_size",
                                 "num_experts_per_tok"])
def test_no_width_is_cut(conf, key):
    assert conf[key] == conf["published"][key]
