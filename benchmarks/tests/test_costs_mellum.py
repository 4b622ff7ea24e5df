"""``benchmarks/costs_mellum.py`` against counts made by hand, and against
the program's own parameter tree; the exchange reader on a hand-made
trace; and the new entries of ``BENCHMARK.json`` against what ISSUE 51
fixes of them."""

import json
import os

import pytest

from benchmarks import costs_afmoe, costs_mellum as cm

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mellum2-12b-a2.5b.ep4.steady"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


def test_parameters_by_hand_and_as_the_file_states(conf):
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert cm.attention_params(conf) == attn == 21_233_664
    outside = attn + 2 * 2304 + 2304 * 64
    expert = 3 * 2304 * 896
    assert (outside, expert, 64 * expert) == (21_385_728, 6_193_152,
                                              396_361_728)
    layer = outside + 64 * expert
    assert layer == 417_747_456
    total = 4 * layer + 2 * 98304 * 2304 + 2304
    assert cm.num_params(conf) == total == conf["as_run"]["parameters"] \
        == 2_123_976_960
    assert total * 16 == conf["as_run"]["state_bytes"]
    # the fullest chip holds at least 8.5 GB of state
    assert conf["as_run"]["state_bytes_a_chip"] == total * 16 // 4 >= 8.49e9
    assert conf["reduced"] == ["num_hidden_layers"]
    for key, value in conf["published"].items():
        if key != "num_hidden_layers":
            assert conf[key] == value, key


def test_parameters_are_the_program_s_tree(conf):
    jax = pytest.importorskip("jax")
    import dataclasses

    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import mellum as ref

    entry = conf["entry"]
    cfg = resolve(entry["config"])(**entry["config_args"])
    one = resolve(entry["model"])(dataclasses.replace(
        cfg, **{entry["depth_arg"]: 1}))
    tree = meta.unbox(ref.expand_layers(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), batch=1, seq=128)),
        conf["n_layer"]))
    assert sum(int(a.size) for a in jax.tree.leaves(tree)) \
        == cm.num_params(conf)
    assert cfg.layer_kinds() == conf["as_run"]["layer_kinds"]
    assert cfg.routed_tokens == conf["as_run"]["routed_tokens"]


def test_flops_a_token_by_hand(conf):
    proj = 2 * 21_233_664
    # a window of 1,024 of 8,192: 959.9 visible keys a query on average;
    # the full layer 4,096.5
    sliding = 4 * 4096 * (1024 * 1025 // 2 + (8192 - 1024) * 1024) / 8192
    full = 4 * 4096 * (8192 * 8193 // 2) / 8192
    moe = 2 * (2304 * 64 + 3 * 2304 * 896 * 8)
    head = 2 * 98304 * 2304
    forward = 4 * (proj + moe) + 3 * sliding + full + head
    assert cm.train_flops_per_token(conf, 8192) == pytest.approx(
        3 * forward)
    # required: 3.40 GFLOP a token (ISSUE 51's "4.0 executed" is near
    # the 4.5 with the recomputed forward), of which the head 40%
    assert 3.39e9 < 3 * forward < 3.41e9 and 4 * forward > 4.5e9
    assert 0.39 < head / forward < 0.41


def test_flash_and_grouped_products_a_step_on_one_chip(conf):
    flash = cm.flash_step_cost(conf, 2, 8192, remat=True)
    assert flash["calls"] == 4 * 2 * 4
    one = costs_afmoe.flash_call_cost("fwd", 1, 8192, 32, 4, 128, 1024)
    assert one["flops"] == 2 * 2.0 * 32 * costs_afmoe.visible_pairs(
        8192, 1024) * 128
    gmm = cm.gmm_step_cost(conf, 2, 8192, remat=True)
    # a call: the four chips' 4,096 tokens x 8 choices, a quarter arrives
    assert cm.arrived_rows(conf, 8192) == 32768
    assert cm.routed_calls(conf, 2, 8192) == 16
    assert gmm["calls"] == 16 * 3 * 4 and gmm["rows"] == 16 * 32768
    assert gmm["flops"] == 16 * 3 * 4 * 2.0 * 32768 * 2304 * 896
    # the whole step's routed FLOPs on a chip: its own tokens' worth
    assert gmm["flops"] / 4 * 3 == pytest.approx(
        3 * 2 * 3 * 2304 * 896 * 8 * 4 * 16384)


def test_exchange_bytes_by_hand_and_as_the_program_says(conf):
    one = cm.exchange_call_bytes(conf, 8192)
    assert one == {"gather": 3 * 4096 * (2304 * 2 + 8 * 8),
                   "scatter": 3 * 4096 * 2304 * 2}
    step = cm.exchange_step_bytes(conf, 2, 8192, remat=True)
    back = one["scatter"] + one["gather"] - 3 * 4096 * 8 * 4
    assert step["bytes"] == 16 * (2 * (one["gather"] + one["scatter"])
                                  + back)
    # ISSUE 51's reckoning: "some 5.4 GB a chip a step"
    assert 5.4e9 < step["bytes"] < 5.5e9
    pytest.importorskip("jax")
    from ray_tpu.parallel import expert

    said = expert.exchange_bytes(4, 4096, 2304, 8, 2)
    assert (said["gather_bytes"], said["scatter_bytes"]) == (
        one["gather"], one["scatter"])


def test_the_exchange_s_collectives_are_told_by_the_program_s_names():
    from benchmarks.reduce import exchange

    parts = ["attn", "mlp", "moe.route", "moe.exchange", "moe.combine",
             "head"]
    g = "jit(train_step)/jvp(M.hidden)/h0/moe/moe._exchanged/shard_map/"
    ops = [
        ("%all-gather.1 = bf16[16384,2304] all-gather(%x)", 0.0, 10.0),
        ("%fusion.1 = bf16[8,8] fusion(%y)", 4.0, 20.0),
        ("%all-gather.2 = f32[2304,4096] all-gather(%w)", 20.0, 25.0),
        ("%collective-permute-done.3 = bf16[384,2304] "
         "collective-permute-done(%s)", 39.0, 40.0),
    ]
    flights = [("%collective-permute-start.3 = (bf16[384,2304]) "
                "collective-permute-start(%z)", 30.0, 40.0),
               ("%collective-permute-start.9 = (bf16[8,8]) "
                "collective-permute-start(%q)", 41.0, 45.0)]
    facts = {ops[0][0]: {"tf_op": g + "moe.exchange/all_gather:"},
             ops[1][0]: {"tf_op": g + "moe.combine/mul:"},
             ops[2][0]: {"tf_op": "jit(train_step)/jvp(M.hidden)/h0/attn/"
                                  "dot_general:"}}
    # the permutes carry no name: they read combine's result, or the head
    neighbours = {"collective-permute-start.3": g + "moe.combine/mul",
                  "collective-permute-done.3": g + "moe.combine/mul",
                  "collective-permute-start.9": "jit(train_step)/head/add"}
    got = exchange.split(ops, flights, facts, (0.0, 100.0), parts,
                         "moe.exchange", neighbours)
    assert got["ops"] == 3 and got["ns"] == 20.0
    # the fusion covers 4..20 of the gather's 0..10
    assert got["exposed_ns"] == 4.0 + 10.0
    assert exchange.split(ops, flights, facts, (0.0, 100.0), parts,
                          "moe.exchange")["ns"] == 10.0
    assert exchange.of_run(None, {}, "moe.exchange") is None


def test_benchmark_json_has_the_cell_issue_51_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    cells = {w["name"]: w for w in b["workloads"]}
    # a count of entries is not pinned: a later PR adds its own
    four = [w["name"] for w in b["workloads"] if w["chips"] == 4]
    assert four[:2] == ["gpt2-xl.fsdp4.steady", CELL]
    assert len(four) <= max(1, len(cells) // 4)
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-a2.5b", "steady", 4)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers"]
    metrics = {m["name"]: m for m in b["end_to_end"] + b["per_layer"]}
    for name in ("part_moe_exchange_ms", "moe_exchange_gbps",
                 "gmm_roofline.mellum", "swa_flash_roofline.mellum",
                 "model_flops_util.mellum", "gmm_ms.mellum"):
        assert metrics[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"))
    for name in ("tokens_per_s_per_chip", "step_ms_p90", "collective_ms", "collective_exposed_ms", "swa_flash_ms",
                 "part_moe_route_ms", "part_moe_plan_ms",
                 "part_moe_dispatch_ms", "part_moe_experts_ms",
                 "part_moe_combine_ms", "scope_unnamed_share"):
        assert CELL in metrics[name]["workloads"]
    # the accepted reader tells kernels by result shapes, and here a
    # norm of 4,096 rows would read as a grouped product (PERF.md s. 7)
    assert CELL not in metrics["gmm_ms"]["workloads"]
    # arguments + temporaries + outputs - aliased reads 18.8 GiB here, on
    # a chip of 15.75: the temporaries it adds up never live together.
    # The compiler's own peak is pinned where the step is compiled for
    # the described chips (tests/test_chip_compile.py)
    assert CELL not in metrics["step_hbm_gib"]["workloads"]
