"""``benchmarks/costs_deepseek_v3.py`` against counts made by hand, and
against the program's own parameter tree; and the new entries of
``BENCHMARK.json`` against what ISSUE 33 fixes of them."""

import json
import os

import pytest

from benchmarks import costs_deepseek_v3 as cd

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kanana-2-30b-a3b.steady"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


def test_parameters_by_hand_and_as_the_file_states(conf):
    attn = (2048 * 32 * 192 + 2048 * (512 + 64) + 512 * 32 * 256
            + 32 * 128 * 2048)
    assert cd.attention_matrices(conf) == attn == 26_345_472
    norms = 2 * 2048 + 512
    dense = attn + norms + 3 * 2048 * 6144
    expert = (attn + norms + 3 * 2048 * 1536 + 2048 * 128
              + 16 * 3 * 2048 * 768)
    assert (dense, expert) == (64_098_816, 111_546_880)
    total = dense + 5 * expert + 2 * 16032 * 2048 + 2048
    assert cd.num_params(conf) == total == conf["as_run"]["parameters"]
    # the state a deployment would hold: at least the fallback's 9.2 GB
    assert total * 16 == conf["as_run"]["state_bytes"] >= 9.2e9


def test_parameters_are_the_program_s_tree(conf):
    jax = pytest.importorskip("jax")
    import dataclasses

    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import deepseek_v3 as ref

    entry = conf["entry"]
    cfg = resolve(entry["config"])(**entry["config_args"])
    one = resolve(entry["model"])(dataclasses.replace(
        cfg, **{entry["depth_arg"]: 1}))
    tree = meta.unbox(ref.expand_layers(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), batch=1, seq=128)),
        conf["n_layer"]))
    assert sum(int(a.size) for a in jax.tree.leaves(tree)) \
        == cd.num_params(conf)


def test_flops_a_token_by_hand(conf):
    proj = 2 * 26_345_472
    # 8,192.5 visible keys a query on average, 192 + 128 a pair a head
    scores = 2 * 32 * (192 + 128) * (16384 * 16385 // 2) / 16384
    expert = 2 * (2048 * 128 + 3 * 2048 * 1536
                  + 3 * 2048 * 768 * 6 * 16 / 128)
    dense = 2 * 3 * 2048 * 6144
    head = 2 * 16032 * 2048
    forward = 6 * (proj + scores) + dense + 5 * expert + head
    assert cd.train_flops_per_token(conf, 16384) == pytest.approx(
        3 * forward)
    assert 167.7e6 < scores < 167.9e6 and 26.4e6 < expert < 26.6e6
    # ISSUE 33's sizing: attention is 63% of the forward pass
    assert 0.62 < 6 * scores / forward < 0.64


def test_flash_call_cost_by_hand():
    # 1 sequence of 4, 2 heads, nope 8 + rope 4, v 6: 10 visible pairs
    f = cd.mla_flash_call_cost("fwd", 1, 4, 2, 8, 4, 6)
    assert f["flops"] == 2.0 * 2 * 10 * (12 + 6)
    # a position: 2 heads x (q 12 + k_nope 8 + v 6 + o 6), k_rope 4 once,
    # bf16; lse f32 a head
    assert f["bytes"] == 4 * ((2 * 32 + 4) * 2 + 2 * 4)
    d = cd.mla_flash_call_cost("dkdv", 1, 4, 2, 8, 4, 6)
    assert d["flops"] == 2.0 * 2 * 10 * (12 + 6 + 6 + 12)
    # q 12, k 8, v 6, do 6 | dk 8, dv 6 a head; k_rope and its gradient
    assert d["bytes"] == 4 * ((2 * 46 + 8) * 2 + 2 * 2 * 4)
    q = cd.mla_flash_call_cost("dq", 1, 4, 2, 8, 4, 6)
    assert q["flops"] == 2.0 * 2 * 10 * (12 + 6 + 12)
    assert q["bytes"] == 4 * ((2 * 44 + 4) * 2 + 2 * 2 * 4)
    # the rotary key is counted once, not once a head: 32 heads cost 32
    # times one head's own part and ONE rotary part
    one = cd.mla_flash_call_cost("fwd", 1, 4, 1, 8, 4, 6)["bytes"]
    many = cd.mla_flash_call_cost("fwd", 1, 4, 32, 8, 4, 6)["bytes"]
    assert many == 32 * (one - 4 * 4 * 2) + 4 * 4 * 2


def test_step_costs_count_the_calls_the_step_makes(conf):
    flash = cd.mla_flash_step_cost(conf, 1, 16384, remat=True)
    assert flash["calls"] == 24 == conf["as_run"]["flash_calls_a_step"]
    one = sum(n * cd.mla_flash_call_cost(k, 1, 16384, 32, 128, 64,
                                         128)["flops"]
              for k, n in (("fwd", 2), ("dkdv", 1), ("dq", 1)))
    assert flash["flops"] == pytest.approx(6 * one)
    assert cd.mla_flash_step_cost(conf, 1, 16384, remat=False)["calls"] == 18
    gmm = cd.gmm_step_cost(conf, 1, 16384, remat=True)
    assert gmm["rows"] == 12288 == cd.expected_live_rows(conf, 16384)
    assert gmm["calls"] == 5 * 3 * 4
    assert gmm["flops"] == 60 * 2.0 * 12288 * 2048 * 768
    assert gmm["bytes"] == 60 * (12288 * (2048 + 768)
                                 + 16 * 2048 * 768) * 2


def test_the_new_entries_are_what_the_issue_fixes():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert len(b["workloads"]) == 6
    assert [w["name"] for w in b["workloads"] if w["chips"] == 4] \
        == ["gpt2-xl.fsdp4.steady"]
    cell = b["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="kanana-2-30b-a3b",
                        traffic="steady", chips=1)
    entry = b["configs"][-1]
    assert entry["name"] == "kanana-2-30b-a3b" and entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    here = {m["name"] for m in b["end_to_end"] + b["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert here == {
        "tokens_per_s_per_chip", "step_ms_p90", "setup_s", "gang_up_s",
        "step_compile_s", "compile_cache_misses", "gang_place_s",
        "gang_spawn_s", "chip_open_s", "step_trace_s", "step_lower_s",
        "step_backend_compile_s", "data_wait_ms", "step_ms_median",
        "dispatch_ms", "step_hbm_gib", "device_idle_share", "gmm_ms",
        "mla_flash_ms", "mla_flash_roofline", "gmm_roofline.dsv3",
        "model_flops_util.dsv3"}
    new = b["per_layer"][-4:]
    assert [m["name"] for m in new] == [
        "mla_flash_ms", "mla_flash_roofline", "gmm_roofline.dsv3",
        "model_flops_util.dsv3"]
    for m in new:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
