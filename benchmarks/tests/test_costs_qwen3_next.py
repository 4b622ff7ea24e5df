"""``benchmarks/costs_qwen3_next.py`` against counts made by hand, and
against the program's own parameter tree; the new entries of
``BENCHMARK.json`` against what ISSUE 58 fixes of them; and the reader of
the ops a program put under one name (``reduce/named_ops.py``) on
recorded events."""

import json
import os

import pytest

from benchmarks import costs_qwen3_next as cq
from benchmarks.reduce import named_ops

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "qwen3-next-80b-a3b.steady"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


def test_parameters_by_hand_and_as_the_file_states(conf):
    w_qkvz, w_ba, w_out = 2048 * 12_288, 2048 * 64, 4096 * 2048
    assert cq.mixer_matrices(conf) == w_qkvz + w_ba + w_out
    mixer = w_qkvz + w_ba + w_out + 8192 * 4 + 32 + 32 + 128
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert cq.attention_matrices(conf) == attention - 512
    beside = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048
    one_expert = 3 * 2048 * 512
    assert (mixer, attention, beside, one_expert) == (
        33_718_464, 27_263_488, 4_200_448, 3_145_728)
    linear_layer = mixer + beside + 32 * one_expert
    full_layer = attention + beside + 32 * one_expert
    assert (linear_layer, full_layer) == (138_582_208, 132_127_232)
    total = 3 * linear_layer + full_layer + 2 * 18_992 * 2048 + 2048
    assert cq.num_params(conf) == total == conf["as_run"]["parameters"] \
        == 625_667_136
    # 10.01 GB of state: 63% of the chip, over the driver's floor of 25%
    assert total * 16 == conf["as_run"]["state_bytes"] == 10_010_674_176
    assert 0.25 * 16e9 < total * 16 < 16e9


def test_parameters_are_the_program_s_tree(conf):
    jax = pytest.importorskip("jax")
    import dataclasses

    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import qwen3_next as ref

    entry = conf["entry"]
    cfg = resolve(entry["config"])(**entry["config_args"])
    one = resolve(entry["model"])(dataclasses.replace(
        cfg, **{entry["depth_arg"]: 1}))
    tree = meta.unbox(ref.expand_layers(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), batch=1, seq=128)),
        conf["n_layer"]))
    assert sum(int(a.size) for a in jax.tree.leaves(tree)) \
        == cq.num_params(conf)
    assert (cfg.embed_dim, cfg.num_layers, cfg.num_heads, cfg.max_seq_len,
            cfg.vocab_size) == tuple(conf[k] for k in (
                "n_embd", "n_layer", "n_head", "n_positions", "vocab_size"))
    assert cfg.chunk == cq.CHUNK


def test_flops_a_token_by_hand(conf):
    scan = cq.scan_macs_per_token(conf)
    # the triangular halves of a chunk of 64: 31.5 pairs a position
    # strictly below the diagonal, 32.5 with it
    assert scan == {"kk": 16 * 31.5 * 128, "qk": 16 * 32.5 * 128,
                    "solve": 32 * 31.5 * 256, "scores_v": 32 * 32.5 * 128,
                    "state": 3 * 32 * 128 * 128}
    mixer = 2 * (2048 * 12_288 + 2048 * 64 + 4096 * 2048) \
        + 2 * sum(scan.values())
    pairs = (4096 + 1) / 2
    attention = 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) \
        + 2 * 16 * 2 * 256 * pairs
    mlp = 2 * (2048 * 512 + 3 * 2048 * 512 + 2048
               + 3 * 2048 * 512 * 10 * 32 / 512)
    forward = 3 * mixer + attention + 4 * mlp + 2 * 18_992 * 2048
    assert cq.train_flops_per_token(conf, 4096) == pytest.approx(3 * forward)
    assert forward == pytest.approx(429.9e6, rel=1e-3)
    # the three linear mixers are the largest part, as ISSUE 58 sized it
    assert 3 * mixer / forward == pytest.approx(0.50, abs=0.01)
    assert 2 * 16 * 2 * 256 * pairs == pytest.approx(33.56e6, rel=1e-3)


def test_scan_call_costs_by_hand(conf):
    fwd = cq.gdn_call_cost("fwd", 1, 8192, conf)
    bwd = cq.gdn_call_cost("bwd", 1, 8192, conf)
    macs = sum(cq.scan_macs_per_token(conf).values())
    assert macs == 2_095_104
    assert fwd["flops"] == 2 * 8192 * macs
    assert bwd["flops"] == 2 * 8192 * (2 * macs + 16 * 64 * 128)
    qk, vo, gates = 8192 * 2048 * 2, 8192 * 4096 * 2, 8192 * 32 * 4
    assert fwd["bytes"] == 2 * qk + 2 * vo + 2 * gates          # o written
    assert bwd["bytes"] == 4 * qk + 4 * vo + 4 * gates
    # 24.8 KB a token a layer forward: bound by bytes on paper
    assert fwd["bytes"] / 8192 == pytest.approx(24.8e3, rel=0.01)
    assert fwd["flops"] / 197e12 < fwd["bytes"] / 819e9
    assert bwd["flops"] / 197e12 < bwd["bytes"] / 819e9 * 1.5


def test_a_step_s_scan_calls_flash_calls_and_grouped_products(conf):
    scan = cq.gdn_step_cost(conf, 4, 4096, remat=True)
    assert (scan["fwd"], scan["bwd"], scan["calls"]) == (24, 12, 36)
    one = {k: cq.gdn_call_cost(k, 1, 4096, conf) for k in ("fwd", "bwd")}
    for what in ("flops", "bytes"):
        assert scan[what] == pytest.approx(
            24 * one["fwd"][what] + 12 * one["bwd"][what])
    assert cq.gdn_step_cost(conf, 4, 4096, remat=False)["calls"] == 24
    assert conf["as_run"]["gated_delta_calls_a_step"] == {
        "forward": scan["fwd"], "backward": scan["bwd"]}
    flash = cq.flash_step_cost(conf, 4, 4096, remat=True)
    assert flash["calls"] == conf["as_run"]["flash_calls_a_step"] == 16
    pairs = 4096 * 4097 // 2
    # (2 x 2 forward + 4 dK/dV + 3 dQ) matmul passes over the visible pairs
    assert flash["flops"] == 4 * (2 * 2 + 4 + 3) * 2.0 * 16 * pairs * 256
    # a held expert meets 320 tokens a step: 10,240 rows a layer, a
    # quarter a call (80 an expert under row tiles of 256)
    assert cq.expected_live_rows(conf, 4096) == 320 * 32 // 4 == 2560
    gmm = cq.gmm_step_cost(conf, 4, 4096, remat=True)
    # 4 layers x 4 sequences x 3 products x (2 forward, d lhs, d rhs)
    assert gmm["calls"] == 192 and gmm["rows"] == 10_240
    assert gmm["flops"] == 192 * 2.0 * 2560 * 2048 * 512


def test_the_benchmark_s_new_entries_are_issue_58_s(conf):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == conf["name"])
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == conf["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "steady", 1)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "gdn_ms", "gdn_roofline", "flash_roofline.qwen3_next",
        "gmm_roofline.qwen3_next", "model_flops_util.qwen3_next"]
    assert all(m["moves"] == "tokens_per_s_per_chip" for m in new)
    assert all(m["unit"] == "%" for m in new if "roofline" in m["name"])
    shared = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", []) and len(m["workloads"]) > 1}
    assert shared == {
        "tokens_per_s_per_chip", "step_ms_p90", "data_wait_ms",
        "step_ms_median", "dispatch_ms", "step_hbm_gib",
        "device_idle_share", "gmm_ms", "scope_unnamed_share", "matmul_ms",
        "matmul_roofline",
        *(f"step_{p}_ms" for p in ("forward", "recompute", "backward",
                                   "optimizer")),
        *(f"part_{p}_ms" for p in (
            "embed", "attn", "mlp", "head", "moe_route", "moe_plan",
            "moe_dispatch", "moe_experts", "moe_combine", "ssm_proj",
            "ssm_conv", "ssm_scan", "ssm_gate_norm")),
        *(f"attn_{p}" for p in ("kernel_ms", "layout_ms", "proj_ms",
                                "pointwise_ms", "unpieced_share",
                                "kernel_recompute_ms"))}
    # the exchange is a four-chip cell's; no cell more asks for four chips
    assert CELL not in next(m for m in bench["per_layer"] if m["name"]
                            == "part_moe_exchange_ms")["workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


@pytest.mark.parametrize("key", [
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
    "partial_rotary_factor", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts_per_tok",
    "full_attention_interval", "rms_norm_eps", "rope_theta"])
def test_no_width_is_cut(conf, key):
    assert conf[key] == conf["published"][key]


def test_the_ops_under_a_name_are_summed_by_self_time_and_loops_counted_in_the_program():
    scan = "jit(train_step)/jvp(h0)/mixer/ssm.scan/gated_delta"
    facts = {
        "%while.3 = (f32[2]) while(...)": {"tf_op": scan + "/while:While"},
        "%fusion.7 = f32[2] fusion(...)": {
            "tf_op": scan + "/while/body/dot_general:Fusion"},
        "%fusion.8 = f32[2] fusion(...)": {
            "tf_op": "jit(train_step)/transpose(jvp(h0))/mixer/ssm.scan/"
                     "gated_delta/mul:Fusion"},
        "%fusion.9 = f32[2] fusion(...)": {
            "tf_op": "jit(train_step)/jvp(h0)/mixer/ssm.scan/mul:Fusion"},
        # its op_name says nothing: it goes by its neighbours
        "%copy.4 = f32[2] copy(...)": {},
    }
    events = [("%while.3 = (f32[2]) while(...)", 0, 100),
              ("%fusion.7 = f32[2] fusion(...)", 10, 40),   # in the loop
              ("%fusion.7 = f32[2] fusion(...)", 50, 80),
              ("%fusion.8 = f32[2] fusion(...)", 100, 130),
              ("%fusion.9 = f32[2] fusion(...)", 130, 190),  # another name
              ("%copy.4 = f32[2] copy(...)", 190, 200),
              ("%fusion.8 = f32[2] fusion(...)", 250, 300)]  # outside
    found = named_ops.split(events, facts, (0, 220), "gated_delta",
                            {"copy.4": scan + "/broadcast_in_dim"})
    assert found["ops"] == 5
    assert found["ns"] == 100 + 30 + 10
    assert found["by_phase"] == {"forward": 110.0, "backward": 30.0}
    assert found["stems"] == {"while": 1, "fusion": 3, "copy": 1}
    none = named_ops.split(events, facts, (0, 220), "ssd")
    assert none["ops"] == 0 and none["ns"] == 0
    assert named_ops.of_run(None, {}, "gated_delta") is None
    # the calls are counted in the step's program, not among the events
    program = {1: {"name": "while.3", "opcode": "while",
                   "op_name": scan + "/while", "operands": [], "calls": []},
               2: {"name": "while.9", "opcode": "while", "operands": [],
                   "op_name": "jit(train_step)/head/while", "calls": []},
               3: {"name": "fusion.7", "opcode": "fusion", "operands": [],
                   "op_name": scan + "/while/body/dot_general",
                   "calls": []}}
    assert named_ops.program_loops(program, "gated_delta") == 1
    assert named_ops.program_loops(program, "head") == 1
