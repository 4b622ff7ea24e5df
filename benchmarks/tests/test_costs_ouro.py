"""``benchmarks/costs_ouro.py`` against counts made by hand and against
the program's own parameter tree; the new entries of ``BENCHMARK.json``
against what ISSUE 47 fixes of them; and the reader that finds a step's
kernel calls by the program's names, on a recorded list."""

import json
import os

import pytest

from benchmarks import costs_ouro as co
from benchmarks.reduce import kernels_named

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro-2.6b.steady"
NEW = ("model_flops_util.ouro", "flash_ms.ouro", "flash_roofline.ouro",
       "part_exit_ms")


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_parameters_by_hand_and_as_the_file_states(conf):
    assert co.layer_matrices(conf) == 4 * 2048 ** 2 + 3 * 2048 * 5632 \
        == 51_380_224
    layer = 51_380_224 + 4 * 2048
    total = 6 * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert co.num_params(conf) == total == conf["as_run"]["parameters"] \
        == 509_661_185
    # 8.15 GB of state: 51% of the chip, over the driver's floor of 25%
    assert total * 16 == conf["as_run"]["state_bytes"]
    assert 0.25 * 16e9 < total * 16 < 16e9
    # the layers are held once however often they run
    whole = dict(conf, num_hidden_layers=48)
    assert co.num_params(whole) == 2_667_970_560 + 2048 + 2049
    assert co.layer_calls(conf) == conf["as_run"]["layer_calls"] == 24


def test_parameters_are_the_program_s_tree(conf):
    jax = pytest.importorskip("jax")
    import dataclasses

    from flax.core import meta

    from benchmarks.kinds.train import resolve
    from benchmarks.reference import ouro as ref

    entry = conf["entry"]
    cfg = resolve(entry["config"])(**entry["config_args"])
    one = resolve(entry["model"])(dataclasses.replace(
        cfg, **{entry["depth_arg"]: 1}))
    tree = meta.unbox(ref.expand_layers(jax.eval_shape(
        lambda: one.init_params(jax.random.PRNGKey(0), batch=1, seq=128)),
        conf["n_layer"]))
    assert sum(int(a.size) for a in jax.tree.leaves(tree)) \
        == co.num_params(conf)
    # depth 1 holds every kind of parameter: one layer run four times
    assert {"embed", "head", "final_norm", "exit_gate", "h0"} \
        <= set(tree)


def test_flops_a_token_by_hand(conf):
    f = co.forward_flops_per_token(conf, 4096)
    assert f["layers"] == 24 * 2 * 51_380_224
    # a position sees 2048.5 keys on average; a pair a head costs a
    # score and a value product of 128
    assert f["attention"] == 24 * (4 * 16 * 128) * 2048.5
    assert f["heads"] == 4 * 2 * 49152 * 2048
    assert f["gate"] == 3 * 2 * 2048
    total = co.train_flops_per_token(conf, 4096)
    assert total == 3 * sum(f.values())
    # ISSUE 47 reckons 13.9 GFLOP for its stage of 8 layers: 6 are 11.0
    assert 11.0e9 < total < 11.1e9
    assert 13.85e9 < co.train_flops_per_token(
        dict(conf, num_hidden_layers=8), 4096) < 13.95e9
    # the heads' share: 22% here, 3.4% of the whole model's step
    assert 0.21 < f["heads"] / sum(f.values()) < 0.23
    whole = co.forward_flops_per_token(
        dict(conf, num_hidden_layers=48), 4096)
    assert 0.033 < whole["heads"] / sum(whole.values()) < 0.035


def test_flash_calls_and_cost_a_step(conf):
    cost = co.flash_step_cost(conf, 2, 4096, remat=True)
    # 6 layers x 4 passes x 2 sequences, each forward twice, dK/dV, dQ
    assert cost["calls"] == 48 * 4 == conf["as_run"]["flash_calls_a_step"]
    pairs = 16 * 4096 * 4096 / 2                 # the causal half, a call
    assert cost["flops"] == 48 * (2 + 2 + 4 + 3) * 2 * 128 * pairs
    rows = 16 * 4096
    per_call = {"fwd": 4 * rows * 128 * 2 + rows * 4,
                "dkdv": 6 * rows * 128 * 2 + 2 * rows * 4,
                "dq": 5 * rows * 128 * 2 + 2 * rows * 4}
    assert cost["bytes"] == 48 * (2 * per_call["fwd"] + per_call["dkdv"]
                                  + per_call["dq"])
    assert co.flash_step_cost(conf, 2, 4096, remat=False)["calls"] == 144


def test_the_benchmark_holds_issue_47_s_entries(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config="ouro-2.6b",
                               traffic="steady", chips=1)
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].startswith(
        "https://huggingface.co/ByteDance/Ouro-2.6B/")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"))
    # the cell reports what every steady cell reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2-large.steady" in m.get("workloads", []) \
                and "trinity-mini.steady" in m["workloads"]:
            assert CELL in m["workloads"], m["name"]


# --------------------------------------------------------------------------
# the reader by names: (event text, tf_op) as a traced step carries them
# (the shapes of PR 47's step compiled for a described v5e; the names as
# tests/test_ouro.py reads them off the program's jaxpr and PERF.md
# section 6 off the chip's files)
# --------------------------------------------------------------------------

G = "jit(train_step)/"
H = "Ouro.hidden"
CALL = ', custom_call_target="tpu_custom_call"'
RECORDED = [
    ("%_flash_nl_forward.5 = (bf16[1,4096,2048], f32[1,16,4096,1]) "
     "custom-call(%a, %b, %c)" + CALL,
     G + f"jvp({H})/h3/attn/pass2/attn.sandwich/attn.full/"
     "jit(_flash_nl_forward)/pallas_call:"),
    ("%_flash_nl_forward.9 = (bf16[1,4096,2048], f32[1,16,4096,1]) "
     "custom-call(%a, %b, %c)" + CALL,
     G + f"transpose(jvp({H}))/h3/jvp({H})/h3/checkpoint/"
     "rematted_computation/attn/pass2/attn.sandwich/attn.full/"
     "jit(_flash_nl_forward)/pallas_call:"),
    ("%_flash_nl_backward.1 = (bf16[1,4096,2048], bf16[1,4096,2048]) "
     "custom-call(%a, %b, %c, %d, %e, %f)" + CALL,
     G + f"transpose(jvp({H}))/h3/jvp({H})/h3/checkpoint/attn/pass2/"
     "attn.sandwich/attn.full/jit(_flash_nl_backward)/pallas_call:"),
    ("%_flash_nl_backward = bf16[1,4096,2048] "
     "custom-call(%a, %b, %c, %d, %e, %f)" + CALL,
     G + f"transpose(jvp({H}))/h3/jvp({H})/h3/checkpoint/attn/pass2/"
     "attn.sandwich/attn.full/jit(_flash_nl_backward)/pallas_call:"),
    # a fused norm under attn: a kernel call, no flash call
    ("%attn_norm.7 = bf16[4096,2048] custom-call(%a, %b)" + CALL,
     G + f"jvp({H})/h3/attn/pass2/attn.sandwich/attn_norm/pallas_call:"),
    # the final norm is the head's
    ("%final_norm.2 = bf16[8192,2048] custom-call(%a, %b)" + CALL,
     G + f"jvp({H})/head/pass1/final_norm/pallas_call:"),
    # a product under attn: no kernel call
    ("%fusion.12 = bf16[4096,2048] fusion(%a, %b), kind=kOutput",
     G + f"jvp({H})/h3/attn/pass2/attn.sandwich/wo/dot_general:"),
]
PARTS = ["embed", "attn", "mlp", "head", "exit", "optimizer"]


def test_flash_calls_are_found_by_the_program_s_names():
    events = [(text, 100.0 * i, 100.0 * i + 10.0 * (i + 1))
              for i, (text, _) in enumerate(RECORDED)]
    facts = {text: {"tf_op": tf_op} for text, tf_op in RECORDED}
    got = kernels_named.split(events, facts, (0.0, 1e9), PARTS, "attn",
                              "_flash_")
    assert got == {"ns": 10.0 + 20.0 + 30.0 + 40.0, "calls": 4}
    norms = kernels_named.split(events, facts, (0.0, 1e9), PARTS, "head",
                                "final_norm")
    assert norms == {"ns": 60.0, "calls": 1}
    # an event the window cuts counts for what lies inside
    assert kernels_named.split(events, facts, (305.0, 1e9), PARTS, "attn",
                               "_flash_") == {"ns": 35.0, "calls": 1}
    # no names in the file (the parent's profiler had them too; a file
    # without them gives nothing to find)
    assert kernels_named.split(events, {}, (0.0, 1e9), PARTS, "attn",
                               "_flash_")["calls"] == 0


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_nothing_without_a_trace(name):
    from benchmarks.run import load_reader

    read = load_reader(os.path.join(REPO, "benchmarks"), name)
    assert read(None, [], {"final": {}, "config": {}, "chips": 1}) is None
