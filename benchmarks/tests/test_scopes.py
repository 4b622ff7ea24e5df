"""The reader of the names the profiler already writes
(``benchmarks/reduce/scopes.py``): the wire-format walker on a recorded
v5e trace, ``phase`` and ``part`` on strings copied from the chip traces
of the four models (PR 37), ``split`` on a hand-made trace, and every
reader's ``None`` where the program said no ``model:step.scopes``."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.reduce import program_spans, scopes, xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(
    REPO, "profiles/gpt2_train_nl/plugins/profile/2026_08_01_10_33_01/"
    "vm.xplane.pb")
PARTS = ("embed,attn,mlp,moe.route,moe.plan,moe.dispatch,moe.experts,"
         "moe.combine,ssm.in_proj,ssm.conv,ssm.scan,ssm.gate_norm,"
         "ssm.out_proj,head,optimizer").split(",")


# --------------------------------------------------------------------------
# the file
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return list(scopes.event_metadata(RECORDED))


def test_walker_reads_the_event_metadata_of_device_0(recorded):
    assert len(recorded) == 6201
    facts = dict(recorded)
    (name,) = [n for n in facts if n.startswith("%fusion.782 = ")]
    assert facts[name] == {
        "hlo_category": "convolution fusion", "flops": 116294418432,
        "bytes_accessed": 244062208,
        "tf_op": "jit(step)/transpose(jvp(GPT2.hidden))/h7/attn_qkv/"
                 "dot_general:"}
    (name,) = [n for n in facts if n.startswith("%fusion.1851 = ")]
    assert facts[name]["tf_op"] == "jit(step)/jvp(GPT2.hidden)/h1/split:"
    assert facts[name]["hlo_category"] == "loop fusion"
    assert scopes.op_facts(RECORDED) == facts
    assert scopes.op_facts(RECORDED, plane="/device:TPU:7") == {}


def test_every_event_of_the_ops_line_joins_its_facts_by_name(recorded):
    facts = dict(recorded)
    plane = xplane.device_planes(xplane.load(RECORDED))[0]
    events = xplane._events(plane, "XLA Ops")
    assert len(events) > 10000
    assert not [n for n, _, _ in events if n not in facts]
    # the split counts every busy nanosecond once: that trace's program
    # had no parts, so all of it is under none
    dev = xplane.reduce_device(plane)
    found = scopes.split(events, facts, dev["window"], PARTS)
    total = sum(found["ns"].values()) + found["collective_ns"]
    assert total == pytest.approx(dev["busy_ns"], rel=1e-9)
    assert {part for _, part in found["ns"]} == {None}
    assert {phase for phase, _ in found["ns"]} == {
        "forward", "recompute", "backward", "other"}
    # XLA's own count over its own time: under the peak
    assert 0.5 < found["matmul_flops"] / (found["matmul_ns"] / 1e9) \
        / 197e12 < 1.0


def test_varints_strings_and_signed_numbers():
    # field 1 varint 300; field 2 bytes "ab"; field 4 int64 -2
    raw = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x20] + [0xFE] + [0xFF] * 8 + [0x01])
    got = list(scopes._fields(memoryview(raw)))
    assert [(f, w) for f, w, _ in got] == [(1, 0), (2, 2), (4, 0)]
    assert got[0][2] == 300 and bytes(got[1][2]) == b"ab"
    assert scopes._stat(memoryview(raw[7:])) == (0, -2)
    with pytest.raises(ValueError):
        list(scopes._fields(memoryview(bytes([0x0B]))))  # a group


# --------------------------------------------------------------------------
# names: strings as the chip's files carry them (PR 37's traces)
# --------------------------------------------------------------------------

G, A = "jit(train_step)/", "AFMoE.hidden"
D, N = "DeepseekV3.hidden", "NemotronH.hidden"
CHIP_STRINGS = [
    # (tf_op, phase, part)
    # gpt2-large.steady (run c1, seed 3700011299)
    (G + "jvp(GPT2.hidden)/h22/attn/jit(_flash_nl_forward)/pallas_call:",
     "forward", "attn"),
    (G + "jvp(GPT2.hidden)/h31/mlp/mlp_down/dot_general:", "forward", "mlp"),
    (G + "jvp(GPT2.hidden)/embed/convert_element_type:", "forward", "embed"),
    (G + "jvp(head)/while/body/closed_call/dot_general:", "forward", "head"),
    (G + "transpose(jvp(GPT2.hidden))/jvp(GPT2.hidden)/checkpoint/"
     "rematted_computation/h34/attn/jit(_flash_nl_forward)/pallas_call:",
     "recompute", "attn"),
    (G + "transpose(jvp(GPT2.hidden))/jvp(GPT2.hidden)/checkpoint/"
     "rematted_computation/h35/mlp/mlp_up/dot_general:", "recompute", "mlp"),
    (G + "transpose(jvp(head))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general:", "recompute", "head"),
    (G + "transpose(jvp(GPT2.hidden))/jvp(GPT2.hidden)/checkpoint/h31/"
     "attn/attn_qkv/dot_general:", "backward", "attn"),
    (G + "transpose(jvp(head))/while/body/closed_call/checkpoint/"
     "dot_general:", "backward", "head"),
    (G + "transpose(jvp(GPT2.hidden))/embed/scatter-add:", "backward",
     "embed"),
    (G + "optimizer/add:", "optimizer", "optimizer"),
    # trinity-mini.steady (run c1, seed 3700030479): a custom_vjp's
    # backward under its forward's part, kernels and XLA ops alike
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/attn/attn.full/"
     "jit(_flash_nl_backward)/pallas_call:", "backward", "attn"),
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/moe/moe.dispatch/"
     "gather:", "backward", "moe.dispatch"),
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/moe/moe.experts/"
     "grouped_matmul_drhs_act/pallas_call:", "backward", "moe.experts"),
    (G + f"transpose(jvp({A}))/h2/jvp({A})/h2/checkpoint/moe/moe.combine/"
     "reduce_sum:", "backward", "moe.combine"),
    (G + f"jvp({A})/h1/moe/moe.plan/jit(take_along_axis)/gather:",
     "forward", "moe.plan"),
    (G + f"jvp({A})/h1/moe/moe.route/top_k:", "forward", "moe.route"),
    (G + f"jvp({A})/h3/moe/moe.dispatch/cond/branch_1_fun/"
     "dynamic_update_slice:", "forward", "moe.dispatch"),
    (G + f"jvp({A})/dense0/mlp/w_gate/dot_general:", "forward", "mlp"),
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/"
     "rematted_computation/moe/moe.experts/grouped_matmul_act/pallas_call:",
     "recompute", "moe.experts"),
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/"
     "rematted_computation/moe/moe.route/top_k:", "recompute", "moe.route"),
    # kanana-2-30b-a3b.steady (run c2, seed 3700104478)
    (G + f"transpose(jvp({D}))/h1/jvp({D})/h1/checkpoint/attn/attn.mla/"
     "jit(_flash_mla_backward)/pallas_call:", "backward", "attn"),
    (G + f"transpose(jvp({D}))/h3/jvp({D})/h3/checkpoint/"
     "rematted_computation/attn/attn.mla/jit(_flash_mla_forward)/"
     "pallas_call:", "recompute", "attn"),
    (G + f"transpose(jvp({D}))/head/final_norm/transpose(jvp())/"
     "reduce_sum:", "backward", "head"),
    (G + f"transpose(jvp({D}))/h1/jvp({D})/h1/checkpoint/moe/moe.route/"
     "dot_general:", "backward", "moe.route"),
    (G + f"jvp({D})/embed/gather:", "forward", "embed"),
    # nemotron-3-nano-30b-a3b.steady (run c2, seed 3700104454): the
    # scan's backward kernel under its forward's part
    (G + f"transpose(jvp({N}))/m2/jvp({N})/m2/checkpoint/mixer/ssm.scan/"
     "ssd_chunk_scan_bwd/pallas_call:", "backward", "ssm.scan"),
    (G + f"transpose(jvp({N}))/m3/jvp({N})/m3/checkpoint/"
     "rematted_computation/mixer/ssm.scan/ssd_chunk_scan/pallas_call:",
     "recompute", "ssm.scan"),
    (G + f"jvp({N})/m1/mixer/ssm.in_proj/in_proj/dot_general:", "forward",
     "ssm.in_proj"),
    (G + f"transpose(jvp({N}))/m3/jvp({N})/m3/checkpoint/mixer/ssm.conv/"
     "reduce_sum:", "backward", "ssm.conv"),
    (G + f"jvp({N})/m3/mixer/ssm.gate_norm/square:", "forward",
     "ssm.gate_norm"),
    (G + f"transpose(jvp({N}))/m2/jvp({N})/m2/checkpoint/mixer/"
     "ssm.out_proj/out_proj/dot_general:", "backward", "ssm.out_proj"),
    (G + f"transpose(jvp({N}))/h1/jvp({N})/h1/checkpoint/mlp/shared_up/"
     "dot_general:", "backward", "mlp"),
    (G + f"transpose(jvp({N}))/a0/jvp({N})/a0/checkpoint/attn/attn.full/"
     "jit(_flash_nl_backward)/pallas_call:", "backward", "attn"),
    # what jax and XLA put in for themselves
    (G + f"transpose(jvp({A}))/dense0/jvp({A})/dense0/remat2:", "backward",
     None),
    ("opt_state[0].nu['h2']['mlp']['moe']['experts_up']:", "other", None),
    ("reduce_window_sum:", "other", None),
    ("", "other", None),
]


@pytest.mark.parametrize("tf_op,phase,part", CHIP_STRINGS)
def test_phase_and_part_of_a_chip_trace_string(tf_op, phase, part):
    assert scopes.phase(tf_op) == phase
    assert scopes.part(tf_op, PARTS) == part


def test_the_outermost_part_decides_and_a_dot_is_a_child():
    part = scopes.part
    assert part("jit(f)/jvp(M.hidden)/h0/attn/attn.sliding/mul:", PARTS) \
        == "attn"
    assert part("jit(f)/jvp(M.hidden)/h0/attn/mla.kv_up/dot_general:",
                PARTS) == "attn"
    assert part("jit(f)/jvp(M.hidden)/h0/mla.kv_up/dot_general:",
                PARTS) is None
    # a part before a dot, never a prefix of a name
    assert part("jit(f)/jvp(M.hidden)/h0/attn_qkv/dot_general:",
                PARTS) is None
    assert part("jit(f)/jvp(M.hidden)/h0/moe/moe.plan/sort:", PARTS) \
        == "moe.plan"
    assert part("jit(f)/transpose(jvp(head))/while/body/mul:", PARTS) \
        == "head"
    assert part("jit(f)/head/optimizer/mul:", PARTS) == "head"
    assert part("jit(f)/head/optimizer/mul:", ("optimizer",)) == "optimizer"
    assert part("", PARTS) is None
    assert scopes.components("jit(f)/transpose(jvp(a.b))/jvp()/x:") == [
        "jit(f)", "a.b", "", "x"]


# --------------------------------------------------------------------------
# the split
# --------------------------------------------------------------------------

def hand_made():
    """One step: a forward fusion, a ``while`` of the head with two
    children and a gap no child covers, a backward product, a collective
    pair, the update; a second forward op that the window cuts."""
    events = [
        ("%fusion.1 = f32[8] fusion(...)", 0.0, 10.0),
        ("%while.2 = (f32[8]) while(...)", 10.0, 50.0),
        ("%fusion.3 = f32[8] fusion(...)", 12.0, 22.0),
        ("%convolution.4 = f32[8] convolution(...)", 30.0, 48.0),
        ("%fusion.5 = f32[8] fusion(...)", 50.0, 70.0),
        ("%all-gather-start.6 = f32[8] all-gather-start(...)", 70.0, 71.0),
        ("%all-gather-done.7 = f32[8] all-gather-done(...)", 71.0, 75.0),
        ("%fusion.8 = f32[8] fusion(...)", 75.0, 85.0),
        ("%copy.9 = f32[8] copy(...)", 85.0, 90.0),
        ("%fusion.1 = f32[8] fusion(...)", 95.0, 105.0),
    ]
    facts = {
        events[0][0]: {"tf_op": "jit(s)/jvp(M.hidden)/h0/attn/mul:",
                       "hlo_category": "loop fusion", "flops": 8},
        events[1][0]: {"tf_op": "jit(s)/jvp(head)/while:",
                       "hlo_category": "while"},
        events[2][0]: {"tf_op": "jit(s)/jvp(head)/while/body/exp:",
                       "hlo_category": "loop fusion"},
        events[3][0]: {
            "tf_op": "jit(s)/transpose(jvp(head))/while/body/checkpoint/"
                     "rematted_computation/dot_general:",
            "hlo_category": "convolution", "flops": 1000},
        events[4][0]: {
            "tf_op": "jit(s)/transpose(jvp(M.hidden))/h0/mlp/w/"
                     "dot_general:",
            "hlo_category": "convolution fusion", "flops": 3000},
        events[7][0]: {"tf_op": "jit(s)/optimizer/mul:",
                       "hlo_category": "loop fusion"},
        events[8][0]: {},
    }
    return events, facts


def test_split_gives_a_container_what_no_child_covers():
    events, facts = hand_made()
    own = scopes.self_times(events, (0.0, 100.0))
    assert [ns for _, ns in own] == [10.0, 12.0, 10.0, 18.0, 20.0, 1.0,
                                     4.0, 10.0, 5.0, 5.0]
    found = scopes.split(events, facts, (0.0, 100.0), PARTS)
    assert found["ns"] == {
        ("forward", "attn"): 15.0,       # the second one cut at 100
        ("forward", "head"): 12.0 + 10.0,  # the while's own, and a child
        ("recompute", "head"): 18.0,
        ("backward", "mlp"): 20.0,
        ("optimizer", "optimizer"): 10.0,
        ("other", None): 5.0,            # a copy the profiler names not
    }
    assert found["collective_ns"] == 5.0
    assert found["matmul_ns"] == 38.0 and found["matmul_flops"] == 4000.0
    # phases and parts each add up to the busy time less the collectives
    busy = xplane.measure(xplane.union((s, min(e, 100.0))
                                       for _, s, e in events))
    assert busy == 95.0
    assert sum(found["ns"].values()) + found["collective_ns"] == busy


def _instruction(name, opcode, op_name="", operands=(), calls=()):
    return {"name": name, "opcode": opcode, "op_name": op_name,
            "operands": list(operands), "calls": list(calls)}


def hand_made_program():
    """What XLA makes for itself carries no name: a fusion whose root is
    a ``convert`` it pushed through (10 calls root 13 < 12 < 11, the
    named ``add``), a layout copy of a kernel's second result (through
    the element 3), a prefetch of an argument that a named product
    reads (20 -> 21 -> 22), and a convert of an argument nobody named
    reads."""
    return {
        1: _instruction("p.1", "parameter", "params['w'].value"),
        2: _instruction("attn.full.2", "custom-call",
                        "jit(s)/jvp(M)/h0/attn/attn.full/pallas_call"),
        3: _instruction("pallas_call.3", "get-tuple-element", "", [2]),
        4: _instruction("copy.4", "copy", "", [3]),
        10: _instruction("add_convert_fusion.10", "fusion", "", [4],
                         calls=[13]),
        11: _instruction("add.11", "add",
                         "jit(s)/transpose(jvp(M))/h0/attn/add_any"),
        12: _instruction("convert.12", "convert", "", [11]),
        13: _instruction("tuple.13", "tuple", "", [12]),
        20: _instruction("copy-start.20", "copy-start", "", [1]),
        21: _instruction("copy-done.21", "copy-done", "", [20]),
        22: _instruction("fusion.22", "fusion",
                         "jit(s)/jvp(M)/h0/mlp/w/dot_general", [21, 10]),
        30: _instruction("convert.30", "convert", "", [1]),
        31: _instruction("bitcast.31", "bitcast", "", [30]),
    }


def test_a_nameless_op_goes_by_what_is_inside_it_or_beside_it():
    names = scopes.inherited(hand_made_program())
    assert names == {
        "pallas_call.3": "jit(s)/jvp(M)/h0/attn/attn.full/pallas_call",
        "copy.4": "jit(s)/jvp(M)/h0/attn/attn.full/pallas_call",
        # its own inside before its operands
        "add_convert_fusion.10":
            "jit(s)/transpose(jvp(M))/h0/attn/add_any",
        "convert.12": "jit(s)/transpose(jvp(M))/h0/attn/add_any",
        "tuple.13": "jit(s)/transpose(jvp(M))/h0/attn/add_any",
        # an argument's name is no scope: on to who reads the prefetch
        "copy-start.20": "jit(s)/jvp(M)/h0/mlp/w/dot_general",
        "copy-done.21": "jit(s)/jvp(M)/h0/mlp/w/dot_general",
    }
    # what no named op reads or feeds stays nameless, and visibly so
    events = [("%convert.30 = bf16[8] convert(%p.1)", 0.0, 4.0),
              ("%copy.4 = f32[8] copy(%pallas_call.3)", 4.0, 10.0)]
    found = scopes.split(events, {e[0]: {} for e in events}, (0.0, 10.0),
                         PARTS, names)
    assert found["ns"] == {("other", None): 4.0, ("forward", "attn"): 6.0}
    assert found["inherited_ns"] == 6.0


def test_the_profilers_file_holds_the_steps_program(recorded):
    program = scopes.step_program(RECORDED, "jit_step")
    assert len(program) == 20691
    by_name = {one["name"]: one for one in program.values()}
    assert by_name["fusion.782"]["op_name"] == \
        "jit(step)/transpose(jvp(GPT2.hidden))/h7/attn_qkv/dot_general"
    assert by_name["fusion.782"]["opcode"] == "fusion"
    assert len(by_name["fusion.782"]["operands"]) == 13
    assert scopes.step_program(RECORDED, "jit_other") == {}
    # every op of the trace that carries no name of its own, bar the
    # loops and the profiler's own rows, finds one
    names = scopes.inherited(program)
    facts = dict(recorded)
    nameless = [xplane.op_name(n) for n, f in facts.items()
                if not f.get("tf_op") and n.startswith("%")]
    left = [n for n in nameless if n not in names]
    assert len(nameless) > 3000 and len(left) <= 2, left
    assert names["slice-done.236"] == "jit(step)/jvp(GPT2.hidden)/h4/split"


def test_split_with_another_list_of_parts_names_less():
    events, facts = hand_made()
    found = scopes.split(events, facts, (0.0, 100.0), ["head"])
    assert sum(v for (_, p), v in found["ns"].items() if p is None) == 50.0
    assert found["ns"]["optimizer", None] == 10.0


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

NEW = ["step_forward_ms", "step_recompute_ms", "step_backward_ms",
       "step_optimizer_ms", "part_embed_ms", "part_attn_ms", "part_mlp_ms",
       "part_head_ms", "part_moe_route_ms", "part_moe_plan_ms",
       "part_moe_dispatch_ms", "part_moe_experts_ms", "part_moe_combine_ms",
       "part_ssm_proj_ms", "part_ssm_conv_ms", "part_ssm_scan_ms",
       "part_ssm_gate_norm_ms", "scope_unnamed_share", "matmul_ms",
       "matmul_roofline"]


def _row(parts):
    return {"cat": "model", "name": "step.scopes", "start": 1.0, "end": 2.0,
            "source": "w", "os_pid": 7, "tid": 1,
            "args": {"parts": parts, "remat": "full"}}


def test_the_list_is_the_spans_and_nothing_elses():
    assert scopes.step_parts([]) is None
    assert scopes.step_parts([_row("embed,attn,head")]) == [
        "embed", "attn", "head"]
    assert scopes.step_parts([_row("")]) is None


def test_the_benchmark_names_the_twenty_and_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    steady = [c for c in entries["step_ms_median"]["workloads"]]
    assert len(steady) == 5
    for name in NEW:
        m = entries[name]
        assert m["source"] == "device_trace"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert set(m["workloads"]) <= set(steady)
    assert entries["part_ssm_scan_ms"]["workloads"] == [
        "nemotron-3-nano-30b-a3b.steady"]
    assert len(entries["part_moe_plan_ms"]["workloads"]) == 3


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_where_the_program_said_no_list(
        name, monkeypatch):
    """The parent commit: a trace there is, a ``step.scopes`` row there
    is not; and a run with no trace at all."""
    read = bench_run.load_reader(os.path.join(REPO, "benchmarks"), name)
    run = {"device": {"kind": "TPU v5 lite"}, "chips": 1}
    assert read(None, [], run) is None
    monkeypatch.setattr(program_spans, "_timeline", [])
    trace = {"path": RECORDED, "devices": [{"steps": 4,
                                            "window": (0.0, 1.0)}]}
    assert read(trace, [], run) is None
    assert trace["_scope_split"] is None  # and the file was not walked


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_the_split_of_a_run_that_has_one(name, monkeypatch):
    read = bench_run.load_reader(os.path.join(REPO, "benchmarks"), name)
    events, facts = hand_made()
    trace = {"path": "unused", "devices": [{"steps": 1}],
             "_scope_split": scopes.split(events, facts, (0.0, 100.0),
                                          PARTS)}
    value = read(trace, [], {"device": {"kind": "TPU v5 lite"},
                             "chips": 1})
    want = {"step_forward_ms": 37e-6, "step_recompute_ms": 18e-6,
            "step_backward_ms": 20e-6, "step_optimizer_ms": 10e-6,
            "part_attn_ms": 15e-6, "part_mlp_ms": 20e-6,
            "part_head_ms": 40e-6, "matmul_ms": 38e-6,
            "scope_unnamed_share": 100 * 5.0 / 90.0,
            "matmul_roofline": 100 * 4000.0 / 38e-9 / 197e12}
    assert value == pytest.approx(want.get(name, 0.0))
