"""The reader of the pieces of ``attn``
(``benchmarks/reduce/pieces.py``) on hand-made rows, as
``test_scopes.py`` pins the parts' reader: the innermost-piece rule on
strings as a chip's file would carry them, phases, a fusion under the
compiler's own stamp looked inside, a fusion of two pieces filed under
its root and counted as mixed, the metrics adding up to the part, and
every reader's ``None`` where the program said no ``attn_pieces``."""

import io
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.reduce import pieces, program_spans, scopes

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PARTS = ["embed", "attn", "mlp", "head", "optimizer"]
PIECES = ["norm", "proj", "pos", "gate", "layout", "kernel"]
STEADY = ["gpt2-large.steady", "gpt2-xl.fsdp4.steady",
          "trinity-mini.steady", "kanana-2-30b-a3b.steady",
          "nemotron-3-nano-30b-a3b.steady", "ouro-2.6b.steady",
          "mellum2-12b-a2.5b.ep4.steady", "xing4.0-29b-a4b.steady"]
NEW = {"attn_kernel_ms": "ms", "attn_layout_ms": "ms", "attn_proj_ms": "ms",
       "attn_pointwise_ms": "ms", "attn_unpieced_share": "%",
       "attn_kernel_recompute_ms": "ms"}

G, A, D = "jit(train_step)/", "AFMoE.hidden", "DeepseekV3.hidden"
STRINGS = [
    # (tf_op, piece)
    # under a kind and under the inner jit of a kernels' builder
    (G + f"jvp({A})/h2/attn/attn.sliding/jit(_flash_forward)/attn.layout/"
     "transpose:", "layout"),
    (G + f"jvp({A})/h2/attn/attn.sliding/jit(_flash_nl_forward)/"
     "attn.kernel/pallas_call:", "kernel"),
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/attn/attn.full/"
     "jit(_flash_nl_backward)/attn.kernel/pallas_call:", "kernel"),
    # a kind alone is no piece
    (G + f"jvp({A})/h2/attn/attn.full/slice:", None),
    # a plain name inside a piece hides none outside it
    (G + f"jvp({D})/h1/attn/attn.proj/mla.kv_up/wkv_b/dot_general:",
     "proj"),
    (G + f"jvp({D})/h1/attn/attn.norm/kv_norm/rsqrt:", "norm"),
    # the innermost of two decides (a shard's own inside the shard_map's)
    (G + "jvp(GPT2.hidden)/h0/attn/attn.layout/shard_map/"
     "jit(_flash_forward)/attn.kernel/pallas_call:", "kernel"),
    (G + "jvp(GPT2.hidden)/h0/attn/attn.layout/shard_map/"
     "jit(_flash_forward)/attn.layout/transpose:", "layout"),
    # jax's markers come off before the name is read
    (G + f"transpose(jvp({A}))/h3/jvp({A})/h3/checkpoint/"
     "rematted_computation/attn/transpose(jvp(attn.pos))/mul:", "pos"),
    # a sequence taken out of the batch: no module's name around it
    (G + f"jvp({A})/h2/attn.norm/slice:", "norm"),
    # a name that is no piece of the list, a part that is no ``attn``
    (G + f"jvp({A})/h2/attn/attn.rotate/mul:", None),
    (G + f"jvp({A})/h2/mlp/w_gate/dot_general:", None),
    ("convert.73:", None),
    ("", None),
]


@pytest.mark.parametrize("tf_op,want", STRINGS)
def test_the_innermost_piece_of_a_chip_trace_string(tf_op, want):
    assert pieces.piece(tf_op, PIECES) == want
    # the parts' rule reads the same string as it always did
    assert scopes.part(tf_op, PARTS) == (
        "attn" if "/attn" in tf_op else "mlp" if "/mlp/" in tf_op else None)


def test_a_stamp_is_the_compilers_own_name_and_nothing_else():
    assert pieces.stamped("") and pieces.stamped("convert.73:")
    assert pieces.stamped("add_convert_fusion.10")
    assert pieces.stamped("copy-start.3:")
    assert not pieces.stamped(G + "optimizer/add:")
    assert not pieces.stamped("reduce_window_sum:")
    assert not pieces.stamped("opt_state[0].nu['h2']['mlp']:")
    assert not pieces.stamped("params['h0']['attn.1']")


# --------------------------------------------------------------------------
# the split
# --------------------------------------------------------------------------

def _instruction(name, opcode, op_name="", operands=(), calls=()):
    return {"name": name, "opcode": opcode, "op_name": op_name,
            "operands": list(operands), "calls": list(calls)}


F = "jit(s)/jvp(M.hidden)/h0/attn/"
B = "jit(s)/transpose(jvp(M.hidden))/h0/jvp(M.hidden)/h0/checkpoint/"


def hand_made():
    """One step under ``attn``: a norm, a projection whose fusion also
    holds the rotation (mixed, the root a product), a rotation fusion
    whose root the compiler made and stamped with its own name, a
    transpose, the kernel forward, recomputed and backward, a nameless
    copy of the kernel's result, a gate, an op under the kind alone
    (unpieced), an ``mlp`` product, a collective and the update."""
    events = [
        ("%fusion.1 = bf16[8] fusion(...)", 0.0, 10.0),       # norm
        ("%fusion.2 = bf16[8] fusion(...)", 10.0, 40.0),      # proj + pos
        ("%convert_fusion.3 = bf16[8] fusion(...)", 40.0, 46.0),  # stamped
        ("%fusion.4 = bf16[8] fusion(...)", 46.0, 50.0),      # layout
        ("%attn.kernel.5 = bf16[8] custom-call(...)", 50.0, 70.0),
        ("%copy.6 = bf16[8] copy(...)", 70.0, 72.0),          # nameless
        ("%fusion.7 = bf16[8] fusion(...)", 72.0, 75.0),      # gate
        ("%slice.8 = bf16[8] slice(...)", 75.0, 76.0),        # unpieced
        ("%fusion.9 = bf16[8] fusion(...)", 76.0, 96.0),      # mlp
        ("%attn.kernel.10 = bf16[8] custom-call(...)", 96.0, 111.0),
        ("%attn.kernel.11 = bf16[8] custom-call(...)", 111.0, 151.0),
        ("%all-gather-start.12 = f32[8] all-gather-start(...)", 151.0,
         153.0),
        ("%fusion.13 = f32[8] fusion(...)", 153.0, 160.0),    # optimizer
    ]
    names = {
        1: F + "attn.norm/attn_norm/mul",
        2: F + "attn.proj/wq/dot_general",
        3: "convert_fusion.3",
        4: F + "attn.full/jit(_flash_forward)/attn.layout/transpose",
        5: F + "attn.full/jit(_flash_forward)/attn.kernel/pallas_call",
        6: "",
        7: F + "attn.gate/mul",
        8: F + "attn.full/slice",
        9: "jit(s)/jvp(M.hidden)/h0/mlp/w/dot_general",
        10: B + "rematted_computation/attn/attn.full/jit(_flash_forward)/"
                "attn.kernel/pallas_call",
        11: B + "attn/attn.full/jit(_flash_backward)/attn.kernel/"
                "pallas_call",
        12: "",
        13: "jit(s)/optimizer/mul",
    }
    facts = {e[0]: ({"tf_op": names[i + 1] + ":"} if names[i + 1] else {})
             for i, e in enumerate(events)}
    program = {
        1: _instruction("fusion.1", "fusion", names[1], calls=[101]),
        101: _instruction("mul.101", "multiply", names[1]),
        # the product is the root; the rotation of its result's halves
        # was fused in as a second output's way
        2: _instruction("fusion.2", "fusion", names[2], [1], calls=[203]),
        201: _instruction("dot.201", "dot", names[2], [200]),
        200: _instruction("p.200", "parameter", "x"),
        202: _instruction("mul.202", "multiply", F + "attn.pos/mul", [201]),
        203: _instruction("tuple.203", "tuple", "", [201, 202]),
        # the compiler's convert is the root: its stamp is the fusion's
        3: _instruction("convert_fusion.3", "fusion", "convert_fusion.3",
                        [2], calls=[302]),
        301: _instruction("add.301", "add", F + "attn.pos/add"),
        302: _instruction("convert.302", "convert", "convert.302", [301]),
        4: _instruction("fusion.4", "fusion", names[4], [3], calls=[401]),
        401: _instruction("transpose.401", "transpose", names[4]),
        5: _instruction("attn.kernel.5", "custom-call", names[5], [4]),
        6: _instruction("copy.6", "copy", "", [5]),
        7: _instruction("fusion.7", "fusion", names[7], [6], calls=[701]),
        701: _instruction("mul.701", "multiply", names[7]),
        8: _instruction("slice.8", "slice", names[8], [7]),
    }
    return events, facts, program


def found():
    events, facts, program = hand_made()
    return pieces.split(events, facts, (0.0, 160.0), PARTS, PIECES, program)


def test_split_by_phase_and_piece_of_the_ops_under_attn():
    got = found()
    assert got["ns"] == {
        ("forward", "norm"): 10.0,
        ("forward", "proj"): 30.0,           # its root's, though mixed
        ("forward", "pos"): 6.0,             # the stamped one, looked inside
        ("forward", "layout"): 4.0,
        ("forward", "kernel"): 20.0 + 2.0,   # and the copy of its result
        ("forward", "gate"): 3.0,
        ("forward", None): 1.0,
        ("recompute", "kernel"): 15.0,
        ("backward", "kernel"): 40.0,
    }


def test_a_stamped_fusion_is_looked_inside_and_counted_as_refiled():
    events, facts, program = hand_made()
    # the parts' reader takes the stamp at its word: under no part
    theirs = scopes.split(events, facts, (0.0, 160.0), PARTS,
                          scopes.inherited(program))
    assert theirs["ns"]["other", None] == 6.0
    part_attn = sum(v for (_, p), v in theirs["ns"].items() if p == "attn")
    got = found()
    assert pieces.looked_inside(program)["convert_fusion.3"] == \
        F + "attn.pos/add"
    assert got["refiled_ns"] == 6.0
    # the pieces and the unpieced time add up to the part, plus that
    assert sum(got["ns"].values()) == part_attn + got["refiled_ns"] == 131.0
    # with no program to look into, the stamp stays what it says
    events, facts, _ = hand_made()
    bare = pieces.split(events, facts, (0.0, 160.0), PARTS, PIECES)
    assert bare["refiled_ns"] == 0.0 and ("forward", "pos") not in bare["ns"]
    assert sum(bare["ns"].values()) == part_attn - 2.0  # nor the copy


def test_a_fusion_of_two_pieces_goes_by_its_root_and_counts_as_mixed():
    got = found()
    assert got["mixed_ns"] == 30.0
    assert got["mixed"] == {"fusion.2": [30.0, ["pos", "proj"]]}
    _, _, program = hand_made()
    assert sorted(pieces.fused(program, program[2])) == [
        F + "attn.pos/mul", F + "attn.proj/wq/dot_general"]
    assert pieces.fused(program, program[3]) == [F + "attn.pos/add"]
    out = io.StringIO()
    pieces.report(got, 1, file=out)
    lines = out.getvalue().splitlines()
    assert lines[0] == ("[pieces] attn_ms=0.000 refiled_ms=0.000 "
                        "mixed_ms=0.000 mixed_share=22.90%")
    assert lines[1].startswith("[pieces]   fusion.2 ") \
        and lines[1].endswith(": pos+proj")


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

def _row(**args):
    return {"cat": "model", "name": "step.scopes", "start": 1.0, "end": 2.0,
            "source": "w", "os_pid": 7, "tid": 1, "args": args}


def test_the_list_is_the_spans_and_nothing_elses():
    assert pieces.step_pieces([]) is None
    assert pieces.step_pieces([_row(parts="embed,attn", remat="full")]) \
        is None
    assert pieces.step_pieces([_row(parts="embed,attn",
                                    attn_pieces="norm,kernel")]) == [
        "norm", "kernel"]


def test_the_benchmark_names_the_six_for_the_eight_steady_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    for name, unit in NEW.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, "lower", "device_trace",
                                "train step", "tokens_per_s_per_chip")
        assert m["workloads"] == STEADY and set(STEADY) <= cells
        assert m["workloads"] == entries["part_attn_ms"]["workloads"]
    # appended: what was there stands before them, in its order
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_where_the_program_said_no_pieces(
        name, monkeypatch):
    """The parent commit: a trace there is, a ``step.scopes`` row with
    its ``parts`` there is, ``attn_pieces`` there is not; and a run with
    no trace at all."""
    read = bench_run.load_reader(os.path.join(REPO, "benchmarks"), name)
    run = {"device": {"kind": "TPU v5 lite"}, "chips": 1}
    assert read(None, [], run) is None
    monkeypatch.setattr(program_spans, "_timeline",
                        [_row(parts=",".join(PARTS), remat="full")])
    trace = {"path": "never/opened.xplane.pb",
             "devices": [{"steps": 4, "window": (0.0, 1.0)}]}
    assert read(trace, [], run) is None
    assert trace["_piece_split"] is None  # and no file was walked


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_reads_the_split_of_a_run_that_has_one(name):
    read = bench_run.load_reader(os.path.join(REPO, "benchmarks"), name)
    trace = {"path": "unused", "devices": [{"steps": 2}],
             "_piece_split": found()}
    value = read(trace, [], {"device": {"kind": "TPU v5 lite"}, "chips": 1})
    want = {"attn_kernel_ms": 77.0, "attn_layout_ms": 4.0,
            "attn_proj_ms": 30.0, "attn_pointwise_ms": 19.0,
            "attn_kernel_recompute_ms": 15.0}
    if name == "attn_unpieced_share":
        assert value == pytest.approx(100 * 1.0 / 131.0)
    else:
        assert value == pytest.approx(want[name] / 2 / 1e6)


def test_the_four_and_the_unpieced_time_add_up_to_the_part():
    trace = {"path": "unused", "devices": [{"steps": 1}],
             "_piece_split": found()}
    run = {"device": {"kind": "TPU v5 lite"}, "chips": 1}
    read = {name: bench_run.load_reader(
        os.path.join(REPO, "benchmarks"), name)(trace, [], run)
        for name in NEW}
    total = sum(found()["ns"].values()) / 1e6
    four = sum(read[n] for n in ("attn_kernel_ms", "attn_layout_ms",
                                 "attn_proj_ms", "attn_pointwise_ms"))
    assert four + read["attn_unpieced_share"] / 100 * total == \
        pytest.approx(total)
    assert pieces.piece_ms(trace, run, None) == pytest.approx(1.0 / 1e6)
