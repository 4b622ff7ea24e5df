"""``benchmarks/reduce/kernels_mla.py``: the Mosaic kernel calls of a
traced step with latent attention told apart by what an event's text
carries.  The event list below is cut from a traced run of
``kanana-2-30b-a3b.steady`` on the chip (PR 33, seed 3300000103: one
event of each kind the step has, text as the profiler gives it up to
``custom_call_target``, times rewritten)."""

import pytest

from benchmarks.reduce import kernels, kernels_mla

#: the cell runs a layer over one sequence at a time, and its batch is one
SIZES = {"batch": 1, "full_batch": 1, "seq": 16384, "held": 16}

RECORDED = [
    # forward: the output 128 wide beside the row statistics; the
    # operands q 192, k_nope 128, ONE rotary head of 64, v 128
    ('%attn.mla.33 = (bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)}, f32[1,32,16384,1]{3,2,1,0:T(8,128)}) custom-call(bf16[1,32,16384,192]{3,2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.7, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1462, bf16[1,1,16384,64]{3,2,1,0:T(8,128)(2,1)S(1)} %copy-done.235, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1461), custom_call_target="tpu_custom_call"',
     'mla_flash'),
    # dK/dV: dk_nope, the shared rotary key's gradient, dv
    ('%attn.mla.37 = (bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)}, bf16[1,1,16384,64]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(bf16[1,32,16384,192]{3,2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.8, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1460, bf16[1,1,16384,64]{3,2,1,0:T(8,128)(2,1)S(1)} %copy-done.238, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1459, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.2255, f32[1,32,16384,1]{3,2,1,0:T(8,128)} %pallas_call.248, f32[1,32,16384,1]{3,2,1,0:T(8,128)} %copy.2755), custom_call_target="tpu_custom_call"',
     'mla_flash'),
    # dQ: as wide as q
    ('%attn.mla.47 = bf16[1,32,16384,192]{3,2,1,0:T(8,128)(2,1)} custom-call(bf16[1,32,16384,192]{3,2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.11, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1454, bf16[1,1,16384,64]{3,2,1,0:T(8,128)(2,1)S(1)} %copy-done.244, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1453, bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.2270, f32[1,32,16384,1]{3,2,1,0:T(8,128)} %pallas_call.296, f32[1,32,16384,1]{3,2,1,0:T(8,128)} %copy.2872), custom_call_target="tpu_custom_call"',
     'mla_flash'),
    ('%grouped_matmul.36 = bf16[102400,768]{1,0:T(8,128)(2,1)} custom-call(s32[400]{0:T(512)S(1)} %copy-done.618, s32[1]{0:T(128)S(6)} %min.46, bf16[102400,2048]{1,0:T(8,128)(2,1)} %fusion.29, bf16[16,2048,768]{2,1,0:T(8,128)(2,1)} %convert_element_type.638), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul.53 = bf16[102400,2048]{1,0:T(8,128)(2,1)} custom-call(s32[400]{0:T(512)S(1)} %copy-done.585, s32[1]{0:T(128)} %min.91, bf16[102400,768]{1,0:T(8,128)(2,1)} %get-tuple-element.1444, bf16[16,768,2048]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.160), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_t.21 = bf16[102400,768]{1,0:T(8,128)(2,1)} custom-call(s32[400]{0:T(512)S(1)} %copy-done.584, s32[1]{0:T(128)} %min.91, bf16[102400,2048]{1,0:T(8,128)(2,1)} %multiply_convert_fusion.2, bf16[16,768,2048]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.159), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_t.22 = bf16[102400,2048]{1,0:T(8,128)(2,1)} custom-call(s32[400]{0:T(512)S(1)} %copy-done.586, s32[1]{0:T(128)} %min.91, bf16[102400,768]{1,0:T(8,128)(2,1)} %get-tuple-element.1446, bf16[16,2048,768]{2,1,0:T(8,128)(2,1)} %convert_element_type.847), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_drhs.21 = bf16[16,768,2048]{2,1,0:T(8,128)(2,1)} custom-call(s32[400]{0:T(512)S(1)} %copy-done.585, s32[1]{0:T(128)} %min.91, bf16[102400,768]{1,0:T(8,128)(2,1)} %get-tuple-element.1444, bf16[102400,2048]{1,0:T(8,128)(2,1)} %multiply_convert_fusion.2), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_drhs.22 = bf16[16,2048,768]{2,1,0:T(8,128)(2,1)} custom-call(s32[400]{0:T(512)S(1)} %copy-done.585, s32[1]{0:T(128)} %min.91, bf16[102400,2048]{1,0:T(8,128)(2,1)} %fusion.85, bf16[102400,768]{1,0:T(8,128)(2,1)} %get-tuple-element.1446), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%attn_norm.12 = bf16[16384,2048]{1,0:T(8,128)(2,1)} custom-call(bf16[16384,2048]{1,0:T(8,128)(2,1)S(1)} %copy-done.35, f32[2048]{0:T(1024)} %params__dense0____attn____attn_norm____scale__.1), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%mlp_norm.19 = bf16[16384,2048]{1,0:T(8,128)(2,1)S(1)} custom-call(bf16[16384,2048]{1,0:T(8,128)(2,1)} %bitcast.3052, f32[2048]{0:T(1024)} %params__h3____mlp____mlp_norm____scale__.1), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%final_norm.1 = bf16[16384,2048]{1,0:T(8,128)(2,1)} custom-call(bf16[16384,2048]{1,0:T(8,128)(2,1)} %get-tuple-element.1231, f32[2048]{0:T(1024)} %params__final_norm____scale__.1), custom_call_target="tpu_custom_call"',
     'norm'),
]


@pytest.mark.parametrize("text,kind", RECORDED,
                         ids=[t.split(" = ")[0][1:] for t, _ in RECORDED])
def test_a_recorded_event_is_told_by_its_result_shapes(text, kind):
    assert kernels_mla.classify(text, SIZES) == kind
    # and by the shapes alone: the same text under another name
    anonymous = "%custom-call.7 = " + text.split(" = ", 1)[1]
    assert kernels_mla.classify(anonymous, SIZES) == kind


def test_the_rule_that_was_there_leaves_the_latent_calls_to_this_one():
    """``kernels.classify`` knows no 4-d result: ``gmm_ms``, whose reader
    uses it, counts the grouped products of this cell and nothing of
    its attention."""
    for text, kind in RECORDED:
        want = None if kind == "mla_flash" else kind
        assert kernels.classify(text, SIZES) == want


def test_a_sequence_of_another_length_or_a_plain_op_is_not_a_latent_call():
    text = RECORDED[0][0]
    assert kernels_mla.classify(text, dict(SIZES, seq=8192)) is None
    assert kernels_mla.classify(
        "%fusion.3 = bf16[1,32,16384,128]{3,2,1,0} fusion(bf16[4] %a)",
        SIZES) is None
    # Trinity-Mini's native-layout flash call stays its own rule's
    trinity = ('%attn.full.8 = (bf16[1,8192,4096]{2,1,0}, '
               'f32[1,32,8192,1]{3,2,1,0}) custom-call(bf16[1,8192,4096] '
               '%a), custom_call_target="tpu_custom_call"')
    assert kernels_mla.classify(trinity, dict(SIZES, seq=8192)) == "flash"


def test_split_adds_up_calls_and_time_inside_the_window():
    events = [(t, 10.0 * i, 10.0 * i + 4.0) for i, (t, _) in
              enumerate(RECORDED)]
    events.append(("%fusion.1 = f32[4]{0} fusion(f32[4] %x)", 0.0, 500.0))
    got = kernels_mla.split(events, (0.0, 1000.0), SIZES)
    assert got == {"mla_flash": {"ns": 12.0, "calls": 3},
                   "gmm": {"ns": 24.0, "calls": 6},
                   "norm": {"ns": 12.0, "calls": 3}}
    # an event that straddles the window's edge counts for its part
    assert kernels_mla.split(events, (2.0, 1000.0), SIZES)[
        "mla_flash"] == {"ns": 10.0, "calls": 3}
