"""``benchmarks/reduce/kernels.py``: the Mosaic kernel calls of a traced
step told apart by what an event's text carries.  The event list below
is cut from a traced run of ``trinity-mini.steady`` on the chip (PR 28:
one event of each kind the step has, text as the profiler gives it,
times rewritten)."""

import pytest

from benchmarks.reduce import kernels

#: the cell runs a layer over one sequence at a time: a call sees one
SIZES = {"batch": 1, "full_batch": 2, "seq": 8192, "held": 16}

RECORDED = [
    ('%attn_norm.21 = bf16[8192,2048]{1,0:T(8,128)(2,1)} custom-call(bf16[8192,2048]{1,0:T(8,128)(2,1)S(1)} %slice_bitcast_fusion.9, f32[2048]{0:T(1024)S(1)} %copy-done.1414), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%attn.sliding.32 = (bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)}, f32[1,32,8192,1]{3,2,1,0:T(8,128)}) custom-call(bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} %copy.4681, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.616, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.1804), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%attn_post_norm.10 = bf16[8192,2048]{1,0:T(8,128)(2,1)} custom-call(bf16[8192,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.2298, f32[2048]{0:T(1024)S(1)} %copy-done.1315), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%mlp_norm.20 = bf16[8192,2048]{1,0:T(8,128)(2,1)} custom-call(bf16[8192,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.9104, f32[2048]{0:T(1024)S(1)} %copy-done.1420), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%mlp_post_norm.10 = bf16[8192,2048]{1,0:T(8,128)(2,1)S(1)} custom-call(bf16[8192,2048]{1,0:T(8,128)(2,1)} %fusion.2745, f32[2048]{0:T(1024)S(1)} %copy-done.1319), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%grouped_matmul.177 = bf16[20480,1024]{1,0:T(8,128)(2,1)} custom-call(s32[80]{0:T(128)S(1)} %get-tuple-element.16334, s32[1]{0:T(128)} %bitcast.2816, bf16[20480,2048]{1,0:T(8,128)(2,1)} %fusion.409, bf16[16,2048,1024]{2,1,0:T(8,128)(2,1)S(1)} %custom-call.863), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul.179 = bf16[20480,2048]{1,0:T(8,128)(2,1)S(1)} custom-call(s32[80]{0:T(128)S(1)} %get-tuple-element.16334, s32[1]{0:T(128)} %bitcast.2816, bf16[20480,1024]{1,0:T(8,128)(2,1)} %multiply_multiply_fusion.27, bf16[16,1024,2048]{2,1,0:T(8,128)(2,1)} %get-tuple-element.16294), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%attn.full.8 = (bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)}, f32[1,32,8192,1]{3,2,1,0:T(8,128)}) custom-call(bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)S(1)} %multiply_convert_fusion.10, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.609, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %convolution_bitcast_fusion.10), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%final_norm.1 = bf16[16384,2048]{1,0:T(8,128)(2,1)} custom-call(bf16[16384,2048]{1,0:T(8,128)(2,1)} %bitcast.9258, f32[2048]{0:T(1024)S(1)} %copy-done.1411), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%grouped_matmul_t.27 = bf16[20480,1024]{1,0:T(8,128)(2,1)} custom-call(s32[80]{0:T(128)S(1)} %copy-done.1712, s32[1]{0:T(128)} %get-tuple-element.889, bf16[20480,2048]{1,0:T(8,128)(2,1)} %select_convert_fusion.3, bf16[16,1024,2048]{2,1,0:T(8,128)(2,1)} %get-tuple-element.890), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_drhs.27 = bf16[16,1024,2048]{2,1,0:T(8,128)(2,1)} custom-call(s32[80]{0:T(128)S(1)} %copy-done.1712, s32[1]{0:T(128)} %get-tuple-element.889, bf16[20480,1024]{1,0:T(8,128)(2,1)} %get-tuple-element.891, bf16[20480,2048]{1,0:T(8,128)(2,1)} %select_convert_fusion.3), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_drhs.29 = bf16[16,2048,1024]{2,1,0:T(8,128)(2,1)} custom-call(s32[80]{0:T(128)S(1)} %copy-done.1712, s32[1]{0:T(128)} %get-tuple-element.889, bf16[20480,2048]{1,0:T(8,128)(2,1)} %get-tuple-element.898, bf16[20480,1024]{1,0:T(8,128)(2,1)} %get-tuple-element.19524), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_t.29 = bf16[20480,2048]{1,0:T(8,128)(2,1)} custom-call(s32[80]{0:T(128)S(1)} %copy-done.1712, s32[1]{0:T(128)} %get-tuple-element.889, bf16[20480,1024]{1,0:T(8,128)(2,1)} %get-tuple-element.19524, bf16[16,2048,1024]{2,1,0:T(8,128)(2,1)} %get-tuple-element.899), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%attn.full.14 = (bf16[1,8192,512]{2,1,0:T(8,128)(2,1)}, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.400, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %multiply_convert_fusion.20, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %convolution_bitcast_fusion.8, bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} %get-tuple-element.21242, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %pallas_call.437, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %bitcast.580), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%attn.full.15 = bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} custom-call(bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.400, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %multiply_convert_fusion.20, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %convolution_bitcast_fusion.8, bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} %get-tuple-element.21242, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %pallas_call.437, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %bitcast.580), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%attn.sliding.44 = (bf16[1,8192,512]{2,1,0:T(8,128)(2,1)}, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.385, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %copy.4765, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %convolution_bitcast_fusion.6, bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} %get-tuple-element.21266, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %pallas_call.455, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %bitcast.668), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%attn.sliding.45 = bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} custom-call(bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.385, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %copy.4765, bf16[1,8192,512]{2,1,0:T(8,128)(2,1)} %convolution_bitcast_fusion.6, bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} %get-tuple-element.21266, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %pallas_call.455, f32[1,32,8192,1]{3,2,1,0:T(8,128)} %bitcast.668), custom_call_target="tpu_custom_call"',
     'flash'),
]


def test_result_shapes_reads_what_stands_before_the_op():
    text = ('%x.3 = (bf16[2,8192,4096]{2,1,0}, f32[2,32,8192,1]{3,2,1,0}) '
            'custom-call(bf16[2,8192,4096] %a, bf16[2,8192,512] %b), '
            'custom_call_target="tpu_custom_call"')
    assert kernels.result_shapes(text) == [(2, 8192, 4096),
                                           (2, 32, 8192, 1)]


@pytest.mark.parametrize("result,kind", [
    ("(bf16[1,8192,4096], f32[1,32,8192,1])", "flash"),    # forward
    ("(bf16[2,8192,512], bf16[2,8192,512])", "flash"),     # dK/dV, whole
    ("bf16[1,8192,4096]", "flash"),                        # dQ
    ("bf16[20480,1024]", "gmm"),                           # gate, up
    ("bf16[20480,2048]", "gmm"),                           # down, d lhs
    ("bf16[16,2048,1024]", "gmm"),                         # d rhs
    ("bf16[8192,2048]", "norm"),                           # a sequence
    ("bf16[16384,2048]", "norm"),                          # final norm
])
def test_classify_by_result_shape(result, kind):
    text = (f'%call.7 = {result} custom-call(bf16[1] %p), '
            f'custom_call_target="tpu_custom_call"')
    assert kernels.classify(text, SIZES) == kind


def test_the_shapes_decide_and_other_ops_are_no_kernels():
    named = ('%grouped_matmul.2 = bf16[2,8192,4096] custom-call(), '
             'custom_call_target="tpu_custom_call"')
    assert kernels.classify(named, SIZES) == "flash"
    assert kernels.classify("%fusion.1 = bf16[2,8192,4096] fusion()",
                            SIZES) is None
    odd = ('%c = s32[7] custom-call(), custom_call_target='
           '"tpu_custom_call"')
    assert kernels.classify(odd, SIZES) is None


def test_split_sums_each_family_inside_the_window():
    call = 'custom-call(), custom_call_target="tpu_custom_call"'
    events = [
        (f"%a = bf16[2,8192,4096] {call}", 0, 10),       # before the window
        (f"%b = bf16[2,8192,4096] {call}", 95, 120),     # clipped to 100
        (f"%c = bf16[20480,1024] {call}", 130, 140),
        (f"%d = bf16[16,1024,2048] {call}", 140, 155),
        (f"%e = bf16[8192,2048] {call}", 160, 165),
        ("%f = bf16[2,8192,4096] fusion()", 165, 190),   # no kernel
        (f"%g = s32[3] {call}", 190, 195),               # unknown kernel
        (f"%h = bf16[2,8192,512] {call}", 195, 230),     # clipped to 200
    ]
    got = kernels.split(events, (100, 200), SIZES)
    assert got == {"flash": {"ns": 25.0, "calls": 2},
                   "gmm": {"ns": 25.0, "calls": 2},
                   "norm": {"ns": 5.0, "calls": 1},
                   "other": {"ns": 5.0, "calls": 1}}


def test_the_recorded_events_of_the_cell_are_all_known():
    """17 distinct kernel instructions of one traced step (my chip run,
    PR 28): 5 norms, 6 flash (sliding and full: forward, dK/dV, dQ), 6
    grouped products (forward, d lhs, d rhs at both widths)."""
    kinds = [kernels.classify(text, SIZES) for text, _ in RECORDED]
    assert kinds == [want for _, want in RECORDED]
    assert (kinds.count("norm"), kinds.count("flash"),
            kinds.count("gmm")) == (5, 6, 6)
