"""``benchmarks/reduce/kernels_ssd.py``: the Mosaic kernel calls of a
traced step that holds chunked state-space scans told apart by what an
event's text carries.  The list below is every kind of kernel call of
``nemotron-3-nano-30b-a3b.steady``'s step: the instructions of the step
compiled for a described v5e from PR 35's tree (the profiler names a
device event by its instruction's text; the chip's traced runs of PR 35
counted the same calls: 24 scan, 64 grouped products, 8 flash)."""

import pytest

from benchmarks.reduce import kernels, kernels_ssd

#: the cell runs a layer over one sequence at a time; its batch is two
SIZES = {"batch": 1, "full_batch": 2, "seq": 8192, "held": 8, "chunk": 128}

RECORDED = [
    ('%mlp_norm.17 = bf16[8192,2688]{1,0:T(8,128)(2,1)S(1)} custom-call(%get-tuple-element.3147, %copy-done.478), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%grouped_matmul.32 = bf16[51200,1856]{1,0:T(8,128)(2,1)} custom-call(%copy-done.1258, %min.68, %fusion.19, %convert_element_type.805), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul.33 = bf16[51200,2688]{1,0:T(8,128)(2,1)} custom-call(%copy-done.1258, %min.68, %maximum_multiply_fusion.3, %copy-done.213), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%norm.16 = bf16[8192,2688]{1,0:T(8,128)(2,1)S(1)} custom-call(%bitcast.3826, %copy-done.803), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%ssd_chunk_scan.16 = (bf16[1,64,128,4096]{3,2,1,0:T(8,128)(2,1)}, f32[1,64,32,128,128]{4,3,2,1,0:T(8,128)}) custom-call(%bitcast.3945, %bitcast.4055, %bitcast.4049, %copy.2267, %bitcast.4104, /*index=5*/%bitcast.4008, %copy_bitcast_fusion.11), custom_call_target="tpu_custom_call"',
     'ssd_fwd'),
    ('%norm.18 = bf16[8192,2688]{1,0:T(8,128)(2,1)} custom-call(%bitcast.3830, %copy-done.810), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%attn_norm.5 = bf16[8192,2688]{1,0:T(8,128)(2,1)S(1)} custom-call(%bitcast.3844, %copy-done.828), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%_flash_nl_forward.5 = (bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)}, f32[1,32,8192,1]{3,2,1,0:T(8,128)}) custom-call(%convolution_bitcast_fusion.18, %convolution_bitcast_fusion.25, %convolution_bitcast_fusion.24), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%final_norm.1 = bf16[16384,2688]{1,0:T(8,128)(2,1)} custom-call(%bitcast.3912, %copy-done.800), custom_call_target="tpu_custom_call"',
     'norm'),
    ('%_flash_nl_forward.6 = (bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)S(1)}, f32[1,32,8192,1]{3,2,1,0:T(8,128)}) custom-call(%convolution_bitcast_fusion.17, %convolution_bitcast_fusion.23, %copy-done.331), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%_flash_nl_backward.1 = (bf16[1,8192,256]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,8192,256]{2,1,0:T(8,128)(2,1)}) custom-call(%convolution_bitcast_fusion.17, %convolution_bitcast_fusion.23, %convolution_bitcast_fusion.22, %copy.2673, %jit__flash_nl_forward_.12, /*index=5*/%bitcast.653), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%_flash_nl_backward = bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)} custom-call(%convolution_bitcast_fusion.17, %convolution_bitcast_fusion.23, %convolution_bitcast_fusion.22, %copy.2673, %jit__flash_nl_forward_.12, /*index=5*/%bitcast.653), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%_flash_nl_backward.3 = (bf16[1,8192,256]{2,1,0:T(8,128)(2,1)}, bf16[1,8192,256]{2,1,0:T(8,128)(2,1)}) custom-call(%convolution_bitcast_fusion.16, %convolution_bitcast_fusion.21, %convolution_bitcast_fusion.20, %copy.2675, %jit__flash_nl_forward_.15, /*index=5*/%bitcast.669), custom_call_target="tpu_custom_call"',
     'flash'),
    ('%ssd_chunk_scan_bwd.8 = (bf16[1,64,128,4096]{3,2,1,0:T(8,128)(2,1)}, f32[1,64,128,1024]{3,2,1,0:T(8,128)}, f32[1,64,128,1024]{3,2,1,0:T(8,128)}, f32[1,64,8,128,8]{4,3,2,1,0:T(8,128)}, f32[1,64,8,8,128]{4,3,2,1,0:T(8,128)}, /*index=5*/f32[1,64,8,128,8]{4,3,2,1,0:T(8,128)}, f32[1,64,8,8,128]{4,3,2,1,0:T(8,128)}) custom-call(%bitcast.3922, %bitcast.3937, %bitcast.4038, %bitcast.4030, %pallas_call.297, /*index=5*/%copy-done.252, %bitcast.4083, %bitcast.3996, %copy_bitcast_fusion.7), custom_call_target="tpu_custom_call"',
     'ssd_bwd'),
    ('%grouped_matmul_t.16 = bf16[51200,1856]{1,0:T(8,128)(2,1)} custom-call(%copy-done.1150, %min.140, %multiply_convert_fusion.7, %convert_element_type.1090), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_drhs.17 = bf16[8,2688,1856]{2,1,0:T(8,128)(2,1)} custom-call(%copy-done.1150, %min.140, %fusion.83, %get-tuple-element.3092), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_drhs.16 = bf16[8,1856,2688]{2,1,0:T(8,128)(2,1)} custom-call(%copy-done.1150, %min.140, %get-tuple-element.3091, %multiply_convert_fusion.7), custom_call_target="tpu_custom_call"',
     'gmm'),
    ('%grouped_matmul_t.17 = bf16[51200,2688]{1,0:T(8,128)(2,1)} custom-call(%copy-done.1150, %min.140, %get-tuple-element.3092, %copy-done.204), custom_call_target="tpu_custom_call"',
     'gmm'),
]


@pytest.mark.parametrize("text,kind", RECORDED,
                         ids=[t.split(" = ")[0][1:] for t, _ in RECORDED])
def test_a_recorded_event_is_told_by_its_result_shapes(text, kind):
    assert kernels_ssd.classify(text, SIZES) == kind
    # and by the shapes alone: the same text under another name
    anonymous = "%custom-call.7 = " + text.split(" = ", 1)[1]
    assert kernels_ssd.classify(anonymous, SIZES) == kind


def test_the_rule_that_was_there_leaves_the_scan_calls_alone():
    """``kernels.classify`` knows no 4-d result: ``gmm_ms``, whose reader
    uses it, counts the grouped products of this cell (a width of 1856
    and all) and nothing of its scans."""
    for text, kind in RECORDED:
        want = None if kind.startswith("ssd") else kind
        assert kernels.classify(text, SIZES) == want


def test_another_chunk_or_length_or_a_plain_op_is_not_a_scan_call():
    forward = next(t for t, k in RECORDED if k == "ssd_fwd")
    assert kernels_ssd.classify(forward, dict(SIZES, seq=16384)) is None
    assert kernels_ssd.classify(forward, dict(SIZES, chunk=256)) is None
    assert kernels_ssd.classify(
        "%fusion.3 = bf16[1,64,128,4096]{3,2,1,0} fusion(bf16[4] %a)",
        SIZES) is None
    # a latent-attention call's first result is 4-d too, by heads
    latent = ('%attn.mla.3 = (bf16[1,32,8192,128]{3,2,1,0}, '
              'f32[1,32,8192,1]{3,2,1,0}) custom-call(bf16[4] %a), '
              'custom_call_target="tpu_custom_call"')
    assert kernels_ssd.classify(latent, SIZES) is None


def test_split_adds_up_calls_and_time_inside_the_window():
    events = [(t, 10.0 * i, 10.0 * i + 4.0) for i, (t, _) in
              enumerate(RECORDED)]
    events.append(("%fusion.1 = f32[4]{0} fusion(f32[4] %x)", 0.0, 500.0))
    got = kernels_ssd.split(events, (0.0, 1000.0), SIZES)
    count = lambda k: sum(kind == k for _, kind in RECORDED)  # noqa: E731
    assert got == {k: {"ns": 4.0 * count(k), "calls": count(k)}
                   for k in ("norm", "gmm", "ssd_fwd", "flash", "ssd_bwd")}
    assert (count("ssd_fwd"), count("ssd_bwd"), count("gmm")) == (1, 1, 6)
