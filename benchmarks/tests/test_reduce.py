"""The trace reduction, on the two traces recorded in ``profiles/`` (what
``profiles/ANALYSIS.md`` read from them by hand) and on a synthetic
trace that pins the interval arithmetic."""

import os

import pytest

from benchmarks import costs, peaks
from benchmarks.reduce import xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: path, step ms, flash ms/step, LM-head `while` ms/step, copies ms/step
#: (ANALYSIS.md: "Where the 309 ms step goes" and the round-5 table)
RECORDED = [
    ("profiles/gpt2_train/plugins/profile/2026_07_31_17_29_36/vm.xplane.pb",
     309.1, 66.7, 84.8, 36.5),
    ("profiles/gpt2_train_nl/plugins/profile/2026_08_01_10_33_01/"
     "vm.xplane.pb", 267.1, 65.3, 79.8, 5.1),
]


@pytest.mark.parametrize("path,step_ms,flash_ms,while_ms,copy_ms", RECORDED)
def test_recorded_trace_reduces_to_what_was_read_by_hand(
        path, step_ms, flash_ms, while_ms, copy_ms):
    profile = xplane.load(os.path.join(REPO, path))
    planes = xplane.device_planes(profile)
    assert [p.name for p in planes] == ["/device:TPU:0"]
    # the other planes are not devices
    assert len(list(profile.planes)) > 1
    dev = xplane.reduce_device(planes[0], module="jit_step")
    assert dev["steps"] == 3
    for ns in dev["step_ns"]:
        assert ns / 1e6 == pytest.approx(step_ms, abs=0.1)
    per_step = lambda ns: ns / 1e6 / dev["steps"]  # noqa: E731
    assert per_step(dev["kernel_ns"]) == pytest.approx(flash_ms, abs=0.1)
    assert dev["kernel_calls"] == 3 * 36  # 12 layers x fwd, dK/dV, dQ
    assert per_step(dev["op_ns"]["tpu_custom_call"]) == pytest.approx(
        flash_ms, abs=0.1)
    assert per_step(dev["op_ns"]["while"]) == pytest.approx(while_ms, abs=0.1)
    assert per_step(dev["op_ns"]["copy"]) == pytest.approx(copy_ms, abs=0.1)
    busy = dev["busy_ns"] / dev["window_ns"]
    assert 0.99 < busy < 1.0
    share = dev["kernel_ns"] / dev["window_ns"]
    assert 0.20 < share < 0.26
    # one chip: no collectives; the old traces carry no benchmark spans
    assert dev["collective_ns"] == 0 and dev["collective_exposed_ns"] == 0
    assert xplane.host_spans(profile) == []
    # the roofline share of those 36 calls cannot pass 100%
    need = costs.flash_step_cost(12, 32, 1024, 12, 64, remat=False)
    assert need["calls"] == 36
    least = costs.roofline_seconds(need["flops"], need["bytes"],
                                   peaks.peaks("TPU v5 lite"))
    assert 0.05 < least["seconds"] / (per_step(dev["kernel_ns"]) / 1e3) < 1.0


class _Event:
    def __init__(self, name, start, end, stats=()):
        self.name, self.start_ns = name, float(start)
        self.duration_ns, self.stats = float(end - start), stats


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _synthetic():
    """One step of 100 ns: a matmul 0-40, an all-gather in flight 30-70
    whose ``-done`` holds the core 60-70, an idle gap 40-60, a kernel
    70-90, then idle to 100 while the host serialises."""
    ops = [
        _Event("%fusion.1 = bf16[8] fusion(...)", 0, 40),
        _Event("%all-gather-done.2 = bf16[8] all-gather-done(...)", 60, 70),
        _Event('%h0.3 = bf16[8] custom-call(...), custom_call_target='
               '"tpu_custom_call"', 70, 90),
    ]
    asyncs = [_Event("%all-gather-start.2 = bf16[8] all-gather-start(...)",
                     30, 70)]
    modules = [_Event("jit_train_step(123)", 0, 90)]
    device = _Plane("/device:TPU:0", [
        _Line("XLA Modules", modules), _Line("XLA Ops", ops),
        _Line("Async XLA Ops", asyncs)])
    host = _Plane("/host:CPU", [_Line("train-loop", [
        _Event("bench:traced", 0, 100), _Event("bench:dispatch", 0, 5),
        _Event("bench:data", 41, 58), _Event("bench:ckpt_serialize", 88, 100),
        _Event("not ours", 0, 100)])])
    return device, host


def test_synthetic_trace_pins_busy_gaps_and_exposed_collectives():
    device, host = _synthetic()

    class Profile:
        planes = [host, device, _Plane("/device:TPU:1", []),
                  _Plane("#Chip0 Misc", [])]

    assert [p.name for p in xplane.device_planes(Profile)] == [
        "/device:TPU:0", "/device:TPU:1"]
    spans = xplane.host_spans(Profile)
    assert [s[0] for s in spans] == ["traced", "dispatch", "data",
                                     "ckpt_serialize"]
    dev = xplane.reduce_device(device, window=(0.0, 100.0),
                               module="jit_train_step")
    assert dev["steps"] == 1 and dev["window_ns"] == 100
    assert dev["busy_ns"] == 40 + 10 + 20
    assert dev["idle_gaps"] == [(40.0, 60.0), (90.0, 100.0)]
    # in flight 30-70; the matmul hides 30-40, nothing runs 40-70
    assert dev["collective_ns"] == 40
    assert dev["collective_exposed_ns"] == 30
    assert dev["kernel_ns"] == 20 and dev["kernel_calls"] == 1
    assert dev["op_ns"] == {"fusion": 40, "all-gather-done": 10,
                            "tpu_custom_call": 20}
    named = xplane.attribute(dev["idle_gaps"],
                             [s for s in spans if s[0] != "traced"])
    assert named == [("data", 40.0, 60.0), ("ckpt_serialize", 90.0, 100.0)]
    assert xplane.attribute([(200.0, 210.0)], spans) == [
        ("other", 200.0, 210.0)]
    # the default window is the steps' own extent
    assert xplane.reduce_device(device, module="jit_train_step")[
        "window"] == (0.0, 90.0)
    assert xplane.reduce_device(device, module="jit_other") == {}


def test_interval_arithmetic():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert xplane.gaps([(1, 2), (4, 20)], 0, 10) == [(0, 1), (2, 4)]
    assert xplane.exposed([(0, 10), (5, 15)], [(3, 4), (12, 20)]) == 11
    assert xplane.op_stem("%convolution_add_fusion.23 = bf16[1] f()") == \
        "convolution_add_fusion"
    assert xplane.is_container("%while.5 = (s32[]) while(...)")
    assert not xplane.is_container("%while_fusion.5 = f32[] fusion(...)")
    assert xplane.is_collective("%all-reduce-scatter-fusion.1 = f()")


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_flops_per_token_is_the_programs_count_kept_apart():
    from ray_tpu.models.gpt2 import GPT2Config

    for ctor, sizes in (
            (GPT2Config.gpt2_large,
             dict(n_embd=1280, n_layer=36, n_head=20)),
            (GPT2Config.gpt2_xl, dict(n_embd=1600, n_layer=48, n_head=25))):
        cfg = ctor()
        sizes.update(n_positions=1024, vocab_size=50257)
        assert costs.gpt2_num_params(sizes) == cfg.num_params()
        assert costs.gpt2_train_flops_per_token(sizes, 1024) == \
            cfg.flops_per_token()
