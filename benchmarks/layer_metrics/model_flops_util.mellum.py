"""Whole step: the benchmark's own FLOPs of one step of the Mellum
stage on ONE CHIP (``benchmarks/costs_mellum.py``: the chip's tokens a
step x FLOPs a token, visible attention pairs only, the eight experts a
token meets somewhere in the group, as many pairs as arrive here at an
even router; recompute not counted, the exchange moves bytes) over the
device time of the step's program (median over the traced steps, device
0) and the chip's published bf16 peak.  Gaps between steps are not in
it: ``device_idle_share`` has those."""

import statistics

from benchmarks import costs_mellum, peaks


def read(trace, spans, run):
    if not trace or not trace["devices"][0]["step_ns"]:
        return None
    final = run["final"]
    tokens = final["batch"] // run["chips"] * final["seq"]
    flops = tokens * costs_mellum.train_flops_per_token(
        run["config"], final["seq"])
    seconds = statistics.median(trace["devices"][0]["step_ns"]) / 1e9
    peak = peaks.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / seconds / peak
