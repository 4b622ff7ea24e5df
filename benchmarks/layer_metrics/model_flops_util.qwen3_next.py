"""Whole step: the benchmark's own FLOPs of one step of the cut
Qwen3-Next model (``benchmarks/costs_qwen3_next.py``: tokens a step x
FLOPs a token: the linear mixers' projections and the scan's necessary
products, visible attention pairs only, the held experts a token meets
on average; recompute not counted) over the device time of the step's
program (median over the traced steps, device 0) and the chip's
published bf16 peak.  Gaps between steps are not in it:
``device_idle_share`` has those."""

import statistics

from benchmarks import costs_qwen3_next, peaks


def read(trace, spans, run):
    if not trace or not trace["devices"][0]["step_ns"]:
        return None
    final = run["final"]
    tokens = final["batch"] // run["chips"] * final["seq"]
    flops = tokens * costs_qwen3_next.train_flops_per_token(
        run["config"], final["seq"])
    seconds = statistics.median(trace["devices"][0]["step_ns"]) / 1e9
    peak = peaks.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / seconds / peak
