"""Whole step: the benchmark's own FLOPs of one step of the cut Xing4.0
model (``benchmarks/costs_xing.py``: tokens a step x FLOPs a token,
visible attention pairs only at the key's and the value's own widths,
the query latent's two products, the connections' projections and the
lanes' sums, the experts a token meets here on average; recompute not
counted) over the device time of the step's program (median over the
traced steps, device 0) and the chip's published bf16 peak.  Gaps
between steps are not in it: ``device_idle_share`` has those."""

import statistics

from benchmarks import costs_xing, peaks


def read(trace, spans, run):
    if not trace or not trace["devices"][0]["step_ns"]:
        return None
    final = run["final"]
    tokens = final["batch"] // run["chips"] * final["seq"]
    flops = tokens * costs_xing.train_flops_per_token(
        run["config"], final["seq"])
    seconds = statistics.median(trace["devices"][0]["step_ns"]) / 1e9
    peak = peaks.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / seconds / peak
