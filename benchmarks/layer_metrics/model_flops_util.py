"""Whole step: tokens/s/chip of this run's window x the benchmark's own
FLOPs per token (6N + 12*L*E*T, recompute not counted) over the chip's
published bf16 peak.  A utilization exists only on an accelerator: on any
other platform (the tests' CPU rehearsal) there is nothing to read."""

from benchmarks import costs, peaks


def read(trace, spans, run):
    if run["device"]["platform"] == "cpu":
        return None
    final = run["final"]
    flops = costs.gpt2_train_flops_per_token(final["sizes"], final["seq"])
    peak = peaks.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * run["end_to_end"]["tokens_per_s_per_chip"] * flops / peak
