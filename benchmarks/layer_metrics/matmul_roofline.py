"""Train step: the matmul ops' share of the chip's bf16 peak: the sum of
the COMPILER's own ``flops`` stat over the ops XLA files as a
convolution (``matmul_ms``'s ops) over their device time, over
``benchmarks/peaks.py``'s peak.  A share over 100% means the join of
events and facts is wrong, not the chip fast."""

from benchmarks import peaks
from benchmarks.reduce import scopes


def read(trace, spans, run):
    found = scopes.of_run(trace, run)
    if not found or not found["matmul_ns"]:
        return None
    peak = peaks.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * found["matmul_flops"] / (found["matmul_ns"] / 1e9) / peak
