"""Residual path: the hyper-connections' share of their bandwidth
roofline: the least time the chip could take to move the bytes all
connections of one step cannot do without (``benchmarks/costs_xing.py``
``hc_step_bytes``: a token's lanes read and written once forward, read
once recomputed, read and written once backward) over the chip's HBM
peak, over the device time under BOTH of their parts, ``hc.coef`` and
``hc.mix``.  ``None`` where the program's list has no such parts (the
parent), or nothing ran under them."""

from benchmarks import costs_xing, peaks
from benchmarks.reduce import program_spans, scopes


def read(trace, spans, run):
    parts = scopes.step_parts(program_spans.timeline())
    if not parts or not {"hc.coef", "hc.mix"} <= set(parts):
        return None
    took = scopes.part_ms(trace, run, "hc.coef", "hc.mix")
    if not took:
        return None
    final = run["final"]
    need = costs_xing.hc_step_bytes(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    peak = peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["bytes"] / peak / (took / 1e3)
