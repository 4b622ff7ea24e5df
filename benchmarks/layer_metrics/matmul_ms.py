"""Train step: device milliseconds a step, device 0, in the ops XLA
files as a convolution (``hlo_category`` ``convolution`` or
``convolution fusion``: its name for every dot on this chip, alone or
as a fusion's root; the Mosaic kernel calls are not among them), each
op's self time."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    found = scopes.of_run(trace, run)
    return scopes.ms_a_step(trace, found["matmul_ns"]) if found else None
