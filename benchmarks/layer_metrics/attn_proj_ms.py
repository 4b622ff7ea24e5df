"""Train step: device milliseconds a step in the matrix products of the
part ``attn``: the piece ``attn.proj`` (q, k, v, gate and output
projections, GPT-2's ``attn_qkv`` and ``attn_proj``, the latent down-
and up-projections, each with its bias and the split or reshape of its
result), all phases, each op's self time on device 0
(``reduce/pieces.py``).  ``None`` where the program said no pieces or
the profiler's file names no op."""

from benchmarks.reduce import pieces


def read(trace, spans, run):
    return pieces.piece_ms(trace, run, "proj")
