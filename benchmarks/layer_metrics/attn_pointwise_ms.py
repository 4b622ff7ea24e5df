"""Train step: device milliseconds a step in the passes of the part
``attn`` that multiply nothing: the pieces ``attn.norm`` (pre-, post-,
per-head and latent norms, the residual stream's read and add),
``attn.pos`` (the rotation and its tables) and ``attn.gate`` (the output
gate's sigmoid and product) together, all phases, each op's self time on
device 0 (``reduce/pieces.py``).  ``None`` where the program said no
pieces or the profiler's file names no op."""

from benchmarks.reduce import pieces


def read(trace, spans, run):
    return pieces.piece_ms(trace, run, *pieces.POINTWISE)
