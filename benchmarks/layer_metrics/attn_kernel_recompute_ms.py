"""Train step: device milliseconds a step in the kernel calls of the
part ``attn`` that the backward pass runs AGAIN: the piece
``attn.kernel`` in the phase ``recompute`` (jax's
``rematted_computation`` in the op's name, ``scopes.phase``), each op's
self time on device 0 (``reduce/pieces.py``): what a recompute that
kept a kernel call's output would not run.  ``None`` where the program
said no pieces or the profiler's file names no op."""

from benchmarks.reduce import pieces


def read(trace, spans, run):
    return pieces.piece_ms(trace, run, "kernel", phase="recompute")
