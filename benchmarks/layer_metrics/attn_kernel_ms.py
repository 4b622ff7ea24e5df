"""Train step: device milliseconds a step in the KERNEL CALLS of the
part ``attn``, told by the program's names: the ops whose innermost
piece is ``attn.kernel`` (``ops/flash_attention.py`` opens it around
each ``pallas_call``: forward, recomputed forward, dK/dV, dQ), all
phases, each op's self time on device 0 (``reduce/pieces.py``; the list
of pieces from the run's ``model:step.scopes`` span, ``attn_pieces``).
What ``flash_ms``, ``swa_flash_ms``, ``mla_flash_ms`` and
``flash_ms.ouro`` read by result shapes, read by name.  ``None`` where
the program said no pieces or the profiler's file names no op."""

from benchmarks.reduce import pieces


def read(trace, spans, run):
    return pieces.piece_ms(trace, run, "kernel")
