"""Train step: the pieces' own health: percent of device 0's time under
the part ``attn`` (as ``reduce/pieces.py`` files it: ``part_attn_ms`` of
the same trace plus what a compiler's stamp had hidden from it) that
lies under NO piece of the program's list.  ``None`` where the program
said no pieces or the profiler's file names no op."""

from benchmarks.reduce import pieces


def read(trace, spans, run):
    return pieces.unpieced_share(trace, run)
