"""Train step: device milliseconds a step in what
``ops/flash_attention.py`` does around its kernel calls that is no
kernel: the piece ``attn.layout`` of the part ``attn`` (the transposes
of q, k, v, o, dO and the gradients in the head-major and latent
families, the packed reshapes of the native-layout one, ``delta``'s
product and sum, the ``shard_map``), all phases, each op's self time on
device 0 (``reduce/pieces.py``).  A fusion that also holds another piece
is filed under its root's (the reader prints those).  ``None`` where the
program said no pieces or the profiler's file names no op."""

from benchmarks.reduce import pieces


def read(trace, spans, run):
    return pieces.piece_ms(trace, run, "layout")
