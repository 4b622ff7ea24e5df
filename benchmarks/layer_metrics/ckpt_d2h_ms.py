"""Checkpoint and object plane: median milliseconds of the device-to-host
leg of ``Checkpoint.from_pytree`` (its ``train:ckpt.d2h`` child: every
transfer started, then every leaf taken), as the ``d2h_ms`` argument of
``train:ckpt.from_pytree`` gives it."""

import statistics

from benchmarks.reduce import program_spans as ps


def median_arg(rows, run, arg):
    """Median of one argument of ``train:ckpt.from_pytree`` over the
    window's saves: over ALL of them, as ``median_leg_ms`` takes its
    legs, or nothing (a program whose span has no such argument, a save
    whose row did not arrive)."""
    saves = run["final"]["window"]["saves"]
    ids = ps.window_saves(rows, saves)
    found = [row["args"].get(arg) for ckpt in ids
             for row in ps.select(rows, "train", "ckpt.from_pytree",
                                  ckpt=ckpt)[:1]]
    if not saves or len(found) < len(saves) or None in found:
        return None
    return statistics.median(found)


def read(trace, spans, run):
    return median_arg(ps.timeline(), run, "d2h_ms")
