"""Checkpoint and object plane: median seconds from the worker's
``session.report(checkpoint=...)`` to the checkpoint directory being
whole on the driver's disk (poll RPC, object plane,
``CheckpointManager.register``); it runs beside the loop and competes
with dispatch."""

import statistics


def read(trace, spans, run):
    xs = run["save_to_disk_s"]
    return statistics.median(xs) if xs else None
