"""Checkpoint and object plane: tokens per second per chip of the window
at this traffic's compressed save cadence.  Nearly all of a cycle is
the save, so this is the save path's speed in another unit, and it
swings with it (spread 2.4% over six runs where the steady cell's is
0.001%): a per-layer reading, not a judge."""


def read(trace, spans, run):
    return run["end_to_end"]["tokens_per_s_per_chip"]
