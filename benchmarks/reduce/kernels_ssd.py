"""The Mosaic kernel calls of a traced step that holds chunked
state-space scans (``ops/ssd.py``), told apart.

``kernels.py`` knows flash calls by a 3-d result, grouped products and
fused norms by 2-d ones (and a 3-d ``[experts held, k, n]``).  The
scan's kernels give neither: every array they write is laid out by
chunk, so their FIRST result is 4-d ``[batch, chunks, chunk, heads x
head_dim]`` with ``chunks x chunk`` the sequence: the forward's ``y``
(beside the float32 states that entered each chunk, 5-d), the
backward's ``d xs`` (beside ``d B``, ``d C`` and the per-head vectors:
seven results).  A forward call has at most two results, a backward
call more.  (A latent-attention call's first result is 4-d too, but
``[batch, heads, seq, width]``: its THIRD extent is the sequence.)
Everything else is ``kernels.classify``'s to tell.

The instruction's NAME says ``ssd_chunk_scan`` / ``ssd_chunk_scan_bwd``
under the scope ``ssm.scan`` on today's program; the shapes decide,
because a name is the program's to change; the recorded events in the
tests carry both.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

from benchmarks.reduce import kernels, xplane


def classify(text: str, sizes: Dict[str, int]) -> Optional[str]:
    """``ssd_fwd`` | ``ssd_bwd`` | ``flash`` | ``gmm`` | ``norm`` |
    ``None``; ``sizes`` as ``kernels.classify`` takes them, and
    ``chunk``."""
    if not xplane.is_kernel_call(text):
        return None
    batches = {sizes["batch"], sizes.get("full_batch", sizes["batch"])}
    results = kernels.result_shapes(text)
    first = results[:1]
    if first and len(first[0]) == 4 and first[0][0] in batches \
            and first[0][2] == sizes.get("chunk") \
            and first[0][1] * first[0][2] == sizes["seq"]:
        return "ssd_fwd" if len(results) <= 2 else "ssd_bwd"
    return kernels.classify(text, sizes)


def split(events: Iterable[xplane.Event], window: xplane.Interval,
          sizes: Dict[str, int]) -> Dict[str, Dict[str, float]]:
    """Device nanoseconds and calls of each family inside ``window``,
    and of the kernel calls no rule knows (``other``)."""
    lo, hi = window
    out: Dict[str, Dict[str, float]] = {}
    for name, s, e in events:
        if e <= lo or s >= hi or not xplane.is_kernel_call(name):
            continue
        kind = classify(name, sizes) or "other"
        row = out.setdefault(kind, {"ns": 0.0, "calls": 0})
        row["ns"] += min(e, hi) - max(s, lo)
        row["calls"] += 1
    return out


def of_run(trace, run) -> Optional[Dict[str, Dict[str, float]]]:
    """The split of device 0's kernel calls over the traced window of a
    run, read once from the profiler's file and kept on ``trace``;
    ``None`` where there is no trace."""
    if not trace or not trace.get("path"):
        return None
    if "_kernel_split_ssd" not in trace:
        final, conf = run["final"], run["config"]
        sizes = {"batch": 1, "full_batch": final["batch"] // run["chips"],
                 "seq": final["seq"], "chunk": conf.get("chunk_size"),
                 "held": (conf.get("as_run") or {}).get(
                     "experts_held", (0, 0))[1]}
        planes = xplane.device_planes(xplane.load(trace["path"]))
        trace["_kernel_split_ssd"] = split(
            xplane._events(planes[0], "XLA Ops"),
            trace["devices"][0]["window"], sizes) if planes else {}
    return trace["_kernel_split_ssd"]
