"""The Mosaic kernel calls of a traced step whose attention is latent
(``ops/flash_attention.py``'s ``k_rope=`` kernels), told apart.

``kernels.py`` knows a flash call by a 3-d ``[batch, seq, heads *
head_dim]`` result: the native-layout family's.  The latent-attention
kernels are head-major, so their results are 4-d ``[batch, heads, seq,
width]``: the forward's output (``dv`` wide) beside its ``f32`` row
statistics ``[batch, heads, seq, 1]``; dK/dV's three (``[.., heads, seq,
nope]``, the shared rotary key's ``[batch, 1, seq, rope]``, ``[.., heads,
seq, dv]``); dQ's one (``nope + rope`` wide).  The FIRST result
decides: a native-layout forward call also carries 4-d row statistics,
behind its 3-d output.  Everything else (grouped products, fused norms,
the native-layout flash calls) is ``kernels.classify``'s to tell.

The instruction's NAME says ``attn.mla`` on today's program (its named
scope); the shapes decide, because a scope's name is the program's to
change; the recorded events in the tests carry both.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

from benchmarks.reduce import kernels, xplane


def classify(text: str, sizes: Dict[str, int]) -> Optional[str]:
    """``mla_flash`` | ``gmm`` | ``norm`` | ``None``; ``sizes`` as
    ``kernels.classify`` takes them."""
    if not xplane.is_kernel_call(text):
        return None
    batches = {sizes["batch"], sizes.get("full_batch", sizes["batch"])}
    first = kernels.result_shapes(text)[:1]
    if first and len(first[0]) == 4 and first[0][0] in batches \
            and first[0][2] == sizes["seq"]:
        return "mla_flash"
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
    if "_kernel_split_mla" not in trace:
        final = run["final"]
        sizes = {"batch": 1, "full_batch": final["batch"] // run["chips"],
                 "seq": final["seq"],
                 "held": (run["config"].get("as_run") or {}).get(
                     "experts_held", (0, 0))[1]}
        planes = xplane.device_planes(xplane.load(trace["path"]))
        trace["_kernel_split_mla"] = split(
            xplane._events(planes[0], "XLA Ops"),
            trace["devices"][0]["window"], sizes) if planes else {}
    return trace["_kernel_split_mla"]
