"""The Mosaic kernel calls of a traced step told apart by the PROGRAM'S
names: every call's ``tf_op`` (the instruction's ``op_name``, which the
profiler keeps on the event metadata; ``scopes.op_facts``) carries the
step's part it ran under and the name of the function that built the
kernel (``.../h3/attn/pass2/attn.full/jit(_flash_nl_forward)/
pallas_call:``), so a reader asks for "the calls under ``attn`` whose
name holds ``_flash_``" and needs no result shapes (PERF.md section 7:
what ``kernels.py`` and its two siblings did before every call was
named).  The parts are the run's own list (``model:step.scopes``).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Sequence

from benchmarks.reduce import program_spans, scopes, xplane


def split(events: Iterable[xplane.Event], facts: Dict[str, Dict[str, Any]],
          window: xplane.Interval, parts: Sequence[str], part: str,
          kernel: str) -> Dict[str, float]:
    """Device nanoseconds and calls, inside ``window``, of the kernel
    calls under ``part`` whose ``tf_op`` holds ``kernel``."""
    lo, hi = window
    out = {"ns": 0.0, "calls": 0}
    for name, s, e in events:
        if e <= lo or s >= hi or not xplane.is_kernel_call(name):
            continue
        tf_op = (facts.get(name) or {}).get("tf_op") or ""
        if kernel in tf_op and scopes.part(tf_op, parts) == part:
            out["ns"] += min(e, hi) - max(s, lo)
            out["calls"] += 1
    return out


def of_run(trace, run, part: str, kernel: str
           ) -> Optional[Dict[str, float]]:
    """:func:`split` of device 0 over the traced window of a run, read
    once a ``(part, kernel)`` and kept on ``trace``; ``None`` where there
    is no trace, the program left no ``step.scopes`` span, or the file
    names no op."""
    if not trace or not trace.get("path"):
        return None
    kept = trace.setdefault("_named_kernels", {})
    if (part, kernel) not in kept:
        kept[(part, kernel)] = None
        parts = scopes.step_parts(program_spans.timeline())
        facts = scopes.op_facts(trace["path"]) if parts else {}
        planes = xplane.device_planes(xplane.load(trace["path"])) \
            if any(f.get("tf_op") for f in facts.values()) else []
        if planes:
            kept[(part, kernel)] = split(
                xplane._events(planes[0], "XLA Ops"), facts,
                trace["devices"][0]["window"], parts, part, kernel)
    return kept[(part, kernel)]
