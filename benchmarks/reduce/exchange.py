"""The collectives of a traced step that ran under ONE of the program's
parts, told by the program's own names (as ``kernels_named.py`` tells
kernel calls): ``scopes.split`` leaves every collective op to
``collective_ms``, whatever part it stands under, so the exchange of a
routed layer that crosses chips (the part ``moe.exchange``: all-gathers
of the group's rows, reduce-scatters of the parts, and their transposes)
is read here.

A collective is in flight from its ``*-start`` to its ``*-done``; the
profiler writes both on the ``XLA Ops`` line and the flight on ``Async
XLA Ops`` (on a v5e host the all-gathers run as one op each on ``XLA
Ops``; a reduce-scatter runs as collective permutes, in flight on the
async line, which carry no name of their own).  :func:`split` takes every event on either line whose name is
a collective's and whose ``tf_op`` stands under the part, and measures
the UNION of their intervals (overlapping ones once) and the part of it
during which no compute op runs.  A program without the part (the parent
of the PR that added it), or a file that names no op: ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Sequence

from benchmarks.reduce import program_spans, scopes, xplane


def split(ops: Iterable[xplane.Event], async_ops: Iterable[xplane.Event],
          facts: Dict[str, Dict[str, Any]], window: xplane.Interval,
          parts: Sequence[str], part: str,
          neighbours: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """``ns``: the union of the part's collectives inside ``window``;
    ``exposed_ns``: of it, what no compute op covers; ``ops``: how many
    events were taken.  A collective with no name of its own (the
    permutes the compiler makes a reduce-scatter of) goes by
    ``neighbours`` (instruction name -> ``op_name``,
    ``scopes.inherited``), which is the name of what it READS, a
    neighbouring part of the same layer (``moe.combine`` for the
    scatter of its result): such a one is taken where that part is of
    ``part``'s family (``moe.`` for ``moe.exchange``: the routed layer
    holds no other collective)."""
    neighbours = neighbours or {}
    family = part.split(".")[0] + "."
    lo, hi = window
    ops = [o for o in ops if o[2] > lo and o[1] < hi]
    taken = []
    for name, s, e in ops + [o for o in async_ops
                             if o[2] > lo and o[1] < hi]:
        if not xplane.is_collective(name):
            continue
        tf_op = (facts.get(name) or {}).get("tf_op") or ""
        if scopes.part(tf_op, parts) == part or (not tf_op and (
                scopes.part(neighbours.get(xplane.op_name(name), ""), parts)
                or "").startswith(family)):
            taken.append((s, e))
    compute = [(s, e) for n, s, e in ops
               if not xplane.is_collective(n) and not xplane.is_container(n)]
    flight = xplane.union(taken)
    return {"ns": xplane.measure(xplane.clip(flight, lo, hi)),
            "exposed_ns": xplane.measure(xplane.clip(
                xplane.subtract(flight, xplane.union(compute)), lo, hi)),
            "ops": len(taken)}


def of_run(trace, run, part: str) -> Optional[Dict[str, float]]:
    """:func:`split` of device 0 over the traced window of a run, read
    once a part and kept on ``trace``; ``None`` where there is no trace,
    the program's list of parts lacks ``part``, or the file names no
    op."""
    if not trace or not trace.get("path"):
        return None
    kept = trace.setdefault("_part_collectives", {})
    if part not in kept:
        kept[part] = None
        parts = scopes.step_parts(program_spans.timeline())
        facts = scopes.op_facts(trace["path"]) \
            if parts and part in parts else {}
        planes = xplane.device_planes(xplane.load(trace["path"])) \
            if any(f.get("tf_op") for f in facts.values()) else []
        if planes:
            kept[part] = split(
                xplane._events(planes[0], "XLA Ops"),
                xplane._events(planes[0], "Async XLA Ops"), facts,
                trace["devices"][0]["window"], parts, part,
                scopes.inherited(scopes.step_program(
                    trace["path"], run["step_module"])))
    return kept[part]
