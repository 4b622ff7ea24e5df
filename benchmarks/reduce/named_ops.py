"""The ops of a traced step that the PROGRAM put under one name, kernel
calls or not: every op's ``tf_op`` (the instruction's ``op_name``, which
the profiler keeps on the event metadata; ``scopes.op_facts``) carries
the scopes the program opened around it, so an op XLA compiled itself
(a fusion, a ``while``, a product) is told as a kernel call is
(``kernels_named.py``), by a path component: ``.../ssm.scan/gated_delta/
while/body/dot_general`` is one of ``gated_delta``'s.  An op with no
name of its own goes by its neighbours' (``scopes.inherited``), as in
the split by parts.

Each op's SELF time on device 0 (``scopes.self_times``: a ``while``
keeps what no child covers), so that the sum over a name's ops is the
device time spent under it, counted once.  The calls of an op whose
carry is a ``lax.scan`` are counted in the step's PROGRAM, which the
profiler's file keeps (``program_loops``: its ``while`` instructions
under the name): a ``while``'s event carries no name of its own.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

from benchmarks.reduce import program_spans, scopes, xplane


def split(events: Iterable[xplane.Event], facts: Dict[str, Dict[str, Any]],
          window: xplane.Interval, component: str,
          neighbours: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Device nanoseconds (``ns``, and ``by_phase``) and ops (``ops``,
    and ``stems``: how many of each kind, ``fusion``, ``while``, ..)
    inside ``window`` of the ops whose name holds the path component
    ``component``."""
    neighbours = neighbours or {}
    out = {"ns": 0.0, "ops": 0, "by_phase": {}, "stems": {}}
    under: Dict[str, bool] = {}
    for name, own in scopes.self_times(events, window):
        tf_op = (facts.get(name) or {}).get("tf_op") or \
            neighbours.get(xplane.op_name(name), "")
        if tf_op not in under:
            under[tf_op] = component in scopes.components(tf_op)
        if not under[tf_op]:
            continue
        out["ns"] += own
        out["ops"] += 1
        phase = scopes.phase(tf_op)
        out["by_phase"][phase] = out["by_phase"].get(phase, 0.0) + own
        stem = xplane.op_stem(name)
        out["stems"][stem] = out["stems"].get(stem, 0) + 1
    return out


def program_loops(program: Dict[int, Dict[str, Any]], component: str
                  ) -> int:
    """``while`` instructions of a step's program (``scopes.
    step_program``) whose own name holds ``component``: each runs once a
    step, whatever the profiler keeps of it on its event."""
    return sum(1 for one in program.values() if one["opcode"] == "while"
               and component in scopes.components(one["op_name"]))


def of_run(trace, run, component: str) -> Optional[Dict[str, Any]]:
    """:func:`split` of device 0 over the traced window of a run, read
    once a ``component`` and kept on ``trace``; ``None`` where there is
    no trace, the program left no ``step.scopes`` span, or the file
    names no op.  An event whose metadata carries no name goes by its
    instruction's own name in the step's program, then by its
    neighbours'; ``program_loops``: :func:`program_loops` of that
    program."""
    if not trace or not trace.get("path"):
        return None
    kept = trace.setdefault("_named_ops", {})
    if component not in kept:
        kept[component] = None
        parts = scopes.step_parts(program_spans.timeline())
        facts = scopes.op_facts(trace["path"]) if parts else {}
        planes = xplane.device_planes(xplane.load(trace["path"])) \
            if any(f.get("tf_op") for f in facts.values()) else []
        if planes:
            program = scopes.step_program(trace["path"], run["step_module"])
            names = {one["name"]: one["op_name"]
                     for one in program.values() if one["op_name"]}
            names.update(scopes.inherited(program))
            kept[component] = dict(split(
                xplane._events(planes[0], "XLA Ops"), facts,
                trace["devices"][0]["window"], component, names),
                program_loops=program_loops(program, component))
    return kept[component]
