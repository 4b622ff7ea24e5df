"""A traced step's device time by the program's own names.

The profiler's file carries, for every HLO instruction that ran, the
facts the compiler knew of it: ``tf_op`` (the instruction's ``op_name``:
jax's transform markers ``jvp(`` and ``transpose(``, every flax module
name on the way and every ``jax.named_scope``), ``hlo_category``,
``flops`` and ``bytes_accessed``.  They sit on the plane's EVENT
METADATA, which ``jax.profiler.ProfileData`` does not show (it gives an
event's own stats: its offset and duration), so :func:`op_facts` reads
them off the file's wire format: four messages of ``xplane.proto``, no
dependency.  The events' times stay ``ProfileData``'s, joined by the
event's name.  The file also keeps the step's whole program (its
``HloProto``, on the plane ``/host:metadata``): :func:`step_program`
reads the five fields of an instruction that :func:`inherited` needs to
name what XLA made for itself and left nameless.

The program says which names are the top-level PARTS of a step in its
plan span ``ray_tpu:model:step.scopes`` (``parts``, comma-joined); this
module takes the list from the run's timeline and from nowhere else.  A
program that says nothing (the parent of the PR that added the span) or
a file without ``tf_op`` gives ``None``, and every reader then ``None``.

Like ``xplane.py``: interval arithmetic on plain rows apart from where
they come from, so that a hand-made trace pins it
(``benchmarks/tests/test_scopes.py``).
"""

from __future__ import annotations

import re
import struct
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, \
    Tuple

from benchmarks.reduce import program_spans, xplane

PLANE = "/device:TPU:0"
FACTS = ("tf_op", "hlo_category", "flops", "bytes_accessed")
PHASES = ("forward", "recompute", "backward", "optimizer", "other")
#: XLA's categories of a dot on this chip, alone or as a fusion's root
MATMUL_CATEGORIES = ("convolution", "convolution fusion")

Key = Tuple[str, Optional[str]]  # (phase, part)


# --------------------------------------------------------------------------
# the file: protobuf wire format, the four messages needed
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: a varint as
    an int, a length-delimited field as a view of its bytes, fixed 64
    and 32 as their bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value (field 2) of a ``map<int64, Message>`` entry."""
    return next((v for f, _, v in _fields(entry) if f == 2), b"")


def _stat(buf) -> Tuple[int, Any]:
    """``XStat``: ``(metadata_id, value)``; a ``ref_value`` comes back as
    ``("ref", id)``: the id of the stat metadata whose NAME is the
    string."""
    key, value = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            value = v
        elif f == 4:  # int64: two's complement in the varint
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = ("ref", v)
    return key, value


def _plane(path: str, name: str) -> List[Tuple[int, int, Any]]:
    """The fields of the file's ``XPlane`` called ``name`` (``[]``: it
    has none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for f1, _, raw in _fields(space):
        if f1 != 1:  # XSpace.planes
            continue
        fields = list(_fields(raw))
        if any(f == 2 and _text(v) == name for f, _, v in fields):
            return fields
    return []


def event_metadata(path: str, plane: str = PLANE
                   ) -> Iterator[Tuple[str, Dict[str, Any]]]:
    """``(name, facts)`` of every event metadata of ``plane``, in the
    file's order; ``facts``: those of :data:`FACTS` the entry has."""
    fields = _plane(path, plane)
    stat_names = {}
    for f2, _, v in fields:
        if f2 == 5:  # XPlane.stat_metadata
            meta = {f: x for f, _, x in _fields(_map_value(v))}
            stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
    for f2, _, entry in fields:
        if f2 != 4:  # XPlane.event_metadata
            continue
        event, facts = None, {}
        for f3, _, v in _fields(_map_value(entry)):
            if f3 == 2:  # XEventMetadata.name
                event = _text(v)
            elif f3 == 5:  # XEventMetadata.stats
                key, value = _stat(v)
                stat = stat_names.get(key)
                if stat in FACTS:
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    facts[stat] = value
        if event is not None:
            yield event, facts


def op_facts(path: str, plane: str = PLANE) -> Dict[str, Dict[str, Any]]:
    """``{event name: {"tf_op", "hlo_category", "flops",
    "bytes_accessed"}}`` for ``plane``: the per-instruction facts the
    profiler wrote, keyed by what ``ProfileData`` calls the event.  (Two
    programs' instructions of one name and text are one entry: the
    later one's.)"""
    return dict(event_metadata(path, plane))


# --------------------------------------------------------------------------
# names
# --------------------------------------------------------------------------

_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def components(tf_op: str) -> List[str]:
    """The path components of an ``op_name``, jax's transform markers
    taken off (``transpose(jvp(head))`` -> ``head``, ``jvp()`` -> ````):
    the profiler's ``tf_op`` is ``<op_name>:<op type>``."""
    out = []
    for piece in tf_op.rsplit(":", 1)[0].split("/"):
        while (m := _TRANSFORM.match(piece)):
            piece = m.group(1)
        out.append(piece)
    return out


def part(tf_op: str, parts: Sequence[str]) -> Optional[str]:
    """The OUTERMOST component that is one of ``parts``, whole or before
    a dot (``attn.sliding`` is ``attn``); ``None``: under no part."""
    for piece in components(tf_op):
        for one in parts:
            if piece == one or piece.startswith(one + "."):
                return one
    return None


def phase(tf_op: str) -> str:
    """``optimizer`` (the part of that name) | ``recompute`` (the forward
    a ``jax.checkpoint`` runs again inside the backward pass: jax's
    ``rematted_computation``) | ``backward`` | ``forward`` | ``other``
    (no marker: what runs outside the gradient and the update)."""
    pieces = components(tf_op)
    if part(tf_op, ("optimizer",)):
        return "optimizer"
    if "rematted_computation" in pieces:
        return "recompute"
    if "transpose(" in tf_op:
        return "backward"
    return "forward" if "jvp(" in tf_op else "other"


# --------------------------------------------------------------------------
# ops XLA made for itself
# --------------------------------------------------------------------------

METADATA_PLANE = "/host:metadata"
#: how far a name is looked for along the data's way
_REACH = 8


def _varints(view) -> List[int]:
    """A packed ``repeated int64``."""
    out, i = [], 0
    while i < len(view):
        value, i = _varint(view, i)
        out.append(value)
    return out


def step_program(path: str, module: str) -> Dict[int, Dict[str, Any]]:
    """The traced step's program as the profiler kept it: the file's
    ``/host:metadata`` plane holds one event metadata a program that ran
    (``jit_train_step(<id>)``), its one stat the serialized ``HloProto``.
    ``{instruction id: {"name", "opcode", "op_name", "operands",
    "calls"}}`` of the first program whose name starts with ``module``:
    every instruction, those inside fusions too (``calls``: ids of the
    ROOT instructions of the computations it calls).  ``{}`` where the
    file has none."""
    for f2, _, entry in _plane(path, METADATA_PLANE):
        if f2 != 4:  # XPlane.event_metadata: one a program
            continue
        fields = list(_fields(_map_value(entry)))
        if not any(f == 2 and _text(v).startswith(module)
                   for f, _, v in fields):
            continue
        for f, _, stat in fields:
            proto = next((v for g, _, v in _fields(stat) if g == 6),
                         None) if f == 5 else None
            if proto is not None:
                return _instructions(proto)
    return {}


def _instructions(hlo_proto) -> Dict[int, Dict[str, Any]]:
    """``HloProto.hlo_module.computations[].instructions[]``: name (1),
    opcode (2), metadata.op_name (7 > 2), id (35), operand_ids (36),
    called_computation_ids (38); a computation's id (5) and root_id
    (6)."""
    module = next((v for f, _, v in _fields(hlo_proto) if f == 1), b"")
    out: Dict[int, Dict[str, Any]] = {}
    roots: Dict[int, int] = {}
    for f, _, computation in _fields(module):
        if f != 3:
            continue
        ident = root = 0
        for g, _, v in _fields(computation):
            if g == 5:
                ident = v
            elif g == 6:
                root = v
            elif g == 2:
                one = {"name": "", "opcode": "", "op_name": "",
                       "operands": [], "calls": []}
                key = 0
                for h, wire, x in _fields(v):
                    if h == 1:
                        one["name"] = _text(x)
                    elif h == 2:
                        one["opcode"] = _text(x)
                    elif h == 7:
                        one["op_name"] = next(
                            (_text(y) for k, _, y in _fields(x) if k == 2),
                            "")
                    elif h == 35:
                        key = x
                    elif h in (36, 38):
                        one["operands" if h == 36 else "calls"] += \
                            _varints(x) if wire == 2 else [x]
                out[key] = one
        roots[ident] = root
    for one in out.values():
        one["calls"] = [roots[c] for c in one["calls"] if c in roots]
    return out


def inherited(program: Dict[int, Dict[str, Any]]) -> Dict[str, str]:
    """An ``op_name`` for the instructions that carry NONE, by
    instruction name.  XLA names a fusion after its ROOT, and where the
    root is an op it made for itself (a ``convert`` pushed through a
    ``concatenate``, a ``bitcast``, a multi-output ``tuple``) the fusion
    has no name though the ops inside it have: such a fusion takes the
    name of the first named op met from its root up the data's way
    INSIDE it.  A layout ``copy``, the ``*-start`` / ``*-done`` of a
    prefetch and their kin format data for a neighbour: they take the
    name of the first named instruction they READ (through tuples,
    elements and bitcasts, up to ``_REACH`` steps) and, where all they
    read is nameless or the program's argument, of the first that reads
    THEM.  An op that has a name of its own keeps it, with or without a
    part in it: what the program did not scope stays visibly
    unscoped."""
    read_by: Dict[int, List[int]] = {}
    for key, one in program.items():
        for o in one["operands"]:
            read_by.setdefault(o, []).append(key)

    def look(start: List[int], way, left: int) -> str:
        seen, front = set(start), list(start)
        while front and left >= 0:
            nxt = []
            for key in front:
                one = program.get(key)
                if one is None:
                    continue
                # an argument's name is its place in the step's pytree
                found = (one["op_name"] if one["opcode"] != "parameter"
                         else "") or (
                    look(one["calls"], operands_of, _REACH)
                    if one["opcode"] == "fusion" else "")
                if found:
                    return found
                nxt += [k for k in way(key) if k not in seen]
                seen.update(nxt)
            front, left = nxt, left - 1
        return ""

    def operands_of(key: int) -> List[int]:
        return program[key]["operands"]

    def readers_of(key: int) -> List[int]:
        return read_by.get(key, [])

    out = {}
    for key, one in program.items():
        if one["op_name"] or one["opcode"] in ("parameter", "constant"):
            continue
        found = look([key], operands_of, _REACH) \
            or look(readers_of(key), readers_of, _REACH)
        if found:
            out[one["name"]] = found
    return out


# --------------------------------------------------------------------------
# the split
# --------------------------------------------------------------------------

def self_times(events: Iterable[xplane.Event], window: xplane.Interval
               ) -> List[Tuple[str, float]]:
    """``(name, nanoseconds)`` of every event inside ``window``, each its
    SELF time: children of a ``while``, ``conditional`` or ``call`` lie
    inside their parent on the same line, get their own, and the parent
    keeps what no child covers.  Every nanosecond is counted once."""
    lo, hi = window
    inside = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in events
                     if e > lo and s < hi), key=lambda x: (x[1], -x[2]))
    out: List[List[Any]] = []
    open_: List[Tuple[int, float]] = []  # (index in out, end)
    for name, s, e in inside:
        while open_ and s >= open_[-1][1]:
            open_.pop()
        if open_:
            e = min(e, open_[-1][1])
            out[open_[-1][0]][1] -= e - s
        out.append([name, e - s])
        open_.append((len(out) - 1, e))
    return [(n, ns) for n, ns in out]


def split(events: Iterable[xplane.Event], facts: Dict[str, Dict[str, Any]],
          window: xplane.Interval, parts: Sequence[str],
          neighbours: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Device nanoseconds of the ops inside ``window``, each op's self
    time, by ``(phase, part)`` (``ns``; part ``None``: under no part).
    An op with no name of its own goes by ``neighbours`` (instruction
    name -> ``op_name``, :func:`inherited`; ``inherited_ns``: how much
    of the time went so).
    Collective ops on the line (``*-start``, ``*-done`` and their kin)
    are left to ``collective_ms`` and summed apart (``collective_ns``);
    ``matmul_ns`` and ``matmul_flops``: the ops XLA files as a
    convolution, and the compiler's own count of their operations."""
    ns: Dict[Key, float] = {}
    out = {"ns": ns, "collective_ns": 0.0, "inherited_ns": 0.0,
           "matmul_ns": 0.0, "matmul_flops": 0.0}
    neighbours = neighbours or {}
    keys: Dict[str, Key] = {}  # a name is read once, not once an event
    for name, own in self_times(events, window):
        if xplane.is_collective(name):
            out["collective_ns"] += own
            continue
        fact = facts.get(name, {})
        tf_op = fact.get("tf_op") or ""
        if not tf_op and xplane.op_name(name) in neighbours:
            tf_op = neighbours[xplane.op_name(name)]
            out["inherited_ns"] += own
        key = keys.get(tf_op)
        if key is None:
            key = keys[tf_op] = (phase(tf_op), part(tf_op, parts))
        ns[key] = ns.get(key, 0.0) + own
        if fact.get("hlo_category") in MATMUL_CATEGORIES:
            out["matmul_ns"] += own
            out["matmul_flops"] += float(fact.get("flops") or 0)
    return out


def step_parts(rows: Sequence[program_spans.Row]) -> Optional[List[str]]:
    """The program's list of parts: ``parts`` of the run's first
    ``model:step.scopes`` span; ``None`` where it left none."""
    found = program_spans.select(rows, "model", "step.scopes")
    parts = found[0]["args"].get("parts") if found else None
    return parts.split(",") if parts else None


def of_run(trace, run) -> Optional[Dict[str, Any]]:
    """The split of device 0's ops over the traced window of a run, read
    once from the profiler's file and kept on ``trace``; ``None`` where
    there is no trace, the program left no ``step.scopes`` span, or the
    file names no op."""
    if not trace or not trace.get("path"):
        return None
    if "_scope_split" not in trace:
        trace["_scope_split"] = None
        parts = step_parts(program_spans.timeline())
        facts = op_facts(trace["path"]) if parts else {}
        planes = xplane.device_planes(xplane.load(trace["path"])) \
            if any(f.get("tf_op") for f in facts.values()) else []
        if planes:
            trace["_scope_split"] = split(
                xplane._events(planes[0], "XLA Ops"), facts,
                trace["devices"][0]["window"], parts,
                inherited(step_program(trace["path"],
                                       run["step_module"])))
    return trace["_scope_split"]


# --------------------------------------------------------------------------
# what the layer metrics read
# --------------------------------------------------------------------------

def ms_a_step(trace, ns: float) -> Optional[float]:
    steps = trace["devices"][0]["steps"]
    return ns / steps / 1e6 if steps else None


def phase_ms(trace, run, name: str) -> Optional[float]:
    """Milliseconds a step on device 0 in one phase, over all parts."""
    found = of_run(trace, run)
    if not found:
        return None
    return ms_a_step(trace, sum(
        v for (p, _), v in found["ns"].items() if p == name))


def part_ms(trace, run, *names: str) -> Optional[float]:
    """Milliseconds a step on device 0 under the named parts, over all
    phases."""
    found = of_run(trace, run)
    if not found:
        return None
    return ms_a_step(trace, sum(
        v for (_, p), v in found["ns"].items() if p in names))


def unnamed_share(trace, run) -> Optional[float]:
    """Percent of the split's device time under NO part."""
    found = of_run(trace, run)
    total = sum(found["ns"].values()) if found else 0.0
    if not total:
        return None
    return 100.0 * sum(v for (_, p), v in found["ns"].items()
                       if p is None) / total
