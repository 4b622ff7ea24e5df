"""The part ``attn`` of a traced step, one level down: its device time
by the program's PIECES.

``scopes.py`` splits a step by the outermost part of each op's name and
stops there; the largest part of most steady cells is ``attn``, and it
was one number.  The program names what an attention half is made of
(``models/step.py`` ``ATTN_PIECES``: ``norm``, ``proj``, ``pos``,
``gate``, ``layout``, ``kernel``, opened as ``attn.<piece>``) and says
the list in the same plan span as its parts
(``ray_tpu:model:step.scopes``, ``attn_pieces``); this module takes it
from the run's timeline and from nowhere else.  A program that says none
(the parent of the PR that added it) gives ``None``, and every reader
then ``None``.

An op's piece is the INNERMOST component of its name that is
``attn.<piece>`` with ``<piece>`` on the list: a kind around the
kernels' call (``attn.sliding``) or a plain name (``mla.kv_up``, a flax
module's, ``jit(_flash_forward)``) is none and hides none outside it.
Device 0, each op's SELF time over the traced window
(``scopes.self_times``), keyed ``(phase, piece)`` for the ops whose part
is ``attn``; collectives left out, as ``scopes.split`` leaves them.

Two rules the parts' reader lacks:

* an op with no name, or whose name is the compiler's own stamp (the
  instruction's name, ``convert.73``: what XLA puts on a fusion whose
  root it made), is NOT taken at its word: it goes by the named ops
  inside its fused computation, then by its neighbours, in the step's
  ``HloProto`` (``scopes.inherited`` over a program with such stamps
  taken off).  What that brings under ``attn`` that ``scopes.split``
  left under no part is ``refiled_ns``;
* a fusion is named after ONE op of it, its root.  One that holds named
  ops of more than one piece is filed under its root's piece and its
  time also summed into ``mixed_ns`` (``mixed``: each such fusion's
  time and the pieces inside): a rotation fused into a projection's
  epilogue is free, and which already are is worth seeing.  What this
  cannot tell is how a mixed fusion's time divides among its pieces.

Like ``scopes.py``: arithmetic on plain rows apart from where they come
from, so that a hand-made trace pins it
(``benchmarks/tests/test_pieces.py``).
"""

from __future__ import annotations

import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.reduce import program_spans, scopes, xplane

PART = "attn"
#: the pieces that multiply nothing: passes of their own over the data
POINTWISE = ("norm", "pos", "gate")
#: an ``op_name`` that is an instruction's own name: no ``/``, a stem and
#: a number
_STAMP = re.compile(r"^[a-z_\-]+\.\d+$")

Key = Tuple[str, Optional[str]]  # (phase, piece)
Program = Dict[int, Dict[str, Any]]


# --------------------------------------------------------------------------
# names
# --------------------------------------------------------------------------

def piece(tf_op: str, pieces: Sequence[str]) -> Optional[str]:
    """The INNERMOST component ``attn.<piece>`` with ``<piece>`` one of
    ``pieces``; ``None``: under no piece."""
    for component in reversed(scopes.components(tf_op)):
        if component.startswith(PART + ".") \
                and component[len(PART) + 1:] in pieces:
            return component[len(PART) + 1:]
    return None


def stamped(tf_op: str) -> bool:
    """No name at all, or the compiler's own stamp."""
    return bool(_STAMP.match(tf_op.rsplit(":", 1)[0])) if tf_op else True


def step_pieces(rows: Sequence[program_spans.Row]) -> Optional[List[str]]:
    """The program's list of pieces: ``attn_pieces`` of the run's first
    ``model:step.scopes`` span; ``None`` where it said none."""
    found = program_spans.select(rows, "model", "step.scopes")
    said = found[0]["args"].get("attn_pieces") if found else None
    return said.split(",") if said else None


# --------------------------------------------------------------------------
# the step's program
# --------------------------------------------------------------------------

def looked_inside(program: Program) -> Dict[str, str]:
    """``scopes.inherited`` (instruction name -> ``op_name``) of the
    program with the compiler's stamps taken off: a stamped fusion goes
    by the first named op from its root up the data's way inside it, a
    stamped or nameless op of any other kind by what it reads or feeds,
    and no look stops at a stamp on its way."""
    return scopes.inherited({
        key: dict(one, op_name="") if stamped(one["op_name"]) else one
        for key, one in program.items()})


def fused(program: Program, fusion: Dict[str, Any]) -> List[str]:
    """The op_names inside ``fusion``: of every instruction of the
    computation it calls, from its root up the data's way (a fused
    computation's parameters read nothing), those that carry a name of
    the program's."""
    names, seen, front = [], set(fusion["calls"]), list(fusion["calls"])
    while front:
        inner = program.get(front.pop())
        if inner is None:
            continue
        if inner["opcode"] != "parameter" and not stamped(inner["op_name"]):
            names.append(inner["op_name"])
        new = [k for k in inner["operands"] + inner["calls"]
               if k not in seen]
        seen.update(new)
        front += new
    return names


# --------------------------------------------------------------------------
# the split
# --------------------------------------------------------------------------

def split(events: Iterable[xplane.Event], facts: Dict[str, Dict[str, Any]],
          window: xplane.Interval, parts: Sequence[str],
          pieces: Sequence[str], program: Optional[Program] = None
          ) -> Dict[str, Any]:
    """Device nanoseconds of the ops inside ``window`` whose part is
    ``attn``, each op's self time, by ``(phase, piece)`` (``ns``; piece
    ``None``: under no piece).  ``refiled_ns``: of that, what
    ``scopes.split`` files under another part or none (a stamp taken at
    its word); ``mixed_ns``: the time of fusions that hold more than one
    piece, each filed under its root's; ``mixed``: ``{instruction name:
    [ns, pieces inside]}`` of those."""
    program = program or {}
    theirs, mine = scopes.inherited(program), looked_inside(program)
    fusions = {one["name"]: one for one in program.values()
               if one["opcode"] == "fusion"}
    ns: Dict[Key, float] = {}
    out = {"ns": ns, "refiled_ns": 0.0, "mixed_ns": 0.0, "mixed": {}}
    keys: Dict[str, Tuple[bool, Key]] = {}
    held_in: Dict[str, List[str]] = {}
    for name, own in scopes.self_times(events, window):
        if xplane.is_collective(name):
            continue
        said = (facts.get(name) or {}).get("tf_op") or ""
        op = xplane.op_name(name)
        tf_op = mine.get(op, "") if stamped(said) else said
        if tf_op not in keys:
            keys[tf_op] = (scopes.part(tf_op, parts) == PART,
                           (scopes.phase(tf_op), piece(tf_op, pieces)))
        under, key = keys[tf_op]
        if not under:
            continue
        ns[key] = ns.get(key, 0.0) + own
        if scopes.part(said or theirs.get(op, ""), parts) != PART:
            out["refiled_ns"] += own
        if op not in held_in:  # a fusion is walked once, and if under attn
            held_in[op] = sorted({p for p in (
                piece(n, pieces) for n in fused(program, fusions[op])) if p}
            ) if op in fusions else []
        held = held_in[op]
        if len(held) > 1:
            out["mixed_ns"] += own
            row = out["mixed"].setdefault(op, [0.0, held])
            row[0] += own
    return out


def report(found: Dict[str, Any], steps: int, file=sys.stderr) -> None:
    """What no metric carries, for the builder who reads the run: the
    total under ``attn``, what was re-filed into it, the mixed share and
    the five largest mixed fusions with the pieces inside."""
    total = sum(found["ns"].values())
    if not total or not steps:
        return
    ms = lambda v: v / steps / 1e6  # noqa: E731
    print(f"[pieces] attn_ms={ms(total):.3f} "
          f"refiled_ms={ms(found['refiled_ns']):.3f} "
          f"mixed_ms={ms(found['mixed_ns']):.3f} "
          f"mixed_share={100 * found['mixed_ns'] / total:.2f}%",
          file=file)
    for op, (own, held) in sorted(found["mixed"].items(),
                                  key=lambda kv: -kv[1][0])[:5]:
        print(f"[pieces]   {op} {ms(own):.3f} ms: {'+'.join(held)}",
              file=file)
    file.flush()


def of_run(trace, run) -> Optional[Dict[str, Any]]:
    """The split of device 0's ops under ``attn`` over the traced window
    of a run, read once from the profiler's file and kept on ``trace``
    (six metrics, one pass); ``None`` where there is no trace, the
    program said no ``attn_pieces``, or the file names no op."""
    if not trace or not trace.get("path"):
        return None
    if "_piece_split" not in trace:
        trace["_piece_split"] = None
        rows = program_spans.timeline()
        parts, said = scopes.step_parts(rows), step_pieces(rows)
        facts = scopes.op_facts(trace["path"]) if parts and said else {}
        planes = xplane.device_planes(xplane.load(trace["path"])) \
            if any(f.get("tf_op") for f in facts.values()) else []
        if planes:
            found = trace["_piece_split"] = split(
                xplane._events(planes[0], "XLA Ops"), facts,
                trace["devices"][0]["window"], parts, said,
                scopes.step_program(trace["path"], run["step_module"]))
            report(found, trace["devices"][0]["steps"])
    return trace["_piece_split"]


# --------------------------------------------------------------------------
# what the layer metrics read
# --------------------------------------------------------------------------

def piece_ms(trace, run, *names: Optional[str],
             phase: Optional[str] = None) -> Optional[float]:
    """Milliseconds a step on device 0 under the named pieces of
    ``attn`` (``None``: under no piece), over all phases or in one."""
    found = of_run(trace, run)
    if not found:
        return None
    return scopes.ms_a_step(trace, sum(
        v for (ph, p), v in found["ns"].items()
        if p in names and phase in (None, ph)))


def unpieced_share(trace, run) -> Optional[float]:
    """Percent of the device time under ``attn`` (as this reader files
    it) that lies under NO piece."""
    found = of_run(trace, run)
    total = sum(found["ns"].values()) if found else 0.0
    if not total:
        return None
    return 100.0 * sum(v for (_, p), v in found["ns"].items()
                       if p is None) / total
