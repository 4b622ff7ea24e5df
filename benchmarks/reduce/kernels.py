"""The Mosaic (Pallas) kernel calls of a traced step, told apart.

``xplane.reduce_device`` lumps every ``tpu_custom_call`` of a step into
one number, which is right where a step has one family of kernels
(GPT-2: flash attention).  A step with several (AFMoE: flash attention,
grouped matrix products, fused RMS norms) needs them split.  Device ops
carry no scope names (PERF.md section 7), but an event's text is the HLO
instruction, and that carries the RESULT SHAPES, which tell the families
apart given the run's sizes (``batch`` is what one kernel call sees: one
sequence where the program runs a layer over a sequence at a time):

* ``flash``  a 3-d ``[batch, seq, heads * head_dim]`` result (forward:
  with the ``f32`` row statistics beside it; dK/dV: two of the K/V
  width; dQ: one);
* ``gmm``    a 2-d ``[rows, n]`` result whose ``rows`` is the routed row
  buffer, not a batch's tokens (forward and d lhs), or a 3-d ``[experts
  held, k, n]`` result (d rhs);
* ``norm``   a 2-d ``[tokens, hidden]`` result, ``tokens`` those of one
  call or of the whole batch (``fused_rmsnorm``).

The instruction's NAME says the same on today's program (``attn.sliding``
and ``attn.full`` from its named scopes, ``grouped_matmul*`` from the
kernel, ``*_norm`` from the module): the shapes decide, because a scope's
name is the program's to change; the recorded events in the tests carry
both.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks.reduce import xplane

_ARRAY = re.compile(r"\b(?:bf16|f16|f32|s32|u32|s8|u8|pred)\[([\d,]*)\]")


def result_shapes(text: str) -> List[Tuple[int, ...]]:
    """Shapes of the arrays an instruction's text gives as its result:
    what stands between ``=`` and the op (``custom-call``)."""
    head = text.split(" = ", 1)[-1]
    head = re.split(r"\s(?:custom-call|fusion|call)\(", head, 1)[0]
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _ARRAY.finditer(head)]


def classify(text: str, sizes: Dict[str, int]) -> Optional[str]:
    """``flash`` | ``gmm`` | ``norm`` | ``None`` (not a kernel call, or
    one that none of the rules knows).  ``sizes``: ``batch`` (sequences
    one kernel call sees), ``full_batch``, ``seq``, ``held`` (experts
    held; 0 where there are none)."""
    if not xplane.is_kernel_call(text):
        return None
    seq, held = sizes["seq"], sizes.get("held", 0)
    batches = {sizes["batch"], sizes.get("full_batch", sizes["batch"])}
    for shape in result_shapes(text):
        if len(shape) == 3 and shape[0] in batches and shape[1] == seq:
            return "flash"
        if len(shape) == 3 and held and shape[0] == held:
            return "gmm"
        if len(shape) == 2:
            return "norm" if shape[0] in {b * seq for b in batches} \
                else "gmm"
    return None


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
    if "_kernel_split" not in trace:
        final = run["final"]
        conf = run["config"]
        full = final["batch"] // run["chips"]
        # a layer's kernels see one sequence a call
        sizes = {"batch": 1, "full_batch": full,
                 "seq": final["seq"],
                 "held": (conf.get("as_run") or {}).get(
                     "experts_held", (0, 0))[1]}
        planes = xplane.device_planes(xplane.load(trace["path"]))
        dev = trace["devices"][0]
        trace["_kernel_split"] = split(
            xplane._events(planes[0], "XLA Ops"), dev["window"], sizes) \
            if planes else {}
    return trace["_kernel_split"]
