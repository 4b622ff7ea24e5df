"""From a profiler trace (``*.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax and
needs no backend.  A trace has planes; ``/device:TPU:<n>`` planes are the
chips, each with a line ``XLA Modules`` (one event per executed
program), ``XLA Ops`` (one event per HLO op on the TensorCore, children
of a ``while`` nested inside their parent) and ``Async XLA Ops`` (DMA
and collectives in flight, from ``*-start`` to ``*-done``); ``/host:CPU``
planes carry one line per host thread, where the benchmark's
``TraceAnnotation("bench:<name>")`` spans land.  All times are
nanoseconds on one clock.

Everything below is interval arithmetic on ``(start, end)`` pairs, kept
apart from the file format so that a three-event synthetic trace pins
it (benchmarks/tests/test_reduce.py).
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start, end

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench:"
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast|async-collective")
#: ops that only contain other ops: their own interval says nothing
#: about what ran inside it
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    b = list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` given merged busy intervals."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


def attribute(gap_list: Sequence[Interval], spans: Sequence[Event],
              other: str = "other") -> List[Tuple[str, float, float]]:
    """Name each gap by the host span that overlaps it longest."""
    out = []
    for s, e in gap_list:
        best, best_len = other, 0.0
        for name, ss, se in spans:
            ov = min(e, se) - max(s, ss)
            if ov > best_len:
                best, best_len = name, ov
        out.append((best, s, e))
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_stem(text: str) -> str:
    """Op name without its numeric suffixes: ``fusion.12`` -> ``fusion``."""
    return re.sub(r"[.\d]+$", "", op_name(text)) or op_name(text)


def is_collective(text: str) -> bool:
    return bool(_COLLECTIVE.search(op_name(text)))


def is_container(text: str) -> bool:
    return bool(_CONTAINER.match(op_name(text)))


def is_kernel_call(text: str) -> bool:
    """A Mosaic (Pallas) kernel as XLA's TPU backend names it."""
    return "tpu_custom_call" in text


def exposed(collective: Iterable[Interval], compute: Iterable[Interval]
            ) -> float:
    """Time inside a collective during which no compute op runs."""
    return measure(subtract(union(collective), union(compute)))


# --------------------------------------------------------------------------
# the file
# --------------------------------------------------------------------------

def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> Optional[str]:
    """The ``*.xplane.pb`` a ``start_trace(trace_dir)`` left behind."""
    import glob
    import os

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(plane, line_name: str) -> List[Event]:
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def device_planes(profile) -> List:
    """The chips' planes, in device order."""
    found = [(int(m.group(1)), p) for p in profile.planes
             if (m := DEVICE_PLANE.match(p.name))]
    return [p for _, p in sorted(found, key=lambda x: x[0])]


def host_spans(profile, prefix: str = SPAN_PREFIX) -> List[Event]:
    """The benchmark's own annotations, prefix stripped, in time order."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name[len(prefix):], e.start_ns,
                                e.start_ns + e.duration_ns))
    return sorted(out, key=lambda x: x[1])


def step_window(plane, module: Optional[str] = None
                ) -> Optional[Interval]:
    """First start .. last end of the executions of the named program
    (prefix match on ``XLA Modules``; every program when ``None``)."""
    modules = [m for m in _events(plane, "XLA Modules")
               if module is None or m[0].startswith(module)]
    if not modules:
        return None
    return min(m[1] for m in modules), max(m[2] for m in modules)


def reduce_device(plane, window: Optional[Interval] = None,
                  module: Optional[str] = None) -> Dict[str, object]:
    """One chip's plane, reduced over ``window`` (default: its
    ``step_window``).  Executions of ``module`` inside it are the steps."""
    window = window or step_window(plane, module)
    if window is None:
        return {}
    modules = [m for m in _events(plane, "XLA Modules")
               if module is None or m[0].startswith(module)]
    ops = _events(plane, "XLA Ops")
    async_ops = _events(plane, "Async XLA Ops")
    lo, hi = window
    steps = [m for m in modules if m[1] >= lo and m[2] <= hi]
    inside = [o for o in ops if o[2] > lo and o[1] < hi]
    busy = clip(union((s, e) for _, s, e in inside), lo, hi)
    compute = [(s, e) for n, s, e in inside
               if not is_collective(n) and not is_container(n)]
    coll = [(s, e) for n, s, e in inside + async_ops
            if is_collective(n) and e > lo and s < hi]
    kernels = [(s, e) for n, s, e in inside if is_kernel_call(n)]
    by_stem: Dict[str, float] = {}
    for n, s, e in _top_level(inside):
        key = "tpu_custom_call" if is_kernel_call(n) else op_stem(n)
        by_stem[key] = by_stem.get(key, 0.0) + (min(e, hi) - max(s, lo))
    return {
        "window_ns": hi - lo, "window": (lo, hi),
        "steps": len(steps),
        "step_ns": [e - s for _, s, e in steps],
        "busy_ns": measure(busy),
        "idle_gaps": gaps(busy, lo, hi),
        "collective_ns": measure(clip(union(coll), lo, hi)),
        "collective_exposed_ns": measure(clip(
            subtract(union(coll), union(compute)), lo, hi)),
        "kernel_ns": measure(clip(kernels, lo, hi)),
        "kernel_calls": len(kernels),
        "op_ns": by_stem,
    }


def _top_level(events: Sequence[Event]) -> List[Event]:
    """Events not nested inside an earlier, longer one (children of a
    ``while`` sit inside their parent on the same line)."""
    out: List[Event] = []
    end = float("-inf")
    for ev in sorted(events, key=lambda x: (x[1], -x[2])):
        if ev[1] >= end:
            out.append(ev)
            end = ev[2]
    return out
