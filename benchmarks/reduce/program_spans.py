"""The program's own spans (``ray_tpu.core.telemetry.span``), from the
two places the program leaves them.

* The **timeline**: every span of every process of the run, on the wall
  clock (seconds), as ``ray_tpu.timeline()`` returns it after
  ``ray_tpu.shutdown()`` wrote the session's file.  Set-up spans end
  minutes before a trace starts and the driver's spans are in no
  worker's profiler session, so most readers use this.
* The **profiler's file**: the spans the gang worker opened while its
  profiler session ran, as ``ray_tpu:<cat>:<name>`` events on the host
  plane, on the clock of the device planes (nanoseconds).  A span that
  was opened before the session started is not in it.

A program that has no such spans (the parent of the PR that added them)
gives an empty timeline and no ``ray_tpu:`` events: every function here
then returns nothing, and the readers ``None``.

Like ``xplane.py`` this is interval arithmetic on plain rows, kept apart
from where the rows come from, so that a hand-made timeline pins it
(benchmarks/tests/test_program_spans.py).
"""

from __future__ import annotations

import statistics
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence

from benchmarks.reduce import xplane

PREFIX = "ray_tpu:"
Row = Dict[str, Any]  # cat, name, start, end (s), source, os_pid, tid, args

_timeline: Optional[List[Row]] = None
_profile_spans: Dict[str, List[xplane.Event]] = {}


# --------------------------------------------------------------------------
# where the rows come from
# --------------------------------------------------------------------------

def rows_of(trace_events: Iterable[Dict[str, Any]]) -> List[Row]:
    """Chrome-trace events of ``ray_tpu.timeline()`` -> span rows in
    seconds, in time order (task events are left out)."""
    out = []
    for e in trace_events:
        if e.get("cat") == "task":
            continue
        args = e.get("args") or {}
        start = e["ts"] / 1e6
        out.append({"cat": e["cat"], "name": e["name"], "start": start,
                    "end": start + e["dur"] / 1e6, "source": e.get("pid"),
                    "os_pid": args.get("os_pid"), "tid": e.get("tid"),
                    "args": args})
    return sorted(out, key=lambda r: r["start"])


def timeline() -> List[Row]:
    """The ended run's timeline, loaded once.  The benchmark's readers
    run in the parent after ``ray_tpu.shutdown()``."""
    global _timeline
    if _timeline is None:
        import ray_tpu

        try:
            _timeline = rows_of(ray_tpu.timeline())
        except ray_tpu.RayTpuError:
            # a program that leaves no file: its timeline() wants a cluster
            _timeline = []
        except Exception as e:  # noqa: BLE001 — the run's line survives
            print(f"program_spans: no timeline: {e!r}", file=sys.stderr)
            _timeline = []
    return _timeline


def profile_spans(trace: Optional[Dict[str, Any]]) -> List[xplane.Event]:
    """``(cat:name, start_ns, end_ns)`` of the ``ray_tpu:`` events in
    the profiler's file of this run, loaded once."""
    path = (trace or {}).get("path")
    if not path:
        return []
    if path not in _profile_spans:
        _profile_spans[path] = xplane.host_spans(xplane.load(path),
                                                 prefix=PREFIX)
    return _profile_spans[path]


# --------------------------------------------------------------------------
# selection
# --------------------------------------------------------------------------

def select(rows: Sequence[Row], cat: str, name: str, **args: Any
           ) -> List[Row]:
    return [r for r in rows if r["cat"] == cat and r["name"] == name
            and all(r["args"].get(k) == v for k, v in args.items())]


def seconds(row: Row) -> float:
    return row["end"] - row["start"]


def last_seconds(rows: Sequence[Row], cat: str, name: str
                 ) -> Optional[float]:
    """Duration of the newest such span (a run has one gang attempt;
    after a restart the newest is the one that ran the window)."""
    found = select(rows, cat, name)
    return seconds(found[-1]) if found else None


def gang_pids(rows: Sequence[Row]) -> set:
    """Process ids of the gang's workers: whoever opened the chips."""
    return {r["os_pid"] for r in select(rows, "train", "chip_open")}


def step_name(module: str) -> str:
    """``jit_train_step`` / ``jit(train_step)`` / ``train_step``: jax
    names the function in its trace event and the module in the others."""
    if module.startswith("jit(") and module.endswith(")"):
        return module[4:-1]
    return module[4:] if module.startswith("jit_") else module


def compile_phase_s(rows: Sequence[Row], phase: str, module: str,
                    before: float) -> Optional[float]:
    """Seconds of the ``xla:<phase>`` spans of the step's function in
    the gang's workers that ended before ``before`` (the window's
    start): the longest worker's sum."""
    want = step_name(module)
    pids = gang_pids(rows)
    per_pid: Dict[Any, float] = {}
    for r in select(rows, "xla", phase):
        if r["os_pid"] in pids and r["end"] <= before \
                and step_name(str(r["args"].get("fun_name"))) == want:
            per_pid[r["os_pid"]] = per_pid.get(r["os_pid"], 0.0) + seconds(r)
    return max(per_pid.values()) if per_pid else None


# --------------------------------------------------------------------------
# a save's journey
# --------------------------------------------------------------------------

def save_legs(rows: Sequence[Row], ckpt: str) -> Optional[Dict[str, float]]:
    """One save, taken apart at the program's own boundaries (seconds):

    ``queue``     end of ``train:report`` -> start of the
                  ``train:next_results`` that carries it
    ``reply``     start of that ``next_results`` -> end of its
                  ``worker:reply`` (the next one on that thread); an
                  inline reply built in under a millisecond leaves no
                  such row, and the ``task_exec`` row around the call,
                  which ends when the reply is built, stands in
    ``fetch``     end of that reply -> end of the driver's ``train:poll``
                  that delivered it (pull, deserialisation)
    ``register``  ``train:ckpt.register``

    ``None`` unless every boundary is there."""
    report = select(rows, "train", "report", ckpt=ckpt)
    carried = [r for r in select(rows, "train", "next_results")
               if ckpt in (r["args"].get("ckpts") or ())]
    polled = [r for r in select(rows, "train", "poll")
              if ckpt in (r["args"].get("ckpts") or ())]
    register = select(rows, "train", "ckpt.register", ckpt=ckpt)
    if not (report and carried and polled and register):
        return None
    nxt, poll = carried[0], polled[0]
    # the driver has the rows only after the reply was built: a reply
    # that starts or ends after that poll is another call's
    replies = [r for r in select(rows, "worker", "reply")
               if r["os_pid"] == nxt["os_pid"] and r["tid"] == nxt["tid"]
               and nxt["end"] - 1e-6 <= r["start"] < poll["end"]] or [
        r for r in select(rows, "task_exec", "next_results")
        if r["os_pid"] == nxt["os_pid"]
        and r["start"] <= nxt["start"] and r["end"] >= nxt["end"]]
    if not replies or replies[0]["end"] > poll["end"] + 0.05:
        return None
    reply = replies[0]
    return {"queue": nxt["start"] - report[0]["end"],
            "reply": reply["end"] - nxt["start"],
            "fetch": poll["end"] - reply["end"],
            "register": seconds(register[0]),
            "t_report": report[0]["start"]}


def window_saves(rows: Sequence[Row], saves: Sequence[Dict[str, float]]
                 ) -> List[str]:
    """Ids of the window's checkpoints, in order: for each save of the
    loop's own record (``t_report``: its stamp just before
    ``session.report``) the ``train:report`` span that starts within half
    a second of it.  Saves are seconds apart; the timeline's rows are
    moved by each process's measured offset to the GCS clock, a few
    milliseconds on one host, so the window's own edges are too sharp."""
    reports = select(rows, "train", "report")
    out = []
    for save in saves:
        near = [r for r in reports if r["args"].get("ckpt")
                and abs(r["start"] - save["t_report"]) < 0.5]
        if near:
            out.append(near[0]["args"]["ckpt"])
    return out


def median_leg_ms(rows: Sequence[Row], run: Dict[str, Any], leg: str
                  ) -> Optional[float]:
    """Median over the window's saves, in milliseconds: over ALL of
    them, the saves ``ckpt_to_disk_s`` is taken over, or nothing.  A
    save whose rows did not all arrive (a lost flush) would move the
    four legs apart from the total they take apart."""
    saves = run["final"]["window"]["saves"]
    ids = window_saves(rows, saves)
    legs = [save_legs(rows, c) for c in ids]
    if not saves or len(ids) < len(saves) or not all(legs):
        if saves and select(rows, "train", "report"):
            print(f"program_spans: {len(saves)} saves in the window, "
                  f"{len(ids)} found in the timeline, "
                  f"{sum(1 for one in legs if one)} joined: no legs",
                  file=sys.stderr)
        return None
    return 1e3 * statistics.median(one[leg] for one in legs)


# --------------------------------------------------------------------------
# the two clocks
# --------------------------------------------------------------------------

def wall_minus_profile_ns(trace: Dict[str, Any], run: Dict[str, Any]
                          ) -> Optional[float]:
    """The profiler's file counts nanoseconds from its own origin; the
    benchmark's ``traced`` span is stamped on both clocks (the loop reads
    ``time.time()`` and opens the annotation in the next statement), so
    ``wall_ns = profile_ns + this``."""
    traced = [s for s in trace.get("spans", ()) if s[0] == "traced"]
    t0 = (run["final"].get("trace") or {}).get("t0")
    if not traced or t0 is None:
        return None
    return t0 * 1e9 - traced[-1][1]


def on_profile_clock(rows: Iterable[Row], offset_ns: float
                     ) -> List[xplane.Interval]:
    return [(r["start"] * 1e9 - offset_ns, r["end"] * 1e9 - offset_ns)
            for r in rows]


def reply_intervals(trace: Dict[str, Any], run: Dict[str, Any],
                    rows: Sequence[Row]) -> List[xplane.Interval]:
    """When the gang worker was building or storing a reply, on the
    profile's clock, merged: the ``ray_tpu:worker:reply*`` events of the
    profiler's file, and the timeline's ``worker:reply*`` rows of the
    gang's workers moved onto that clock (a reply that was opened before
    the profiler session started is only there)."""
    found = [(s, e) for name, s, e in profile_spans(trace)
             if name.startswith("worker:reply")]
    offset = wall_minus_profile_ns(trace, run)
    if offset is not None:
        pids = gang_pids(rows)
        found += on_profile_clock(
            (r for r in rows if r["cat"] == "worker"
             and r["name"].startswith("reply") and r["os_pid"] in pids),
            offset)
    return xplane.union(found)


def idle_under(gaps: Sequence[xplane.Interval],
               spans: Sequence[xplane.Interval]) -> float:
    """Nanoseconds of the idle gaps that the merged spans cover."""
    gaps = xplane.union(gaps)
    return xplane.measure(gaps) - xplane.measure(
        xplane.subtract(gaps, spans))
