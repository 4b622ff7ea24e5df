"""``benchmarks/reduce/program_spans.py`` on a hand-made timeline and a
hand-made span list: the joins by ``ckpt``, the medians, the compile
phases, the two clocks and the gap intersection."""

import pytest

from benchmarks.reduce import program_spans as ps

WORKER, DRIVER = 4242, 4000


def _ev(cat, name, start, end, pid=WORKER, tid=1, **args):
    """One chrome-trace event as ``ray_tpu.timeline()`` gives it."""
    source = "worker-w" if pid == WORKER else "driver-d"
    return {"name": name, "ph": "X", "cat": cat, "ts": start * 1e6,
            "dur": (end - start) * 1e6, "pid": source, "tid": tid,
            "args": dict(args, os_pid=pid)}


def _save(ckpt, t, queue, reply, fetch, register):
    """The spans of one save that is reported at ``t``."""
    t_next = t + 0.001 + queue
    return [
        _ev("train", "ckpt.from_pytree", t - 5.0, t, tid=9, ckpt=ckpt,
            bytes=3, leaves=1),
        _ev("train", "report", t, t + 0.001, tid=9, ckpt=ckpt),
        _ev("train", "next_results", t_next, t_next + 0.002, tid=2,
            results=2, ckpts=[ckpt]),
        _ev("worker", "reply", t_next + 0.002, t_next + reply, tid=2,
            fn="next_results", bytes=3, path="plasma"),
        _ev("worker", "reply.serialize", t_next + 0.002,
            t_next + reply / 2, tid=2),
        # another thread's reply in between must not be taken
        _ev("worker", "reply", t_next + 0.003, t_next + 0.004, tid=3,
            fn="next_results", bytes=1, path="inline"),
        _ev("train", "poll", t_next - 0.5, t_next + reply + fetch,
            pid=DRIVER, results=2, ckpts=[ckpt]),
        _ev("train", "ckpt.register", t_next + reply + fetch,
            t_next + reply + fetch + register, pid=DRIVER, ckpt=ckpt,
            bytes=3, path="/x"),
    ]


@pytest.fixture
def rows():
    events = [
        {"name": "f", "ph": "X", "cat": "task", "ts": 1.0, "dur": 1.0,
         "pid": "w", "tid": "t", "args": {}},
        _ev("train", "gang.place", 10.0, 10.5, pid=DRIVER, bundles=1, tpu=1),
        _ev("train", "gang.spawn", 10.5, 16.5, pid=DRIVER, workers=1),
        _ev("lease", "spawn", 10.6, 14.0, pid=1, tpu=1),
        _ev("train", "chip_open", 17.0, 24.0, backend="tpu", devices=1),
        _ev("train", "chip_open", 17.0, 19.0, pid=4243, backend="tpu",
            devices=1),
        _ev("xla", "trace", 30.0, 31.0, fun_name="init_like"),
        _ev("xla", "trace", 40.0, 52.0, fun_name="train_step"),
        _ev("xla", "trace", 41.0, 41.5, fun_name="inner_helper"),
        _ev("xla", "lower", 52.0, 75.0, fun_name="jit(train_step)"),
        _ev("xla", "backend_compile", 75.0, 97.0,
            fun_name="jit(train_step)"),
        # the same function in a process that is not the gang's
        _ev("xla", "trace", 40.0, 49.0, pid=DRIVER, fun_name="train_step"),
        # ... and compiled again after the window opened
        _ev("xla", "backend_compile", 150.0, 151.0,
            fun_name="jit(train_step)"),
    ]
    events += _save("aaaa", 90.0, queue=0.1, reply=1.0, fetch=2.0,
                    register=3.0)            # warm-up: before the window
    events += _save("bbbb", 120.0, queue=0.2, reply=10.0, fetch=4.0,
                    register=5.0)
    events += _save("cccc", 150.0, queue=6.0, reply=12.0, fetch=2.0,
                    register=7.0)
    events += _save("dddd", 180.0, queue=0.4, reply=14.0, fetch=3.0,
                    register=9.0)
    events += _save("eeee", 260.0, queue=9.0, reply=9.0, fetch=9.0,
                    register=9.0)            # the traced tail's
    return ps.rows_of(events)


RUN = {"step_module": "jit_train_step",
       "final": {"window": {"t_start": 100.0, "t_end": 200.0,
                            # the loop's stamp and the span's start differ
                            # by the call and by the clock correction
                            "saves": [{"t_report": 119.99},
                                      {"t_report": 150.004},
                                      {"t_report": 179.9}]},
                 "trace": {"t0": 250.0, "saves": 1}}}


def test_rows_drop_task_events_and_are_in_time_order(rows):
    assert all(r["cat"] != "task" for r in rows)
    assert [r["start"] for r in rows] == sorted(r["start"] for r in rows)
    (place,) = ps.select(rows, "train", "gang.place")
    assert place["os_pid"] == DRIVER and place["source"] == "driver-d"
    assert ps.seconds(place) == pytest.approx(0.5)


@pytest.mark.parametrize("cat,name,want", [
    ("train", "gang.place", 0.5), ("train", "gang.spawn", 6.0),
    ("train", "gang.nowhere", None)])
def test_last_seconds(rows, cat, name, want):
    got = ps.last_seconds(rows, cat, name)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("module", ["jit_train_step", "jit(train_step)",
                                    "train_step"])
@pytest.mark.parametrize("phase,want", [
    ("trace", 12.0), ("lower", 23.0), ("backend_compile", 22.0)])
def test_compile_phases_of_the_step_in_the_gang_before_the_window(
        rows, module, phase, want):
    assert ps.gang_pids(rows) == {WORKER, 4243}
    assert ps.compile_phase_s(rows, phase, module, before=100.0) == \
        pytest.approx(want)


def test_compile_phase_of_a_function_nobody_compiled_is_none(rows):
    assert ps.compile_phase_s(rows, "trace", "jit_other", 100.0) is None


def test_a_save_is_joined_by_its_ckpt_id(rows):
    legs = ps.save_legs(rows, "cccc")
    assert legs["queue"] == pytest.approx(6.0)
    assert legs["reply"] == pytest.approx(12.0)  # this thread's reply
    assert legs["fetch"] == pytest.approx(2.0)
    assert legs["register"] == pytest.approx(7.0)
    # the four legs are report -> registered, taken apart
    (reg,) = ps.select(rows, "train", "ckpt.register", ckpt="cccc")
    (rep,) = ps.select(rows, "train", "report", ckpt="cccc")
    assert sum(legs[k] for k in ("queue", "reply", "fetch", "register")) \
        == pytest.approx(reg["end"] - rep["end"])
    assert ps.save_legs(rows, "zzzz") is None


def _without(rows, name, after, before):
    return [r for r in rows if not (
        r["name"] == name and after < r["start"] < before)]


@pytest.mark.parametrize("name", ["report", "next_results", "reply",
                                  "poll", "ckpt.register"])
def test_a_partial_join_gives_no_legs_at_all(rows, name, capsys):
    """``ckpt_to_disk_s`` is over every save of the window; a median over
    the saves that happened to join would be of other saves."""
    lost = _without(rows, name, 149.0, 175.0)  # a row of the second save
    assert ps.save_legs(lost, "bbbb") is not None
    assert ps.save_legs(lost, "cccc") is None
    for leg in ("queue", "reply", "fetch", "register"):
        assert ps.median_leg_ms(lost, RUN, leg) is None
    assert "3 saves in the window" in capsys.readouterr().err


def test_a_fast_inline_reply_is_read_from_task_exec(rows):
    """Under a millisecond an inline reply leaves no ``worker:reply`` row;
    the ``task_exec`` row around the call ends with the reply."""
    (nxt,) = [r for r in ps.select(rows, "train", "next_results")
              if r["args"]["ckpts"] == ["cccc"]]
    inline = _without(rows, "reply", 150.0, 160.0) + ps.rows_of([
        _ev("task_exec", "next_results", nxt["start"] - 0.8,
            nxt["start"] + 0.5),
        # the call before it, which carried nothing
        _ev("task_exec", "next_results", nxt["start"] - 2.0,
            nxt["start"] - 0.9)])
    legs = ps.save_legs(inline, "cccc")
    assert legs["reply"] == pytest.approx(0.5)
    assert legs["fetch"] == pytest.approx(12.0 + 2.0 - 0.5)


@pytest.mark.parametrize("leg,want_ms", [
    ("queue", 400.0), ("reply", 12e3), ("fetch", 3e3),
    ("register", 7e3)])
def test_medians_are_over_the_windows_saves_only(rows, leg, want_ms):
    saves = RUN["final"]["window"]["saves"]
    assert ps.window_saves(rows, saves) == ["bbbb", "cccc", "dddd"]
    assert ps.window_saves(rows, [{"t_report": 135.0}] + saves[:1]) == \
        ["bbbb"]  # a save whose report span is gone is left out
    assert ps.median_leg_ms(rows, RUN, leg) == pytest.approx(want_ms)


def test_no_spans_no_numbers(capsys):
    assert ps.median_leg_ms([], RUN, "queue") is None
    assert capsys.readouterr().err == ""  # the parent commit: silence
    assert ps.last_seconds([], "train", "gang.place") is None
    assert ps.compile_phase_s([], "trace", "jit_train_step", 1.0) is None
    assert ps.profile_spans(None) == [] and ps.profile_spans({}) == []


@pytest.mark.parametrize("error,said", [
    (None, ""), ("RayTpuError", ""), ("ValueError", "no timeline")])
def test_timeline_is_silent_only_where_the_program_leaves_none(
        monkeypatch, capsys, error, said):
    import ray_tpu

    def timeline():
        if error:
            raise {"RayTpuError": ray_tpu.RayTpuError,
                   "ValueError": ValueError}[error]("boom")
        return [_ev("train", "gang.place", 1.0, 2.0, pid=DRIVER)]

    monkeypatch.setattr(ray_tpu, "timeline", timeline)
    monkeypatch.setattr(ps, "_timeline", None)
    assert len(ps.timeline()) == (0 if error else 1)
    err = capsys.readouterr().err
    assert (said in err) if said else (err == "")


# -- the two clocks and the gap intersection --------------------------------

#: the profile's clock starts 249 s of wall clock after the epoch of the
#: hand-made timeline: the ``traced`` span opens at 1e9 ns = wall 250.0
TRACE = {"spans": [("dispatch", 0.5e9, 0.9e9), ("traced", 1.0e9, 30e9)],
         "path": None}


def test_wall_clock_is_moved_onto_the_profiles_clock(rows):
    offset = ps.wall_minus_profile_ns(TRACE, RUN)
    assert offset == pytest.approx(249e9)
    assert ps.wall_minus_profile_ns({"spans": []}, RUN) is None
    assert ps.wall_minus_profile_ns(TRACE, {"final": {"trace": None}}) \
        is None
    (moved,) = ps.on_profile_clock(
        ps.select(rows, "train", "report", ckpt="eeee"), offset)
    assert moved[0] == pytest.approx(11e9) and moved[1] == \
        pytest.approx(11.001e9)


def test_reply_intervals_take_both_sources_and_only_the_gangs(
        rows, monkeypatch):
    in_file = [("worker:reply.store", 2.0e9, 3.0e9),
               ("train:report", 2.5e9, 9.0e9),
               ("worker:reply", 28e9, 29e9)]
    monkeypatch.setattr(ps, "profile_spans", lambda trace: in_file)
    # a reply of a process outside the gang, at the same time
    extra = ps.rows_of([_ev("worker", "reply", 250.5, 259.0, pid=77,
                            fn="f", bytes=1, path="inline")])
    got = ps.reply_intervals(TRACE, RUN, list(rows) + extra)
    inside = [(s, e) for s, e in got if e > 0]
    # timeline: reply of "eeee" is wall [269.003, 278.001] -> profile
    # [20.003e9, 29.001e9]; it swallows the file's [28e9, 29e9]
    assert inside[0] == (2.0e9, 3.0e9)
    assert inside[-1][0] == pytest.approx(20.003e9)
    assert inside[-1][1] == pytest.approx(29.001e9)
    assert not any(s < 5e9 < e for s, e in inside)  # pid 77 is not there
    # without the stamp of the traced span only the file's own remain
    got = ps.reply_intervals({"spans": [], "path": None}, RUN, rows)
    assert got == [(2.0e9, 3.0e9), (28e9, 29e9)]


@pytest.mark.parametrize("gaps,spans,want", [
    ([(0, 10), (20, 30)], [(5, 25)], 10),       # 5 of each gap
    ([(0, 10)], [], 0),
    ([(0, 10)], [(0, 10)], 10),
    ([(0, 10), (5, 15)], [(8, 12), (14, 40)], 5),  # overlapping gaps
    ([], [(0, 10)], 0),
])
def test_idle_under_is_the_intersection(gaps, spans, want):
    assert ps.idle_under(gaps, spans) == pytest.approx(want)
