"""The two readers PR 26 added (``ckpt_d2h_ms``, ``ckpt_encode_ms``) on a
hand-made timeline: a median of an argument of ``train:ckpt.from_pytree``
over ALL the window's saves, or nothing."""

import pytest

from benchmarks import run as bench_run
from benchmarks.reduce import program_spans as ps

#: ckpt -> (reported at, d2h_ms, encode_ms); "aaaa" is the warm-up's save
#: and "eeee" the traced tail's: neither is in the window
SAVES = {"aaaa": (90.0, 9e3, 9e3), "bbbb": (120.0, 2000.0, 800.0),
         "cccc": (150.0, 1000.0, 900.0), "dddd": (180.0, 3000.0, 1000.0),
         "eeee": (260.0, 9e3, 9e3)}
RUN = {"final": {"window": {"t_start": 100.0, "t_end": 200.0,
                            # the loop's stamp and the span's start differ
                            "saves": [{"t_report": 119.99},
                                      {"t_report": 150.004},
                                      {"t_report": 179.9}]}}}


def _ev(name, start, end, **args):
    return {"name": name, "ph": "X", "cat": "train", "ts": start * 1e6,
            "dur": (end - start) * 1e6, "pid": "worker-w", "tid": 9,
            "args": dict(args, os_pid=4242)}


def _rows(saves, with_legs=True):
    events = []
    for ckpt, (t, d2h, encode) in saves.items():
        legs = {"d2h_ms": d2h, "encode_ms": encode, "copies": 1.0} \
            if with_legs else {}
        events += [_ev("ckpt.from_pytree", t - 4.0, t, ckpt=ckpt, bytes=3,
                       leaves=1, **legs),
                   _ev("ckpt.d2h", t - 4.0, t - 3.0, ckpt=ckpt),
                   _ev("ckpt.encode", t - 3.0, t, ckpt=ckpt),
                   _ev("report", t, t + 0.001, ckpt=ckpt)]
    return ps.rows_of(events)


def _read(metric, rows, run=RUN, *, monkeypatch):
    monkeypatch.setattr(ps, "_timeline", rows)
    reader = bench_run.load_reader(
        bench_run.load_cell("gpt2-large.ckpt")["bench_dir"], metric)
    return reader(None, [], run)


@pytest.mark.parametrize("metric,want", [
    ("ckpt_d2h_ms", 2000.0), ("ckpt_encode_ms", 900.0)])
def test_legs_are_medians_over_all_the_windows_saves(
        monkeypatch, metric, want):
    assert _read(metric, _rows(SAVES), monkeypatch=monkeypatch) == \
        pytest.approx(want)
    # a save of the window whose row did not arrive: nothing, not a
    # median over the other two
    lost = {k: v for k, v in SAVES.items() if k != "cccc"}
    assert _read(metric, _rows(lost), monkeypatch=monkeypatch) is None
    # ... or whose row lacks the argument
    rows = _rows(SAVES)
    for r in rows:
        if r["name"] == "ckpt.from_pytree" and r["args"]["ckpt"] == "dddd":
            del r["args"]["d2h_ms"], r["args"]["encode_ms"]
    assert _read(metric, rows, monkeypatch=monkeypatch) is None


@pytest.mark.parametrize("metric", ["ckpt_d2h_ms", "ckpt_encode_ms"])
def test_legs_are_none_where_the_program_names_none(
        monkeypatch, capsys, metric):
    """The parent commit: the span is there, its arguments are not; a
    program with no timeline; a window with no save."""
    read = lambda rows, run=RUN: _read(  # noqa: E731
        metric, rows, run, monkeypatch=monkeypatch)
    assert read(_rows(SAVES, with_legs=False)) is None
    assert read([]) is None
    assert read(_rows(SAVES), {"final": {"window": {"saves": []}}}) is None
    assert capsys.readouterr().err == ""


def test_the_two_legs_are_declared_for_the_ckpt_cell_only():
    cell = bench_run.load_cell("gpt2-large.ckpt")
    steady = bench_run.load_cell("gpt2-large.steady")
    for name in ("ckpt_d2h_ms", "ckpt_encode_ms"):
        (m,) = [m for m in cell["per_layer"] if m["name"] == name]
        assert m["moves"] == "save_stall_ms" and m["source"] == \
            "program_span" and m["layer"] == "checkpoint and object plane"
        assert name not in {m["name"] for m in steady["per_layer"]}
