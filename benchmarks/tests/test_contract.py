"""``BENCHMARK.json`` against the rules of its contract that a file can
break without any run: names, units, lengths, which metric exists in
which cell, and that every name has its file."""

import json
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_units_and_lengths():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert len(b["command"]) <= 32 and all(_line(w) for w in b["command"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert len(c["reduced"]) <= 16
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert len({w["name"] for w in b["workloads"]}) == len(b["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_every_cell_reports_what_it_must_and_moves_point_somewhere():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    here = lambda m, c: c in m.get("workloads", cells)  # noqa: E731
    for m in b["end_to_end"] + b["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
    for c in cells:
        e2e = {m["name"] for m in b["end_to_end"] if here(m, c)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(here(m, c) for m in b["per_layer"])
        for m in b["per_layer"]:
            if here(m, c):
                assert m["moves"] in e2e, (m["name"], c)
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


def test_every_name_has_its_file_and_configs_keep_published_widths():
    b = _bench()
    root = os.path.join(REPO, b["paths"][0])
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(
            root, "layer_metrics", m["name"] + ".py")), m["name"]
    for w in b["workloads"]:
        with open(os.path.join(root, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(root, "kinds", kind + ".py"))
    published = {"gpt2-large": (1280, 36, 20), "gpt2-xl": (1600, 48, 25)}
    for c in b["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"] == []
        assert (conf["n_embd"], conf["n_layer"], conf["n_head"]) == \
            published[c["name"]]
        assert (conf["n_positions"], conf["vocab_size"]) == (1024, 50257)
    files = [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
             if "__pycache__" not in d]
    for path in files:
        rel = os.path.relpath(path, REPO)
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
