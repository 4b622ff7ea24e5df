"""The README describes the instrument that exists: ``BENCHMARK.json``'s
command, cells and end-to-end metrics, by name, and no table of numbers
that a run would have to regenerate."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name):
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


BENCHMARK = json.loads(_read("BENCHMARK.json"))


@pytest.fixture(scope="module")
def benchmarks_section():
    readme = _read("README.md")
    start = readme.index("\n## Benchmarks\n")
    end = readme.find("\n## ", start + 1)
    return readme[start:end if end != -1 else None]


def test_readme_names_every_cell_and_metric(benchmarks_section):
    names = [w["name"] for w in BENCHMARK["workloads"]] \
        + [m["name"] for m in BENCHMARK["end_to_end"]]
    assert names
    missing = [n for n in names if f"`{n}`" not in benchmarks_section]
    assert not missing, f"README's Benchmarks section does not name {missing}"
    assert " ".join(BENCHMARK["command"]) in benchmarks_section
    for flag in ("--workload", "--seed", "--seconds", "--trace"):
        assert flag in benchmarks_section
    assert "PERF.md" in benchmarks_section
    assert "PERF_LEDGER.jsonl" in benchmarks_section


def test_readme_publishes_no_number_of_its_own(benchmarks_section):
    """Numbers live in ``PERF.md`` and the ledger, with their origin: a
    rate, a time or a share printed here would go stale unseen."""
    assert "BENCH_TABLE" not in _read("README.md")
    measured = re.findall(
        r"\d[\d,.]*\s*(?:%|ms\b|us\b|s\b|tokens/s|/s\b|GiB|GB|TFLOP)",
        benchmarks_section)
    assert not measured, measured


DOCUMENTS = ["README.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(ROOT, "docs"))
    if name.endswith(".md"))
_PATH = re.compile(
    r"(?:scripts|tests|ray_tpu|benchmarks|docs|examples)/[\w./-]*")
MAKE_TARGETS = set(re.findall(r"^([\w-]+):", _read("Makefile"), re.M))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_what_exists(document):
    """Every path of the tree, every test and every ``make`` target a
    document names in backticks is there, and it quotes no record of
    the CPU instrument that is gone (``BENCH_r*.json``)."""
    text = re.sub(r"^```.*?^```", "", _read(document), flags=re.M | re.S)
    assert not re.findall(r"BENCH_r\S*", text)
    missing, test_file = [], None
    for quoted in re.findall(r"`([^`]+)`", text):
        quoted = re.sub(r"\s+", " ", quoted.strip())
        target = re.fullmatch(r"make ([\w-]+)", quoted)
        if target and target.group(1) not in MAKE_TARGETS:
            missing.append(quoted)
        path = _PATH.match(quoted)
        if path:
            rest = quoted[path.end():]
            # a pattern (`ray_tpu/ops/*.py`, `tests/test_<x>.py`): the
            # directory it is in
            name = path.group() if rest[:1] not in ("*", "<", "{") \
                else os.path.dirname(path.group())
            if not os.path.exists(os.path.join(ROOT, name)):
                missing.append(quoted)
            elif name.startswith("tests/") and name.endswith(".py"):
                test_file = name
        case = re.search(r"::\s?(test_\w+)", quoted)
        if case and (path or quoted.startswith("::")) and test_file \
                and f"def {case.group(1)}(" not in _read(test_file):
            missing.append(quoted)
    assert not missing, f"{document} names what is not there: {missing}"
