"""The whole control flow of every cell at a tiny size on the CPU, with a
chatty worker: the last line stays last and has the contract's keys;
without a chip the real command prints no result; a later PR's cell,
configuration, traffic file, kind and per-layer reader are found as new
files with no edit to an existing one."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
#: a number that only a chip can give: never in a CPU run's line
DEVICE_METRICS = {"flash_ms", "flash_roofline", "device_idle_share",
                  "device_idle_share.ckpt", "model_flops_util",
                  "collective_ms", "collective_exposed_ms"}
TINY = {"vocab_size": 256, "max_seq_len": 128, "num_layers": 2}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _rehearse(workload, trace, root=REPO, seconds=2.0, extra=()):
    bench = _bench() if root == REPO else json.load(
        open(os.path.join(root, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    heads = 5 if cell["chips"] == 4 else 2   # 25 heads are odd: keep that
    rehearse = {"config_args": dict(TINY, num_heads=heads,
                                    embed_dim=32 * heads),
                "batch": 8, "chatter": 200}
    code = ("from benchmarks import run; run.main(%r, rehearse=%r)" % (
        ["--workload", workload, "--seed", str(2 ** 31 + 7), "--seconds",
         str(seconds), "--trace", str(trace), *extra], rehearse))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
               % cell["chips"])
    env.pop("RAY_TPU_CHIPS", None)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=300)


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.endswith("}\n")
    assert "worker chatter" not in proc.stdout
    line = json.loads(proc.stdout.splitlines()[-1])
    assert set(line) == LINE_KEYS  # no breakdown: the CPU has no trace
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
    return line


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      _bench()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_and_its_last_line_stays_last(workload, trace):
    bench = _bench()
    line = _last_line(_rehearse(workload, trace))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    assert line["device"]["count"] == cell["chips"]
    here = lambda m: workload in m.get("workloads", [workload])  # noqa
    if trace:
        allowed = {m["name"] for m in bench["per_layer"] if here(m)}
        assert set(line["metrics"]) <= allowed - DEVICE_METRICS
        host = {m["name"] for m in bench["per_layer"] if here(m)
                and m["source"] != "device_trace"} - DEVICE_METRICS
        assert host <= set(line["metrics"])  # every host-side reader read
    else:
        assert set(line["metrics"]) == {
            m["name"] for m in bench["end_to_end"] if here(m)}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def _real_command(root, workload="gpt2-large.steady"):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_CHIPS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [*_bench()["command"], "--workload", workload, "--seed", "1",
         "--seconds", "1", "--trace", "0"], env=env, cwd=root,
        capture_output=True, text=True, timeout=300)


def test_real_command_without_a_chip_exits_nonzero_and_prints_no_result():
    proc = _real_command(REPO)
    assert proc.returncode != 0
    assert "no accelerator, no result" in proc.stderr
    assert '"metrics"' not in proc.stdout and "tokens" not in proc.stdout


def _copy_benchmark(tmp_path, link_program):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(REPO, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    if link_program:  # the program itself, untouched
        for name in ("ray_tpu", "src", "build"):
            os.symlink(os.path.join(REPO, name), root / name)
    return root


def test_real_command_with_only_the_benchmarks_files_prints_no_result(
        tmp_path):
    root = _copy_benchmark(tmp_path, link_program=False)
    proc = _real_command(str(root))
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


def test_a_later_pr_adds_a_cell_as_files_and_entries_only(tmp_path):
    """New configuration, traffic mix, kind, per-layer reader and cell
    in a copy of the benchmark: every file that was there is
    byte-identical afterwards, and the new cell runs."""
    root = _copy_benchmark(tmp_path, link_program=True)
    bdir = root / "benchmarks"
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}

    conf = json.loads((bdir / "configs" / "gpt2-large.json").read_text())
    conf.update(name="gpt2-medium", n_embd=1024, n_layer=24, n_head=16)
    conf["entry"]["config"] = "ray_tpu.models.gpt2:GPT2Config.gpt2_medium"
    (bdir / "configs" / "gpt2-medium.json").write_text(json.dumps(conf))
    traffic = json.loads((bdir / "traffic" / "ckpt.json").read_text())
    traffic.update(kind="train_again", checkpoint_every=4, report_every=3)
    (bdir / "traffic" / "ckpt-every4.json").write_text(json.dumps(traffic))
    (bdir / "kinds" / "train_again.py").write_text(
        "from benchmarks.kinds.train import run  # noqa: F401\n")
    (bdir / "layer_metrics" / "last_loss.py").write_text(
        "def read(trace, spans, run):\n"
        "    return run['final']['window']['losses'][-1]\n")
    (bdir / "layer_metrics" / "never_there.py").write_text(
        "def read(trace, spans, run):\n    return None\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = "gpt2-medium.ckpt-every4"
    bench["configs"].append({
        "name": "gpt2-medium", "source": conf["source"],
        "file": "benchmarks/configs/gpt2-medium.json", "reduced": [],
        "why": "a later PR's configuration"})
    bench["workloads"].append({
        "name": name, "config": "gpt2-medium", "traffic": "ckpt-every4",
        "chips": 1, "why": "a later PR's cell"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "save_stall_ms":
            metric["workloads"].append(name)
    for extra in ("last_loss", "never_there"):
        bench["per_layer"].append({
            "name": extra, "unit": "nats", "better": "lower",
            "source": "program_counter", "layer": "train step",
            "moves": "save_stall_ms", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = _last_line(_rehearse(name, 0, root=str(root)))
    assert set(line["metrics"]) == {"save_stall_ms", "setup_s"}
    line = _last_line(_rehearse(name, 1, root=str(root)))
    assert line["metrics"]["last_loss"]["unit"] == "nats"
    assert "never_there" not in line["metrics"]  # nothing read: left out
    assert "ckpt_serialize_ms" not in line["metrics"]  # not listed here
    assert {p: p.read_bytes() for p in before} == before
    assert not (bdir.parent / ".bench_scratch").exists() or not os.listdir(
        bdir.parent / ".bench_scratch")
