"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once, in a new process, and prints
as the LAST line of standard output one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run).  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Everything that belongs to one cell is found BY NAME, so a later PR
adds files and entries and edits nothing here:

* ``configs/<config>.json``          the sizes as run, and the program's
                                     entry points as dotted paths;
* ``traffic/<traffic>.json``         the job's shape, and its ``kind``;
* ``kinds/<kind>.py``                ``run(cell, args, t0, rehearse)``:
                                     the driver side and the worker loop
                                     of one kind of cell;
* ``layer_metrics/<metric>.py``      ``read(trace, spans, run)``: one
                                     per-layer metric, or ``None`` when
                                     there is nothing to read.

This process never initialises a jax backend: the chip belongs to the
worker that leases it.  Without the chips the cell asks for it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The workload entry with its configuration, its traffic file and
    the metrics that exist in it."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(by_name)}")
    workload = by_name[name]
    entry = next(c for c in bench["configs"]
                 if c["name"] == workload["config"])
    here = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {
        "workload": workload,
        "config": _json(os.path.join(root, entry["file"])),
        "traffic": _json(os.path.join(
            root, bench["paths"][0], "traffic",
            workload["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
        "bench_dir": os.path.join(root, bench["paths"][0]),
    }


def load_reader(bench_dir: str, metric: str):
    path = os.path.join(bench_dir, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def reduce_trace(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The worker's profiler trace, reduced in this process (reading the
    file needs no backend).  ``devices`` holds one reduction per chip,
    over the window the benchmark's ``traced`` span marks."""
    from benchmarks.reduce import xplane

    info = run["final"].get("trace")
    path = xplane.find_xplane(info["dir"]) if info else None
    if not path:
        return None
    profile = xplane.load(path)
    spans = xplane.host_spans(profile)
    traced = [s for s in spans if s[0] == "traced"]
    planes = xplane.device_planes(profile)[:run["chips"]]
    devices = []
    for plane in planes:
        steps = xplane.step_window(plane, run["step_module"])
        if steps:  # first step start .. end of the traced span
            hi = max(steps[1], traced[-1][2] if traced else 0.0)
            devices.append(xplane.reduce_device(
                plane, window=(steps[0], hi), module=run["step_module"]))
    if not devices:
        return None
    named = xplane.attribute(
        devices[0]["idle_gaps"], [s for s in spans if s[0] != "traced"])
    idle = [1.0 - d["busy_ns"] / d["window_ns"] for d in devices]
    return {"devices": devices, "spans": spans, "gaps": named,
            "idle_share": sum(idle) / len(idle), "path": path}


def breakdown(trace: Dict[str, Any]) -> Dict[str, List]:
    """Device 0: seconds per step of the ten op families with most time
    (XLA's own names, numeric suffix dropped), and idle seconds per step
    by what the host was doing."""
    dev = trace["devices"][0]
    steps = max(1, dev["steps"])
    ops = sorted(dev["op_ns"].items(), key=lambda kv: -kv[1])[:10]
    idle: Dict[str, float] = {}
    for name, s, e in trace["gaps"]:
        idle[name] = idle.get(name, 0.0) + (e - s)
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, v / 1e9 / steps] for n, v in ops],
            "idle_gaps": [[n, v / 1e9 / steps] for n, v in gaps]}


def main(argv=None, *, rehearse: Optional[Dict[str, Any]] = None,
         root: str = ROOT) -> None:
    """``rehearse`` (tests only, not on the command line): the whole
    control flow of the cell's kind at a tiny size on the CPU, to prove
    the output discipline — never a device result."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep", default=None, metavar="DIR",
                        help="also leave the run's record (and trace) in "
                             "DIR; the driver never passes it")
    args = parser.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(_json(
            os.path.join(root, "BENCHMARK.json"))["run_seconds"])

    cell = load_cell(args.workload, root)
    kind = importlib.import_module(
        "benchmarks.kinds." + cell["traffic"]["kind"])
    run = kind.run(cell, args, T_PROCESS_START, rehearse=rehearse)

    device = dict(run["device"],
                  memory_peak_bytes=run["memory_peak_bytes"])
    line: Dict[str, Any] = {
        "correct": bool(run["correct"]), "attempted": run["attempted"],
        "failed": run["failed"], "metrics": {}, "device": device}
    if args.trace:
        trace = reduce_trace(run)
        for metric in cell["per_layer"]:
            value = load_reader(cell["bench_dir"], metric["name"])(
                trace, run["final"]["window"]["spans"], run)
            if value is not None:
                line["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        if trace:
            n = len(trace["devices"])
            device["busy_s"] = sum(
                d["busy_ns"] for d in trace["devices"]) / n / 1e9
            device["window_s"] = trace["devices"][0]["window_ns"] / 1e9
            line["breakdown"] = breakdown(trace)
    else:
        for metric in cell["end_to_end"]:
            line["metrics"][metric["name"]] = {
                "value": run["end_to_end"][metric["name"]],
                "unit": metric["unit"]}
    keep = args.keep
    if keep:
        os.makedirs(keep, exist_ok=True)
        tag = f"{args.workload}.s{args.seed}.t{args.trace}"
        with open(os.path.join(keep, tag + ".json"), "w") as f:
            json.dump({k: v for k, v in run.items()
                       if k not in ("config", "traffic")}, f)
        if args.trace and trace:
            shutil.copy(trace["path"],
                        os.path.join(keep, tag + ".xplane.pb"))
    shutil.rmtree(run["scratch"], ignore_errors=True)
    if not run["correct"]:
        print(f"[bench] checks: {run['checks']}", file=sys.stderr,
              flush=True)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    os._exit(0)  # nothing runs after the last line: no atexit, no echo


if __name__ == "__main__":
    main()
