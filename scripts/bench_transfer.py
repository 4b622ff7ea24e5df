"""Quick object-transfer microbench: broadcast + multi-client put.

Runs the two transfer-plane rows (the 1->N broadcast over a 4-node
virtual cluster and the 4-putter multi-client put) at a reduced repeat
count, then prints ONE line of JSON with the measured
values and their delta against the repo baseline, so ``make
bench-transfer`` gives a sub-two-minute signal on transfer-plane work.
A count on the host's CPUs, not a chip measurement.

Baseline resolution: the newest parseable ``BENCH_r*.json`` artifact
(the per-round records kept next to ``BASELINE.json``); rows missing
there fall back to the seed reference numbers.

Usage::

    python scripts/bench_transfer.py [--mb 256] [--consumers 6]
                                     [--reps 2] [--skip-put]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# runnable as `python scripts/bench_transfer.py` (make bench-transfer)
# without an installed package or PYTHONPATH
if HERE not in sys.path:
    sys.path.insert(0, HERE)

#: seed-era fallbacks when no BENCH_r*.json artifact parses
FALLBACK_BASELINE = {
    "broadcast_256mb_4node_s": 1.66,
    "put_gbps_multi_client": 18.18,
}


def load_baseline() -> dict:
    arts = sorted(
        glob.glob(os.path.join(HERE, "BENCH_r*.json")),
        key=lambda p: int(re.search(r"r(\d+)", os.path.basename(p)).group(1)))
    for path in reversed(arts):
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
            details = parsed.get("details") or {}
        except Exception:  # noqa: BLE001 — artifact tails can truncate
            continue
        if any(k in details for k in FALLBACK_BASELINE):
            base = dict(FALLBACK_BASELINE)
            base.update({k: details[k] for k in FALLBACK_BASELINE
                         if k in details})
            base["baseline_round"] = int(
                re.search(r"r(\d+)", os.path.basename(path)).group(1))
            return base
    return dict(FALLBACK_BASELINE)


def bench(mb: int, consumers: int, reps: int, skip_put: bool,
          skip_broadcast: bool = False) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    out: dict = {}
    # full default-size prestart pool: with a smaller
    # pool the broadcast row measures worker-spawn churn, not transfer
    # (the idle-pool trim re-spawns workers between repeats)
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    try:
        for _ in range(3):
            c.add_node(num_cpus=4)
        c.connect()
        c.wait_for_nodes(timeout=300.0)

        # -- broadcast: every node pulls one large object --------------
        @ray_tpu.remote(num_cpus=0.01, scheduling_strategy="SPREAD")
        def fetch_size(refs):
            return ray_tpu.get(refs[0]).nbytes

        samples = []
        for _ in range(0 if skip_broadcast else reps):
            blob_ref = ray_tpu.put(np.ones(mb * 1024 * 1024, np.uint8))
            t0 = time.perf_counter()
            sizes = ray_tpu.get([fetch_size.remote([blob_ref])
                                 for _ in range(consumers)], timeout=300)
            assert all(s == mb * 1024 * 1024 for s in sizes)
            samples.append(time.perf_counter() - t0)
            del blob_ref
            time.sleep(1.0)
        if samples:
            key = f"broadcast_{mb}mb_4node_s" if mb != 256 \
                else "broadcast_256mb_4node_s"
            out[key] = round(statistics.median(samples), 3)

        if skip_put:
            return out

        # -- multi-client put ------------------------------------------
        @ray_tpu.remote(num_cpus=0)
        class Putter:
            def __init__(self, mb):
                import numpy as _np
                self.data = _np.ones(mb * 1024 * 1024, dtype=_np.uint8)

            def put_big(self, n):
                import ray_tpu as _rt
                for _ in range(n):
                    _rt.put(self.data)
                return n

        gbits = 64 * 1024 * 1024 * 8 / 1e9
        putters = [Putter.remote(64) for _ in range(4)]
        ray_tpu.get([p.put_big.remote(1) for p in putters], timeout=120)
        time.sleep(2.0)
        mc = []
        for i in range(reps):
            if i:
                time.sleep(2.0)
            t0 = time.perf_counter()
            ray_tpu.get([p.put_big.remote(2) for p in putters],
                        timeout=300)
            mc.append(4 * 2 * gbits / (time.perf_counter() - t0))
        out["put_gbps_multi_client"] = round(statistics.median(mc), 2)
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001 — teardown must not eat results
            pass
        try:
            c.shutdown()
        except Exception:  # noqa: BLE001
            pass
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mb", type=int, default=256,
                    help="broadcast object size in MiB")
    ap.add_argument("--consumers", type=int, default=6)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--skip-put", action="store_true")
    ap.add_argument("--skip-broadcast", action="store_true")
    args = ap.parse_args()

    result = bench(args.mb, args.consumers, args.reps, args.skip_put,
                   args.skip_broadcast)
    baseline = load_baseline()
    delta = {}
    for key, value in result.items():
        base = baseline.get(key)
        if not isinstance(base, (int, float)) or base <= 0:
            continue
        # time rows improve when they SHRINK, throughput when they grow
        delta[f"vs_baseline_{key}"] = round(
            base / value if key.endswith("_s") else value / base, 2)
    line = dict(result)
    line.update(delta)
    if "baseline_round" in baseline:
        line["baseline_round"] = baseline["baseline_round"]
    print(json.dumps(line))


if __name__ == "__main__":
    main()
