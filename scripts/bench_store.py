"""Object-store microbench: writer-count put sweep + spill roundtrip.

Runs the two object-plane rows this plane's work is gated on — the
1/2/4/8-writer aggregate put-bandwidth sweep (``put_gbps_by_writers``,
the curve the sharded store metadata exists for) and a put/get round
over a working set ~2x the arena that rotates through the raylet's
spill tier with transparent restore — then prints ONE line of JSON
with the measured values and their delta against the repo baseline, so
``make bench-store`` gives a sub-two-minute signal on store work.  A
count on the host's CPUs, not a chip measurement.

Baseline resolution: the newest parseable ``BENCH_r*.json`` artifact
(the per-round records kept next to ``BASELINE.json``); rows missing
there fall back to the seed reference numbers.

Usage::

    python scripts/bench_store.py [--mb 64] [--reps 2] [--skip-spill]
                                  [--skip-sweep]
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

# runnable as `python scripts/bench_store.py` (make bench-store)
# without an installed package or PYTHONPATH
if HERE not in sys.path:
    sys.path.insert(0, HERE)

#: seed-era fallbacks when no BENCH_r*.json artifact parses
#: (put_gbps_multi_client is the 4-writer sweep point's ancestor row)
FALLBACK_BASELINE = {
    "put_gbps_single_client": 76.2,
    "put_gbps_multi_client": 18.2,
}


def load_baseline() -> dict:
    arts = sorted(
        glob.glob(os.path.join(HERE, "BENCH_r*.json")),
        key=lambda p: int(re.search(r"r(\d+)", os.path.basename(p)).group(1)))
    keys = set(FALLBACK_BASELINE) | {"put_gbps_by_writers",
                                     "spill_roundtrip_gbps"}
    for path in reversed(arts):
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
            details = parsed.get("details") or {}
        except Exception:  # noqa: BLE001 — artifact tails can truncate
            continue
        if any(k in details for k in keys):
            base = dict(FALLBACK_BASELINE)
            base.update({k: details[k] for k in keys if k in details})
            base["baseline_round"] = int(
                re.search(r"r(\d+)", os.path.basename(path)).group(1))
            return base
    return dict(FALLBACK_BASELINE)


def put_writer_sweep(putters, gbits: float, reps: int) -> dict:
    """Aggregate put bandwidth at 1/2/4/8 concurrent writers: each
    point is a median of ``reps`` timed rounds of 2 puts per writer."""
    import ray_tpu

    sweep = {}
    for n in (1, 2, 4, 8):
        samples = []
        for i in range(reps):
            if i:
                time.sleep(1.5)
            t0 = time.perf_counter()
            ray_tpu.get([p.put_big.remote(2) for p in putters[:n]],
                        timeout=600)
            samples.append(n * 2 * gbits / (time.perf_counter() - t0))
        sweep[str(n)] = round(statistics.median(samples), 2)
        time.sleep(1.5)
    return sweep


def bench_store_spill() -> dict:
    """Larger-than-arena put/get round: a working set ~2x the object
    store rotates through the raylet's spill tier and restores
    transparently on get — correctness (checksums) plus round-trip
    bandwidth.  Runs on its own mini cluster so the deliberately tiny
    arena can't bleed into other sections."""
    import numpy as np

    import ray_tpu

    out: dict = {}
    arena = 256 * 1024 * 1024
    chunk = 32 * 1024 * 1024
    n_objects = 16  # 512 MiB working set vs the 256 MiB arena
    ray_tpu.init(_system_config={
        "object_store_memory": arena,
        "object_spill_threshold": 0.8,
        "num_prestart_workers": 1,
    })
    try:
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 255, chunk, dtype=np.uint8)
        sums, refs = [], []
        t0 = time.perf_counter()
        for i in range(n_objects):
            payload[:8] = i  # distinct objects, one allocation
            refs.append(ray_tpu.put(payload))
            sums.append(int(payload.sum()))
        put_s = time.perf_counter() - t0
        from ray_tpu.experimental.state import object_store_stats
        try:
            stats = object_store_stats()[0]
        except Exception:  # noqa: BLE001 — accounting row is optional
            stats = {}
        t0 = time.perf_counter()
        for i, ref in enumerate(refs):
            got = ray_tpu.get(ref, timeout=120)
            assert int(np.asarray(got).sum()) == sums[i], \
                f"spill roundtrip corrupted object {i}"
            del got
        get_s = time.perf_counter() - t0
        total_gbits = n_objects * chunk * 8 / 1e9
        out["spill_put_gbps"] = round(total_gbits / put_s, 2)
        out["spill_get_gbps"] = round(total_gbits / get_s, 2)
        out["spill_roundtrip_gbps"] = round(
            2 * total_gbits / (put_s + get_s), 2)
        if isinstance(stats, dict) and stats.get("num_spilled"):
            out["spill_objects_peak"] = stats["num_spilled"]
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
    return out


def bench_sweep(mb: int, reps: int) -> dict:
    """1/2/4/8-writer aggregate put bandwidth on a default-size arena."""
    import ray_tpu

    out: dict = {}
    ray_tpu.init()
    try:
        @ray_tpu.remote(num_cpus=0)
        class Putter:
            """Per-client payload allocated ONCE outside the timed loop
            (a fresh np.zeros per put would measure page faults)."""

            def __init__(self, mb):
                import numpy as _np
                self.data = _np.ones(mb * 1024 * 1024, dtype=_np.uint8)

            def put_big(self, n):
                import ray_tpu as _rt
                for _ in range(n):
                    _rt.put(self.data)
                return n

        gbits = mb * 1024 * 1024 * 8 / 1e9
        putters = [Putter.remote(mb) for _ in range(8)]
        ray_tpu.get([p.put_big.remote(1) for p in putters], timeout=180)
        time.sleep(3.0)
        sweep = put_writer_sweep(putters, gbits, reps)
        out["put_gbps_by_writers"] = sweep
        out["put_gbps_single_client"] = sweep["1"]
        out["put_gbps_multi_client"] = sweep["4"]
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001 — teardown must not eat results
            pass
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mb", type=int, default=64,
                    help="per-put object size in MiB")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--skip-spill", action="store_true")
    ap.add_argument("--skip-sweep", action="store_true")
    args = ap.parse_args()

    result: dict = {}
    if not args.skip_sweep:
        result.update(bench_sweep(args.mb, args.reps))
    if not args.skip_spill:
        result.update(bench_store_spill())

    baseline = load_baseline()
    delta = {}
    for key, value in result.items():
        base = baseline.get(key)
        if not isinstance(base, (int, float)) or base <= 0:
            continue
        delta[f"vs_baseline_{key}"] = round(value / base, 2)
    # the sweep's 4-writer point also rates against the multi-client row
    sweep = result.get("put_gbps_by_writers") or {}
    if "4" in sweep and isinstance(
            baseline.get("put_gbps_multi_client"), (int, float)):
        delta["vs_baseline_put_gbps_multi_client"] = round(
            sweep["4"] / baseline["put_gbps_multi_client"], 2)
    if "1" in sweep and sweep.get("1"):
        delta["multi_over_single_4w"] = round(
            sweep.get("4", 0) / sweep["1"], 2)
    line = dict(result)
    line.update(delta)
    if "baseline_round" in baseline:
        line["baseline_round"] = baseline["baseline_round"]
    print(json.dumps(line))


if __name__ == "__main__":
    main()
