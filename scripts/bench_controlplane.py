"""Quick control-plane microbench: actor storms, PG churn, lease p99.

Runs the control-plane rows — the ``many_actors``
creation-to-ready rate over a 4-node virtual cluster (the ISSUE-10
headline row), the actor create+destroy churn and PG churn cycles, and
the lease-grant p99 at 1 node vs 4 nodes (flatness ratio) — then
prints ONE line of JSON with the measured values and their delta
against the repo baseline, so ``make bench-controlplane`` gives a
minutes-scale signal on scheduler work.  A count on the host's CPUs,
not a chip measurement.

Baseline resolution: the newest parseable ``BENCH_r*.json`` artifact
(the per-round records kept next to ``BASELINE.json``); rows missing
there fall back to the seed reference numbers.

Usage::

    python scripts/bench_controlplane.py [--skip-churn] [--skip-p99]
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

# runnable as `python scripts/bench_controlplane.py` from a fresh
# checkout without an installed package or PYTHONPATH
if HERE not in sys.path:
    sys.path.insert(0, HERE)

#: newest-round fallbacks when no BENCH_r*.json artifact parses
#: (BENCH_r05 values — the numbers ISSUE 10 targets a multiple of)
FALLBACK_BASELINE = {
    "many_actors_per_sec_4node": 93.69,
    "many_pgs_per_sec_4node": 1674.16,
    "actor_churn_per_sec_4node": None,   # new row: no seed baseline
    "pg_churn_per_sec_4node": None,
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
            base = {k: v for k, v in FALLBACK_BASELINE.items()
                    if v is not None}
            base.update({k: details[k] for k in FALLBACK_BASELINE
                         if k in details})
            base["baseline_round"] = int(
                re.search(r"r(\d+)", os.path.basename(path)).group(1))
            return base
    return {k: v for k, v in FALLBACK_BASELINE.items() if v is not None}


def _lease_grant_hist() -> "tuple | None":
    """(boundaries, buckets) of ``ray_tpu_lease_grant_latency_s`` from
    the live GCS metrics table (the raylets' queue-entry -> grant
    histogram, merged across nodes)."""
    import ray_tpu.core.worker as _cw

    gw = _cw.global_worker_or_none()
    if gw is None:
        return None
    for rec in gw.gcs_call("get_metrics", timeout=30):
        if rec.get("name") == "ray_tpu_lease_grant_latency_s" \
                and rec.get("type") == "histogram":
            return (list(rec.get("boundaries") or []),
                    list(rec.get("buckets") or []))
    return None


def _lease_grant_p99_ms(since: "tuple | None" = None) -> "float | None":
    """p99 upper-bound (ms) of the lease-grant histogram, optionally
    over the DELTA since a prior :func:`_lease_grant_hist` snapshot —
    the warm-storm tail, not the cluster's cold-boot fork waits."""
    cur = _lease_grant_hist()
    if cur is None:
        return None
    bounds, buckets = cur
    if since is not None and len(since[1]) == len(buckets):
        buckets = [b - a for a, b in zip(since[1], buckets)]
    total = sum(buckets)
    if not total or not bounds:
        return None
    acc = 0
    for i, n in enumerate(buckets):
        acc += n
        if acc >= 0.99 * total:
            bound = bounds[i] if i < len(bounds) else bounds[-1] * 2
            return round(bound * 1000, 3)
    return None


def bench_controlplane(budget_s: float = 240.0) -> dict:
    """Control-plane scale-out section (ISSUE 10): actor-storm
    create+destroy churn, placement-group churn, and the lease-grant
    p99 at 1 node vs 4 nodes.  The flatness ratio is the scale-out
    claim: batched registration + pipelined bring-up must not let the
    grant tail grow with node count."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    out: dict = {}

    def actor_cls():
        @ray_tpu.remote(num_cpus=0.01)
        class A:
            def ping(self):
                return 1
        return A

    def storm(A, n, waves, settle=0.0):
        """create+ping+destroy cycles; returns actors/s THROUGH the
        full cycle (kills included in the clock, settles excluded)."""
        total = 0.0
        for _ in range(waves):
            t0 = time.perf_counter()
            actors = [A.remote() for _ in range(n)]
            ray_tpu.get([a.ping.remote() for a in actors],
                        timeout=budget_s)
            for a in actors:
                ray_tpu.kill(a)
            total += time.perf_counter() - t0
            if settle:
                time.sleep(settle)
        return n * waves / total

    # -- phase 1: single node (the p99 baseline) -----------------------
    c = None
    try:
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
        c.connect()
        A = actor_cls()
        storm(A, 30, 1)          # warm pool + exercise the grant path
        time.sleep(6.0)          # flush the warmup's grant latencies
        h0 = _lease_grant_hist()
        storm(A, 30, 2, settle=2.0)
        time.sleep(6.0)          # one metrics_report_period_s flush
        p99_1 = _lease_grant_p99_ms(since=h0)
        if p99_1 is not None:
            out["lease_grant_p99_ms_1node"] = p99_1
    except Exception as e:  # noqa: BLE001 — report, keep benching
        out["controlplane_error"] = f"1node: {type(e).__name__}: {e}"
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if c is not None:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001
                pass

    # -- phase 2: 4 nodes (churn + p99 flatness) -----------------------
    c = None
    try:
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
        for _ in range(3):
            c.add_node(num_cpus=4)
        c.connect()
        c.wait_for_nodes()
        # PG churn FIRST: PG cycles spawn no workers, but the actor
        # storms below leave ~200 worker reaps + the demand-driven
        # pool rebuild in their wake, which would tax whatever runs
        # next (the r03 many_pgs "regression" was this interference)
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        t0 = time.perf_counter()
        cycles = 3
        for _ in range(cycles):
            pgs = [placement_group([{"CPU": 0.01}]) for _ in range(100)]
            for pg in pgs:
                pg.wait(30)
            for pg in pgs:
                remove_placement_group(pg)
        out["pg_churn_per_sec_4node"] = round(
            cycles * 100 / (time.perf_counter() - t0), 2)

        A = actor_cls()
        # warmup sized like the churn waves (demand-driven pool learns
        # the wave size), then the p99 probe and the churn cycles
        storm(A, 50, 1)
        time.sleep(6.0)          # flush warmup grants before the delta
        h0 = _lease_grant_hist()
        # p99 probe: the IDENTICAL storm shape the 1-node phase ran
        # (same offered load on 4x capacity — flatness is the claim)
        storm(A, 30, 2, settle=2.0)
        time.sleep(6.0)
        p99_4 = _lease_grant_p99_ms(since=h0)
        if p99_4 is not None:
            out["lease_grant_p99_ms_4node"] = p99_4
            p99_1 = out.get("lease_grant_p99_ms_1node")
            if p99_1:
                out["lease_p99_ratio_4v1"] = round(p99_4 / p99_1, 3)
        # churn keeps kills + reaping IN the clock — the serve-replica
        # / RL-fleet turnover shape, where creation storms overlap
        # destruction storms
        out["actor_churn_per_sec_4node"] = round(storm(A, 50, 4), 2)
    except Exception as e:  # noqa: BLE001
        out["controlplane_error"] = f"4node: {type(e).__name__}: {e}"
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if c is not None:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001
                pass
    return out


def bench(skip_churn: bool, skip_p99: bool) -> dict:
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    out: dict = {}
    # churn + p99 rows: that section owns its clusters' lifecycle
    if not (skip_churn and skip_p99):
        out.update(bench_controlplane())
        if skip_churn:
            out.pop("actor_churn_per_sec_4node", None)
            out.pop("pg_churn_per_sec_4node", None)
        if skip_p99:
            for k in ("lease_grant_p99_ms_1node",
                      "lease_grant_p99_ms_4node", "lease_p99_ratio_4v1"):
                out.pop(k, None)

    # many_actors headline row (demand-sized warmup wave, 3 timed waves
    # of 100, settles between so the rebuild is not measured)
    c = None
    try:
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
        for _ in range(3):
            c.add_node(num_cpus=4)
        c.connect()
        c.wait_for_nodes()

        # many_pgs FIRST (cluster-scale section parity): PG cycles
        # spawn no workers, but the actor waves below leave worker
        # reaps + the demand-driven pool rebuild in their wake, which
        # would tax whatever runs next
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        warm_pgs = [placement_group([{"CPU": 0.01}]) for _ in range(10)]
        for pg in warm_pgs:
            pg.wait(30)
        for pg in warm_pgs:
            remove_placement_group(pg)
        time.sleep(1.0)
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            pgs = [placement_group([{"CPU": 0.01}]) for _ in range(100)]
            for pg in pgs:
                pg.wait(30)
            samples.append(100 / (time.perf_counter() - t0))
            for pg in pgs:
                remove_placement_group(pg)
            time.sleep(2.0)
        out["many_pgs_per_sec_4node"] = round(
            statistics.median(samples), 2)

        @ray_tpu.remote(num_cpus=0.01)
        class A:
            def ping(self):
                return 1

        warm = [A.remote() for _ in range(100)]
        ray_tpu.get([a.ping.remote() for a in warm], timeout=120)
        for a in warm:
            ray_tpu.kill(a)
        time.sleep(4.5)
        # median of 5 (not 3): single-core waves occasionally eat a
        # multi-second scheduler stall (pre-existing, shows on the
        # seed tree too); one bad wave must not own the median
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            actors = [A.remote() for _ in range(100)]
            ray_tpu.get([a.ping.remote() for a in actors], timeout=120)
            samples.append(100 / (time.perf_counter() - t0))
            for a in actors:
                ray_tpu.kill(a)
            time.sleep(4.5)
        out["many_actors_per_sec_4node"] = round(
            statistics.median(samples), 2)
        out["many_actors_samples"] = [round(s, 1) for s in samples]
    except Exception as e:  # noqa: BLE001 — always report what we have
        out["controlplane_bench_error"] = f"{type(e).__name__}: {e}"
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if c is not None:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001
                pass
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--skip-churn", action="store_true")
    ap.add_argument("--skip-p99", action="store_true")
    args = ap.parse_args()

    result = bench(args.skip_churn, args.skip_p99)
    baseline = load_baseline()
    delta = {}
    for key, value in result.items():
        base = baseline.get(key)
        if not isinstance(base, (int, float)) or base <= 0 \
                or not isinstance(value, (int, float)):
            continue
        # every baselined row here is a throughput: improves when it grows
        delta[f"vs_baseline_{key}"] = round(value / base, 2)
    line = dict(result)
    line.update(delta)
    if "baseline_round" in baseline:
        line["baseline_round"] = baseline["baseline_round"]
    print(json.dumps(line))


if __name__ == "__main__":
    main()
