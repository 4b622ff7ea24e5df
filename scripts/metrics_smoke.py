"""Metrics smoke test: boot a mini-cluster, scrape ``/metrics``, diff
the exported series list against the checked-in golden file.

Catches accidental metric renames/removals: every name in
``scripts/metrics_golden.txt`` must appear in a fresh scrape, and every
scraped ``ray_tpu_*`` name must be either in the golden file or in the
TRAFFIC_DEPENDENT allowlist (series that only appear under multi-node
traffic or failures).  A NEW runtime series therefore fails the smoke
until the golden file is updated deliberately::

    python scripts/metrics_smoke.py            # check (CI: make metrics-smoke)
    python scripts/metrics_smoke.py --update   # regenerate the golden file
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "metrics_golden.txt")

# runnable as `python scripts/metrics_smoke.py` from a fresh checkout
_ROOT = os.path.dirname(HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

#: legitimately absent from a quiet single-node boot: transfer data
#: paths need a second node, failure counters need failures
TRAFFIC_DEPENDENT = {
    "ray_tpu_transfer_chunks_total",
    "ray_tpu_transfer_bytes_total",
    "ray_tpu_transfer_pulls_total",
    "ray_tpu_transfer_failovers_total",
    "ray_tpu_transfer_window_occupancy",
    "ray_tpu_transfer_throughput_mbps",
    "ray_tpu_rpc_retries_total",
    "ray_tpu_rpc_deadline_exceeded_total",
    # control-plane scheduler series: need actor/lease traffic (a quiet
    # boot never registers a batch, grants a lease, or parks one)
    "ray_tpu_sched_registration_batch_size",
    "ray_tpu_sched_warm_pool_total",
    "ray_tpu_sched_lease_cache_total",
    "ray_tpu_gcs_heartbeat_misses_total",
    "ray_tpu_gcs_node_deaths_total",
    # autoscaler / drain plane: decision counters need a running
    # AutoscalerMonitor, drain transitions need a drain_node call, and
    # the throttle gauge needs a quota actually deferring leases
    "ray_tpu_gcs_node_drain_transitions_total",
    "ray_tpu_sched_quota_throttled_total",
    "ray_tpu_autoscaler_decisions_total",
    "ray_tpu_autoscaler_launch_failures_total",
    "ray_tpu_autoscaler_target_nodes",
    # HA persistence plane: failure counters need failures, replay /
    # recovery series need a head restart, and the WAL series are
    # absent entirely on ephemeral (memory-storage) clusters
    "ray_tpu_gcs_persist_failures_total",
    "ray_tpu_gcs_wal_appends_total",
    "ray_tpu_gcs_wal_fsyncs_total",
    "ray_tpu_gcs_wal_append_failures_total",
    "ray_tpu_gcs_wal_replayed_records_total",
    "ray_tpu_gcs_wal_size_bytes",
    "ray_tpu_gcs_recovery_duration_s",
    "ray_tpu_task_events_dropped_total",
    "ray_tpu_arena_doomed_objects",
    # spill-tier series: counters need actual spill/restore traffic; the
    # gauges ride the same stats_ex gate as the arena extras above
    "ray_tpu_store_spilled_bytes_total",
    "ray_tpu_store_restored_bytes_total",
    "ray_tpu_store_spill_objects",
    "ray_tpu_store_shard_contention_total",
    # sharded serving plane: KV/gang series need a sharded or paged
    # deployment serving traffic; gcs_respawns needs a head death
    "ray_tpu_serve_kv_pages_active",
    "ray_tpu_serve_kv_pages_allocated_total",
    "ray_tpu_serve_kv_pages_freed_total",
    "ray_tpu_serve_kv_page_occupancy",
    # routed expert layers report only where a training loop asks
    # (models/afmoe.py report_router_stats; models/deepseek_v3.py's and
    # models/nemotron_h.py's under their own `model` tags, the same
    # four series).  What a step was COMPILED as is in spans, not
    # series, so nothing of it is listed here: `model:moe.plan`,
    # `model:mla.plan`, `model:hybrid.plan`, `ops:ssd.plan` (the chunked
    # scan: heads, groups, state, chunk, heads_a_step, carry) and
    # `ops:flash.plan`
    # (family, heads, width, seq, block, window, sub, sub_forward,
    # tiles_live, tiles_cut, pairs_worked, pairs_worked_forward,
    # pairs_visible: docs/observability.md)
    # a looped stack's exit gate likewise (models/ouro.py
    # report_exit_stats); what loop was compiled is the `model:loop.plan`
    # span
    # hyper-connections likewise (models/deepseek_v3.py report_hc_stats);
    # what was compiled is the `model:hc.plan` span
    "ray_tpu_hc_offdiag_mass",
    "ray_tpu_hc_doubly_stochastic_error",
    "ray_tpu_hc_pre_entropy",
    "ray_tpu_loop_exit_share",
    "ray_tpu_loop_expected_passes",
    "ray_tpu_loop_exit_entropy",
    "ray_tpu_moe_exchange_bytes",
    "ray_tpu_moe_expert_load",
    "ray_tpu_moe_landed_share",
    "ray_tpu_moe_live_share",
    "ray_tpu_moe_load_imbalance",
    "ray_tpu_serve_gang_bringup_seconds",
    "ray_tpu_serve_gang_shards",
    "ray_tpu_serve_gang_deaths_total",
    # serving economics: prefix-cache / multiplex / steering series need
    # a prefix-enabled or multiplexed deployment actually serving
    "ray_tpu_serve_prefix_cache_total",
    "ray_tpu_serve_prefix_pages_shared",
    "ray_tpu_serve_mux_swaps_total",
    "ray_tpu_serve_mux_swap_seconds",
    "ray_tpu_serve_xgang_steered_total",
    "ray_tpu_gcs_respawns_total",
    # streaming data plane: series only appear once a streaming dataset
    # executes (and locality routing needs multi-node block placement)
    "ray_tpu_data_blocks_in_flight",
    "ray_tpu_data_backpressure_stalls_total",
    "ray_tpu_data_blocks_produced_total",
    "ray_tpu_data_prefetch_total",
    "ray_tpu_data_shuffle_spilled_bytes_total",
    "ray_tpu_sched_locality_leases_total",
    # profiler series: the sampler is off by default (profiler_enabled /
    # `ray-tpu profile` arm it), so a quiet boot exports none of them
    "ray_tpu_profiler_samples_total",
    "ray_tpu_profiler_stacks_dropped_total",
    "ray_tpu_profiler_records_evicted_total",
    # serve series: only exported once a deployment is running/serving
    "ray_tpu_serve_request_latency_s",
    "ray_tpu_serve_shed_total",
    "ray_tpu_serve_batch_occupancy",
    "ray_tpu_serve_queue_depth",
    "ray_tpu_serve_replicas",
    "ray_tpu_serve_ttft_seconds",
    # RL pipeline series: only exported while a decoupled PPO job runs
    # (inference actors / learner processes)
    "ray_tpu_rl_inference_batch_occupancy",
    "ray_tpu_rl_fragment_queue_depth",
    "ray_tpu_rl_weight_sync_age_s",
    "ray_tpu_rl_fragments_dropped_stale_total",
    "ray_tpu_serve_decode_step_seconds",
    # tracing series: need traced traffic (and retention/eviction need
    # the tail-sampler / ring pressure to actually fire)
    "ray_tpu_trace_spans_total",
    "ray_tpu_trace_retained_total",
    "ray_tpu_trace_sampled_out_total",
    "ray_tpu_trace_evicted_total",
    # per-job attribution: counters need task/put/spill traffic, the
    # arena gauge needs plasma-resident primaries
    "ray_tpu_job_tasks_total",
    "ray_tpu_job_cpu_seconds_total",
    "ray_tpu_job_submitted_bytes_total",
    "ray_tpu_job_spilled_bytes_total",
    "ray_tpu_job_arena_bytes",
    # history/alert plane: evictions need the ring to wrap a full
    # window, sample failures need the failpoint, transitions need an
    # alert to actually fire
    "ray_tpu_metrics_history_evicted_total",
    "ray_tpu_metrics_history_sample_failures_total",
    "ray_tpu_alerts_transitions_total",
    # device plane: compile/step/skew series need a jitted engine
    # actually stepping (serve batcher, train loop, RL inference); a
    # quiet boot compiles nothing and runs no steps
    "ray_tpu_xla_compiles_total",
    "ray_tpu_xla_compile_seconds",
    "ray_tpu_step_phase_seconds",
    "ray_tpu_step_goodput_per_s",
    "ray_tpu_train_mfu",
    "ray_tpu_train_step_data_wait_frac",
    "ray_tpu_serve_decode_device_frac",
    "ray_tpu_gang_rank_skew_seconds",
    # incident forensics: incidents need a death or firing alert, tail
    # ships need a crashed process, event-ring evictions need a ring to
    # actually wrap (5000 events of one severity)
    "ray_tpu_incidents_total",
    "ray_tpu_flight_tails_shipped_total",
    "ray_tpu_events_evicted_total",
}


def constructed_names() -> set:
    """Every ``ray_tpu_*`` series name constructed anywhere in the
    tree, via rtpu-check's AST scan — the same view its metric-drift
    rule enforces against the golden file."""
    from ray_tpu.tools.check.cli import discover_files, parse_files
    from ray_tpu.tools.check.project import collect_metric_names
    files = discover_files([os.path.join(_ROOT, "ray_tpu")])
    return set(collect_metric_names(parse_files(files, _ROOT)))


def scrape_series(timeout_s: float = 60.0) -> set:
    import ray_tpu
    from ray_tpu.dashboard import Dashboard

    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024 * 1024,
                 _system_config={"metrics_report_period_s": 0.5})
    try:
        @ray_tpu.remote
        def probe(i):
            return i * 2

        assert ray_tpu.get([probe.remote(i) for i in range(8)],
                           timeout=120) == [i * 2 for i in range(8)]
        ray_tpu.put(bytes(1_000_000))

        dash = Dashboard(port=0)
        url = dash.start()
        try:
            deadline = time.monotonic() + timeout_s
            names: set = set()
            stable_since = None
            while time.monotonic() < deadline:
                with urllib.request.urlopen(url + "/metrics",
                                            timeout=30) as r:
                    text = r.read().decode()
                new = {line.split()[2] for line in text.splitlines()
                       if line.startswith("# TYPE ")}
                if new == names and stable_since is not None and \
                        time.monotonic() - stable_since > 2.0 and names:
                    break  # two quiet seconds: the flush loops caught up
                if new != names:
                    names = new
                    stable_since = time.monotonic()
                time.sleep(0.5)
            return names
        finally:
            dash.stop()
    finally:
        ray_tpu.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--update", action="store_true",
                    help="rewrite the golden file from a fresh scrape")
    args = ap.parse_args()

    names = scrape_series()
    runtime = {n for n in names if n.startswith("ray_tpu_")}
    if args.update:
        # basis: the names the code actually constructs (rtpu-check's
        # view), so feature-gated series survive a quiet-boot regen
        # while renamed/removed series genuinely drop out
        constructed = constructed_names()
        # NOT unioned with TRAFFIC_DEPENDENT: every live entry there is
        # also constructed, so including it could only ever re-write
        # stale names into the catalogue
        catalogue = runtime | constructed
        with open(GOLDEN, "w") as f:
            f.write(
                "# Golden catalogue of every ray_tpu_* series the "
                "runtime constructs.\n"
                "# Two classes:\n"
                "#   - boot series: exported by a quiet single-node "
                "boot; metrics_smoke\n"
                "#     fails if a scrape is missing one (renamed or "
                "producer broken).\n"
                "#   - traffic-dependent series (listed in "
                "TRAFFIC_DEPENDENT in\n"
                "#     scripts/metrics_smoke.py): only appear under "
                "multi-node traffic\n"
                "#     or failures; smoke tolerates their absence, but "
                "rtpu-check's\n"
                "#     metric-drift rule still requires them HERE so "
                "the catalogue is\n"
                "#     the single source of truth for dashboards.\n"
                "# Regenerate: python scripts/metrics_smoke.py "
                "--update\n")
            for n in sorted(catalogue):
                f.write(n + "\n")
        print(f"wrote {len(catalogue)} series to {GOLDEN}")
        # a constructed series that neither appears in a quiet boot nor
        # is classified traffic-dependent would make the next check
        # report it MISSING — and rerunning --update can't fix that, so
        # say exactly what will
        rc = 0
        unclassified = constructed - runtime - TRAFFIC_DEPENDENT
        if unclassified:
            print("these constructed series are absent from a quiet "
                  "boot and not in TRAFFIC_DEPENDENT; the next check "
                  "will report them MISSING — add them to "
                  "TRAFFIC_DEPENDENT in scripts/metrics_smoke.py:",
                  file=sys.stderr)
            for n in sorted(unclassified):
                print(f"  {n}", file=sys.stderr)
            rc = 1
        # the inverse rot: an entry that outlived its constructor would
        # be re-written into the catalogue by every --update and
        # excused from the missing-check forever
        stale = TRAFFIC_DEPENDENT - constructed
        if stale:
            print("these TRAFFIC_DEPENDENT entries are no longer "
                  "constructed anywhere (renamed/removed metric?); "
                  "drop them from scripts/metrics_smoke.py:",
                  file=sys.stderr)
            for n in sorted(stale):
                print(f"  {n}", file=sys.stderr)
            rc = 1
        return rc

    try:
        from ray_tpu.tools.check.project import parse_catalogue
        with open(GOLDEN) as f:
            golden = parse_catalogue(f.read())
    except FileNotFoundError:
        print(f"missing golden file {GOLDEN}; run with --update first",
              file=sys.stderr)
        return 2

    # the golden file is the FULL catalogue (rtpu-check's metric-drift
    # rule keys on it); traffic-dependent series are legitimately
    # absent from a quiet boot
    missing = golden - names - TRAFFIC_DEPENDENT
    unexpected = runtime - golden
    ok = not missing and not unexpected
    print(f"scraped {len(runtime)} ray_tpu_* series "
          f"({len(names)} total)")
    if missing:
        print("MISSING (renamed or producer broken):", file=sys.stderr)
        for n in sorted(missing):
            print(f"  - {n}", file=sys.stderr)
    if unexpected:
        print("UNEXPECTED (new series? update the golden file):",
              file=sys.stderr)
        for n in sorted(unexpected):
            print(f"  + {n}", file=sys.stderr)
    print("metrics smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
