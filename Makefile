# Native runtime components (C++). `make` builds build/librtpu-<sha>.so; the
# Python side also builds it on demand (ray_tpu/core/native.py).
#
# Sanitizer targets (the race-detection story for the native plane —
# parity with the reference's tsan/asan CI configs):
#   make tsan   — ThreadSanitizer build of the concurrency stress
#                 harness (src/store_stress.cc) + run
#   make asan   — AddressSanitizer+UBSan build + run
.PHONY: all native check check-fast test chaos metrics-smoke \
	metrics-history-smoke postmortem-smoke tsan asan sanitize clean

CXX ?= g++
CXXFLAGS = -std=c++17 -O1 -g -fno-omit-frame-pointer -Wall -Wextra
SAN_SRCS = src/object_store.cc src/sched_core.cc src/store_stress.cc

all: native

native:
	python -m ray_tpu.core.native

# Static analysis (rtpu-check): async-safety lints + registry
# conformance over ray_tpu/ (docs/static_analysis.md).  Exits non-zero
# on any finding that is neither inline-suppressed nor baselined;
# output is file:line rule message.
check:
	python -m ray_tpu.tools.check

# Pre-commit-speed variant: only git-modified modules plus their direct
# dependents (resolved through the module graph) are scanned; the
# summary cache makes a one-file edit sub-second.  Whole-tree
# registries (handlers, IDEMPOTENT_METHODS, metrics golden) still come
# from the full index, so scoping never hides cross-file findings.
check-fast:
	python -m ray_tpu.tools.check --changed-only

# Tier-1: fast static preamble, then the suite under a wall-clock
# budget (conftest.pytest_sessionfinish fails a green-but-slow run).
test: native check-fast
	RTPU_TIER1_BUDGET_S=870 python -m pytest tests/ -q

# The long-running training/learning regressions that tier-1 slow-marks
# to stay inside its time budget: full RL algorithm runs, example
# walkthroughs, DDP/HF trainer convergence, the node-kill campaigns,
# and the heaviest eight-node cases.  Run nightly / before a release.
test-heavy: native
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/test_chaos.py tests/test_rllib_extras.py \
	  tests/test_rllib_algorithms.py tests/test_rllib_zoo.py \
	  tests/test_rllib_meta.py tests/test_examples.py \
	  tests/test_train.py tests/test_train_frameworks.py \
	  tests/test_tune.py tests/test_cluster_scale.py \
	  -q -m "slow or not slow" \
	  -p no:cacheprovider -p no:randomly

# Deterministic chaos: failpoint-injection suite + node-kill suite +
# mid-transfer source-kill suite with fixed seeds (failpoint sites seed
# per-site; NodeKiller seeds in-test; PYTHONHASHSEED pins dict/hash
# order) so a failing run replays exactly.  The explicit -m expression
# also opts IN the slow-marked transfer failover test that plain runs
# auto-skip.
chaos: native
	PYTHONHASHSEED=0 JAX_PLATFORMS=cpu python -m pytest \
	  tests/test_failpoints.py tests/test_chaos.py \
	  tests/test_object_transfer.py tests/test_serve_batching.py \
	  tests/test_serve_sharded.py \
	  tests/test_tracing.py tests/test_rllib_pipeline.py \
	  tests/test_controlplane_scale.py tests/test_store_scale.py \
	  tests/test_gcs_ha.py tests/test_data_streaming.py \
	  tests/test_metrics_history.py tests/test_incidents.py \
	  tests/test_node_drain.py tests/test_autoscaler_monitor.py \
	  tests/test_fair_queue.py tests/test_autoscaler_chaos.py \
	  -q -m "slow or not slow" \
	  -p no:cacheprovider -p no:randomly

# Boot a mini-cluster, scrape dashboard /metrics, and diff the exported
# ray_tpu_* series list against scripts/metrics_golden.txt (catches
# accidental metric renames; update deliberately with --update).
metrics-smoke: native
	JAX_PLATFORMS=cpu python scripts/metrics_smoke.py

# Boot a mini-cluster, wait two history sample intervals, assert
# /api/timeseries returns >=2 points for a traffic-independent series
# and /healthz verdicts ok (docs/observability.md).
metrics-history-smoke: native
	JAX_PLATFORMS=cpu python scripts/metrics_history_smoke.py

# Boot a mini-cluster, SIGKILL a worker mid-workload, assert the
# incident journal opened with the dead worker's flight tail, that
# `ray-tpu postmortem --last` renders, and that the debug bundle
# tar-extracts with a manifest (docs/observability.md).
postmortem-smoke: native
	JAX_PLATFORMS=cpu python scripts/postmortem_smoke.py

build/store_stress_tsan: $(SAN_SRCS)
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=thread $(SAN_SRCS) -o $@ -pthread

build/store_stress_asan: $(SAN_SRCS)
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=address,undefined \
	  -fno-sanitize-recover=all $(SAN_SRCS) -o $@ -pthread

tsan: build/store_stress_tsan
	TSAN_OPTIONS="halt_on_error=1" ./build/store_stress_tsan

asan: build/store_stress_asan
	ASAN_OPTIONS="detect_leaks=1" UBSAN_OPTIONS="halt_on_error=1" \
	  ./build/store_stress_asan

sanitize: tsan asan

clean:
	rm -rf build
