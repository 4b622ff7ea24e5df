"""Cost of one ``telemetry.span()`` enter and exit, in nanoseconds: in a
process that has not imported jax, in one that has (the span is then a
``TraceAnnotation`` too), and with a profiler session open.

    python3 scripts/bench_span.py [iterations]
    python3 scripts/bench_span.py tasks [n]

The second form is what the span sites on a task's path cost where it
would show: no-op tasks a second through one worker, replies inline
(``CoreWorker._post_return`` runs once a task).  It uses the public API
alone, so the same file runs in a checkout that has no ``span()``.

A host number, not a device number: the backend it opens for the
profiler session is the CPU's, so a chip that this host leases stays
free.  PERF.md section 6 records what the chip host gave.
"""

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def per_span_ns(n: int) -> float:
    from ray_tpu.core import telemetry

    span = telemetry.span
    t0 = time.perf_counter()
    for _ in range(n):
        with span("bench", "span"):
            pass
    dt = time.perf_counter() - t0
    telemetry.drain_spans("bench")
    return 1e9 * dt / n


def tasks_per_s(n: int, rounds: int = 5) -> dict:
    import ray_tpu

    ray_tpu.init(num_cpus=1, _system_config={"log_to_driver": False})
    try:
        @ray_tpu.remote
        def nothing():
            return None

        ray_tpu.get([nothing.remote() for _ in range(200)])  # warm
        rates = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            ray_tpu.get([nothing.remote() for _ in range(n)])
            rates.append(n / (time.perf_counter() - t0))
    finally:
        ray_tpu.shutdown()
    return {"tasks": n, "tasks_per_s": rates}


def main() -> None:
    if sys.argv[1:2] == ["tasks"]:
        os.environ["JAX_PLATFORMS"] = "cpu"
        print(json.dumps(tasks_per_s(
            int(sys.argv[2]) if len(sys.argv) > 2 else 2000)))
        return
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    out = {"iterations": n, "no_jax_ns": per_span_ns(n)}
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    out["jax_imported_no_session_ns"] = per_span_ns(n)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            out["profiler_session_open_ns"] = per_span_ns(n)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
