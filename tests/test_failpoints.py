"""Deterministic failpoint injection across the control plane.

Each armed site drives a live mini-cluster through a partial failure —
a dropped reply, a slow lease grant, a GCS stall mid-registration —
and asserts the system degrades gracefully: a successful retry, a
re-dispatch, or a typed error.  Never a hang, never a silent wrong
answer.  All sites run with a fixed seed (``prob=1.0`` sites are fully
deterministic; probabilistic sites reproduce per-seed).

Layers covered by armed sites here:
  rpc     — ``rpc.echo.reply_drop``, ``rpc.echo.request_drop``,
            ``rpc.push_tasks.handler_delay``
  gcs     — ``gcs.heartbeat.delay``, ``gcs.register_actor.stall``
  raylet  — ``raylet.lease_grant.delay``
  worker  — ``worker.push_task.pre``, ``worker.actor_resolve.pre``

Arming surfaces exercised: in-process ``arm()``, the
``RAY_TPU_FAILPOINTS`` env var (inherited by the head/raylet/worker
subprocesses), and the internal-KV ``arm_cluster()`` path (adopted by
workers spawned after arming).
"""

import asyncio
import os
import time

import pytest

import ray_tpu
from ray_tpu._test_utils import wait_for_condition
from ray_tpu.core import rpc
from ray_tpu.util import failpoint as fp

pytestmark = pytest.mark.failpoints

SEED = 1234


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.disarm_all()
    yield
    fp.disarm_all()


# ---------------------------------------------------------------------------
# registry unit tests (no cluster)
# ---------------------------------------------------------------------------
def test_registry_deterministic_for_seed():
    """A probabilistic site replays the exact same fire pattern for the
    same seed — chaos runs are reproducible."""
    def pattern():
        fp.disarm_all()
        fp.arm("unit.prob", "drop", prob=0.5, count=-1, seed=SEED)
        return [fp.failpoint("unit.prob") for _ in range(64)]

    first, second = pattern(), pattern()
    assert first == second
    assert any(first) and not all(first)  # prob actually gates


def test_registry_count_and_skip():
    fp.arm("unit.count", "drop", count=2, skip=1)
    fired = [fp.failpoint("unit.count") for _ in range(5)]
    # one skipped evaluation, two fires, then dormant
    assert fired == [False, True, True, False, False]
    assert fp.fire_count("unit.count") == 2


def test_spec_parse_roundtrip():
    spec = ("rpc.push_tasks.reply_drop=drop:count=1;"
            "gcs.heartbeat.delay=delay:delay_s=2.0,count=3,seed=7")
    sites = fp.parse_spec(spec)
    assert set(sites) == {"rpc.push_tasks.reply_drop",
                          "gcs.heartbeat.delay"}
    assert sites["gcs.heartbeat.delay"].delay_s == 2.0
    assert sites["gcs.heartbeat.delay"].seed == 7
    reparsed = fp.parse_spec(fp.format_spec(sites))
    assert reparsed["rpc.push_tasks.reply_drop"].count == 1
    with pytest.raises(ValueError):
        fp.parse_spec("site=explode")


def test_raise_action_is_typed():
    fp.arm("unit.raise", "raise")
    with pytest.raises(fp.FailpointError) as ei:
        fp.failpoint("unit.raise")
    assert "unit.raise" in str(ei.value)


# ---------------------------------------------------------------------------
# rpc layer: retry/backoff policy against a live framed-RPC server
# ---------------------------------------------------------------------------
class _EchoService:
    async def handle_echo(self, conn, data):
        return {"echo": data["x"]}


def _run(coro):
    return asyncio.run(coro)


def test_rpc_retry_rides_out_dropped_replies():
    """An idempotent call whose replies are lost retries with backoff
    until a reply lands (graceful retry, not a hang)."""
    async def scenario():
        server = rpc.Server(_EchoService(), validate_schemas=False)
        addr = await server.start()
        pool = rpc.ConnectionPool()
        try:
            fp.arm("rpc.echo.reply_drop", "drop", count=2, seed=SEED)
            policy = rpc.RetryPolicy(max_attempts=5, base_delay_s=0.02,
                                     deadline_s=20.0)
            reply = await pool.call(addr, "echo", {"x": 41},
                                    timeout=0.5, policy=policy,
                                    idempotent=True)
            return reply
        finally:
            pool.close_all()
            await server.stop()

    assert _run(scenario()) == {"echo": 41}
    assert fp.fire_count("rpc.echo.reply_drop") == 2


def test_rpc_deadline_budget_is_typed_not_a_hang():
    """When every request frame is lost, the chain fails inside its
    deadline budget with RpcDeadlineExceeded — never an unbounded wait."""
    async def scenario():
        server = rpc.Server(_EchoService(), validate_schemas=False)
        addr = await server.start()
        pool = rpc.ConnectionPool()
        try:
            fp.arm("rpc.echo.request_drop", "drop", count=-1, seed=SEED)
            policy = rpc.RetryPolicy(max_attempts=4, base_delay_s=0.02,
                                     max_delay_s=0.1, deadline_s=2.0)
            t0 = time.monotonic()
            with pytest.raises(rpc.RpcDeadlineExceeded):
                await pool.call(addr, "echo", {"x": 1}, timeout=0.3,
                                policy=policy, idempotent=True)
            return time.monotonic() - t0
        finally:
            pool.close_all()
            await server.stop()

    assert _run(scenario()) < 10.0


def test_rpc_non_idempotent_never_blind_retries():
    """A mutating (non-idempotent) call fails on the FIRST lost reply
    instead of re-executing the callee."""
    async def scenario():
        server = rpc.Server(_EchoService(), validate_schemas=False)
        addr = await server.start()
        pool = rpc.ConnectionPool()
        try:
            fp.arm("rpc.echo.reply_drop", "drop", count=-1, seed=SEED)
            policy = rpc.RetryPolicy(max_attempts=5, base_delay_s=0.02,
                                     deadline_s=10.0)
            with pytest.raises(asyncio.TimeoutError):
                await pool.call(addr, "echo", {"x": 1}, timeout=0.3,
                                policy=policy, idempotent=False)
        finally:
            pool.close_all()
            await server.stop()

    _run(scenario())
    # exactly one handler execution: the classification refused a blind
    # second send
    assert fp.fire_count("rpc.echo.reply_drop") == 1


def test_backoff_grows_and_caps():
    import random

    policy = rpc.RetryPolicy(base_delay_s=0.1, multiplier=2.0,
                             max_delay_s=0.5, jitter=0.0)
    rng = random.Random(SEED)
    delays = [policy.backoff_delay(i, rng) for i in range(5)]
    assert delays == [0.1, 0.2, 0.4, 0.5, 0.5]
    assert rpc.is_idempotent("kv_get")
    assert rpc.is_idempotent("return_worker")
    assert not rpc.is_idempotent("push_tasks")
    assert not rpc.is_idempotent("request_worker_lease")


# ---------------------------------------------------------------------------
# live mini-cluster: driver-local armed sites (worker layer)
# ---------------------------------------------------------------------------
@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def test_worker_push_task_fault_redispatches(cluster):
    """An injected fault on the owner's task-push path consumes one
    retry and the task still completes (worker layer)."""
    fp.arm("worker.push_task.pre", "raise", count=1, seed=SEED)

    @ray_tpu.remote(num_cpus=0, max_retries=3)
    def f():
        return "ok"

    assert ray_tpu.get(f.remote(), timeout=60) == "ok"
    assert fp.fire_count("worker.push_task.pre") == 1


def test_worker_push_task_fault_exhausts_to_typed_error(cluster):
    """With no retry budget the same fault surfaces as the typed
    WorkerCrashedError — not a hang, not a silent success."""
    fp.arm("worker.push_task.pre", "raise", count=-1, seed=SEED)

    @ray_tpu.remote(num_cpus=0, max_retries=0)
    def f():
        return "ok"

    with pytest.raises(ray_tpu.WorkerCrashedError):
        ray_tpu.get(f.remote(), timeout=60)


def test_worker_actor_resolve_fault_retries(cluster):
    """An injected failure while resolving/connecting to an actor
    consumes one task retry; the call still lands (worker layer)."""
    @ray_tpu.remote(num_cpus=0, max_task_retries=3)
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    fp.arm("worker.actor_resolve.pre", "raise", count=1, seed=SEED)
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    assert fp.fire_count("worker.actor_resolve.pre") == 1


def test_arm_cluster_reaches_future_workers(cluster):
    """KV-armed sites are adopted by workers spawned after arming
    (max_calls=1 recycles the pool, forcing fresh spawns)."""
    fp.arm_cluster("rpc.push_task.handler_delay", "delay",
                   delay_s=0.3, count=2, seed=SEED)
    try:
        from ray_tpu.experimental.internal_kv import _internal_kv_get
        raw = _internal_kv_get(fp.KV_KEY, namespace=fp.KV_NAMESPACE)
        assert raw and b"rpc.push_task.handler_delay" in raw

        @ray_tpu.remote(num_cpus=1, max_calls=1)
        def f(i):
            return i

        # recycled workers force fresh spawns which sync from the KV;
        # delayed pushes must still complete (graceful slow-down only)
        out = ray_tpu.get([f.remote(i) for i in range(6)], timeout=120)
        assert out == list(range(6))
    finally:
        fp.disarm_cluster()


# ---------------------------------------------------------------------------
# live mini-cluster: env-armed sites in the head subprocess (gcs + raylet)
# ---------------------------------------------------------------------------
@pytest.fixture
def faulty_head_cluster():
    """Head (GCS + raylet) boots with control-plane delay sites armed
    via the inherited env var."""
    spec = (f"gcs.heartbeat.delay=delay:delay_s=1.5,count=2,seed={SEED};"
            f"raylet.lease_grant.delay=delay:delay_s=1.0,count=2,"
            f"seed={SEED};"
            f"gcs.register_actor.stall=delay:delay_s=1.0,count=1,"
            f"seed={SEED};"
            f"rpc.push_tasks.reply_drop=drop:count=1,seed={SEED}")
    os.environ["RAY_TPU_FAILPOINTS"] = spec
    fp.reload_env()
    try:
        ray_tpu.init(num_cpus=4)
        yield
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_FAILPOINTS", None)
        fp.reload_env()


def test_cluster_rides_out_gcs_and_raylet_stalls(faulty_head_cluster):
    """Stalled heartbeat acks (gcs layer), slow lease grants (raylet
    layer), a stalled actor registration (gcs layer), and one lost
    ``push_tasks`` final ack (rpc layer — results stream per task
    BEFORE the ack, so a dropped ack must lose nothing) only slow the
    cluster down: tasks and actors complete, and no node is falsely
    declared dead."""
    @ray_tpu.remote(num_cpus=0)
    def f(i):
        return i * 2

    @ray_tpu.remote(num_cpus=0)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    out = ray_tpu.get([f.remote(i) for i in range(8)], timeout=120)
    assert out == [i * 2 for i in range(8)]
    c = Counter.remote()  # registration rides out the injected stall
    assert ray_tpu.get(c.bump.remote(), timeout=120) == 1
    # the heartbeat delays (< health_timeout_s) must not kill the node
    nodes = ray_tpu.nodes()
    assert nodes and all(n["alive"] for n in nodes)


# ---------------------------------------------------------------------------
# regression (ADVICE high): rejected batch push must re-dispatch
# ---------------------------------------------------------------------------
@pytest.fixture
def rejecting_worker_cluster():
    """Cluster whose workers reject their first ``push_tasks`` batch
    with the exiting-worker reply (``worker.push_tasks.reject`` fires
    inside ``handle_push_tasks``), forcing the batch-rejection path
    deterministically — the production trigger (a batch racing the
    max_calls exit decision) is a sub-millisecond window.  A worker
    that rejects then exits, as the one it stands for does, so the
    raylet takes its lease back."""
    spec = f"worker.push_tasks.reject=drop:count=1,seed={SEED}"
    os.environ["RAY_TPU_FAILPOINTS"] = spec
    fp.reload_env()
    try:
        ray_tpu.init(num_cpus=4)
        yield
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_FAILPOINTS", None)
        fp.reload_env()


def test_rejected_batch_redispatches_elsewhere(rejecting_worker_cluster):
    """A worker that decided to exit rejects an in-flight task batch;
    the owner must re-dispatch every rejected task instead of stranding
    it (regression for the unassigned ``push_tasks`` reply: the
    rejected branch read an undefined ``reply``, the NameError was
    swallowed by the done-callback, and rejected batches hung their
    callers forever)."""
    @ray_tpu.remote(num_cpus=1)
    def g(i):
        return i + 100

    # a burst larger than the CPU count pipelines BATCHES onto the
    # granted workers; each worker rejects its first batch
    burst = [g.remote(i) for i in range(24)]
    out = ray_tpu.get(burst, timeout=90)
    assert out == [i + 100 for i in range(24)]
    # no rejecting worker kept its lease: four that did left the owner
    # with no CPU to re-dispatch onto (the 90 s timeouts of PR 25-30)
    # (the GCS learns a node's free resources from its health report,
    # one a second: let the burst's own reports arrive first)
    time.sleep(2.5)
    wait_for_condition(
        lambda: ray_tpu.available_resources().get("CPU") == 4.0)
