"""Incident forensics plane (ISSUE 20 / docs/observability.md
"Incidents and postmortems"): flight-ring crash consistency (torn
frames dropped, wrap-ordering, foreign files rejected), recorder
hot-path cost, the GCS incident journal (open/merge, death-tail
attach, collect_fail degrade, eviction cap, WAL survival across a
GCS SIGKILL+respawn), and the headline chaos case — a serve replica
SIGKILLed mid-request on a 2-node cluster yields one incident holding
the dead worker's flight tail (newest frame <1s before death), the
retained trace of the retried request, and the firing-alert linkage."""

import asyncio
import json
import os
import struct
import time
import urllib.request
import zlib

import pytest

import ray_tpu
from ray_tpu.core import flight_recorder as flt
from ray_tpu.core.config import Config
from ray_tpu._test_utils import wait_for_condition
from ray_tpu.util import failpoint as fp

SEED = 2020


# ---------------------------------------------------------------------------
# flight-ring units (no cluster)
# ---------------------------------------------------------------------------
def test_ring_roundtrip_orders_across_wrap(tmp_path):
    """Frames survive a file reopen seq-ordered even after the ring
    wraps several times — the reader sorts by seq, not file offset."""
    rec = flt.FlightRecorder("unit", str(tmp_path), ring_bytes=8192)
    total = rec.nframes * 3 + 5  # wrap ~3 times
    for i in range(total):
        rec.record("mark", f"frame-{i}")
    rec.close()

    out = flt.read_ring(rec.path)
    assert out is not None and out["torn"] == 0
    assert out["source"] == "unit" and out["pid"] == os.getpid()
    seqs = [fr["seq"] for fr in out["frames"]]
    assert seqs == sorted(seqs)
    # the newest nframes survive; everything older was overwritten
    assert seqs[-1] == total - 1
    assert out["frames"][-1]["detail"] == f"frame-{total - 1}"
    assert len(seqs) <= rec.nframes


def test_ring_torn_frame_truncated_not_fatal(tmp_path):
    """The crash-consistency contract: a frame corrupted mid-write
    (SIGKILL between the payload copy and a consistent CRC) is counted
    torn and DROPPED; every other frame still decodes.  'Loses at most
    one frame'."""
    rec = flt.FlightRecorder("unit", str(tmp_path), ring_bytes=8192)
    for i in range(10):
        rec.record("mark", f"frame-{i}")
    rec.close()

    # corrupt frame seq=4 mid-payload without updating its CRC
    hdr, fsize = flt._HDR.size, flt.FRAME_SIZE
    with open(rec.path, "r+b") as f:
        f.seek(hdr + 4 * fsize + flt._FRM.size + 2)
        f.write(b"\xff\xff\xff")

    out = flt.read_ring(rec.path)
    assert out["torn"] == 1
    details = [fr["detail"] for fr in out["frames"]]
    assert "frame-4" not in details
    assert details == [f"frame-{i}" for i in range(10) if i != 4]

    # a torn LENGTH field (dlen past the frame) is also just torn, not
    # an out-of-bounds read
    with open(rec.path, "r+b") as f:
        f.seek(hdr + 7 * fsize)
        crc_off = f.tell()
        blob = bytearray(f.read(fsize))
        struct.pack_into("<H", blob, flt._FRM.size - 2, 60000)
        struct.pack_into("<I", blob, 0, zlib.crc32(bytes(blob[4:])))
        f.seek(crc_off)
        f.write(bytes(blob))
    out2 = flt.read_ring(rec.path)
    assert out2["torn"] == 2
    assert "frame-7" not in [fr["detail"] for fr in out2["frames"]]


def test_ring_rejects_foreign_and_missing_files(tmp_path):
    bogus = tmp_path / "flight-x-1.ring"
    bogus.write_bytes(b"NOTARING" + b"\0" * 100)
    assert flt.read_ring(str(bogus)) is None
    assert flt.read_ring(str(tmp_path / "absent.ring")) is None
    short = tmp_path / "flight-y-2.ring"
    short.write_bytes(b"\x01\x02")
    assert flt.read_ring(str(short)) is None


def test_ring_undeclared_type_degrades_to_mark(tmp_path):
    """A writer passing a type outside EVENT_TYPES (version skew) must
    not corrupt the ring: the frame lands as 'mark' with the original
    type folded into the detail."""
    rec = flt.FlightRecorder("unit", str(tmp_path), ring_bytes=8192)
    rec.record("definitely_not_declared", "hello")  # noqa — on purpose
    rec.close()
    out = flt.read_ring(rec.path)
    assert out["frames"][-1]["type"] == "mark"
    assert "definitely_not_declared" in out["frames"][-1]["detail"]


def test_rings_for_pid_and_graceful_unlink(tmp_path):
    """Death-path discovery keys on the pid suffix; a graceful close
    unlinks the ring so a SURVIVING ring unambiguously means crash."""
    rec = flt.FlightRecorder("unit", str(tmp_path), ring_bytes=8192)
    rec.record("mark", "alive")
    pid = os.getpid()
    assert flt.rings_for_pid(str(tmp_path), pid) == [rec.path]
    assert flt.rings_for_pid(str(tmp_path), pid + 1) == []
    rec.close(unlink=True)
    assert flt.rings_for_pid(str(tmp_path), pid) == []
    # crash path: a second recorder closed WITHOUT unlink stays behind
    rec2 = flt.FlightRecorder("unit", str(tmp_path), ring_bytes=8192)
    rec2.record("mark", "crashing")
    rec2.close(unlink=False)
    assert flt.rings_for_pid(str(tmp_path), pid) == [rec2.path]


def test_recorder_overhead_and_disabled_noop(tmp_path):
    """The hot-path bars: record() through the module facade with NO
    recorder is nanoseconds (one None test), and an enabled record stays
    in single-digit microseconds — cheap enough for task_start/finish
    on every task."""
    saved = flt._recorder
    try:
        flt._recorder = None
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            flt.record("mark", "off")
        off_us = (time.perf_counter() - t0) / n * 1e6
        assert off_us < 2.0, f"disabled record costs {off_us:.2f}us"

        flt._recorder = flt.FlightRecorder("unit", str(tmp_path),
                                           ring_bytes=1 << 16)
        t0 = time.perf_counter()
        for i in range(n):
            flt.record("mark", f"on-{i}")
        on_us = (time.perf_counter() - t0) / n * 1e6
        # generous CI bar; typical is ~1-2us
        assert on_us < 50.0, f"enabled record costs {on_us:.2f}us"
        assert flt.stats()["frames_recorded"] == n
        flt._recorder.close(unlink=True)
    finally:
        flt._recorder = saved


# ---------------------------------------------------------------------------
# GCS incident-journal units (GcsServer outside a cluster)
# ---------------------------------------------------------------------------
def _mk_gcs(tmp_path, **cfg):
    from ray_tpu.core.gcs import GcsServer

    config = Config().apply_overrides(cfg)
    return GcsServer(config, snapshot_path=str(tmp_path / "snap.pkl"),
                     session_dir=str(tmp_path))


def _tail(pid=4242, nframes=3, source="worker"):
    now = time.time()
    return {
        "source": source, "pid": pid, "reason": "exit code -9",
        "torn": 1,
        "frames": [{"seq": i, "ts": now - (nframes - i) * 0.01,
                    "type": "task_start", "detail": f"f{i}"}
                   for i in range(nframes)],
    }


def test_report_flight_tail_opens_incident(tmp_path):
    g = _mk_gcs(tmp_path)

    async def report():
        out = await g.handle_report_flight_tail(None, _tail())
        return out
    out = asyncio.run(report())
    inc_id = out["incident_id"]
    assert inc_id in g._incidents
    inc = g._incidents[inc_id]
    assert inc["kind"] == "death" and inc["state"] == "open"
    assert not inc["partial"]
    [death] = inc["deaths"]
    assert death["pid"] == 4242 and death["source"] == "worker"
    assert len(death["frames"]) == 3 and death["torn"] == 1
    # the evidence window opens BEFORE the death
    assert inc["window"][0] < inc["opened_at"]

    # list/get handlers: newest first, prefix lookup
    rows = asyncio.run(g.handle_list_incidents(None, {}))
    assert rows[0]["id"] == inc_id and rows[0]["n_deaths"] == 1
    got = asyncio.run(g.handle_get_incident(
        None, {"incident_id": inc_id[:7]}))
    assert got["id"] == inc_id
    assert asyncio.run(g.handle_get_incident(
        None, {"incident_id": "inc-nope"})) is None


def test_deaths_merge_into_one_episode(tmp_path):
    """Two deaths inside incident_window_s are ONE incident (a gang
    death is one episode, not N pages); the same pid reported twice
    (raylet ship + node-death path racing) dedupes."""
    g = _mk_gcs(tmp_path)

    async def report():
        a = await g.handle_report_flight_tail(None, _tail(pid=1))
        b = await g.handle_report_flight_tail(None, _tail(pid=2))
        c = await g.handle_report_flight_tail(None, _tail(pid=2))
        return a, b, c
    a, b, c = asyncio.run(report())
    assert a["incident_id"] == b["incident_id"] == c["incident_id"]
    inc = g._incidents[a["incident_id"]]
    assert [d["pid"] for d in inc["deaths"]] == [1, 2]

    # outside the window: a fresh incident opens
    inc["last_update"] -= 1000.0
    out = asyncio.run(g.handle_report_flight_tail(None, _tail(pid=3)))
    assert out["incident_id"] != a["incident_id"]
    assert len(g._incidents) == 2


def test_collect_fail_failpoint_degrades_to_partial(tmp_path):
    """gcs.incident.collect_fail (docs/fault_injection.md): the tail is
    lost mid-death-notification but the incident STILL opens with the
    death entry — tail collection never wedges the death path."""
    g = _mk_gcs(tmp_path)
    fp.arm("gcs.incident.collect_fail", "drop", count=1, seed=SEED)
    try:
        out = asyncio.run(g.handle_report_flight_tail(None, _tail()))
    finally:
        fp.disarm_all()
    inc = g._incidents[out["incident_id"]]
    assert inc["partial"] is True
    [death] = inc["deaths"]
    assert death["frames"] == [] and death["partial"] is True
    assert death["pid"] == 4242 and death["reason"] == "exit code -9"


def test_incident_table_eviction_cap(tmp_path):
    g = _mk_gcs(tmp_path, incident_table_size=4, incident_window_s=0.0)

    async def report(pid):
        await g.handle_report_flight_tail(None, _tail(pid=pid))
    for pid in range(10, 18):
        asyncio.run(report(pid))
        time.sleep(0.002)  # window_s=0: every report opens fresh
    assert len(g._incidents) == 4
    pids = [i["deaths"][0]["pid"] for i in g._incidents.values()]
    assert pids == [14, 15, 16, 17]  # oldest evicted first


def test_incidents_survive_gcs_sigkill_and_respawn(tmp_path):
    """The acceptance bar: incidents persist via the WAL.  An acked
    report with NO snapshot flush (SIGKILL inside the debounce window)
    replays on respawn with tails, state, and links intact; the
    collected state re-WALed later also converges (full-value set)."""
    g = _mk_gcs(tmp_path)

    async def report():
        out = await g.handle_report_flight_tail(None, _tail())
        await g._wal_flush()
        return out["incident_id"]
    inc_id = asyncio.run(report())
    # no _persist_now(): the snapshot never saw this incident
    g2 = _mk_gcs(tmp_path)
    assert inc_id in g2._incidents
    inc = g2._incidents[inc_id]
    assert inc["state"] == "open"
    assert inc["deaths"][0]["frames"][-1]["detail"] == "f2"

    # collected links re-WAL as a full value: the replay converges on
    # the newest write, not the open-state one
    async def collect_and_flush():
        await g2._collect_incident(inc_id)
        await g2._wal_flush()
    asyncio.run(collect_and_flush())
    assert g2._incidents[inc_id]["state"] == "collected"
    g3 = _mk_gcs(tmp_path)
    assert g3._incidents[inc_id]["state"] == "collected"
    assert "trace_ids" in g3._incidents[inc_id]["links"]
    # the journal surfaces in healthz for `ray-tpu status`
    hz = asyncio.run(g3.handle_healthz(None, None))
    assert hz["incidents"] == 1 and hz["last_incident"] == inc_id


# ---------------------------------------------------------------------------
# headline chaos (make chaos): serve replica SIGKILLed mid-request on a
# 2-node cluster -> one incident with the dead worker's flight tail,
# the retained retried trace, and the firing-alert linkage
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.failpoints
def test_replica_sigkill_postmortem_completeness():
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.exceptions import ActorDiedError
    from ray_tpu.core.worker import global_worker
    from ray_tpu.serve.http_proxy import start_proxy
    from ray_tpu.serve.toy_decoder import ToyDecoder, make_prompt

    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 3},
                _system_config={
                    "metrics_report_period_s": 0.5,
                    "metrics_history_interval_s": 0.5,
                    # every request misses the 1ms SLO, so the burn
                    # alert fires DURING the incident window — the
                    # linkage under test
                    "serve_slo_latency_s": 0.001,
                    "serve_slo_error_budget": 0.01,
                })
    try:
        c.add_node(num_cpus=3)
        c.connect()
        c.wait_for_nodes()

        @serve.deployment(num_replicas=2, max_concurrent_queries=8,
                          ray_actor_options={
                              "scheduling_strategy": "SPREAD"},
                          batching={"max_batch_size": 2,
                                    "max_seq_len": 32})
        class Echo(ToyDecoder):
            def __init__(self):
                super().__init__(step_delay_s=0.01)

        serve.run(Echo.bind())
        from ray_tpu.serve._internal import CONTROLLER_NAME
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        table = ray_tpu.get(
            controller.get_routing_table.remote(-1, 1.0), timeout=30)
        replicas = table["table"]["Echo"]["replicas"]
        nodes = [ray_tpu.get(r.node_id.remote(), timeout=30)
                 for r in replicas]
        assert len(set(nodes)) == 2, "replicas must spread across nodes"

        host, port = start_proxy()
        proxy = ray_tpu.get_actor("SERVE_HTTP_PROXY")
        proxy_node = ray_tpu.get(proxy.node_id.remote(), timeout=30)
        doomed_idx = nodes.index(proxy_node) \
            if proxy_node in nodes else 0
        doomed = replicas[doomed_idx]
        ray_tpu.get(doomed.arm_failpoint.remote(
            "serve.replica.handle_request", "kill"), timeout=30)

        def post(i):
            payload = {"prompt": make_prompt(i, 4), "max_new_tokens": 3}
            req = urllib.request.Request(
                f"http://{host}:{port}/Echo",
                data=json.dumps(payload).encode())
            return json.loads(
                urllib.request.urlopen(req, timeout=90).read())

        killed_at = None
        for i in range(10):
            assert "result" in post(i)  # client always answered
            try:
                ray_tpu.get(doomed.ready.remote(), timeout=5)
            except (ActorDiedError, Exception):
                killed_at = time.time()
                break
        assert killed_at is not None, "armed replica never hit"
        # keep traffic flowing: the SLO burn must SUSTAIN past for_s
        for i in range(10, 24):
            assert "result" in post(i)

        w = global_worker()

        def retried_rows():
            return [r for r in w.gcs_call(
                        "list_traces", {"deployment": "Echo",
                                        "limit": 50})
                    if r.get("retried")]

        def burn_firing():
            return [a for a in w.gcs_call("get_alerts", {})["firing"]
                    if a["rule"] == "ServeSLOBurnRate"]

        def death_incident():
            for row in w.gcs_call("list_incidents", {}):
                if row["kind"] == "death" and row["n_deaths"]:
                    return w.gcs_call("get_incident",
                                      {"incident_id": row["id"]})
            return None

        # each plane assembles on its own cadence; wait for all three
        wait_for_condition(lambda: bool(retried_rows()), timeout=60)
        wait_for_condition(lambda: bool(burn_firing()), timeout=60)
        wait_for_condition(lambda: death_incident() is not None,
                           timeout=60)
        # the planes are populated NOW — merge one synthetic event into
        # the episode so link collection re-runs and snapshots them
        w.gcs_call("report_flight_tail", {
            "source": "chaos-probe", "pid": 1,
            "reason": "re-collect after planes settled",
            "frames": [{"seq": 0, "ts": time.time(), "type": "mark",
                        "detail": "probe"}], "torn": 0})

        def collected():
            inc = death_incident()
            return inc is not None and inc["state"] == "collected" \
                and (inc.get("links") or {}).get("traces") \
                and inc["alerts"]
        wait_for_condition(collected, timeout=60)
        inc = death_incident()

        # 1) the dead worker's flight tail, frames <1s before death
        tails = [d for d in inc["deaths"]
                 if d["source"] == "worker" and d["frames"]]
        assert tails, f"no worker flight tail in {inc['deaths']}"
        frames = tails[0]["frames"]
        gap = tails[0]["ts"] - frames[-1]["ts"]
        assert gap < 1.0, f"newest frame {gap:.2f}s before death"
        assert any(fr["type"] in ("task_start", "batch_step", "span")
                   for fr in frames), frames
        assert inc["nodes"], "death entry did not tag its node"

        # 2) the retried request's trace is retained AND linked
        linked = inc["links"]["traces"]
        assert any(r.get("retried") for r in linked), linked
        assert inc["links"]["trace_ids"]

        # 3) firing-alert linkage: the burn transition merged into the
        # episode and the still-firing set was snapshotted
        assert any(t["rule"] == "ServeSLOBurnRate"
                   for t in inc["alerts"]), inc["alerts"]
        assert any(a["rule"] == "ServeSLOBurnRate"
                   for a in inc["links"]["alerts_firing"])
        # severity escalated: the burn rule is critical
        assert inc["severity"] == "error"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        c.shutdown()
