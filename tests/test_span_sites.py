"""``telemetry.span()``: one span source on two clocks, the span sites of
the training path, and the timeline a session leaves behind.

All on the CPU: a duration read here is checked against another clock,
never reported as a speed.
"""

import glob
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import telemetry
from ray_tpu.experimental.state import api as state_api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_spans():
    # a test that ran a cluster earlier in this worker process has left
    # its offset against that cluster's clock (a millisecond either way),
    # and which files share a worker changes with every file added:
    # these spans are read on this process's own clock
    was = telemetry.clock_offset()
    telemetry.set_clock_offset(0.0)
    telemetry.drain_spans("test")
    yield
    telemetry.drain_spans("test")
    telemetry.set_clock_offset(was)


def _by_name(rows):
    return {r["name"]: r for r in rows}


# ---------------------------------------------------------------------------
# (a) the context manager
# ---------------------------------------------------------------------------

def test_span_nests_with_parent_thread_args_and_wall_clock(clean_spans):
    t0 = time.time()
    with telemetry.span("layer", "outer", k=1) as outer:
        with telemetry.span("layer", "inner"):
            time.sleep(0.01)
        with telemetry.span("layer", "second"):
            pass
        outer.args["bytes"] = 7  # a count known only at the end
    with telemetry.span("layer", "after"):
        pass
    t1 = time.time()
    rows = _by_name(telemetry.drain_spans("test"))
    outer, inner = rows["outer"], rows["inner"]
    assert outer["parent"] is None and rows["after"]["parent"] is None
    assert inner["parent"] == outer["id"] == rows["second"]["parent"]
    assert len({r["id"] for r in rows.values()}) == 4
    assert outer["args"] == {"k": 1, "bytes": 7}
    for row in rows.values():
        assert row["tid"] == threading.get_native_id()
        assert row["pid"] == os.getpid() and row["cat"] == "layer"
        assert t0 <= row["start"] <= row["end"] <= t1
        assert row["source"] == "test"
    assert outer["start"] <= inner["start"] and inner["end"] <= outer["end"]
    assert inner["end"] - inner["start"] >= 0.01
    # self time: the duration minus the children's
    children = sum(r["end"] - r["start"] for r in rows.values()
                   if r["parent"] == outer["id"])
    assert 0 <= (outer["end"] - outer["start"]) - children < 0.01


def test_span_parent_is_per_thread_and_survives_an_exception(clean_spans):
    def other():
        with telemetry.span("layer", "elsewhere"):
            pass

    with pytest.raises(KeyError):
        with telemetry.span("layer", "failing"):
            thread = threading.Thread(target=other)
            thread.start()
            thread.join(timeout=10)
            assert not thread.is_alive()
            raise KeyError("x")
    with telemetry.span("layer", "next"):
        pass
    rows = _by_name(telemetry.drain_spans("test"))
    assert rows["elsewhere"]["parent"] is None  # another thread's stack
    assert rows["elsewhere"]["tid"] != rows["failing"]["tid"]
    assert rows["next"]["parent"] is None       # the stack was unwound


@pytest.mark.parametrize("lowered", [False, True])
def test_span_under_its_min_s_is_not_buffered(clean_spans, lowered):
    """A site that runs once a task keeps only the spans that matter."""
    with telemetry.span("layer", "fast", min_s=5.0, k=1) as sp:
        with telemetry.span("layer", "child"):
            pass
        if lowered:  # the body found out that this one matters
            sp.min_s = 0.0
    with telemetry.span("layer", "slow", min_s=0.005):
        time.sleep(0.01)
    with telemetry.span("layer", "after"):
        pass
    rows = _by_name(telemetry.drain_spans("test"))
    assert set(rows) == {"child", "slow", "after"} | (
        {"fast"} if lowered else set())
    assert "min_s" not in rows["slow"]["args"]
    assert rows["after"]["parent"] is None  # dropped or not, it unwound


@pytest.mark.parametrize("enabled", ["1", "0"])
def test_span_without_jax_buffers_and_imports_nothing(enabled):
    """GCS, raylet and a driver that owns no chip use the same span():
    it must not be the thing that imports jax."""
    code = textwrap.dedent("""
        import sys
        from ray_tpu.core import telemetry
        assert "jax" not in sys.modules
        with telemetry.span("layer", "outer", n=1):
            with telemetry.span("layer", "inner"):
                pass
        telemetry.record_span("layer", "late", 1.0, 2.0)
        assert "jax" not in sys.modules and "jaxlib" not in sys.modules
        rows = telemetry.drain_spans("t")
        print(sorted(r["name"] for r in rows))
    """)
    env = dict(os.environ, PYTHONPATH=REPO,
               RAY_TPU_METRICS_ENABLED=enabled)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = ["inner", "late", "outer"] if enabled == "1" else []
    assert proc.stdout.strip() == str(want)


# ---------------------------------------------------------------------------
# (b) the same span in the profiler's file
# ---------------------------------------------------------------------------

def test_span_lands_in_the_profilers_file_on_its_clock(tmp_path,
                                                       clean_spans):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jnp.zeros(3).block_until_ready()
    with telemetry.span("layer", "before_session"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("layer", "outer", k=3):
            time.sleep(0.02)
            with telemetry.span("layer", "inner"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    rows = _by_name(telemetry.drain_spans("test"))
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ray_tpu:"):
                        events[e.name] = e
    # opened outside the session: buffered, but not in the file
    assert set(events) == {"ray_tpu:layer:outer", "ray_tpu:layer:inner"}
    assert "before_session" in rows
    offsets = []
    for name in ("outer", "inner"):
        e, row = events[f"ray_tpu:layer:{name}"], rows[name]
        assert abs(e.duration_ns / 1e9 - (row["end"] - row["start"])) < 1e-3
        offsets.append(row["start"] - e.start_ns / 1e9)
    # one offset maps the file's clock onto the wall clock
    assert abs(offsets[0] - offsets[1]) < 1e-3
    assert dict(events["ray_tpu:layer:outer"].stats)["k"] == 3


# ---------------------------------------------------------------------------
# (c) the training path, read after shutdown()
# ---------------------------------------------------------------------------

def _loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import Checkpoint, session

    @jax.jit
    def tiny_step(x):
        return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((32, 32))
    w = jnp.ones((2048, 2048))  # 16 MB: its reply goes through the store
    for i in range(2):
        session.report({"step": i, "loss": float(tiny_step(x))},
                       checkpoint=Checkpoint.from_pytree({"w": w}))
        time.sleep(0.2)
    # a pytree's payload rides out of band, so serialising its reply is
    # too quick to leave a row; ``bytes`` in a dict checkpoint are pickled
    # in band, and 32 MB of them take milliseconds
    session.report({"step": 2}, checkpoint=Checkpoint.from_dict(
        {"blob": b"x" * (32 << 20)}))
    time.sleep(0.2)


@pytest.fixture(scope="module")
def ended_run(tmp_path_factory):
    """One one-worker CPU fit() whose loop reports two pytree checkpoints
    and a dict one, then shutdown(): what is left is read by the tests
    below."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                 _system_config={
                     # a checkpoint's reply goes through the store, not inline
                     "max_direct_call_object_size": 1024})
    session_dir = ray_tpu.connection_info()["session_dir"]
    try:
        result = JaxTrainer(
            _loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(storage_path=str(
                tmp_path_factory.mktemp("ckpts")))).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None
    assert not ray_tpu.is_initialized()
    return {"timeline": ray_tpu.timeline(), "session_dir": session_dir}


def _spans(ended_run, cat, name):
    return [e for e in ended_run["timeline"]
            if e["cat"] == cat and e["name"] == name]


@pytest.mark.parametrize("cat,name,args", [
    ("lease", "spawn", {"pid", "tpu"}),
    ("worker", "boot", {"imports_ms", "connect_ms"}),
    ("train", "gang.place", {"bundles", "tpu"}),
    ("train", "gang.spawn", {"workers"}),
    ("train", "chip_open", {"backend", "devices"}),
    ("train", "gang.run", set()),
    ("xla", "trace", {"fun_name"}),
    ("xla", "lower", {"fun_name"}),
    ("xla", "backend_compile", {"fun_name"}),
    ("train", "ckpt.from_pytree", {"ckpt", "bytes", "leaves", "d2h_ms",
                                   "encode_ms", "copies", "pieces"}),
    ("train", "ckpt.d2h", {"ckpt"}),
    ("train", "ckpt.encode", {"ckpt"}),
    ("train", "report", {"ckpt"}),
    ("train", "next_results", {"results", "ckpts", "waited_s"}),
    ("worker", "reply", {"fn", "bytes", "path"}),
    ("worker", "reply.serialize", set()),
    ("worker", "reply.store", {"bytes"}),
    ("worker", "get.deserialize", {"bytes"}),
    ("train", "poll", {"results", "ckpts"}),
    ("train", "ckpt.register", {"ckpt", "bytes", "path"}),
])
def test_training_path_span_is_in_the_timeline_after_shutdown(
        ended_run, cat, name, args):
    found = _spans(ended_run, cat, name)
    assert found, f"no {cat}:{name} in the ended run's timeline"
    for e in found:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert args <= set(e["args"]), (e["name"], e["args"])
        assert isinstance(e["args"]["os_pid"], int)


def test_one_ckpt_id_joins_each_save_across_processes(ended_run):
    saves = [e["args"]["ckpt"]
             for e in _spans(ended_run, "train", "ckpt.from_pytree")]
    assert len(saves) == len(set(saves)) == 2
    for ckpt in saves:
        t = {}
        for name in ("ckpt.from_pytree", "report", "ckpt.register"):
            (t[name],) = [e for e in _spans(ended_run, "train", name)
                          if e["args"]["ckpt"] == ckpt]
        for name in ("next_results", "poll"):
            (t[name],) = [e for e in _spans(ended_run, "train", name)
                          if ckpt in e["args"]["ckpts"]]
        order = ["ckpt.from_pytree", "report", "next_results",
                 "ckpt.register"]
        starts = [t[n]["ts"] for n in order]
        assert starts == sorted(starts)
        end = lambda e: e["ts"] + e["dur"]  # noqa: E731
        assert end(t["poll"]) <= t["ckpt.register"]["ts"]
        # worker side and driver side are different processes
        assert t["report"]["args"]["os_pid"] != t["poll"]["args"]["os_pid"]


def test_from_pytree_names_its_two_children_and_its_copies(ended_run):
    for top in _spans(ended_run, "train", "ckpt.from_pytree"):
        kids = {e["name"]: e for e in ended_run["timeline"]
                if e["cat"] == "train"
                and e["args"].get("parent_id") == top["args"]["span_id"]
                and e["args"]["os_pid"] == top["args"]["os_pid"]}
        assert set(kids) == {"ckpt.d2h", "ckpt.encode"}
        assert kids["ckpt.d2h"]["ts"] <= kids["ckpt.encode"]["ts"]
        for arg, name in (("d2h_ms", "ckpt.d2h"),
                          ("encode_ms", "ckpt.encode")):
            assert kids[name]["args"]["ckpt"] == top["args"]["ckpt"]
            assert abs(top["args"][arg] - kids[name]["dur"] / 1e3) < 20
        # array bytes copied on the host over the tree's: a whole leaf of
        # the CPU backend is the device's own buffer, so it is copied (a
        # leaf the transfer left on the host is carried: 0.0 on a chip,
        # tests/test_checkpoint_encode.py)
        assert top["args"]["copies"] == 1.0
        # the array pieces of the payload, each a buffer of the reply
        assert top["args"]["pieces"] == top["args"]["leaves"] == 1
        assert top["args"]["bytes"] > 2048 * 2048 * 4
        # the two are all of it
        assert top["args"]["d2h_ms"] + top["args"]["encode_ms"] \
            <= top["dur"] / 1e3 + 1


def test_reply_children_nest_and_fast_inline_replies_leave_no_row(
        ended_run):
    replies = _spans(ended_run, "worker", "reply")
    assert "plasma" in {e["args"]["path"] for e in replies}
    # a reply a task: only the stored and the slow ones are kept, the
    # polls that carried nothing are in their task_exec rows alone
    assert all(e["dur"] >= 1e3 for e in replies
               if e["args"]["path"] == "inline")
    polls = [e for e in ended_run["timeline"]
             if e["cat"] == "task_exec" and e["name"] == "next_results"]
    assert len(polls) > sum(1 for e in replies
                            if e["args"]["fn"] == "next_results")
    by_id = {(e["args"]["os_pid"], e["args"]["span_id"]): e
             for e in replies}
    for child in (_spans(ended_run, "worker", "reply.serialize")
                  + _spans(ended_run, "worker", "reply.store")):
        parent = by_id[(child["args"]["os_pid"],
                        child["args"]["parent_id"])]
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1
        if child["name"] == "reply.store":
            assert parent["args"]["path"] == "plasma"


def test_xla_rows_name_the_jitted_function(ended_run):
    names = {name: {e["args"]["fun_name"]
                    for e in _spans(ended_run, "xla", name)}
             for name in ("trace", "lower", "backend_compile")}
    assert "tiny_step" in names["trace"]
    assert "jit(tiny_step)" in names["lower"]
    assert "jit(tiny_step)" in names["backend_compile"]


def test_timeline_file_is_in_the_session_directory(ended_run):
    import json

    path = os.path.join(ended_run["session_dir"], "timeline.json")
    with open(path) as f:
        assert json.load(f) == ended_run["timeline"]


# ---------------------------------------------------------------------------
# (d) nothing to read, nobody to ask
# ---------------------------------------------------------------------------

def test_timeline_with_no_session_file_is_empty(monkeypatch, tmp_path):
    ray_tpu.shutdown()
    monkeypatch.setattr(state_api, "_last_timeline_path", None)
    assert ray_tpu.timeline() == []
    monkeypatch.setattr(state_api, "_last_timeline_path",
                        str(tmp_path / "gone.json"))
    out = tmp_path / "copy.json"
    assert ray_tpu.timeline(str(out)) == []
    assert out.read_text() == "[]"


@pytest.mark.parametrize("delivered", [True, False])
def test_a_span_batch_whose_report_failed_is_sent_again_and_kept_once(
        monkeypatch, delivered):
    """A flush that times out while the io loop is held up (a 3 GB reply
    being stored) must not cost the spans it had drained, and a delivery
    whose acknowledgement was lost must not be appended twice."""
    import asyncio

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    try:
        core = ray_tpu._worker_mod.global_worker()
        core.flush_telemetry()
        name = f"kept-{delivered}"
        with telemetry.span("layer", name):
            pass
        conn, failed = core.gcs_conn, []
        real = conn.call

        async def flaky(method, data=None, **kw):
            if method == "report_spans" and not failed:
                failed.append(data["seq"])
                if delivered:
                    await real(method, data, **kw)
                raise asyncio.TimeoutError
            return await real(method, data, **kw)

        monkeypatch.setattr(conn, "call", flaky)
        core.flush_telemetry()       # fails, silently
        assert failed and core._unsent_spans[0] == failed[0]
        with telemetry.span("layer", name + "-later"):
            pass
        core.flush_telemetry()       # the same batch again, then the new
        assert core._unsent_spans is None
        names = [s["name"] for s in state_api.list_spans(cat="layer")]
        assert names.count(name) == 1 and names.count(name + "-later") == 1
    finally:
        ray_tpu.shutdown()


def test_two_flushes_at_once_lose_no_batch(monkeypatch):
    """The flush loop's tick and a flush_telemetry() take turns: the one
    that resends must not forget the batch the other drained."""
    import asyncio

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    try:
        core = ray_tpu._worker_mod.global_worker()
        core.flush_telemetry()
        conn, in_flight = core.gcs_conn, []
        real = conn.call

        async def slow(method, data=None, **kw):
            if method == "report_spans":
                in_flight.append(len(in_flight) + 1)
                assert in_flight[-1] == 1, "two flushes overlapped"
                await asyncio.sleep(0.3)
                reply = await real(method, data, **kw)
                in_flight.pop()
                return reply
            return await real(method, data, **kw)

        monkeypatch.setattr(conn, "call", slow)
        with telemetry.span("layer", "first-batch"):
            pass
        first = threading.Thread(target=core.flush_telemetry)
        first.start()
        time.sleep(0.1)              # the first is inside its report
        with telemetry.span("layer", "second-batch"):
            pass
        core.flush_telemetry()
        first.join(timeout=10)
        assert not first.is_alive() and core._unsent_spans is None
        names = [s["name"] for s in state_api.list_spans(cat="layer")]
        assert names.count("first-batch") == 1
        assert names.count("second-batch") == 1
    finally:
        ray_tpu.shutdown()


def test_leaving_the_timeline_gives_up_on_an_unreachable_gcs():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    head = ray_tpu._head_proc
    try:
        with telemetry.span("layer", "pending"):
            pass
        os.kill(head.pid, signal.SIGSTOP)  # reachable, and never answers
        core = ray_tpu._worker_mod.global_worker()
        t0 = time.monotonic()
        state_api.leave_timeline(core, timeout=1.0)
        assert time.monotonic() - t0 < 3.0
        assert state_api._last_timeline_path is None
    finally:
        os.kill(head.pid, signal.SIGCONT)
        ray_tpu.shutdown()
    assert not ray_tpu.is_initialized()
