"""The worker-spawn / device-selection seam, as far as the CPU can show it.

A worker spawned for a ``TPU`` lease gets the driver's own platform
selection back, the compile-cache variable, and libtpu narrowed to the
chips it leased — all before its interpreter starts; every other worker
stays pinned to the CPU.  The chip itself is ``chip_smoke.py``'s job.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import node

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAST_LINE_KEYS = ({"ok", "device"}, {"platform", "kind", "count"})


# ---------------------------------------------------------------------------
# the environment of a spawned worker (pure functions of the raylet's env)
# ---------------------------------------------------------------------------

def test_tpu_worker_gets_the_drivers_platform_selection_back():
    env = {"JAX_PLATFORMS": "cpu", "RAY_TPU_STASH_JAX_PLATFORMS": "tpu,cpu"}
    node.tpu_worker_env(env, (0,), host_chips=1)
    assert env["JAX_PLATFORMS"] == "tpu,cpu"
    assert "RAY_TPU_STASH_JAX_PLATFORMS" not in env


def test_tpu_worker_of_a_driver_with_no_selection_gets_none():
    env = {"JAX_PLATFORMS": "cpu"}  # the daemon's pin, nothing stashed
    node.tpu_worker_env(env, (0,), host_chips=1)
    assert "JAX_PLATFORMS" not in env


def test_plain_worker_stays_on_the_cpu():
    env = {"RAY_TPU_STASH_JAX_PLATFORMS": "tpu,cpu"}
    node.plain_worker_env(env)
    assert env == {"JAX_PLATFORMS": "cpu"}


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout():
    env = {"RAY_TPU_STASH_JAX_PLATFORMS": "tpu"}
    node.tpu_worker_env(env, (0,), host_chips=1)
    assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(REPO, ".jax_cache")


def test_compile_cache_placed_from_outside_is_left_alone():
    env = {"RAY_TPU_STASH_JAX_PLATFORMS": "tpu",
           "JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    node.tpu_worker_env(env, (0,), host_chips=1)
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"


@pytest.mark.parametrize("chips,host,visible,bounds", [
    ((2,), 4, "2", "1,1,1"),
    ((1, 3), 4, "1,3", "1,2,1"),
    ((0, 1, 2, 3), 4, None, None),   # the whole host: libtpu's defaults
    ((0,), 1, None, None),
])
def test_worker_sees_exactly_the_chips_it_leased(chips, host, visible, bounds):
    env = {"TPU_VISIBLE_CHIPS": "7", "TPU_PROCESS_BOUNDS": "9,9,9"}  # stale
    node.tpu_worker_env(env, chips, host_chips=host)
    assert env.get("TPU_VISIBLE_CHIPS") == visible
    assert env.get("TPU_CHIPS_PER_PROCESS_BOUNDS") == bounds
    assert env.get("TPU_PROCESS_BOUNDS") == ("1,1,1" if visible else None)


def test_chips_are_counted_from_device_nodes_not_topology_variables(
        monkeypatch):
    # the one-chip machine: the variables name the full 2x2 host
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    monkeypatch.setattr(node.glob, "glob", lambda pat: [])
    monkeypatch.setattr(node.os, "listdir", lambda d: ["2", "vfio"])
    assert node.detect_tpu_resources() == {"TPU": 1.0}
    monkeypatch.setattr(node.glob, "glob",
                        lambda pat: ["/dev/accel0", "/dev/accel1"])
    assert node.detect_tpu_resources() == {"TPU": 2.0}
    monkeypatch.setenv("RAY_TPU_CHIPS", "0")
    assert node.detect_tpu_resources() == {}


# ---------------------------------------------------------------------------
# the same, through a real raylet
# ---------------------------------------------------------------------------

def _worker_env():
    keys = ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "TPU_VISIBLE_CHIPS",
            "TPU_CHIPS_PER_PROCESS_BOUNDS", "RAY_TPU_STASH_JAX_PLATFORMS")
    return {"pid": os.getpid(), "ids": ray_tpu.get_tpu_ids(),
            **{k: os.environ.get(k) for k in keys}}


def test_leases_bind_workers_to_disjoint_visible_chips(shutdown_only):
    ray_tpu.init(num_cpus=4, resources={"TPU": 4})

    @ray_tpu.remote(num_tpus=1)
    class OneChip:
        def env(self):
            return _worker_env()

    a, b = OneChip.remote(), OneChip.remote()
    ea, eb = ray_tpu.get([a.env.remote(), b.env.remote()], timeout=120)
    for e in (ea, eb):
        # conftest's driver runs with JAX_PLATFORMS=cpu: that is restored
        assert e["JAX_PLATFORMS"] == "cpu"
        assert e["RAY_TPU_STASH_JAX_PLATFORMS"] is None
        assert e["TPU_VISIBLE_CHIPS"] == str(e["ids"][0])
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert ea["ids"] != eb["ids"] and ea["pid"] != eb["pid"]

    two = ray_tpu.get(
        ray_tpu.remote(num_tpus=2)(_worker_env).remote(), timeout=120)
    assert len(two["ids"]) == 2
    assert two["TPU_VISIBLE_CHIPS"] == ",".join(map(str, two["ids"]))
    assert not set(two["ids"]) & {ea["ids"][0], eb["ids"][0]}

    plain = ray_tpu.get(ray_tpu.remote(_worker_env).remote(), timeout=60)
    assert plain["JAX_PLATFORMS"] == "cpu" and plain["ids"] == []
    assert plain["TPU_VISIBLE_CHIPS"] is None


_UNPINNED_DRIVER = """
import json, os, sys
import ray_tpu
ray_tpu.init(num_cpus=2, resources={"TPU": 1},
             _system_config={"log_to_driver": False})

def env():
    return {k: os.environ.get(k)
            for k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}

@ray_tpu.remote
def arrays():
    import jax.numpy as jnp
    return {"loss": jnp.float32(1.5), "big": jnp.ones((512, 512)),
            "weak": jnp.asarray(2.0)}

out = {"tpu": ray_tpu.get(ray_tpu.remote(num_tpus=1)(env).remote(), timeout=120),
       "plain": ray_tpu.get(ray_tpu.remote(env).remote(), timeout=60)}
got = ray_tpu.get(arrays.remote(), timeout=120)
out["types"] = {k: type(v).__module__ for k, v in got.items()}
out["loss"] = float(got["loss"])
xb = sys.modules.get("jax._src.xla_bridge")
out["backend_initialised"] = bool(xb and xb.backends_are_initialized())
ray_tpu.shutdown()
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def unpinned_driver():
    """A driver with NO platform selection and no cache variable."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO
    proc = subprocess.run([sys.executable, "-c", _UNPINNED_DRIVER], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT:"))
    return json.loads(line[len("RESULT:"):])


def test_unpinned_driver_leaves_its_tpu_worker_unpinned(unpinned_driver):
    assert unpinned_driver["tpu"]["JAX_PLATFORMS"] is None
    assert unpinned_driver["tpu"]["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(REPO, ".jax_cache")
    assert unpinned_driver["plain"]["JAX_PLATFORMS"] == "cpu"
    assert unpinned_driver["plain"]["JAX_COMPILATION_CACHE_DIR"] is None


def test_jax_array_from_a_worker_opens_no_backend_in_the_reader(
        unpinned_driver):
    assert unpinned_driver["types"] == {"loss": "numpy", "big": "numpy",
                                        "weak": "numpy"}
    assert unpinned_driver["loss"] == 1.5
    assert unpinned_driver["backend_initialised"] is False


def test_jax_array_round_trip_keeps_a_reader_that_has_a_backend():
    import jax
    import jax.numpy as jnp

    from ray_tpu.core import serialization as ser

    jax.devices()  # this process holds a (CPU) backend
    vals = {"loss": jnp.float32(3.5), "weak": jnp.asarray(2.0),
            "big": jnp.ones((1024, 600), jnp.bfloat16)}
    back, _ = ser.deserialize(ser.serialize(vals).to_bytes())
    assert all(isinstance(v, jax.Array) for v in back.values())
    assert back["weak"].weak_type and not back["loss"].weak_type
    assert back["big"].dtype == jnp.bfloat16 and float(back["loss"]) == 3.5


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

def test_setup_jax_raises_on_a_worker_that_did_not_open_a_tpu():
    from ray_tpu.train.worker_group import TrainWorker

    worker = TrainWorker(0, 1)
    assert worker.setup_jax(None, use_tpu=False) is True
    with pytest.raises(RuntimeError, match=r"opened 'cpu'.*JAX_PLATFORMS"):
        worker.setup_jax(None, use_tpu=True)


def test_peak_flops_is_unknown_for_the_cpu_and_mfu_is_left_out():
    from ray_tpu.core import device_telemetry as dt

    assert dt.peak_flops_per_chip() is None
    mon = dt.StepMonitor("train", name="t", flops_per_token=100.0)
    mon.record_step(device_s=0.01, tokens=50.0)
    stats = mon.stats()
    assert stats["mfu"] is None and stats["goodput_per_s"] > 0


def test_gang_asking_for_chips_no_node_has_fails_at_once(shutdown_only):
    from ray_tpu.train import JaxTrainer, ScalingConfig

    ray_tpu.init(num_cpus=2)
    trainer = JaxTrainer(lambda config: None, scaling_config=ScalingConfig(
        num_workers=1, tpus_per_worker=1))
    with pytest.raises(RuntimeError, match="needs 1 TPU chips but the "
                                           "cluster has 0"):
        trainer.fit()


# ---------------------------------------------------------------------------
# chip_smoke.py on the CPU
# ---------------------------------------------------------------------------

def _run(args, **kw):
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("RAY_TPU_CHIPS", None)
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300, **kw)


def test_chip_smoke_without_a_chip_exits_nonzero_and_prints_no_result():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert "0 TPU chip(s)" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_last_line_stays_last(chips):
    """The rehearsal copy (tiny model, CPU worker that prints 200 lines):
    the script's own checks passed — among them that its parent opened no
    jax backend — and what it printed last is the contract's object."""
    code = ("import chip_smoke; chip_smoke.main(['--chips', '%d', '--steps', "
            "'3', '--batch', '8'], rehearse=True)" % chips)
    env = dict(os.environ, PYTHONPATH=REPO,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.endswith("}\n")
    last = json.loads(proc.stdout.splitlines()[-1])
    assert set(last) == LAST_LINE_KEYS[0]
    assert set(last["device"]) == LAST_LINE_KEYS[1]
    assert last["device"]["count"] == chips and last["ok"] is True
    assert "worker chatter" not in proc.stdout


# ---------------------------------------------------------------------------
# built from what git commits
# ---------------------------------------------------------------------------

def test_native_library_is_keyed_on_source_content_and_built_once(
        tmp_path, monkeypatch):
    from ray_tpu.core import native

    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path))
    calls = []
    real_run = subprocess.run

    def counting_run(cmd, **kw):
        calls.append(cmd)
        return real_run(cmd, **kw)

    monkeypatch.setattr(native.subprocess, "run", counting_run)
    (tmp_path / "librtpu-stale.so").write_bytes(b"old revision")
    paths = []
    threads = [threading.Thread(target=lambda: paths.append(native.build()))
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(paths)) == 1 and len(calls) == 1
    assert paths[0] == native.lib_path() and os.path.exists(paths[0])
    assert sorted(p.name for p in tmp_path.glob("librtpu*")) == \
        [os.path.basename(paths[0])]
    # a changed source is a different library, whatever the mtimes say
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ["-DX=1"])
    assert native.lib_path() != paths[0]


# ---------------------------------------------------------------------------
# found by the four-chip run: a stalled head is not a dead node
# ---------------------------------------------------------------------------

def test_head_stalled_past_the_health_timeout_keeps_its_node(shutdown_only):
    """Opening four chips froze the head's loop for 11 s (> the 10 s
    health timeout); on waking, the GCS read its own silence as the
    raylet's and killed the only node.  The same happens to a shared
    test cluster on a loaded box."""
    import signal
    import time

    ray_tpu.init(num_cpus=2, _system_config={
        "health_report_period_s": 0.2, "health_timeout_s": 1.0})
    f = ray_tpu.remote(lambda: 1)
    assert ray_tpu.get(f.remote(), timeout=60) == 1
    head = ray_tpu._head_proc.pid
    os.kill(head, signal.SIGSTOP)
    time.sleep(2.5)
    os.kill(head, signal.SIGCONT)
    time.sleep(1.0)  # several health rounds after the stall
    assert [n["alive"] for n in ray_tpu.nodes()] == [True]
    assert ray_tpu.get(f.remote(), timeout=60) == 1


def test_raylet_that_reconnects_after_a_head_stall_stays_alive():
    """A head stalled past the raylet's 5 s health-RPC timeout: the
    raylet re-registers on a new connection and closes the old one, and
    the GCS read that close as the node's death — the way a shared test
    cluster lost its node on a loaded box."""
    import signal
    import time

    from ray_tpu.cluster_utils import Cluster

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        c.add_node(num_cpus=1, resources={"side": 1})
        c.connect()
        c.wait_for_nodes()
        os.kill(c.head.proc.pid, signal.SIGSTOP)
        time.sleep(6.5)
        os.kill(c.head.proc.pid, signal.SIGCONT)
        deadline = time.monotonic() + 12  # past the reconnect and a few beats
        while time.monotonic() < deadline:
            time.sleep(1.0)
            assert sum(n["alive"] for n in ray_tpu.nodes()) == 2
        on_side = ray_tpu.remote(resources={"side": 1})(lambda: "ran")
        assert ray_tpu.get(on_side.remote(), timeout=60) == "ran"
    finally:
        ray_tpu.shutdown()
        c.shutdown()


# ---------------------------------------------------------------------------
# a chip whose last owner is leaving (PR 45): the gang waits for it, and
# shutdown() waits for the processes of its session
# ---------------------------------------------------------------------------

class _Busy:
    """Stands in for device nodes another process holds: ``node``'s
    ``open`` of a path in ``held`` raises ``EBUSY``."""

    def __init__(self, monkeypatch, held, other_errno=None):
        import errno

        self.held, self.opens, self.closes = set(held), [], []
        self.errno = other_errno or errno.EBUSY
        busy = self

        class Node:
            def __init__(self, path):
                self.path = path

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                busy.closes.append(self.path)

        def fake_open(path, mode="r", buffering=-1):
            if not path.startswith("/dev/"):
                return open(path, mode, buffering)
            assert (mode, buffering) == ("r+b", 0)
            self.opens.append(path)
            if path in self.held:
                raise OSError(self.errno, os.strerror(self.errno), path)
            if not path.startswith("/dev/standin"):
                raise FileNotFoundError(path)
            return Node(path)

        monkeypatch.setattr(node, "open", fake_open, raising=False)


CHIPS = [f"/dev/standin{i}" for i in range(4)]


def test_free_chips_cost_an_open_and_a_close_each(monkeypatch):
    busy = _Busy(monkeypatch, held=())
    assert node.wait_for_chips(CHIPS, timeout=5.0) == 0.0
    assert busy.opens == CHIPS and len(busy.closes) == 4


def test_the_probe_waits_for_a_chip_its_last_owner_is_closing(monkeypatch):
    busy = _Busy(monkeypatch, held={CHIPS[2]})
    threading.Timer(0.6, busy.held.clear).start()
    waited = node.wait_for_chips(CHIPS, timeout=30.0, poll=0.05)
    assert 0.5 <= waited < 5.0
    assert busy.opens.count(CHIPS[2]) > 2 and busy.opens[-1] == CHIPS[3]
    assert len(busy.closes) == 4     # every chip was seen free, once


def test_the_probe_gives_up_at_its_bound_and_leaves_the_error_to_jax(
        monkeypatch):
    busy = _Busy(monkeypatch, held={CHIPS[0], CHIPS[1]})
    waited = node.wait_for_chips(CHIPS, timeout=0.4, poll=0.05)
    assert 0.4 <= waited < 2.0       # ONE bound for all the chips
    assert busy.opens[-2:] == CHIPS[2:] and len(busy.closes) == 2


def test_an_error_that_is_not_busy_is_not_waited_for(monkeypatch):
    import errno

    _Busy(monkeypatch, held=set(CHIPS), other_errno=errno.EACCES)
    assert node.wait_for_chips(CHIPS, timeout=30.0) == 0.0
    assert node.wait_for_chips(["/dev/none"], timeout=30.0) == 0.0


@pytest.mark.parametrize("visible,mine", [
    (None, [0, 1, 2, 3]), ("2", [2]), ("1,3", [1, 3]), ("0,9", [0]),
    ("all", [0, 1, 2, 3])])
def test_the_probe_looks_at_the_leased_chips_alone(monkeypatch, visible,
                                                   mine):
    monkeypatch.setattr(node.glob, "glob", lambda pat: [])
    monkeypatch.setattr(node.os, "listdir",
                        lambda d: ["vfio", "10", "2", "0", "3"])
    assert node.chip_device_files() == [
        "/dev/vfio/0", "/dev/vfio/2", "/dev/vfio/3", "/dev/vfio/10"]
    if visible is None:
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    else:
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", visible)
    assert node.leased_chip_files() == [
        node.chip_device_files()[i] for i in mine]


def test_the_chip_open_span_says_how_long_it_waited(monkeypatch):
    """``waited_s`` on ``train:chip_open``, before jax opens anything: a
    worker that leased chips on this CPU finds no device node, waits for
    nothing, and then fails as it always did."""
    from ray_tpu.core import telemetry
    from ray_tpu.train import worker_group

    busy = _Busy(monkeypatch, held={CHIPS[1]})
    monkeypatch.setattr(node, "chip_device_files", lambda: CHIPS[:2])
    threading.Timer(0.4, busy.held.clear).start()
    telemetry.drain_spans("test")
    with pytest.raises(RuntimeError, match="leased TPU chips but jax"):
        worker_group.TrainWorker(0, 1).setup_jax(None, True)
    (span,) = [r for r in telemetry.drain_spans("test")
               if (r["cat"], r["name"]) == ("train", "chip_open")]
    assert 0.25 <= span["args"]["waited_s"] < 5.0
    assert span["args"]["backend"] == "cpu"
    monkeypatch.setattr(node, "chip_device_files", lambda: [])
    assert worker_group._chips_free() == 0.0


_IGNORES_SIGTERM = ("import signal, time; "
                    "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
                    "print('up', flush=True); time.sleep(120)")


def test_no_process_of_a_session_is_alive_when_shutdown_returns():
    ray_tpu.init(num_cpus=2)
    try:
        pids = set(ray_tpu.get([ray_tpu.remote(os.getpid).remote()
                                for _ in range(4)], timeout=60))
        below = node.processes_below(ray_tpu._head_proc.pid)
        assert pids <= set(below) and os.getpid() not in below
    finally:
        ray_tpu.shutdown()
    assert node.wait_until_gone(below, 0.0) == {}


def test_a_process_that_outlives_its_head_is_killed_at_the_bound(caplog):
    proc = subprocess.Popen([sys.executable, "-c", _IGNORES_SIGTERM],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "up"
        mine = node.processes_below(os.getpid())
        below = {proc.pid: mine[proc.pid]}
        proc.terminate()                       # what PDEATHSIG would send
        assert node.wait_until_gone(below, 0.3) == below
        with caplog.at_level("WARNING", logger="ray_tpu"):
            ray_tpu._await_session_processes(below, patience=0.3)
        assert "SIGKILL" in caplog.text and str(proc.pid) in caplog.text
        assert node.wait_until_gone(below, 0.0) == {}
        assert proc.wait(timeout=10) == -9
    finally:
        proc.kill()


def test_a_pid_given_to_another_process_counts_as_gone():
    mine = node.processes_below(os.getppid())
    assert os.getpid() in mine
    assert node.wait_until_gone({os.getpid(): mine[os.getpid()]}, 0.0)
    assert node.wait_until_gone({os.getpid(): mine[os.getpid()] + 1},
                                0.0) == {}


_LEADER_LEAVES_FIRST = """
import ctypes, threading, time
threading.Thread(target=time.sleep, args=(1.5,)).start()
print('up', flush=True)
ctypes.CDLL(None).pthread_exit(None)   # the main thread alone
"""


def _until_a_zombie(pid: int, bound: float) -> None:
    deadline = time.monotonic() + bound
    while node._proc_stat(pid)[0] != "Z" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert node._proc_stat(pid)[0] == "Z"


def test_a_zombie_whose_threads_run_on_is_not_gone():
    """What four busy chips hid behind (PR 45): the worker's main thread
    had exited, ``/proc/<pid>/stat`` read ``Z``, and another thread was
    still closing the device files."""
    proc = subprocess.Popen([sys.executable, "-c", _LEADER_LEAVES_FIRST],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "up"
        born = node.processes_below(os.getpid())[proc.pid]
        _until_a_zombie(proc.pid, 1.0)
        assert node.wait_until_gone({proc.pid: born}, 0.0) == \
            {proc.pid: born}
        # ... and once the last thread has ended, a zombie like any other
        assert node.wait_until_gone({proc.pid: born}, 10.0) == {}
    finally:
        proc.kill()
        proc.wait()


def test_a_zombie_counts_while_a_chip_of_the_host_is_busy(monkeypatch):
    """A sandbox whose init does not reap: a session's workers read
    ``Z``, list no threads, and the chips they held are busy for seconds
    more; they are gone when the chips are free."""
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    try:
        born = node.processes_below(os.getpid())[proc.pid]
        _until_a_zombie(proc.pid, 10.0)
        mine = {proc.pid: born}
        assert node.wait_until_gone(mine, 0.0) == {}   # no chip here
        busy = _Busy(monkeypatch, held={CHIPS[3]})
        monkeypatch.setattr(node, "chip_device_files", lambda: CHIPS)
        assert node.wait_until_gone(mine, 0.2) == mine
        threading.Timer(0.3, busy.held.clear).start()
        started = time.monotonic()
        assert node.wait_until_gone(mine, 10.0) == {}
        assert 0.2 <= time.monotonic() - started < 5.0
    finally:
        proc.wait()
