"""Node process orchestration: bring-up of head and worker nodes.

Parity: reference ``python/ray/_private/node.py`` + ``services.py`` —
spawn/monitor the per-node daemons and the cluster head.  Here a *head
node* process hosts the GCS and a raylet in one asyncio loop; additional
*worker node* processes host one raylet each.  ``ray_tpu.init()`` spawns a
head subprocess and connects the driver to it; test clusters add more
node subprocesses (see ``ray_tpu.cluster_utils``).

The head writes a small JSON handshake file into the session dir once its
services are listening so the parent can discover the ports.
"""

from __future__ import annotations

import argparse
import asyncio
import errno
import glob
import json
import logging
import os
import signal
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core.config import Config

logger = logging.getLogger(__name__)


def new_session_dir(config: Config) -> str:
    root = config.session_root
    os.makedirs(root, exist_ok=True)
    session = os.path.join(
        root, f"session_{time.strftime('%Y%m%d-%H%M%S')}_{uuid.uuid4().hex[:8]}")
    os.makedirs(os.path.join(session, "logs"), exist_ok=True)
    return session


def detect_tpu_resources() -> Dict[str, float]:
    """TPU chips attached to this host, as schedulable resources.

    Counted from the device nodes the chips expose (``/dev/accel*``, or
    one numbered VFIO group per chip under ``/dev/vfio``), never from
    ``TPU_ACCELERATOR_TYPE``/``TPU_CHIPS_PER_HOST_BOUNDS``: those name
    the host's full topology even on a machine that was handed one chip
    of it.  ``RAY_TPU_CHIPS`` overrides.  jax is deliberately not
    imported: the raylet must not open the accelerator.
    """
    n = os.environ.get("RAY_TPU_CHIPS")
    if n is not None:
        return {"TPU": float(n)} if float(n) > 0 else {}
    chips = len(chip_device_files())
    return {"TPU": float(chips)} if chips else {}


def chip_device_files() -> List[str]:
    """The device nodes the chips expose, in the chips' order."""
    found = sorted(glob.glob("/dev/accel*"))
    if not found:
        try:
            found = [os.path.join("/dev/vfio", e) for e in sorted(
                (e for e in os.listdir("/dev/vfio") if e.isdigit()), key=int)]
        except OSError:
            found = []
    return found


def leased_chip_files() -> List[str]:
    """Device nodes of the chips this process was spawned for
    (``TPU_VISIBLE_CHIPS`` of :func:`tpu_worker_env`; all of the host's
    where the lease is the whole host)."""
    files = chip_device_files()
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    try:
        mine = [int(i) for i in visible.split(",")] if visible else None
    except ValueError:
        mine = None
    if mine is None:
        return files
    return [files[i] for i in mine if 0 <= i < len(files)]


def wait_for_chips(paths, timeout: float = 60.0, poll: float = 0.25) -> float:
    """Seconds waited until none of the device nodes ``paths`` is held by
    another process, ``timeout`` at most.  A chip's node opens for one
    process at a time (``EBUSY`` otherwise), and its last owner may
    still be exiting: a gang worker whose head was just terminated takes
    up to 19 s to close four chips (PERF.md, PR 45), and a backend that
    failed to initialise is not safely initialised again in one process.
    So the wait is HERE, before jax opens anything.  A free chip costs
    an ``open`` and a ``close``; any other error is the backend's to
    report."""
    started, busy = time.monotonic(), False
    for path in paths:
        while _held(path) and time.monotonic() - started < timeout:
            busy = True
            time.sleep(poll)
    return time.monotonic() - started if busy else 0.0


def _held(path: str) -> bool:
    """Whether another process holds the device node ``path``."""
    try:
        with open(path, "r+b", buffering=0):
            return False
    except OSError as e:
        return e.errno == errno.EBUSY


def _proc_stat(pid: int) -> Optional[Tuple[str, int, int]]:
    """``(state, parent, start time)`` of a process from ``/proc``, or
    ``None`` where it is gone (or there is no ``/proc``)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return fields[0], int(fields[1]), int(fields[19])
    except (OSError, IndexError, ValueError):
        return None


def processes_below(pid: int) -> Dict[int, int]:
    """``{pid: start time}`` of every process that descends from ``pid``:
    a head's raylet workers, its zygote and what that forked."""
    born: Dict[int, int] = {}
    children: Dict[int, List[int]] = {}
    try:
        entries = os.listdir("/proc")
    except OSError:
        return {}
    for entry in entries:
        stat = _proc_stat(int(entry)) if entry.isdigit() else None
        if stat is not None:
            born[int(entry)] = stat[2]
            children.setdefault(stat[1], []).append(int(entry))
    below: Dict[int, int] = {}
    todo = [pid]
    while todo:
        for child in children.get(todo.pop(), ()):
            if child not in below:
                below[child] = born[child]
                todo.append(child)
    return below


def _threads_run_on(pid: int) -> bool:
    """Whether a process whose main thread has exited (it reads ``Z``)
    still has other threads: a gang worker in ``exit_group`` closes its
    chips in whichever thread drops the last reference to them, seconds
    after its leader is a zombie."""
    try:
        return len(os.listdir(f"/proc/{pid}/task")) > 1
    except OSError:
        return False


def wait_until_gone(procs: Dict[int, int], timeout: float,
                    poll: float = 0.05) -> Dict[int, int]:
    """Those of ``procs`` (``{pid: start time}``) still alive after
    ``timeout`` seconds.  A zombie holds nothing and is gone, with two
    exceptions, both met on the chip (PERF.md, PR 45): one whose other
    threads run on, and any zombie while a chip of this host is still
    busy: in a sandbox whose init does not reap (gVisor), the workers
    of a session read ``Z`` with no threads to list for the seconds it
    takes to close their chips, and vanish when the chips are free."""
    deadline = time.monotonic() + timeout
    left = dict(procs)
    while left:
        chips_busy = None
        for pid, born in list(left.items()):
            stat = _proc_stat(pid)
            if stat is None or stat[2] != born:
                del left[pid]
            elif stat[0] == "Z" and not _threads_run_on(pid):
                if chips_busy is None:
                    chips_busy = any(map(_held, chip_device_files()))
                if not chips_busy:
                    del left[pid]
        if not left or time.monotonic() >= deadline:
            break
        time.sleep(poll)
    return left


#: chips-per-process bounds libtpu accepts for a sub-host lease
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
_VISIBLE_CHIP_VARS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                      "TPU_PROCESS_BOUNDS")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def plain_worker_env(env: Dict[str, str]) -> None:
    """Pool workers never open a chip: N of them on one host would
    fight over it, and a lease-less process holding it starves the
    worker that leased it."""
    env.pop("RAY_TPU_STASH_JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"


def tpu_worker_env(env: Dict[str, str], chip_ids, host_chips: int) -> None:
    """Environment of a worker spawned for a TPU lease, set before its
    interpreter starts: the driver's own platform selection comes back
    (node daemons run pinned to the CPU with the original stashed), and
    libtpu is narrowed to exactly the leased chips so that two leases
    on one host never open each other's.

    One placeable persistent compile cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set it is left alone (jax reads it
    natively, and it reached this environment from the driver's);
    otherwise chip workers get a fixed directory of the checkout — the
    path is part of the cache key, so it must never move between runs."""
    stashed = env.pop("RAY_TPU_STASH_JAX_PLATFORMS", None)
    if stashed:
        env["JAX_PLATFORMS"] = stashed
    else:
        env.pop("JAX_PLATFORMS", None)
    if env.get("JAX_PLATFORMS") != "cpu":  # a CPU-pinned lease opens no chip
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(repo_root(), ".jax_cache"))
    for var in _VISIBLE_CHIP_VARS:
        env.pop(var, None)
    if len(chip_ids) < host_chips:
        env["TPU_VISIBLE_CHIPS"] = ",".join(str(i) for i in chip_ids)
        bounds = _CHIP_BOUNDS.get(len(chip_ids))
        if bounds:
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"


def detect_topology() -> Dict[str, Any]:
    """Slice/host coordinates for gang scheduling (SURVEY.md §7.2)."""
    topo: Dict[str, Any] = {}
    if os.environ.get("TPU_NAME"):
        topo["slice"] = os.environ["TPU_NAME"]
    if os.environ.get("TPU_WORKER_ID"):
        try:
            topo["worker_index"] = int(os.environ["TPU_WORKER_ID"])
        except ValueError:
            pass
    if os.environ.get("TPU_ACCELERATOR_TYPE"):
        topo["accelerator_type"] = os.environ["TPU_ACCELERATOR_TYPE"]
    return topo


def _write_handshake(path: str, payload: Dict[str, Any]) -> None:
    """Write the session handshake file atomically (tmp + rename).
    Sync on purpose: callers are async and run it in an executor so the
    raylet/GCS loop never blocks on filesystem latency."""
    with open(path + ".tmp", "w") as f:
        json.dump(payload, f)
    os.replace(path + ".tmp", path)


async def _publish_handshake(handshake_path: str, raylet: "Raylet",
                             gcs_address: Tuple[str, int],
                             raylet_address: Tuple[str, int],
                             session_dir: str) -> None:
    """One handshake schema for head and worker nodes — consumers
    (connect(), the CLI) must never need to care which wrote it."""
    await asyncio.get_running_loop().run_in_executor(
        None, _write_handshake, handshake_path, {
            "gcs_address": list(gcs_address),
            "raylet_address": list(raylet_address),
            "node_id": raylet.node_id.hex(),
            "store_path": raylet.store.path,
            "store_capacity": raylet.store_capacity,
            "session_dir": session_dir,
        })


async def run_head(config: Config, session_dir: str,
                   resources: Optional[Dict[str, float]],
                   handshake_path: str, host: str = "127.0.0.1",
                   gcs_port: int = 0) -> None:
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.raylet import Raylet

    # durable GCS tables: kv/jobs/functions/detached actors survive a
    # head restart (reference: GCS recovery from Redis,
    # test_gcs_fault_tolerance.py); the snapshot lives in the session dir
    gcs = GcsServer(config, host=host, port=gcs_port,
                    snapshot_path=os.path.join(session_dir,
                                               "gcs_snapshot.pkl"),
                    session_dir=session_dir)
    gcs_address = await gcs.start()
    merged = dict(resources or {})
    for k, v in detect_tpu_resources().items():
        merged.setdefault(k, v)
    raylet = Raylet(config, gcs_address, session_dir, resources=merged,
                    topology=detect_topology(), host=host)
    raylet_address = await raylet.start()
    _spawn_dashboard_agent(session_dir, raylet.node_id.hex(),
                           gcs_address, config, host=host)
    await _publish_handshake(handshake_path, raylet, gcs_address,
                             raylet_address, session_dir)
    stop = asyncio.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        asyncio.get_running_loop().add_signal_handler(sig, stop.set)
    await stop.wait()
    await raylet.stop()
    await gcs.stop()


async def run_node(config: Config, gcs_address: Tuple[str, int],
                   session_dir: str, resources: Optional[Dict[str, float]],
                   handshake_path: str, host: str = "127.0.0.1") -> None:
    from ray_tpu.core.raylet import Raylet

    merged = dict(resources or {})
    for k, v in detect_tpu_resources().items():
        merged.setdefault(k, v)
    raylet = Raylet(config, gcs_address, session_dir, resources=merged,
                    topology=detect_topology(), host=host)
    raylet_address = await raylet.start()
    _spawn_dashboard_agent(session_dir, raylet.node_id.hex(),
                           gcs_address, config, host=host)
    await _publish_handshake(handshake_path, raylet, gcs_address,
                             raylet_address, session_dir)
    stop = asyncio.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        asyncio.get_running_loop().add_signal_handler(sig, stop.set)
    await stop.wait()
    await raylet.stop()




def _spawn_dashboard_agent(session_dir: str, node_id_hex: str,
                           gcs_address, config: Config,
                           host: str = "127.0.0.1"):
    """Per-node dashboard agent (reference dashboard/agent.py): serves
    node-local stats/logs over HTTP on the node's host address and
    registers itself in the GCS KV.  Spawned through _spawn so it gets
    the same env-stash/PDEATHSIG/posix_spawn discipline as the other
    daemons (it dies with this node process)."""
    if not getattr(config, "dashboard_agent", True):
        return None
    cmd = [sys.executable, "-m", "ray_tpu.dashboard_agent",
           "--session-dir", session_dir,
           "--node-id", node_id_hex,
           "--host", host,
           "--gcs", f"{gcs_address[0]}:{gcs_address[1]}"]
    try:
        return _spawn(cmd, session_dir, f"dashboard-agent-{node_id_hex[:8]}",
                      die_with_parent=safe_die_with_parent())
    except Exception:  # noqa: BLE001 — observability must not block boot
        logging.getLogger(__name__).exception(
            "dashboard agent failed to start")
        return None



def safe_die_with_parent() -> bool:
    """PDEATHSIG fires when the spawning THREAD exits, not the process
    (man prctl) — only arm it when spawning from the main thread, else a
    driver calling init() from a short-lived worker thread would have its
    cluster SIGTERMed when that thread finishes."""
    import threading

    return threading.current_thread() is threading.main_thread()


def preexec_die_with_parent():
    """preexec_fn: SIGTERM this child when its parent dies (Linux
    PR_SET_PDEATHSIG).  Driver-owned clusters must not orphan their head
    when the driver is SIGKILLed; CLI-started daemons do NOT use this
    (a ``ray-tpu start`` cluster outlives the CLI process).  Callers
    must gate on :func:`safe_die_with_parent`.

    Prefer the env-flag + :func:`maybe_arm_pdeathsig` pair for OUR OWN
    daemons: any preexec_fn forces subprocess down the fork path, and
    forking a driver whose jax threads are running is the canonical
    latent-deadlock (and warning spam) in this stack.  This
    preexec variant remains for spawning third-party commands that can't
    arm themselves."""
    try:
        import ctypes
        import signal as sig

        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(1, sig.SIGTERM)  # PR_SET_PDEATHSIG = 1
    except Exception:  # non-Linux: best effort only
        pass


def maybe_arm_pdeathsig() -> None:
    """Child-side PDEATHSIG: called first thing in daemon/worker mains
    when the spawner set ``RAY_TPU_PDEATHSIG=<spawner pid>``.  Keeps the
    Popen call preexec_fn-free so CPython can use posix_spawn(3) instead
    of fork+exec (the spawning driver has jax threads running).  The
    spawn→arm window is covered by re-checking getppid() against the
    spawner's pid (NOT against 1 — a containerized driver legitimately
    runs as PID 1, and a reparented orphan may land on a subreaper)."""
    val = os.environ.pop("RAY_TPU_PDEATHSIG", None)
    if not val:
        return
    try:
        import ctypes
        import signal as sig

        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(1, sig.SIGTERM)  # PR_SET_PDEATHSIG = 1
        try:
            spawner = int(val)
        except ValueError:
            return
        if os.getppid() != spawner:  # parent died inside the window
            os._exit(1)
    except Exception:  # non-Linux: best effort only
        pass


def spawn_head(config: Config, session_dir: str,
               resources: Optional[Dict[str, float]] = None,
               gcs_port: int = 0, die_with_parent: bool = False,
               ) -> Tuple[subprocess.Popen, Dict[str, Any]]:
    """Spawn the head node subprocess; returns (proc, handshake)."""
    handshake = os.path.join(session_dir, "head_handshake.json")
    if os.path.exists(handshake):  # restart: await a FRESH handshake
        os.remove(handshake)
    cmd = [sys.executable, "-m", "ray_tpu.core.node",
           "--mode", "head",
           "--session-dir", session_dir,
           "--handshake", handshake,
           "--config", config.to_json()]
    if resources is not None:
        cmd += ["--resources", json.dumps(resources)]
    if gcs_port:
        cmd += ["--gcs-port", str(gcs_port)]
    proc = _spawn(cmd, session_dir, "head", die_with_parent)
    return proc, _await_handshake(proc, handshake)


def spawn_node(config: Config, session_dir: str,
               gcs_address: Tuple[str, int],
               resources: Optional[Dict[str, float]] = None,
               die_with_parent: bool = False,
               ) -> Tuple[subprocess.Popen, Dict[str, Any]]:
    handshake = os.path.join(
        session_dir, f"node_handshake_{uuid.uuid4().hex[:8]}.json")
    cmd = [sys.executable, "-m", "ray_tpu.core.node",
           "--mode", "node",
           "--gcs", f"{gcs_address[0]}:{gcs_address[1]}",
           "--session-dir", session_dir,
           "--handshake", handshake,
           "--config", config.to_json()]
    if resources is not None:
        cmd += ["--resources", json.dumps(resources)]
    proc = _spawn(cmd, session_dir, "node", die_with_parent)
    return proc, _await_handshake(proc, handshake)


def _spawn(cmd, session_dir: str, tag: str,
           die_with_parent: bool = False) -> subprocess.Popen:
    log_base = os.path.join(session_dir, "logs",
                            f"{tag}-{uuid.uuid4().hex[:8]}")
    out = open(log_base + ".out", "ab")
    err = open(log_base + ".err", "ab")
    env = dict(os.environ)
    # daemons must import ray_tpu regardless of the driver's cwd
    env["PYTHONPATH"] = repo_root() + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # Node daemons never open an accelerator and their pool workers
    # inherit the CPU pin.  The driver's own selection is STASHED so the
    # raylet can give it back to a worker spawned for a TPU lease
    # (tpu_worker_env).
    if os.environ.get("JAX_PLATFORMS"):
        env["RAY_TPU_STASH_JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    env["JAX_PLATFORMS"] = "cpu"
    if die_with_parent:
        # armed child-side (maybe_arm_pdeathsig); value = our pid so the
        # child can detect a parent that died before it armed
        env["RAY_TPU_PDEATHSIG"] = str(os.getpid())
    # close_fds=False + no preexec_fn + no cwd → CPython uses
    # posix_spawn(3): never forks this (jax-threaded) driver process.
    # PEP 446 makes Python-created fds CLOEXEC, so not closing is safe.
    proc = subprocess.Popen(
        cmd, stdout=out, stderr=err, env=env, close_fds=False)
    proc._rtpu_err_path = log_base + ".err"  # for handshake diagnostics
    return proc


def _await_handshake(proc: subprocess.Popen, path: str,
                     timeout: float = 60.0) -> Dict[str, Any]:
    # 60s: heavily loaded CI boxes (full-suite runs with TF/torch tests
    # hogging cores) have shown >30s fork-to-listen latency
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        if proc.poll() is not None:
            raise RuntimeError(
                f"node process exited with code {proc.returncode} before "
                f"handshake: {_stderr_tail(proc)}")
        time.sleep(0.02)
    proc.terminate()
    raise TimeoutError("timed out waiting for node handshake")


def _stderr_tail(proc: subprocess.Popen, limit: int = 2000) -> str:
    """Last bytes of the daemon's .err log for exception messages."""
    try:
        err = getattr(proc, "_rtpu_err_path", None)
        if err and os.path.exists(err):
            with open(err, "rb") as f:
                f.seek(max(0, os.path.getsize(err) - limit))
                return f.read().decode(errors="replace").strip() \
                    or "(empty stderr)"
    except OSError:
        pass
    return "see logs in the session dir"


def main() -> None:
    maybe_arm_pdeathsig()
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode", choices=["head", "node"], required=True)
    parser.add_argument("--gcs", default=None)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--handshake", required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--resources", default=None)
    parser.add_argument("--gcs-port", type=int, default=0)
    args = parser.parse_args()

    logging.basicConfig(
        level=os.environ.get("RAY_TPU_LOG_LEVEL", "INFO"),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    config = Config.from_json(args.config)
    resources = json.loads(args.resources) if args.resources else None
    if args.mode == "head":
        asyncio.run(run_head(config, args.session_dir, resources,
                             args.handshake, gcs_port=args.gcs_port))
    else:
        host, port = args.gcs.rsplit(":", 1)
        asyncio.run(run_node(config, (host, int(port)), args.session_dir,
                             resources, args.handshake))


if __name__ == "__main__":
    main()
