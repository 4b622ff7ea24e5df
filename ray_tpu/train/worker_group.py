"""Training worker gangs.

Parity: reference ``python/ray/train/_internal/worker_group.py`` (actor
gang) + ``backend_executor.py`` (backend lifecycle).  A
:class:`WorkerGroup` places N ``TrainWorker`` actors inside a placement
group (PACK over a TPU slice by default) and runs the same function on
every worker in lockstep — the property multi-host jax requires
(SURVEY.md §7 hard parts: all hosts must execute the same program).

The jax backend replaces the reference's torch-process-group bootstrap
(``train/torch/config.py:69-113``): worker 0 picks a coordinator port and
every worker calls ``jax.distributed.initialize(coordinator, n, rank)``
before user code runs.
"""

from __future__ import annotations

import logging
import os
import queue
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.core import device_telemetry as _dt
from ray_tpu.core import telemetry as _tm
from ray_tpu.core import worker as worker_mod
from ray_tpu.train import session as session_mod
from ray_tpu.train.config import ScalingConfig
from ray_tpu.util.placement_group import (
    PlacementGroup,
    placement_group,
    remove_placement_group,
)
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

logger = logging.getLogger(__name__)


class TrainWorker:
    """Actor hosting one training process (one per TPU host)."""

    def __init__(self, world_rank: int, world_size: int):
        self.world_rank = world_rank
        self.world_size = world_size
        self._thread: Optional[threading.Thread] = None
        self._session: Optional[session_mod._TrainSession] = None

    def hostname_and_port(self) -> tuple:
        """Reserve a coordinator port (called on rank 0 only)."""
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return (socket.gethostbyname(socket.gethostname()), port)

    def setup_torch(self, init_method: str) -> bool:
        """torch.distributed gloo rendezvous (parity: the reference's
        _setup_torch_process_group, train/torch/config.py:69-113 — TCP
        store at rank 0; gloo because this stack's accelerators speak
        XLA, so torch collectives run on host CPU)."""
        import torch.distributed as dist

        if dist.is_initialized():
            dist.destroy_process_group()
        dist.init_process_group("gloo", init_method=init_method,
                                rank=self.world_rank,
                                world_size=self.world_size)
        return True

    def setup_jax(self, coordinator: Optional[str], use_tpu: bool) -> bool:
        """Initialize the jax runtime for this worker.

        The platform was selected when the raylet spawned this process
        for its lease (jax reads ``JAX_PLATFORMS`` at import, which
        unpickling this class already triggered), so a TPU gang only
        CHECKS that it really opened the chip — a mis-pinned worker
        would otherwise train on the CPU and report a loss.  Multi-host
        gangs rendezvous at the rank-0 coordinator (the torch TCP-store
        analog).
        """
        _dt.record_xla_phases()
        # every gang leaves this span, a CPU one too (then ~0 s): what
        # opening the chips costs; ``waited_s``: for their last owner
        with _tm.span("train", "chip_open", backend=None, devices=0) as sp:
            if not use_tpu:
                return True
            import jax
            sp.args.update(waited_s=_chips_free())
            if coordinator is not None and self.world_size > 1:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=self.world_size,
                    process_id=self.world_rank)
            backend = jax.default_backend()
            sp.args.update(backend=backend,
                           devices=jax.local_device_count())
        if backend != "tpu":
            seen = {k: v for k, v in os.environ.items()
                    if k.startswith(("JAX_", "TPU_", "XLA_", "RAY_TPU_"))}
            raise RuntimeError(
                f"train worker {self.world_rank} leased TPU chips but jax "
                f"opened {backend!r}; environment: {seen}")
        return True

    def setup_tensorflow(self, cluster_workers: List[str]) -> bool:
        """Write TF_CONFIG for MultiWorkerMirroredStrategy (parity:
        reference ``train/tensorflow/config.py`` ``_setup_tensorflow_
        environment`` — cluster spec of every gang member plus this
        worker's task index)."""
        import json

        os.environ["TF_CONFIG"] = json.dumps({
            "cluster": {"worker": cluster_workers},
            "task": {"type": "worker", "index": self.world_rank},
        })
        return True

    def run(self, fn: Callable, config: Dict[str, Any],
            dataset_shard: Any = None, resume_checkpoint=None) -> bool:
        """Start the user loop on a background thread; returns
        immediately.  Results stream via ``next_results``."""
        self._session = session_mod._TrainSession(
            self.world_rank, self.world_size, local_rank=0,
            dataset_shard=dataset_shard)
        self._session.resume_checkpoint = resume_checkpoint
        session_mod._set_session(self._session)

        def _target():
            try:
                fn(config)
            except BaseException as e:  # noqa: BLE001 — forwarded to driver
                logger.exception("train loop failed on rank %d",
                                 self.world_rank)
                self._session.error = e
            finally:
                self._session.finished.set()

        self._thread = threading.Thread(target=_target, daemon=True,
                                        name="train-loop")
        self._thread.start()
        return True

    def next_results(self, timeout: float = 1.0) -> Dict[str, Any]:
        """Drain queued results; reports liveness and errors."""
        assert self._session is not None
        results: List[Dict[str, Any]] = []
        t_call = time.time()
        try:
            results.append(self._session.result_queue.get(timeout=timeout))
        except queue.Empty:
            pass
        if results:
            # the span starts at the first row: the wait on an empty
            # queue is the loop's time, not the checkpoint's
            with _tm.span("train", "next_results",
                          waited_s=round(time.time() - t_call, 6)) as sp:
                try:
                    while True:
                        results.append(
                            self._session.result_queue.get_nowait())
                except queue.Empty:
                    pass
                sp.args.update(_rows(results))
        error = None
        if self._session.error is not None:
            import traceback

            error = "".join(traceback.format_exception(self._session.error))
        return {
            "results": results,
            "finished": self._session.finished.is_set()
                        and self._session.result_queue.empty(),
            "error": error,
        }

    def flush_telemetry(self) -> bool:
        """Send what the flush period still holds (up to 5 s of spans):
        the driver asks once, before it kills the gang."""
        worker_mod.global_worker().flush_telemetry()
        return True

    def shutdown_jax(self) -> bool:
        try:
            import jax

            jax.distributed.shutdown()
        except Exception:
            pass
        return True


def _rows(results: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Span arguments for a batch of reported rows: how many, and the
    ids of the checkpoints that ride in it."""
    return {"results": len(results),
            "ckpts": [r["checkpoint"].id for r in results
                      if r.get("checkpoint") is not None]}


class WorkerGroup:
    def __init__(self, scaling: ScalingConfig):
        self.scaling = scaling
        self.pg: Optional[PlacementGroup] = None
        self.workers: List[Any] = []

    def start(self) -> None:
        bundles = [self.scaling.worker_resources()
                   for _ in range(self.scaling.num_workers)]
        want = sum(b.get("TPU", 0) for b in bundles)
        have = ray_tpu.cluster_resources().get("TPU", 0) if want else 0
        if want > have:
            # infeasible, not busy: fail now instead of waiting out the
            # placement timeout for chips no node of the cluster has
            raise RuntimeError(
                f"training gang needs {want:g} TPU chips but the cluster "
                f"has {have:g} (chips are counted from /dev/accel* or "
                f"/dev/vfio; RAY_TPU_CHIPS overrides)")
        with _tm.span("train", "gang.place", bundles=len(bundles),
                      tpu=want):
            self.pg = placement_group(
                bundles, strategy=self.scaling.placement_strategy)
            placed = self.pg.wait(120)
        if not placed:
            remove_placement_group(self.pg)
            raise RuntimeError(
                f"could not place training gang: {bundles} "
                f"({self.scaling.placement_strategy})")
        actor_cls = ray_tpu.remote(TrainWorker)
        self.workers = []
        with _tm.span("train", "gang.spawn",
                      workers=self.scaling.num_workers):
            for rank in range(self.scaling.num_workers):
                strategy = PlacementGroupSchedulingStrategy(
                    placement_group=self.pg,
                    placement_group_bundle_index=rank)
                worker = actor_cls.options(
                    num_cpus=self.scaling.cpus_per_worker,
                    num_tpus=self.scaling.tpus_per_worker or None,
                    resources=self.scaling.resources_per_worker or None,
                    scheduling_strategy=strategy,
                    max_concurrency=4,  # run + poll concurrently
                ).remote(rank, self.scaling.num_workers)
                self.workers.append(worker)
            # barrier: all actors alive
            ray_tpu.get([w.__ray_ready__() for w in self.workers],
                        timeout=300)

    def setup_backend(self, backend: str = "jax") -> None:
        if backend == "torch":
            host, port = ray_tpu.get(
                self.workers[0].hostname_and_port.remote(), timeout=60)
            ray_tpu.get([w.setup_torch.remote(f"tcp://{host}:{port}")
                         for w in self.workers], timeout=600)
            return
        if backend == "tensorflow":
            addrs = ray_tpu.get(
                [w.hostname_and_port.remote() for w in self.workers],
                timeout=60)
            cluster = [f"{h}:{p}" for h, p in addrs]
            ray_tpu.get([w.setup_tensorflow.remote(cluster)
                         for w in self.workers], timeout=600)
            return
        use_tpu = (self.scaling.tpus_per_worker or 0) > 0
        coordinator = None
        if self.scaling.num_workers > 1 and use_tpu:
            host, port = ray_tpu.get(
                self.workers[0].hostname_and_port.remote(), timeout=60)
            coordinator = f"{host}:{port}"
        ray_tpu.get([w.setup_jax.remote(coordinator, use_tpu)
                     for w in self.workers], timeout=600)

    def run(self, fn: Callable, config: Dict[str, Any],
            dataset_shards: Optional[List[Any]] = None,
            resume_checkpoint=None) -> None:
        with _tm.span("train", "gang.run"):
            ray_tpu.get([
                w.run.remote(fn, config,
                             dataset_shards[i] if dataset_shards else None,
                             resume_checkpoint)
                for i, w in enumerate(self.workers)
            ], timeout=300)

    def poll(self, timeout: float = 1.0) -> List[Dict[str, Any]]:
        with _tm.span("train", "poll") as sp:
            polls = ray_tpu.get(
                [w.next_results.remote(timeout) for w in self.workers],
                timeout=max(60.0, timeout * 10))
            sp.args.update(_rows([r for p in polls for r in p["results"]]))
        return polls

    def shutdown(self) -> None:
        if self.workers and _tm.enabled():
            try:  # a killed worker takes its unflushed spans with it
                ray_tpu.get([w.flush_telemetry.remote()
                             for w in self.workers], timeout=3.0)
            except Exception:
                pass
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None


def _chips_free() -> float:
    """Seconds waited for this worker's leased chips to be let go by a
    process that is exiting (``core/node.py`` ``wait_for_chips``); before
    jax opens them, because a backend that failed to initialise is not
    safely initialised again in this process.  (Down here so that no
    line above the train loop's frame moves: a kernel's cache key holds
    the line of every frame above its trace.)"""
    from ray_tpu.core import node

    return round(node.wait_for_chips(node.leased_chip_files()), 3)
