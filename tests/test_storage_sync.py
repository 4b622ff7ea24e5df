"""Durable experiment/checkpoint sync (parity model: reference
tune/syncer.py + air/_internal/remote_storage.py + Tuner.restore).

The headline test kills a head process mid-experiment (SIGKILL — real
head loss) and resumes every trial from its last synced checkpoint on a
completely fresh cluster via ``Tuner.restore(uri)``.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from ray_tpu.air import storage


def test_file_storage_roundtrip(tmp_path):
    root = str(tmp_path / "store")
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.txt").write_text("hello")
    uri = f"file://{root}/ck"
    storage.upload_dir(str(src), uri)
    assert storage.exists(uri)
    dst = tmp_path / "dst"
    storage.download_dir(uri, str(dst))
    assert (dst / "a.txt").read_text() == "hello"
    # re-upload replaces atomically (no .tmp/.old residue)
    (src / "a.txt").write_text("v2")
    storage.upload_dir(str(src), uri)
    backend, path = storage.get_storage(uri)
    assert sorted(os.listdir(os.path.dirname(path))) == ["ck"]
    storage.write_bytes(f"file://{root}/meta.bin", b"x")
    assert storage.read_bytes(f"file://{root}/meta.bin") == b"x"


def test_unknown_scheme_rejected():
    with pytest.raises(ValueError, match="no storage backend"):
        storage.get_storage("s3://bucket/x")


_HEAD_SCRIPT = """
import sys, os
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import time
import ray_tpu
from ray_tpu import tune
from ray_tpu.train.config import RunConfig
from ray_tpu.train.checkpoint import Checkpoint

ray_tpu.init(num_cpus=2)

def trainable(config):
    ckpt = tune.get_checkpoint()
    start = ckpt.to_dict()["iter"] if ckpt is not None else 0
    for i in range(start + 1, 11):
        tune.report({{"iter": i, "mark": config["mark"]}},
                    checkpoint=Checkpoint.from_dict({{"iter": i}}))
        time.sleep(0.35)

tuner = tune.Tuner(
    trainable,
    param_space={{"mark": tune.grid_search([1, 2])}},
    tune_config=tune.TuneConfig(metric="iter", mode="max"),
    run_config=RunConfig(name="exp", storage_path={uri!r}))
tuner.fit()
print("FINISHED-UNEXPECTEDLY")
"""


@pytest.mark.usefixtures("shutdown_only")
def test_tuner_restore_after_head_kill(tmp_path):
    """Kill -9 the whole head process mid-experiment; a FRESH cluster
    resumes every trial from its last synced checkpoint and finishes."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    uri = f"file://{tmp_path}/durable"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c",
         _HEAD_SCRIPT.format(repo=repo, uri=uri)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, close_fds=False)
    # wait for some (not all) checkpoints to sync
    state_uri = f"{uri}/exp/experiment_state.pkl"
    deadline = time.monotonic() + 120
    seen_progress = False
    while time.monotonic() < deadline:
        if storage.exists(state_uri):
            import pickle
            state = pickle.loads(storage.read_bytes(state_uri))
            iters = [t["last_result"].get("iter", 0)
                     for t in state["trials"]]
            if all(3 <= i for i in iters) and all(i < 10 for i in iters):
                seen_progress = True
                break
        time.sleep(0.2)
    assert seen_progress, "experiment never reached mid-progress state"
    proc.send_signal(signal.SIGKILL)  # the head dies, cluster orphaned
    proc.wait(30)

    # fresh cluster in THIS process
    import ray_tpu
    from ray_tpu import tune
    from ray_tpu.train.checkpoint import Checkpoint
    ray_tpu.init(num_cpus=2)

    resumed_from = []

    def trainable(config):
        ckpt = tune.get_checkpoint()
        start = ckpt.to_dict()["iter"] if ckpt is not None else 0
        resumed_from.append(start)
        for i in range(start + 1, 11):
            tune.report({"iter": i, "mark": config["mark"],
                         "resumed_from": start},
                        checkpoint=Checkpoint.from_dict({"iter": i}))

    tuner = tune.Tuner.restore(f"{uri}/exp", trainable)
    grid = tuner.fit()
    assert len(grid) == 2
    for i in range(2):
        res = grid[i]
        assert res.metrics["iter"] == 10
        # continued from a synced checkpoint, not from scratch
        assert res.metrics["resumed_from"] >= 3


@pytest.mark.usefixtures("ray_start_regular")
def test_jax_trainer_restore_from_uri(tmp_path):
    """JaxTrainer mirrors checkpoints to a URI and restore() resumes
    from the latest one on the same URI."""
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.config import RunConfig, ScalingConfig
    from ray_tpu.train.session import get_checkpoint, report

    uri = f"file://{tmp_path}/train_ckpts"

    def loop(config):
        ckpt = get_checkpoint()
        start = ckpt.to_dict()["step"] if ckpt is not None else 0
        for step in range(start + 1, start + 4):
            report({"step": step},
                   checkpoint=Checkpoint.from_dict({"step": step}))

    trainer = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=uri))
    r1 = trainer.fit()
    assert r1.error is None
    assert r1.metrics["step"] == 3
    assert JaxTrainer.can_restore(uri)

    resumed = JaxTrainer.restore(
        uri, loop, scaling_config=ScalingConfig(num_workers=1))
    r2 = resumed.fit()
    assert r2.error is None
    assert r2.metrics["step"] == 6  # continued 4..6 from the synced ckpt


def test_gcs_table_storage_backends(tmp_path):
    """TableStorage interface (parity model: reference gcs_table_storage.h
    over redis/in-memory store clients): memory, file, and URI backends."""
    from ray_tpu.core.table_storage import (FileTableStorage,
                                            InMemoryTableStorage,
                                            URITableStorage,
                                            make_table_storage)

    snap = {"kv": {"ns": {"k": b"v"}}, "job_counter": 3}

    mem = make_table_storage("memory", str(tmp_path / "x.pkl"))
    assert isinstance(mem, InMemoryTableStorage)
    mem.store(snap)
    assert mem.load() is None  # explicitly ephemeral

    f = make_table_storage("", str(tmp_path / "snap.pkl"))
    assert isinstance(f, FileTableStorage)
    assert f.load() is None
    f.store(snap)
    assert f.load() == snap

    uri = make_table_storage(f"file://{tmp_path}/durable_gcs", None)
    assert isinstance(uri, URITableStorage)
    assert uri.load() is None
    uri.store(snap)
    assert uri.load() == snap
    # a second instance (fresh head on another "host") sees the tables
    again = make_table_storage(f"file://{tmp_path}/durable_gcs", None)
    assert again.load() == snap
