"""``Checkpoint.from_pytree`` writes flax's msgpack format itself, in one
pass into one host buffer: the bytes must be ``flax.serialization
.to_bytes``'s, for every kind of leaf a training loop can hand it, and
the buffer must ride the object plane out of band."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.train import Checkpoint
from ray_tpu.train import checkpoint as checkpoint_mod

Point = collections.namedtuple("Point", ["x", "y"])


def _sharded():
    mesh = Mesh(np.array(jax.devices()), ("d",))
    x = jnp.arange(len(jax.devices()) * 6, dtype=jnp.float32).reshape(-1, 3)
    return {"w": jax.device_put(x, NamedSharding(mesh, P("d", None))),
            "replicated": jax.device_put(x[:2], NamedSharding(mesh, P()))}


#: name -> (builder of the tree, flax's MAX_CHUNK_SIZE for the case)
CASES = {
    "f32": (lambda: {"w": np.linspace(0, 1, 12, dtype=np.float32)
                     .reshape(3, 4)}, None),
    "bf16": (lambda: {"w": jnp.arange(10, dtype=jnp.bfloat16)}, None),
    "int32": (lambda: {"i": np.arange(7, dtype=np.int32)}, None),
    "bool": (lambda: {"b": np.array([True, False, True])}, None),
    "jax_arrays": (lambda: {"a": jnp.ones((4, 5)), "n": jnp.int32(3)}, None),
    # () float32 makes a 16-byte ext body: the fixext 16 header
    "zero_d": (lambda: {"f4": np.float32(1.5) * np.ones(()),
                        "f8": np.ones(()), "i1": np.zeros((), np.int8)},
               None),
    "empty": (lambda: {"e": np.zeros((0, 3), np.float32),
                       "e1": np.zeros((0,), np.int8)}, None),
    "non_contiguous": (lambda: {
        "t": np.arange(24, dtype=np.float32).reshape(4, 6).T,
        "s": np.arange(40, dtype=np.int64)[::3]}, None),
    "python_scalars": (lambda: {"i": 3, "f": 2.5, "s": "text", "n": None,
                                "t": True, "c": 1 + 2j, "big": 2 ** 40},
                       None),
    "numpy_scalars": (lambda: {"f": np.float32(2.5), "i": np.int64(-7),
                               "b": np.bool_(True)}, None),
    "nested": (lambda: {"d": {"l": [np.ones(3), {"x": np.zeros(2)}],
                              "t": (np.arange(3), 4)},
                        "p": Point(np.ones(2), 5),
                        "z": {}, "zl": []}, None),
    "frozen_dict": (lambda: FrozenDict(
        {"params": {"k": np.ones((2, 2), np.float32)}}), None),
    "bare_array": (lambda: jnp.arange(5.0), None),
    "bare_scalar": (lambda: 7, None),
    # header widths: bin 8/16/32 and ext 8/16/32
    "widths": (lambda: {"b8": np.zeros(200, np.uint8),
                        "b16": np.zeros(300, np.uint8),
                        "e16": np.zeros(65000, np.uint8),
                        "b32": np.zeros(70000, np.uint8)}, None),
    "many_keys": (lambda: {f"k{i}": np.full(i % 5, i, np.int16)
                           for i in range(210)}, None),
    "chunked": (lambda: {"big": np.arange(100, dtype=np.float32)
                         .reshape(10, 10), "small": np.arange(4)}, 64),
    "chunked_non_contiguous": (lambda: {
        "big": np.arange(100, dtype=np.float32).reshape(10, 10).T}, 64),
    "chunked_bare": (lambda: np.arange(50, dtype=np.int64), 100),
    # a chunk size below the item size: one element a chunk
    "chunked_tiny": (lambda: {"v": np.arange(5, dtype=np.float64)}, 3),
    "chunked_jax": (lambda: {"w": jnp.arange(64, dtype=jnp.bfloat16)}, 32),
    "sharded": (_sharded, None),
    "sharded_chunked": (_sharded, 40),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_payload_is_flax_to_bytes_byte_for_byte(case, monkeypatch):
    build, chunk = CASES[case]
    if chunk is not None:
        monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", chunk)
    tree = build()
    want = serialization.to_bytes(tree)
    ckpt = Checkpoint.from_pytree(tree)
    blob = ckpt.to_dict()["pytree_msgpack"]
    assert isinstance(blob, np.ndarray) and blob.dtype == np.uint8 \
        and blob.ndim == 1 and blob.flags.c_contiguous
    assert blob.tobytes() == want
    # and flax reads it back, from the array as from bytes
    back = ckpt.to_pytree(tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_every_array_byte_is_copied_once(monkeypatch):
    tree = {"a": jnp.ones((16, 8)), "t": np.arange(12.0).reshape(3, 4).T,
            "s": 3}
    state = checkpoint_mod._to_host(tree)
    assert all(isinstance(v, (np.ndarray, int)) for v in state.values())
    _, copies = checkpoint_mod._encode(state)
    assert copies == 1.0
    # a leaf that has to be flattened before it is chunked is copied twice
    monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", 32)
    _, copies = checkpoint_mod._encode({"t": tree["t"]})
    assert copies == 2.0
    assert checkpoint_mod._encode({})[1] == 1.0


def test_a_leaf_is_copied_in_pieces(monkeypatch):
    """No single numpy call moves more than ``_COPY_BYTES``."""
    monkeypatch.setattr(checkpoint_mod, "_COPY_BYTES", 48)
    tree = {"c": np.arange(100, dtype=np.float32),
            "t": np.arange(120, dtype=np.float64).reshape(10, 12).T,
            "wide": np.arange(64, dtype=np.float64).reshape(2, 32).T}
    blob = Checkpoint.from_pytree(tree).to_dict()["pytree_msgpack"]
    assert blob.tobytes() == serialization.to_bytes(tree)


def test_object_leaves_are_refused_as_flax_refuses_them():
    with pytest.raises(ValueError, match="Object and structured"):
        Checkpoint.from_pytree({"o": np.array([{}, 1], dtype=object)})
    with pytest.raises(TypeError):
        Checkpoint.from_pytree({"s": {1, 2}})


@pytest.mark.parametrize("kind", ["numpy", "jax"])
def test_what_is_saved_is_the_tree_at_the_call(kind):
    """The loop goes on to write (or donate) what it saved."""
    w = np.arange(6, dtype=np.float32)
    tree = {"w": w if kind == "numpy" else jnp.asarray(w)}
    ckpt = Checkpoint.from_pytree(tree)
    before = ckpt.to_dict()["pytree_msgpack"].tobytes()
    if kind == "numpy":
        w += 100.0
    else:
        tree["w"].delete()
    assert ckpt.to_dict()["pytree_msgpack"].tobytes() == before
    np.testing.assert_array_equal(
        ckpt.to_pytree({"w": np.zeros(6, np.float32)})["w"],
        np.arange(6, dtype=np.float32))


def test_the_payload_rides_out_of_band():
    from ray_tpu.core import serialization as wire

    tree = {"w": np.arange(1 << 16, dtype=np.float32), "b": np.ones(3)}
    ckpt = Checkpoint.from_pytree(tree, metrics={"loss": 1.0})
    blob = ckpt.to_dict()["pytree_msgpack"]
    ser = wire.serialize([{"metrics": {"loss": 1.0}, "checkpoint": ckpt}])
    assert len(ser.meta) < 1024
    assert [memoryview(b).nbytes for b in ser.buffers] == [blob.nbytes]
    (item,), is_exc = wire.deserialize(ser.to_bytes())
    assert not is_exc
    got = item["checkpoint"]
    assert got.id == ckpt.id and got.metrics == {"loss": 1.0}
    payload = got.to_dict()["pytree_msgpack"]
    # a view over the wire's buffer, not a copy out of a pickle
    assert isinstance(payload, np.ndarray) and not payload.flags.owndata
    assert payload.tobytes() == blob.tobytes()


def test_directory_round_trips_from_a_read_only_view(tmp_path):
    tree = {"w": np.arange(10, dtype=np.float32), "n": 3}
    ckpt = Checkpoint.from_pytree(tree, metrics={"loss": 0.5})
    blob = ckpt.to_dict()["pytree_msgpack"]
    # what the driver holds: a read-only view over the store's mapping
    view = np.frombuffer(blob.tobytes(), np.uint8)
    assert not view.flags.writeable
    held = Checkpoint.from_dict({**ckpt.to_dict(), "pytree_msgpack": view,
                                 "u8": np.arange(4, dtype=np.uint8)})
    path = held.to_directory(str(tmp_path / "c"))
    with open(tmp_path / "c" / "pytree_msgpack", "rb") as f:
        assert f.read() == serialization.to_bytes(tree)
    back = Checkpoint.from_directory(path).to_dict()
    assert back["pytree_msgpack"] == serialization.to_bytes(tree)
    assert back["metrics"] == {"loss": 0.5}
    # any other array of a dict checkpoint is pickled, and comes back
    np.testing.assert_array_equal(back["u8"], np.arange(4, dtype=np.uint8))
    assert isinstance(back["u8"], np.ndarray)
    again = Checkpoint.from_dict(back)
    restored = again.to_pytree(tree)
    np.testing.assert_array_equal(restored["w"], tree["w"])
    assert restored["n"] == 3
    # dict -> directory -> dict -> directory: the same files
    second = again.to_directory(str(tmp_path / "d"))
    assert Checkpoint.from_directory(second).to_dict().keys() == back.keys()
    with open(tmp_path / "d" / "pytree_msgpack", "rb") as f:
        assert f.read() == serialization.to_bytes(tree)


def test_a_checkpoint_written_with_flax_to_bytes_restores(tmp_path):
    """A checkpoint an earlier version left on a disk."""
    tree = {"w": np.arange(5, dtype=np.float32), "n": 2}
    old = Checkpoint.from_dict({"metrics": {},
                                "pytree_msgpack": serialization.to_bytes(tree)})
    path = old.to_directory(str(tmp_path / "old"))
    for ckpt in (old, Checkpoint.from_directory(path)):
        back = ckpt.to_pytree(tree)
        np.testing.assert_array_equal(back["w"], tree["w"])


# ---------------------------------------------------------------------------
# the whole ride: worker -> object plane -> driver -> disk
# ---------------------------------------------------------------------------

def _saving_loop(config):
    import jax.numpy as jnp

    from ray_tpu.train import Checkpoint, session

    for i in (1, 2):
        tree = {"w": jnp.arange(1 << 20, dtype=jnp.float32) * i,  # 4 MB
                "b": jnp.full((3,), i, jnp.bfloat16), "step": i}
        session.report({"step": i},
                       checkpoint=Checkpoint.from_pytree(tree))


def _store_used():
    from ray_tpu.experimental.state import object_store_stats

    return sum(s["used"] for s in object_store_stats())


def test_a_save_lands_whole_and_the_driver_lets_go_of_the_store(
        tmp_path, shutdown_only):
    import time

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                 _system_config={"max_direct_call_object_size": 1024})
    before = _store_used()
    result = JaxTrainer(
        _saving_loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path))).fit()
    assert result.error is None
    # the pinned views of both payloads are gone with the rows that
    # carried them (a release is a message to the raylet: give it time)
    deadline = time.time() + 20
    while _store_used() > before and time.time() < deadline:
        time.sleep(0.1)
    assert _store_used() <= before

    for i in (1, 2):
        want = {"w": np.arange(1 << 20, dtype=np.float32) * i,
                "b": np.full((3,), i, jnp.bfloat16), "step": i}
        path = tmp_path / f"checkpoint_{i:06d}"
        with open(path / "pytree_msgpack", "rb") as f:
            assert f.read() == serialization.to_bytes(want)
        back = Checkpoint.from_directory(str(path)).to_pytree(want)
        np.testing.assert_array_equal(back["w"], want["w"])
        np.testing.assert_array_equal(back["b"], want["b"])
        assert back["step"] == i
    latest = result.checkpoint.to_pytree(want)
    np.testing.assert_array_equal(latest["w"], want["w"])
