"""``Checkpoint.from_pytree`` works out flax's msgpack format itself and
keeps it as pieces, framing and leaves: joined, and in the file a
directory gets, the bytes must be ``flax.serialization.to_bytes``'s, for
every kind of leaf a training loop can hand it; a leaf the transfer left
on the host must be carried, not copied, any other copied once; and the
leaves must ride the object plane out of band."""

import collections
import gc

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


def _over_devices(x, spec=P("d")):
    """``x`` split along its first axis over all the devices: jax
    assembles the host array of such a leaf itself."""
    mesh = Mesh(np.array(jax.devices()), ("d",))
    return jax.device_put(x, NamedSharding(mesh, spec))


def _sharded():
    x = jnp.arange(len(jax.devices()) * 6, dtype=jnp.float32).reshape(-1, 3)
    return {"w": _over_devices(x), "replicated": _over_devices(x[:2], P())}


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


def _payload(ckpt):
    return ckpt.to_dict()["pytree_msgpack"]


def _arrays(pieces):
    return [p for p in pieces if isinstance(p, np.ndarray)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_payload_is_flax_to_bytes_byte_for_byte(case, monkeypatch, tmp_path):
    build, chunk = CASES[case]
    if chunk is not None:
        monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", chunk)
    tree = build()
    want = serialization.to_bytes(tree)
    ckpt = Checkpoint.from_pytree(tree)
    pieces = _payload(ckpt)
    assert isinstance(pieces, tuple)
    for piece in pieces:
        assert type(piece) is bytes or (
            type(piece) is np.ndarray and piece.dtype == np.uint8
            and piece.ndim == 1 and piece.flags.c_contiguous and piece.size)
    # framing between two leaves is one piece
    assert not any(type(a) is type(b) is bytes
                   for a, b in zip(pieces, pieces[1:]))
    assert b"".join(pieces) == want
    # the directory's one file is the pieces in order
    ckpt.to_directory(str(tmp_path))
    with open(tmp_path / "pytree_msgpack", "rb") as f:
        assert f.read() == want
    # and flax reads it back, from the pieces as from the file
    for held in (ckpt, Checkpoint.from_directory(str(tmp_path))):
        back = held.to_pytree(tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _from_pytree_args(tree):
    """The checkpoint, and the arguments ``from_pytree`` left on its span."""
    from ray_tpu.core import telemetry

    telemetry.drain_spans("test")
    ckpt = Checkpoint.from_pytree(tree)
    (row,) = [r for r in telemetry.drain_spans("test")
              if r["name"] == "ckpt.from_pytree"]
    return ckpt, row["args"]


def test_a_leaf_the_transfer_left_is_carried_and_any_other_copied_once(
        monkeypatch):
    """``copies``: array bytes copied on the host over the tree's."""
    sharded = _sharded()["w"]  # jax assembles it into a host array of its own
    state, own = checkpoint_mod._to_host({"w": sharded})
    assert own == {id(state["w"])} and not state["w"].flags.writeable
    pieces, copies = checkpoint_mod._encode(state, own)
    assert copies == 0.0
    (carried,) = _arrays(pieces)
    assert np.shares_memory(carried, state["w"])
    # through the front door: the same, and the span says so
    ckpt, args = _from_pytree_args({"w": sharded})
    assert args["copies"] == 0.0 and args["pieces"] == 1 \
        and args["leaves"] == 1
    assert args["bytes"] == len(serialization.to_bytes({"w": sharded}))
    assert np.shares_memory(_arrays(_payload(ckpt))[0], np.asarray(sharded))

    # a numpy leaf is its caller's, a whole leaf of the CPU backend is
    # the device's own buffer: neither is in ``own``, both are copied
    tree = {"a": jnp.ones((16, 8)), "n": np.arange(12.0), "s": 3,
            "t": np.arange(12.0).reshape(3, 4).T}
    state, own = checkpoint_mod._to_host(tree)
    assert not own
    pieces, copies = checkpoint_mod._encode(state, own)
    assert copies == 1.0 and len(_arrays(pieces)) == 3
    assert not any(np.shares_memory(p, leaf) for p in _arrays(pieces)
                   for leaf in (state["a"], state["n"], state["t"]))
    # the share, in a tree of both kinds
    _, args = _from_pytree_args({"w": sharded, "n": np.zeros(
        sharded.size, np.float32)})
    assert args["copies"] == 0.5 and args["pieces"] == 2
    # a leaf of its own that is not C-contiguous is copied all the same
    t = np.arange(12.0).reshape(3, 4).T
    assert checkpoint_mod._encode({"t": t}, {id(t)})[1] == 1.0
    # flattened and chunked: still once
    monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", 32)
    assert checkpoint_mod._encode({"t": t}, {id(t)})[1] == 1.0
    assert checkpoint_mod._encode({})[1] == 0.0
    assert checkpoint_mod._encode({"s": 3, "e": np.zeros((0, 3))})[1] == 0.0


def test_the_chunks_of_a_carried_leaf_are_views_of_it(monkeypatch):
    monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", 40)
    tree = {"w": _sharded()["w"]}
    state, own = checkpoint_mod._to_host(tree)
    pieces, copies = checkpoint_mod._encode(state, own)
    chunks = _arrays(pieces)
    assert copies == 0.0 and len(chunks) == -(-state["w"].nbytes // 40)
    flat = state["w"].reshape(-1).view(np.uint8)
    at = 0
    for chunk in chunks:
        assert np.shares_memory(chunk, state["w"])
        np.testing.assert_array_equal(chunk, flat[at:at + chunk.size])
        at += chunk.size
    assert b"".join(pieces) == serialization.to_bytes(tree)


def test_object_leaves_are_refused_as_flax_refuses_them():
    with pytest.raises(ValueError, match="Object and structured"):
        Checkpoint.from_pytree({"o": np.array([{}, 1], dtype=object)})
    with pytest.raises(TypeError):
        Checkpoint.from_pytree({"s": {1, 2}})


@pytest.mark.parametrize("kind", ["numpy", "jax_deleted", "jax_donated",
                                  "sharded_donated"])
def test_what_is_saved_is_the_tree_at_the_call(kind):
    """The loop goes on to write what it saved, or to donate it to the
    next step, which writes its result where the old value lay."""
    n = 4096 * len(jax.devices())
    w = np.arange(n, dtype=np.float32)
    if kind == "numpy":
        leaf = w
    elif kind == "sharded_donated":  # carried, not copied
        leaf = _over_devices(w)
    else:
        leaf = jnp.asarray(w)
    tree = {"w": leaf}
    ckpt = Checkpoint.from_pytree(tree)
    before = b"".join(_payload(ckpt))
    assert before == serialization.to_bytes({"w": w.copy()})
    if kind == "numpy":
        w += 100.0
    elif kind == "jax_deleted":
        leaf.delete()
    else:
        # on the CPU backend ``np.asarray(leaf)`` is a view of the device's
        # buffer, ``_to_host`` leaves its views in garbage that waits for
        # a collection, and a buffer with a view outstanding is silently
        # not donated: collect, so that the donation below is one
        gc.collect()
        step = jax.jit(lambda x: x + 100.0, donate_argnums=0)
        for _ in range(3):
            leaf, old = step(leaf), leaf
            assert old.is_deleted()
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.arange(n, dtype=np.float32) + 300.0)
    assert b"".join(_payload(ckpt)) == before
    np.testing.assert_array_equal(
        ckpt.to_pytree({"w": np.zeros(n, np.float32)})["w"],
        np.arange(n, dtype=np.float32))


def test_the_payload_rides_out_of_band():
    from ray_tpu.core import serialization as wire

    tree = {"w": np.arange(1 << 16, dtype=np.float32), "b": np.ones(3),
            "h": jnp.ones((128,), jnp.bfloat16),
            # carried (a host array of jax's own) and copied alike
            "v": _over_devices(
                jnp.ones((len(jax.devices()), 300), jnp.bfloat16)),
            "edge": np.zeros(512, np.uint8), "under": np.zeros(511, np.uint8)}
    ckpt = Checkpoint.from_pytree(tree, metrics={"loss": 1.0})
    ser = wire.serialize([{"metrics": {"loss": 1.0}, "checkpoint": ckpt}])
    assert len(ser.meta) < 2048
    # one buffer a leaf of 512 bytes or more, as large as the leaf
    assert [memoryview(b).nbytes for b in ser.buffers] == [
        (1 << 16) * 4, len(jax.devices()) * 600, 512]
    assert {bytes(b) for b in ser.buffers} <= {
        bytes(p) for p in _arrays(_payload(ckpt))}
    (item,), is_exc = wire.deserialize(ser.to_bytes())
    assert not is_exc
    got = item["checkpoint"]
    assert got.id == ckpt.id and got.metrics == {"loss": 1.0}
    payload = _payload(got)
    assert [type(p) for p in payload] == [type(p) for p in _payload(ckpt)]
    # views over the wire's buffers, not copies out of a pickle
    assert all(not p.flags.owndata and not p.flags.writeable
               for p in _arrays(payload) if p.nbytes >= 512)
    assert b"".join(payload) == serialization.to_bytes(tree)


def test_directory_round_trips_from_a_read_only_view(tmp_path):
    tree = {"w": np.arange(10, dtype=np.float32), "n": 3,
            "b": np.ones(200, jnp.bfloat16), "k": {"z": np.arange(5)}}
    ckpt = Checkpoint.from_pytree(tree, metrics={"loss": 0.5})
    # what the driver holds: the framing, and a read-only view over the
    # store's mapping for each leaf
    views = tuple(p if isinstance(p, bytes)
                  else np.frombuffer(p.tobytes(), np.uint8)
                  for p in _payload(ckpt))
    assert len(_arrays(views)) == 3
    assert not any(v.flags.writeable for v in _arrays(views))
    held = Checkpoint.from_dict({**ckpt.to_dict(), "pytree_msgpack": views,
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
                "b": jnp.full((3,), i, jnp.bfloat16), "step": i,
                "layers": [jnp.full((1 << 16,), i + k, jnp.float32)
                           for k in range(12)]}
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
    # the pinned views of both payloads, one a leaf, are gone with the
    # rows that carried them (a release is a message to the raylet: give
    # it time)
    deadline = time.time() + 20
    while _store_used() > before and time.time() < deadline:
        time.sleep(0.1)
    assert _store_used() <= before

    for i in (1, 2):
        want = {"w": np.arange(1 << 20, dtype=np.float32) * i,
                "b": np.full((3,), i, jnp.bfloat16), "step": i,
                "layers": [np.full((1 << 16,), i + k, np.float32)
                           for k in range(12)]}
        path = tmp_path / f"checkpoint_{i:06d}"
        with open(path / "pytree_msgpack", "rb") as f:
            assert f.read() == serialization.to_bytes(want)
        back = Checkpoint.from_directory(str(path)).to_pytree(want)
        np.testing.assert_array_equal(back["w"], want["w"])
        np.testing.assert_array_equal(back["b"], want["b"])
        np.testing.assert_array_equal(back["layers"][11], want["layers"][11])
        assert back["step"] == i
    latest = result.checkpoint.to_pytree(want)
    np.testing.assert_array_equal(latest["w"], want["w"])
