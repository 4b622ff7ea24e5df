"""Checkpoints: interconvertible dict / directory / object-store forms.

Parity: reference ``python/ray/air/checkpoint.py`` — a ``Checkpoint`` can
be created from an in-memory dict (small states), a directory (orbax /
msgpack artifacts), or an ObjectRef, and converted between forms.  The
manager implements keep-K + score-attribute retention
(``CheckpointConfig``, reference ``air/config.py:513``).

JAX pytrees are saved in flax's msgpack format (no pickle for tensors):
``Checkpoint.from_pytree`` works out its framing and keeps it with the
array leaves as a sequence of pieces, a leaf the device-to-host transfer
left on the host carried as it is; joined, or written in order into the
directory's one file, they are byte for byte what
``flax.serialization.to_bytes`` gives, and
``flax.serialization.from_bytes`` reads it (``to_pytree``).
"""

from __future__ import annotations

import json
import os
import pickle
import re
import shutil
import tempfile
import struct
import time
from typing import AbstractSet, Any, Dict, List, Optional, Set, Tuple

import numpy as np

from ray_tpu.core import telemetry as _tm
from ray_tpu.train.config import CheckpointConfig


#: the key of a pytree's msgpack payload: ``bytes`` when it was read from
#: a directory, otherwise ``from_pytree``'s tuple of pieces (framing as
#: ``bytes``, array leaves as flat ``uint8`` arrays) that joined are them
_PYTREE = "pytree_msgpack"


def _is_raw(key: str, value: Any) -> bool:
    """Whether a checkpoint's value goes to a directory as it is (anything
    else is pickled there)."""
    return isinstance(value, bytes) or (
        key == _PYTREE and isinstance(value, tuple))


def _raw_pieces(value: Any) -> Tuple[Any, ...]:
    """A raw value as the buffers that, in order, are its bytes."""
    return value if isinstance(value, tuple) else (value,)


def _nbytes(pieces: Tuple[Any, ...]) -> int:
    return sum(memoryview(p).nbytes for p in pieces)


def _to_host(pytree: Any) -> Tuple[Any, Set[int]]:
    """The pytree's flax state dict with every ``jax.Array`` leaf on the
    host: all the transfers are started before the first is waited for.
    And the ``id`` of each host array that is nobody else's: jax made it
    for the transfer (off an accelerator, or assembling shards) and keeps
    it read-only, so neither a caller's write nor the donation of the
    device's buffer reaches it.  On the CPU backend the host array of a
    whole leaf IS the device's buffer, which a donation hands on."""
    import jax
    from flax import serialization

    # under a key, so that a bare array is a dict's value like the rest
    root = {"": serialization.to_state_dict(pytree)}
    found = []

    def walk(node: dict) -> None:
        for key, value in node.items():
            if isinstance(value, jax.Array):
                found.append((node, key, value))
            elif isinstance(value, dict):
                walk(value)

    walk(root)
    for _, _, value in found:
        value.copy_to_host_async()
    own = set()
    for node, key, value in found:
        node[key] = host = np.asarray(value)
        if not host.flags.writeable and (
                not value.is_fully_replicated
                or all(d.platform != "cpu" for d in value.devices())):
            own.add(id(host))
    return root[""], own


#: msgpack's ``fixext`` type bytes, by the length of the body
_FIXEXT = {1: 0xd4, 2: 0xd5, 4: 0xd6, 8: 0xd7, 16: 0xd8}


def _ext_header(code: int, n: int) -> bytes:
    if n in _FIXEXT:
        return struct.pack(">BB", _FIXEXT[n], code)
    if n < 1 << 8:
        return struct.pack(">BBB", 0xc7, n, code)
    if n < 1 << 16:
        return struct.pack(">BHB", 0xc8, n, code)
    return struct.pack(">BIB", 0xc9, n, code)


def _bin_header(n: int) -> bytes:
    if n < 1 << 8:
        return struct.pack(">BB", 0xc4, n)
    if n < 1 << 16:
        return struct.pack(">BH", 0xc5, n)
    return struct.pack(">BI", 0xc6, n)


def _encode(state: Any, own: AbstractSet[int] = frozenset()
            ) -> Tuple[Tuple[Any, ...], float]:
    """What ``flax.serialization.msgpack_serialize(state)`` returns, as
    the pieces that joined are it: the framing as ``bytes``, each array
    leaf as a flat ``uint8`` array; and the array bytes copied on the
    host over the array bytes of the tree.

    A C-contiguous leaf whose ``id`` is in ``own`` is carried: its piece
    is a view of it.  Any other is copied once, since its owner may go on
    to write it.  The framing (map headers, keys, an array leaf's
    ``ext 1`` around ``[shape, dtype.name, bin]``, the chunked form of a
    leaf above ``MAX_CHUNK_SIZE``) is ours; any other leaf goes through
    flax's own packer."""
    import msgpack
    from flax import serialization

    packer = msgpack.Packer(strict_types=True)
    pieces: List[Any] = []
    frame: List[bytes] = []  # framing since the last array piece
    copied = tree_bytes = 0

    def array(leaf: np.ndarray) -> None:
        """``leaf`` is ours and C-contiguous."""
        inner = (b"\x93" + msgpack.packb(leaf.shape)
                 + msgpack.packb(leaf.dtype.name) + _bin_header(leaf.nbytes))
        frame.append(_ext_header(1, len(inner) + leaf.nbytes) + inner)
        if leaf.nbytes:
            pieces.append(b"".join(frame))
            pieces.append(leaf.reshape(-1).view(np.uint8))
            frame.clear()

    def value(node: Any) -> None:
        nonlocal copied, tree_bytes
        if type(node) is dict:
            frame.append(packer.pack_map_header(len(node)))
            for key, item in node.items():
                frame.append(packer.pack(key))
                value(item)
            return
        if not isinstance(node, np.ndarray):
            frame.append(serialization.msgpack_serialize(node))
            return
        if node.dtype.hasobject or node.dtype.isalignedstruct:
            raise ValueError("Object and structured dtypes not supported "
                             "for serialization of ndarrays.")
        tree_bytes += node.nbytes
        if id(node) not in own or not node.flags.c_contiguous:
            copied += node.nbytes
            node = np.array(node, order="C")
        if node.nbytes <= serialization.MAX_CHUNK_SIZE:
            array(node)
            return
        flat = node.reshape(-1)  # flax's _chunk, over views
        n = max(1, int(serialization.MAX_CHUNK_SIZE / node.itemsize))
        frame.append(packer.pack_map_header(3)
                     + packer.pack("__msgpack_chunked_array__")
                     + packer.pack(True) + packer.pack("shape"))
        value({str(i): d for i, d in enumerate(node.shape)})
        frame.append(packer.pack("chunks")
                     + packer.pack_map_header(-(-flat.size // n)))
        for i, at in enumerate(range(0, flat.size, n)):
            frame.append(packer.pack(str(i)))
            array(flat[at:at + n])

    value(state)
    if frame:
        pieces.append(b"".join(frame))
    return tuple(pieces), copied / tree_bytes if tree_bytes else 0.0


class Checkpoint:
    def __init__(self, *, data: Optional[Dict[str, Any]] = None,
                 directory: Optional[str] = None):
        if (data is None) == (directory is None):
            raise ValueError("exactly one of data/directory required")
        self._data = data
        self._dir = directory
        #: short id that travels with the object (it pickles with it):
        #: the spans of one save, in worker and driver, share it
        self.id = os.urandom(4).hex()

    # -- constructors -----------------------------------------------------
    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Checkpoint":
        return cls(data=dict(data))

    @classmethod
    def from_directory(cls, directory: str) -> "Checkpoint":
        return cls(directory=directory)

    @classmethod
    def from_pytree(cls, pytree: Any,
                    metrics: Optional[Dict[str, Any]] = None) -> "Checkpoint":
        """The pytree as it is now, whole and encoded on return: later
        writes to its arrays (or their donation) do not reach it."""
        import jax

        ckpt = cls(data={"metrics": metrics or {}})
        with _tm.span("train", "ckpt.from_pytree", ckpt=ckpt.id) as sp:
            t0 = time.time()
            with _tm.span("train", "ckpt.d2h", ckpt=ckpt.id):
                state, own = _to_host(pytree)
            t1 = time.time()
            with _tm.span("train", "ckpt.encode", ckpt=ckpt.id):
                pieces, copies = _encode(state, own)
            sp.args.update(bytes=_nbytes(pieces),
                           leaves=len(jax.tree_util.tree_leaves(pytree)),
                           d2h_ms=1e3 * (t1 - t0),
                           encode_ms=1e3 * (time.time() - t1),
                           copies=copies,
                           pieces=sum(isinstance(p, np.ndarray)
                                      for p in pieces))
        ckpt._data[_PYTREE] = pieces
        return ckpt

    # -- accessors --------------------------------------------------------
    _MANIFEST = ".pickled_keys.json"

    def to_dict(self) -> Dict[str, Any]:
        if self._data is not None:
            return self._data
        pickled: List[str] = []
        manifest = os.path.join(self._dir, self._MANIFEST)
        if os.path.exists(manifest):
            with open(manifest) as f:
                pickled = json.load(f)
        out: Dict[str, Any] = {}
        for name in os.listdir(self._dir):
            if name == self._MANIFEST:
                continue
            with open(os.path.join(self._dir, name), "rb") as f:
                blob = f.read()
            # non-bytes values were pickled on the way to disk
            # (to_directory); un-pickle them so dict -> dir -> dict round
            # trips preserve types across process/host boundaries
            out[name] = pickle.loads(blob) if name in pickled else blob
        return out

    def to_directory(self, path: Optional[str] = None) -> str:
        if self._dir is not None:
            if path is None or \
                    os.path.abspath(path) == os.path.abspath(self._dir):
                return self._dir
            shutil.copytree(self._dir, path, dirs_exist_ok=True)
            return path
        path = path or tempfile.mkdtemp(prefix="rtpu_ckpt_")
        os.makedirs(path, exist_ok=True)
        pickled: List[str] = []
        for key, value in self._data.items():
            if not _is_raw(key, value):
                value = pickle.dumps(value)
                pickled.append(key)
            with open(os.path.join(path, key), "wb") as f:
                f.writelines(_raw_pieces(value))
        with open(os.path.join(path, self._MANIFEST), "w") as f:
            json.dump(pickled, f)
        return path

    def as_directory(self):
        """Context manager yielding a directory view (reference
        ``Checkpoint.as_directory``); temp dirs for dict-backed
        checkpoints are cleaned up on exit."""
        import contextlib

        @contextlib.contextmanager
        def _cm():
            if self._dir is not None:
                yield self._dir
                return
            path = self.to_directory()
            try:
                yield path
            finally:
                shutil.rmtree(path, ignore_errors=True)

        return _cm()

    def to_pytree(self, target: Any) -> Any:
        """Restore a pytree saved by ``from_pytree`` (``target`` supplies
        the structure)."""
        from flax import serialization

        blob = self.to_dict()[_PYTREE]
        if not _is_raw(_PYTREE, blob):
            blob = pickle.loads(blob)
        return serialization.from_bytes(target, b"".join(_raw_pieces(blob)))

    @property
    def metrics(self) -> Dict[str, Any]:
        data = self._data or {}
        m = data.get("metrics", {})
        return m if isinstance(m, dict) else pickle.loads(m)

    def __repr__(self) -> str:
        kind = "dict" if self._data is not None else f"dir:{self._dir}"
        return f"Checkpoint({kind})"


class CheckpointManager:
    """Keep-K checkpoint retention with optional score ordering.

    With ``storage_uri`` set, every registered checkpoint is mirrored to
    durable storage (``ray_tpu.air.storage``) and retention prunes the
    mirror too — a lost host loses nothing (parity: the reference's
    checkpoint upload through ``RunConfig.storage_path``).
    """

    def __init__(self, directory: str,
                 config: Optional[CheckpointConfig] = None,
                 storage_uri: Optional[str] = None):
        self.directory = directory
        self.config = config or CheckpointConfig()
        self.storage_uri = storage_uri
        os.makedirs(directory, exist_ok=True)
        self._entries: List[Tuple[float, str, Dict[str, Any]]] = []
        # Resume numbering after any checkpoints already present locally
        # or at the mirror — a restored run that restarted at 1 would
        # overwrite the earlier mirror files, and a later restore's
        # max(names) would then pick a STALE checkpoint.
        self._counter = self._existing_max_index()

    _NAME_RE = re.compile(r"^checkpoint_(\d{6})$")

    @classmethod
    def checkpoint_index(cls, name: str) -> Optional[int]:
        """Index of a well-formed checkpoint dir name (None for residue
        like ``checkpoint_000003.old`` / ``.tmp``)."""
        m = cls._NAME_RE.match(name)
        return int(m.group(1)) if m else None

    def _existing_max_index(self) -> int:
        names = list(os.listdir(self.directory))
        if self.storage_uri:
            try:
                from ray_tpu.air import storage
                backend, path = storage.get_storage(self.storage_uri)
                names += backend.listdir(path)
            except Exception:  # noqa: BLE001 — mirror scan is best-effort
                pass
        return max((self.checkpoint_index(n) or 0 for n in names),
                   default=0)

    def register(self, checkpoint: Checkpoint,
                 metrics: Optional[Dict[str, Any]] = None) -> str:
        with _tm.span("train", "ckpt.register", ckpt=checkpoint.id) as sp:
            self._counter += 1
            path = os.path.join(self.directory,
                                f"checkpoint_{self._counter:06d}")
            checkpoint.to_directory(path)
            metrics = dict(metrics or checkpoint.metrics)
            with open(os.path.join(path, ".metrics.json"), "w") as f:
                json.dump({k: v for k, v in metrics.items()
                           if isinstance(v, (int, float, str, bool))}, f)
            if self.storage_uri:
                from ray_tpu.air import storage
                storage.upload_dir(path, storage.join(
                    self.storage_uri, os.path.basename(path)))
            score = self._score(metrics)
            self._entries.append((score, path, metrics))
            self._enforce_retention()
            sp.args.update(path=path, bytes=sum(
                _nbytes(_raw_pieces(v))
                for k, v in (checkpoint._data or {}).items()
                if _is_raw(k, v)))
        return path

    def _score(self, metrics: Dict[str, Any]) -> float:
        attr = self.config.checkpoint_score_attribute
        if attr is None:
            return float(self._counter)  # recency
        value = float(metrics.get(attr, float("-inf")))
        return value if self.config.checkpoint_score_order == "max" else -value

    def _enforce_retention(self) -> None:
        keep = self.config.num_to_keep
        if keep is None or len(self._entries) <= keep:
            return
        self._entries.sort(key=lambda e: e[0], reverse=True)
        for _, path, _ in self._entries[keep:]:
            shutil.rmtree(path, ignore_errors=True)
            if self.storage_uri:
                from ray_tpu.air import storage
                try:
                    backend, spath = storage.get_storage(storage.join(
                        self.storage_uri, os.path.basename(path)))
                    backend.delete(spath)
                except Exception:  # noqa: BLE001 — prune is best-effort
                    pass
        self._entries = self._entries[:keep]

    def best_checkpoint(self) -> Optional[Checkpoint]:
        if not self._entries:
            return None
        best = max(self._entries, key=lambda e: e[0])
        return Checkpoint.from_directory(best[1])

    def latest_checkpoint(self) -> Optional[Checkpoint]:
        if not self._entries:
            return None
        latest = max(self._entries, key=lambda e: e[1])
        return Checkpoint.from_directory(latest[1])
