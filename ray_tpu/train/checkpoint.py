"""Checkpoints: interconvertible dict / directory / object-store forms.

Parity: reference ``python/ray/air/checkpoint.py`` — a ``Checkpoint`` can
be created from an in-memory dict (small states), a directory (orbax /
msgpack artifacts), or an ObjectRef, and converted between forms.  The
manager implements keep-K + score-attribute retention
(``CheckpointConfig``, reference ``air/config.py:513``).

JAX pytrees serialize with flax's msgpack (no pickle for tensors);
``save_pytree`` / ``load_pytree`` are the convenience entry points used by
``JaxTrainer`` workers.
"""

from __future__ import annotations

import json
import os
import pickle
import re
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core import telemetry as _tm
from ray_tpu.train.config import CheckpointConfig


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
        import jax
        from flax import serialization

        ckpt = cls(data={"metrics": metrics or {}})
        with _tm.span("train", "ckpt.from_pytree", ckpt=ckpt.id) as sp:
            blob = serialization.to_bytes(pytree)
            sp.args.update(bytes=len(blob),
                           leaves=len(jax.tree_util.tree_leaves(pytree)))
        ckpt._data["pytree_msgpack"] = blob
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
            if isinstance(value, bytes):
                blob = value
            else:
                blob = pickle.dumps(value)
                pickled.append(key)
            with open(os.path.join(path, key), "wb") as f:
                f.write(blob)
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

        data = self.to_dict()
        blob = data["pytree_msgpack"]
        if not isinstance(blob, bytes):
            blob = pickle.loads(blob)
        return serialization.from_bytes(target, blob)

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
                len(v) for v in (checkpoint._data or {}).values()
                if isinstance(v, bytes)))
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
