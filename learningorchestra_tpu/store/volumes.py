"""Volume-backed binary artifact storage.

The reference persists model instances and transform outputs as files on
service-type-keyed Docker volumes — keras SavedModel when possible, dill
otherwise (reference: microservices/binary_executor_image/utils.py:199-251,
model_image/utils.py:186-210).  Here the same contract is a host directory
tree keyed by service type, with three formats:

- ``pytree``: JAX pytrees (model params / optimizer states) saved as an
  orbax-style checkpoint directory — the TPU-native replacement for keras
  SavedModel, shard-friendly and HBM↔host explicit;
- ``dill``: arbitrary Python objects (classical estimators, tuples of
  arrays) — the reference's fallback path, kept for parity;
- ``bytes``: raw streams (generic dataset ingest,
  database_api_image/database.py:61-83).

An object bigger than :data:`PART_BYTES` is written as several files:
hosts cap the size of one file (``RLIMIT_FSIZE`` — the write fails with
``EFBIG``), and a BERT-base train artifact is ~1.3 GB with its Adam
state.  The artifact's path then holds a small manifest naming the part
files next to it; anything that fits one part stays the plain dill file
it always was.
"""

from __future__ import annotations

import io
import json
import os
import re
import shutil
import uuid
from pathlib import Path
from typing import Any

import dill

# Same grammar as DocumentStore collection names: binary names come from
# REST request JSON and become file names — no separators, no traversal.
_NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]*$")


def _validate_name(name: str) -> str:
    if not _NAME_RE.match(name or "") or ".." in name:
        raise ValueError(f"invalid artifact name: {name!r}")
    return name


#: Largest file an object artifact is written as (see module docstring).
PART_BYTES = 16 << 20
#: First bytes of a manifest; a pickle starts with ``\x80``.
_MANIFEST_MAGIC = b"LO-ARTIFACT-PARTS\n"


def max_file_bytes() -> int:
    """The most one written file may hold: :data:`PART_BYTES`, or the
    process's file-size limit if that is lower."""
    try:
        import resource

        soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    except (ImportError, OSError, ValueError):
        return PART_BYTES
    if soft != resource.RLIM_INFINITY and 0 < soft < PART_BYTES:
        return soft
    return PART_BYTES


def _parts_dir(path: Path) -> Path:
    # Leading '.' can never collide with an artifact binary: _NAME_RE
    # requires names to start with an alphanumeric.
    return path.with_name("." + path.name + ".parts")


def _manifest_of(path: Path) -> dict | None:
    """The manifest at ``path``, or None where it is a plain file."""
    with open(path, "rb") as fh:
        if fh.read(len(_MANIFEST_MAGIC)) != _MANIFEST_MAGIC:
            return None
        return json.loads(fh.read())


class _PartWriter:
    """The file ``dill.dump`` writes to: rolls over to a new part file
    ``<gen>-<i>`` in ``directory`` every ``part_bytes``."""

    def __init__(self, directory: Path, gen: str, part_bytes: int):
        self.directory, self.gen, self.part_bytes = directory, gen, part_bytes
        self.parts: list[Path] = []
        self._fh = None
        self._room = 0

    def write(self, data) -> int:
        view = memoryview(data).cast("B")
        done = 0
        while done < len(view):
            if not self._room:
                self.close()
                self.directory.mkdir(exist_ok=True)
                self.parts.append(
                    self.directory / f"{self.gen}-{len(self.parts):05d}"
                )
                self._fh = open(self.parts[-1], "wb")
                self._room = self.part_bytes
            chunk = view[done:done + self._room]
            self._fh.write(chunk)
            self._room -= len(chunk)
            done += len(chunk)
        return done

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class _PartReader(io.RawIOBase):
    """The part files of one manifest as one stream, opened one at a
    time."""

    def __init__(self, paths: list[Path]):
        self._paths = iter(paths)
        self._fh = None

    def readable(self) -> bool:
        return True

    def readinto(self, buf) -> int:
        while True:
            if self._fh is None:
                path = next(self._paths, None)
                if path is None:
                    return 0
                self._fh = open(path, "rb", buffering=0)
            n = self._fh.readinto(buf)
            if n:
                return n
            self._fh.close()
            self._fh = None

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        super().close()


# Service-type → volume directory, mirroring the reference's six named
# volumes (binary_executor_image/Dockerfile:10-13, docker-compose.yml:355-363).
VOLUME_KEYS = (
    "datasets",
    "models",
    "binaries",
    "transform",
    "explore",
    "code_executions",
)


def volume_key_for_type(artifact_type: str) -> str:
    """Map an artifact type like ``train/tensorflow`` to its volume."""
    head = artifact_type.split("/", 1)[0]
    return {
        "dataset": "datasets",
        "model": "models",
        "train": "binaries",
        "tune": "binaries",
        "evaluate": "binaries",
        "predict": "binaries",
        "builder": "binaries",
        "transform": "transform",
        "explore": "explore",
        "function": "code_executions",
    }.get(head, "binaries")


class VolumeStorage:
    def __init__(self, root: str | Path):
        self.root = Path(root).expanduser()
        self.part_bytes = max_file_bytes()
        for key in VOLUME_KEYS:
            (self.root / key).mkdir(parents=True, exist_ok=True)

    def path_for(self, artifact_type: str, name: str) -> Path:
        return self.root / volume_key_for_type(artifact_type) / _validate_name(
            name
        )

    # -- dill (parity fallback) ----------------------------------------------

    def save_object(self, artifact_type: str, name: str, obj: Any) -> Path:
        return self._dump_atomic(self.path_for(artifact_type, name), obj)

    def _dump_atomic(self, path: Path, obj: Any) -> Path:
        """Publish by rename: a PATCH re-run rewriting a binary while a
        concurrent job dill-loads it must never expose a torn file
        (same discipline as the shard writer's os.replace).  The parts
        are complete before the one rename that makes them the
        artifact — of the only part where the object fits one, of the
        manifest otherwise — and the parts it supersedes go after."""
        path.parent.mkdir(parents=True, exist_ok=True)
        directory = _parts_dir(path)
        old = _manifest_of(path) if path.is_file() else None
        writer = _PartWriter(directory, uuid.uuid4().hex, self.part_bytes)
        try:
            try:
                dill.dump(obj, writer)
            finally:
                writer.close()
            if len(writer.parts) == 1:
                os.replace(writer.parts[0], path)
            else:
                tmp = directory / f"{writer.gen}-manifest"
                tmp.write_bytes(_MANIFEST_MAGIC + json.dumps({
                    "gen": writer.gen, "parts": len(writer.parts),
                }).encode())
                os.replace(tmp, path)
        except BaseException:
            for part in writer.parts:
                part.unlink(missing_ok=True)
            raise
        if old is not None:
            for i in range(old["parts"]):
                (directory / f"{old['gen']}-{i:05d}").unlink(missing_ok=True)
        try:
            directory.rmdir()  # nothing left in it: a one-part artifact
        except OSError:
            pass
        return path

    def read_object(self, artifact_type: str, name: str) -> Any:
        path = self.path_for(artifact_type, name)
        directory = _parts_dir(path)
        for attempt in range(3):
            with open(path, "rb") as fh:
                if fh.read(len(_MANIFEST_MAGIC)) != _MANIFEST_MAGIC:
                    fh.seek(0)
                    return dill.load(fh)
                manifest = json.loads(fh.read())
            stream = io.BufferedReader(_PartReader([
                directory / f"{manifest['gen']}-{i:05d}"
                for i in range(manifest["parts"])
            ]), buffer_size=1 << 20)
            try:
                return dill.load(stream)
            except FileNotFoundError:
                # A re-run published a new generation and removed this
                # one's parts mid-read: load what it published.
                if attempt == 2 or _manifest_of(path) == manifest:
                    raise
            finally:
                stream.close()

    # -- pytree checkpoints (TPU-native model persistence) --------------------

    def save_pytree(self, artifact_type: str, name: str, tree: Any) -> Path:
        """Checkpoint a JAX pytree.  Arrays are device_get'd to host before
        serialization so the HBM↔host boundary is explicit at the job edge
        (SURVEY §5.4 TPU-native plan)."""
        import jax
        import numpy as np

        host_tree = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x))
            if hasattr(x, "shape")
            else x,
            tree,
        )
        return self._dump_atomic(self.path_for(artifact_type, name),
                                 host_tree)

    def read_pytree(self, artifact_type: str, name: str) -> Any:
        return self.read_object(artifact_type, name)

    # -- raw bytes ------------------------------------------------------------

    def save_stream(
        self, artifact_type: str, name: str, stream: io.BufferedIOBase,
        chunk_size: int = 1 << 20,
    ) -> Path:
        path = self.path_for(artifact_type, name)
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "wb") as fh:
            shutil.copyfileobj(stream, fh, chunk_size)
        return path

    def read_bytes(self, artifact_type: str, name: str) -> bytes:
        return self.path_for(artifact_type, name).read_bytes()

    def object_bytes(self, artifact_type: str, name: str) -> int:
        """What :meth:`save_object` wrote for this artifact: the one
        file, or the parts behind its manifest."""
        path = self.path_for(artifact_type, name)
        manifest = _manifest_of(path)
        if manifest is None:
            return path.stat().st_size
        directory = _parts_dir(path)
        return sum(
            (directory / f"{manifest['gen']}-{i:05d}").stat().st_size
            for i in range(manifest["parts"])
        )

    # -- lifecycle ------------------------------------------------------------

    def exists(self, artifact_type: str, name: str) -> bool:
        return self.path_for(artifact_type, name).exists()

    def delete(self, artifact_type: str, name: str) -> bool:
        return self._remove(self.path_for(artifact_type, name))

    @staticmethod
    def _remove(path: Path) -> bool:
        shutil.rmtree(_parts_dir(path), ignore_errors=True)
        if path.is_dir():
            shutil.rmtree(path)
            return True
        if path.exists():
            path.unlink()
            return True
        return False

    def delete_everywhere(self, name: str) -> bool:
        """Remove a named binary from whichever volume holds it."""
        _validate_name(name)
        hits = [self._remove(self.root / key / name) for key in VOLUME_KEYS]
        return any(hits)
