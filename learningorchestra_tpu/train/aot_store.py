"""Durable AOT executable store — the compiled hot set survives the
process.

The process-wide ``CompiledProgramCache`` (train/compile_cache.py)
amortizes tracing across jobs, but it dies with the process: a restart,
deploy or failover re-pays XLA tracing for the entire hot set at
exactly the moment a production fleet can least afford it (ROADMAP
item 3).  The persistent XLA cache only dedups the *XLA compile* step —
Python tracing and executable loading still cost seconds per program on
TPU.

This store closes the gap with JAX's AOT export: when the deep cost
probe (obs/costs.py) lowers-and-compiles a just-built program, the
serialized executable payload (``jax.experimental.serialize_executable``
— a picklable ``(blob, in_tree, out_tree)`` tuple) is *offered* here and
written next to the XLA disk cache.  A later process loads it with
``deserialize_and_load`` and installs the restored ``Compiled`` straight
into the program cache — first dispatch skips trace AND compile.

Blob format (one file per program, ``<fingerprint>.aotx``)::

    LOAOT1\\n
    {json header: version, key, label, deviceSig, sha256, bytes}\\n
    <pickled serialize_executable payload>

Safety contract: a stale or corrupt blob must degrade to a live
re-trace, never a crash — every load validates magic, format version,
key, device signature (compiled executables pin device handles;
``train/compile_cache.py::_device_signature``) and a payload checksum;
any mismatch counts ``loadErrors``, deletes the blob and returns None.
The fault points ``cache.aot_load`` / ``cache.aot_store`` (faults/
plane.py) chaos-test exactly this degradation.

A ``manifest.json`` beside the blobs records the hot set (fingerprint,
label, hit count, measured bytes) ordered by observed heat — the boot
pre-warm (services/context.py) walks it hottest-first.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Any

from learningorchestra_tpu.concurrency_rt import make_lock
from learningorchestra_tpu.log import get_logger, kv

__all__ = [
    "AOTExecutableStore",
    "enabled",
    "get_store",
    "reset_store",
    "serialize",
    "stats_snapshot",
]

logger = get_logger("aot_store")

_MAGIC = b"LOAOT1\n"
# 2: the payload carries the executable's device ids (see serialize).
_FORMAT_VERSION = 2
_MANIFEST = "manifest.json"


def serialize(compiled) -> tuple:
    """The payload :meth:`AOTExecutableStore.offer` persists for a
    ``jax.stages.Compiled``: ``serialize_executable``'s ``(blob,
    in_tree, out_tree)`` plus the ids of the devices the executable
    runs on, in assignment order.  ``deserialize_and_load`` otherwise
    assumes EVERY device of the backend, which breaks (or aborts the
    process) for a single-chip program restored on a multi-chip host."""
    from jax.experimental import serialize_executable

    return (
        *serialize_executable.serialize(compiled),
        [d.id for d in compiled.runtime_executable().local_devices()],
    )


def _faults():
    """Lazy fault-plane handle (the compile-cache idiom): this module
    sits on the train import path and must stay flat."""
    from learningorchestra_tpu import faults

    return faults


def _device_signature() -> tuple:
    from learningorchestra_tpu.train import compile_cache

    return compile_cache._device_signature()


class AOTExecutableStore:
    """On-disk store of AOT-serialized executables + hot-set manifest.

    All mutation happens under one lock; blob and manifest writes are
    atomic (tmp + rename) so a crash mid-store leaves the previous
    state, never a torn file.  Loading is deliberately paranoid — see
    the module docstring's safety contract.
    """

    def __init__(
        self,
        root: str,
        *,
        max_entries: int = 64,
        max_bytes: int = 1 << 30,
    ):
        self.root = os.path.expanduser(root)
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self._lock = make_lock("AOTExecutableStore._lock")
        # key -> {"label", "hits", "bytes", "storedAt"}
        self._manifest: dict[str, dict] = {}
        # Counters (process lifetime; stats() snapshots them).
        self.hits = 0
        self.misses = 0
        self.load_errors = 0
        self.stores = 0
        self.store_errors = 0
        self.evictions = 0
        self.call_fallbacks = 0
        os.makedirs(self.root, exist_ok=True)
        self._read_manifest()

    # -- paths / persistence -------------------------------------------------

    def _blob_path(self, key: str) -> str:
        # Keys are sha256 hexdigests (compile_cache.fingerprint), safe
        # as filenames verbatim.
        return os.path.join(self.root, f"{key}.aotx")

    def _read_manifest(self) -> None:
        path = os.path.join(self.root, _MANIFEST)
        try:
            with open(path, encoding="utf-8") as fh:
                raw = json.load(fh)
            entries = raw.get("entries", {})
            if isinstance(entries, dict):
                self._manifest = {
                    str(k): dict(v) for k, v in entries.items()
                    if isinstance(v, dict)
                }
        except FileNotFoundError:
            return
        except Exception as exc:  # noqa: BLE001 — a torn manifest
            # must not fail boot; the blobs re-register as they are
            # re-offered.
            logger.warning(kv(
                event="aot_manifest_unreadable", path=path,
                error=repr(exc),
            ))
            self._manifest = {}

    def _write_manifest_locked(self) -> None:
        path = os.path.join(self.root, _MANIFEST)
        tmp = f"{path}.tmp.{os.getpid()}"
        doc = {"version": _FORMAT_VERSION, "entries": self._manifest}
        try:
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(doc, fh)
            os.replace(tmp, path)
        except OSError as exc:
            logger.warning(kv(
                event="aot_manifest_write_failed", error=repr(exc),
            ))
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def _drop_locked(self, key: str, *, evicted: bool = False) -> None:
        self._manifest.pop(key, None)
        if evicted:
            self.evictions += 1
        try:
            os.unlink(self._blob_path(key))
        except OSError:
            pass

    def _prune_locked(self, keep: str | None = None) -> None:
        """Bound the store to max_entries/max_bytes, evicting the
        coldest (fewest hits, oldest) blobs first.  ``keep`` — the key
        just stored — is never evicted (the compile cache's
        never-evict-the-just-inserted rule)."""
        def total() -> int:
            return sum(
                int(rec.get("bytes", 0) or 0)
                for rec in self._manifest.values()
            )

        while self._manifest and (
            len(self._manifest) > self.max_entries
            or total() > self.max_bytes
        ):
            victims = sorted(
                (k for k in self._manifest if k != keep),
                key=lambda k: (
                    int(self._manifest[k].get("hits", 0) or 0),
                    float(self._manifest[k].get("storedAt", 0.0) or 0.0),
                ),
            )
            if not victims:
                break
            self._drop_locked(victims[0], evicted=True)

    # -- store / load --------------------------------------------------------

    def offer(self, key: str, payload: Any, *,
              label: str | None = None) -> bool:
        """Persist one program's serialized-executable ``payload``
        (what :func:`serialize` returned).  Best
        effort: any failure counts ``storeErrors`` and the build it
        rides proceeds untouched.  Re-offering a stored key refreshes
        its label/bytes and bumps its heat."""
        try:
            _faults().hit("cache.aot_store")
            blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
            header = {
                "version": _FORMAT_VERSION,
                "key": key,
                "label": label,
                "deviceSig": [list(d) for d in _device_signature()],
                "sha256": hashlib.sha256(blob).hexdigest(),
                "bytes": len(blob),
            }
            path = self._blob_path(key)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                fh.write(_MAGIC)
                fh.write(json.dumps(header).encode("utf-8"))
                fh.write(b"\n")
                fh.write(blob)
            os.replace(tmp, path)
        except Exception as exc:  # noqa: BLE001 — never fail the build
            with self._lock:
                self.store_errors += 1
            logger.warning(kv(
                event="aot_store_failed", key=key[:12],
                label=label or "", error=repr(exc),
            ))
            return False
        with self._lock:
            rec = self._manifest.get(key)
            if rec is None:
                rec = self._manifest[key] = {"hits": 0}
            rec["label"] = label
            rec["bytes"] = len(blob)
            rec["storedAt"] = time.time()
            rec["hits"] = int(rec.get("hits", 0) or 0) + 1
            self.stores += 1
            self._prune_locked(keep=key)
            self._write_manifest_locked()
        return True

    def load(self, key: str):
        """Deserialize-and-load the stored executable for ``key``;
        ``None`` on a miss OR any validation/decode failure (the
        caller falls back to a live re-trace — a bad blob must never
        fail a request).  Corrupt blobs are deleted so the error pays
        once."""
        with self._lock:
            known = key in self._manifest
        path = self._blob_path(key)
        try:
            _faults().hit("cache.aot_load")
            with open(path, "rb") as fh:
                if fh.read(len(_MAGIC)) != _MAGIC:
                    raise ValueError("bad magic")
                header = json.loads(fh.readline().decode("utf-8"))
                blob = fh.read()
            if header.get("version") != _FORMAT_VERSION:
                raise ValueError(
                    f"format version {header.get('version')!r} != "
                    f"{_FORMAT_VERSION}"
                )
            if header.get("key") != key:
                raise ValueError("header key mismatch")
            sig = [list(d) for d in _device_signature()]
            if header.get("deviceSig") != sig:
                raise ValueError("device signature mismatch")
            if hashlib.sha256(blob).hexdigest() != header.get("sha256"):
                raise ValueError("payload checksum mismatch")
            import jax
            from jax.experimental import serialize_executable

            serialized, in_tree, out_tree, device_ids = pickle.loads(blob)
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except FileNotFoundError:
            with self._lock:
                self.misses += 1
                if known:
                    # Blob vanished under the manifest (operator rm,
                    # partial copy): forget it.
                    self._manifest.pop(key, None)
                    self._write_manifest_locked()
            return None
        except BaseException as exc:
            from learningorchestra_tpu.jobs.engine import Preempted

            if isinstance(exc, Preempted):
                # The fault plane's preempt mode models device-level
                # preemption — that is the JOB retry loop's contract,
                # not a blob-corruption fallback.
                raise
            injected = type(exc).__name__ == "FaultInjected"
            with self._lock:
                self.load_errors += 1
                if not injected:
                    # Real corruption/mismatch: pay the error once.
                    # An INJECTED error is transient chaos — deleting
                    # a healthy blob would turn a drill into data loss.
                    self._drop_locked(key)
                    self._write_manifest_locked()
            logger.warning(kv(
                event="aot_load_failed", key=key[:12],
                error=repr(exc),
            ))
            return None
        with self._lock:
            self.hits += 1
            rec = self._manifest.get(key)
            if rec is None:
                # Blob present without a manifest row (torn manifest
                # at a previous crash): re-register it.
                rec = self._manifest[key] = {
                    "label": header.get("label"),
                    "bytes": len(blob),
                    "storedAt": time.time(),
                    "hits": 0,
                }
            rec["hits"] = int(rec.get("hits", 0) or 0) + 1
            self._write_manifest_locked()
        return compiled

    def note_call_fallback(self) -> None:
        """A restored executable failed at CALL time and its consumer
        re-traced live (train/compile_cache.py guard)."""
        with self._lock:
            self.call_fallbacks += 1

    # -- introspection -------------------------------------------------------

    def contains(self, key: str) -> bool:
        with self._lock:
            return key in self._manifest

    def entry(self, key: str) -> dict | None:
        with self._lock:
            rec = self._manifest.get(key)
            return dict(rec) if rec is not None else None

    def manifest_entries(self) -> list[dict]:
        """Hot set, hottest first — the boot pre-warm's work list."""
        with self._lock:
            entries = [
                {"key": key, **rec} for key, rec in self._manifest.items()
            ]
        entries.sort(
            key=lambda rec: int(rec.get("hits", 0) or 0), reverse=True
        )
        return entries

    def stats(self) -> dict:
        with self._lock:
            persisted_bytes = sum(
                int(rec.get("bytes", 0) or 0)
                for rec in self._manifest.values()
            )
            return {
                "enabled": True,
                "dir": self.root,
                "persistedEntries": len(self._manifest),
                "persistedBytes": persisted_bytes,
                "maxEntries": self.max_entries,
                "maxBytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "loadErrors": self.load_errors,
                "stores": self.stores,
                "storeErrors": self.store_errors,
                "evictions": self.evictions,
                "callFallbacks": self.call_fallbacks,
                "entries_detail": [
                    {
                        "key": key[:12],
                        "label": rec.get("label"),
                        "hits": int(rec.get("hits", 0) or 0),
                        "bytes": int(rec.get("bytes", 0) or 0),
                    }
                    for key, rec in self._manifest.items()
                ],
            }


# -- process-wide singleton ---------------------------------------------------

_store: AOTExecutableStore | None = None
_store_lock = make_lock("aot_store._store_lock")


def _cfg():
    from learningorchestra_tpu.config import get_config

    return get_config().aot


def enabled() -> bool:
    """Off by default (LO_TPU_AOT_ENABLED): restored executables pin
    exact shapes/dtypes and cross-run state, so durability is an
    explicit deployment opt-in — the deploy manifests enable it."""
    try:
        cfg = _cfg()
    except Exception:  # noqa: BLE001 — a config error must not turn
        return False  # every compile-cache miss into a crash
    return bool(cfg.enabled) and cfg.max_entries > 0


def get_store() -> AOTExecutableStore | None:
    """The process-wide store, or None when disabled.  An explicitly
    installed store (``reset_store`` with overrides — tests) is served
    regardless of config."""
    global _store
    with _store_lock:
        if _store is not None:
            return _store
    if not enabled():
        return None
    with _store_lock:
        if _store is None:
            cfg = _cfg()
            try:
                _store = AOTExecutableStore(
                    cfg.dir,
                    max_entries=cfg.max_entries,
                    max_bytes=cfg.max_bytes,
                )
            except OSError as exc:
                logger.warning(kv(
                    event="aot_store_unavailable", dir=cfg.dir,
                    error=repr(exc),
                ))
                return None
        return _store


def reset_store(**overrides) -> AOTExecutableStore | None:
    """Replace the singleton (tests; config swap).  With ``overrides``
    (root/max_entries/max_bytes) builds an explicit store regardless of
    config; bare call drops it for lazy rebuild from config."""
    global _store
    with _store_lock:
        if overrides:
            _store = AOTExecutableStore(**overrides)
            return _store
        _store = None
    return get_store()


def stats_snapshot() -> dict:
    """Stats for the monitoring payload and Prometheus exposition —
    zeros when disabled, so scrape shape stays stable."""
    store = get_store()
    if store is None:
        return {
            "enabled": False,
            "persistedEntries": 0,
            "persistedBytes": 0,
            "hits": 0,
            "misses": 0,
            "loadErrors": 0,
            "stores": 0,
            "storeErrors": 0,
            "evictions": 0,
            "callFallbacks": 0,
        }
    return store.stats()
