"""Process-wide compiled-program cache — trace once, run many.

Every submitted train/tune job used to rebuild its jitted epoch/eval
closures from scratch (``train/neural.py`` ``build_*_epoch_fns``), so an
identical second job — or every candidate of a tune sweep sharing one
architecture — re-paid full Python tracing and XLA compilation even
though jax's per-function jit cache would have served it instantly *had
the function object survived*.  The persistent XLA cache
(services/context.py) only dedups the XLA compile step; Python tracing
and closure construction were still repeated per job, and on TPU a
trace alone is seconds for the zoo's larger models.

This module keeps the jitted callables themselves alive across jobs,
keyed by a canonical fingerprint of the *program*:

  (builder kind, model architecture spec, optimizer config, loss kind,
   compute dtype, batch/dataset shape, donation flags, mesh layout)

On a hit the caller gets the exact wrapper a previous job compiled —
jax's C++ fastpath then dispatches with zero tracing.  On a miss the
builder runs once; concurrent callers for the same key (tune candidates
submit together) coalesce onto the single build instead of racing N
identical traces.

Correctness notes:

- optax transforms and flax modules are pure: a cached callable closing
  over job A's optimizer/module objects is behaviorally identical for
  job B *iff the fingerprints match*, which is exactly what the key
  guarantees.  Opaque optimizer objects (no declarative spec) cannot be
  fingerprinted and fall back to identity keys — correct, merely
  uncached across jobs.
- mesh-aware modules (models/longcontext.py) carry their bound ``Mesh``
  as a dataclass field, so the module fingerprint distinguishes
  ring-attention-for-mesh-X from vanilla automatically; distributed
  entries additionally key on mesh axis names + device assignment.
- the cache clears itself whenever the visible device set changes
  (TPU runtime restart): compiled executables pin device
  handles that are dead afterwards.

Observability: hit/miss/eviction/trace-time counters (``stats()``)
surface through the monitoring service endpoint
(GET /monitoring/<tool>/compileCache), per-job metadata deltas
(services/executor.py) and the tfevents writer on monitored distributed
jobs.  Sizing knobs live in config.py (LO_TPU_COMPILE_CACHE_*).
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from typing import Any, Callable

from learningorchestra_tpu.concurrency_rt import make_lock

__all__ = [
    "CompiledProgramCache",
    "apply_program_key",
    "canonical",
    "fingerprint",
    "get_cache",
    "module_fingerprint",
    "optimizer_fingerprint",
    "program_key",
    "reset_cache",
    "counters_snapshot",
    "delta_since",
    "warm_fingerprint",
]


def _faults():
    """Lazy fault-plane handle: the cache is imported from low-level
    train paths; keep its import graph flat."""
    from learningorchestra_tpu import faults

    return faults


def _costs():
    """Lazy cost-ledger handle (obs/costs.py), same discipline: every
    build notes a ProgramCost entry, and inserts charge the MEASURED
    serialized size against the byte cap when an analysis produced
    one."""
    from learningorchestra_tpu.obs import costs

    return costs


def _aot():
    """Lazy durable-executable-store handle (train/aot_store.py): a
    miss consults the on-disk AOT store before paying a live trace."""
    from learningorchestra_tpu.train import aot_store

    return aot_store


def _flight():
    """Lazy flight-recorder handle (obs/flight.py): builds and AOT
    restores land in the ``compile`` ring of the incident timeline."""
    from learningorchestra_tpu.obs import flight

    return flight


# -- canonical fingerprinting -------------------------------------------------


def canonical(obj: Any) -> Any:
    """Reduce ``obj`` to a deterministic, repr-stable structure.

    Handles the vocabulary a training-program spec is made of: flax
    modules (class identity + dataclass fields, recursively), meshes
    (axis names + shape + device assignment), dicts/sequences, dtypes
    and numpy scalars.  Anything unrecognized degrades to an
    identity-keyed token — correct (never a false hit), merely
    uncacheable across distinct objects.
    """
    # Late imports keep this module importable without initializing jax.
    import numpy as np

    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, (np.integer, np.floating, np.bool_)):
        return obj.item()
    if isinstance(obj, dict):
        return (
            "dict",
            tuple(sorted((str(k), canonical(v)) for k, v in obj.items())),
        )
    if isinstance(obj, (list, tuple)):
        return ("seq", tuple(canonical(v) for v in obj))
    if isinstance(obj, (set, frozenset)):
        return ("set", tuple(sorted(repr(canonical(v)) for v in obj)))
    # numpy/jax dtypes stringify deterministically.
    if isinstance(obj, np.dtype) or (
        isinstance(obj, type) and issubclass(obj, np.generic)
    ):
        return ("dtype", np.dtype(obj).name)
    try:
        from flax import linen as nn

        if isinstance(obj, nn.Module):
            return module_fingerprint(obj)
    except Exception:  # pragma: no cover — flax always present here
        pass
    try:
        from jax.sharding import Mesh

        if isinstance(obj, Mesh):
            return mesh_fingerprint(obj)
    except Exception:  # pragma: no cover
        pass
    if callable(obj):
        # Named functions (e.g. an activation passed as a module field)
        # key on their qualified name; lambdas/closures can't be proven
        # equal, so they key on identity (never a false hit).
        name = getattr(obj, "__qualname__", "")
        mod = getattr(obj, "__module__", "")
        if name and "<lambda>" not in name and "<locals>" not in name:
            return ("fn", mod, name)
        return ("opaque", id(obj))
    return ("opaque", id(obj))


def module_fingerprint(module: Any) -> Any:
    """Canonical spec of a flax module: class identity plus every
    dataclass field (``parent``/``name`` are flax bookkeeping, not
    architecture), recursing into nested modules and meshes."""
    fields = tuple(
        (f.name, canonical(getattr(module, f.name, None)))
        for f in dataclasses.fields(module)
        if f.name not in ("parent", "name")
    )
    return (
        "module",
        type(module).__module__,
        type(module).__qualname__,
        fields,
    )


def mesh_fingerprint(mesh: Any) -> Any:
    """Axis names + per-axis sizes + flat device assignment — two jobs
    share a sharded program only on the SAME devices in the SAME order
    (executables pin device handles)."""
    return (
        "mesh",
        tuple(str(a) for a in mesh.axis_names),
        tuple(sorted((str(k), int(v)) for k, v in mesh.shape.items())),
        tuple(
            (int(d.id), str(getattr(d, "platform", "")))
            for d in mesh.devices.flat
        ),
    )


def optimizer_fingerprint(estimator: Any) -> Any:
    """Optimizer identity as the REST surface expresses it: the
    declarative spec (name/dict/None) + learning rate (float or
    schedule spec) + accumulation wrapping.  An opaque optax object
    passed programmatically has no spec — key on identity, which keeps
    per-instance reuse but (correctly) never matches across jobs."""
    spec = getattr(estimator, "_optimizer_spec", None)
    if spec is None and estimator.optimizer is not None:
        # id() reuse after GC cannot produce a false hit: the cached
        # callable closes over this very optimizer object, so while an
        # entry keyed on this id lives, the object lives and the id
        # stays taken; once evicted there is no entry left to hit.
        return ("opaque", id(estimator.optimizer))
    return (
        "opt",
        canonical(spec),
        canonical(getattr(estimator, "learning_rate", None)),
        int(getattr(estimator, "_accumulate_steps", 1)),
    )


def fingerprint(*parts: Any) -> str:
    """Stable digest of canonicalized parts — the cache key."""
    payload = repr(tuple(canonical(p) for p in parts))
    return hashlib.sha256(payload.encode()).hexdigest()


def program_key(
    kind: str,
    *,
    module: Any,
    optimizer: Any,
    loss: Any,
    dtype: Any,
    shapes: Any = None,
    mesh: Any = None,
    donate: Any = None,
) -> str:
    """Fingerprint one compiled training program.

    ``optimizer`` should already be a canonical token (see
    :func:`optimizer_fingerprint`); ``shapes`` carries whatever the
    builder bakes into the trace (dataset length, batch size, shuffle,
    epoch count); ``mesh`` the trainer-level mesh fingerprint for
    sharded variants.
    """
    return fingerprint(
        kind, module, optimizer, str(loss), str(dtype), shapes, mesh,
        donate,
    )


def apply_program_key(module: Any, *, rows: int | None = None) -> str:
    """Key for a pure-inference ``apply`` program.

    Optimizer and loss play no part in inference, so every consumer of
    an architecture shares one program family.  ``rows`` is the
    SHAPE-BUCKET dimension (a serving bucket or predict's batch size):
    keyed this way, a whole deployment compiles at most one executable
    per (architecture, bucket) and the cache's miss counter counts
    buckets — never requests.  The one place the predict/serve key
    scheme lives; train/neural.py and serve/ both resolve through it.
    """
    return program_key(
        "apply",
        module=module_fingerprint(module),
        optimizer=None,
        loss="-",
        dtype="-",
        shapes=None if rows is None else ("rows", int(rows)),
    )


def _device_signature() -> tuple:
    """Identity of the visible device set; compiled executables are
    invalid the moment this changes (restarted TPU runtime, resized
    slice)."""
    import jax

    try:
        return tuple(
            (int(d.id), str(getattr(d, "platform", "")))
            for d in jax.devices()
        )
    except Exception:  # backend not initialized yet / unavailable
        return ()


def _record_compile_span(built_s: float, label, key: str) -> None:
    """Trace span for one program build: the build IS the "where did
    the time go" event this cache exists to amortize — a traced job
    shows each miss as a compile span nested where it happened (inside
    the lease, under the job root), including when the cache is
    disabled and every lookup builds.  No-op outside an active trace;
    never fails a build."""
    try:
        from learningorchestra_tpu.obs import tracing

        tracing.record_span(
            "compile", built_s, label=label or "", key=key[:12]
        )
    except Exception:  # noqa: BLE001
        pass


# -- durable warm start -------------------------------------------------------

#: Request knobs that do not shape the traced program: two submissions
#: differing only here share every compiled executable, so the warm
#: hint must treat them as identical.
_WARM_HINT_EXCLUDE = frozenset((
    "verbose", "description", "monitoring_path", "monitoringPath",
    "checkpoint_dir", "checkpointDir", "resume",
))


def warm_fingerprint(module_path, class_name, method,
                     parameters: dict | None = None) -> str:
    """Program-level warm-start hint for the engine's dispatcher.

    The old hint was ``module:class:method`` — coarse enough that two
    tune candidates with different optimizers (different programs!)
    claimed the same warmth.  This fingerprints the SUBMITTED SPEC
    through the same canonicalizer the cache keys use, minus the knobs
    that never reach a trace (verbosity, monitoring/checkpoint paths),
    so warm-start preference actually predicts cache hits.  Still a
    HINT: exact matching happens inside the cache; a collision merely
    reorders one class's queue."""
    params = {
        k: v for k, v in (parameters or {}).items()
        if k not in _WARM_HINT_EXCLUDE
    }
    return fingerprint(
        "warm", str(module_path), str(class_name), str(method), params
    )


class _AOTRestored:
    """A deserialized AOT executable standing in for the jit wrapper a
    builder would have produced, with a one-shot live-rebuild fallback.

    A restored ``Compiled`` pins the exact input avatars of the
    original trace, so an argument shape/dtype it never saw raises
    where a jit wrapper would simply re-trace.  The first call failure
    rebuilds live through the builder captured at lookup time and
    permanently swaps the rebuilt program in (counted store-side as a
    ``callFallbacks``); the request re-raises only if the REBUILT
    program fails too — genuine errors stay errors, stale executables
    cost one re-trace."""

    __slots__ = ("_fn", "_builder", "_key", "_label", "_fell_back")

    def __init__(self, fn, builder, key, label):
        self._fn = fn
        self._builder = builder
        self._key = key
        self._label = label
        self._fell_back = False

    def bind_builder(self, builder) -> None:
        """Boot pre-warm restores with no builder in hand; the first
        ``get_or_build`` hit re-arms the fallback with its caller's."""
        if self._builder is None:
            self._builder = builder

    def __call__(self, *args, **kwargs):
        if self._fell_back:
            return self._fn(*args, **kwargs)
        try:
            return self._fn(*args, **kwargs)
        except Exception:
            builder = self._builder
            if builder is None:
                raise
            self._fell_back = True
            t0 = time.perf_counter()
            rebuilt = builder()
            if isinstance(rebuilt, tuple):
                rebuilt = rebuilt[0]
            self._fn = rebuilt
            _record_compile_span(
                time.perf_counter() - t0, self._label, self._key
            )
            try:
                store = _aot().get_store()
                if store is not None:
                    store.note_call_fallback()
            except Exception:  # noqa: BLE001 — accounting only
                pass
            return self._fn(*args, **kwargs)


# -- the cache ---------------------------------------------------------------


class _Entry:
    __slots__ = ("value", "nbytes", "label", "built_s", "measured")

    def __init__(self, value, nbytes, label, built_s,
                 measured=False):
        self.value = value
        self.nbytes = nbytes
        self.label = label
        self.built_s = built_s
        # True when nbytes is a MEASURED serialized size (obs/costs)
        # rather than the flat per-entry fallback estimate.
        self.measured = measured


class CompiledProgramCache:
    """LRU cache of compiled-program callables with build coalescing.

    ``max_entries <= 0`` disables caching entirely (every lookup
    builds).  ``max_bytes`` bounds the *estimated* resident size: jax
    exposes no portable executable-size API, so each entry charges
    ``entry_bytes`` (config-tunable) unless the caller provides a
    better estimate — the cap is a safety valve against unbounded
    program diversity, not an exact accountant.
    """

    def __init__(
        self,
        max_entries: int = 64,
        max_bytes: int = 2 << 30,
        entry_bytes: int = 32 << 20,
    ):
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self.entry_bytes = int(entry_bytes)
        self._entries: OrderedDict[str, _Entry] = OrderedDict()
        self._building: dict[str, threading.Event] = {}
        self._lock = make_lock("CompiledProgramCache._lock")
        self._devices: tuple | None = None
        # Bumped on every device-set clear: a build that STARTED
        # before an invalidation must not be inserted after it (its
        # trace may pin handles into the dead device set).
        self._generation = 0
        # Fired (under the cache lock — keep them fast, never call
        # back into the cache) when the device-set check clears the
        # cache, so dependent state (the engine's warm-start hints)
        # doesn't keep claiming programs are compiled.
        self._invalidation_listeners: list[Callable[[], None]] = []
        # Counters (process lifetime; ``stats()`` snapshots them).
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.coalesced = 0
        self.invalidations = 0
        self.trace_time_s = 0.0

    # -- internals ----------------------------------------------------------

    def _bytes_locked(self) -> int:
        return sum(e.nbytes for e in self._entries.values())

    def _check_devices_locked(self) -> None:
        sig = _device_signature()
        if self._devices is None:
            self._devices = sig
            return
        if sig != self._devices:
            # Every cached executable pins handles into the OLD device
            # set — running one would crash or silently target dead
            # devices.  Drop them all; the next jobs re-trace.
            if self._entries:
                self.invalidations += 1
            self._entries.clear()
            self._devices = sig
            self._generation += 1
            for listener in self._invalidation_listeners:
                try:
                    listener()
                except Exception:  # noqa: BLE001 — never break a lookup
                    pass

    def _evict_locked(self) -> None:
        while self._entries and (
            len(self._entries) > self.max_entries
            or self._bytes_locked() > self.max_bytes
        ):
            if len(self._entries) == 1:
                break  # never evict the entry just inserted
            self._entries.popitem(last=False)
            self.evictions += 1

    # -- public surface -----------------------------------------------------

    def get_or_build(
        self,
        key: str,
        builder: Callable[[], Any],
        *,
        label: str | None = None,
        nbytes: int | None = None,
    ) -> Any:
        """Return the cached program for ``key``, building it (once,
        even under concurrent callers) on a miss."""
        if self.max_entries <= 0:
            with self._lock:
                self.misses += 1
            t0 = time.perf_counter()
            _faults().hit("compile.build")
            value = builder()
            built_s = time.perf_counter() - t0
            _record_compile_span(built_s, label, key)
            _flight().record(
                "compile", "build",
                key=key, label=label or "", builtS=round(built_s, 4),
            )
            self._note_cost(key, label, built_s)
            return value
        while True:
            with self._lock:
                self._check_devices_locked()
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    value = entry.value
                    if type(value) is _AOTRestored:
                        # A pre-warmed executable has no rebuild path
                        # yet; arm its call-time fallback with this
                        # caller's builder.
                        value.bind_builder(builder)
                    return value
                pending = self._building.get(key)
                if pending is None:
                    pending = self._building[key] = threading.Event()
                    build_generation = self._generation
                    break
            # Another thread is tracing this exact program right now
            # (tune candidates submit together): wait for it rather
            # than racing a duplicate trace, then re-check — a hit if
            # it succeeded, our turn to build if it raised.
            pending.wait()
            with self._lock:
                if key in self._entries:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    self.coalesced += 1
                    value = self._entries[key].value
                    if type(value) is _AOTRestored:
                        value.bind_builder(builder)
                    return value
        t0 = time.perf_counter()
        restored = None
        try:
            # Durable warm start: a persisted AOT executable satisfies
            # the miss without tracing OR compiling (train/aot_store.py
            # validates headers/checksums; any mismatch returns None
            # and the live build below proceeds as if no store existed).
            restored = self._aot_restore(key, builder, label)
            if restored is None:
                # Chaos probe on the BUILD path only: cache hits must
                # stay untouched (a compile fault models tracing/XLA
                # failure, which by definition happens when a program
                # builds).
                _faults().hit("compile.build")
                value = builder()
            else:
                value = restored[0]
        except BaseException:
            with self._lock:
                ev = self._building.pop(key, None)
            if ev is not None:
                ev.set()
            raise
        built_s = time.perf_counter() - t0
        if restored is None:
            # An AOT-satisfied lookup records NO compile span — the
            # restart drill asserts pre-warmed keys rebuild nothing.
            _record_compile_span(built_s, label, key)
        _flight().record(
            "compile",
            "build" if restored is None else "aot_restore",
            key=key, label=label or "", builtS=round(built_s, 4),
        )
        self._note_cost(key, label, built_s)
        measured = False
        if nbytes is None:
            if restored is not None:
                # The store's manifest carries the blob's measured size.
                nbytes = restored[1]
                measured = nbytes is not None
            else:
                # Real serialized size when the builder's cost analysis
                # measured one (ROADMAP item 3's carried debt: the byte
                # cap charged a flat 32 MiB per entry); the flat
                # estimate survives only as the fallback for unanalyzed
                # programs.
                nbytes = self._measured_bytes(key)
                measured = nbytes is not None
        with self._lock:
            ev = self._building.pop(key, None)
            self.misses += 1
            if restored is None:
                self.trace_time_s += built_s
            if build_generation == self._generation:
                self._entries[key] = _Entry(
                    value,
                    self.entry_bytes if nbytes is None else int(nbytes),
                    label,
                    built_s,
                    measured=measured,
                )
                self._entries.move_to_end(key)
                self._evict_locked()
            # else: the device set changed while this build was in
            # flight — the program may pin handles into the dead set;
            # hand it to THIS caller only (it fails fast if devices
            # really died) and never cache it.
        if ev is not None:
            ev.set()
        return value

    @staticmethod
    def _aot_restore(key: str, builder, label):
        """``(guarded_value, nbytes|None)`` from the durable AOT store,
        or None → build live.  Never raises except the fault plane's
        ``Preempted`` (the store re-raises it: preemption belongs to
        the job retry loop, not the corruption fallback)."""
        try:
            store = _aot().get_store()
        except Exception:  # noqa: BLE001 — a broken store must never
            return None  # break the build path it shortcuts
        if store is None:
            return None
        compiled = store.load(key)
        if compiled is None:
            return None
        rec = store.entry(key) or {}
        return (
            _AOTRestored(compiled, builder, key, label),
            rec.get("bytes"),
        )

    def install(self, key: str, value, *, label: str | None = None,
                nbytes: int | None = None) -> bool:
        """Install an externally restored program (boot pre-warm,
        services/context.py) WITHOUT counting a hit or miss and without
        recording a compile span.  Respects the device-set check and
        the eviction policy; an already-resident key wins (never
        clobber a live entry).  Returns True when the key is resident
        afterwards."""
        if self.max_entries <= 0:
            return False
        with self._lock:
            self._check_devices_locked()
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            self._entries[key] = _Entry(
                value,
                self.entry_bytes if nbytes is None else int(nbytes),
                label,
                0.0,
                measured=nbytes is not None,
            )
            self._entries.move_to_end(key)
            self._evict_locked()
            return key in self._entries

    @staticmethod
    def _note_cost(key: str, label, built_s: float) -> None:
        """Every build — cached or not, analyzed or not — lands a
        ProgramCost ledger entry (obs/costs.py).  Never fails a
        build."""
        try:
            _costs().note_build(key, label, built_s)
        except Exception:  # noqa: BLE001
            pass

    @staticmethod
    def _measured_bytes(key: str):
        try:
            return _costs().serialized_bytes(key)
        except Exception:  # noqa: BLE001
            return None

    def contains(self, key: str) -> bool:
        with self._lock:
            return key in self._entries

    def add_invalidation_listener(self, listener: Callable[[], None]):
        """Register a callback fired when a device-set change clears
        the cache.  Runs under the cache lock: must be fast and must
        not call back into the cache.  Pair with
        :meth:`remove_invalidation_listener` on owner teardown."""
        with self._lock:
            self._invalidation_listeners.append(listener)

    def remove_invalidation_listener(self, listener) -> None:
        with self._lock:
            try:
                self._invalidation_listeners.remove(listener)
            except ValueError:
                pass

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        """Counter snapshot for the monitoring endpoint / tfevents."""
        with self._lock:
            return {
                "entries": len(self._entries),
                "maxEntries": self.max_entries,
                "bytesEstimate": self._bytes_locked(),
                "maxBytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "coalesced": self.coalesced,
                "deviceInvalidations": self.invalidations,
                "traceTimeS": round(self.trace_time_s, 4),
                "measuredEntries": sum(
                    1 for e in self._entries.values() if e.measured
                ),
                "programs": [
                    e.label for e in self._entries.values() if e.label
                ],
                # Per-entry accounting: what each resident program
                # charges the byte cap, and whether that charge is a
                # measured serialized size or the flat fallback.
                "entries_detail": [
                    {
                        "key": key[:12],
                        "label": e.label,
                        "bytes": e.nbytes,
                        "measured": e.measured,
                        "builtS": round(e.built_s, 4),
                    }
                    for key, e in self._entries.items()
                ],
            }


# -- process-wide singleton ---------------------------------------------------

_cache: CompiledProgramCache | None = None
_cache_lock = make_lock("compile_cache._cache_lock")


def get_cache() -> CompiledProgramCache:
    """The process-wide cache, sized from config (LO_TPU_COMPILE_CACHE_*)."""
    global _cache
    with _cache_lock:
        if _cache is None:
            from learningorchestra_tpu.config import get_config

            cc = get_config().compile_cache
            _cache = CompiledProgramCache(
                max_entries=cc.max_entries,
                max_bytes=cc.max_bytes,
                entry_bytes=cc.entry_bytes,
            )
        return _cache


def reset_cache(**overrides) -> CompiledProgramCache:
    """Replace the singleton (tests; or re-size after a config change)."""
    global _cache
    with _cache_lock:
        if overrides:
            _cache = CompiledProgramCache(**overrides)
            return _cache
        _cache = None
    return get_cache()  # rebuild from config OUTSIDE the lock


# -- per-job accounting helpers ----------------------------------------------

_COUNTER_KEYS = ("hits", "misses", "evictions", "coalesced", "traceTimeS")


def enabled() -> bool:
    """False when the operator disabled caching
    (LO_TPU_COMPILE_CACHE_ENTRIES=0) — callers publishing warm-start
    hints must not claim programs are cached when nothing ever is."""
    return get_cache().max_entries > 0


def counters_snapshot() -> dict:
    stats = get_cache().stats()
    return {k: stats[k] for k in _COUNTER_KEYS}


def delta_since(before: dict) -> dict:
    """Counter delta for one job.  Counters are process-wide, so under
    concurrent jobs a delta attributes overlapping activity — exact for
    serial submissions, an upper bound otherwise."""
    now = counters_snapshot()
    out = {k: now[k] - before.get(k, 0) for k in _COUNTER_KEYS}
    out["traceTimeS"] = round(out["traceTimeS"], 4)
    return out
