"""Cost-accounting plane: per-program FLOPs/HBM ledgers and
device-time attribution.

The obs layer (metrics.py, tracing.py) answers *how often* and *how
long* things run; nothing answered *what the hardware is doing*: no
per-program FLOPs or bytes, no HBM footprint, no way to say which job
or served model consumed the device seconds, no achieved-vs-peak
utilization.  That visibility is the precondition the pjit/TPUv4
scaling work treats as table stakes for capacity planning (PAPERS.md)
— and it closes a standing debt: the compiled-program cache's byte cap
charged a flat 32 MiB per entry because nothing ever measured one.

Two ledgers, both process-wide singletons sized from config
(``LO_TPU_COSTS_*``):

- :class:`CostLedger` — one :class:`ProgramCost` per compiled-program
  fingerprint.  Builders with example arguments in hand call
  :func:`analyze_jitted`, which lowers the jitted callable against
  shape avatars and reads XLA's own numbers: ``Lowered.cost_analysis``
  (flops, bytes accessed — no backend compile needed) and, when
  ``deep`` analysis is on, an AOT ``compile()`` for
  ``Compiled.memory_analysis()`` (argument/output/temp/generated-code
  bytes — the HBM footprint) plus the serialized executable size.
  Backends that report nothing (CPU leaves several fields zero)
  degrade field-by-field, never fail a build.  The compile cache calls
  :func:`note_build` on EVERY build, so every entry exists even when
  no builder could analyze it, and charges the measured serialized
  size against its byte cap instead of the flat estimate.

- :class:`DeviceTimeLedger` — sampled per-dispatch attribution.
  Dispatch sites (the train epoch loop, the serving batcher dispatch)
  call :func:`attribute` with the elapsed device interval and the
  program's cost record; the ledger accumulates device seconds, flops
  and bytes per job (bounded ring), per served model and per
  (model, bucket), from which model-FLOPs-utilization (MFU) is
  ``flops / (device_s * peak_flops)`` when the operator configured the
  chip's peak (``LO_TPU_COSTS_PEAK_FLOPS``; unknown peak reports no
  MFU rather than a fabricated one).  ``LO_TPU_COSTS_SAMPLE`` thins
  the hook deterministically (every k-th dispatch, contributions
  scaled by k) so a microsecond-dispatch workload can dial the
  bookkeeping arbitrarily far down.

Everything here is disabled by ``LO_TPU_COSTS_ENABLED=0``: probes
return immediately and builders skip analysis.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
import time
from collections import OrderedDict

from learningorchestra_tpu.concurrency_rt import make_lock

__all__ = [
    "CostLedger",
    "DeviceTimeLedger",
    "ProgramCost",
    "analyze_jitted",
    "attribute",
    "current_job",
    "devtime",
    "enabled",
    "get_ledger",
    "job_scope",
    "job_summary",
    "mfu",
    "note_build",
    "reset",
    "serialized_bytes",
    "serving_totals",
]


@dataclasses.dataclass
class ProgramCost:
    """What ONE execution of a compiled program costs, as XLA reports
    it.  ``None`` fields mean "the backend reported nothing" — never
    fabricated."""

    key: str
    label: str = ""
    flops: float | None = None
    bytes_accessed: float | None = None
    argument_bytes: int | None = None
    output_bytes: int | None = None
    temp_bytes: int | None = None
    generated_code_bytes: int | None = None
    serialized_bytes: int | None = None
    built_s: float = 0.0
    builds: int = 0
    analyzed: bool = False
    # True when the analyzed lowering is collective-free by
    # construction (single-device MPMD stage programs, host-avatar
    # serve probes): the flops/bytes are pure compute, so MFU and
    # lo_serving_bucket_* derived from them stay honest for multi-chip
    # programs — a whole-mesh lowering's collective FLOPs would
    # inflate both.
    collectives_excluded: bool = False
    created_at: float = dataclasses.field(default_factory=time.time)

    @property
    def peak_bytes(self) -> int | None:
        """Approximate peak HBM while this program runs: arguments +
        outputs + XLA temporaries + code."""
        parts = [self.argument_bytes, self.output_bytes,
                 self.temp_bytes, self.generated_code_bytes]
        known = [p for p in parts if p is not None]
        return sum(known) if known else None

    def to_doc(self) -> dict:
        return {
            "key": self.key[:12],
            "label": self.label,
            "flops": self.flops,
            "bytesAccessed": self.bytes_accessed,
            "argumentBytes": self.argument_bytes,
            "outputBytes": self.output_bytes,
            "tempBytes": self.temp_bytes,
            "generatedCodeBytes": self.generated_code_bytes,
            "peakBytes": self.peak_bytes,
            "serializedBytes": self.serialized_bytes,
            "builtS": round(self.built_s, 4),
            "builds": self.builds,
            "analyzed": self.analyzed,
            "collectivesExcluded": self.collectives_excluded,
        }


class CostLedger:
    """Bounded per-fingerprint ProgramCost map (LRU on insertion): a
    process that builds unbounded program diversity must not grow this
    without limit — evicted records simply fall back to flat byte
    charges if their cache entry is ever re-inserted."""

    def __init__(self, max_programs: int = 256):
        self.max_programs = max(1, int(max_programs))
        self._lock = make_lock("CostLedger._lock")
        self._programs: OrderedDict[str, ProgramCost] = OrderedDict()
        self.analyses = 0
        self.analysis_failures = 0
        self.analysis_time_s = 0.0

    def _entry_locked(self, key: str, label: str) -> ProgramCost:
        cost = self._programs.get(key)
        if cost is None:
            cost = self._programs[key] = ProgramCost(key=key)
            while len(self._programs) > self.max_programs:
                self._programs.popitem(last=False)
        if label and not cost.label:
            cost.label = label
        return cost

    def note_build(self, key: str, label: str | None,
                   built_s: float) -> ProgramCost:
        """Called by the compile cache on EVERY build: guarantees a
        ledger entry per built program (analyzed or not) and keeps the
        per-program build time current."""
        with self._lock:
            cost = self._entry_locked(key, label or "")
            cost.builds += 1
            cost.built_s = float(built_s)
            return cost

    def record_analysis(self, key: str, label: str | None, *,
                        flops=None, bytes_accessed=None, memory=None,
                        serialized=None, analysis_s: float = 0.0,
                        collectives_excluded: bool = False
                        ) -> ProgramCost:
        with self._lock:
            cost = self._entry_locked(key, label or "")
            if collectives_excluded:
                cost.collectives_excluded = True
            if flops is not None:
                cost.flops = float(flops)
            if bytes_accessed is not None:
                cost.bytes_accessed = float(bytes_accessed)
            if memory is not None:
                # Field-by-field: a backend omitting an attribute
                # leaves the field None (unreported), never a
                # fabricated 0.
                def _mem(attr):
                    value = getattr(memory, attr, None)
                    return int(value) if value is not None else None

                cost.argument_bytes = _mem("argument_size_in_bytes")
                cost.output_bytes = _mem("output_size_in_bytes")
                cost.temp_bytes = _mem("temp_size_in_bytes")
                cost.generated_code_bytes = _mem(
                    "generated_code_size_in_bytes"
                )
            if serialized is not None:
                cost.serialized_bytes = int(serialized)
            cost.analyzed = True
            self.analyses += 1
            self.analysis_time_s += float(analysis_s)
            return cost

    def note_failure(self) -> None:
        with self._lock:
            self.analysis_failures += 1

    def get(self, key: str) -> ProgramCost | None:
        with self._lock:
            return self._programs.get(key)

    def serialized_bytes(self, key: str) -> int | None:
        with self._lock:
            cost = self._programs.get(key)
        if cost is None:
            return None
        return cost.serialized_bytes

    def snapshot(self) -> dict:
        with self._lock:
            programs = [c.to_doc() for c in self._programs.values()]
            return {
                "programs": programs,
                "maxPrograms": self.max_programs,
                "analyses": self.analyses,
                "analysisFailures": self.analysis_failures,
                "analysisTimeS": round(self.analysis_time_s, 4),
            }


class DeviceTimeLedger:
    """Sampled device-time attribution: who consumed the device.

    ``attribute`` accumulates (device seconds, flops, bytes,
    dispatches) per job — a bounded insertion-ordered ring, so a
    long-lived server keeps the freshest N jobs — per served model,
    and per (model, bucket).  All counters are scaled by the sampling
    weight, so thinned recording stays an unbiased estimate."""

    def __init__(self, max_jobs: int = 64, sample: float = 1.0,
                 max_models: int = 64):
        self.max_jobs = max(1, int(max_jobs))
        self.max_models = max(1, int(max_models))
        self.sample = min(1.0, max(0.0, float(sample)))
        # Every k-th dispatch records, contributions scaled by k —
        # deterministic (drills reproduce) and unbiased in the mean.
        # The rate QUANTIZES to 1/round(1/sample): only 1, 1/2, 1/3,
        # ... are representable — e.g. 0.7 records at full rate, 0.4
        # records 1-in-2 (the config knob documents this).
        self._stride = (
            max(1, round(1.0 / self.sample)) if self.sample > 0 else 0
        )
        self._lock = make_lock("DeviceTimeLedger._lock")
        # PER-KEY stride counters (bounded ring): one global counter
        # would alias deterministic interleavings — two models whose
        # dispatches strictly alternate at stride 2 would leave one
        # of them never sampled and the other double-counted.  Keyed
        # by the attribution entity (model or job), each stream thins
        # independently and stays unbiased.
        self._counters: OrderedDict[str, int] = OrderedDict()
        # Entries are 4-slot lists [device_s, flops, bytes,
        # dispatches], not dicts: record() sits on the serving
        # dispatch hot path and list indexing keeps the recorded hit
        # short (not measured on a chip).  Jobs AND models ride
        # bounded freshest-N rings (a multi-tenant server
        # churning model names must not grow these — or the per-model
        # metric cardinality — without limit); a model's bucket
        # entries die with it.
        self._jobs: OrderedDict[str, list] = OrderedDict()
        self._models: OrderedDict[str, list] = OrderedDict()
        self._buckets: dict[tuple, list] = {}
        self._totals = [0.0, 0.0, 0.0, 0]

    def will_record(self, key: str = "") -> int:
        """Advance ``key``'s sampling stride (the model or job being
        attributed): the weight to record this dispatch with, or 0
        (sampled out) — callers skip the device sync entirely for a
        0, which is what keeps a thinned hook off the dispatch
        pipeline."""
        stride = self._stride
        if stride == 1:
            return 1  # full rate: no counter, no lock
        if stride == 0:
            return 0
        with self._lock:
            n = self._counters.get(key)
            if n is None:
                n = 0
                while len(self._counters) >= 4 * self.max_models:
                    self._counters.popitem(last=False)
            n += 1
            self._counters[key] = n
            # LRU, not FIFO: a hot stream's counter must outlive
            # one-shot stale keys, or churny job names would keep
            # resetting its stride phase.
            self._counters.move_to_end(key)
            return stride if n % stride == 0 else 0

    def _model_entry_locked(self, model: str) -> list:
        """The model's accumulator, evicting the OLDEST model (and
        cascading its bucket entries) past the cap.  Caller holds the
        lock."""
        entry = self._models.get(model)
        if entry is None:
            entry = self._models[model] = [0.0, 0.0, 0.0, 0]
            while len(self._models) > self.max_models:
                evicted, _ = self._models.popitem(last=False)
                for bkey in [
                    k for k in self._buckets if k[0] == evicted
                ]:
                    del self._buckets[bkey]
        return entry

    def record_model(self, weight, duration_s, flops, nbytes, model,
                     bucket) -> None:
        """Positional fast path for the serving dispatch hook (no
        kwargs parsing, no job branch), which is why the accumulate
        blocks stay hand-inlined here; its share of a serving dispatch
        is not measured on a chip."""
        d = duration_s * weight
        f = (flops or 0.0) * weight
        b = (nbytes or 0.0) * weight
        with self._lock:
            t = self._totals
            t[0] += d
            t[1] += f
            t[2] += b
            t[3] += weight
            entry = self._model_entry_locked(model)
            entry[0] += d
            entry[1] += f
            entry[2] += b
            entry[3] += weight
            if bucket is not None:
                bkey = (model, bucket)
                entry = self._buckets.get(bkey)
                if entry is None:
                    entry = self._buckets[bkey] = [0.0, 0.0, 0.0, 0]
                entry[0] += d
                entry[1] += f
                entry[2] += b
                entry[3] += weight

    def record(self, weight: int, duration_s: float, *, flops=None,
               nbytes=None, job: str | None = None,
               model: str | None = None,
               bucket: int | None = None) -> None:
        """General form (not the serving hot path): totals + any of
        job/model/bucket.  The model/bucket half delegates to
        :meth:`record_model` so the eviction cascade exists once."""
        if model:
            self.record_model(
                weight, duration_s, flops, nbytes, model, bucket
            )
            if not job:
                return
            totals = None  # record_model already added them
        else:
            totals = self._totals
        d = duration_s * weight
        f = (flops or 0.0) * weight
        b = (nbytes or 0.0) * weight
        with self._lock:
            if totals is not None:
                totals[0] += d
                totals[1] += f
                totals[2] += b
                totals[3] += weight
            if job:
                entry = self._jobs.get(job)
                if entry is None:
                    entry = self._jobs[job] = [0.0, 0.0, 0.0, 0]
                    while len(self._jobs) > self.max_jobs:
                        self._jobs.popitem(last=False)
                entry[0] += d
                entry[1] += f
                entry[2] += b
                entry[3] += weight

    def attribute(self, duration_s: float, *, flops=None, nbytes=None,
                  job: str | None = None, model: str | None = None,
                  bucket: int | None = None) -> bool:
        """One-shot form (the train epoch loop, which is already
        synced): sampling decision + record in one call; returns
        whether it recorded."""
        weight = self.will_record(model or job or "")
        if not weight:
            return False
        self.record(
            weight, duration_s, flops=flops, nbytes=nbytes,
            job=job, model=model, bucket=bucket,
        )
        return True

    @staticmethod
    def _doc(entry: list, peak_flops: float) -> dict:
        doc = {
            "deviceTimeS": round(entry[0], 6),
            "flops": entry[1],
            "bytes": entry[2],
            "dispatches": entry[3],
        }
        util = mfu(entry[1], entry[0], peak_flops=peak_flops)
        if util is not None:
            doc["mfu"] = util
        return doc

    def model_device_s(self, model: str) -> float:
        """Accumulated device-seconds attributed to ``model`` (0.0
        when unseen/evicted) — the fleet autoscaler's cost-aware
        scale-up signal reads this as a monotone counter and takes
        deltas per tick."""
        with self._lock:
            entry = self._models.get(model)
            return float(entry[0]) if entry else 0.0

    def job_summary(self, job: str,
                    peak_flops: float = 0.0) -> dict | None:
        with self._lock:
            entry = self._jobs.get(job)
            entry = list(entry) if entry else None
        return self._doc(entry, peak_flops) if entry else None

    def snapshot(self, peak_flops: float = 0.0) -> dict:
        with self._lock:
            jobs = {k: list(v) for k, v in self._jobs.items()}
            models = {k: list(v) for k, v in self._models.items()}
            buckets = {k: list(v) for k, v in self._buckets.items()}
            totals = list(self._totals)
        return {
            "sample": self.sample,
            "totals": self._doc(totals, peak_flops),
            "jobs": {k: self._doc(v, peak_flops)
                     for k, v in jobs.items()},
            "models": {k: self._doc(v, peak_flops)
                       for k, v in models.items()},
            "buckets": {
                f"{m}:{b}": self._doc(v, peak_flops)
                for (m, b), v in sorted(buckets.items())
            },
        }


def mfu(flops: float, device_s: float, *,
        peak_flops: float) -> float | None:
    """Model-FLOPs-utilization: achieved over peak.  None when the
    peak is unconfigured or nothing ran — no fabricated utilization."""
    if peak_flops <= 0 or device_s <= 0 or flops <= 0:
        return None
    value = flops / (device_s * peak_flops)
    if not math.isfinite(value):
        return None
    # Significant digits, not decimal places: a tiny model on a big
    # chip legitimately runs at 1e-8 MFU and must not round to zero.
    return float(f"{value:.4g}")


# -- process-wide singletons --------------------------------------------------

_lock = make_lock("costs._lock")
_ledger: CostLedger | None = None
_devtime: DeviceTimeLedger | None = None
_cfg_cache = None


def _cfg():
    global _cfg_cache
    if _cfg_cache is None:
        from learningorchestra_tpu.config import get_config

        _cfg_cache = get_config().costs
    return _cfg_cache


def enabled() -> bool:
    return _cfg().enabled


def deep_enabled() -> bool:
    return _cfg().enabled and _cfg().deep


def peak_flops() -> float:
    return float(_cfg().peak_flops)


def get_ledger() -> CostLedger:
    global _ledger
    with _lock:
        if _ledger is None:
            _ledger = CostLedger(max_programs=_cfg().max_programs)
        return _ledger


def devtime() -> DeviceTimeLedger:
    global _devtime
    with _lock:
        if _devtime is None:
            cfg = _cfg()
            _devtime = DeviceTimeLedger(
                max_jobs=cfg.max_jobs, sample=cfg.sample
            )
        return _devtime


def reset(config=None) -> None:
    """Drop both ledgers (tests; config swap).  ``config`` overrides
    the CostsConfig the rebuilt singletons size from."""
    global _ledger, _devtime, _cfg_cache
    with _lock:
        _ledger = None
        _devtime = None
        _cfg_cache = config


# -- the compile-cache hooks --------------------------------------------------


def note_build(key: str, label: str | None, built_s: float) -> None:
    """Every compile-cache build lands here (see
    ``CompiledProgramCache.get_or_build``): the ledger entry exists
    from this moment even if no builder could run an analysis."""
    if not enabled():
        return
    get_ledger().note_build(key, label, built_s)


def serialized_bytes(key: str) -> int | None:
    """Measured executable size for the cache's byte cap, or None →
    the cache falls back to its flat per-entry estimate."""
    if not enabled():
        return None
    return get_ledger().serialized_bytes(key)


def _avatar(leaf):
    """Shape/dtype avatar of one example leaf, dtype-canonicalized:
    a float64 numpy example must lower as the float32 the real
    ``jnp.asarray`` call would produce under x64-disabled jax, or the
    probed program would not be the one that runs."""
    import jax
    import numpy as np

    if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
        leaf = np.asarray(leaf)
    try:
        dtype = jax.dtypes.canonicalize_dtype(leaf.dtype)
    except Exception:  # noqa: BLE001 — e.g. typed PRNG key dtypes
        dtype = leaf.dtype
    return jax.ShapeDtypeStruct(tuple(leaf.shape), dtype)


def _flatten_cost_analysis(raw):
    """Normalize ``cost_analysis()`` across jax versions: a dict, or a
    list of per-partition dicts (summed)."""
    if raw is None:
        return None
    if isinstance(raw, dict):
        return raw
    if isinstance(raw, (list, tuple)) and raw:
        merged: dict = {}
        for part in raw:
            if not isinstance(part, dict):
                return None
            for k, v in part.items():
                try:
                    merged[k] = merged.get(k, 0.0) + float(v)
                except (TypeError, ValueError):
                    continue
        return merged
    return None


def analyze_jitted(key: str, label: str | None, fn,
                   example_args: tuple, *,
                   aot_eligible: bool = True,
                   collectives_excluded: bool = False
                   ) -> ProgramCost | None:
    """Run XLA cost (and, deep, memory/size) analysis for the program
    ``fn(*example_args)`` and record it under ``key``.

    ``example_args`` may be real arrays or anything with shape/dtype —
    they are reduced to ShapeDtypeStruct avatars, so nothing touches
    (or donates) real buffers.  The lowering re-traces the function
    (~the cost of the trace the build already paid); the deep AOT
    ``compile()`` pays an XLA compile that the persistent XLA disk
    cache dedups against the first real call's.  Best-effort by
    design: any failure counts in ``analysis_failures`` and the build
    proceeds with the un-analyzed ledger entry.

    The deep path's serialized payload is the SAME artifact the
    durable warm-start store persists (train/aot_store.py), so when
    that store is enabled the payload is offered to it here — one
    serialize, two consumers.  ``aot_eligible=False`` opts a program
    out (tuple-valued builders: a restored single executable could not
    stand in for the (epoch, evaluate) pair consumers unpack)."""
    if not enabled():
        return None
    ledger = get_ledger()
    existing = ledger.get(key)
    if existing is not None and existing.analyzed:
        return existing  # device-set invalidation rebuilt it: costs hold
    t0 = time.perf_counter()
    payload = None
    try:
        import jax

        avatars = jax.tree_util.tree_map(_avatar, tuple(example_args))
        lowered = fn.lower(*avatars)
        cost = _flatten_cost_analysis(lowered.cost_analysis())
        memory = None
        serialized = None
        if deep_enabled():
            compiled = lowered.compile()
            try:
                memory = compiled.memory_analysis()
            except Exception:  # noqa: BLE001 — backend may not report
                memory = None
            payload = _serialize_payload(compiled)
            serialized = (
                len(payload[0]) if payload is not None
                else _hlo_proto_size(compiled)
            )
            if cost is None:
                cost = _flatten_cost_analysis(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — analysis must never fail a build
        ledger.note_failure()
        return None
    record = ledger.record_analysis(
        key, label,
        flops=(cost or {}).get("flops"),
        bytes_accessed=(cost or {}).get("bytes accessed"),
        memory=memory,
        serialized=serialized,
        analysis_s=time.perf_counter() - t0,
        collectives_excluded=collectives_excluded,
    )
    if aot_eligible and payload is not None:
        _offer_aot(key, label, payload)
    return record


def _offer_aot(key: str, label: str | None, payload) -> None:
    """Hand the just-serialized executable to the durable store
    (disabled → one attribute check).  The store swallows its own
    failures; this guard covers import/config breakage."""
    try:
        from learningorchestra_tpu.train import aot_store

        store = aot_store.get_store()
        if store is not None:
            store.offer(key, payload, label=label)
    except Exception:  # noqa: BLE001 — persistence never fails a build
        pass


def _serialize_payload(compiled):
    """The durable store's payload tuple for ``compiled`` (blob first —
    train/aot_store.py ``serialize``).  None when the backend can't
    serialize."""
    try:
        from learningorchestra_tpu.train import aot_store

        return aot_store.serialize(compiled)
    except Exception:  # noqa: BLE001
        return None


def _hlo_proto_size(compiled) -> int | None:
    """Fallback size estimate when the AOT serializer is unavailable:
    the serialized HLO proto; None when neither is available."""
    try:
        memory = compiled.memory_analysis()
        proto = getattr(memory, "serialized_hlo_proto", None)
        if proto:
            return len(proto)
    except Exception:  # noqa: BLE001
        pass
    return None


# -- device-time attribution --------------------------------------------------

_JOB: contextvars.ContextVar = contextvars.ContextVar(
    "lo_costs_job", default=None
)


def current_job() -> str | None:
    return _JOB.get()


@contextlib.contextmanager
def job_scope(name: str):
    """Bind the calling thread's dispatches to job ``name`` — the
    executor wraps job bodies (and tune trials: worker-pool threads
    don't inherit context) so the epoch loop attributes correctly."""
    token = _JOB.set(name)
    try:
        yield
    finally:
        _JOB.reset(token)


def attribute(duration_s: float, *, cost: ProgramCost | None = None,
              key: str | None = None, model: str | None = None,
              bucket: int | None = None,
              job: str | None = None) -> bool:
    """The per-dispatch accounting hook.  ``cost`` (or ``key`` to look
    it up) supplies the program's flops/bytes; ``job`` defaults to the
    ambient :func:`job_scope`.  Disabled, this is one config check."""
    if not enabled():
        return False
    if cost is None and key is not None:
        cost = get_ledger().get(key)
    return devtime().attribute(
        duration_s,
        flops=cost.flops if cost is not None else None,
        nbytes=cost.bytes_accessed if cost is not None else None,
        job=job if job is not None else _JOB.get(),
        model=model,
        bucket=bucket,
    )


def job_summary(name: str) -> dict | None:
    """The job's accumulated device-time doc (None when nothing was
    attributed) — the executor stamps it into finished-job metadata."""
    if not enabled():
        return None
    return devtime().job_summary(name, peak_flops=peak_flops())


def serving_totals() -> dict:
    """Aggregate over served models (the tfevents serving_* scalars):
    device seconds, flops, and MFU when a peak is configured."""
    if not enabled():
        return {"deviceTimeS": 0.0, "flops": 0.0, "dispatches": 0}
    snap = devtime().snapshot(peak_flops=peak_flops())
    device_s = sum(
        m["deviceTimeS"] for m in snap["models"].values()
    )
    flops = sum(m["flops"] for m in snap["models"].values())
    out = {
        "deviceTimeS": round(device_s, 6),
        "flops": flops,
        "dispatches": sum(
            m["dispatches"] for m in snap["models"].values()
        ),
    }
    util = mfu(flops, device_s, peak_flops=peak_flops())
    if util is not None:
        out["mfu"] = util
    return out


def snapshot() -> dict:
    """Everything, JSON-shaped — the monitoring endpoint's view."""
    return {
        "enabled": enabled(),
        "peakFlopsPerChip": peak_flops(),
        "ledger": get_ledger().snapshot() if enabled() else {},
        "deviceTime": (
            devtime().snapshot(peak_flops=peak_flops())
            if enabled() else {}
        ),
    }
