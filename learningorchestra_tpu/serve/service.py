"""ServingService — the REST-facing facade over registry + batchers.

Ties a :class:`~learningorchestra_tpu.serve.registry.ModelRegistry`
(artifact → device-resident params) to one
:class:`~learningorchestra_tpu.serve.batcher.MicroBatcher` per served
model, resolves each bucket's jitted ``apply`` through the process-wide
compiled-program cache (``compile_cache.apply_program_key`` — one
executable per (architecture, bucket) for the whole deployment), and
exposes the synchronous predict the API layer serves at
``POST /serve/<model>/predict``.

Invalidation: subscribes to the service context's artifact-change
notifications, so a PATCH re-train or DELETE of a served artifact drops
its resident weights before the next request.
"""

from __future__ import annotations

import os
import time

import numpy as np

from learningorchestra_tpu.concurrency_rt import make_lock
from learningorchestra_tpu.jobs.leases import LeaseTimeout
from learningorchestra_tpu.obs import tracing as obs_tracing
from learningorchestra_tpu.obs.metrics import get_registry
from learningorchestra_tpu.serve.batcher import MicroBatcher
from learningorchestra_tpu.serve.fleet.manager import FleetManager
from learningorchestra_tpu.serve.registry import ModelRegistry, ServeError

#: Steps of serving_* scalar history kept (and rewritten per snapshot).
_SCALAR_WINDOW = 512


class _PredictHist:
    """Identity-cached handle on the current registry's per-model
    predict latency histogram — the API server's ``_obs_handles``
    rebind idiom, so a ``reset_registry()`` mid-life re-homes the
    series while the steady state pays one identity check instead of
    a name lookup per predict.  The rollup engine derives windowed
    per-model quantiles from this family's bucket deltas and the
    predict-latency SLO reads good/bad fractions off the same series.
    Cardinality is bounded by the serving registry's max_models cap;
    no-op when LO_TPU_OBS_ENABLED=0."""

    __slots__ = ("_reg", "_hist", "_bound")

    def __init__(self):
        self._reg = None
        self._hist = None
        self._bound: dict = {}

    def observe(self, dt_s: float, model: str) -> None:
        reg = get_registry()
        if reg is not self._reg:
            self._hist = reg.histogram(
                "lo_serving_predict_duration_seconds",
                "End-to-end predict latency per served model "
                "(queue wait + coalesce + jitted apply + handoff).",
                labels=("model",),
            )
            self._bound = {}
            self._reg = reg
        # Per-model bound series (<= max_models entries): the steady
        # state is one dict hit + Histogram series update.
        bound = self._bound.get(model)
        if bound is None:
            if len(self._bound) >= 256:
                # Lifetime guard: max_models bounds CONCURRENT models,
                # not every name ever served — a churny deployment
                # must not grow this cache forever.
                self._bound.clear()
            bound = self._bound[model] = self._hist.bind(model=model)
        bound.observe(dt_s)


_predict_hist = _PredictHist()


class ServingService:
    def __init__(self, ctx, monitoring_root: str | None = None):
        self.ctx = ctx
        self.cfg = ctx.config.serve
        self.monitoring_root = monitoring_root
        self.registry = ModelRegistry(
            self._load_estimator,
            max_models=self.cfg.max_models,
            max_bytes=self.cfg.max_bytes,
            # An LRU-evicted model's batcher (worker thread + stats)
            # must die with its entry, or serving N distinct models
            # over a process lifetime leaks N threads.
            on_evict=self._teardown_model,
        )
        self._batchers: dict[str, MicroBatcher] = {}
        # Fleet serving (serve/fleet/): per-model replica sets over
        # leased chips + the shared autoscaler.  Dormant (one dict
        # read per predict, no thread) until a model's replica bounds
        # allow max > 1.
        self.fleet = FleetManager(self)
        # Streaming decode (serve/decode/): resident KV page pools +
        # continuous batching for GreedyDecodeMixin models.  Dormant
        # (no thread, no pools) until the first /generate.
        from learningorchestra_tpu.serve.decode import DecodeEngine

        self.decode = DecodeEngine(self)
        self._lock = make_lock("ServingService._lock")
        self._closed = False
        # tfevents snapshot state: a fixed wall_time keeps one stable
        # events file that each snapshot rewrites with the (windowed)
        # history; the lock serializes concurrent monitoring polls —
        # two truncating writers on one file would interleave records
        # and break the CRC framing.
        self._t0 = time.time()
        self._scalar_history: dict[str, list] = {}
        self._scalar_lock = make_lock("ServingService._scalar_lock")
        ctx.add_artifact_change_listener(self._on_artifact_changed)

    # -- model residency -----------------------------------------------------

    def _load_estimator(self, name: str):
        from learningorchestra_tpu.services.context import ValidationError
        from learningorchestra_tpu.train.neural import NeuralEstimator

        meta = self.ctx.require_finished_parent(name)
        instance = self.ctx.volumes.read_object(meta.get("type", ""), name)
        # The registry's ``load_artifact`` span is open around this.
        obs_tracing.set_span_attrs(
            **self.ctx.volumes.raw_extent(meta.get("type", ""), name)
        )
        if not isinstance(instance, NeuralEstimator):
            raise ValidationError(
                f"artifact {name!r} is not a neural model binary "
                f"({type(instance).__name__}); only NeuralEstimator "
                "artifacts are servable"
            )
        return instance

    def load(self, name: str) -> dict:
        """Pin ``name`` resident (idempotent) — the explicit warm-up the
        ops path uses before pointing traffic at a model."""
        return self.registry.get(name).to_dict()

    def unload(self, name: str) -> bool:
        self._teardown_model(name, keep_bounds=False)
        return self.registry.unload(name)

    def list_loaded(self) -> list[dict]:
        return self.registry.list()

    def _on_artifact_changed(self, name: str) -> None:
        """Artifact overwritten (re-train) or deleted: resident weights
        are stale — drop them; the next request reloads or 404s.  A
        DELETED artifact also forgets its fleet bounds — a future
        model reusing the name must not silently inherit them and
        fleet itself onto leased chips — while an overwrite keeps
        them, so a re-trained model comes back at its configured
        scale."""
        gone = not self.ctx.artifacts.metadata.exists(name)
        if self.registry.invalidate(name) or gone:
            self._teardown_model(name, keep_bounds=not gone)

    def _drop_batcher(self, name: str) -> None:
        """Discard the classic single-path batcher (teardown/unload
        paths).  NOT the fleet cutover — that goes through
        :meth:`retire_single_path`, which also carries the batcher's
        lifetime counters into the replica set."""
        with self._lock:
            batcher = self._batchers.pop(name, None)
        if batcher is not None:
            batcher.close()

    def _teardown_model(self, name: str, *, keep_bounds: bool = True
                        ) -> None:
        """Release everything serving ``name``: the single-path
        batcher AND any fleet replica set (drained, chips released).
        ``keep_bounds`` survives invalidation/eviction so a re-trained
        model comes back at its configured scale; an explicit unload
        forgets the model entirely."""
        self._drop_batcher(name)
        # Decode pools hold the stale architecture's KV shapes and
        # step closures — in-flight streams fail fast, the next
        # /generate rebuilds against the reloaded artifact.
        self.decode.drop_model(name)
        self.fleet.drop(name, keep_bounds=keep_bounds)

    # -- predict -------------------------------------------------------------

    def _batcher_for(self, name: str) -> MicroBatcher:
        with self._lock:
            batcher = self._batchers.get(name)
            if batcher is None:
                if self._closed:
                    raise RuntimeError("serving is shut down")
                if self.fleet.engaged(name):
                    # Raced a fleet enable between the predict's
                    # routing check and here: refuse retriably (429 +
                    # Retry-After) instead of resurrecting the batcher
                    # the fleet just retired — the retry routes onto
                    # the replicas.
                    from learningorchestra_tpu.serve.batcher import (
                        BatcherClosed,
                    )

                    raise BatcherClosed(
                        f"model {name!r} is moving to fleet serving; "
                        "retry"
                    )
                batcher = self._batchers[name] = MicroBatcher(
                    lambda padded, _n=name: self._dispatch(_n, padded),
                    max_batch=self.cfg.max_batch,
                    max_queue=self.cfg.max_queue,
                    flush_ms=self.cfg.flush_ms,
                    name=name,
                )
            return batcher

    def _dispatch(self, name: str, padded: np.ndarray, replica=None):
        """Run one padded bucket through the cache-resolved apply.

        Resolving the registry entry HERE (not at batcher creation)
        means an invalidation between requests serves the reloaded
        artifact's weights, never a stale closure's.

        ``replica`` (a fleet Replica) redirects only the DATA — its
        device-placed parameter copy and inputs — never the program:
        every replica of an architecture resolves the same
        (arch, bucket) executable from the compile cache, so scaling
        1→N adds zero misses to THIS cache.  (XLA itself still
        compiles per device underneath the shared jitted callable —
        with ``LO_TPU_AOT_REPLICA_PREWARM`` on, a fresh replica pays
        that device-side warm-up against the recorded hot bucket set
        BEFORE the router may pick it; see
        :meth:`replica_warmup_factory`.)"""
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu import faults
        from learningorchestra_tpu.obs import costs as obs_costs
        from learningorchestra_tpu.train import compile_cache as cc

        # Chaos probe at the batch boundary: one injected failure
        # fails every request coalesced into THIS dispatch (the real
        # blast radius of a device fault mid-batch), leaving the
        # batcher worker and later dispatches healthy.
        faults.hit("serve.apply")
        entry = self.registry.get(name)
        rows = padded.shape[0]
        apply = entry.apply_fns.get(rows)
        if apply is None:
            key = cc.apply_program_key(
                entry.estimator.module, rows=rows
            )
            label = (
                f"serve:{type(entry.estimator.module).__name__}"
                f":b{rows}"
            )

            def builder():
                from learningorchestra_tpu.train.neural import (
                    _probe_program_cost,
                )

                jitted = jax.jit(entry.estimator.module.apply)
                # Cost probe on the build-once path (the one shared
                # wrapper, train/neural.py): the bucket's flops/HBM
                # land in the program ledger, so every later dispatch
                # attributes with real numerators.  The lowering runs
                # on host avatars with no mesh — collective-free by
                # construction — so the numbers stay honest when a
                # SHARDED replica later runs this bucket under GSPMD
                # (lo_serving_bucket_* must not book collective flops).
                _probe_program_cost(
                    key, label, jitted,
                    lambda: (entry.params, padded),
                    collectives_excluded=True,
                )
                return jitted

            apply = entry.apply_fns[rows] = (
                cc.get_cache().get_or_build(key, builder, label=label)
            )
        # Record the bucket for replica pre-warm (shape + dtype is
        # all a dummy dispatch needs); dies with the entry alongside
        # apply_fns, so invalidation never warms a stale architecture.
        entry.warm_shapes[rows] = (padded.shape, str(padded.dtype))
        if replica is not None:
            # Hand place() the HOST array: one host→replica-device
            # transfer, not host→default-device→replica-device.
            params, x = replica.place(entry, padded)
        else:
            params, x = entry.params, jnp.asarray(padded)
        if not obs_costs.enabled():
            return apply(params, x)
        # Per-dispatch device-time attribution, sampled: only a
        # dispatch the stride selects pays the sync (the consumer
        # blocks on the result right after, so steady-state throughput
        # is unmoved; sampled-out dispatches keep jax's async
        # pipelining).  Books the interval against the model and shape
        # bucket — the fleet's replica dispatches land here too, so
        # per-model ledgers cover single-path and fleet serving alike.
        led = obs_costs.devtime()
        weight = led.will_record(name)
        if not weight:
            return apply(params, x)
        cost = self._apply_cost(entry, rows)
        t0 = time.perf_counter()
        out = apply(params, x)
        try:
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001
            pass
        led.record_model(
            weight, time.perf_counter() - t0,
            cost.flops if cost is not None else None,
            cost.bytes_accessed if cost is not None else None,
            name, rows,
        )
        return out

    @staticmethod
    def _apply_cost(entry, rows: int):
        """The (arch, bucket) ProgramCost for attribution, memoized on
        the registry entry next to the apply itself.  A ledger MISS
        memoizes too (False sentinel): analysis happens at build time,
        before any dispatch, so a missing record stays missing — and
        re-deriving the fingerprint per dispatch is exactly the hot-
        path cost the memo exists to avoid."""
        cost = entry.apply_costs.get(rows)
        if cost is None:
            from learningorchestra_tpu.obs import costs as obs_costs
            from learningorchestra_tpu.train import compile_cache as cc

            cost = obs_costs.get_ledger().get(
                cc.apply_program_key(entry.estimator.module, rows=rows)
            )
            entry.apply_costs[rows] = cost if cost is not None else False
        return cost or None

    def replica_dispatch_factory(self, name: str):
        """Per-replica dispatch binder for the fleet manager: same
        registry/compile-cache path as the single-batcher dispatch,
        plus the replica's device placement.  Binder ONLY — the
        single-path batcher is retired via :meth:`retire_single_path`
        after the first replica actually places, so a failed scale-up
        (chip pool exhausted) leaves the model serving exactly as
        before instead of knocking it off the air."""
        def factory(replica):
            return lambda padded: self._dispatch(
                name, padded, replica=replica
            )

        return factory

    def replica_warmup_factory(self, name: str):
        """Pre-warm binder for the fleet manager, or None when
        ``LO_TPU_AOT_REPLICA_PREWARM`` is off.  The returned callable
        runs dummy dispatches for every bucket the model has actually
        served (``entry.warm_shapes``) through the new replica —
        paying XLA's per-device executable load/compile BEFORE the
        P2C router can pick the replica, so scale-up under a traffic
        spike no longer exposes cold p99.  Warm-up failures are the
        caller's to log: a replica that can't warm still serves (cold)
        rather than stranding acquired chips."""
        from learningorchestra_tpu.config import get_config

        try:
            if not get_config().aot.replica_prewarm:
                return None
        except Exception:  # noqa: BLE001 — config breakage → no warmup
            return None

        def warm(replica):
            try:
                entry = self.registry.get(name)
            except Exception:  # noqa: BLE001 — gone → nothing to warm
                return
            for rows, (shape, dtype) in sorted(
                entry.warm_shapes.items()
            ):
                dummy = np.zeros(shape, dtype=dtype)
                self._dispatch(name, dummy, replica=replica)
            # Decode leg: replay recorded (slot, kv) step executables
            # so streamed generation never pays a cold replica either.
            self.decode.warm_replica(name, replica)

        return warm

    def pop_single_path(self, name: str) -> MicroBatcher | None:
        """Detach (NOT close) the model's single-path batcher — THE
        fleet cutover entry point (``FleetManager._finish_cutover``).
        The manager absorbs its counters into the live set, registers
        the set, and only then drains the detached batcher: predicts
        route onto replicas immediately instead of stalling behind
        the old path's flush."""
        with self._lock:
            return self._batchers.pop(name, None)

    @staticmethod
    def _as_batch(instances) -> np.ndarray:
        """Request JSON → input batch, REST dtype discipline: float
        features land f32 (f64 would retrace against f32-traced
        programs), integer features stay int (token models)."""
        try:
            x = np.asarray(instances)
        except (ValueError, TypeError) as exc:
            # Ragged rows (inhomogeneous shapes) are a malformed
            # request body → 406, not an unhandled 500.
            raise ServeError(
                f"'instances' is not a rectangular array: {exc}"
            ) from None
        if x.ndim == 0:
            raise ServeError("'instances' must be a non-empty array")
        if x.ndim == 1:
            # A single instance's feature vector: serve it as one row.
            x = x[None, :] if x.shape[0] else x
        if x.shape[0] == 0:
            raise ServeError("'instances' must be a non-empty array")
        if np.issubdtype(x.dtype, np.floating):
            return x.astype(np.float32)
        if np.issubdtype(x.dtype, np.integer):
            return x.astype(np.int32)
        raise ServeError(
            f"instances dtype {x.dtype} is not numeric"
        )

    def predict(self, name: str, instances) -> dict:
        """Synchronous low-latency predict: coalesced, bucketed, split.

        Raises ``QueueFull`` under backpressure (API → 429) and the
        context's NotFound/Validation errors for bad models (404/406).
        """
        x = self._as_batch(instances)
        entry = self.registry.get(name)  # load-before-queue: 404 fast
        t0 = time.perf_counter()
        try:
            rs = self.fleet.routing_set(name)
        except LeaseTimeout:
            # A PARTIAL cutover registers a routable set before
            # re-raising — serve on it; otherwise the single-path
            # batcher is only retired AFTER the first replica places,
            # so degrade to it rather than going dark.  Only with
            # neither does the 503 + Retry-After surface.
            rs = self.fleet.registered_set(name)
            if rs is None and self._batchers.get(name) is None:
                raise
        if rs is not None:
            out, replica = rs.submit(x)
            entry.requests += 1
            dt = time.perf_counter() - t0
            _predict_hist.observe(dt, model=name)
            return {
                "model": name,
                "predictions": out.tolist(),
                "latencyMs": round(dt * 1e3, 3),
                "replica": replica.idx,
                "device": replica.device_id or "host",
            }
        out = self._batcher_for(name).submit(x)
        entry.requests += 1
        dt = time.perf_counter() - t0
        _predict_hist.observe(dt, model=name)
        return {
            "model": name,
            "predictions": out.tolist(),
            "latencyMs": round(dt * 1e3, 3),
        }

    def generate(self, name: str, prompts, **kwargs):
        """Streaming/batch LM generation — the decode engine's facade
        (``POST /serve/<model>/generate``).  Returns a dict for
        non-stream requests, a :class:`~learningorchestra_tpu.serve.
        decode.DecodeStream` (the SSE payload) for ``stream=True``."""
        return self.decode.generate(name, prompts, **kwargs)

    # -- observability -------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            per_model = {
                name: b.stats() for name, b in self._batchers.items()
            }
        # Fleet models surface through the SAME per-model stats shape
        # (replica batchers merged), so aggregate()/tfevents/Prometheus
        # see one consistent view; per-replica detail rides the
        # dedicated "fleet" key.
        for name, rs in self.fleet.sets_snapshot():
            per_model[name] = rs.merged_stats()
        return {
            "registry": self.registry.stats(),
            "models": per_model,
            "fleet": self.fleet.snapshot(),
            "decode": self.decode.stats(),
            "config": {
                "maxBatch": self.cfg.max_batch,
                "maxQueue": self.cfg.max_queue,
                "flushMs": self.cfg.flush_ms,
                "maxModels": self.cfg.max_models,
                "maxBytes": self.cfg.max_bytes,
                "retryAfterS": self.cfg.retry_after_s,
            },
        }

    def aggregate(self, stats: dict | None = None) -> dict:
        """Cross-model roll-up over :meth:`stats` — the ONE place the
        per-batcher aggregation lives; the tfevents snapshot below and
        the API server's Prometheus collector both consume it, so a
        new batcher stat lands on every surface from here."""
        if stats is None:
            stats = self.stats()
        agg = {"requests": 0, "rows": 0, "batches": 0, "overflows": 0,
               "padded_rows": 0, "queue_depth": 0}
        occ: list[float] = []
        quantiles = {"p50": 0.0, "p95": 0.0, "p99": 0.0}
        for mstats in stats["models"].values():
            agg["requests"] += mstats["requests"]
            agg["rows"] += mstats["rows"]
            agg["batches"] += mstats["batches"]
            agg["overflows"] += mstats["overflows"]
            agg["padded_rows"] += mstats["paddedRows"]
            agg["queue_depth"] += mstats["queueDepth"]
            occ.append(mstats["batchOccupancy"])
            for q in quantiles:
                # Max over models: the worst served model is the one
                # an SLO cares about.
                quantiles[q] = max(quantiles[q], mstats["latencyMs"][q])
        agg["occupancy"] = (
            round(sum(occ) / len(occ), 4) if occ else 0.0
        )
        agg["quantiles"] = quantiles
        agg["resident_models"] = stats["registry"]["residentModels"]
        agg["resident_bytes"] = stats["registry"]["residentBytes"]
        return agg

    def snapshot_scalars(self, stats: dict | None = None) -> dict:
        """Append current aggregate stats to the serving history and
        (when a monitoring root exists) rewrite them as ``serving_*``
        tfevents scalars — each poll of the monitoring endpoint adds
        one step, so TensorBoard shows serving health over time.
        Pass ``stats`` when the caller already computed :meth:`stats`
        (the monitoring route serves both) to avoid taking every
        batcher lock twice per poll."""
        a = self.aggregate(stats)
        agg = {
            "serving_requests": a["requests"],
            "serving_rows": a["rows"],
            "serving_batches": a["batches"],
            "serving_overflows": a["overflows"],
            "serving_queue_depth": a["queue_depth"],
            "serving_batch_occupancy": a["occupancy"],
            "serving_p50_ms": a["quantiles"]["p50"],
            "serving_p95_ms": a["quantiles"]["p95"],
            "serving_p99_ms": a["quantiles"]["p99"],
            "serving_resident_models": a["resident_models"],
            "serving_resident_bytes": a["resident_bytes"],
        }
        # Cost-accounting scalars (obs/costs.py): attributed device
        # seconds across served models, and achieved-vs-peak MFU when
        # the operator configured the chip's peak FLOP/s.
        try:
            from learningorchestra_tpu.obs import costs as obs_costs

            totals = obs_costs.serving_totals()
            agg["serving_device_time_s"] = totals["deviceTimeS"]
            if "mfu" in totals:
                agg["serving_mfu"] = totals["mfu"]
        except Exception:  # noqa: BLE001 — scalars must never fail
            pass  # the monitoring poll
        with self._scalar_lock:
            for key, val in agg.items():
                steps = self._scalar_history.setdefault(key, [])
                steps.append(float(val))
                # Bounded window: a long-lived server polled every few
                # seconds must not grow this (or the rewritten events
                # file) without limit.
                if len(steps) > _SCALAR_WINDOW:
                    del steps[:-_SCALAR_WINDOW]
            if self.monitoring_root:
                from learningorchestra_tpu.services.tfevents import (
                    write_scalars,
                )

                logdir = os.path.join(
                    str(self.monitoring_root), "serving"
                )
                try:
                    # Fixed wall_time → fixed file name: every
                    # snapshot rewrites ONE events file with the
                    # windowed history.
                    write_scalars(
                        logdir, self._scalar_history,
                        wall_time=self._t0,
                    )
                except OSError:
                    pass  # observability must never fail the poll
        return agg

    def close(self) -> None:
        with self._lock:
            self._closed = True
            batchers = list(self._batchers.values())
            self._batchers.clear()
        # Decode first: in-flight streams get a terminal event before
        # their replicas/chips go away under them.
        self.decode.close()
        # Fleet next: stops the autoscaler (no scale decisions against
        # a closing service), drains replica batchers, releases chips.
        self.fleet.close()
        for batcher in batchers:
            batcher.close()
        self.registry.clear()
