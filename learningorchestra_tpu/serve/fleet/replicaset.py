"""ReplicaSet — N replicas of one served model across leased chips.

Before the fleet, a served model was one MicroBatcher dispatching on
the default device: the chip-lease subsystem and the serving registry
never met, and the only answer to saturation was 429.  A ``ReplicaSet``
pins each replica to a chip acquired through
:meth:`jobs.leases.DeviceLeaser.acquire` (held for the replica's
lifetime, not a with-block), gives it its own MicroBatcher, and routes
each request with power-of-two-choices on live batcher queue depth —
429 only when EVERY replica's bounded queue refuses the request.

Executable sharing: replicas do NOT get their own compiled programs.
The dispatch factory (bound by the serving service) resolves applies
through the process-wide compile cache keyed on (architecture, bucket),
so scaling 1→N adds zero compile-cache misses; only the parameter copy
is per-device (``Replica.place``).  On CPU-only backends leases grant
no devices and replicas share the registry's resident params — the
fleet machinery is then pure routing, which is what the unit tests
exercise.

Drain-before-unload: scale-down removes the victim from the routable
list FIRST, then closes its batcher (``MicroBatcher.close`` flushes
everything queued), then releases the chip.  A request that raced into
the victim either rides the final flush or gets ``BatcherClosed`` and
is re-routed to a surviving replica by :meth:`ReplicaSet.submit` — no
in-flight predict is dropped.
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np

from learningorchestra_tpu.concurrency_rt import make_lock
from learningorchestra_tpu.log import get_logger, kv
from learningorchestra_tpu.obs import tracing
from learningorchestra_tpu.serve.batcher import (
    BatcherClosed,
    MicroBatcher,
    QueueFull,
)
from learningorchestra_tpu.serve.fleet.router import P2CRouter

logger = get_logger("fleet")

#: Batcher lifetime-counter keys a set's retired pool accumulates.
_COUNTER_KEYS = ("requests", "rows", "batches", "paddedRows",
                 "overflows")


def _stats_delta(final: dict, pre: dict) -> dict:
    """What a batcher did AFTER the ``pre`` snapshot — stats-shaped,
    so ``absorb_stats`` takes it unchanged."""
    delta = {key: final[key] - pre[key] for key in _COUNTER_KEYS}
    pre_w = pre["batchOccupancy"] * pre["batches"]
    final_w = final["batchOccupancy"] * final["batches"]
    delta["batchOccupancy"] = (
        (final_w - pre_w) / delta["batches"] if delta["batches"] else 0.0
    )
    pre_buckets = pre["bucketHistogram"]
    delta["bucketHistogram"] = {
        bucket: count - pre_buckets.get(bucket, 0)
        for bucket, count in final["bucketHistogram"].items()
        if count - pre_buckets.get(bucket, 0)
    }
    return delta


def _shard_tree(params, devs):
    """Place a param tree across a multi-chip slice: one-axis GSPMD
    mesh, leading-dim sharding where the dim divides the slice size,
    replication elsewhere.  Returns ``(placed_tree, spec)`` where
    ``spec`` describes the layout (status surface) and carries the
    replicated input sharding under the private ``"_repl"`` key."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    n = len(devs)
    mesh = Mesh(_np.array(devs), ("shard",))
    repl = NamedSharding(mesh, P())
    counts = {"sharded": 0, "replicated": 0}

    def put(leaf):
        if (getattr(leaf, "ndim", 0) >= 1
                and leaf.shape[0] >= n and leaf.shape[0] % n == 0):
            counts["sharded"] += 1
            return jax.device_put(
                leaf, NamedSharding(mesh, P("shard"))
            )
        counts["replicated"] += 1
        return jax.device_put(leaf, repl)

    placed = jax.tree_util.tree_map(put, params)
    spec = {
        "axis": "shard", "devices": n,
        "strategy": "leading-dim",
        "shardedLeaves": counts["sharded"],
        "replicatedLeaves": counts["replicated"],
        "_repl": repl,
    }
    return placed, spec


class Replica:
    """One routable copy of a served model: chip lease + batcher +
    per-device parameter placement.

    A replica may hold MORE than one chip (``devices_per_replica`` on
    the set): the lease then carries the whole slice and ``place``
    shards the parameter tree across it with a one-axis GSPMD mesh —
    leaves whose leading dim divides evenly split along it, the rest
    replicate.  The router/autoscaler/pre-warm never look inside: a
    sharded replica is one routable unit with one batcher, exactly
    like a single-chip one."""

    __slots__ = (
        "model", "idx", "device_id", "devices", "shard_spec",
        "batcher", "created_at", "warmed", "_handle", "_jax_device",
        "_jax_devices", "_device_resolved", "_placed",
    )

    def __init__(self, model: str, idx: int, handle):
        self.model = model
        self.idx = idx
        self._handle = handle
        self.devices: list[str] = (
            list(handle.devices) if handle is not None else []
        )
        self.device_id: str | None = (
            self.devices[0] if self.devices else None
        )
        # Populated on first multi-chip placement: how the param tree
        # landed on the slice (surfaced via GET /serve/<m>/replicas).
        self.shard_spec: dict | None = None
        self._jax_devices: list | None = None
        self.created_at = time.time()
        # True once the pre-warm dispatches (hot bucket set) completed
        # before the replica became routable; False means it serves
        # cold (warm-up off, no recorded buckets, or warm-up failed).
        self.warmed = False
        self.batcher: MicroBatcher | None = None
        self._jax_device = None
        self._device_resolved = False
        # (registry entry, params placed on this replica's device) —
        # keyed by entry IDENTITY so an artifact invalidation/reload
        # re-places fresh weights, never serves a stale copy.
        self._placed: tuple | None = None

    def place(self, entry, x):
        """(params, inputs) for this replica's device(s), from the
        HOST input array — one host→device transfer, never a bounce
        through the default device.  Unplaced replicas (CPU backend,
        unresolvable id) share the registry's resident tree — zero
        extra memory, shared executables (jit converts host inputs
        itself).

        Multi-chip leases shard instead of copy: the param tree lands
        on a one-axis mesh over the slice (leaves split along the
        leading dim when it divides, replicated otherwise) and the
        input is replicated — ``jax.jit`` then runs the bucket program
        under GSPMD across the slice, so a model too big for one
        chip's HBM still serves as ONE routable replica."""
        if not self._device_resolved:
            self._device_resolved = True
            if self.devices:
                from learningorchestra_tpu.jobs.leases import (
                    jax_device_for,
                )

                resolved = [jax_device_for(d) for d in self.devices]
                if all(d is not None for d in resolved):
                    self._jax_devices = resolved
                    self._jax_device = resolved[0]
        devs = self._jax_devices
        if devs is None:
            return entry.params, x
        import jax

        if len(devs) == 1:
            cached = self._placed
            if cached is None or cached[0] is not entry:
                self._placed = cached = (
                    entry, jax.device_put(entry.params, devs[0])
                )
            return cached[1], jax.device_put(x, devs[0])
        cached = self._placed
        if cached is None or cached[0] is not entry:
            placed, spec = _shard_tree(entry.params, devs)
            self.shard_spec = spec
            self._placed = cached = (entry, placed, spec["_repl"])
        return cached[1], jax.device_put(x, cached[2])

    def release(self) -> None:
        self._placed = None
        if self._handle is not None:
            self._handle.release()

    def status(self) -> dict:
        stats = self.batcher.stats() if self.batcher is not None else {}
        spec = self.shard_spec
        return {
            "replica": self.idx,
            "device": self.device_id or "host",
            "devices": self.devices or ["host"],
            "shardSpec": (
                {k: v for k, v in spec.items() if not k.startswith("_")}
                if spec is not None else None
            ),
            "createdAt": self.created_at,
            "requests": stats.get("requests", 0),
            "queueDepth": stats.get("queueDepth", 0),
            "batches": stats.get("batches", 0),
            "overflows": stats.get("overflows", 0),
            "latencyMs": stats.get("latencyMs", {}),
            "warmed": self.warmed,
        }


class ReplicaSet:
    """The per-model fleet: replica lifecycle + P2C request routing.

    ``dispatch_factory(replica)`` returns the padded-bucket dispatch
    for one replica — the serving service binds the real registry +
    compile-cache + device-placement dispatch; tests inject stubs to
    exercise routing/scaling without a model.
    """

    def __init__(
        self,
        name: str,
        serve_cfg,
        leaser,
        dispatch_factory: Callable[[Replica], Callable],
        *,
        min_replicas: int = 1,
        max_replicas: int = 1,
        lease_timeout_s: float = 5.0,
        router_seed: int = 0,
        warmup: Callable[[Replica], None] | None = None,
        devices_per_replica: int = 1,
    ):
        if not 1 <= min_replicas <= max_replicas:
            raise ValueError(
                f"need 1 <= min ({min_replicas}) <= max "
                f"({max_replicas})"
            )
        if int(devices_per_replica) < 1:
            raise ValueError(
                "devices_per_replica must be >= 1, got "
                f"{devices_per_replica}"
            )
        self.name = name
        self._cfg = serve_cfg
        self._leaser = leaser
        self._factory = dispatch_factory
        # Optional pre-router warm-up (serve.ServingService binds the
        # hot-bucket dummy dispatches when LO_TPU_AOT_REPLICA_PREWARM
        # is on): runs against a fresh replica BEFORE it joins the
        # routable list, so the P2C router never picks a cold device.
        self._warmup = warmup
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        # Chips per replica: > 1 turns every lease into a multi-chip
        # slice and every replica into a GSPMD-sharded one (models
        # bigger than one chip's HBM).  Fixed for the set's lifetime —
        # changing it means re-placing every param tree, i.e. a new
        # set.
        self.devices_per_replica = int(devices_per_replica)
        self.lease_timeout_s = float(lease_timeout_s)
        import zlib

        # Seed mixed with a stable CRC of the model name (the faults
        # plane's idiom): distinct models route through distinct but
        # reproducible RNG streams.
        self.router = P2CRouter(
            (int(router_seed) << 32) ^ zlib.crc32(name.encode())
        )
        self._replicas: list[Replica] = []
        self._lock = make_lock("ReplicaSet._lock")
        # Scaling is serialized separately from the routing lock: a
        # lease acquisition may block for seconds, and two concurrent
        # scalers (autoscaler tick + manual POST + lazy ensure) must
        # converge on one target instead of overshooting; routing
        # meanwhile keeps reading the replica list freely.
        self._scale_lock = make_lock("ReplicaSet._scale_lock")
        self._closed = False
        self.scale_ups = 0
        self.scale_downs = 0
        # CLIENT-VISIBLE sheds: submit exhausted every candidate and
        # raised (→ a real 429).  Deliberately distinct from the
        # per-replica batcher ``overflows``, which also count requests
        # that overflowed one replica but were re-routed and SERVED by
        # another — scaling on those would lease chips no load needs.
        self.sheds = 0
        # Lifetime counters folded in from drained (scaled-down)
        # replicas: the set's cumulative requests/overflows must stay
        # monotonic across scale cycles — a counter that regresses
        # would corrupt the autoscaler's per-tick deltas (negative
        # "served"/"shed") and move counter-typed Prometheus series
        # backwards.
        self._retired = {
            "requests": 0, "rows": 0, "batches": 0, "paddedRows": 0,
            "overflows": 0, "occ_weighted": 0.0, "buckets": {},
        }

    # -- scaling -------------------------------------------------------------

    @property
    def size(self) -> int:
        return len(self._replicas)

    def set_bounds(self, min_replicas: int, max_replicas: int) -> None:
        if not 1 <= min_replicas <= max_replicas:
            raise ValueError(
                f"need 1 <= min ({min_replicas}) <= max "
                f"({max_replicas})"
            )
        with self._lock:
            self.min_replicas = int(min_replicas)
            self.max_replicas = int(max_replicas)

    def scale_to(self, n: int, *, reason: str = "manual") -> int:
        """Grow/shrink to ``n`` replicas (clamped to [min, max]);
        returns the resulting count.  Scale-up may raise
        ``LeaseTimeout`` when the chip pool can't place a new replica
        within the lease budget — already-added replicas stay.

        The clamp re-reads the bounds EVERY iteration: a concurrent
        ``set_bounds`` shrinking ``max`` mid-scale must re-target, not
        spin leasing-and-discarding chips forever."""
        with self._scale_lock:
            while True:
                with self._lock:
                    if self._closed:
                        return 0
                    cur = len(self._replicas)
                    target = max(
                        self.min_replicas,
                        min(self.max_replicas, int(n)),
                    )
                if cur < target:
                    if not self._add_replica(reason):
                        # Bounds shrank (or the set closed) while the
                        # lease was being placed — re-read and settle.
                        with self._lock:
                            return len(self._replicas)
                elif cur > target:
                    self._remove_replica(reason)
                else:
                    return cur

    def _add_replica(self, reason: str) -> bool:
        with self._lock:
            # Lowest free index, NOT a monotonic counter: replica
            # indices are Prometheus label values, and a fleet
            # oscillating under the autoscaler for days must cycle
            # through a bounded label set (<= max_replicas distinct
            # values), not mint r47, r48, ... forever.
            live = {r.idx for r in self._replicas}
            idx = next(
                i for i in range(len(live) + 1) if i not in live
            )
        # "@" keeps the label OUT of the deadline watchdog's revoke
        # namespace: revoke(job) matches "<job>" or "<job>:*", and job
        # names can be any NAME-regex token ("serve" included) but can
        # never contain "@" — a job named "serve" expiring its
        # deadline must not force-free every fleet replica's chip.
        handle = self._leaser.acquire(
            self.devices_per_replica,
            label=f"serve@{self.name}:r{idx}",
            timeout=self.lease_timeout_s,
        )
        replica = Replica(self.name, idx, handle)
        replica.batcher = MicroBatcher(
            self._factory(replica),
            max_batch=self._cfg.max_batch,
            max_queue=self._cfg.max_queue,
            flush_ms=self._cfg.flush_ms,
            name=f"{self.name}:r{idx}",
        )
        if self._warmup is not None:
            # Warm BEFORE the replica is routable: the dummy
            # dispatches pay XLA's per-device executable load here,
            # not under the first routed request's latency.  A failed
            # warm-up is logged and the replica serves cold (warmed
            # stays False) — availability beats warmth.
            try:
                with tracing.span(
                    "replica.warmup", model=self.name, replica=idx,
                    device=replica.device_id or "host",
                ):
                    self._warmup(replica)
                replica.warmed = True
            except Exception as exc:  # noqa: BLE001
                logger.warning(kv(
                    event="replica_warmup_failed", model=self.name,
                    replica=idx,
                    device=replica.device_id or "host",
                    error=repr(exc),
                ))
        with self._lock:
            # Closed (or raced past max by a concurrent scaler) while
            # the lease was being placed: hand everything straight back.
            discard = (
                self._closed
                or len(self._replicas) >= self.max_replicas
            )
            if not discard:
                self._replicas.append(replica)
                self.scale_ups += 1
        if discard:
            replica.batcher.close()
            replica.release()
            return False
        logger.info(kv(
            event="replica_up", model=self.name, replica=idx,
            device=replica.device_id or "host", reason=reason,
        ))
        return True

    def _remove_replica(self, reason: str) -> None:
        with self._lock:
            if len(self._replicas) <= 1:
                return  # never drain the last routable replica
            # Newest-first keeps replica 0 (the longest-warm one)
            # stable across scale cycles.
            victim = self._replicas.pop()
            self.scale_downs += 1
        # Counters move to _retired BEFORE the (up to 30 s) drain: a
        # scrape during the drain window must not see the victim's
        # lifetime totals in neither the live list nor the retired
        # pool — that transient dip would read as a Prometheus counter
        # reset and feed the autoscaler spurious negative deltas.
        pre = victim.batcher.stats()
        self.absorb_stats(pre)
        # Drain OUTSIDE the lock: close() flushes everything already
        # queued (requests keep completing), new submits re-route.
        victim.batcher.close(join=False)
        self._retire(victim, reason, pre)

    def _retire(self, victim: Replica, reason: str,
                pre: dict | None = None) -> None:
        """Post-close teardown: fold in final counters and return the
        chip — but ONLY once the batcher worker has really exited.  A
        join that timed out behind a wedged dispatch means the device
        is still in use; releasing it would double-book the chip with
        the next lessee, so the lease is deliberately retained (and
        logged) instead.  ``pre`` is the stats snapshot already
        absorbed at pop time; only the drain's delta is added here."""
        drained = victim.batcher.wait_drained(timeout=30)
        final = victim.batcher.stats()
        self.absorb_stats(_stats_delta(final, pre) if pre else final)
        if drained:
            victim.release()
            logger.info(kv(
                event="replica_down", model=self.name,
                replica=victim.idx,
                device=victim.device_id or "host", reason=reason,
            ))
        else:
            logger.warning(kv(
                event="replica_down_undrained", model=self.name,
                replica=victim.idx,
                device=victim.device_id or "host", reason=reason,
                note="worker still dispatching; lease retained",
            ))

    def _absorb_retired(self, batcher: MicroBatcher) -> None:
        self.absorb_stats(batcher.stats())

    def absorb_stats(self, stats: dict, *,
                     overflows_were_sheds: bool = False) -> None:
        """Fold another batcher's lifetime counters into this set's
        retired totals: drained replicas at scale-down, and the
        single-path batcher a model retires when it moves onto the
        fleet — per-model counters stay monotonic across both.

        ``overflows_were_sheds``: on the SINGLE-path batcher every
        overflow was a client 429, so the cutover carries them into
        the set-level shed counter; a drained replica's overflows are
        not (those requests may have re-routed and served)."""
        with self._lock:
            retired = self._retired
            for key in _COUNTER_KEYS:
                retired[key] += stats[key]
            if overflows_were_sheds:
                self.sheds += stats["overflows"]
            retired["occ_weighted"] += (
                stats["batchOccupancy"] * stats["batches"]
            )
            for bucket, count in stats["bucketHistogram"].items():
                retired["buckets"][bucket] = (
                    retired["buckets"].get(bucket, 0) + count
                )

    # -- routing -------------------------------------------------------------

    def submit(self, x: np.ndarray) -> tuple:
        """Route one request: P2C on live queue depth, falling through
        the candidate order on per-replica overflow; raises
        ``QueueFull`` (→ 429 + Retry-After) only when EVERY replica
        refused.  Returns ``(outputs, replica)``."""
        with self._lock:
            replicas = list(self._replicas)
        if not replicas:
            raise BatcherClosed(
                f"no routable replicas for {self.name!r}; retry"
            )
        order = self.router.choose(
            [r.batcher.queue_depth for r in replicas]
        )
        last: QueueFull | None = None
        for i in order:
            replica = replicas[i]
            try:
                # Replica/device attribution on the serve span: a
                # single contextvar read when no trace is active.
                with tracing.span(
                    "serve.predict",
                    model=self.name, replica=replica.idx,
                    device=replica.device_id or "host",
                ):
                    return replica.batcher.submit(x), replica
            except BatcherClosed as exc:
                # Drained under us mid-route — not saturation; the
                # next candidate absorbs the request.
                last = exc
            except QueueFull as exc:
                last = exc
            if getattr(last, "partial", False):
                # Part of a chunked request already queued (and will
                # dispatch) on that replica: replaying the whole
                # request on another would DUPLICATE device work under
                # exactly the saturation that overflowed it — shed and
                # let the client's 429 backoff do its job.
                break
        with self._lock:
            self.sheds += 1
        raise last  # every replica saturated → shed (429)

    # -- signals / observability ---------------------------------------------

    def signals(self) -> dict:
        """The autoscaler's per-tick inputs — the same numbers the
        Prometheus exposition serves (queue depth, p99, cumulative
        requests and 429 overflows), read from the batchers' own
        counters.  Batch occupancy is deliberately NOT here: with
        power-of-two bucket padding a lone request dispatches at
        occupancy 1.0 (bucket 1), so occupancy stays high at trickle
        load and cannot distinguish a busy fleet from an idle one —
        it remains an operator metric (merged_stats), not a scale
        signal."""
        with self._lock:
            replicas = list(self._replicas)
            requests = self._retired["requests"]
            sheds = self.sheds
        depth = 0
        p99 = 0.0
        for r in replicas:
            stats = r.batcher.stats()
            depth += stats["queueDepth"]
            requests += stats["requests"]
            p99 = max(p99, stats["latencyMs"]["p99"])
        n = len(replicas)
        cap = max(1, n * self._cfg.max_queue)
        return {
            "replicas": n,
            "queue_depth": depth,
            "queue_frac": depth / cap,
            "p99_ms": p99,
            # Set-level: only requests EVERY candidate refused (real
            # 429s), not per-replica overflows that re-routed fine.
            "sheds": sheds,
            "requests": requests,
        }

    def merged_stats(self) -> dict:
        """Replica batcher stats merged into the single-batcher shape
        ``ServingService.aggregate`` consumes, so fleet models land on
        every existing surface (tfevents, /metrics.prom, monitoring)
        without a second aggregation path."""
        with self._lock:
            replicas = list(self._replicas)
            retired = {
                key: (dict(val) if isinstance(val, dict) else val)
                for key, val in self._retired.items()
            }
            sheds = self.sheds
        merged = {
            "requests": retired["requests"], "rows": retired["rows"],
            "batches": retired["batches"],
            "paddedRows": retired["paddedRows"],
            # Client-visible 429s only: per-replica overflows that
            # re-routed and SERVED are a routing detail, and the
            # serving_overflows surfaces have always meant "requests
            # answered 429".
            "overflows": sheds, "queueDepth": 0,
            "maxBatch": self._cfg.max_batch,
            "maxQueue": self._cfg.max_queue,
            "flushMs": self._cfg.flush_ms,
            "replicas": len(replicas),
        }
        occ_weighted = retired["occ_weighted"]
        buckets: dict[str, int] = retired["buckets"]
        lat = {"p50": 0.0, "p95": 0.0, "p99": 0.0}
        for r in replicas:
            stats = r.batcher.stats()
            for key in ("requests", "rows", "batches", "paddedRows",
                        "queueDepth"):
                merged[key] += stats[key]
            occ_weighted += stats["batchOccupancy"] * stats["batches"]
            for b, count in stats["bucketHistogram"].items():
                buckets[b] = buckets.get(b, 0) + count
            for q in lat:
                lat[q] = max(lat[q], stats["latencyMs"][q])
        merged["batchOccupancy"] = round(
            occ_weighted / merged["batches"], 4
        ) if merged["batches"] else 0.0
        merged["bucketHistogram"] = dict(sorted(buckets.items()))
        merged["latencyMs"] = lat
        return merged

    def placements(self) -> dict:
        with self._lock:
            return {
                r.idx: (r.device_id or "host") for r in self._replicas
            }

    def status(self) -> dict:
        with self._lock:
            replicas = list(self._replicas)
        return {
            "model": self.name,
            "replicas": [r.status() for r in replicas],
            "size": len(replicas),
            "min": self.min_replicas,
            "max": self.max_replicas,
            "devicesPerReplica": self.devices_per_replica,
            "scaleUps": self.scale_ups,
            "scaleDowns": self.scale_downs,
        }

    def close(self) -> None:
        """Tear the whole set down (unload/invalidation/shutdown):
        drain every batcher, release every chip."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            replicas = self._replicas
            self._replicas = []
        # Signal every batcher first so the drains overlap, then wait
        # and release — serial close-then-join would stack each
        # replica's drain timeout on shutdown's critical path.
        pres = []
        for r in replicas:
            pres.append(r.batcher.stats())
            self.absorb_stats(pres[-1])
            r.batcher.close(join=False)
        for r, pre in zip(replicas, pres):
            self._retire(r, "close", pre)
