"""The HTTP front server and route table.

Route scheme (reference: microservices/krakend/krakend.json):
``{verb} /api/learningOrchestra/v1/{service}/{tool}[/{name}]``, with the
dataset service's paginated GET as the universal poll path (SURVEY §3.5).
Status mapping follows the reference's validation pipeline: 409 duplicate
name, 404 missing artifact, 406 semantic errors, 201 created with the
artifact's GET URI in the body (binary_executor_image/server.py:99-107).

Implementation: stdlib ``ThreadingHTTPServer`` + a regex route registry —
no web-framework dependency; handlers are thin adapters onto the service
classes.
"""

from __future__ import annotations

import json
import re
import threading
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable
from urllib.parse import parse_qs, urlparse

from learningorchestra_tpu import faults
from learningorchestra_tpu.concurrency_rt import make_lock
from learningorchestra_tpu.config import Config, get_config
from learningorchestra_tpu.jobs.cluster import QuotaExceeded, bind_tenant
from learningorchestra_tpu.jobs.leases import LeaseTimeout
from learningorchestra_tpu.obs import metrics as obs_metrics
from learningorchestra_tpu.obs import tracing as obs_tracing
from learningorchestra_tpu.obs.bundle import (
    BundleBusy,
    BundleError,
    BundleNotFound,
)
from learningorchestra_tpu.obs.profiling import (
    ProfilerConflict,
    ProfilerError,
    ProfilerNotFound,
)
from learningorchestra_tpu.services import (
    BuilderService,
    DatasetService,
    ExecutorService,
    ExploreService,
    FunctionService,
    ModelService,
    ServiceContext,
    TransformService,
)
from learningorchestra_tpu.services.context import (
    ConflictError,
    NotFoundError,
    ValidationError,
)
from learningorchestra_tpu.serve.batcher import QueueFull
from learningorchestra_tpu.serve.registry import ServeError
from learningorchestra_tpu.store.artifacts import DuplicateArtifact
from learningorchestra_tpu.toolkit import registry
from learningorchestra_tpu.toolkit.registry import RegistryError


class BadRequest(Exception):
    """Malformed client input (non-JSON body handled separately) → 400."""


class Router:
    """Regex route table: (verb, pattern) → handler(match, body, query).

    Per-route flags carry the gateway budget semantics (reference:
    krakend.json global ``timeout``/``cache_ttl`` + metrics exporter):
    ``cacheable`` opts a GET into the response cache (poll GETs must
    NOT cache — job completion writes through the store, not HTTP, so a
    TTL cache would serve stale ``finished`` flags); ``no_timeout``
    exempts deliberate long-polls (observe) from the request deadline.
    """

    def __init__(self, prefix: str):
        self.prefix = prefix.rstrip("/")
        self.routes: list[tuple[str, re.Pattern, Callable, str, dict]] = []

    def add(self, verb: str, pattern: str, handler: Callable, *,
            cacheable: bool = False, no_timeout: bool = False) -> None:
        full = re.compile("^" + self.prefix + pattern + "/?$")
        verb = verb.upper()
        self.routes.append((
            verb, full, handler, f"{verb} {pattern}",
            {"cacheable": cacheable, "no_timeout": no_timeout},
        ))

    def resolve(self, verb: str, path: str):
        """→ (handler, match, route_key, flags) | (None, None, key, {})."""
        matched_path = False
        for route_verb, pattern, handler, key, flags in self.routes:
            m = pattern.match(path)
            if m:
                matched_path = True
                if route_verb == verb:
                    return handler, m, key, flags
        key = "405" if matched_path else "404"
        return None, None, key, {"matched_path": matched_path}

    def dispatch(self, verb: str, path: str, body: dict, query: dict):
        handler, m, _key, flags = self.resolve(verb, path)
        if handler is None:
            if flags.get("matched_path"):
                return 405, {
                    "error": f"method {verb} not allowed on {path}"
                }
            return 404, {"error": f"no such route: {path}"}
        return handler(m, body, query)


class _BoundedThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a hard cap on connection threads.

    The ``max_inflight`` semaphore bounds ADMITTED handlers, but
    stdlib ThreadingMixIn spawns one thread per accepted connection
    before a byte of the request is parsed — a slow-loris client
    trickling request bodies would grow threads without bound
    underneath the handler cap.  Beyond ``max_connections`` the
    socket is closed immediately on accept.
    """

    daemon_threads = True

    def __init__(self, addr, handler, *, max_connections: int = 256):
        self._conn_slots = (
            threading.BoundedSemaphore(max_connections)
            if max_connections > 0 else None
        )
        super().__init__(addr, handler)

    def process_request(self, request, client_address):
        if self._conn_slots is not None and \
                not self._conn_slots.acquire(blocking=False):
            self.shutdown_request(request)
            return
        try:
            super().process_request(request, client_address)
        except BaseException:
            if self._conn_slots is not None:
                self._conn_slots.release()
            raise

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            if self._conn_slots is not None:
                self._conn_slots.release()


class _Slot:
    """One in-flight-request semaphore slot with shared ownership.

    The gateway dispatcher and (for timed-out requests) the abandoned
    handler worker each own a reference; the underlying semaphore slot
    frees only when the LAST owner releases.  This is what makes the
    ``max_inflight`` cap bound real threads: a 504'd request's zombie
    handler keeps its slot until the handler actually returns.
    """

    def __init__(self, sem):
        self._sem = sem
        self._lock = make_lock("_Slot._lock")
        self._owners = 1

    def share(self) -> None:
        with self._lock:
            self._owners += 1

    def release(self) -> None:
        if self._sem is None:
            return
        with self._lock:
            self._owners -= 1
            if self._owners > 0:
                return
        self._sem.release()


class APIServer:
    """Service wiring + route table + HTTP plumbing."""

    def __init__(self, config: Config | None = None,
                 ctx: ServiceContext | None = None):
        self.config = config or get_config()
        self.ctx = ctx or ServiceContext(self.config)
        self.dataset = DatasetService(self.ctx)
        self.transform = TransformService(self.ctx)
        self.explore = ExploreService(self.ctx)
        self.model = ModelService(self.ctx)
        self.executor = ExecutorService(self.ctx)
        self.function = FunctionService(self.ctx)
        self.builder = BuilderService(self.ctx)
        import os as _os

        from learningorchestra_tpu.services.distributed_exec import (
            DistributedExecutorService,
        )
        from learningorchestra_tpu.services.monitoring import (
            MonitoringService,
        )

        monitoring_root = _os.path.join(
            self.config.store.volume_path(), "_monitoring"
        )
        self.monitoring = MonitoringService(
            monitoring_root,
            external_host=self.config.api.monitoring_external_host,
        )
        self.distributed = DistributedExecutorService(
            self.ctx, self.monitoring
        )
        from learningorchestra_tpu.serve import ServingService

        # Resident model serving (serve/): synchronous low-latency
        # predict over device-pinned params, request-coalescing
        # micro-batches, shape-bucketed compiles.
        self.serving = ServingService(self.ctx, monitoring_root)
        # On-demand profiler capture (obs/profiling.py): jax.profiler
        # behind POST /observability/profile/start|stop — one capture
        # at a time into a bounded dir, auto-stop deadline.
        from learningorchestra_tpu.obs.profiling import ProfilerService

        prof = self.config.profiling
        self.profiler = ProfilerService(
            prof.dir or _os.path.join(
                self.config.store.volume_path(), "_profiles"
            ),
            max_seconds=prof.max_seconds,
            max_captures=prof.max_captures,
        )
        # Windowed rollups + SLO burn-rate alerting (obs/rollup.py,
        # obs/slo.py): process-wide singletons sized from THIS
        # server's config when it is the first to construct them
        # (mirroring the registry); the engine daemon snapshots
        # selected registry families each tick and the SLO service
        # evaluates its objectives on the same clock.
        from learningorchestra_tpu.obs import rollup as obs_rollup
        from learningorchestra_tpu.obs import slo as obs_slo

        self.rollup = obs_rollup.ensure_engine(self.config.rollup)
        self.slo = obs_slo.ensure_service(self.config.slo)
        self.rollup.start()
        # Always-on flight recorder + incident debug bundles
        # (obs/flight.py, obs/bundle.py): the recorder arms at boot
        # and rides every request/step at a lock-free deque append;
        # the bundle assembler snapshots rings + every subsystem's
        # live state whenever an SLO fires, a job dies terminally, a
        # lock stalls — or an operator POSTs /observability/bundle.
        from learningorchestra_tpu.obs import bundle as obs_bundle
        from learningorchestra_tpu.obs import flight as obs_flight

        obs_flight.ensure(self.config.flight)
        if not self.config.bundle.dir:
            # Derived default beside the profiler's capture store:
            # bundles are artifacts of the same volume lifecycle.
            self.config.bundle.dir = _os.path.join(
                self.config.store.volume_path(), "_bundles"
            )
        self.bundles = obs_bundle.ensure_service(
            self.config.bundle,
            providers=self._bundle_providers(),
            profiler=self.profiler,
        )
        self.slo.add_sink(self._slo_bundle_sink)
        # Unified observability (obs/): push metrics for the HTTP
        # layer, pull collectors over every subsystem's existing stats,
        # rendered at GET /metrics.prom.  The legacy JSON endpoints
        # remain as views over the same instrumentation points.
        # Handles bind lazily against the CURRENT registry (identity-
        # checked per use, like the engine/lease helpers), so a
        # reset_registry() mid-life re-homes both the push metrics and
        # the collector instead of splitting them across registries.
        self._obs_registry = None
        self._obs_rebind_lock = make_lock("APIServer._obs_rebind_lock")
        self._obs_handles()
        self.router = Router(self.config.api.api_prefix)
        self._register_routes()
        self._httpd: ThreadingHTTPServer | None = None
        # Gateway budget (reference: krakend.json global timeout /
        # cache_ttl / metrics exporter on :8090 — SURVEY §5.1, §6).
        self._cache: dict[tuple, tuple] = {}
        self._cache_lock = make_lock("APIServer._cache_lock")
        self._metrics: dict[str, dict] = {}
        self._metrics_lock = make_lock("APIServer._metrics_lock")
        n_inflight = self.config.api.max_inflight
        self._inflight = (
            threading.BoundedSemaphore(n_inflight)
            if n_inflight > 0 else None
        )
        import time as _time

        self._t_start = _time.time()
        # Shutdown/demotion coordination: the event gates the dispatch
        # path (kept-alive connections get 503+close) and ends the
        # fence watch; the lock+flag make shutdown() idempotent.
        self._shutting_down = threading.Event()
        self._shutdown_lock = make_lock("APIServer._shutdown_lock")
        self._shut_down = False
        # Idempotency ledger (mongo's retryable-writes txnNumber,
        # reference: docker-compose.yml:42-90 replica set + driver
        # retry).  Lives in the DOCUMENT STORE so records WAL-ship to
        # the standby: a mutation retried across a failover replays
        # its recorded response instead of executing twice.
        self._idem_lock = make_lock("APIServer._idem_lock")
        self._idem_writes = 0
        # Without shared storage, a primary revived DURING a standby's
        # promotion can serve until its fence watch first polls the
        # peer — the check interval bounds that dual-writable window
        # (a 2-node pair has no majority to elect with; the w:1
        # tradeoff).  Configured like every other knob (HAConfig /
        # LO_HA_FENCE_INTERVAL); floored so "0" can't hot-spin peer
        # polls.
        if self.config.ha.fence_interval_s > 0:
            self.FENCE_CHECK_INTERVAL_S = max(
                0.05, self.config.ha.fence_interval_s
            )
        # Fault-injection plane: arm any LO_TPU_FAULT_* schedules the
        # config carried, so a deployment boots straight into its
        # chaos drill.  Bad specs raise HERE (boot), loudly.
        faults.load_env({
            faults.ENV_PREFIX + suffix: spec
            for suffix, spec in self.config.faults.specs.items()
        })

    # -- debug bundles --------------------------------------------------------

    def _bundle_providers(self) -> dict:
        """Content sources for obs/bundle.py, stem → zero-arg callable.
        Each runs inside the assembler's per-provider try/except: a
        broken subsystem becomes a manifest error, not a lost bundle."""

        def metrics():
            from learningorchestra_tpu.obs.metrics import get_registry

            return get_registry().snapshot()

        def rollup():
            eng = self.rollup
            series = {}
            for fam in eng.families:
                try:
                    series[fam] = eng.timeseries(fam, max_points=60)
                except Exception as exc:  # noqa: BLE001 — one family
                    series[fam] = {"error": repr(exc)}  # at a time
            return {"status": eng.status(), "series": series}

        def slo():
            return {
                "alerts": self.slo.alerts(),
                "status": self.slo.status(),
            }

        def journal():
            tail = max(0, int(self.config.bundle.journal_tail))
            j = self.ctx.journal
            docs = self.ctx.documents
            from learningorchestra_tpu.jobs.journal import (
                JOURNAL_COLLECTION,
            )

            try:
                j.flush()
            except Exception:  # noqa: BLE001 — a flush failure still
                pass  # leaves the already-persisted records readable
            if not docs.collection_exists(JOURNAL_COLLECTION):
                return {"records": []}
            records = list(docs.find(JOURNAL_COLLECTION))
            return {"records": records[-tail:] if tail else []}

        def locks():
            from learningorchestra_tpu import concurrency_rt

            return concurrency_rt.snapshot()

        def cluster():
            doc = {
                "enabled": self.ctx.cluster is not None,
                "engines": [],
                "claims": [],
            }
            if self.ctx.cluster is not None:
                doc.update(self.ctx.cluster.status())
            if self.ctx.admission is not None:
                doc["tenants"] = self.ctx.admission.snapshot()
            return doc

        return {
            "metrics": metrics,
            "rollup": rollup,
            "slo": slo,
            "fleet": lambda: self.serving.fleet.snapshot(),
            "journal": journal,
            "faults": lambda: faults.status(),
            "locks": locks,
            "cluster": cluster,
        }

    def _slo_bundle_sink(self, event: dict) -> None:
        """SLO alert-transition sink: a ``firing`` transition IS the
        incident signal — ask for a bundle (debounced/single-flight
        inside the service; assembly runs on its own thread, so the
        rollup tick this sink rides never blocks on file IO)."""
        if event.get("state") != "firing":
            return
        self.bundles.trigger("slo_firing", {
            "slo": event.get("slo"),
            "instance": event.get("instance"),
            "burnFast": event.get("burnFast"),
            "burnSlow": event.get("burnSlow"),
        })

    # -- idempotency ----------------------------------------------------------

    #: Store collection holding idempotency records.  Underscore
    #: prefix keeps it out of the artifact namespace; it sorts first
    #: in WAL shipping, so the "begun" marker tends to reach the
    #: standby no later than the mutation's own effects.
    IDEM_COLLECTION = "_idempotency"
    #: Records older than this are swept (a retry arriving a day later
    #: is a new request, matching mongo's retryable-write session TTL).
    IDEM_TTL_S = 86400.0
    #: Sweep cadence, counted in new records.
    IDEM_SWEEP_EVERY = 512

    @staticmethod
    def _idem_id(key: str) -> int:
        """Record ``_id`` derived from the key: the store's atomic
        ``insert_unique`` then gives O(1) lock-free claim semantics
        instead of a scan under a global lock.  63-bit hash space —
        collision odds are negligible, and the stored key string is
        verified on every hit anyway."""
        import hashlib

        digest = hashlib.sha256(key.encode()).digest()
        return int.from_bytes(digest[:8], "big") >> 1

    @staticmethod
    def _idem_fingerprint(verb: str, path: str, body: dict,
                          query: dict | None = None) -> str:
        """Request identity recorded with the key: a key reused for a
        DIFFERENT mutation must be rejected, not replayed — replaying
        operation A's response to operation B would report success
        for work that never ran.  Query params are part of the
        identity: handlers receive them, so two requests differing
        only there are different operations."""
        import hashlib

        canon = json.dumps(
            [body or {}, sorted((query or {}).items())],
            sort_keys=True, default=str,
        )
        return hashlib.sha256(
            f"{verb} {path} {canon}".encode()
        ).hexdigest()[:32]

    def _idem_begin(self, key: str, fingerprint: str):
        """Claim ``key`` or report its prior outcome.

        → ``("replay", status, payload)`` — the mutation already
        completed; hand back the recorded response (exactly-once).
        → ``("mismatch", rec)`` — the key was already used for a
        DIFFERENT request (or, vanishingly, a hash collision).
        → ``("ambiguous", rec)`` — a prior attempt began but never
        recorded completion (in flight, or the primary died
        mid-handler): the system cannot know whether side effects
        happened, so the caller gets an explicit conflict instead of
        a silent double-execution.
        → ``("fresh", _id)`` — first time: a ``begun`` marker is
        durably inserted before the handler runs.
        """
        import time as _time

        from learningorchestra_tpu.store.document_store import (
            DuplicateKey,
        )

        docs = self.ctx.documents
        _id = self._idem_id(key)
        try:
            docs.insert_unique(
                self.IDEM_COLLECTION,
                {"key": key, "fp": fingerprint, "state": "begun",
                 "at": _time.time()},
                _id,
            )
        except DuplicateKey:
            rec = docs.find_one(self.IDEM_COLLECTION, _id) or {}
            if rec.get("key") != key or rec.get("fp") != fingerprint:
                return ("mismatch", rec)
            if rec.get("state") == "done":
                payload = rec.get("payload")
                return (
                    "replay",
                    rec.get("status", 200),
                    payload if payload is not None else {},
                )
            return ("ambiguous", rec)
        with self._idem_lock:
            self._idem_writes += 1
            # First keyed write after startup ALSO sweeps: the counter
            # is in-memory, so without it a server restarting before
            # SWEEP_EVERY writes would never honor the TTL and expired
            # records would accumulate across restarts (and ship to
            # every replica).
            sweep = (
                self._idem_writes == 1
                or self._idem_writes % self.IDEM_SWEEP_EVERY == 0
            )
        if sweep:
            # Off the request path: a day-sized ledger sweep must cost
            # some background thread the time, not an unlucky client.
            threading.Thread(
                target=self._idem_sweep, daemon=True
            ).start()
        return ("fresh", _id)

    def _idem_finish(self, _id: int, status: int, payload) -> None:
        """Record the terminal response for replay.  Runs in the
        handler's thread even after a gateway 504 — the REAL outcome
        is what a retry must see, not the timeout envelope."""
        if not isinstance(payload, (dict, list)):
            payload = None  # mutations return JSON; belt-and-braces
        try:
            self.ctx.documents.update_one(
                self.IDEM_COLLECTION, _id,
                {"state": "done", "status": status, "payload": payload},
            )
        except Exception:
            pass  # a lost record degrades to at-least-once, not 500

    def _idem_sweep(self) -> None:
        import time as _time

        docs = self.ctx.documents
        cutoff = _time.time() - self.IDEM_TTL_S
        if not docs.collection_exists(self.IDEM_COLLECTION):
            return
        try:
            for rec in docs.find(self.IDEM_COLLECTION):
                if rec.get("at", 0) < cutoff:
                    docs.delete_one(self.IDEM_COLLECTION, rec["_id"])
        except Exception:
            pass

    # -- helpers --------------------------------------------------------------

    def _render_status(self) -> str:
        """The ops status page body: agents, leases, jobs, fairness
        queues, and recent events, rendered server-side from state the
        process already holds — no polling scripts, a meta-refresh
        keeps it live in a browser during bring-up."""
        import html as _html
        import time as _time

        esc = _html.escape

        def table(headers, rows):
            head = "".join(f"<th>{esc(str(h))}</th>" for h in headers)
            body = "".join(
                "<tr>" + "".join(
                    f"<td>{esc(str(c))}</td>" for c in row
                ) + "</tr>"
                for row in rows
            )
            return (f"<table><thead><tr>{head}</tr></thead>"
                    f"<tbody>{body or ''}</tbody></table>")

        sections: list[str] = []

        # -- cluster agents (coordinator fetch, cluster mode only) ----
        coord = self.config.dist.task_coordinator
        if coord:
            try:
                import urllib.request as _rq

                with _rq.urlopen(
                    f"http://{coord}/agents", timeout=2
                ) as resp:
                    agents = json.loads(resp.read()).get("agents", {})
                rows = [
                    (aid, a.get("capacity", ""),
                     "yes" if a.get("alive") else "NO",
                     f"{_time.time() - a.get('last_seen', 0):.1f}s ago"
                     if a.get("last_seen") else "never")
                    for aid, a in sorted(agents.items())
                ]
                sections.append(
                    f"<h2>Agents ({len(rows)})</h2>"
                    + table(("agent", "capacity", "alive",
                             "heartbeat"), rows)
                )
            except Exception as exc:  # noqa: BLE001 — page must render
                sections.append(
                    f"<h2>Agents</h2><p class=err>coordinator "
                    f"{esc(coord)} unreachable: {esc(repr(exc))}</p>"
                )
        else:
            sections.append(
                "<h2>Agents</h2><p>in-process mode "
                "(no task coordinator configured)</p>"
            )

        # -- chip leases (snapshot never forces device discovery:
        # that could block on remote hardware) -------------------------
        snap = self.ctx.leaser.snapshot()
        free, all_devs, recent = snap["free"], snap["all"], snap["recent"]
        if snap["initialized"]:
            sections.append(
                f"<h2>Device leases</h2><p>{len(free)}/{len(all_devs)}"
                f" free — {esc(', '.join(all_devs) or 'cpu (no-op)')}"
                "</p>"
                + table(
                    ("job", "device", "held"),
                    [(label, dev, f"{t1 - t0:.2f}s")
                     for label, dev, t0, t1 in recent],
                )
            )
        else:
            sections.append(
                "<h2>Device leases</h2><p>no lease taken yet "
                "(device discovery is lazy)</p>"
            )

        # -- store HA: role, election epoch, peer (store/ha.py).  Same
        # page-must-render convention as the coordinator fetch above:
        # a bad peer or unreadable store degrades this section only.
        try:
            from learningorchestra_tpu.store.ha import (
                is_fenced,
                peer_status,
            )
            from learningorchestra_tpu.store.replica import read_epoch

            root = self.config.store.store_path()
            fence = is_fenced(root)
            # Same role logic as GET /replication/status: a fenced
            # store is not a primary, whatever this process thinks.
            role = "fenced" if fence is not None else "primary"
            ha_bits = [
                f"role: <b>{role}</b> — election epoch "
                f"{read_epoch(root)}"
            ]
            if fence is not None:
                ha_bits.append(
                    '<span class=err>FENCED by '
                    f"{esc(str(fence.get('promoted_to') or '?'))}"
                    "</span>"
                )
            peer = self.config.ha.peer
            if peer:
                st = peer_status(peer)
                if not isinstance(st, dict):
                    # A monitoring standby answers its status route
                    # (store/ha.py) — unreachable means DOWN.
                    ha_bits.append(
                        f'<span class=err>peer {esc(peer)}: '
                        "unreachable</span>"
                    )
                else:
                    ha_bits.append(
                        f"peer {esc(peer)}: "
                        f"role={esc(str(st.get('role')))} "
                        f"epoch={esc(str(st.get('epoch')))}"
                    )
            else:
                ha_bits.append("no HA peer configured")
            sections.append(
                "<h2>Store HA</h2><p>" + " · ".join(ha_bits) + "</p>"
            )
        except Exception as exc:  # noqa: BLE001 — page must render
            sections.append(
                f"<h2>Store HA</h2><p class=err>{esc(repr(exc))}</p>"
            )

        # -- jobs: running + queued per fairness class ----------------
        running = self.ctx.engine.running_jobs()
        rows = []
        for name in running[:50]:
            meta = self.ctx.artifacts.metadata.read(name) or {}
            rows.append((name, meta.get("type", ""),
                         meta.get("jobState", "")))
        depths = self.ctx.engine.queue_depths()
        sections.append(
            f"<h2>Jobs ({len(running)} live)</h2>"
            + table(("artifact", "type", "state"), rows)
            + ("<p>queued per class: " + esc(json.dumps(depths))
               + "</p>" if depths else "")
        )

        # -- recent events, failures highlighted ----------------------
        events = self.ctx.webhooks.latest_events(20)
        ev_rows = "".join(
            "<tr class={cls}><td>{ts}</td><td>{name}</td>"
            "<td>{event}</td><td>{typ}</td></tr>".format(
                cls="err" if e.get("event") == "failed" else "ok",
                ts=_time.strftime(
                    "%H:%M:%S", _time.localtime(e.get("ts", 0))
                ),
                name=esc(str(e.get("artifact", ""))),
                event=esc(str(e.get("event", ""))),
                typ=esc(str(e.get("artifactType") or "")),
            )
            for e in reversed(events)
        )
        sections.append(
            "<h2>Recent events</h2><table><thead><tr><th>time</th>"
            "<th>artifact</th><th>event</th><th>type</th></tr></thead>"
            f"<tbody>{ev_rows}</tbody></table>"
        )

        uptime = _time.time() - self._t_start
        return (
            "<!doctype html><html><head>"
            "<title>learningorchestra_tpu status</title>"
            '<meta http-equiv="refresh" content="5">'
            "<style>"
            "body{font-family:system-ui,sans-serif;margin:2em;"
            "color:#222}"
            "table{border-collapse:collapse;margin:0.5em 0}"
            "td,th{border:1px solid #ccc;padding:4px 10px;"
            "text-align:left;font-size:14px}"
            "th{background:#f0f0f0}"
            "tr.err td{background:#fde8e8}"
            ".err{color:#b00}"
            "h2{margin-top:1.2em;font-size:16px}"
            "</style></head><body>"
            "<h1>learningorchestra_tpu</h1>"
            f"<p>uptime {uptime:.0f}s — store backend "
            f"{type(self.ctx.documents).__name__} — "
            f"{len(running)} live jobs</p>"
            + "".join(sections)
            + "</body></html>"
        )

    def _uri(self, service_path: str, name: str) -> str:
        return f"{self.config.api.api_prefix}/{service_path}/{name}"

    def _created(self, service_path: str, meta: dict):
        """201 + GET URI (reference: server.py:99-107)."""
        return 201, {
            "result": self._uri(service_path, meta["name"]),
            "name": meta["name"],
            "metadata": meta,
        }

    @staticmethod
    def _page_args(query: dict):
        q = query.get("query")
        parsed = json.loads(q) if q else None
        return {
            "query": parsed,
            "skip": _int_param(query, "skip", 0),
            "limit": _int_param(query, "limit", 20),
        }

    # URL tool → stored artifact-type prefix, where they differ: the
    # reference's gateway maps /train/horovod onto type=train/tensorflow
    # and /builder/{tensorflow,pytorch} onto type=builder/horovod
    # (krakend.json backend query params), so collection GETs must list
    # the type the POST actually stored.
    _TYPE_ALIASES = {
        ("train", "horovod"): "train/tensorflow",
        ("train", "distributed"): "train/tensorflow",
        ("builder", "tensorflow"): "builder/horovod",
        ("builder", "pytorch"): "builder/horovod",
    }

    def _list_handler(self, service: str, tool: str | None = None):
        """Collection-GET handler: list a family's metadata docs.

        ``tool=None`` reads the tool from the matched URL."""

        def handler(m, b, q):
            t = tool if tool is not None else m.group("tool")
            prefix = self._TYPE_ALIASES.get(
                (service, t), f"{service}/{t}" if t else f"{service}/"
            )
            docs = self.dataset.list_metadata(prefix)
            # Internal coordinator artifacts (builder runs) are not
            # client-facing.
            return 200, [d for d in docs if not d.get("hidden")]

        return handler

    # -- route table (SURVEY §2.2) -------------------------------------------

    def _register_routes(self) -> None:
        add = self.router.add
        TOOL = r"(?P<tool>[A-Za-z0-9_\-]+)"
        NAME = r"(?P<name>[A-Za-z0-9_.\-]+)"

        # ---- Dataset ----
        def dataset_create(m, body, query):
            kind = m.group("tool")
            name, url = body.get("datasetName") or body.get("name"), \
                body.get("url")
            if not url:
                raise ValidationError("missing 'url'")
            if kind == "csv":
                shard_rows = body.get("shardRows")
                if shard_rows is not None:
                    try:
                        shard_rows = int(shard_rows)
                    except (TypeError, ValueError):
                        raise ValidationError(
                            "'shardRows' must be a positive integer"
                        ) from None
                    if shard_rows <= 0:
                        raise ValidationError(
                            "'shardRows' must be a positive integer"
                        )
                meta = self.dataset.create_csv(
                    name, url, shard_rows=shard_rows
                )
            elif kind == "tensor":
                labels_url = body.get("labelsUrl")
                if not labels_url:
                    raise ValidationError(
                        "tensor ingest needs 'labelsUrl' (.npy labels)"
                    )
                shard_rows = body.get("shardRows", 4096)
                try:
                    shard_rows = int(shard_rows)
                except (TypeError, ValueError):
                    raise ValidationError(
                        "'shardRows' must be a positive integer"
                    ) from None
                if shard_rows <= 0:
                    # Same contract as the CSV path: an explicit bad
                    # value errors, never silently takes the default.
                    raise ValidationError(
                        "'shardRows' must be a positive integer"
                    )
                try:
                    meta = self.dataset.create_tensor(
                        name, url, labels_url=labels_url,
                        shard_rows=shard_rows,
                    )
                except ValueError as exc:
                    raise ValidationError(str(exc)) from None
            else:
                meta = self.dataset.create_generic(name, url)
            return self._created(f"dataset/{kind}", meta)

        add("POST", rf"/dataset/{TOOL}", dataset_create)
        add("GET", rf"/dataset/{TOOL}", self._list_handler("dataset"))
        add(
            "GET", rf"/dataset/{TOOL}/{NAME}",
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )
        add(
            "DELETE", rf"/dataset/{TOOL}/{NAME}",
            lambda m, b, q: (
                self.dataset.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Transform: projection ----
        def projection_create(m, body, query):
            meta = self.transform.create_projection(
                body.get("projectionName") or body.get("name"),
                body.get("datasetName") or body.get("parentName"),
                body.get("fields") or [],
            )
            return self._created("transform/projection", meta)

        def projection_update(m, body, query):
            meta = self.transform.update_projection(
                body.get("projectionName") or body.get("name"),
                fields=body.get("fields"),
            )
            return 200, {"metadata": meta}

        add("POST", r"/transform/projection", projection_create)
        # Reference: PATCH /transform/projection carries the name in the
        # body (krakend.json transform block); also accept /{name}.
        add("PATCH", r"/transform/projection", projection_update)
        add(
            "PATCH", r"/transform/projection/" + NAME,
            lambda m, b, q: (
                200,
                {
                    "metadata": self.transform.update_projection(
                        m.group("name"), fields=b.get("fields")
                    )
                },
            ),
        )
        add(
            "GET", r"/transform/projection/" + NAME,
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )
        add(
            "DELETE", r"/transform/projection/" + NAME,
            lambda m, b, q: (
                self.dataset.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Transform: text (BPE tokenization) ----
        # Beyond the reference's surface (its text configs assume
        # user-shipped preprocessing in compile_code); follows the
        # projection family's verb/URI/polling contract.
        def text_create(m, body, query):
            meta = self.transform.create_text(
                body.get("name"),
                body.get("datasetName") or body.get("parentName"),
                text_field=body.get("textField"),
                label_field=body.get("labelField"),
                vocab_size=body.get("vocabSize", 8000),
                max_len=body.get("maxLen", 128),
                lowercase=body.get("lowercase", True),
                tokenizer_from=body.get("tokenizerFrom"),
                shard_rows=body.get("shardRows", 4096),
            )
            return self._created("transform/text", meta)

        add("POST", r"/transform/text", text_create)
        add("PATCH", r"/transform/text", lambda m, b, q: (
            200, {"metadata": self.transform.update_text(b.get("name"))},
        ))
        add("PATCH", r"/transform/text/" + NAME, lambda m, b, q: (
            200, {"metadata": self.transform.update_text(m.group("name"))},
        ))
        add("GET", r"/transform/text/" + NAME, lambda m, b, q: (
            200,
            self.dataset.read_page(m.group("name"), **self._page_args(q)),
        ))
        add("DELETE", r"/transform/text/" + NAME, lambda m, b, q: (
            self.dataset.delete(m.group("name")),
            (200, {"result": "deleted"}),
        )[1])

        # ---- Transform: dataType ----
        def datatype_patch(m, body, query):
            meta = self.transform.update_field_types(
                body.get("datasetName") or body.get("name"),
                body.get("types") or body.get("fields") or {},
            )
            return 200, {"metadata": meta}

        add("PATCH", r"/transform/dataType", datatype_patch)
        # Reference routes the dataType collection GET onto the dataset
        # service (krakend.json transform block → databaseapi /files);
        # per-name GET/DELETE resolve via the generic /transform/{t}
        # routes below.  _list_handler("dataset", "") lists the whole
        # dataset family (prefix "dataset").
        add("GET", r"/transform/dataType",
            self._list_handler("dataset", ""))

        # ---- Transform: generic (scikitlearn | tensorflow) ----
        def transform_create(m, body, query):
            tool = m.group("tool")
            meta = self.transform.create_generic(
                body.get("name"),
                module_path=body.get("modulePath"),
                class_name=body.get("class"),
                class_parameters=body.get("classParameters"),
                method=body.get("method"),
                method_parameters=body.get("methodParameters"),
                artifact_type=f"transform/{tool}",
                description=body.get("description", ""),
            )
            return self._created(f"transform/{tool}", meta)

        def transform_update(m, body, query):
            meta = self.transform.update_generic(
                m.group("name"),
                class_parameters=body.get("classParameters"),
                method_parameters=body.get("methodParameters"),
                description=body.get("description", ""),
            )
            return 200, {"metadata": meta}

        add("POST", rf"/transform/{TOOL}", transform_create)
        add("GET", rf"/transform/{TOOL}", self._list_handler("transform"))
        add("PATCH", rf"/transform/{TOOL}/{NAME}", transform_update)
        add(
            "GET", rf"/transform/{TOOL}/{NAME}",
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )
        add(
            "DELETE", rf"/transform/{TOOL}/{NAME}",
            lambda m, b, q: (
                self.executor.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Explore ----
        def histogram_create(m, body, query):
            meta = self.explore.create_histogram(
                body.get("histogramName") or body.get("name"),
                body.get("datasetName") or body.get("parentName"),
                body.get("fields") or [],
            )
            return self._created("explore/histogram", meta)

        add("POST", r"/explore/histogram", histogram_create)
        add(
            "GET", r"/explore/histogram/" + NAME,
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )

        def curves_create(m, body, query):
            meta = self.explore.create_curves(
                body.get("name"),
                body.get("parentName"),
                fields=body.get("fields"),
            )
            return self._created("explore/curves", meta)

        # Specific before the generic /explore/{TOOL} routes — the
        # dispatcher is first-match; GET image/metadata/list fall
        # through to the shared TOOL handlers below.
        add("POST", r"/explore/curves", curves_create)
        add(
            "PATCH", r"/explore/curves/" + NAME,
            lambda m, b, q: (
                200,
                {"metadata": self.explore.update_curves(
                    m.group("name"), fields=(b or {}).get("fields"),
                )},
            ),
        )

        def explore_create(m, body, query):
            tool = m.group("tool")
            meta = self.explore.create_plot(
                body.get("name"),
                module_path=body.get("modulePath"),
                class_name=body.get("class"),
                class_parameters=body.get("classParameters"),
                method=body.get("method", "fit_transform"),
                method_parameters=body.get("methodParameters"),
                artifact_type=f"explore/{tool}",
                color_by=body.get("colorBy"),
                description=body.get("description", ""),
            )
            return self._created(f"explore/{tool}", meta)

        def explore_update(m, body, query):
            meta = self.explore.update_plot(
                m.group("name"),
                class_parameters=body.get("classParameters"),
                method_parameters=body.get("methodParameters"),
                color_by=body.get("colorBy"),
                description=body.get("description", ""),
            )
            return 200, {"metadata": meta}

        add("POST", rf"/explore/{TOOL}", explore_create)
        add("GET", rf"/explore/{TOOL}", self._list_handler("explore"))
        add("PATCH", rf"/explore/{TOOL}/{NAME}", explore_update)
        # GET {name} returns the PNG; {name}/metadata returns docs
        # (reference: krakend.json explore block, SURVEY §2.2).
        add(
            "GET", rf"/explore/{TOOL}/{NAME}/metadata",
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )

        def explore_image(m, body, query):
            data = self.explore.read_image(m.group("name"))
            return 200, ("image/png", data)

        # NOT cacheable: a PATCH re-render writes the new PNG from a
        # background job AFTER the invalidation fires, so a TTL cache
        # could re-trap the old image for cache_ttl_s.
        add("GET", rf"/explore/{TOOL}/{NAME}", explore_image)
        add(
            "DELETE", rf"/explore/{TOOL}/{NAME}",
            lambda m, b, q: (
                self.executor.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Model ----
        def model_create(m, body, query):
            tool = m.group("tool")
            meta = self.model.create(
                body.get("modelName") or body.get("name"),
                module_path=body.get("modulePath"),
                class_name=body.get("class"),
                class_parameters=body.get("classParameters"),
                artifact_type=f"model/{tool}",
                description=body.get("description", ""),
            )
            return self._created(f"model/{tool}", meta)

        def model_update(m, body, query):
            meta = self.model.update(
                m.group("name"),
                class_parameters=body.get("classParameters"),
                description=body.get("description", ""),
            )
            return 200, {"metadata": meta}

        add("POST", rf"/model/{TOOL}", model_create)
        add("GET", rf"/model/{TOOL}", self._list_handler("model"))
        add("PATCH", rf"/model/{TOOL}/{NAME}", model_update)
        add(
            "GET", rf"/model/{TOOL}/{NAME}",
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )
        add(
            "DELETE", rf"/model/{TOOL}/{NAME}",
            lambda m, b, q: (
                self.model.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Tune / Train / Evaluate / Predict ----
        def _deadline_s(body):
            """Per-submit job deadline override (``deadlineS``): None
            inherits the engine default (LO_TPU_JOB_DEADLINE_S), 0
            disables for this job."""
            raw = body.get("deadlineS")
            if raw is None:
                return None
            try:
                return float(raw)
            except (TypeError, ValueError):
                raise ValidationError(
                    f"deadlineS must be a number, got {raw!r}"
                ) from None

        def exec_create(service):
            def handler(m, body, query):
                tool = m.group("tool")
                name = body.get("name")
                parent = body.get("parentName") or body.get("modelName")
                if service == "tune" and body.get("paramGrid"):
                    meta = self.executor.create_tune(
                        name,
                        parent_name=parent,
                        method=body.get("method", "fit"),
                        param_grid=body.get("paramGrid"),
                        method_parameters=body.get("methodParameters"),
                        scoring_parameters=body.get("scoringParameters"),
                        artifact_type=f"tune/{tool}",
                        description=body.get("description", ""),
                        deadline_s=_deadline_s(body),
                    )
                else:
                    meta = self.executor.create(
                        name,
                        parent_name=parent,
                        method=body.get("method"),
                        method_parameters=body.get("methodParameters"),
                        artifact_type=f"{service}/{tool}",
                        description=body.get("description", ""),
                        deadline_s=_deadline_s(body),
                    )
                return self._created(f"{service}/{tool}", meta)

            return handler

        def exec_update(m, body, query):
            meta = self.executor.update(
                m.group("name"),
                method_parameters=body.get("methodParameters"),
                description=body.get("description", ""),
                deadline_s=_deadline_s(body),
            )
            return 200, {"metadata": meta}

        # ---- Distributed training (reference: POST /train/horovod →
        # /distributedTraining?type=train/tensorflow, SURVEY §2.2) ----
        def distributed_train_create(m, body, query):
            meta, extra = self.distributed.create_train(
                body.get("name"),
                parent_name=body.get("parentName")
                or body.get("modelName"),
                training_parameters=body.get("trainingParameters")
                or body.get("methodParameters"),
                compile_spec=body.get("compile"),
                mesh=body.get("mesh"),
                monitoring_path=body.get("monitoringPath"),
                description=body.get("description", ""),
            )
            status, payload = self._created("train/horovod", meta)
            if extra:
                payload["extra_results"] = extra
            return status, payload

        add("POST", r"/train/(?:horovod|distributed)",
            distributed_train_create)

        def distributed_train_update(m, body, query):
            meta = self.distributed.update_train(
                m.group("name"),
                training_parameters=body.get("trainingParameters")
                or body.get("methodParameters"),
                compile_spec=body.get("compile"),
                mesh=body.get("mesh"),
                description=body.get("description", ""),
            )
            return 200, {"metadata": meta}

        add("PATCH", rf"/train/(?:horovod|distributed)/{NAME}",
            distributed_train_update)

        # ---- Monitoring (reference: GET /monitoring/tensorflow/{name} →
        # TensorBoard URL lookup, server.py:185-200) ----
        def monitoring_lookup(m, body, query):
            from learningorchestra_tpu.services.monitoring import (
                MonitoringError,
            )

            # Reserved nickname: the compiled-program cache's counter
            # endpoint (train/compile_cache.py) — hit/miss/eviction/
            # trace-time, process-wide.
            if m.group("name") in ("compileCache", "compile_cache"):
                return 200, self.monitoring.compile_cache_stats()
            # Reserved nickname: serving observability (serve/) —
            # latency percentiles, queue depth, batch occupancy,
            # bucket histogram; each poll also appends one step of
            # serving_* tfevents scalars to the serving logdir.
            if m.group("name") == "serving":
                stats = self.serving.stats()
                scalars = self.serving.snapshot_scalars(stats)
                return 200, {**stats, "scalars": scalars}
            try:
                return 200, self.monitoring.lookup(m.group("name"))
            except MonitoringError as exc:
                return 404, {"error": str(exc)}

        add("GET", rf"/monitoring/{TOOL}/{NAME}", monitoring_lookup)
        add(
            "GET", rf"/monitoring/{TOOL}",
            lambda m, b, q: (200, self.monitoring.list_sessions()),
        )
        add(
            "DELETE", rf"/monitoring/{TOOL}/{NAME}",
            lambda m, b, q: (
                200, {"stopped": self.monitoring.stop(m.group("name"))},
            ),
        )

        # ---- Serve (resident model serving, serve/) ----
        # The ONE synchronous data-plane surface: unlike every
        # executor route (async job + poll), predict answers in the
        # request — coalesced with concurrent requests into a padded
        # shape bucket, run against device-resident params.
        def serve_predict(m, body, query):
            instances = body.get("instances")
            if instances is None:
                instances = body.get("x")
            if instances is None:
                raise ValidationError("missing 'instances'")
            try:
                return 200, self.serving.predict(
                    m.group("name"), instances
                )
            except QueueFull as exc:
                # Backpressure: bounded queue full — shed load with an
                # explicit retry budget (the Retry-After header is
                # attached by the HTTP layer from 'retryAfter').
                return 429, {
                    "error": str(exc),
                    "retryAfter": self.config.serve.retry_after_s,
                }

        add("POST", rf"/serve/{NAME}/predict", serve_predict)

        def serve_generate(m, body, query):
            """Autoregressive decode against a resident LM.  With
            ``stream=true`` the return value is the DecodeStream
            itself — the HTTP layer recognizes its ``sse_events``
            surface and writes a ``text/event-stream`` body, one
            event per generated token (registered ``no_timeout``: the
            stream outlives any slot budget; backpressure lives in
            the engine's own stream cap)."""
            body = body or {}
            prompts = body.get("prompts")
            if prompts is None:
                prompts = body.get("instances")
            if prompts is None:
                raise ValidationError("missing 'prompts'")
            stream = bool(body.get("stream"))
            kwargs = {
                "max_new_tokens": int(body.get("maxNewTokens", 32)),
                "stream": stream,
                "seed": int(body.get("seed", 0)),
            }
            if body.get("temperature") is not None:
                kwargs["temperature"] = float(body["temperature"])
            if body.get("topK") is not None:
                kwargs["top_k"] = int(body["topK"])
            if body.get("topP") is not None:
                kwargs["top_p"] = float(body["topP"])
            # Generation by diffusion over blocks; any other model
            # answers 406 to them.
            if body.get("denoisingSteps") is not None:
                kwargs["denoising_steps"] = int(body["denoisingSteps"])
            if body.get("remasking") is not None:
                kwargs["remasking"] = str(body["remasking"])
            if body.get("confidenceThreshold") is not None:
                kwargs["confidence_threshold"] = float(
                    body["confidenceThreshold"]
                )
            try:
                result = self.serving.generate(
                    m.group("name"), prompts, **kwargs
                )
            except QueueFull as exc:
                return 429, {
                    "error": str(exc),
                    "retryAfter": self.config.serve.retry_after_s,
                }
            # stream=true returns the DecodeStream itself; _send
            # duck-types its sse_events surface into an SSE body.
            return 200, result

        add("POST", rf"/serve/{NAME}/generate", serve_generate,
            no_timeout=True)

        def serve_generate_abort(m, body, query):
            """Server-side abort of an in-flight decode stream: frees
            the KV page slot at the next step boundary even when the
            SSE socket is still nominally open (lost client)."""
            ok = self.serving.decode.abort(
                m.group("name"), m.group("stream"),
                reason="aborted by DELETE",
            )
            if not ok:
                return 404, {
                    "error": f"no active stream {m.group('stream')!r} "
                    f"for model {m.group('name')!r}"
                }
            return 200, {"aborted": m.group("stream")}

        add("DELETE",
            rf"/serve/{NAME}/generate/(?P<stream>[A-Za-z0-9]+)",
            serve_generate_abort)
        # ``no_timeout``: a load lasts as long as the artifact is large
        # (10 GB of parameters: ten seconds to read and place), and it
        # is the call that takes that wait off the first request.
        add(
            "POST", rf"/serve/{NAME}/load",
            lambda m, b, q: (
                200, {"result": self.serving.load(m.group("name"))},
            ),
            no_timeout=True,
        )

        def serve_unload(m, body, query):
            if not self.serving.unload(m.group("name")):
                return 404, {
                    "error": f"model {m.group('name')!r} is not loaded"
                }
            return 200, {"result": "unloaded"}

        add("POST", rf"/serve/{NAME}/unload", serve_unload)
        add("DELETE", rf"/serve/{NAME}", serve_unload)
        add(
            "GET", r"/serve",
            lambda m, b, q: (
                200,
                {"models": self.serving.list_loaded(),
                 "stats": self.serving.stats()},
            ),
        )

        # ---- Fleet (multi-replica data plane, serve/fleet/) ----
        # Registered BEFORE the per-model replica routes so the
        # literal "fleet" path never parses as a model name.
        add(
            "GET", r"/serve/fleet",
            lambda m, b, q: (200, self.serving.fleet.snapshot()),
        )

        def serve_replicas_get(m, body, query):
            status = self.serving.fleet.status_for(m.group("name"))
            if not status:
                return 404, {
                    "error": f"model {m.group('name')!r} has no "
                    "replica set (POST bounds/count to create one)"
                }
            return 200, status

        add("GET", rf"/serve/{NAME}/replicas", serve_replicas_get)

        def serve_replicas_post(m, body, query):
            """Create/resize a model's replica set: any of ``min``,
            ``max`` (autoscaler bounds), ``count`` (manual scale,
            clamped to the bounds) and ``devicesPerReplica`` (chips
            each replica leases; > 1 shards the params across the
            slice).  Leases chips per replica; an exhausted pool
            surfaces as the LeaseTimeout 503."""
            body = body or {}

            def _int(key):
                val = body.get(key)
                if val is None:
                    return None
                try:
                    return int(val)
                except (TypeError, ValueError):
                    raise ValidationError(
                        f"{key!r} must be an integer, got {val!r}"
                    ) from None

            mn, mx, count = _int("min"), _int("max"), _int("count")
            dpr = _int("devicesPerReplica")
            if mn is None and mx is None and count is None and (
                    dpr is None):
                raise ValidationError(
                    "body needs at least one of 'min', 'max', "
                    "'count', 'devicesPerReplica'"
                )
            return 200, self.serving.fleet.configure(
                m.group("name"), min_replicas=mn, max_replicas=mx,
                count=count, devices_per_replica=dpr,
            )

        add("POST", rf"/serve/{NAME}/replicas", serve_replicas_post)

        def serve_replicas_delete(m, body, query):
            """Dissolve the model's fleet: drain replicas, release
            chips, return to single-path serving (the model stays
            loaded).  Idempotent."""
            name = m.group("name")
            return 200, {
                "model": name,
                "dissolved": self.serving.fleet.dissolve(name),
            }

        add("DELETE", rf"/serve/{NAME}/replicas", serve_replicas_delete)

        for service in ("tune", "train", "evaluate", "predict"):
            add("POST", rf"/{service}/{TOOL}", exec_create(service))
            add(
                "GET", rf"/{service}/{TOOL}",
                self._list_handler(service),
            )
            add("PATCH", rf"/{service}/{TOOL}/{NAME}", exec_update)
            add(
                "GET", rf"/{service}/{TOOL}/{NAME}",
                lambda m, b, q: (
                    200,
                    self.dataset.read_page(
                        m.group("name"), **self._page_args(q)
                    ),
                ),
            )
            add(
                "DELETE", rf"/{service}/{TOOL}/{NAME}",
                lambda m, b, q: (
                    self.executor.delete(m.group("name")),
                    (200, {"result": "deleted"}),
                )[1],
            )

        # ---- Builder ----
        def builder_create(m, body, query):
            tool = m.group("tool")
            if tool in ("tensorflow", "pytorch", "horovod"):
                # Distributed builder: one user function on every rank
                # (reference: POST /builder/tensorflow|pytorch →
                # /builderHorovod?type=builder/horovod, SURVEY §2.2).
                n_workers = body.get("nWorkers")
                if n_workers is None:  # explicit: 0 must reach validation
                    n_workers = body.get("n_workers")
                meta = self.distributed.create_builder(
                    body.get("name"),
                    function=body.get("function")
                    or body.get("modelingCode"),
                    function_parameters=body.get("functionParameters"),
                    n_workers=n_workers,
                    description=body.get("description", ""),
                )
                return self._created(f"builder/{tool}", meta)
            metas = self.builder.create(
                training_dataset=body.get("trainDatasetName"),
                test_dataset=body.get("testDatasetName"),
                classifiers=body.get("classifiersList")
                or body.get("classifiers") or [],
                label_field=body.get("labelField", "label"),
                feature_fields=body.get("featureFields"),
                modeling_code=body.get("modelingCode"),
                classifier_parameters=body.get("classifierParameters"),
                description=body.get("description", ""),
            )
            return 201, {
                "result": [
                    self._uri("builder/sparkml", mm["name"]) for mm in metas
                ]
            }

        add("POST", rf"/builder/{TOOL}", builder_create)
        add("GET", rf"/builder/{TOOL}", self._list_handler("builder"))
        add(
            "GET", rf"/builder/{TOOL}/{NAME}",
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )
        add(
            "DELETE", rf"/builder/{TOOL}/{NAME}",
            lambda m, b, q: (
                self.executor.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Function ----
        def function_create(m, body, query):
            meta = self.function.create(
                body.get("name"),
                function=body.get("function"),
                function_parameters=body.get("functionParameters"),
                description=body.get("description", ""),
                deadline_s=_deadline_s(body),
            )
            return self._created("function/python", meta)

        def function_update(m, body, query):
            meta = self.function.update(
                m.group("name"),
                function=body.get("function"),
                function_parameters=body.get("functionParameters"),
                description=body.get("description", ""),
                deadline_s=_deadline_s(body),
            )
            return 200, {"metadata": meta}

        add("POST", r"/function/python", function_create)
        add("GET", r"/function/python",
            self._list_handler("function", "python"))
        add("PATCH", r"/function/python/" + NAME, function_update)
        add(
            "GET", r"/function/python/" + NAME,
            lambda m, b, q: (
                200,
                self.dataset.read_page(m.group("name"), **self._page_args(q)),
            ),
        )
        add(
            "DELETE", r"/function/python/" + NAME,
            lambda m, b, q: (
                self.function.delete(m.group("name")),
                (200, {"result": "deleted"}),
            )[1],
        )

        # ---- Observe (the reference's separate-repo watch service) ----
        def observe_wait(m, body, query):
            name = m.group("name")
            try:
                timeout = float(query.get("timeout", 30))
            except (TypeError, ValueError):
                raise BadRequest("timeout must be a number")
            self.ctx.require_existing(name)
            import time as _time

            deadline = _time.time() + min(timeout, 300)
            while _time.time() < deadline:
                meta = self.ctx.artifacts.metadata.read(name)
                if meta.get("finished") or meta.get("jobState") == "failed":
                    return 200, {"metadata": meta}
                _time.sleep(0.1)
            return 200, {"metadata": self.ctx.artifacts.metadata.read(name)}

        # ---- Observe event feed + wildcard webhooks (before the NAME
        # routes: "events"/"webhook" would otherwise match as artifact
        # names; the dispatcher is first-match) ----
        def observe_events(m, body, query):
            try:
                since = int(query.get("sinceId", -1))
                limit = int(query.get("limit", 100))
            except (TypeError, ValueError):
                raise BadRequest("sinceId/limit must be integers")
            return 200, {"result": self.ctx.webhooks.events(since, limit)}

        add("GET", r"/observe/events", observe_events)

        def webhook_register_all(m, body, query):
            try:
                hook = self.ctx.webhooks.register(
                    "*", body.get("url"), body.get("events")
                )
            except ValueError as exc:
                raise ValidationError(str(exc)) from None
            return 201, {"result": hook}

        add("POST", r"/observe/webhook", webhook_register_all)
        add(
            "GET", r"/observe/webhook",
            lambda m, b, q: (200, {"result": self.ctx.webhooks.list("*")}),
        )
        add(
            "DELETE", r"/observe/webhook/(?P<hook>[0-9]+)",
            lambda m, b, q: (
                (200, {"result": "deleted"})
                if self.ctx.webhooks.unregister("*", int(m.group("hook")))
                else (404, {"error": "no such webhook"})
            ),
        )

        # Deliberate long-poll: exempt from the gateway deadline.
        add("GET", r"/observe/" + NAME, observe_wait, no_timeout=True)

        # ---- Observe push (webhooks on state transitions) ----
        def webhook_register(m, body, query):
            name = m.group("name")
            self.ctx.require_existing(name)
            try:
                hook = self.ctx.webhooks.register(
                    name, body.get("url"), body.get("events")
                )
            except ValueError as exc:
                raise ValidationError(str(exc)) from None
            # Registration raced the job: if the artifact is ALREADY
            # terminal, the engine's completion path has fired and
            # will never fire again — deliver now instead of leaving
            # the client waiting forever.  The metadata re-read comes
            # AFTER the hook insert: a job finishing in between sees
            # the hook (engine fires) OR we see the terminal state
            # (immediate fire) — both orders deliver; reading before
            # the insert would let the completion slip through the gap
            # unseen by either side.  (Worst case both fire — webhook
            # delivery is at-least-once, the standard contract.)
            meta = self.ctx.artifacts.metadata.read(name) or {}
            event = None
            if meta.get("jobState") == "failed":
                event = "failed"
            elif meta.get("finished"):
                event = "finished"
            if event is not None and event in hook["events"]:
                # deliver_to, not notify: the transition already hit
                # the event feed and wildcard hooks when it happened —
                # only THIS late registration needs the catch-up POST.
                self.ctx.webhooks.deliver_to(hook, name, event, meta)
                hook = {**hook, "firedImmediately": event}
            return 201, {"result": hook}

        def webhook_list(m, body, query):
            name = m.group("name")
            self.ctx.require_existing(name)
            return 200, {"result": self.ctx.webhooks.list(name)}

        def webhook_delete(m, body, query):
            ok = self.ctx.webhooks.unregister(
                m.group("name"), int(m.group("hook"))
            )
            if not ok:
                return 404, {"error": "no such webhook"}
            return 200, {"result": "deleted"}

        add("POST", rf"/observe/{NAME}/webhook", webhook_register)
        add("GET", rf"/observe/{NAME}/webhook", webhook_list)
        add("DELETE", rf"/observe/{NAME}/webhook/(?P<hook>[0-9]+)",
            webhook_delete)

        # ---- Job control plane (jobs/engine.py + jobs/journal.py) ----
        # DELETE cancels a queued job outright, or flips a RUNNING
        # job's CancelToken — the body observes it at its next
        # epoch/batch boundary, winds down like an early stop and the
        # engine records a journaled `cancelled` terminal state
        # (202: accepted, cooperative — poll the artifact).
        def job_cancel(m, body, query):
            name = m.group("name")
            self.ctx.require_existing(name)
            result = self.ctx.engine.cancel(name)
            if result is True:
                return 200, {"job": name, "result": "cancelled"}
            if result:
                return 202, {"job": name, "result": "cancelling"}
            return 409, {
                "error": f"job {name!r} is not queued or running "
                "(already terminal)"
            }

        add("DELETE", rf"/jobs/{NAME}", job_cancel)

        # ---- Introspection ----
        add(
            "GET", r"/registry",
            lambda m, b, q: (200, registry.list_registered()),
            cacheable=True,
        )
        add(
            "GET", r"/artifacts",
            lambda m, b, q: (
                200, self.dataset.list_metadata(q.get("type", ""))
            ),
        )
        add("GET", r"/health", lambda m, b, q: (200, {"status": "ok"}))

        def metrics_view(m, body, query):
            # Legacy JSON view, now backed by the same per-route
            # instrumentation that feeds the registry histograms.
            with self._metrics_lock:
                routes = {
                    k: {
                        **v,
                        "avg_ms": round(v["total_ms"] / v["count"], 3)
                        if v["count"] else 0.0,
                    }
                    for k, v in self._metrics.items()
                }
            return 200, {
                "routes": routes,
                "budget": {
                    "request_timeout_s":
                        self.config.api.request_timeout_s,
                    "cache_ttl_s": self.config.api.cache_ttl_s,
                },
            }

        # Per-route request counts/latencies — the krakend :8090
        # metrics exporter's role (SURVEY §5.1).
        add("GET", r"/metrics", metrics_view)

        # ---- Unified observability (obs/) ----
        def metrics_prom(m, body, query):
            """Prometheus text exposition over the whole registry:
            HTTP latency histograms, job queue waits, lease
            utilization, compile-cache counters, serving occupancy
            and store/replication state — one scrapeable surface
            unifying the four legacy JSON endpoints."""
            text = self.obs.render_prometheus()
            return 200, (
                "text/plain; version=0.0.4; charset=utf-8",
                text.encode(),
            )

        add("GET", r"/metrics\.prom", metrics_prom)

        def job_trace(m, body, query):
            """Span tree of a job's life (queue wait → lease →
            compile → per-epoch steps), read back from the newest
            execution-ledger record carrying a trace."""
            name = m.group("name")
            self.ctx.require_existing(name)
            doc = None
            for rec in reversed(
                self.ctx.artifacts.ledger.history(name)
            ):
                if rec.get("trace"):
                    doc = rec["trace"]
                    break
            if doc is None:
                return 404, {
                    "error": f"no trace recorded for {name!r} (job "
                             "still running, predates tracing, or "
                             "LO_TPU_OBS_TRACE=0)"
                }
            spans = doc.get("spans", [])
            return 200, {
                "name": name,
                "requestId": doc.get("requestId"),
                "droppedSpans": doc.get("droppedSpans", 0),
                "spans": spans,
                "tree": obs_tracing.span_tree(spans),
            }

        add("GET", rf"/observability/jobs/{NAME}/trace", job_trace)

        # ---- Windowed time-series rollups (obs/rollup.py) ----
        # The in-process time dimension: counter rates, gauge
        # min/avg/max and histogram-delta quantiles over the rollup
        # rings.  Query: ?name=<family>&windowS=<s>&points=<n> plus
        # any other key as a label filter (e.g. &model=mnist,
        # &route=POST+/serve/...); no name lists the tracked
        # families.
        def timeseries_view(m, body, query):
            name = query.get("name")
            try:
                window_s = float(query.get("windowS", 300.0))
                max_points = int(query.get("points", 0))
            except (TypeError, ValueError):
                raise ValidationError(
                    "windowS/points must be numeric"
                ) from None
            labels = {
                k: v for k, v in query.items()
                if k not in ("name", "windowS", "points")
            }
            return 200, self.rollup.timeseries(
                name, labels or None, window_s=window_s,
                max_points=max_points,
            )

        add("GET", r"/observability/timeseries", timeseries_view)

        # ---- SLO objectives + burn-rate alerts (obs/slo.py) ----
        # /alerts is the drill surface: pending/firing/resolved state
        # per (objective, instance) with the burn rates that produced
        # it; /slo is the objective/budget view.  Both mirror onto
        # /metrics.prom (lo_alert_active, lo_slo_burn_rate).
        add(
            "GET", r"/observability/alerts",
            lambda m, b, q: (200, self.slo.alerts()),
        )
        add(
            "GET", r"/observability/slo",
            lambda m, b, q: (200, self.slo.status()),
        )

        # Runtime objectives: the drill surface — POST an ad-hoc
        # objective (e.g. availability scoped to one route) before an
        # experiment, DELETE it after.  Config-built objectives are
        # the deployment's contract and stay non-removable.
        def slo_create(m, body, query):
            body = body or {}
            threshold_ms = body.get("thresholdMs")
            try:
                doc = self.slo.add_objective(
                    body.get("name"), body.get("kind"),
                    body.get("target", 0),
                    threshold_s=(
                        float(threshold_ms) / 1000.0
                        if threshold_ms is not None else None
                    ),
                    metric=body.get("metric"),
                    route=body.get("route"),
                )
            except (TypeError, ValueError) as exc:
                raise ValidationError(str(exc)) from None
            return 201, {"objective": doc}

        def slo_delete(m, body, query):
            name = m.group("name")
            if not self.slo.remove_objective(name):
                return 404, {
                    "error": f"no runtime objective {name!r}"
                }
            return 200, {"result": "deleted"}

        add("POST", r"/observability/slo", slo_create)
        add("DELETE", rf"/observability/slo/{NAME}", slo_delete)

        # ---- Flight recorder + debug bundles (obs/flight.py,
        # obs/bundle.py) ----
        # /flight is the live incident view: per-domain rings plus
        # the merged timeline.  /bundle (POST) freezes everything
        # into a durable on-disk bundle NOW; /bundles is the store.
        def flight_view(m, body, query):
            from learningorchestra_tpu.obs import flight as obs_flight

            domains = None
            if query.get("domain"):
                domains = tuple(
                    d for d in str(query["domain"]).split(",") if d
                )
            try:
                limit = int(query.get("limit", 0))
            except ValueError:
                raise ValidationError(
                    "limit must be an integer"
                ) from None
            doc = obs_flight.snapshot(domains=domains, limit=limit)
            doc["timeline"] = obs_flight.timeline(
                domains=domains, limit=limit
            )
            return 200, doc

        def bundle_create(m, body, query):
            body = body or {}
            reason = str(body.get("reason") or "manual")
            return 201, {
                "bundle": self.bundles.build(reason, {"via": "rest"})
            }

        def bundle_get(m, body, query):
            name = m.group("name")
            rel = query.get("file")
            if rel:
                # Retrieval: one bundle artifact's bytes (path
                # traversal is rejected inside read_file).
                return 200, (
                    "application/octet-stream",
                    self.bundles.read_file(name, rel),
                )
            doc = self.bundles.manifest(name)
            if doc is None:
                return 404, {"error": f"no bundle {name!r}"}
            return 200, doc

        def bundle_delete(m, body, query):
            name = m.group("name")
            if not self.bundles.delete(name):
                return 404, {"error": f"no bundle {name!r}"}
            return 200, {"result": "deleted"}

        add("GET", r"/observability/flight", flight_view)
        add("POST", r"/observability/bundle", bundle_create)
        add(
            "GET", r"/observability/bundles",
            lambda m, b, q: (200, self.bundles.status()),
        )
        add(
            "DELETE", r"/observability/bundles",
            lambda m, b, q: (
                200, {"deleted": self.bundles.delete_all()},
            ),
        )
        add("GET", rf"/observability/bundles/{NAME}", bundle_get)
        add("DELETE", rf"/observability/bundles/{NAME}",
            bundle_delete)

        # ---- On-demand profiler capture (obs/profiling.py) ----
        # start/stop wrap jax.profiler around a LIVE process: capture
        # a device trace while production traffic runs, list the
        # retained captures, pull the .xplane.pb artifacts for
        # offline TensorBoard analysis.  One capture at a time
        # (double-start → 409), auto-stop deadline, bounded dir.
        # NOTE: /start registered before /stop — the every-route-
        # metered gate dispatches in registration order, so its sweep
        # opens and then closes a capture instead of leaking one.
        def profile_start(m, body, query):
            body = body or {}
            return 201, {
                "capture": self.profiler.start(
                    name=body.get("name"),
                    max_seconds=body.get("maxSeconds"),
                )
            }

        def profile_stop(m, body, query):
            return 200, {"capture": self.profiler.stop()}

        add("POST", r"/observability/profile/start", profile_start)
        add("POST", r"/observability/profile/stop", profile_stop)
        add(
            "GET", r"/observability/profile",
            lambda m, b, q: (200, self.profiler.status()),
        )
        add(
            "GET", r"/observability/profile/captures",
            lambda m, b, q: (
                200, {"captures": self.profiler.list_captures()},
            ),
        )

        def profile_capture(m, body, query):
            name = m.group("name")
            rel = query.get("file")
            if rel:
                # Retrieval: one capture artifact's bytes (path
                # traversal is rejected inside read_file).
                return 200, (
                    "application/octet-stream",
                    self.profiler.read_file(name, rel),
                )
            doc = self.profiler.capture(name)
            if doc is None:
                return 404, {"error": f"no capture {name!r}"}
            return 200, doc

        add("GET", rf"/observability/profile/captures/{NAME}",
            profile_capture)
        add(
            "DELETE", rf"/observability/profile/captures/{NAME}",
            lambda m, b, q: (
                (200, {"result": "deleted"})
                if self.profiler.delete(m.group("name"))
                else (404, {"error": f"no capture {m.group('name')!r}"})
            ),
        )

        # ---- Cost accounting (obs/costs.py): the JSON view over the
        # per-program FLOPs/HBM ledger and the device-time ledgers
        # (per job / per model / per bucket) — the same numbers the
        # lo_program_* and lo_device_time_* Prometheus families carry.
        def costs_view(m, body, query):
            from learningorchestra_tpu.obs import costs as obs_costs

            return 200, obs_costs.snapshot()

        add("GET", r"/observability/costs", costs_view)

        # ---- Runtime lock witness (concurrency_rt.py) ----
        # The deadlock-diagnosis surface: witnessed acquisition-order
        # edges, held-while-blocking contention events, and every
        # currently held/contended lock with its holder, waiters and
        # their live thread stacks.  Meaningful under LO_TPU_WITNESS=1
        # (otherwise answers enabled=false with empty data — the
        # endpoint stays probeable either way).
        def locks_view(m, body, query):
            from learningorchestra_tpu import concurrency_rt

            return 200, concurrency_rt.snapshot(include_stacks=True)

        add("GET", r"/observability/locks", locks_view)

        # ---- Fault-injection plane (faults/plane.py) ----
        # The chaos drill's REST surface: inspect every registered
        # fault point, arm a seeded schedule against one, disarm one
        # or all.  Trigger counters also export at /metrics.prom
        # (lo_fault_triggers_total).
        def faults_status(m, body, query):
            return 200, faults.status()

        def faults_arm(m, body, query):
            body = body or {}
            mode = body.get("mode")
            if not mode:
                raise ValidationError(
                    f"missing 'mode' (one of {list(faults.MODES)})"
                )
            try:
                doc = faults.arm(
                    m.group("name"), str(mode),
                    rate=float(body.get("rate", 1.0)),
                    seed=int(body.get("seed", 0)),
                    after=int(body.get("after", 0)),
                    max_triggers=int(body.get("maxTriggers", 0)),
                    delay_ms=float(body.get("delayMs", 0.0)),
                )
            except (TypeError, ValueError) as exc:
                raise ValidationError(str(exc)) from None
            return 201, {"point": m.group("name"), "armed": doc}

        def faults_disarm(m, body, query):
            try:
                disarmed = faults.disarm(m.group("name"))
            except ValueError as exc:  # unknown point
                raise ValidationError(str(exc)) from None
            if not disarmed:
                return 404, {
                    "error": f"fault point {m.group('name')!r} is "
                             "not armed"
                }
            return 200, {"result": "disarmed"}

        add("GET", r"/faults", faults_status)
        add("DELETE", r"/faults",
            lambda m, b, q: (faults.disarm_all(),
                             (200, {"result": "disarmed"}))[1])
        add("POST", rf"/faults/{NAME}", faults_arm)
        add("DELETE", rf"/faults/{NAME}", faults_disarm)

        # ---- Ops status page (the reference's Portainer GUI role,
        # reference: docker-compose.yml:102-129): one human-readable
        # HTML view over the JSON the system already exposes — jobs,
        # fairness queues, chip leases, cluster agents, recent events.
        def status_view(m, body, query):
            return 200, ("text/html; charset=utf-8",
                         self._render_status().encode())

        add("GET", r"/status", status_view)

        # ---- Replication + HA peering (store/ha.py — the reference's
        # mongo replica set, reference: docker-compose.yml:42-90).
        # A network standby pulls WAL listings and byte ranges from
        # here, so the secondary replicates over the wire with no
        # shared mount (the mongo-secondary topology); the fence POST
        # lets a promoted standby demote a live-but-partitioned
        # primary; /replication/status carries the election epoch a
        # restarted node compares against its own before serving.
        from learningorchestra_tpu.store.ha import is_fenced
        from learningorchestra_tpu.store.replica import (
            FENCE_FILE,
            read_epoch,
        )

        def replication_wals(m, body, query):
            root = self.config.store.store_path()
            wals = []
            if root.is_dir():
                for wal in sorted(root.glob("*.wal")):
                    try:
                        wals.append(
                            {"name": wal.stem, "size": wal.stat().st_size}
                        )
                    except OSError:
                        continue  # dropped between glob and stat
            return 200, {
                "wals": wals,
                "epoch": read_epoch(root),
                "fenced": is_fenced(root) is not None,
            }

        add("GET", r"/replication/wals", replication_wals)

        def replication_wal_read(m, body, query):
            # NAME excludes "/" and "%", so the stem cannot traverse
            # out of the store root.
            root = self.config.store.store_path()
            offset = max(0, _int_param(query, "from", 0))
            length = _int_param(query, "len", 0)
            try:
                with open(root / f"{m.group('name')}.wal", "rb") as fh:
                    fh.seek(offset)
                    data = fh.read(length) if length > 0 else fh.read()
            except FileNotFoundError:
                return 404, {"error": f"no WAL {m.group('name')!r}"}
            return 200, ("application/octet-stream", data)

        add("GET", rf"/replication/wal/{NAME}", replication_wal_read)

        def replication_status(m, body, query):
            root = self.config.store.store_path()
            fence = is_fenced(root)
            return 200, {
                "role": "fenced" if fence is not None else "primary",
                "epoch": read_epoch(root),
                "fence": fence,
            }

        add("GET", r"/replication/status", replication_status)

        def replication_fence(m, body, query):
            root = self.config.store.store_path()
            # Same epoch discipline as every other demotion path: only
            # a STRICTLY HIGHER election epoch may fence this store.  A
            # stale standby from a prior election (or a replayed /
            # misdirected POST) must not take down a healthy primary.
            ours = read_epoch(root)
            theirs = int((body or {}).get("epoch", 0) or 0)
            if theirs <= ours:
                return 409, {
                    "error": f"fence epoch {theirs} is not newer than "
                             f"this store's epoch {ours}",
                    "epoch": ours,
                }
            root.mkdir(parents=True, exist_ok=True)
            (root / FENCE_FILE).write_text(
                json.dumps(dict(body or {}))
            )
            # Demote AFTER this response flushes: the caller (a
            # promoted standby) needs the acknowledgement, and the
            # fence watch would take up to an interval to notice.
            def demote():
                import time as _time

                _time.sleep(0.2)
                print(
                    "store fenced by peer over /replication/fence — "
                    "demoting: shutting down to prevent split-brain",
                    flush=True,
                )
                self.shutdown()

            threading.Thread(target=demote, daemon=True).start()
            return 200, {"fenced": True}

        add("POST", r"/replication/fence", replication_fence)

        # ---- scale-out control plane (jobs/cluster.py) ----

        def cluster_status(m, body, query):
            # Always 200 so ctx.cluster.status() works against any
            # topology: single-engine deployments report enabled=False
            # instead of a 404 the client would have to special-case.
            if self.ctx.cluster is None:
                doc = {"enabled": False, "engines": [], "claims": []}
            else:
                doc = {"enabled": True, **self.ctx.cluster.status()}
            if self.ctx.admission is not None:
                doc["tenants"] = self.ctx.admission.snapshot()
            return 200, doc

        add("GET", r"/cluster/status", cluster_status)

    # -- HTTP plumbing --------------------------------------------------------

    def _handle_raw(self, handler, m, body, query):
        try:
            # Chaos probe: an armed ``http.handler`` schedule can
            # delay or fail any admitted request — inside the try, so
            # an injected error exercises the real 500 path and an
            # injected delay the real gateway-timeout path.  For the
            # profiler routes this also proves an injected failure
            # fires BEFORE the handler claims the single-capture
            # lock — a chaos drill must not wedge profiling.
            faults.hit("http.handler")
            return handler(m, body, query)
        except (DuplicateArtifact, ConflictError,
                ProfilerConflict, BundleBusy) as exc:
            return 409, {"error": str(exc)}
        except (NotFoundError, ProfilerNotFound,
                BundleNotFound) as exc:
            return 404, {"error": str(exc)}
        except (ValidationError, RegistryError, ServeError,
                ProfilerError, BundleError) as exc:
            return 406, {"error": str(exc)}
        except LeaseTimeout as exc:
            # No chip lease within the placement budget: the pool is
            # saturated, not broken — same contract as the serving
            # tier's 429: explicit retry budget instead of a generic
            # 500 (Retry-After attached by the HTTP layer).
            return 503, {
                "error": str(exc),
                "retryAfter": self.config.serve.retry_after_s,
            }
        except QueueFull as exc:
            # Serving backpressure escaping ANY route (predict maps
            # it locally; a replicas POST racing shutdown lands here):
            # saturated/teardown, not broken — shed retriably.
            return 429, {
                "error": str(exc),
                "retryAfter": self.config.serve.retry_after_s,
            }
        except QuotaExceeded as exc:
            # Defense in depth: admission normally rejects in
            # _handle_slotted before the handler runs, but a handler
            # that submits extra jobs internally can still trip a
            # tenant quota mid-flight.
            return 429, {
                "error": str(exc),
                "retryAfter": exc.retry_after_s,
            }
        except (json.JSONDecodeError, BadRequest) as exc:
            return 400, {"error": f"bad JSON: {exc}"
                         if isinstance(exc, json.JSONDecodeError)
                         else str(exc)}
        except Exception as exc:  # pragma: no cover - defensive
            from learningorchestra_tpu.log import get_logger

            get_logger("api").exception("unhandled handler error: %r", exc)
            return 500, {"error": repr(exc)}

    @property
    def obs(self):
        """The registry this server currently exposes (collector
        registration guaranteed) — the process-wide one."""
        self._obs_handles()
        return self._obs_registry

    def _obs_handles(self):
        """HTTP metric handles on the current registry, rebinding (and
        re-registering the collector) if reset_registry() replaced it
        since the last use.  Double-checked under a lock: two racing
        requests must not register the collector twice."""
        reg = obs_metrics.get_registry()
        if reg is not self._obs_registry:
            with self._obs_rebind_lock:
                if reg is not self._obs_registry:
                    buckets_s = tuple(
                        ms / 1e3
                        for ms in self.config.obs.latency_buckets_ms
                    )
                    self._http_hist = reg.histogram(
                        "lo_http_request_duration_seconds",
                        "HTTP request latency by route.",
                        labels=("route",),
                        buckets=buckets_s,
                    )
                    self._http_total = reg.counter(
                        "lo_http_requests_total",
                        "HTTP requests by route and status class.",
                        labels=("route", "status"),
                    )
                    self._http_max = reg.gauge(
                        "lo_http_request_max_ms",
                        "Max observed request latency by route.",
                        labels=("route",),
                    )
                    reg.add_collector(self._collect_families)
                    self._obs_registry = reg
        return self._http_hist, self._http_total, self._http_max

    def _record_metric(self, key: str, status: int, dt_ms: float,
                       request_id: str | None = None) -> None:
        # Flight-recorder timeline entry FIRST (lock-free append).
        # The request id is threaded explicitly: this runs on the HTTP
        # thread, outside invoke()'s contextvar binding.
        from learningorchestra_tpu.obs import flight as obs_flight

        if request_id is not None:
            obs_flight.record(
                "http", "request", route=key, status=status,
                ms=round(dt_ms, 3), requestId=request_id,
            )
        else:
            obs_flight.record(
                "http", "request", route=key, status=status,
                ms=round(dt_ms, 3),
            )
        with self._metrics_lock:
            rec = self._metrics.setdefault(
                key,
                {"count": 0, "errors": 0, "total_ms": 0.0, "max_ms": 0.0},
            )
            rec["count"] += 1
            if status >= 400:
                rec["errors"] += 1
            rec["total_ms"] += dt_ms
            rec["max_ms"] = max(rec["max_ms"], dt_ms)
        # Registry mirror (obs/metrics.py): real latency HISTOGRAMS —
        # the avg/max dict above survives only as the legacy /metrics
        # JSON view's backing.  No-ops when LO_TPU_OBS_ENABLED=0.
        http_hist, http_total, http_max = self._obs_handles()
        http_hist.observe(dt_ms / 1e3, route=key)
        http_total.inc(
            route=key, status=f"{min(max(status // 100, 1), 5)}xx"
        )
        http_max.set_max(dt_ms, route=key)

    def _collect_families(self):
        """Pull-side exposition for GET /metrics.prom: snapshot the
        subsystems that already keep exact counters under their own
        locks — job queues, the chip-lease pool, the compiled-program
        cache, serving batchers, store WALs and replication state —
        into Prometheus families.  Runs at scrape time; must stay
        fast and must not throw (the renderer drops a failing
        collector's families, never the exposition)."""
        import time as _time

        from learningorchestra_tpu.obs.metrics import Family
        from learningorchestra_tpu.store.ha import is_fenced
        from learningorchestra_tpu.store.replica import read_epoch
        from learningorchestra_tpu.train import aot_store, compile_cache

        fams: list[Family] = []
        fams.append(
            Family(
                "gauge", "lo_uptime_seconds",
                "Seconds since this API process started.",
            ).sample(_time.time() - self._t_start)
        )

        # -- job engine: queue depth per fairness class ---------------
        depth = Family(
            "gauge", "lo_jobs_queue_depth",
            "Queued-but-undispatched jobs per fairness class.",
        )
        for cls, n in self.ctx.engine.queue_depths(
            include_empty=True
        ).items():
            depth.sample(n, job_class=cls)
        # Per-tenant breakdown rides the same family as extra samples
        # (labelled job_class + tenant) — emitted only once a tenant
        # has been seen, so single-tenant scrapes keep their shape.
        for (cls, tenant), n in (
            self.ctx.engine.queue_depths_by_tenant().items()
        ):
            depth.sample(n, job_class=cls, tenant=tenant or "-")
        fams.append(depth)

        # -- scale-out control plane ----------------------------------
        engines_live = 0
        if self.ctx.cluster is not None:
            try:
                cstat = self.ctx.cluster.status()
                engines_live = sum(
                    1 for e in cstat.get("engines", ()) if e.get("live")
                )
            except Exception:  # noqa: BLE001 — scrape must not fail
                engines_live = 0
        fams.append(
            Family(
                "gauge", "lo_cluster_engines",
                "Live job engines sharing this store "
                "(0 = clustering off).",
            ).sample(engines_live)
        )

        # -- chip-lease pool utilization ------------------------------
        snap = self.ctx.leaser.snapshot()
        n_all, n_free = len(snap["all"]), len(snap["free"])
        fams.append(
            Family(
                "gauge", "lo_lease_devices",
                "Chip-lease pool state (all/free/in_use).",
            )
            .sample(n_all, state="all")
            .sample(n_free, state="free")
            .sample(n_all - n_free, state="in_use")
        )

        # -- compiled-program cache -----------------------------------
        stats = compile_cache.get_cache().stats()
        events = Family(
            "counter", "lo_compile_cache_events_total",
            "Compiled-program cache lifetime counters.",
        )
        for kind in ("hits", "misses", "evictions", "coalesced"):
            events.sample(stats[kind], kind=kind)
        events.sample(
            stats["deviceInvalidations"], kind="device_invalidations"
        )
        fams.append(events)
        fams.append(
            Family(
                "counter", "lo_compile_cache_trace_seconds_total",
                "Cumulative seconds spent tracing/compiling programs.",
            ).sample(stats["traceTimeS"])
        )
        fams.append(
            Family(
                "gauge", "lo_compile_cache_entries",
                "Resident compiled-program cache entries.",
            ).sample(stats["entries"])
        )
        fams.append(
            Family(
                "gauge", "lo_compile_cache_bytes_estimate",
                "Estimated resident bytes of cached programs.",
            ).sample(stats["bytesEstimate"])
        )
        fams.append(
            Family(
                "gauge", "lo_compile_cache_measured_entries",
                "Cache entries charged at their MEASURED serialized "
                "size (vs the flat fallback estimate).",
            ).sample(stats.get("measuredEntries", 0))
        )

        # -- durable AOT executable store (train/aot_store.py) --------
        # Zeros when disabled (stats_snapshot keeps scrape shape
        # stable), so dashboards never see a series appear/vanish on a
        # config flip.
        aot = aot_store.stats_snapshot()
        fams.append(
            Family(
                "counter", "lo_compile_cache_aot_hits",
                "AOT executables restored from the durable store "
                "(dispatches that skipped trace AND compile).",
            ).sample(aot["hits"])
        )
        fams.append(
            Family(
                "counter", "lo_compile_cache_aot_misses",
                "Durable-store lookups with no usable blob.",
            ).sample(aot["misses"])
        )
        fams.append(
            Family(
                "counter", "lo_compile_cache_aot_load_errors",
                "Stale/corrupt AOT blobs that degraded to a live "
                "re-trace.",
            ).sample(aot["loadErrors"])
        )
        fams.append(
            Family(
                "gauge", "lo_compile_cache_aot_persisted_entries",
                "Executables currently persisted in the AOT store.",
            ).sample(aot["persistedEntries"])
        )
        fams.append(
            Family(
                "gauge", "lo_compile_cache_aot_persisted_bytes",
                "On-disk bytes of persisted AOT executables.",
            ).sample(aot["persistedBytes"])
        )

        # -- cost accounting: per-program FLOPs/HBM + device-time
        # attribution (obs/costs.py).  Cardinality is bounded by
        # construction: programs <= the cost ledger's cap (itself <=
        # program diversity the compile cache admits), jobs ride a
        # bounded freshest-N ring, buckets <= models x log2(max_batch).
        try:
            from learningorchestra_tpu.obs import costs as obs_costs

            fams += self._collect_cost_families(obs_costs)
        except Exception:  # noqa: BLE001 — cost families must never
            pass  # take down the whole exposition

        # -- serving: registry residency + batcher aggregates (the
        # same roll-up the tfevents snapshot uses — ONE aggregation,
        # serve/service.py aggregate()) ------------------------------
        sstats = self.serving.stats()
        agg = self.serving.aggregate(sstats)
        fams.append(
            Family(
                "gauge", "lo_serving_resident_models",
                "Models pinned resident on device.",
            ).sample(agg["resident_models"])
        )
        fams.append(
            Family(
                "gauge", "lo_serving_resident_bytes",
                "Parameter bytes pinned resident on device.",
            ).sample(agg["resident_bytes"])
        )
        sevents = Family(
            "counter", "lo_serving_events_total",
            "Serving lifetime counters, summed over served models.",
        )
        for kind in ("requests", "rows", "batches", "overflows",
                     "padded_rows"):
            sevents.sample(agg[kind], kind=kind)
        fams.append(sevents)
        fams.append(
            Family(
                "gauge", "lo_serving_queue_depth",
                "Rows queued across serving batchers.",
            ).sample(agg["queue_depth"])
        )
        fams.append(
            Family(
                "gauge", "lo_serving_batch_occupancy",
                "Mean dispatch occupancy (rows/bucket) over models.",
            ).sample(agg["occupancy"])
        )
        slat = Family(
            "gauge", "lo_serving_latency_ms",
            "Rolling request-latency quantiles (max over models).",
        )
        for q, val in agg["quantiles"].items():
            slat.sample(val, quantile=q)
        fams.append(slat)
        if sstats["models"]:
            # Per-model queue depth (fleet replicas summed): the
            # series the rollup engine tracks and the autoscaler's
            # growth-slope trigger fits against.  Cardinality <= the
            # registry's max_models cap.
            mdepth = Family(
                "gauge", "lo_serving_model_queue_depth",
                "Rows queued per served model (replicas summed).",
            )
            for model, mstats in sstats["models"].items():
                mdepth.sample(mstats["queueDepth"], model=model)
            fams.append(mdepth)

        # -- decode concurrency: live stream count and admission
        # headroom per resident-LM model, straight from the decoder's
        # own stats (free = unoccupied slots across its page pools —
        # the number of streams admittable without a pool grow).
        dstats = self.serving.decode.stats()
        if dstats["models"]:
            dactive = Family(
                "gauge", "lo_serving_decode_active_streams",
                "Streams active (queued+resident) per decode model.",
            )
            dfree = Family(
                "gauge", "lo_serving_decode_free_slots",
                "Unoccupied page-pool slots per decode model.",
            )
            for model, ds in dstats["models"].items():
                dactive.sample(ds["activeStreams"], model=model)
                dfree.sample(
                    sum(p["slots"] - p["live"] for p in ds["pools"]),
                    model=model,
                )
            fams += [dactive, dfree]

        # -- fleet: per-replica attribution.  Cardinality is bounded
        # by construction (models <= registry max_models, replicas <=
        # the per-model max bound, and replica indices are REUSED
        # lowest-free-first so scale oscillation cycles a fixed label
        # set instead of minting new ones), so these stay inside the
        # LO_TPU_OBS_MAX_SERIES budget without collapsing. -----------
        fleet = self.serving.fleet.snapshot()
        if fleet["models"]:
            nrepl = Family(
                "gauge", "lo_serving_replicas",
                "Active replicas per fleet-served model.",
            )
            rdepth = Family(
                "gauge", "lo_serving_replica_queue_depth",
                "Rows queued per replica batcher.",
            )
            rreq = Family(
                "counter", "lo_serving_replica_requests_total",
                "Requests routed per replica.",
            )
            for model, st in fleet["models"].items():
                nrepl.sample(st["size"], model=model)
                for r in st["replicas"]:
                    labels = {
                        "model": model,
                        "replica": str(r["replica"]),
                        "device": r["device"],
                    }
                    rdepth.sample(r["queueDepth"], **labels)
                    rreq.sample(r["requests"], **labels)
            fams += [nrepl, rdepth, rreq]
        if fleet["scaleTotals"]:
            # From the manager's CUMULATIVE totals, not the live sets:
            # a counter series must survive dissolve/invalidation
            # instead of vanishing or resetting mid-series.
            scale = Family(
                "counter", "lo_serving_fleet_scale_events_total",
                "Replica scale events per model and direction.",
            )
            for model, t in fleet["scaleTotals"].items():
                scale.sample(t["up"], model=model, direction="up")
                scale.sample(t["down"], model=model, direction="down")
            fams.append(scale)
        # Emitted even with no replica sets: the control loop keeps
        # ticking while fleets are drained away, and a counter that
        # vanishes mid-series breaks rate()/absence liveness alerts.
        fams.append(
            Family(
                "counter", "lo_serving_fleet_autoscaler_ticks_total",
                "Autoscaler control-loop passes.",
            ).sample(fleet["autoscaler"]["ticks"])
        )

        # -- store WALs + replication ---------------------------------
        root = self.config.store.store_path()
        wal_bytes, wal_files = 0, 0
        if root.is_dir():
            for wal in root.glob("*.wal"):
                try:
                    wal_bytes += wal.stat().st_size
                    wal_files += 1
                except OSError:
                    continue  # dropped between glob and stat
        fams.append(
            Family(
                "gauge", "lo_store_wal_bytes",
                "Total bytes across store WAL files.",
            ).sample(wal_bytes)
        )
        fams.append(
            Family(
                "gauge", "lo_store_wal_files",
                "Store WAL file count.",
            ).sample(wal_files)
        )
        fams.append(
            Family(
                "gauge", "lo_replication_epoch",
                "This store's election epoch.",
            ).sample(read_epoch(root))
        )
        fams.append(
            Family(
                "gauge", "lo_store_fenced",
                "1 when a standby fenced this store, else 0.",
            ).sample(1 if is_fenced(root) is not None else 0)
        )

        # -- rollup engine health + SLO burn/alert mirror -------------
        try:
            fams += self.rollup.prom_families()
            fams += self.slo.prom_families()
        except Exception:  # noqa: BLE001 — the mirror must never
            pass  # take down the whole exposition
        return fams

    def _collect_cost_families(self, obs_costs) -> list:
        """lo_program_* and lo_device_time_* / MFU families from the
        cost-accounting plane (obs/costs.py) — what each compiled
        program costs per execution, and who consumed the device."""
        from learningorchestra_tpu.obs.metrics import Family

        if not obs_costs.enabled():
            return []
        fams: list = []
        ledger = obs_costs.get_ledger().snapshot()
        programs = [p for p in ledger["programs"] if p["label"]]
        if programs:
            flops = Family(
                "gauge", "lo_program_flops",
                "XLA-reported FLOPs per execution of each compiled "
                "program.",
            )
            accessed = Family(
                "gauge", "lo_program_bytes_accessed",
                "XLA-reported bytes accessed per execution.",
            )
            hbm = Family(
                "gauge", "lo_program_hbm_bytes",
                "Per-program HBM footprint by kind "
                "(argument/output/temp/code).",
            )
            size = Family(
                "gauge", "lo_program_serialized_bytes",
                "Serialized executable size (what the compile cache's "
                "byte cap charges).",
            )
            for p in programs:
                # program + key: labels alone are NOT unique (two
                # fits of one architecture at different shapes share
                # a label string), and duplicate label sets would
                # make Prometheus reject the ENTIRE scrape — the
                # fingerprint prefix disambiguates.
                labels = {"program": p["label"], "key": p["key"]}
                if p["flops"] is not None:
                    flops.sample(p["flops"], **labels)
                if p["bytesAccessed"] is not None:
                    accessed.sample(p["bytesAccessed"], **labels)
                for kind, field in (
                    ("argument", "argumentBytes"),
                    ("output", "outputBytes"),
                    ("temp", "tempBytes"),
                    ("code", "generatedCodeBytes"),
                ):
                    if p[field] is not None:
                        hbm.sample(p[field], kind=kind, **labels)
                if p["serializedBytes"] is not None:
                    size.sample(p["serializedBytes"], **labels)
            fams += [f for f in (flops, accessed, hbm, size)
                     if f.samples]
        fams.append(
            Family(
                "counter", "lo_program_analyses_total",
                "Cost/memory analyses run at program build time.",
            )
            .sample(ledger["analyses"], outcome="ok")
            .sample(ledger["analysisFailures"], outcome="failed")
        )
        dt = obs_costs.devtime().snapshot(
            peak_flops=obs_costs.peak_flops()
        )
        totals = dt["totals"]
        fams.append(
            Family(
                "counter", "lo_device_time_seconds_total",
                "Attributed device seconds (sampled; scaled to be "
                "unbiased).",
            ).sample(totals["deviceTimeS"])
        )
        fams.append(
            Family(
                "counter", "lo_device_flops_total",
                "Attributed FLOPs across dispatches.",
            ).sample(totals["flops"])
        )
        if dt["jobs"]:
            jt = Family(
                "gauge", "lo_job_device_seconds",
                "Attributed device seconds per job (freshest-N ring).",
            )
            jmfu = Family(
                "gauge", "lo_job_mfu",
                "Model-FLOPs-utilization per job (needs "
                "LO_TPU_COSTS_PEAK_FLOPS).",
            )
            for job, doc in dt["jobs"].items():
                jt.sample(doc["deviceTimeS"], job=job)
                if "mfu" in doc:
                    jmfu.sample(doc["mfu"], job=job)
            fams.append(jt)
            if jmfu.samples:
                fams.append(jmfu)
        if dt["models"]:
            mt = Family(
                "gauge", "lo_model_device_seconds",
                "Attributed device seconds per served model.",
            )
            for model, doc in dt["models"].items():
                mt.sample(doc["deviceTimeS"], model=model)
            fams.append(mt)
        if dt["buckets"]:
            bmfu = Family(
                "gauge", "lo_serving_bucket_mfu",
                "Model-FLOPs-utilization per (model, bucket) (needs "
                "LO_TPU_COSTS_PEAK_FLOPS).",
            )
            bt = Family(
                "gauge", "lo_serving_bucket_device_seconds",
                "Attributed device seconds per (model, bucket).",
            )
            for key, doc in dt["buckets"].items():
                model, _, bucket = key.rpartition(":")
                bt.sample(doc["deviceTimeS"], model=model,
                          bucket=bucket)
                if "mfu" in doc:
                    bmfu.sample(doc["mfu"], model=model,
                                bucket=bucket)
            fams.append(bt)
            if bmfu.samples:
                fams.append(bmfu)
        return fams

    #: Route prefixes whose POST/PATCH enqueue engine jobs — the set
    #: per-tenant admission gates.  Serving routes (/serve/...) are
    #: deliberately absent: the batcher has its own QueueFull
    #: backpressure, and admin/observability mutations are not jobs.
    _JOB_ROUTE_PREFIXES = (
        "/dataset/", "/transform/", "/explore/", "/model/", "/train/",
        "/tune/", "/evaluate/", "/predict/", "/function/", "/builder/",
    )

    def _is_job_route(self, path: str) -> bool:
        prefix = self.config.api.api_prefix.rstrip("/")
        if prefix and path.startswith(prefix):
            path = path[len(prefix):]
        return path.startswith(self._JOB_ROUTE_PREFIXES)

    def handle(self, verb: str, path: str, body: dict, query: dict,
               idem_key: str | None = None,
               request_id: str | None = None,
               tenant: str | None = None):
        """Dispatch with the gateway budget enforced: request deadline
        (reference: krakend 10 s global timeout → 504), TTL response
        cache on opted-in GETs (300 s ``cache_ttl``), and per-route
        metrics (krakend's :8090 exporter → GET /metrics).

        ``idem_key`` (the X-Idempotency-Key header) makes a mutation
        replay-safe across store failover: a completed attempt's
        response is recorded in the store and handed back to retries
        instead of executing the handler twice.
        """
        import time as _time

        t0 = _time.perf_counter()
        if self._inflight is None:
            return self._handle_admitted(
                verb, path, body, query, t0, _Slot(None), idem_key,
                request_id, tenant,
            )
        if not self._inflight.acquire(blocking=False):
            # Saturated: shed load NOW rather than queue behind
            # max_inflight stuck handlers (a slow-loris of long POSTs
            # must not grow threads without bound).
            self._record_metric("saturated", 503, 0.0,
                                request_id=request_id)
            return 503, {
                "error": "gateway saturated "
                         f"({self.config.api.max_inflight} requests "
                         "in flight); retry with backoff"
            }
        return self._handle_admitted(
            verb, path, body, query, t0, _Slot(self._inflight),
            idem_key, request_id, tenant,
        )

    def _handle_admitted(self, verb, path, body, query, t0, slot,
                         idem_key=None, request_id=None, tenant=None):
        try:
            return self._handle_slotted(
                verb, path, body, query, t0, slot, idem_key,
                request_id, tenant,
            )
        finally:
            # The slot frees only when its LAST owner releases: for a
            # timed-out request the worker thread co-owns it, so an
            # abandoned handler keeps its slot until it really ends —
            # that's what keeps zombie threads BOUNDED by the cap.
            slot.release()

    def _handle_slotted(self, verb, path, body, query, t0, slot,
                        idem_key=None, request_id=None, tenant=None):
        import time as _time

        handler, m, route_key, flags = self.router.resolve(verb, path)
        if handler is None:
            status, payload = self.router.dispatch(verb, path, body, query)
            self._record_metric(
                route_key, status, (_time.perf_counter() - t0) * 1e3,
                request_id=request_id,
            )
            return status, payload

        # Per-tenant fair-share admission, checked at the gateway tier
        # BEFORE the handler runs: a rejected request must not leave an
        # orphan metadata document behind (the services write metadata
        # before submitting the job).
        if (
            self.ctx.admission is not None
            and verb in ("POST", "PATCH")
            and self._is_job_route(path)
        ):
            try:
                self.ctx.admission.check(tenant)
            except QuotaExceeded as exc:
                self._record_metric(
                    route_key, 429,
                    (_time.perf_counter() - t0) * 1e3,
                    request_id=request_id,
                )
                return 429, {
                    "error": str(exc),
                    "retryAfter": exc.retry_after_s,
                }

        ttl = self.config.api.cache_ttl_s
        cache_key = None
        if verb == "GET" and flags.get("cacheable") and ttl > 0:
            cache_key = (path, tuple(sorted(query.items())))
            with self._cache_lock:
                hit = self._cache.get(cache_key)
                if hit is not None and hit[0] > _time.monotonic():
                    self._record_metric(
                        route_key, hit[1],
                        (_time.perf_counter() - t0) * 1e3,
                        request_id=request_id,
                    )
                    return hit[1], hit[2]
        elif verb != "GET":
            # Any mutation invalidates the whole response cache — cheap
            # and safe (mutations are rare next to poll GETs).
            with self._cache_lock:
                self._cache.clear()

        idem_id = None
        if idem_key and verb in ("POST", "PATCH", "DELETE"):
            kind, *rest = self._idem_begin(
                idem_key,
                self._idem_fingerprint(verb, path, body, query),
            )
            if kind == "replay":
                status, payload = rest
                self._record_metric(
                    route_key, status,
                    (_time.perf_counter() - t0) * 1e3,
                    request_id=request_id,
                )
                return status, payload
            if kind == "mismatch":
                self._record_metric(
                    route_key, 422, (_time.perf_counter() - t0) * 1e3,
                    request_id=request_id,
                )
                return 422, {
                    "error": "this idempotency key was already used "
                             "for a different request — keys identify "
                             "ONE logical mutation; mint a fresh key "
                             "per operation",
                    "idempotency_key": idem_key,
                }
            if kind == "ambiguous":
                self._record_metric(
                    route_key, 409, (_time.perf_counter() - t0) * 1e3,
                    request_id=request_id,
                )
                return 409, {
                    "error": "a previous attempt with this "
                             "idempotency key began but has no "
                             "recorded outcome (still in flight, or "
                             "the primary died mid-request) — inspect "
                             "the artifact's state before retrying "
                             "with a fresh key",
                    "idempotency_key": idem_key,
                }
            idem_id = rest[0]

        def invoke():
            # Bind the request id INSIDE invoke: on the timeout path
            # the handler runs on a fresh worker thread, which does not
            # inherit the HTTP thread's context — binding here covers
            # both the inline and the threaded execution, so a job
            # submitted anywhere below carries the id into its trace.
            token = (
                obs_tracing.set_request_id(request_id)
                if request_id else None
            )
            try:
                # The tenant rides a contextvar for the same reason as
                # the request id: engine.submit() below stamps it onto
                # the job without every service signature changing.
                with bind_tenant(tenant):
                    result = self._handle_raw(handler, m, body, query)
            finally:
                if token is not None:
                    obs_tracing.reset_request_id(token)
            if idem_id is not None:
                self._idem_finish(idem_id, *result)
            return result

        timeout = self.config.api.request_timeout_s
        if flags.get("no_timeout") or timeout <= 0:
            status, payload = invoke()
        else:
            # Per-request thread (NOT a shared pool: N stuck handlers
            # must not poison a fixed pool into serving only 504s). The
            # abandoned thread finishes on its own; Python offers no
            # safe cancellation, so a timed-out mutation may still
            # commit later — same semantics as any gateway timeout.
            box: dict = {}

            def _run():
                try:
                    box["result"] = invoke()
                finally:
                    slot.release()  # holds the slot until REALLY done

            slot.share()  # worker co-owns; slot frees on LAST release
            worker = threading.Thread(
                target=_run, name="gateway-req", daemon=True
            )
            worker.start()
            worker.join(timeout)
            if "result" in box:
                status, payload = box["result"]
            else:
                status, payload = 504, {
                    "error": f"request exceeded {timeout}s gateway budget"
                }

        if cache_key is not None and status < 400:
            with self._cache_lock:
                self._cache[cache_key] = (
                    _time.monotonic() + ttl, status, payload
                )
        self._record_metric(
            route_key, status, (_time.perf_counter() - t0) * 1e3,
            request_id=request_id,
        )
        return status, payload

    def serve_forever(self, host: str | None = None, port: int | None = None):
        api = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            #: Client-supplied request ids must be header-safe and
            #: bounded; anything else gets a freshly minted id.
            _RID_RE = re.compile(r"[A-Za-z0-9_.\-]{1,64}")

            def _run(self, verb: str):
                # Request id: echo the client's X-Request-Id or mint
                # one — set BEFORE the drain check so even a 503
                # carries it.
                rid = (self.headers.get("X-Request-Id") or "").strip()
                if not self._RID_RE.fullmatch(rid):
                    rid = obs_tracing.new_request_id()
                self._request_id = rid
                if api._drain_if_shutting_down(self):
                    return
                parsed = urlparse(self.path)
                query = {
                    k: v[0] for k, v in parse_qs(parsed.query).items()
                }
                # Tenant identity for fair-share admission: same
                # header-safety rules as the request id, but a bad
                # value is a 400 (silently reassigning a tenant would
                # bill one tenant's jobs to another's quota).
                tenant = (self.headers.get("X-Tenant") or "").strip()
                if tenant and not self._RID_RE.fullmatch(tenant):
                    self._send(400, {
                        "error": "invalid X-Tenant header: expected "
                                 "1-64 chars of [A-Za-z0-9_.-]",
                    })
                    return
                body = {}
                length = int(self.headers.get("Content-Length") or 0)
                if length:
                    raw = self.rfile.read(length)
                    try:
                        body = json.loads(raw) if raw.strip() else {}
                    except json.JSONDecodeError:
                        self._send(400, {"error": "request body is not JSON"})
                        return
                status, payload = api.handle(
                    verb, parsed.path, body, query,
                    idem_key=self.headers.get("X-Idempotency-Key"),
                    request_id=rid,
                    tenant=tenant or None,
                )
                self._send(status, payload)

            def _send(self, status: int, payload):
                events = getattr(payload, "sse_events", None)
                if callable(events):
                    self._send_sse(status, payload, events)
                    return
                if (
                    isinstance(payload, tuple)
                    and len(payload) == 2
                    and isinstance(payload[1], (bytes, bytearray))
                ):
                    ctype, data = payload
                else:
                    ctype = "application/json"
                    data = json.dumps(payload, default=str).encode()
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                rid = getattr(self, "_request_id", None)
                if rid:
                    # Echoed on EVERY response (including errors): the
                    # correlation key across logs, metadata and the
                    # job's span tree.
                    self.send_header("X-Request-Id", rid)
                if status in (429, 503) and isinstance(payload, dict) \
                        and payload.get("retryAfter") is not None:
                    # Backpressure contract (serving queue overflow,
                    # chip-lease timeout): clients honor the standard
                    # header, the JSON field carries the same value
                    # for non-HTTP consumers.
                    self.send_header(
                        "Retry-After", str(payload["retryAfter"])
                    )
                self.end_headers()
                self.wfile.write(data)

            def _send_sse(self, status: int, stream, events):
                """Server-sent-events body for a DecodeStream payload.
                No Content-Length is possible (the token count is not
                known up front), so under HTTP/1.1 the body is
                EOF-delimited: ``Connection: close`` and the handler
                drops keep-alive for this socket.  A broken pipe mid-
                stream IS the client-disconnect signal — it aborts the
                stream so the engine frees its KV pages at the next
                step boundary."""
                self.close_connection = True
                self.send_response(status)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-store")
                self.send_header("Connection", "close")
                rid = getattr(self, "_request_id", None)
                if rid:
                    self.send_header("X-Request-Id", rid)
                self.end_headers()
                try:
                    for name, doc in events():
                        chunk = (
                            f"event: {name}\n"
                            f"data: {json.dumps(doc, default=str)}\n\n"
                        )
                        self.wfile.write(chunk.encode())
                        self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError,
                        OSError):
                    abort = getattr(stream, "abort", None)
                    if callable(abort):
                        abort("client disconnected")

            def do_GET(self):
                self._run("GET")

            def do_POST(self):
                self._run("POST")

            def do_PATCH(self):
                self._run("PATCH")

            def do_DELETE(self):
                self._run("DELETE")

        host = host or self.config.api.host
        port = self.config.api.port if port is None else port
        httpd = _BoundedThreadingHTTPServer(
            (host, port), Handler,
            max_connections=self.config.api.max_connections,
        )
        # Publish under the shutdown lock: serve_forever runs on a
        # daemon thread (start_background), so a shutdown() racing
        # this construction window would otherwise read _httpd as
        # None, "stop" nothing, and leak a live accept loop — the
        # exact stale-primary window the fence demotion closes.
        with self._shutdown_lock:
            if self._shut_down:
                httpd.server_close()
                return
            self._httpd = httpd
        self._start_fence_watch()
        try:
            httpd.serve_forever()
        except Exception:
            # shutdown() can claim and close the listener between the
            # publish above and serve_forever() entering its poll loop
            # — the serve call then trips on the closed socket.  That
            # interleaving is a clean stop, not an error.
            with self._shutdown_lock:
                if self._shut_down:
                    return
            raise

    #: Seconds between fence checks (tests shrink it).
    FENCE_CHECK_INTERVAL_S = 5.0

    def _drain_if_shutting_down(self, handler) -> bool:
        """503+Connection:close for requests arriving on kept-alive
        connections after shutdown/demotion — the accept loop is gone,
        but HTTP/1.1 persistent connections would otherwise keep being
        served by their handler threads (the split-brain window the
        fence demotion exists to close)."""
        if not self._shutting_down.is_set():
            return False
        handler.close_connection = True
        handler._send(503, {"error": "server is shutting down"})
        return True

    def _start_fence_watch(self) -> None:
        """Self-demote if a standby fences this store while we serve.

        serve() refuses to START on a fenced store, but a RUNNING
        primary can be fenced underneath itself: a network partition
        makes the standby declare us dead and promote; when the
        partition heals, clients that never lost their connection
        would keep writing HERE while new ones write to the promoted
        replica — the split-brain the fence exists to prevent.  On a
        shared filesystem (where the fence write succeeds) the demoted
        primary notices within one check interval and stops serving;
        the supervisor's restart then hits serve()'s startup refusal.
        Without shared storage the same watch polls the HA peer's
        /replication/status: a peer serving a HIGHER election epoch
        promoted over us — self-fence and demote (store/ha.py).
        """
        from learningorchestra_tpu.store.ha import is_fenced

        store_root = self.config.store.store_path()
        peer = self.config.ha.peer

        def watch():
            # wait() doubles as the sleep AND the exit signal: a
            # normal shutdown ends the thread promptly instead of
            # leaking one fence-poller per serve cycle.
            while not self._shutting_down.wait(
                self.FENCE_CHECK_INTERVAL_S
            ):
                fence = is_fenced(store_root)
                if fence is None and peer:
                    fence = _peer_supersedes(store_root, peer)
                if fence is not None:
                    print(
                        "store fenced while serving (promoted_to="
                        f"{fence.get('promoted_to')!r}) — demoting: "
                        "shutting down to prevent split-brain",
                        flush=True,
                    )
                    self.shutdown()
                    return

        threading.Thread(target=watch, daemon=True).start()

    def start_background(self, host: str = "127.0.0.1",
                         port: int | None = None) -> int:
        """Start on a daemon thread; returns the bound port (None/0 picks
        an ephemeral one)."""
        if port in (None, 0):
            import socket

            sock = socket.socket()
            sock.bind((host, 0))
            port = sock.getsockname()[1]
            sock.close()
        self._port = port
        threading.Thread(
            target=lambda: self.serve_forever(host=host, port=port),
            daemon=True,
        ).start()
        # Wait until the socket accepts.
        import socket as _socket
        import time as _time

        deadline = _time.time() + 10
        while _time.time() < deadline:
            try:
                with _socket.create_connection((host, port), timeout=0.2):
                    break
            except OSError:
                _time.sleep(0.02)
        return port

    def shutdown(self) -> None:
        """Idempotent stop: accept loop halted, LISTENING SOCKET
        CLOSED (reconnecting clients get an immediate refusal — what
        triggers their failover retry — instead of hanging in the
        kernel backlog), kept-alive connections answered 503+close by
        the dispatch gate, resources released once."""
        with self._shutdown_lock:
            if self._shut_down:
                return
            self._shut_down = True
            # Claim the listener under the same lock serve_forever
            # publishes it with: a shutdown racing the daemon-thread
            # construction either sees the httpd (and stops it) or
            # flips _shut_down first (and serve_forever refuses to
            # serve) — never a leaked accept loop.
            httpd, self._httpd = self._httpd, None
        self._shutting_down.set()
        # The registry outlives this server (process-global): drop the
        # collector so scrapes never touch a closed context.
        if self._obs_registry is not None:
            self._obs_registry.remove_collector(self._collect_families)
        # Stop the rollup/SLO clock: a demoted or stopped node must
        # not keep evaluating objectives over frozen windows (or
        # paging a webhook).  The singleton survives — a later
        # APIServer's construction re-arms the daemon.
        self.rollup.stop()
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        self.profiler.close()
        self.serving.close()
        self.monitoring.close()
        self.ctx.close()


def _peer_supersedes(store_root, peer: str) -> dict | None:
    """Did the HA peer promote over this store?  Returns the fence
    record (after writing it locally, best-effort) when the peer is a
    primary serving a STRICTLY HIGHER election epoch, else None.

    This is the no-shared-disk half of fencing: the standby couldn't
    write our marker and the fence POST hit a dead process, so the
    epoch comparison is what stops the stale side.  An unreachable
    peer means "not superseded"; so does a peer answering
    ``role="standby"`` — a MONITORING standby serves its status route
    pre-promotion (store/ha.py _start_standby_status), which is why
    the check below requires ``role == "primary"``, not merely a
    response.
    """
    from learningorchestra_tpu.store.ha import peer_status
    from learningorchestra_tpu.store.replica import (
        FENCE_FILE,
        read_epoch,
    )

    status = peer_status(peer)
    if (
        status is None
        or status.get("role") != "primary"
        or int(status.get("epoch", 0)) <= read_epoch(store_root)
    ):
        return None
    fence = {
        "promoted_to": peer,
        "epoch": status.get("epoch"),
        "reason": "peer holds higher election epoch",
    }
    try:
        # Durable self-fence: the supervisor's restart refuses at
        # startup without another peer round-trip.
        store_root.mkdir(parents=True, exist_ok=True)
        (store_root / FENCE_FILE).write_text(json.dumps(fence))
    except OSError:
        pass
    return fence


def serve(config: Config | None = None) -> None:
    from learningorchestra_tpu.store.ha import (
        is_fenced,
        promotion_record,
        run_standby,
    )

    from pathlib import Path as _Path

    config = config or get_config()
    store_root = config.store.store_path()
    rejoin_root = _Path(str(store_root) + ".rejoined")

    def standby_of(target: str) -> None:
        # The ONE run_standby parameterization every rejoin path uses.
        # With a promotion record in rejoin_root this short-circuits
        # into resuming as primary; otherwise it monitors `target`
        # with the conservative rejoin takeover window (ha.rejoin_*:
        # an ordinary partner restart must never get fenced out).
        run_standby(
            target, None, rejoin_root, config.api.port,
            host=config.api.host,
            check_interval=config.ha.rejoin_interval_s,
            max_misses=config.ha.rejoin_misses,
        )

    def archive_stale_rejoin(reason: str) -> bool:
        # A stale .rejoined directory must move ASIDE, not merely be
        # ignored: run_standby treats a leftover .promoted record in
        # the replica root as "resume as primary", so a later rejoin
        # flow reusing the root would serve the stale history the
        # moment the real primary was unreachable.  Never delete —
        # the bytes stay for the operator.
        dst = rejoin_root.with_name(rejoin_root.name + ".stale")
        n = 0
        while dst.exists():
            n += 1
            dst = rejoin_root.with_name(f"{rejoin_root.name}.stale{n}")
        try:
            rejoin_root.rename(dst)
        except OSError as exc:
            print(
                f"stale rejoin replica {rejoin_root} ({reason}) could "
                f"not be archived ({exc}) — refusing to serve rather "
                "than risk resuming from it; move the directory away "
                "and restart.",
                flush=True,
            )
            return False
        print(
            f"archived stale rejoin replica to {dst} ({reason})",
            flush=True,
        )
        return True

    # A previous auto-rejoin cycle may already have PROMOTED this node
    # back to primary (partner died after we rejoined): the rejoined
    # replica — not the long-fenced original store — is then the
    # system of record, and a supervisor restart must resume serving
    # it, never re-stand-by for a dead partner.
    rejoin_rec = (
        promotion_record(rejoin_root) if config.ha.auto_rejoin else None
    )
    fence = is_fenced(store_root)
    if rejoin_rec:
        from learningorchestra_tpu.store.replica import read_epoch

        rejoin_epoch = read_epoch(rejoin_root)
        try:
            fence_epoch = int((fence or {}).get("epoch"))
        except (TypeError, ValueError):
            # Unreadable/malformed fence record: SOMEONE fenced the
            # store at an unknown epoch.  Every other is_fenced
            # consumer fails safe on this sentinel — so does the
            # comparison below (unknown ≠ "old").
            fence_epoch = None
        # The rejoin replica only shadows the original store while it
        # holds the HIGHEST election epoch this node knows of.  Two
        # ways it can be stale: an operator restored the original
        # store as system of record (fence cleared, epoch caught up),
        # or a LATER promotion fenced the original at an epoch beyond
        # the rejoin promotion's — either way resuming from the
        # replica would serve superseded history.
        if fence is None and read_epoch(store_root) >= rejoin_epoch:
            if not archive_stale_rejoin(
                "original store restored as system of record at an "
                "equal-or-higher epoch"
            ):
                return
        elif fence is not None and (
            fence_epoch is None or fence_epoch >= rejoin_epoch
        ):
            if not archive_stale_rejoin(
                "a later promotion fenced the original store at "
                + (
                    f"epoch {fence_epoch}, past"
                    if fence_epoch is not None
                    else "an UNKNOWN epoch (unreadable fence record — "
                         "failing safe), possibly past"
                )
                + f" the rejoin epoch {rejoin_epoch}"
            ):
                return
        else:
            print(
                "resuming as primary from the promoted rejoin replica "
                f"{rejoin_root}", flush=True,
            )
            standby_of(
                config.ha.peer or rejoin_rec.get("old_primary")
                or "127.0.0.1:0"
            )
            return

    if fence is None and config.ha.peer:
        fence = _peer_supersedes(store_root, config.ha.peer)
    if fence is not None:
        # A standby promoted itself over this store: serving from it
        # now would split-brain the cluster.
        new_primary = fence.get("promoted_to") or config.ha.peer
        if config.ha.auto_rejoin and new_primary:
            # Mongo's stepped-down primary rejoins as a SECONDARY on
            # its own: become the new primary's standby, shipping its
            # WALs over the network into a fresh replica root — the
            # pair regains redundancy with no operator action, and if
            # the new primary later dies, THIS node promotes and
            # serves on its original address again.
            print(
                "store is fenced — auto-rejoining as a standby of "
                f"{new_primary} (replica: {rejoin_root})",
                flush=True,
            )
            standby_of(new_primary)
            return
        # Exit CLEANLY so the supervisor's restart-on-failure loop
        # ends instead of resurrecting a fenced primary (store/ha.py).
        hint = (
            "auto-rejoin is ON but no rejoin target could be "
            "determined (unreadable fence marker and no LO_HA_PEER) — "
            "fix the pairing or re-join manually."
            if config.ha.auto_rejoin
            else "Re-join by running this node as a standby of the "
                 "new primary, or set LO_HA_AUTO_REJOIN=1 to do this "
                 "automatically."
        )
        print(
            "store is fenced — a standby promoted itself to "
            f"{fence.get('promoted_to') or 'a new primary'}; refusing "
            f"to serve. {hint}",
            flush=True,
        )
        return
    APIServer(config).serve_forever()


if __name__ == "__main__":
    serve()


def _int_param(query: dict, key: str, default: int) -> int:
    try:
        return int(query.get(key, default))
    except (TypeError, ValueError):
        raise BadRequest(f"{key} must be an integer")
