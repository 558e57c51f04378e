"""Shared service context: store + volumes + job engine + artifact loader.

Also defines the request-validation exceptions the API layer maps onto the
reference's status codes (409 duplicate, 404 missing, 406 semantic errors —
reference: microservices/binary_executor_image/server.py:332-398).
"""

from __future__ import annotations

from typing import Any

import pandas as pd

from learningorchestra_tpu.config import (
    DEFAULT_XLA_CACHE_DIR,
    Config,
    get_config,
)
from learningorchestra_tpu.jobs import JobEngine
from learningorchestra_tpu.log import get_logger, kv
from learningorchestra_tpu.store import (
    ArtifactStore,
    VolumeStorage,
    open_document_store,
)


import re

# Same shape the document store enforces (document_store._NAME_RE):
# first char word-like, no separators — '..' and '/x' can never match.
_ARTIFACT_NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]*")


class ValidationError(Exception):
    """Semantic request error → HTTP 406 (reference's NOT_ACCEPTABLE)."""


class NotFoundError(Exception):
    """Missing artifact → HTTP 404."""


class ConflictError(Exception):
    """Duplicate artifact name → HTTP 409."""


class ServiceContext:
    def __init__(self, config: Config | None = None):
        self.config = config or get_config()
        self.documents = open_document_store(
            self.config.store.store_path(),
            durable_writes=self.config.store.durable_writes,
            backend=self.config.store.backend,
        )
        self.artifacts = ArtifactStore(self.documents)
        self.volumes = VolumeStorage(self.config.store.volume_path())
        self.engine = JobEngine(
            self.artifacts,
            max_workers=self.config.jobs.max_workers,
            max_preemption_retries=(
                self.config.jobs.max_preemption_retries
            ),
            class_weights=self.config.jobs.class_weights,
            retry_backoff_s=self.config.jobs.retry_backoff_s,
            retry_backoff_max_s=self.config.jobs.retry_backoff_max_s,
            deadline_s=self.config.jobs.deadline_s,
            shutdown_drain_s=self.config.jobs.shutdown_drain_s,
        )
        self.loader = StoreLoader(self)
        from learningorchestra_tpu.services.webhooks import (
            WebhookNotifier,
        )

        # Observe PUSH path: job completion fires registered webhooks
        # (the reference's pub/sub Observe shape, README.md:71).
        self.webhooks = WebhookNotifier(self.documents)
        self.engine.notifier = self.webhooks
        from learningorchestra_tpu.jobs.leases import DeviceLeaser

        # Per-job accelerator placement (jobs/leases.py): concurrent
        # neural jobs serialize per chip instead of contending for HBM.
        # The engine's deadline watchdog revokes an expired job's
        # leases through the same pool.
        self.leaser = DeviceLeaser()
        self.engine.leaser = self.leaser
        # When the compiled-program cache clears on a device-set change
        # (TPU runtime restart), the engine's warm-start
        # hints are stale — 'warm' jobs would trace like any other.
        # Weakly bound: short-lived contexts (tests) must not pin dead
        # engines through the process-global cache.
        import weakref

        from learningorchestra_tpu.train import compile_cache

        engine_ref = weakref.ref(self.engine)

        def _drop_warm_hints():
            engine = engine_ref()
            if engine is not None:
                engine.clear_warm_keys()

        # Keep the handle so close() can deregister — the cache is
        # process-global and must not accumulate dead listeners across
        # short-lived contexts.
        self._warm_hint_listener = _drop_warm_hints
        compile_cache.get_cache().add_invalidation_listener(
            _drop_warm_hints
        )
        # Artifact-change fan-out: anything holding derived state keyed
        # by artifact name (the serving registry's device-resident
        # params, serve/registry.py) subscribes here; delete and
        # binary-overwrite paths notify so stale state is dropped
        # before the next read.
        self._artifact_change_listeners: list = []
        # Scale-out control plane (jobs/cluster.py): when enabled, N
        # engine processes over ONE store root share dispatch through
        # the store-backed claim table.  Constructed BEFORE the
        # journal so epoch minting runs under the cluster's
        # cross-process lock (two engines booting concurrently must
        # mint distinct epochs).  Requires the python store backend —
        # the native backend has no WAL-refresh coherence primitive,
        # so clustering is LOUDLY disabled rather than silently
        # incoherent.
        self.cluster = None
        self.admission = None
        if self.config.cluster.enabled:
            if not hasattr(self.documents, "refresh"):
                get_logger("context").error(
                    "LO_TPU_CLUSTER_ENABLED requires the python "
                    "store backend (LO_TPU_STORE_BACKEND=python): "
                    "the native backend has no WAL-refresh coherence "
                    "primitive — clustering DISABLED for this process"
                )
            else:
                from learningorchestra_tpu.jobs.cluster import (
                    ClusterCoordinator,
                )

                self.cluster = ClusterCoordinator(
                    self.documents,
                    self.config.store.store_path(),
                    engine_id=self.config.cluster.engine_id,
                    heartbeat_s=self.config.cluster.heartbeat_s,
                    ttl_s=self.config.cluster.ttl_s,
                    sweep_s=self.config.cluster.sweep_s,
                )
        # Per-tenant fair-share admission: constructed whenever a
        # quota is configured; store-backed counters when clustered so
        # every engine enforces identically.
        if (
            self.config.tenant.max_queued > 0
            or self.config.tenant.max_running > 0
        ):
            from learningorchestra_tpu.jobs.cluster import (
                TenantAdmission,
            )

            self.admission = TenantAdmission(
                max_queued=self.config.tenant.max_queued,
                max_running=self.config.tenant.max_running,
                retry_after_s=self.config.tenant.retry_after_s,
                cluster=self.cluster,
            )
        self.engine.admission = self.admission
        # Crash-durable job journal + engine-epoch fencing
        # (jobs/journal.py): construction mints this boot's engine
        # epoch, so any straggler from a previous life is refused at
        # its terminal commit.  The engine appends every transition
        # through it.
        from learningorchestra_tpu.jobs.journal import JobJournal

        self.journal = JobJournal(
            self.documents,
            self.config.store.store_path(),
            enabled=self.config.jobs.journal,
            max_records=self.config.jobs.journal_max_records,
            epoch_lock=(
                (lambda: self.cluster._guard(refresh=()))
                if self.cluster is not None else None
            ),
        )
        self.engine.journal = (
            self.journal if self.journal.enabled else None
        )
        if self.cluster is not None:
            # Wire the plane together: the coordinator publishes this
            # boot's epoch on every claim; the journal's fence
            # delegates to claim ownership and its appends/replays run
            # under the cross-process guard; the engine claims before
            # every dispatch.  join() starts heartbeat + sweep.
            self.cluster.epoch = self.journal.epoch
            self.cluster.on_steal = self._cluster_steal
            self.cluster.on_engine_dead = self._cluster_engine_dead
            if self.journal.enabled:
                self.journal.cluster = self.cluster
                self.journal.exclusive = self.cluster.journal_guard
            self.engine.cluster = self.cluster
            self.cluster.join()
        # Backend init FIRST: recovery may re-dispatch train fits,
        # and job threads racing first-time backend init deadlock
        # inside jax (the race _init_backend exists to remove).
        self._init_backend()
        if self.cluster is not None:
            with self.cluster.journal_guard():
                self.journal.prune()
        else:
            self.journal.prune()
        self._recover_jobs()
        # Durable warm start: restore the persisted AOT hot set into
        # the compile cache on a background thread, so recovered fits
        # and the first post-deploy requests hit warm executables
        # instead of re-tracing (ROADMAP item 3).
        self._aot_prewarm_thread = None
        self._start_aot_prewarm()

    def add_artifact_change_listener(self, listener) -> None:
        """Register ``listener(name)`` to fire when an artifact's
        binary or metadata is replaced or deleted.  Listeners must be
        fast and must not raise (exceptions are swallowed — a broken
        subscriber must not fail a delete)."""
        self._artifact_change_listeners.append(listener)

    def notify_artifact_changed(self, name: str) -> None:
        for listener in self._artifact_change_listeners:
            try:
                listener(name)
            except Exception:  # noqa: BLE001 — never fail the mutation
                pass

    def _recover_jobs(self) -> None:
        """Boot-time restart recovery over the job journal.

        Any pending/running jobState at startup belonged to a DEAD
        process — this process hasn't run a job yet.  Left alone it
        wedges the artifact forever: the job will never finish, and
        ``require_not_running`` would 409 every PATCH re-run.  Matters
        most after store failover, where the promoted standby inherits
        the killed primary's in-flight states (and its journal)
        through the shipped WAL.

        With the journal enabled and ``jobs.journal_recover`` on,
        journaled jobs whose bodies are re-dispatchable are RESUBMITTED
        through the existing PATCH machinery, in their pre-crash queue
        order: train fits resume from their newest managed checkpoint
        (services/executor.py's resume path), distributed fits through
        ``update_train``.  Everything else — and every job when
        recovery is off — is terminally failed with an explicit
        ``orphaned-by-restart`` reason instead of leaving phantom
        "running" metadata; jobs with NO journal record (stores that
        predate the journal, or a disabled journal) keep the legacy
        interrupted-re-flag message.  The reference re-flags
        unfinished work at service startup
        (data_type_handler_image/data_type_update.py:47-59); this
        resolves it into automatic resumption.
        """
        journaled = (
            self.journal.replay() if self.journal.enabled else {}
        )
        recover = (
            self.journal.enabled and self.config.jobs.journal_recover
        )
        interrupted: list[tuple] = []
        for name in self.documents.list_collections():
            if name.startswith("_"):
                continue  # internal ledgers/journal have no jobs
            try:
                meta = self.artifacts.metadata.read(name)
            except Exception:
                continue
            if not meta or meta.get("jobState") not in (
                "pending", "running"
            ):
                continue
            if (
                self.cluster is not None
                and not self.cluster.claimable(name)
            ):
                # A LIVE peer engine holds this job's claim: the job
                # is running over there, not orphaned here — adopting
                # it would be the double-run.  If that peer dies, the
                # sweep steals the claim and resumes it then.
                continue
            rec = journaled.get(name)
            # Re-enqueue order = pre-crash queue admission order (the
            # journal's latest `queued` sequence number); journal-less
            # jobs go last, name-ordered for determinism.
            seq = (
                rec["seq"] if rec and rec["seq"] >= 0
                else float("inf")
            )
            interrupted.append((seq, name, meta, rec))
        interrupted.sort(key=lambda t: (t[0], t[1]))
        log = get_logger("context")
        for _seq, name, meta, rec in interrupted:
            kind = (
                self._recoverable_kind(meta)
                # A journal-terminal record under non-terminal
                # metadata means the job's life ENDED (refused
                # submission, or a crash between the journal append
                # and the metadata commit) — orphan it, don't
                # resurrect it.
                if recover and rec is not None
                and not rec.get("terminal")
                else None
            )
            if kind is None:
                self._orphan_job(name, journaled=rec is not None)
                continue
            try:
                self._redispatch(name, kind, rec.get("spec") or {})
                log.warning(
                    f"recovered job {name!r} from the journal "
                    f"(epoch {self.journal.epoch}): re-dispatched "
                    "through the checkpoint-resume path"
                )
            except Exception as exc:  # noqa: BLE001 — recovery must
                # finish: one unrecoverable job (deleted parent, bad
                # spec) must not wedge the whole boot.
                log.error(
                    f"could not re-dispatch recovered job {name!r}: "
                    f"{exc!r} — failing it orphaned-by-restart"
                )
                self._orphan_job(
                    name, journaled=True, detail=repr(exc)
                )

    @staticmethod
    def _recoverable_kind(meta: dict) -> str | None:
        """How a journaled job can be re-dispatched, or None.

        Executor-family artifacts re-run through the PATCH path with
        their last recorded parameters; tune grids are excluded (a
        grid re-submission is not expressible through the generic
        PATCH — their trials resume only across in-engine preemption
        retries) and so is anything without a parent/method spec
        (functions, models: their bodies are not derivable from
        metadata alone)."""
        if meta.get("distributed"):
            return "distributed"
        kind = str(meta.get("type", ""))
        if (
            kind.startswith(("train/", "evaluate/", "predict/"))
            and meta.get("parentName")
            and meta.get("method")
        ):
            return "executor"
        return None

    def _redispatch(self, name: str, kind: str, spec: dict) -> None:
        """Resubmit a recovered job through the existing PATCH
        machinery, carrying the journaled submit spec forward (a job
        submitted with a deadline must resume under it, not under the
        engine default).  Marking it failed FIRST is what routes a
        train fit into the checkpoint-resume path (update() resumes
        failed jobs from their newest managed checkpoint instead of
        epoch 0)."""
        self.artifacts.metadata.mark_failed(
            name,
            "orphaned-by-restart: re-dispatching from the job journal",
        )
        description = spec.get("description") or ""
        if kind == "distributed":
            from learningorchestra_tpu.services.distributed_exec import (
                DistributedExecutorService,
            )

            DistributedExecutorService(self, None).update_train(
                name, description=description
            )
        else:
            from learningorchestra_tpu.services.executor import (
                ExecutorService,
            )

            ExecutorService(self).update(
                name,
                description=description,
                deadline_s=spec.get("deadlineS"),
            )

    def _orphan_job(self, name: str, *, journaled: bool,
                    detail: str | None = None) -> None:
        """Terminally fail an interrupted job that cannot (or must
        not) be re-dispatched — never leave phantom 'running'
        metadata."""
        if journaled:
            reason = (
                "orphaned-by-restart: the orchestrator died while "
                "this job was queued or running and its body is not "
                "automatically re-dispatchable"
                + (f" ({detail})" if detail else "")
                + "; re-run it with a PATCH (bare PATCH re-uses the "
                "last recorded parameters)"
            )
        else:
            reason = (
                "job interrupted by a server restart or store "
                "failover before completing; re-run it with a "
                "PATCH (bare PATCH re-uses the last recorded "
                "parameters)"
            )
        self.artifacts.metadata.mark_failed(name, reason)
        if journaled:
            self.journal.append(
                "failed", name, reason="orphaned-by-restart"
            )
        get_logger("context").warning(
            f"re-flagged interrupted job {name!r} "
            "(was mid-run when the previous process died)"
        )
        # Subscribers must see the terminal transition: the
        # observe event feed + any registered webhooks fire
        # exactly as the engine's own failure path would
        # (jobs/engine.py _notify) — a watcher of the dead
        # job would otherwise wait forever.
        try:
            self.webhooks.notify(
                name, "failed",
                self.artifacts.metadata.read(name) or {},
            )
        except Exception:  # noqa: BLE001 — startup must finish
            pass

    def _cluster_steal(self, job: str, prev_engine: str) -> None:
        """Sweep callback: this engine now owns a claim stolen from a
        dead (or partitioned) peer.  Re-read the job's TRUE state from
        the shared store and either close it out (the peer finished it
        before dying) or resume it through the same checkpoint-resume
        machinery boot recovery uses.  The stolen claim stays ours
        across the re-dispatch (the dispatch-time claim() renews it),
        so a revived straggler is fenced at its terminal commit."""
        log = get_logger("context")
        try:
            if hasattr(self.documents, "refresh"):
                # The dead peer's process wrote this job's collection;
                # fold its WAL tail into our in-memory view first.
                self.documents.refresh(job)
            replayed = self.journal.replay()
            rec = replayed.get(job)
            if rec is not None and rec.get("terminal"):
                # Finished/failed before the peer died — release the
                # claim (its doneAt supersedes stale queue entries)
                # and touch nothing.
                self.cluster.release(job)
                return
            meta = self.artifacts.metadata.read(job)
            if meta is None:
                self.cluster.release(job)
                return
            kind = self._recoverable_kind(meta)
            if kind is None:
                self._orphan_job(job, journaled=rec is not None)
                self.cluster.release(job)
                return
            self._redispatch(job, kind, (rec or {}).get("spec") or {})
            log.warning(
                f"stole job {job!r} from engine {prev_engine!r} "
                f"(epoch {self.journal.epoch}): re-dispatched "
                "through the checkpoint-resume path"
            )
        except Exception as exc:  # noqa: BLE001 — one bad adoption
            # must not kill the sweep loop.
            log.error(
                f"could not adopt stolen job {job!r}: {exc!r} — "
                "failing it orphaned-by-restart"
            )
            try:
                self._orphan_job(job, journaled=True,
                                 detail=repr(exc))
                self.cluster.release(job)
            except Exception:  # noqa: BLE001
                pass

    def _cluster_engine_dead(self, engine_id: str, epoch: int) -> None:
        """Sweep callback: a peer engine's membership expired.  Its
        RUNNING jobs are adopted by the steal path (they hold claims);
        this adopts its QUEUED-but-never-claimed jobs — journaled
        under the dead epoch, non-terminal, no live claim — in
        pre-crash queue order.  A racing duplicate (the 'dead' engine
        was only partitioned and still dispatches its copy) is safe:
        both race the dispatch-time claim CAS and exactly one runs."""
        log = get_logger("context")
        try:
            replayed = self.journal.replay()
        except Exception:  # noqa: BLE001
            return
        work = sorted(
            (
                (rec.get("seq", -1), job, rec)
                for job, rec in replayed.items()
                if rec.get("epoch") == epoch
                and not rec.get("terminal")
                and rec.get("state") in ("submitted", "queued")
            ),
            key=lambda t: (t[0], t[1]),
        )
        for _seq, job, rec in work:
            if not self.cluster.claimable(job):
                continue
            try:
                if hasattr(self.documents, "refresh"):
                    self.documents.refresh(job)
                meta = self.artifacts.metadata.read(job)
                kind = (
                    self._recoverable_kind(meta)
                    if meta is not None else None
                )
                if kind is None:
                    if meta is not None and meta.get("jobState") in (
                        "pending", "running"
                    ):
                        self._orphan_job(job, journaled=True)
                    continue
                self._redispatch(job, kind, rec.get("spec") or {})
                log.warning(
                    f"adopted queued job {job!r} from dead engine "
                    f"{engine_id!r} (epoch {epoch})"
                )
            except Exception as exc:  # noqa: BLE001
                log.error(
                    f"could not adopt queued job {job!r} from dead "
                    f"engine {engine_id!r}: {exc!r}"
                )

    def require_current_epoch(self) -> None:
        """Epoch fence at artifact-publication time: a job body from a
        stale engine epoch (pre-crash straggler, or a partitioned
        duplicate orchestrator once the control plane goes
        multi-process) raises :class:`~learningorchestra_tpu.jobs.
        journal.StaleEpochError` here instead of double-publishing.
        No-op outside an engine dispatch."""
        self.journal.fence_check()

    def _start_aot_prewarm(self) -> None:
        """Kick off the boot pre-warm when the durable AOT store is on
        (``LO_TPU_AOT_ENABLED`` + ``LO_TPU_AOT_PREWARM``) and has a
        manifest to walk.  Background by design: restoring executables
        costs device-time seconds and must not gate readiness — the
        API comes up immediately; programs not yet restored simply
        build live as before."""
        from learningorchestra_tpu.train import aot_store, compile_cache

        try:
            if not (
                aot_store.enabled()
                and self.config.aot.prewarm
                and compile_cache.enabled()
            ):
                return
            store = aot_store.get_store()
            work = store.manifest_entries() if store is not None else []
        except Exception:  # noqa: BLE001 — warm start is best-effort
            return
        if not work:
            return
        import threading

        self._aot_prewarm_thread = threading.Thread(
            target=self._aot_prewarm, args=(store, work),
            name="aot-prewarm", daemon=True,
        )
        self._aot_prewarm_thread.start()

    def _aot_prewarm(self, store, work: list[dict]) -> None:
        """Walk the manifest hottest-first, deserializing each blob and
        installing the restored executable into the compile cache.
        Every restore is a span on a dedicated boot trace
        (``boot.prewarm`` — the trace surfaces in logs; per-key
        failures degrade to live builds, never crash the boot)."""
        import time

        from learningorchestra_tpu.obs import tracing
        from learningorchestra_tpu.train import compile_cache

        cache = compile_cache.get_cache()
        trace = tracing.new_trace("boot.prewarm")
        warmed = skipped = failed = 0
        t0 = time.perf_counter()
        with tracing.activate(trace):
            for rec in work:
                key = rec.get("key")
                if not key or cache.contains(key):
                    skipped += 1
                    continue
                label = rec.get("label")
                try:
                    with tracing.span(
                        "prewarm", key=key[:12], label=label or "",
                    ):
                        compiled = store.load(key)
                        if compiled is None:
                            failed += 1
                            continue
                        ok = cache.install(
                            key,
                            compile_cache._AOTRestored(
                                compiled, None, key, label
                            ),
                            label=label,
                            nbytes=rec.get("bytes"),
                        )
                    warmed += 1 if ok else 0
                except Exception:  # noqa: BLE001 — a bad blob costs
                    failed += 1    # one key, not the boot
        get_logger("services").info(kv(
            event="aot_prewarm_done", warmed=warmed, skipped=skipped,
            failed=failed, total=len(work),
            seconds=round(time.perf_counter() - t0, 3),
        ))

    def _init_backend(self) -> None:
        """Eagerly initialize the JAX backend on the main thread.

        Two job threads racing first-time backend init deadlock inside
        jax's backend registry (observed with concurrent fits on worker
        threads); paying init once at service startup removes the race
        and also front-loads the TPU client handshake out of the first
        job's latency.  A backend that cannot initialize fails the boot.

        The persistent compilation cache lets a re-submitted job (or a
        restarted server) skip the TPU compile.  Where
        ``JAX_COMPILATION_CACHE_DIR`` is set jax already uses it and
        nothing is set here; otherwise the cache goes to one fixed path
        in the checkout (``config.DEFAULT_XLA_CACHE_DIR`` — the path is
        part of the cache key, so it must not move between runs)."""
        import os

        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update(
                "jax_compilation_cache_dir", str(DEFAULT_XLA_CACHE_DIR)
            )
        devices = jax.devices()
        # Logged once per boot: with JAX_PLATFORMS unset jax drops to
        # the CPU when the TPU client cannot start, and nothing else on
        # the job path would say so.
        get_logger("context").info(kv(
            event="backend",
            platform=devices[0].platform,
            kind=devices[0].device_kind,
            count=len(devices),
            xlaCacheDir=jax.config.jax_compilation_cache_dir,
        ))

    def close(self) -> None:
        from learningorchestra_tpu.train import compile_cache

        compile_cache.get_cache().remove_invalidation_listener(
            getattr(self, "_warm_hint_listener", None)
        )
        # Bounded wait for an in-flight boot pre-warm (daemon thread):
        # installs racing a closing process are harmless — the compile
        # cache is process-global — but a short join keeps test
        # teardown deterministic.
        thread = getattr(self, "_aot_prewarm_thread", None)
        if thread is not None and thread.is_alive():
            thread.join(timeout=5.0)
        # With a drain budget configured (LO_TPU_JOB_DRAIN_S — both
        # deploy manifests set one) the graceful path WAITS, bounded:
        # running bodies get their cancel tokens flipped past the
        # budget and stragglers are abandoned after a grace.  Without
        # one, keep the legacy non-blocking close (never hang a
        # SIGTERM on an unbounded drain).
        self.engine.shutdown(
            wait=self.config.jobs.shutdown_drain_s > 0
        )
        # Journal AFTER the engine (shutdown journals its cancelled
        # drops), BEFORE the store (a drain into closed WAL handles
        # would drop every record).  The cluster leaves after the
        # journal's final drain (its guard serializes that drain) and
        # before the store closes (retracting the membership document
        # is a store write).
        self.journal.close()
        if self.cluster is not None:
            self.cluster.close()
        self.documents.close()

    # -- validation helpers shared by services --------------------------------

    def require_new_name(self, name: str) -> None:
        if not name or not isinstance(name, str):
            raise ValidationError("missing or invalid 'name'")
        # Artifact names become collection files, volume paths AND
        # checkpoint directories; reject path-shaped names here (406)
        # rather than relying on the store's internal gate (500) — and
        # never let '..'/absolute names reach a shutil.rmtree.
        if not _ARTIFACT_NAME_RE.fullmatch(name) or ".." in name:
            raise ValidationError(f"invalid artifact name: {name!r}")
        # Reserved: text transforms store their trained tokenizer
        # binary at "<artifact>.tokenizer" in the shared transform
        # volume (services/transform.py::_tokenizer_volume_name); an
        # artifact claiming such a name would collide with it.
        if name.endswith(".tokenizer"):
            raise ValidationError(
                f"artifact name {name!r} uses the reserved "
                "'.tokenizer' suffix"
            )
        # Reserved: these segments are fixed observe sub-routes
        # (GET /observe/events, POST /observe/webhook); an artifact so
        # named would be silently shadowed off the observe long-poll.
        # MIGRATION CAVEAT (ADVICE r3): a store that predates this
        # gate may already hold an artifact named "events"/"webhook";
        # its /observe/<name> long-poll and per-artifact webhook routes
        # are permanently shadowed by the fixed routes.  Rename such
        # artifacts before upgrading (the data itself remains readable
        # via the service GET routes, which are not shadowed).
        if name in ("events", "webhook"):
            raise ValidationError(
                f"artifact name {name!r} is reserved (observe route)"
            )
        if self.artifacts.metadata.exists(name):
            raise ConflictError(f"duplicate artifact name: {name!r}")

    def require_existing(self, name: str) -> dict:
        meta = self.artifacts.metadata.read(name)
        if meta is None:
            raise NotFoundError(f"no such artifact: {name!r}")
        return meta

    def require_not_running(self, name: str) -> dict:
        """PATCH re-run gate: two jobs for one artifact must not run
        concurrently (each would interleave delete/insert over the same
        collection and flip ``finished`` under the other) — 409 while
        the previous job is still executing."""
        meta = self.require_existing(name)
        if meta.get("jobState") in ("pending", "running"):
            raise ConflictError(
                f"artifact {name!r} has a job in state "
                f"{meta.get('jobState')!r}; wait for it to finish"
            )
        return meta

    def last_recorded_parameters(self, name: str):
        """The most recent request parameters persisted for ``name`` —
        the fallback a bare PATCH re-run (no body parameters, the
        natural "just resume" call after a preemption or failover)
        re-submits with, instead of failing on missing x/y.  Terminal
        ledger rows win (they reflect what actually ran); the
        submit-time metadata copy covers a job whose FIRST run died
        before writing any ledger record."""
        rows = [
            d
            for d in self.documents.find(
                name, query={"docType": "execution"}
            )
            if d.get("parameters") is not None
        ]
        if rows:
            return rows[-1]["parameters"]
        meta = self.artifacts.metadata.read(name) or {}
        return meta.get("requestParameters")

    def checkpoint_dir(self, name: str):
        """Managed per-artifact train-checkpoint tree — the ONE place
        this path is built (executor, distributed route and delete all
        share it)."""
        return self.volumes.root / "_checkpoints" / name

    def delete_artifact(self, name: str) -> dict:
        """Shared delete: collection + volume binary (dataset/model/
        executor/function services all expose the same DELETE), plus any
        managed train checkpoints — a recreated artifact with the same
        name must never resume from a deleted job's state."""
        meta = self.require_existing(name)
        self.artifacts.delete(name)
        self.volumes.delete(meta.get("type", ""), name)
        # Serving registry (and any other subscriber) must drop
        # resident state derived from this artifact NOW — a recreated
        # artifact with the same name must never serve deleted weights.
        self.notify_artifact_changed(name)
        # A text transform also owns a trained-tokenizer binary next to
        # its shard directory; deleting the artifact must not leave it
        # behind (a later tokenizerFrom would silently load the stale
        # vocab of a name that no longer exists).
        if meta.get("type") == "transform/text":
            self.volumes.delete(
                meta.get("type", ""), name + ".tokenizer"
            )
        import shutil

        ckdir = self.checkpoint_dir(name)
        if ckdir.exists():
            shutil.rmtree(ckdir, ignore_errors=True)
        return meta

    def require_finished_parent(self, name: str) -> dict:
        """Downstream steps refuse unfinished parents (reference:
        projection_image/utils.py:88-95)."""
        meta = self.require_existing(name)
        if not meta.get("finished"):
            raise ValidationError(
                f"parent artifact {name!r} is not finished "
                f"(jobState={meta.get('jobState')})"
            )
        return meta


class StoreLoader:
    """The DSL's ``$name`` resolution over store + volumes.

    Mirrors the reference's load rules (binary_executor_image/
    utils.py:322-336): dataset collections load as DataFrames; everything
    else loads its volume binary (checkpointed estimator / pytree / raw
    object)."""

    def __init__(self, ctx: ServiceContext):
        self.ctx = ctx

    def load(self, name: str) -> Any:
        meta = self.ctx.artifacts.metadata.read(name)
        if meta is None:
            raise KeyError(name)
        kind = str(meta.get("type", ""))
        if meta.get("sharded"):
            # Beyond-RAM datasets resolve to a LAZY handle (train paths
            # stream its shards); materializing a DataFrame here would
            # be exactly the O(dataset)-host-memory step the sharded
            # format exists to avoid.  ``$name.col`` indexes to a
            # single-column view via ShardedDataset.__getitem__.
            from learningorchestra_tpu.store.sharded import ShardedDataset

            return ShardedDataset(self.ctx.volumes.path_for(kind, name))
        if kind.startswith("dataset/csv") or not self.ctx.volumes.exists(
            kind, name
        ):
            return self.load_dataframe(name)
        return self.ctx.volumes.read_object(kind, name)

    def load_dataframe(self, name: str) -> pd.DataFrame:
        docs = self.ctx.documents.find(
            name,
            query={"_id": {"$gte": 1}, "docType": {"$ne": "execution"}},
        )
        if not docs:
            raise KeyError(f"artifact {name!r} has no rows")
        df = pd.DataFrame(docs)
        return df.drop(columns=["_id"])
