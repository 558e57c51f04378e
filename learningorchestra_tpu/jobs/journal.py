"""Crash-durable job journal + engine-epoch execution fencing.

The engine's queue, running set and lease bookkeeping are in-memory:
before this module, a ``kill -9`` of the orchestrator silently lost
every queued job and stranded RUNNING jobs as forever-"running"
metadata — exactly the durability gap the reference system's
"stateful, persisted, independently re-executable" pipeline-step
contract promises away (PAPER.md).  Two pieces close it:

**Journal.**  Every job state transition (``submitted → queued →
running(attempt N) → finished | failed | cancelled``, plus
``preempted``/``deadline``/``cancel_requested`` events) is appended to
the ``_job_journal`` store collection BEFORE the in-memory transition
commits.  The collection rides the document store's existing WAL
machinery (document_store.py), so journal records get the same
torn-tail recovery, compaction and WAL-shipping (store/replica.py —
a promoted standby inherits the journal) as every artifact.  Records
are keyed by job name and carry the submit spec (method, parameters,
class, deadline), so the full engine state is reconstructible from
the journal alone: :meth:`JobJournal.replay` folds the records into
one terminal-or-latest state per job, preserving queue admission
order.

**Epoch fencing.**  Each recovery boot mints an **engine epoch** — a
monotonic counter in ``.engine_epoch`` inside the store root, the
same idiom as the HA tier's ``.epoch`` election term
(store/replica.py) but scoped to engine restarts over ONE store
directory.  The engine stamps the boot epoch on every dispatched job
body (a contextvar, like the retry attempt); terminal metadata
commits and artifact publications re-read the durable file and
refuse to commit when a NEWER epoch exists (:func:`JobJournal.
fence_check` raises :class:`StaleEpochError`).  A pre-crash straggler
thread that somehow survives into a recovered world — or, once the
control plane goes multi-process (ROADMAP item 4), a partitioned
duplicate orchestrator over the shared store — cannot double-publish
artifacts or lost-update job metadata.

Cost discipline: every journal record — the submit pair included —
is GROUP-COMMITTED: the hot path enqueues a slim record (one deque
append) and an eager flusher drains FIFO batches into the store's
WAL within the time of one batch write.  The sub-ms window this
opens is harmless by construction: recovery is metadata-authoritative
(the artifact's own collection records the same transitions, flushed
inline, with the request parameters stamped at submit), so a crash
inside the window can at worst demote a job from auto-re-dispatch to
the explicit orphaned-by-restart path — never lose or double-run
one.  A job's life is four records (tests/test_journal_recovery.py);
their cost on the submit/dispatch path is not measured on a chip.
Fence checks re-read a one-line file and run only at terminal
commits/publications, never per epoch.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from collections import deque
from pathlib import Path

from learningorchestra_tpu.concurrency_rt import make_lock
from learningorchestra_tpu.log import get_logger, kv
from learningorchestra_tpu.store.document_store import DocumentStore

logger = get_logger("journal")

#: Store collection holding journal records.  Underscore prefix keeps
#: it out of the artifact namespace (same convention as the
#: idempotency ledger) and sorts it early in WAL shipping.
JOURNAL_COLLECTION = "_job_journal"

#: Engine-epoch counter file inside the store root — the restart
#: analogue of the HA tier's ``.epoch`` election term.
ENGINE_EPOCH_FILE = ".engine_epoch"

#: Journal events that end a job's life.  Everything else is
#: non-terminal: a restart must recover the job.
TERMINAL_EVENTS = frozenset(
    {"finished", "failed", "cancelled", "deadline"}
)

#: Every event the engine journals — the replay goldens enumerate
#: these (tests/test_journal_recovery.py).
EVENTS = (
    "submitted",
    "queued",
    "running",
    "preempted",
    "cancel_requested",
    "finished",
    "failed",
    "cancelled",
    "deadline",
)


class StaleEpochError(RuntimeError):
    """A worker from an older engine epoch tried to commit: a newer
    recovery (or a duplicate orchestrator over the shared store) owns
    this store now — the write is refused, not merged."""


def read_engine_epoch(store_root: str | Path) -> int:
    """The store's engine epoch; 0 for a store no engine booted on."""
    try:
        return int((Path(store_root) / ENGINE_EPOCH_FILE).read_text())
    except (OSError, ValueError):
        return 0


def write_engine_epoch(store_root: str | Path, epoch: int) -> None:
    """Durably publish ``epoch`` (write + fsync + atomic replace):
    fencing is only as strong as this file's crash-durability."""
    root = Path(store_root)
    root.mkdir(parents=True, exist_ok=True)
    tmp = root / (ENGINE_EPOCH_FILE + ".tmp")
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(str(int(epoch)))
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, root / ENGINE_EPOCH_FILE)


#: The dispatched job body's engine epoch (None outside a dispatch —
#: direct library use keeps working, unfenced).
_STAMP: contextvars.ContextVar = contextvars.ContextVar(
    "lo_engine_epoch", default=None
)


def current_stamp() -> int | None:
    """The engine epoch stamped on the calling job body's dispatch."""
    return _STAMP.get()


@contextlib.contextmanager
def stamp(epoch: int | None):
    """Bind ``epoch`` as the current body's engine epoch (the engine
    wraps each dispatch; tests bind stale values to drive the fence)."""
    handle = _STAMP.set(epoch)
    try:
        yield
    finally:
        _STAMP.reset(handle)


class JobJournal:
    """Append/replay surface over the ``_job_journal`` collection.

    Thread-safety: writes delegate to the document store, whose
    per-collection lock serializes WAL appends and allocates
    monotonic ``_id`` sequence numbers.  The group-commit flusher is
    serialized by ``_flush_lock`` (drains never interleave, so batch
    order equals enqueue order).
    """

    # ``documents`` is annotated DocumentStore for the whole-program
    # lock analyzer's constructor-typed-attribute resolution (the
    # native backend shares the API; the annotation is the static
    # model, not a runtime constraint).
    def __init__(self, documents: DocumentStore,
                 store_root: str | Path, *,
                 enabled: bool = True, max_records: int = 4096,
                 epoch_lock=None):
        self.documents = documents
        self.store_root = Path(store_root)
        self.enabled = bool(enabled)
        self.max_records = int(max_records)
        #: Zero-arg callable returning a context manager that holds
        #: the CLUSTER's cross-process lock (services/context.py wires
        #: the coordinator's guard in).  Epoch minting runs under it
        #: so two engines booting concurrently over one store root
        #: mint distinct epochs.  None → single-process boot, no lock.
        self._epoch_lock = epoch_lock
        #: Under clustering (jobs/cluster.py) the context sets these:
        #: ``cluster`` delegates the fence to claim ownership, and
        #: ``exclusive`` (a zero-arg guard factory refreshing the
        #: journal collection) serializes cross-process appends so
        #: two engines cannot allocate conflicting ``_id`` sequence
        #: numbers.  Both None in the single-engine world — the hot
        #: path pays one attribute check.
        self.cluster = None
        self.exclusive = None
        #: Appends that failed (store fault, disk full) — surfaced so
        #: a silently lossy journal is at least countable.
        self.dropped = 0
        # Group-commit state: the hot path enqueues (GIL-atomic deque
        # append) and wakes the flusher; drains are serialized.
        self._pending: deque = deque()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._flush_lock = make_lock("JobJournal._flush_lock")
        self._flusher: threading.Thread | None = None
        # Each construction is an engine boot: mint the next epoch so
        # stragglers from any previous life are fenced at commit time.
        # Disabled journals keep epoch 0 and never fence.
        self.epoch = (
            self._mint_epoch() if self.enabled else 0
        )

    # -- epoch fencing --------------------------------------------------------

    def _mint_epoch(self) -> int:
        lock = (
            self._epoch_lock() if self._epoch_lock is not None
            else contextlib.nullcontext()
        )
        with lock:
            epoch = read_engine_epoch(self.store_root) + 1
            write_engine_epoch(self.store_root, epoch)
        logger.info(kv(event="engine_epoch_minted", epoch=epoch))
        return epoch

    def durable_epoch(self) -> int:
        """The store's CURRENT epoch, re-read from disk — what a
        newer recovery (or duplicate orchestrator) would have bumped."""
        return read_engine_epoch(self.store_root)

    def fence_check(self, stamped: int | None = None) -> None:
        """Refuse a commit from a stale engine epoch.

        ``stamped`` defaults to the calling job body's dispatch stamp;
        unstamped callers (direct library use, tests without an
        engine) pass the check — fencing guards engine-dispatched
        work, not ad-hoc scripts.
        """
        if not self.enabled:
            return
        if stamped is None:
            stamped = current_stamp()
        if stamped is None:
            return
        if self.cluster is not None:
            # Multi-engine world: two LIVE engines legitimately hold
            # different durable epochs, so the single-process
            # "newer epoch exists" comparison is wrong here.  The
            # fence becomes claim OWNERSHIP: a cluster dispatch may
            # commit only while its engine still owns the live claim
            # under the stamped epoch — a stolen claim (partition,
            # missed heartbeats) refuses the straggler's publication.
            from learningorchestra_tpu.jobs.cluster import current_claim

            claim = current_claim()
            if claim is None:
                return  # direct library use on a clustered store
            if not self.cluster.verify(claim, stamped):
                from learningorchestra_tpu.obs import flight as obs_flight

                obs_flight.record(
                    "cluster", "fence_refused", job=claim,
                    engine=self.cluster.engine_id, epoch=stamped,
                )
                raise StaleEpochError(
                    f"claim for job {claim!r} is no longer owned by "
                    f"engine {self.cluster.engine_id!r} under epoch "
                    f"{stamped} — the claim was stolen or released by "
                    "a peer; refusing to commit"
                )
            return
        durable = self.durable_epoch()
        if durable > stamped:
            raise StaleEpochError(
                f"engine epoch {stamped} is stale: the store's "
                f"current epoch is {durable} — a newer recovery owns "
                "this store; refusing to commit"
            )

    # -- append ---------------------------------------------------------------

    def record_submit(self, job: str, *, job_class: str,
                      method=None, description=None, parameters=None,
                      deadline_s=None, request_id=None) -> None:
        """The ``submitted``+``queued`` pair, enqueued as adjacent
        records in the group-commit FIFO (one WAL batch, durable
        within the flusher's next drain — see the module docstring
        for why the window is safe).

        ``parameters`` are NOT copied into the journal — the engine
        already stamps them durably into the artifact's metadata
        (``requestParameters``) BEFORE journaling, and recovery
        re-dispatches through ``last_recorded_parameters``;
        duplicating a possibly-large request body here would put its
        serialization cost on every submit."""
        if not self.enabled:
            return
        del parameters  # recorded in artifact metadata (see above)
        spec = {"jobClass": job_class}
        if method is not None:
            spec["method"] = method
        if description is not None:
            spec["description"] = description
        if deadline_s is not None:
            spec["deadlineS"] = deadline_s
        if request_id is not None:
            spec["requestId"] = request_id
        base = {
            "docType": "journal",
            "job": job,
            "epoch": self.epoch,
            "at": time.time(),
        }
        self._pending.append(
            {**base, "event": "submitted", "spec": spec}
        )
        self._enqueue({**base, "event": "queued"})

    def append(self, event: str, job: str, *, attempt=None,
               reason=None) -> None:
        """One transition record, group-committed: the hot path is a
        deque append + flusher wake; the flusher drains FIFO batches
        into the store's WAL within one batch-write time.  Recovery
        stays correct across the sub-ms window because the artifact's
        own metadata (flushed inline by the engine, and stamped with
        the request parameters at submit) is authoritative — the
        journal adds the spec, ordering and event detail; at worst a
        crash inside the window demotes a job from auto-re-dispatch
        to the explicit orphaned-by-restart path."""
        if not self.enabled:
            return
        doc = {
            "docType": "journal",
            "job": job,
            "event": event,
            "epoch": self.epoch,
            "at": time.time(),
        }
        if attempt is not None:
            doc["attempt"] = attempt
        if reason is not None:
            doc["reason"] = reason
        self._enqueue(doc)

    # -- group-commit flusher -------------------------------------------------

    def _enqueue(self, doc: dict) -> None:
        self._pending.append(doc)
        if self._stop.is_set():
            # Late append after close() (a straggler body journaling
            # its terminal under shutdown_drain_s=0): the flusher is
            # gone — write through inline.  If the store already
            # closed, _drain counts the loss in `dropped` instead of
            # silently eating it.
            self._drain()
            return
        self._wake.set()
        if self._flusher is None:
            self._ensure_flusher()

    def _ensure_flusher(self) -> None:
        with self._flush_lock:
            if self._flusher is None or not self._flusher.is_alive():
                self._flusher = threading.Thread(
                    target=self._flush_loop,
                    name="lo-job-journal", daemon=True,
                )
                self._flusher.start()

    def _flush_loop(self) -> None:
        while True:
            self._wake.wait(0.2)
            self._wake.clear()
            self._drain()
            if self._stop.is_set() and not self._pending:
                return

    def _drain(self) -> int:
        """Write every enqueued record, in order; returns the count.
        Serialized so concurrent drains (flusher + submit + close)
        can never interleave batch order."""
        with self._flush_lock:
            batch = []
            while self._pending:
                batch.append(self._pending.popleft())
            if not batch:
                return 0
            # Under clustering, appends run inside the coordinator's
            # cross-process guard (flock + WAL refresh): two engines
            # draining concurrently would otherwise allocate the same
            # ``_id`` sequence numbers from stale in-memory tails.
            guard = (
                self.exclusive() if self.exclusive is not None
                else contextlib.nullcontext()
            )
            try:
                with guard:
                    self.documents.insert_many(
                        JOURNAL_COLLECTION, batch
                    )
            except Exception:  # noqa: BLE001
                self.dropped += len(batch)
                logger.error(kv(event="journal_append_failed",
                                batch=len(batch)))
            return len(batch)

    def flush(self) -> None:
        """Drain synchronously — shutdown and tests call this before
        reading the journal back."""
        if self.enabled:
            self._drain()

    def close(self) -> None:
        """Stop the flusher after a final synchronous drain.  Call
        BEFORE closing the document store (a drain into closed WAL
        handles would count every record dropped)."""
        self._stop.set()
        self._wake.set()
        flusher = self._flusher
        if flusher is not None and flusher.is_alive():
            flusher.join(timeout=2.0)
        self.flush()

    # -- replay ---------------------------------------------------------------

    def replay(self) -> dict:
        """Fold the journal into one record per job, in queue
        admission order (insertion ``_id`` is the sequence number).

        Returns ``{job: {"state", "terminal", "spec", "attempts",
        "epoch", "seq"}}`` where ``seq`` is the job's LATEST
        ``queued`` sequence number — re-enqueueing recovered jobs in
        ``seq`` order preserves the pre-crash queue order.
        """
        if not self.enabled:
            return {}
        self.flush()  # same-process readers see enqueued records
        if self.exclusive is not None:
            # Fold peer engines' appends in before reading (the guard
            # refreshes the journal collection from its WAL).
            with self.exclusive():
                docs = list(
                    self.documents.find(JOURNAL_COLLECTION)
                ) if self.documents.collection_exists(
                    JOURNAL_COLLECTION
                ) else []
        elif not self.documents.collection_exists(JOURNAL_COLLECTION):
            return {}
        else:
            docs = self.documents.find(JOURNAL_COLLECTION)
        out: dict = {}
        for doc in docs:
            if doc.get("docType") != "journal" or not doc.get("job"):
                continue
            job = doc["job"]
            event = doc.get("event")
            rec = out.setdefault(job, {
                "state": "submitted", "terminal": False,
                "spec": None, "attempts": 0, "epoch": 0, "seq": -1,
            })
            rec["epoch"] = max(rec["epoch"], doc.get("epoch", 0))
            if event == "submitted":
                rec["spec"] = doc.get("spec") or rec["spec"]
                if rec["terminal"]:
                    # Re-submission of a completed job (PATCH re-run):
                    # a fresh life starts.
                    rec.update(terminal=False, attempts=0)
                rec["state"] = "submitted"
            elif event == "queued":
                rec["state"] = "queued"
                rec["terminal"] = False
                rec["seq"] = doc["_id"]
            elif event == "running":
                rec["state"] = "running"
                rec["attempts"] = max(
                    rec["attempts"], doc.get("attempt", 1)
                )
            elif event == "preempted":
                rec["state"] = "running"
            elif event == "cancel_requested":
                rec["state"] = "cancelling"
            elif event in TERMINAL_EVENTS:
                rec["state"] = (
                    "failed" if event == "deadline" else event
                )
                rec["terminal"] = True
                if doc.get("reason"):
                    rec["reason"] = doc["reason"]
        return out

    # -- maintenance ----------------------------------------------------------

    def prune(self) -> int:
        """Boot-time compaction: once the journal exceeds
        ``max_records``, drop all but the last record of each
        TERMINAL job (non-terminal jobs keep their full history —
        recovery needs it) and compact the backing WAL.  Returns the
        number of records dropped."""
        if not self.enabled or self.max_records <= 0:
            return 0
        if not self.documents.collection_exists(JOURNAL_COLLECTION):
            return 0
        if self.documents.count(JOURNAL_COLLECTION) <= self.max_records:
            return 0
        replayed = self.replay()
        terminal = {
            job for job, rec in replayed.items() if rec["terminal"]
        }
        last_seen: dict = {}
        for doc in self.documents.find(JOURNAL_COLLECTION):
            if doc.get("job") in terminal:
                last_seen[doc["job"]] = doc["_id"]
        dropped = 0
        for doc in self.documents.find(JOURNAL_COLLECTION):
            job = doc.get("job")
            if job in terminal and doc["_id"] != last_seen.get(job):
                self.documents.delete_one(
                    JOURNAL_COLLECTION, doc["_id"]
                )
                dropped += 1
        if dropped:
            try:
                self.documents.compact(JOURNAL_COLLECTION)
            except Exception:  # noqa: BLE001 — compaction is an
                pass  # optimization; the deletes already landed
            logger.info(kv(event="journal_pruned", dropped=dropped))
        return dropped
