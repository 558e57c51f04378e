"""Scale-out control plane (jobs/cluster.py + the context/API/client
integration): claim-table CAS goldens, heartbeat-lease expiry with
steal in pre-crash queue order, lease fencing of stolen claims, the
two-subprocess partition drill (kill -9 one engine mid-fit, the peer
steals and resumes from the newest checkpoint, exactly one terminal
publication), per-tenant quota 429s at the gateway, and the
tenant-fair scheduling flood.

Two coordinators in these tests each get their OWN DocumentStore over
one root directory — the same shape as two engine processes: views
sync only through the WAL catch-up under the cross-process file lock,
so the goldens exercise the real coherence machinery, not shared
memory.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from learningorchestra_tpu import faults
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.jobs import (
    JobEngine,
    JobJournal,
    QuotaExceeded,
    StaleEpochError,
    TenantAdmission,
    bind_tenant,
)
from learningorchestra_tpu.jobs import journal as journal_mod
from learningorchestra_tpu.jobs.cluster import (
    ClusterCoordinator,
    bind_claim,
)
from learningorchestra_tpu.store import DocumentStore

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _coord(store, engine_id, **kw):
    """A coordinator with parked timers (no join() — tests drive
    claim/heartbeat/sweep explicitly for deterministic interleaving)."""
    kw.setdefault("heartbeat_s", 30.0)
    kw.setdefault("ttl_s", 60.0)
    kw.setdefault("sweep_s", 30.0)
    return ClusterCoordinator(store, store.root, engine_id=engine_id,
                              **kw)


@pytest.fixture()
def duo(tmp_path):
    """Two engines over one store root, each with its own
    DocumentStore instance (see module docstring)."""
    sa = DocumentStore(tmp_path / "store")
    sb = DocumentStore(tmp_path / "store")
    a = _coord(sa, "A")
    b = _coord(sb, "B")
    yield a, b
    for c in (a, b):
        c.close()
    sa.close()
    sb.close()


# -- claim CAS goldens -------------------------------------------------------


class TestClaimGoldens:
    def test_cas_resolves_concurrent_claims_to_one_owner(self, duo):
        a, b = duo
        assert a.claim("j") is True
        assert b.claim("j") is False  # live peer claim: lost, not raced
        assert a.verify("j") is True
        assert b.verify("j") is False

    def test_own_reclaim_renews_instead_of_losing(self, duo):
        """A preemption retry / recovered boot re-claims a job this
        engine already owns — renewal, never a self-inflicted loss."""
        a, _ = duo
        assert a.claim("j") is True
        assert a.claim("j") is True

    def test_released_claim_supersedes_stale_queue_entries(self, duo):
        """The double-run guard: a queue entry enqueued BEFORE a
        peer's completion describes work that already published —
        superseded.  A genuinely new submission (enqueued after the
        release) re-adopts the slot by CAS."""
        a, b = duo
        assert a.claim("j") is True
        a.release("j")
        assert b.claim("j", enqueued_at=time.time() - 100) is False
        assert b.claim("j", enqueued_at=time.time() + 100) is True
        assert b.verify("j") is True

    def test_expired_peer_claim_taken_over_at_dispatch(self, tmp_path):
        sa = DocumentStore(tmp_path / "store")
        sb = DocumentStore(tmp_path / "store")
        a = _coord(sa, "A")
        b = _coord(sb, "B", ttl_s=0.05)
        try:
            assert a.claim("j") is True
            time.sleep(0.12)  # lease idles past B's TTL
            assert b.claim("j") is True
            assert a.verify("j") is False
        finally:
            a.close()
            b.close()
            sa.close()
            sb.close()

    def test_claimable_gates_boot_adoption_on_live_peers(self, duo):
        """Boot recovery must not adopt a job a LIVE peer is running;
        released (finished) and own claims stay adoptable."""
        a, b = duo
        assert a.claim("j") is True
        assert b.claimable("j") is False
        assert a.claimable("j") is True
        a.release("j")
        assert b.claimable("j") is True


# -- lease expiry: steal order + engine death --------------------------------


class TestStealAndMembership:
    def test_sweep_steals_expired_claims_in_claim_order(self, tmp_path):
        """Claim-table _ids are the admission sequence: a dead
        engine's claims transfer oldest-first, preserving its
        pre-crash queue order."""
        sa = DocumentStore(tmp_path / "store")
        sb = DocumentStore(tmp_path / "store")
        dead = _coord(sa, "dead")
        thief = _coord(sb, "thief", ttl_s=0.05)
        try:
            for job in ("j1", "j2", "j3"):
                assert dead.claim(job) is True
            time.sleep(0.12)
            stolen = thief.sweep()
            assert stolen == [
                ("j1", "dead"), ("j2", "dead"), ("j3", "dead"),
            ]
            assert all(thief.verify(j) for j in ("j1", "j2", "j3"))
            assert not any(dead.verify(j) for j in ("j1", "j2", "j3"))
        finally:
            dead.close()
            thief.close()
            sa.close()
            sb.close()

    def test_engine_death_fires_callback_and_retracts_doc(
        self, tmp_path
    ):
        sa = DocumentStore(tmp_path / "store")
        sb = DocumentStore(tmp_path / "store")
        dead = _coord(sa, "dead")
        dead.epoch = 7
        thief = _coord(sb, "thief", ttl_s=0.05)
        seen = []
        thief.on_engine_dead = lambda eng, epoch: seen.append(
            (eng, epoch)
        )
        try:
            dead.heartbeat()  # publishes the membership document
            time.sleep(0.12)
            thief.sweep()
            assert seen == [("dead", 7)]
            assert all(
                e["engine"] == "thief"
                for e in thief.status()["engines"]
            )
        finally:
            dead.close()
            thief.close()
            sa.close()
            sb.close()


# -- lease fencing: the stolen claim refuses the straggler's commit ----------


class TestLeaseFencing:
    def test_stolen_claim_refuses_stale_commit(self, tmp_path):
        """The partition story in-process: engine A's fit keeps
        running after its claim is stolen — its terminal commit must
        raise StaleEpochError even though A never crashed."""
        sa = DocumentStore(tmp_path / "store")
        sb = DocumentStore(tmp_path / "store")
        journal = JobJournal(sa, tmp_path / "store")
        a = _coord(sa, "A")
        a.epoch = journal.epoch
        journal.cluster = a
        thief = _coord(sb, "thief", ttl_s=0.05)
        try:
            assert a.claim("fit1") is True
            with bind_claim("fit1"), journal_mod.stamp(a.epoch):
                journal.fence_check()  # owned: commit allowed
                time.sleep(0.12)
                assert [j for j, _ in thief.sweep()] == ["fit1"]
                with pytest.raises(StaleEpochError):
                    journal.fence_check()
        finally:
            journal.close()
            a.close()
            thief.close()
            sa.close()
            sb.close()

    def test_released_claim_also_fences(self, tmp_path):
        """A claim released by a peer's completed adoption fences the
        original engine the same way a steal does."""
        sa = DocumentStore(tmp_path / "store")
        journal = JobJournal(sa, tmp_path / "store")
        a = _coord(sa, "A")
        a.epoch = journal.epoch
        journal.cluster = a
        try:
            assert a.claim("fit2") is True
            a.release("fit2")
            with bind_claim("fit2"), journal_mod.stamp(a.epoch):
                with pytest.raises(StaleEpochError):
                    journal.fence_check()
        finally:
            journal.close()
            a.close()
            sa.close()

    def test_unclaimed_direct_use_passes_the_fence(self, tmp_path):
        """Library code on a clustered store without a bound claim
        (scripts, tests) is not fenced — claims guard engine
        dispatches, not ad-hoc writes."""
        sa = DocumentStore(tmp_path / "store")
        journal = JobJournal(sa, tmp_path / "store")
        a = _coord(sa, "A")
        journal.cluster = a
        try:
            with journal_mod.stamp(journal.epoch):
                journal.fence_check()  # no claim bound: passes
        finally:
            journal.close()
            a.close()
            sa.close()


# -- per-tenant admission: shared counters, quotas, fairness -----------------


class TestTenantAdmission:
    def test_quota_answers_identically_on_every_engine(self, duo):
        """Counters live in the store: jobs queued through engine A
        count against the tenant's quota on engine B."""
        a, b = duo
        adm_a = TenantAdmission(max_queued=1, cluster=a)
        adm_b = TenantAdmission(max_queued=1, cluster=b)
        adm_a.check("t1")  # under quota everywhere
        adm_a.note_queued("t1")
        with pytest.raises(QuotaExceeded) as exc:
            adm_b.check("t1")
        assert exc.value.retry_after_s == 1.0
        adm_b.check("t2")  # another tenant is unaffected
        # Dispatch moves queued -> running; executor fits count
        # against the running quota.
        adm_a.note_dispatch("t1", "executor")
        adm_b.check("t1")
        adm_run = TenantAdmission(max_running=1, cluster=b)
        with pytest.raises(QuotaExceeded):
            adm_run.check("t1")
        adm_a.note_done("t1", "executor")
        adm_run.check("t1")

    def test_counters_clamp_at_zero(self, duo):
        a, _ = duo
        adm = TenantAdmission(max_queued=2, cluster=a)
        adm.note_dequeued("t")  # cancel races must not go negative
        adm.note_queued("t")
        assert adm.snapshot()["t"] == {"queued": 1, "running": 0}

    def test_flood_cannot_starve_peer_tenant(self, artifacts):
        """The fairness drill: one worker, a six-job flood from one
        tenant, two jobs from another — nested per-tenant round-robin
        inside the class serves the quiet tenant every other turn
        instead of after the flood."""
        eng = JobEngine(artifacts, max_workers=1)
        done: list[str] = []
        gate = threading.Event()
        started = threading.Event()

        def blocker():
            started.set()
            gate.wait(30)
            return "b"

        try:
            artifacts.metadata.create("blk", "function/python")
            eng.submit("blk", blocker, job_class="f")
            assert started.wait(10)

            def body(tag):
                return lambda: done.append(tag) or tag

            with bind_tenant("noisy"):
                for i in range(6):
                    artifacts.metadata.create(f"n{i}", "function/x")
                    eng.submit(f"n{i}", body(f"n{i}"), job_class="f")
            with bind_tenant("quiet"):
                for i in range(2):
                    artifacts.metadata.create(f"q{i}", "function/x")
                    eng.submit(f"q{i}", body(f"q{i}"), job_class="f")
            gate.set()
            for name in [f"n{i}" for i in range(6)] + ["q0", "q1"]:
                eng.wait(name, timeout=30)
        finally:
            gate.set()
            eng.shutdown()
        # Alternating service: both quiet jobs complete within the
        # first four post-flood slots (noisy, quiet, noisy, quiet...).
        assert {"q0", "q1"} <= set(done[:4]), done


def _wait_finished(server, name, timeout=30):
    server.ctx.engine.wait(name, timeout=timeout)
    deadline = time.time() + timeout
    meta = {}
    while time.time() < deadline:
        meta = server.ctx.artifacts.metadata.read(name) or {}
        if meta.get("jobState") in ("finished", "failed"):
            break
        time.sleep(0.02)
    assert meta.get("jobState") == "finished", meta


# -- the gateway 429 drill + client contract ---------------------------------


class TestQuota429:
    @pytest.fixture()
    def quota_server(self, tmp_path):
        from learningorchestra_tpu.api import APIServer

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        cfg.jobs.max_workers = 1
        cfg.tenant.max_queued = 1
        cfg.tenant.retry_after_s = 0.2
        server = APIServer(cfg)
        yield server, tmp_path
        server.shutdown()

    def _blocking_fn(self, name, start, gate):
        return {
            "name": name,
            "function": (
                "import os, time\n"
                f"open({str(start)!r}, 'w').close()\n"
                f"while not os.path.exists({str(gate)!r}):\n"
                "    time.sleep(0.01)\n"
                "response = 1\n"
            ),
            "functionParameters": {},
        }

    def test_gateway_429_with_retry_after(self, quota_server):
        """Over-quota submissions 429 BEFORE any metadata exists, with
        the configured Retry-After; other tenants stay admitted."""
        server, tmp = quota_server
        start = tmp / "b0_started"
        gate = tmp / "drain"
        st, _ = server.handle(
            "POST", f"{PREFIX}/function/python",
            self._blocking_fn("b0", start, gate), {}, tenant="acme",
        )
        assert st == 201
        deadline = time.time() + 30
        while not start.exists():  # worker occupied, queue empty
            assert time.time() < deadline
            time.sleep(0.01)
        st, _ = server.handle(
            "POST", f"{PREFIX}/function/python",
            self._blocking_fn("q1", tmp / "q1s", gate), {},
            tenant="acme",
        )
        assert st == 201  # fills the queued quota
        st, body = server.handle(
            "POST", f"{PREFIX}/function/python",
            self._blocking_fn("q2", tmp / "q2s", gate), {},
            tenant="acme",
        )
        assert st == 429
        assert body["retryAfter"] == pytest.approx(0.2)
        # No orphan artifact was created for the refused job.
        st, _ = server.handle(
            "GET", f"{PREFIX}/function/python/q2", {}, {}
        )
        assert st == 404
        # A different tenant is not starved by acme's quota.
        st, _ = server.handle(
            "POST", f"{PREFIX}/function/python",
            self._blocking_fn("other1", tmp / "o1s", gate), {},
            tenant="tenant-b",
        )
        assert st == 201
        # The rejection is metered per tenant and reason.
        st, payload = server.handle(
            "GET", f"{PREFIX}/metrics.prom", {}, {}
        )
        assert st == 200
        text = payload[1].decode()  # (content-type, body-bytes)
        assert (
            'lo_admission_rejections_total{'
            'reason="queued_quota",tenant="acme"} 1' in text
            or 'lo_admission_rejections_total{'
            'tenant="acme",reason="queued_quota"} 1' in text
        )
        gate.write_text("go")
        for name in ("b0", "q1", "other1"):
            _wait_finished(server, name)

    def test_client_sends_tenant_and_retries_429_once(
        self, quota_server
    ):
        """End to end over HTTP: Context(tenant=...) transmits
        X-Tenant (the per-tenant 429 proves it — an untenanted request
        would be admitted), honors Retry-After with ONE bounded retry,
        then surfaces the second 429."""
        from learningorchestra_tpu.client import ClientError, Context

        server, tmp = quota_server
        port = server.start_background()
        ctx = Context("127.0.0.1", port=port, tenant="acme")
        start = tmp / "cb0_started"
        gate = tmp / "cdrain"
        ctx.request(
            "POST", "/function/python",
            self._blocking_fn("cb0", start, gate),
        )
        deadline = time.time() + 30
        while not start.exists():
            assert time.time() < deadline
            time.sleep(0.01)
        ctx.request(
            "POST", "/function/python",
            self._blocking_fn("cq1", tmp / "cq1s", gate),
        )
        t0 = time.time()
        with pytest.raises(ClientError) as exc:
            ctx.request(
                "POST", "/function/python",
                self._blocking_fn("cq2", tmp / "cq2s", gate),
            )
        assert exc.value.status == 429
        assert time.time() - t0 >= 0.2  # slept Retry-After once
        # Drain; the retried submission then lands.
        gate.write_text("go")
        for name in ("cb0", "cq1"):
            _wait_finished(server, name)
        ctx.request(
            "POST", "/function/python",
            self._blocking_fn("cq2", tmp / "cq2s2", gate),
        )
        _wait_finished(server, "cq2")
        # The cluster binding: single-engine deployments answer 200
        # with enabled=false (never a 404), tenants included whenever
        # admission is configured.
        status = ctx.cluster.status()
        assert status["enabled"] is False
        assert status["engines"] == [] and status["claims"] == []
        assert "acme" in status["tenants"]

    def test_client_does_not_retry_non_429(self, quota_server):
        from learningorchestra_tpu.client import ClientError, Context

        server, _tmp = quota_server
        port = server.start_background()
        ctx = Context("127.0.0.1", port=port)
        calls = []
        routed = ctx._request_routed

        def counting(*a, **kw):
            calls.append(a)
            return routed(*a, **kw)

        ctx._request_routed = counting
        with pytest.raises(ClientError) as exc:
            ctx.request("GET", "/function/python/missing_job")
        assert exc.value.status == 404
        assert len(calls) == 1


# -- the two-subprocess partition drill --------------------------------------

_CHILD_ENGINE_A = r"""
import os, signal, sys, time
import numpy as np
from learningorchestra_tpu import faults
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.services.context import ServiceContext
from learningorchestra_tpu.services.executor import ExecutorService
from learningorchestra_tpu.services.model import ModelService

cfg = Config.from_env()
cfg.store.backend = "python"
# The acceptance faults: failover + WAL-ship wobble armed for the
# whole drill, and every claim CAS rides an injected delay.
faults.arm("store.ha.failover", "error", rate=1.0)
faults.arm("replica.wal_ship", "delay", delay_ms=5)
faults.arm("cluster.claim", "delay", delay_ms=20)
ctx = ServiceContext(cfg)
model = ModelService(ctx)
ex = ExecutorService(ctx)
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 4)).astype("float32")
y = (x.sum(1) > 0).astype("int32")
model.create(
    "m", module_path="learningorchestra_tpu.models.mlp",
    class_name="MLPClassifier",
    class_parameters={"hidden_layer_sizes": [4], "num_classes": 2},
)
ctx.engine.wait("m", timeout=180)
# Epochs 0-1 run free (and checkpoint); every later epoch's top delays
# 4 s — the parent boots engine B (seconds of imports) BEFORE it kills
# this engine, and the SIGKILL must still land while the fit runs.
faults.arm("train.epoch", "delay", delay_ms=4000, after=2)
ex.create(
    "fit1", parent_name="m", method="fit",
    method_parameters={
        "x": x.tolist(), "y": y.tolist(), "epochs": 6,
        "checkpoint_every": 1, "checkpoint_min_interval_s": 0,
        "checkpoint_async": False,
    },
    artifact_type="train/tensorflow",
)
print("SUBMITTED", flush=True)
time.sleep(600)  # the parent SIGKILLs this engine mid-fit
"""

_CHILD_ENGINE_B = r"""
import json, os, sys, time
from pathlib import Path
from learningorchestra_tpu import faults
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.jobs.journal import JOURNAL_COLLECTION
from learningorchestra_tpu.services.context import ServiceContext

cfg = Config.from_env()
cfg.store.backend = "python"
faults.arm("store.ha.failover", "error", rate=1.0)
faults.arm("replica.wal_ship", "delay", delay_ms=5)
faults.arm("cluster.claim", "delay", delay_ms=20)
ctx = ServiceContext(cfg)
# Boot recovery must NOT have adopted fit1 — engine A is alive and
# holds the live claim.
adopted_early = "fit1" in ctx.engine.running_jobs()
Path(os.environ["DRILL_B_BOOTED"]).write_text("1")
deadline = time.time() + 240
meta = {}
while time.time() < deadline:
    try:
        ctx.documents.refresh("fit1")
    except Exception:
        pass
    meta = ctx.artifacts.metadata.read("fit1") or {}
    if meta.get("finished") or meta.get("jobState") == "failed":
        break
    time.sleep(0.1)
with ctx.cluster.journal_guard():
    finished_events = sum(
        1 for d in ctx.documents.find(JOURNAL_COLLECTION)
        if d.get("docType") == "journal"
        and d.get("job") == "fit1" and d.get("event") == "finished"
    )
hist = ctx.artifacts.ledger.history("fit1")
trace = next(
    (r.get("trace") for r in reversed(hist) if r.get("trace")), None
)
epochs = sorted(
    s["attrs"]["epoch"]
    for s in (trace or {}).get("spans", [])
    if s.get("name") == "epoch"
)
print("RESULT " + json.dumps({
    "jobState": meta.get("jobState"),
    "engineEpoch": meta.get("engineEpoch"),
    "myEpoch": ctx.journal.epoch,
    "adoptedEarly": adopted_early,
    "finishedEvents": finished_events,
    "claimTriggers": faults.triggers("cluster.claim"),
    "epochs": epochs,
}), flush=True)
ctx.close()
"""


def _drill_env(tmp_path, engine_id):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LO_TPU_STORE_ROOT": str(tmp_path / "store"),
        "LO_TPU_VOLUME_ROOT": str(tmp_path / "vol"),
        "LO_TPU_CLUSTER_ENABLED": "1",
        "LO_TPU_CLUSTER_ENGINE_ID": engine_id,
        "LO_TPU_CLUSTER_HEARTBEAT_S": "0.2",
        "LO_TPU_CLUSTER_TTL_S": "1.2",
        "LO_TPU_CLUSTER_SWEEP_S": "0.3",
    })
    env.pop("LO_TPU_WITNESS", None)
    return env


def test_partition_drill_peer_steals_and_resumes(tmp_path):
    """The acceptance drill: two engine processes over one replicated
    store root, engine A SIGKILLed mid-train-fit under armed
    store.ha.failover + replica.wal_ship + cluster.claim faults —
    engine B's sweep steals the expired claim, resumes the fit from
    its newest checkpoint, and the journal records EXACTLY ONE
    terminal publication, stamped with B's engine epoch."""
    booted = tmp_path / "b_booted"
    env_b = _drill_env(tmp_path, "B")
    env_b["DRILL_B_BOOTED"] = str(booted)
    a = subprocess.Popen(
        [sys.executable, "-c", _CHILD_ENGINE_A],
        env=_drill_env(tmp_path, "A"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    b = None
    try:
        marker = (
            tmp_path / "vol" / "_checkpoints" / "fit1" / "latest.json"
        )
        deadline = time.time() + 240
        while time.time() < deadline:
            assert a.poll() is None, (
                "engine A died before the drill",
                a.communicate()[1][-2000:],
            )
            try:
                if json.loads(marker.read_text()).get("step", 0) >= 2:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.02)
        else:
            raise AssertionError("fit1 never reached checkpoint 2")
        b = subprocess.Popen(
            [sys.executable, "-c", _CHILD_ENGINE_B], env=env_b,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        deadline = time.time() + 240
        while not booted.exists():
            assert time.time() < deadline, "engine B never booted"
            assert b.poll() is None, (
                "engine B died at boot", b.communicate()[1][-2000:],
            )
            time.sleep(0.05)
        # Partition: engine A vanishes mid-fit, heartbeats stop.
        a.send_signal(signal.SIGKILL)
        a.wait(timeout=30)
        out, err = b.communicate(timeout=420)
    finally:
        for proc in (a, b):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.communicate()
    assert b.returncode == 0, (out[-2000:], err[-2000:])
    result = json.loads(
        out.split("RESULT ", 1)[1].splitlines()[0]
    )
    assert result["jobState"] == "finished", result
    assert result["adoptedEarly"] is False, result
    assert result["finishedEvents"] == 1, result
    # The terminal commit carries the STEALING engine's epoch (A was
    # epoch 1, B's boot minted 2) — the fence's exactly-once witness.
    assert result["engineEpoch"] == result["myEpoch"] == 2, result
    # Resumed from the newest checkpoint, not restarted: only the
    # tail epochs ran on B.
    assert result["epochs"], "no epoch spans on the resumed run"
    assert min(result["epochs"]) >= 2, result
    assert max(result["epochs"]) == 5, result
    assert len(result["epochs"]) < 6, result
    # The armed claim fault actually rode the drill's claims.
    assert result["claimTriggers"] >= 1, result
