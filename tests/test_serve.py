"""Serving subsystem tests: bucketing, micro-batching, registry
residency, the REST surface (429 backpressure, invalidation), and the
predict compile-count regression (one executable per shape bucket).
"""

import threading
import time

import numpy as np
import pytest
import requests

from learningorchestra_tpu.serve.batcher import MicroBatcher, QueueFull
from learningorchestra_tpu.serve.bucketing import (
    bucket_for,
    bucket_sizes,
    pad_rows,
)
from learningorchestra_tpu.serve.registry import ModelRegistry

PREFIX = "/api/learningOrchestra/v1"


# -- bucketing ---------------------------------------------------------------


class TestBucketing:
    def test_bucket_for_rounds_to_power_of_two(self):
        assert bucket_for(1, 64) == 1
        assert bucket_for(2, 64) == 2
        assert bucket_for(3, 64) == 4
        assert bucket_for(5, 64) == 8
        assert bucket_for(9, 64) == 16
        assert bucket_for(33, 64) == 64
        assert bucket_for(64, 64) == 64

    def test_bucket_for_caps_at_max(self):
        assert bucket_for(100, 64) == 64
        # A non-power-of-two cap is itself a legal bucket.
        assert bucket_for(40, 48) == 48
        assert bucket_for(3, 48) == 4

    def test_bucket_for_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            bucket_for(0, 64)

    def test_bucket_sizes_enumerates_all(self):
        assert bucket_sizes(64) == [1, 2, 4, 8, 16, 32, 64]
        assert bucket_sizes(48) == [1, 2, 4, 8, 16, 32, 48]
        assert bucket_sizes(1) == [1]

    def test_pad_rows_roundtrip(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        padded = pad_rows(x, 8)
        assert padded.shape == (8, 4)
        np.testing.assert_array_equal(padded[:3], x)
        # Pad rows repeat row 0 (in-distribution, outputs discarded).
        np.testing.assert_array_equal(
            padded[3:], np.broadcast_to(x[:1], (5, 4))
        )

    def test_pad_rows_noop_and_errors(self):
        x = np.ones((4, 2), np.float32)
        assert pad_rows(x, 4) is x
        with pytest.raises(ValueError):
            pad_rows(x, 2)  # over the bucket
        with pytest.raises(ValueError):
            pad_rows(np.ones((0, 2), np.float32), 4)


# -- micro-batching ----------------------------------------------------------


class TestMicroBatcher:
    def test_concurrent_requests_coalesce_to_max_batch(self):
        """8 concurrent single-row requests + max_batch=8 + a long
        flush deadline → exactly one padded dispatch, results split
        back per request."""
        seen = []

        def dispatch(padded):
            seen.append(padded.shape[0])
            return padded * 2.0

        mb = MicroBatcher(
            dispatch, max_batch=8, max_queue=64, flush_ms=2000,
            name="t-coalesce",
        )
        try:
            results = {}

            def submit(i):
                results[i] = mb.submit(
                    np.full((1, 3), float(i), np.float32)
                )

            threads = [
                threading.Thread(target=submit, args=(i,))
                for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            # One dispatch of exactly the max batch, no padding needed.
            assert seen == [8]
            for i in range(8):
                np.testing.assert_array_equal(
                    results[i], np.full((1, 3), 2.0 * i, np.float32)
                )
            stats = mb.stats()
            assert stats["batches"] == 1
            assert stats["batchOccupancy"] == 1.0
            assert stats["bucketHistogram"] == {"8": 1}
        finally:
            mb.close()

    def test_flush_deadline_fires_lone_request(self):
        """A lone request must not wait for max_batch: the flush
        deadline dispatches it (padded to bucket 1) after flush_ms."""
        seen = []

        def dispatch(padded):
            seen.append(padded.shape[0])
            return padded + 1.0

        mb = MicroBatcher(
            dispatch, max_batch=64, max_queue=64, flush_ms=30,
            name="t-flush",
        )
        try:
            t0 = time.monotonic()
            out = mb.submit(np.zeros((1, 2), np.float32))
            elapsed = time.monotonic() - t0
            np.testing.assert_array_equal(
                out, np.ones((1, 2), np.float32)
            )
            assert seen == [1]  # bucket 1, not 64
            # It waited (deadline honored) but not forever.
            assert 0.02 <= elapsed < 5.0
        finally:
            mb.close()

    def test_oversized_request_chunks_and_preserves_order(self):
        def dispatch(padded):
            return padded.copy()

        mb = MicroBatcher(
            dispatch, max_batch=4, max_queue=64, flush_ms=1,
            name="t-chunk",
        )
        try:
            x = np.arange(10, dtype=np.float32).reshape(10, 1)
            out = mb.submit(x)
            np.testing.assert_array_equal(out, x)
            # Every dispatch stayed within max_batch's bucket set.
            for bucket in mb.stats()["bucketHistogram"]:
                assert int(bucket) <= 4
        finally:
            mb.close()

    def test_queue_overflow_raises_queue_full(self):
        release = threading.Event()

        def dispatch(padded):
            release.wait(10)
            return padded

        mb = MicroBatcher(
            dispatch, max_batch=1, max_queue=2, flush_ms=0,
            name="t-overflow",
        )
        try:
            threads = [
                threading.Thread(
                    target=mb.submit, args=(np.zeros((1, 1)),),
                    daemon=True,
                )
                for _ in range(3)
            ]
            # First submit is dequeued into the (blocked) dispatch;
            # the next two fill the 2-row queue.
            threads[0].start()
            time.sleep(0.2)
            threads[1].start()
            threads[2].start()
            time.sleep(0.2)
            with pytest.raises(QueueFull):
                mb.submit(np.zeros((1, 1)))
            assert mb.stats()["overflows"] == 1
        finally:
            release.set()
            for t in threads:
                t.join(5)
            mb.close()

    def test_dispatch_error_fails_requests_not_worker(self):
        calls = {"n": 0}

        def dispatch(padded):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("model exploded")
            return padded

        mb = MicroBatcher(
            dispatch, max_batch=4, max_queue=16, flush_ms=0,
            name="t-err",
        )
        try:
            with pytest.raises(RuntimeError, match="model exploded"):
                mb.submit(np.zeros((1, 1), np.float32))
            # The worker survived: the next request succeeds.
            out = mb.submit(np.ones((1, 1), np.float32))
            np.testing.assert_array_equal(
                out, np.ones((1, 1), np.float32)
            )
        finally:
            mb.close()

    def test_close_rejects_new_submits_retriably(self):
        # BatcherClosed subclasses QueueFull so the API layer's 429 +
        # Retry-After path absorbs an unload/predict race — never 500.
        from learningorchestra_tpu.serve.batcher import BatcherClosed

        mb = MicroBatcher(
            lambda p: p, max_batch=2, max_queue=4, flush_ms=0,
            name="t-close",
        )
        mb.close()
        with pytest.raises(QueueFull, match="closed"):
            mb.submit(np.zeros((1, 1)))
        with pytest.raises(BatcherClosed):
            mb.submit(np.zeros((1, 1)))


# -- registry ----------------------------------------------------------------


class _FakeEstimator:
    """Duck-typed NeuralEstimator: params tree + module tag."""

    class _Module:
        pass

    def __init__(self, n_floats: int):
        self.params = {"w": np.ones((n_floats,), np.float32)}
        self.module = self._Module()


class TestModelRegistry:
    def _registry(self, sizes: dict, **kw):
        loads = []

        def loader(name):
            loads.append(name)
            return _FakeEstimator(sizes[name])

        return ModelRegistry(loader, **kw), loads

    def test_load_is_cached_and_counts_bytes(self):
        reg, loads = self._registry({"a": 256}, max_models=4)
        entry = reg.get("a")
        assert entry.nbytes == 256 * 4
        reg.get("a")
        assert loads == ["a"]  # one artifact read, one upload
        assert reg.stats()["residentModels"] == 1
        assert reg.stats()["residentBytes"] == 1024

    def test_lru_evicts_by_model_count(self):
        reg, _ = self._registry(
            {"a": 8, "b": 8, "c": 8}, max_models=2
        )
        reg.get("a"), reg.get("b")
        reg.get("a")          # refresh a → b is now LRU
        reg.get("c")          # evicts b
        assert {e["name"] for e in reg.list()} == {"a", "c"}
        assert reg.evictions == 1

    def test_lru_evicts_by_byte_cap(self):
        # 1024 floats = 4096 bytes each; cap at 6000 → only one fits.
        reg, _ = self._registry(
            {"a": 1024, "b": 1024}, max_models=8, max_bytes=6000
        )
        reg.get("a")
        reg.get("b")
        assert [e["name"] for e in reg.list()] == ["b"]
        assert reg.evictions == 1

    def test_on_evict_callback_fires_per_victim(self):
        evicted = []
        reg, _ = self._registry(
            {"a": 8, "b": 8, "c": 8}, max_models=2,
            on_evict=evicted.append,
        )
        reg.get("a"), reg.get("b"), reg.get("c")
        assert evicted == ["a"]

    def test_invalidate_forces_reload(self):
        reg, loads = self._registry({"a": 8}, max_models=4)
        reg.get("a")
        assert reg.invalidate("a") is True
        assert reg.invalidate("a") is False  # already gone
        reg.get("a")
        assert loads == ["a", "a"]
        assert reg.stats()["invalidations"] == 1

    def test_invalidate_during_inflight_load_is_not_cached(self):
        """An artifact overwrite/delete racing a slow load must doom
        that load's result: the caller gets its one answer, but the
        possibly-superseded weights never become resident."""
        gate = threading.Event()
        loads = []

        def loader(name):
            loads.append(name)
            gate.wait(5)
            return _FakeEstimator(8)

        reg = ModelRegistry(loader, max_models=4)
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault("entry", reg.get("a"))
        )
        t.start()
        time.sleep(0.1)  # loader is now parked inside gate.wait
        assert reg.invalidate("a") is True  # in-flight load → doomed
        gate.set()
        t.join(5)
        assert out["entry"] is not None  # the caller was still served
        assert reg.peek("a") is None     # but nothing was cached
        reg.get("a")
        assert loads == ["a", "a"]       # next request reloaded fresh

    def test_unload_and_peek(self):
        reg, _ = self._registry({"a": 8}, max_models=4)
        assert reg.peek("a") is None
        reg.get("a")
        assert reg.peek("a") is not None
        assert reg.unload("a") is True
        assert reg.unload("a") is False

    def test_no_params_is_a_serve_error(self):
        from learningorchestra_tpu.serve.registry import ServeError

        est = _FakeEstimator(4)
        est.params = None
        reg = ModelRegistry(lambda name: est, max_models=2)
        with pytest.raises(ServeError, match="no trained parameters"):
            reg.get("a")
        # The failed load must not wedge the coalescing event.
        with pytest.raises(ServeError):
            reg.get("a")


# -- predict compile-count regression ----------------------------------------


class TestPredictCompileBuckets:
    def test_predict_compiles_per_bucket_not_per_tail(self):
        """The old predict dispatched the ragged tail at its own shape:
        every distinct tail length re-traced apply.  Now tails pad to
        their power-of-two bucket, so compile-cache misses are bounded
        by the bucket set of the batch size — never by tail diversity
        — and a full-multiple predict compiles exactly ONE shape per
        batch size."""
        import jax.numpy as jnp

        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()  # isolate the miss counter from other tests
        est = MLPClassifier(
            hidden_layer_sizes=[7], num_classes=3, seed=0
        )
        est.compute_dtype = "float32"
        rng = np.random.default_rng(0)
        x = rng.standard_normal((100, 5)).astype(np.float32)
        est._init_params(jnp.asarray(x[:1]))

        before = cc.counters_snapshot()
        # Full multiple: ONE shape (the batch size itself).
        out = est.predict(x[:64], batch_size=32)
        assert out.shape == (64, 3)
        d1 = cc.delta_since(before)
        assert d1["misses"] == 1

        # Ragged tails land on buckets, not bespoke shapes: tail 4 →
        # bucket 4 (one new compile)...
        est.predict(x[:68], batch_size=32)
        d2 = cc.delta_since(before)
        assert d2["misses"] == 2
        # ...tail 26 → bucket 32, already compiled; tail 3 → bucket 4,
        # already compiled.  Zero new misses for new tail lengths.
        est.predict(x[:90], batch_size=32)
        est.predict(x[:67], batch_size=32)
        assert cc.delta_since(before)["misses"] == 2

        # Whole-deployment bound: a fresh estimator of the SAME
        # architecture resolves every bucket from the cache.
        est2 = MLPClassifier(
            hidden_layer_sizes=[7], num_classes=3, seed=1
        )
        est2.compute_dtype = "float32"
        est2._init_params(jnp.asarray(x[:1]))
        mid = cc.counters_snapshot()
        est2.predict(x[:68], batch_size=32)
        assert cc.delta_since(mid)["misses"] == 0

        # And the padded tail's values match an unpadded reference.
        ref = np.asarray(est.module.apply(est.params, jnp.asarray(x[:68])))
        np.testing.assert_allclose(
            est.predict(x[:68], batch_size=32), ref, rtol=1e-5,
            atol=1e-6,
        )

    def test_coalesced_serving_compiles_per_bucket_not_per_request(self):
        """The serving dispatch shape — a MicroBatcher whose dispatch
        resolves ``apply`` from the compile cache by padded bucket —
        under 8 concurrent clients: however the 64 requests coalesce,
        the programs built are at most the bucket set."""
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        est = MLPClassifier(hidden_layer_sizes=[16], num_classes=8)
        est.compute_dtype = "float32"
        rng = np.random.default_rng(0)
        row = rng.standard_normal((1, 8)).astype(np.float32)
        est._init_params(jnp.asarray(row))
        params, module = est.params, est.module
        want = np.asarray(module.apply(params, jnp.asarray(row)))

        def dispatch(padded):
            apply = cc.get_cache().get_or_build(
                cc.apply_program_key(module, rows=padded.shape[0]),
                lambda: jax.jit(module.apply),
                label=f"t-serve:b{padded.shape[0]}",
            )
            return apply(params, jnp.asarray(padded))

        before = cc.counters_snapshot()
        mb = MicroBatcher(
            dispatch, max_batch=8, max_queue=256, flush_ms=2.0,
            name="t-buckets",
        )
        errors = []

        def client():
            try:
                for _ in range(8):
                    np.testing.assert_allclose(
                        mb.submit(row), want, rtol=1e-5, atol=1e-6
                    )
            except Exception as exc:  # noqa: BLE001 - re-raised below
                errors.append(exc)

        try:
            threads = [threading.Thread(target=client) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            assert not errors, errors[0]
            stats = mb.stats()
        finally:
            mb.close()
        buckets = bucket_sizes(8)
        assert 1 <= cc.delta_since(before)["misses"] <= len(buckets) == 4
        assert set(stats["bucketHistogram"]) <= {str(b) for b in buckets}
        assert 0 < stats["batchOccupancy"] <= 1


# -- REST surface ------------------------------------------------------------


def _install_trained_model(server, name):
    """Fabricate a finished train artifact holding a fitted estimator
    (bypasses the async job pipeline — serving is what's under test)."""
    import jax.numpy as jnp

    from learningorchestra_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=0)
    est.compute_dtype = "float32"
    est.fit(x, y, epochs=1, batch_size=32)
    server.ctx.volumes.save_object("train/tensorflow", name, est)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)
    _ = jnp  # keep the lazy import explicit
    return est, x


@pytest.fixture(scope="module")
def serve_api(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("serve_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    cfg.serve.max_batch = 8
    cfg.serve.flush_ms = 1.0
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    yield server, base, tmp
    server.shutdown()


class TestServeRest:
    def test_load_predict_unload_roundtrip(self, serve_api):
        server, base, _ = serve_api
        est, x = _install_trained_model(server, "srv_round")

        resp = requests.post(f"{base}/serve/srv_round/load", json={})
        assert resp.status_code == 200, resp.text
        assert resp.json()["result"]["name"] == "srv_round"

        listed = requests.get(f"{base}/serve").json()
        assert "srv_round" in {m["name"] for m in listed["models"]}

        resp = requests.post(
            f"{base}/serve/srv_round/predict",
            json={"instances": x[:5].tolist()},
        )
        assert resp.status_code == 200, resp.text
        body = resp.json()
        assert body["model"] == "srv_round"
        preds = np.asarray(body["predictions"], np.float32)
        assert preds.shape == (5, 2)
        import jax.numpy as jnp

        ref = np.asarray(est.module.apply(est.params, jnp.asarray(x[:5])))
        np.testing.assert_allclose(preds, ref, rtol=1e-4, atol=1e-5)
        assert body["latencyMs"] >= 0

        resp = requests.post(f"{base}/serve/srv_round/unload", json={})
        assert resp.status_code == 200
        resp = requests.post(f"{base}/serve/srv_round/unload", json={})
        assert resp.status_code == 404
        # Predict auto-reloads after an unload.
        resp = requests.post(
            f"{base}/serve/srv_round/predict",
            json={"instances": x[:1].tolist()},
        )
        assert resp.status_code == 200

    def test_predict_missing_model_404(self, serve_api):
        _, base, _ = serve_api
        resp = requests.post(
            f"{base}/serve/no_such_model/predict",
            json={"instances": [[0.0, 0.0, 0.0, 0.0]]},
        )
        assert resp.status_code == 404

    def test_predict_missing_instances_406(self, serve_api):
        server, base, _ = serve_api
        _install_trained_model(server, "srv_noinst")
        resp = requests.post(
            f"{base}/serve/srv_noinst/predict", json={}
        )
        assert resp.status_code == 406

    def test_ragged_instances_406(self, serve_api):
        server, base, _ = serve_api
        _install_trained_model(server, "srv_ragged")
        resp = requests.post(
            f"{base}/serve/srv_ragged/predict",
            json={"instances": [[1.0, 2.0], [3.0]]},
        )
        assert resp.status_code == 406, resp.text

    def test_non_neural_artifact_406(self, serve_api):
        server, base, _ = serve_api
        server.ctx.volumes.save_object(
            "train/tensorflow", "srv_blob", {"not": "a model"}
        )
        server.ctx.artifacts.metadata.create(
            "srv_blob", "train/tensorflow"
        )
        server.ctx.artifacts.metadata.mark_finished("srv_blob")
        resp = requests.post(
            f"{base}/serve/srv_blob/predict",
            json={"instances": [[1.0]]},
        )
        assert resp.status_code == 406

    def test_delete_invalidates_resident_model(self, serve_api):
        server, base, _ = serve_api
        _, x = _install_trained_model(server, "srv_gone")
        resp = requests.post(
            f"{base}/serve/srv_gone/predict",
            json={"instances": x[:1].tolist()},
        )
        assert resp.status_code == 200
        assert server.serving.registry.peek("srv_gone") is not None
        server.ctx.delete_artifact("srv_gone")
        # The change listener dropped the resident weights...
        assert server.serving.registry.peek("srv_gone") is None
        # ...and the reload path 404s (artifact really gone).
        resp = requests.post(
            f"{base}/serve/srv_gone/predict",
            json={"instances": x[:1].tolist()},
        )
        assert resp.status_code == 404

    def test_monitoring_endpoint_and_tfevents(self, serve_api):
        server, base, tmp = serve_api
        _, x = _install_trained_model(server, "srv_mon")
        requests.post(
            f"{base}/serve/srv_mon/predict",
            json={"instances": x[:3].tolist()},
        )
        resp = requests.get(f"{base}/monitoring/tensorflow/serving")
        assert resp.status_code == 200
        body = resp.json()
        assert body["registry"]["residentModels"] >= 1
        model_stats = body["models"]["srv_mon"]
        assert model_stats["requests"] >= 1
        assert {"p50", "p95", "p99"} <= set(model_stats["latencyMs"])
        assert body["scalars"]["serving_requests"] >= 1
        # serving_* scalars landed as a real tfevents file.
        logdir = tmp / "volumes" / "_monitoring" / "serving"
        assert list(logdir.glob("events.out.tfevents.*"))

    def test_queue_overflow_429_with_retry_after(self, tmp_path):
        """Dedicated tiny-queue server: one request parked inside the
        flush window fills the 1-row queue; the next gets 429 with a
        Retry-After header (and the parked one still answers 200)."""
        from learningorchestra_tpu.api import APIServer
        from learningorchestra_tpu.config import Config

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        cfg.serve.max_batch = 4      # > queued rows: flush wait applies
        cfg.serve.max_queue = 1
        cfg.serve.flush_ms = 700.0   # park the first request
        cfg.serve.retry_after_s = 2.5
        server = APIServer(cfg)
        try:
            port = server.start_background()
            base = f"http://127.0.0.1:{port}{PREFIX}"
            _, x = _install_trained_model(server, "srv_backpressure")
            # Warm the load + compile OUTSIDE the timed window so the
            # parked request is parked by the flush deadline only.
            requests.post(f"{base}/serve/srv_backpressure/load", json={})

            first: dict = {}

            def parked():
                first["resp"] = requests.post(
                    f"{base}/serve/srv_backpressure/predict",
                    json={"instances": x[:1].tolist()},
                )

            t = threading.Thread(target=parked)
            t.start()
            time.sleep(0.25)  # let it enqueue (queue now full)
            resp = requests.post(
                f"{base}/serve/srv_backpressure/predict",
                json={"instances": x[:1].tolist()},
            )
            assert resp.status_code == 429, resp.text
            assert resp.headers["Retry-After"] == "2.5"
            assert resp.json()["retryAfter"] == 2.5
            t.join(15)
            assert first["resp"].status_code == 200
        finally:
            server.shutdown()
