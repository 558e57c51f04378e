"""Compiled-program cache (train/compile_cache.py): trace once, run many.

Covers the ISSUE 1 acceptance surface: fingerprint stability (same spec
hits; changed dtype/batch-shape/mesh misses), LRU eviction order, the
byte-estimate cap, invalidation on device-set change, estimator-level
reuse across fresh instances, the executor-level contract (a second
identical train job and all same-arch tune candidates report cache
hits), the engine's warm-start dispatch preference, and the monitoring
endpoint.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest


def _mlp(hidden=(4,), num_classes=2, **kw):
    from learningorchestra_tpu.models.mlp import MLPClassifier

    return MLPClassifier(
        hidden_layer_sizes=list(hidden), num_classes=num_classes, **kw
    )


def _key_for(est, *, loss="softmax_ce", dtype=None, shapes=(64, 32, True),
             mesh=None):
    from learningorchestra_tpu.train import compile_cache as cc

    return cc.program_key(
        "device_epoch",
        module=cc.module_fingerprint(est.module),
        optimizer=cc.optimizer_fingerprint(est),
        loss=loss,
        dtype=dtype if dtype is not None else est.compute_dtype,
        shapes=shapes,
        mesh=mesh,
    )


class TestFingerprints:
    def test_same_spec_same_key(self):
        # Two FRESH estimator instances (the repeated-REST-job shape)
        # fingerprint identically.
        assert _key_for(_mlp()) == _key_for(_mlp())

    def test_seed_not_part_of_program(self):
        # PRNG keys are runtime arguments, not trace constants: a tune
        # sweep over seeds shares one program.
        assert _key_for(_mlp(seed=1)) == _key_for(_mlp(seed=2))

    def test_changed_arch_misses(self):
        assert _key_for(_mlp(hidden=(4,))) != _key_for(_mlp(hidden=(8,)))

    def test_changed_optimizer_misses(self):
        assert _key_for(_mlp(learning_rate=1e-3)) != _key_for(
            _mlp(learning_rate=3e-4)
        )

    def test_changed_dtype_misses(self):
        est = _mlp()
        assert _key_for(est, dtype="bfloat16") != _key_for(
            est, dtype="float32"
        )

    def test_changed_batch_shape_misses(self):
        est = _mlp()
        assert _key_for(est, shapes=(64, 32, True)) != _key_for(
            est, shapes=(64, 16, True)
        )

    def test_changed_mesh_misses(self):
        import jax
        from jax.sharding import Mesh

        from learningorchestra_tpu.train import compile_cache as cc

        devs = np.array(jax.devices()[:4])
        m_flat = Mesh(devs.reshape(4, 1), ("dp", "tp"))
        m_square = Mesh(devs.reshape(2, 2), ("dp", "tp"))
        est = _mlp()
        assert _key_for(est, mesh=cc.mesh_fingerprint(m_flat)) != _key_for(
            est, mesh=cc.mesh_fingerprint(m_square)
        )
        # Same layout on a DIFFERENT device assignment must also miss —
        # executables pin device handles.
        m_other = Mesh(np.array(jax.devices()[4:8]).reshape(4, 1),
                       ("dp", "tp"))
        assert cc.mesh_fingerprint(m_flat) != cc.mesh_fingerprint(m_other)

    def test_opaque_optimizer_never_false_hits(self):
        import optax

        from learningorchestra_tpu.train import compile_cache as cc

        a = _mlp()
        b = _mlp()
        a.compile(optimizer=optax.adam(1e-3))
        b.compile(optimizer=optax.adam(1e-3))
        # No declarative spec — identity-keyed, so two objects never
        # collide (correct, merely uncached across jobs).
        assert cc.optimizer_fingerprint(a) != cc.optimizer_fingerprint(b)


class TestLRU:
    def test_eviction_order_is_lru(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(max_entries=2)
        cache.get_or_build("k1", lambda: "v1")
        cache.get_or_build("k2", lambda: "v2")
        assert cache.get_or_build("k1", lambda: "WRONG") == "v1"  # refresh
        cache.get_or_build("k3", lambda: "v3")  # evicts k2, not k1
        assert cache.contains("k1")
        assert cache.contains("k3")
        assert not cache.contains("k2")
        stats = cache.stats()
        assert stats["evictions"] == 1
        assert stats["hits"] == 1
        assert stats["misses"] == 3

    def test_byte_estimate_cap_evicts(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(
            max_entries=10, max_bytes=100, entry_bytes=60
        )
        cache.get_or_build("k1", lambda: "v1")
        cache.get_or_build("k2", lambda: "v2")  # 120 est. bytes > 100
        assert not cache.contains("k1")
        assert cache.contains("k2")

    def test_disabled_cache_always_builds(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(max_entries=0)
        assert cache.get_or_build("k", lambda: 1) == 1
        assert cache.get_or_build("k", lambda: 2) == 2
        assert cache.stats()["hits"] == 0

    def test_failed_build_not_cached_and_releases_waiters(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(max_entries=4)
        with pytest.raises(RuntimeError):
            cache.get_or_build("k", lambda: (_ for _ in ()).throw(
                RuntimeError("trace failed")
            ))
        assert not cache.contains("k")
        assert cache.get_or_build("k", lambda: "ok") == "ok"

    def test_concurrent_same_key_builds_once(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(max_entries=4)
        builds = []
        gate = threading.Event()

        def builder():
            gate.wait(5)
            builds.append(1)
            time.sleep(0.02)
            return "v"

        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(
                    cache.get_or_build("k", builder)
                )
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(10)
        assert results == ["v"] * 4
        assert len(builds) == 1  # one trace, three coalesced hits
        stats = cache.stats()
        assert stats["misses"] == 1
        assert stats["hits"] == 3


class TestDeviceInvalidation:
    def test_device_set_change_clears_cache(self, monkeypatch):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(max_entries=4)
        cache.get_or_build("k", lambda: "v")
        assert cache.contains("k")
        # The visible device set changes (TPU runtime restart):
        # every cached executable pins dead handles.
        monkeypatch.setattr(
            cc, "_device_signature", lambda: ((99, "tpu"),)
        )
        assert cache.get_or_build("k", lambda: "rebuilt") == "rebuilt"
        assert cache.stats()["deviceInvalidations"] == 1


class TestReviewHardening:
    def test_in_flight_build_not_cached_across_device_change(
        self, monkeypatch
    ):
        from learningorchestra_tpu.train import compile_cache as cc

        cache = cc.CompiledProgramCache(max_entries=4)
        started, release = threading.Event(), threading.Event()
        result = {}

        def slow_builder():
            started.set()
            release.wait(5)
            return "stale"

        t = threading.Thread(
            target=lambda: result.setdefault(
                "v", cache.get_or_build("k", slow_builder)
            )
        )
        t.start()
        assert started.wait(5)
        # Device set changes WHILE the build is in flight: the built
        # program may pin dead handles — serve it to its one caller
        # but never cache it.
        monkeypatch.setattr(
            cc, "_device_signature", lambda: ((123, "tpu"),)
        )
        cache.get_or_build("other", lambda: "fresh")  # triggers clear
        release.set()
        t.join(5)
        assert result["v"] == "stale"
        assert not cache.contains("k")
        assert cache.contains("other")

    def test_enabled_reflects_entry_cap(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache(max_entries=0)
        try:
            assert not cc.enabled()
        finally:
            cc.reset_cache()
        assert cc.enabled()

    def test_reserved_monitoring_nickname_rejected(self, tmp_path):
        from learningorchestra_tpu.services.monitoring import (
            MonitoringError,
            MonitoringService,
        )

        svc = MonitoringService(str(tmp_path))
        assert not svc.valid_nickname("compileCache")
        assert not svc.valid_nickname("compile_cache")
        assert svc.valid_nickname("my_run")
        with pytest.raises(MonitoringError):
            svc.start("compileCache", spawn_tensorboard=False)

    def test_context_close_deregisters_invalidation_listener(
        self, tmp_path
    ):
        from learningorchestra_tpu.config import Config
        from learningorchestra_tpu.services.context import ServiceContext
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        cache = cc.get_cache()
        n0 = len(cache._invalidation_listeners)
        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        ctx = ServiceContext(cfg)
        assert len(cache._invalidation_listeners) == n0 + 1
        ctx.close()
        assert len(cache._invalidation_listeners) == n0


class TestEstimatorReuse:
    def test_second_fresh_estimator_fit_traces_nothing(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)

        def one_job():
            est = _mlp()
            t0 = time.perf_counter()
            est.fit(x, y, epochs=1, batch_size=16)
            return time.perf_counter() - t0

        before = cc.counters_snapshot()
        cold_s = one_job()
        mid = cc.counters_snapshot()
        assert mid["misses"] - before["misses"] >= 1
        warm_s = one_job()
        delta = cc.delta_since(mid)
        # EXACTLY one trace across both jobs: the warm job misses
        # nothing and resolves every program from the cache.
        assert delta["misses"] == 0
        assert delta["hits"] >= 1
        # Warm submit→first-step strictly below cold (the acceptance
        # latency claim; on CPU the gap is 10-100x, so the comparison
        # is not flaky).
        assert warm_s < cold_s

    def test_compile_new_optimizer_misses_then_hits(self):
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        est = _mlp()
        est.fit(x, y, epochs=1, batch_size=16)
        before = cc.counters_snapshot()
        # compile() invalidates per-instance refs AND changes the
        # program fingerprint — the refit re-traces...
        est.compile(optimizer="sgd", learning_rate=1e-2)
        est.fit(x, y, epochs=1, batch_size=16)
        assert cc.delta_since(before)["misses"] >= 1
        # ...and a second estimator with the SAME new spec hits.
        mid = cc.counters_snapshot()
        est2 = _mlp()
        est2.compile(optimizer="sgd", learning_rate=1e-2)
        est2.fit(x, y, epochs=1, batch_size=16)
        delta = cc.delta_since(mid)
        assert delta["misses"] == 0
        assert delta["hits"] >= 1


class TestExecutorLevel:
    @pytest.fixture()
    def ctx(self, tmp_path):
        from learningorchestra_tpu.config import Config
        from learningorchestra_tpu.services.context import ServiceContext

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        ctx = ServiceContext(cfg)
        yield ctx
        ctx.close()

    @staticmethod
    def _fit_data():
        rng = np.random.default_rng(0)
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        return {"x": x.tolist(), "y": y.tolist(), "epochs": 1,
                "batch_size": 16}

    def _make_model(self, ctx, name):
        from learningorchestra_tpu.services.model import ModelService

        ModelService(ctx).create(
            name,
            module_path="learningorchestra_tpu.models.mlp",
            class_name="MLPClassifier",
            class_parameters={"hidden_layer_sizes": [4],
                              "num_classes": 2},
        )
        ctx.engine.wait(name, timeout=60)

    def test_second_identical_train_job_reports_hits(self, ctx):
        from learningorchestra_tpu.services.executor import ExecutorService
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        self._make_model(ctx, "cc_mlp")
        executor = ExecutorService(ctx)
        params = self._fit_data()
        executor.create("cc_fit1", parent_name="cc_mlp", method="fit",
                        method_parameters=params)
        ctx.engine.wait("cc_fit1", timeout=120)
        meta1 = ctx.artifacts.metadata.read("cc_fit1")
        assert meta1["jobState"] == "finished", meta1.get("exception")
        assert meta1["compileCache"]["misses"] >= 1

        executor.create("cc_fit2", parent_name="cc_mlp", method="fit",
                        method_parameters=params)
        ctx.engine.wait("cc_fit2", timeout=120)
        meta2 = ctx.artifacts.metadata.read("cc_fit2")
        assert meta2["jobState"] == "finished", meta2.get("exception")
        # Exactly one trace across both jobs: the second submits into
        # a warm cache and traces NOTHING.
        assert meta2["compileCache"]["misses"] == 0
        assert meta2["compileCache"]["hits"] >= 1

    def test_same_arch_tune_candidates_all_hit(self, ctx):
        from learningorchestra_tpu.services.executor import ExecutorService
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        self._make_model(ctx, "cc_tune_mlp")
        executor = ExecutorService(ctx)
        executor.create_tune(
            "cc_tune",
            parent_name="cc_tune_mlp",
            param_grid={"seed": [1, 2, 3]},  # same arch, every trial
            method_parameters=self._fit_data(),
        )
        ctx.engine.wait("cc_tune", timeout=300)
        meta = ctx.artifacts.metadata.read("cc_tune")
        assert meta["jobState"] == "finished", meta.get("exception")
        delta = meta["compileCache"]
        # One trace per program kind regardless of candidate count
        # (concurrent candidates coalesce onto the single build);
        # every other candidate resolves from the cache.
        assert delta["misses"] <= 2
        assert delta["hits"] >= 2 * (3 - 1)


class TestWarmStartDispatch:
    def test_warm_job_dispatches_before_cold_within_class(self, artifacts):
        from learningorchestra_tpu.jobs import JobEngine

        engine = JobEngine(artifacts, max_workers=1)
        try:
            order = []
            release = threading.Event()
            for name in ("blocker", "cold_a", "cold_b", "warm_j"):
                artifacts.metadata.create(name, "train/x")

            def blocker():
                release.wait(10)
                return "blocked"

            engine.submit("blocker", blocker, job_class="t")
            time.sleep(0.1)  # let the blocker occupy the only worker
            engine.submit("cold_a", lambda: order.append("cold_a"),
                          job_class="t", warm_key="prog:cold")
            engine.submit("cold_b", lambda: order.append("cold_b"),
                          job_class="t", warm_key="prog:cold")
            engine.submit("warm_j", lambda: order.append("warm_j"),
                          job_class="t", warm_key="prog:warm")
            engine.note_warm("prog:warm")
            release.set()
            for name in ("cold_a", "cold_b", "warm_j"):
                engine.wait(name, timeout=10)
            # The warm job queued LAST but dispatched FIRST: its
            # compiled programs are cached, so the freed worker starts
            # stepping instead of tracing.
            assert order[0] == "warm_j"
            assert set(order) == {"warm_j", "cold_a", "cold_b"}
        finally:
            engine.shutdown(wait=True)

    def test_warm_bypass_is_bounded_no_cold_starvation(self, artifacts):
        from learningorchestra_tpu.jobs import JobEngine

        engine = JobEngine(artifacts, max_workers=1)
        try:
            order = []
            release = threading.Event()
            names = ["blocker", "cold"] + [f"warm{i}" for i in range(8)]
            for name in names:
                artifacts.metadata.create(name, "train/x")
            engine.submit("blocker", lambda: release.wait(10),
                          job_class="t")
            time.sleep(0.1)
            engine.submit("cold", lambda: order.append("cold"),
                          job_class="t", warm_key="prog:cold")
            for i in range(8):
                engine.submit(
                    f"warm{i}",
                    lambda i=i: order.append(f"warm{i}"),
                    job_class="t", warm_key="prog:warm",
                )
            engine.note_warm("prog:warm")
            release.set()
            for name in names[1:]:
                engine.wait(name, timeout=10)
            # Warm jobs may jump the cold FIFO head at most
            # _max_warm_bypass (4) consecutive times — then the cold
            # job runs.  Never starved by the sustained warm stream.
            assert order.index("cold") <= engine._max_warm_bypass
        finally:
            engine.shutdown(wait=True)

    def test_device_invalidation_drops_warm_hints(self, tmp_path,
                                                  monkeypatch):
        from learningorchestra_tpu.config import Config
        from learningorchestra_tpu.services.context import ServiceContext
        from learningorchestra_tpu.train import compile_cache as cc

        cc.reset_cache()
        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        ctx = ServiceContext(cfg)
        try:
            cache = cc.get_cache()
            cache.get_or_build("k", lambda: "v")  # pin device signature
            ctx.engine.note_warm("prog:x")
            assert "prog:x" in ctx.engine._warm_keys
            monkeypatch.setattr(
                cc, "_device_signature", lambda: ((77, "tpu"),)
            )
            cache.get_or_build("k2", lambda: "v2")  # triggers clear
            # Stale hints dropped with the cache: a 'warm' job would
            # now trace like any other.
            assert not ctx.engine._warm_keys
        finally:
            ctx.close()

    def test_note_warm_is_bounded_and_null_safe(self, artifacts):
        from learningorchestra_tpu.jobs import JobEngine

        engine = JobEngine(artifacts, max_workers=1)
        try:
            engine.note_warm(None)  # no-op, never raises
            engine._max_warm_keys = 4
            for i in range(10):
                engine.note_warm(f"k{i}")
            assert len(engine._warm_keys) == 4
            assert "k9" in engine._warm_keys
            assert "k0" not in engine._warm_keys
        finally:
            engine.shutdown(wait=True)


class TestMonitoringSurface:
    def test_monitoring_service_exposes_stats(self, tmp_path):
        from learningorchestra_tpu.services.monitoring import (
            MonitoringService,
        )

        stats = MonitoringService(str(tmp_path)).compile_cache_stats()
        for key in ("hits", "misses", "evictions", "traceTimeS",
                    "entries"):
            assert key in stats

    def test_endpoint_serves_compile_cache_counters(self, tmp_path):
        import json
        import urllib.request

        from learningorchestra_tpu.api.server import APIServer
        from learningorchestra_tpu.config import Config

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        server = APIServer(cfg)
        port = server.start_background()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/learningOrchestra/v1"
                "/monitoring/tensorflow/compileCache"
            ) as resp:
                assert resp.status == 200
                stats = json.loads(resp.read())
            for key in ("hits", "misses", "evictions", "traceTimeS"):
                assert key in stats
        finally:
            server.shutdown()
