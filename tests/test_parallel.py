"""Parallel layer tests on the 8-virtual-device CPU mesh (conftest.py).

This is the fake-backend story the reference never had (SURVEY §4): mesh
construction, sharded data-parallel training vs. the single-device loop,
ring attention vs. the unsharded oracle, and the coordinator/agent
control plane.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full tier only

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from learningorchestra_tpu.parallel import (
    DistributedTrainer,
    MeshSpec,
    build_mesh,
    default_spec,
    ring_attention,
)
from learningorchestra_tpu.parallel.distributed import distributed_fit
from learningorchestra_tpu.parallel.mesh import spec_for_devices
from learningorchestra_tpu.parallel.ring_attention import (
    reference_attention,
)
from learningorchestra_tpu.parallel.sharding import (
    batch_sharding,
    param_shardings,
)


# -- mesh -------------------------------------------------------------------


def test_default_spec_uses_all_devices():
    spec = default_spec()
    assert spec.size == jax.device_count() == 8


def test_build_mesh_shapes():
    mesh = build_mesh(MeshSpec(dp=2, tp=2, sp=2))
    assert dict(mesh.shape) == {
        "dp": 2, "fsdp": 1, "pp": 1, "ep": 1, "tp": 2, "sp": 2,
    }


def test_build_mesh_folds_spare_devices_into_dp():
    mesh = build_mesh(MeshSpec(dp=1, tp=2))
    assert mesh.shape["dp"] == 4  # 8 devices / tp=2


def test_build_mesh_rejects_oversize():
    with pytest.raises(ValueError):
        build_mesh(MeshSpec(dp=16))


def test_spec_for_devices():
    spec = spec_for_devices(8, model_parallel=2, sequence_parallel=2)
    assert (spec.dp, spec.tp, spec.sp) == (2, 2, 2)


# -- shardings --------------------------------------------------------------


def test_param_shardings_tp_and_replication():
    mesh = build_mesh(MeshSpec(dp=2, tp=2, sp=2))
    params = {
        "dense": {"kernel": jnp.zeros((16, 8)), "bias": jnp.zeros((8,))},
        "embed": {"embedding": jnp.zeros((100, 8))},
    }
    sh = param_shardings(params, mesh)
    assert sh["dense"]["kernel"].spec == P(None, "tp")  # 16 % fsdp=1
    assert sh["dense"]["bias"].spec == P()
    assert sh["embed"]["embedding"].spec == P("tp", None)


def test_batch_sharding_seq_axis():
    mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
    sh = batch_sharding(mesh, seq_axis=1)
    assert sh.spec == P(("dp", "fsdp"), "sp")


# -- distributed training ---------------------------------------------------


def _toy_problem(n=256, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, classes))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def test_distributed_fit_learns_and_matches_contract():
    from learningorchestra_tpu.models.mlp import MLPClassifier

    x, y = _toy_problem()
    est = MLPClassifier(
        hidden_layer_sizes=(16,), num_classes=4, seed=1, learning_rate=1e-2
    )
    trainer = DistributedTrainer(est, spec=MeshSpec(dp=8))
    trainer.fit(x, y, epochs=30, batch_size=64)
    # state handed back to the estimator: single-device predict works
    acc = est.score(x, y)
    assert acc > 0.8
    assert trainer.history["samples_per_sec"]
    assert "accuracy" in trainer.history


def test_distributed_early_stopping():
    """The distributed surface honors the same early_stopping spec as
    the single-device fit."""
    from learningorchestra_tpu.models.mlp import MLPClassifier

    x, y = _toy_problem()
    est = MLPClassifier(
        hidden_layer_sizes=(16,), num_classes=4, seed=1, learning_rate=0.0
    )
    trainer = DistributedTrainer(est, spec=MeshSpec(dp=8))
    trainer.fit(
        x, y, epochs=20, batch_size=64,
        early_stopping={"monitor": "loss", "patience": 2},
    )
    # lr 0: epoch 0 best, epochs 1-2 don't improve -> exactly 3 run,
    # and the stitched estimator history matches the actual count.
    assert len(trainer.history["loss"]) == 3
    assert len(est.history["loss"]) == 3


def test_distributed_restore_best_weights():
    """restoreBestWeights on the mesh-sharded fit: the best epoch's
    params are snapshotted device-side (sharded jnp.copy) and rolled
    back on stop; the moments are dropped (they belong to later
    epochs), matching the single-device contract."""
    import jax as _jax
    import jax.numpy as _jnp

    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.train.neural import EarlyStopping

    x, y = _toy_problem()
    # A huge learning rate makes later epochs WORSE, so the restored
    # best must differ measurably from the final epoch's params.
    est = MLPClassifier(
        hidden_layer_sizes=(16,), num_classes=4, seed=1, learning_rate=5.0
    )
    cb = EarlyStopping(monitor="loss", patience=2,
                       restore_best_weights=True)
    seen = {}

    def record(epoch, metrics, model):
        # Runs BEFORE the EarlyStopping callback each epoch, so it
        # captures that epoch's params pre-rollback.
        seen[epoch] = _jax.tree_util.tree_map(_jnp.copy, model.params)

    trainer = DistributedTrainer(est, spec=MeshSpec(dp=8))
    trainer.fit(x, y, epochs=20, batch_size=64, callbacks=[record, cb])
    assert cb.best_epoch is not None
    last_epoch = max(seen)
    assert cb.best_epoch < last_epoch  # lr 5.0: later epochs got worse
    best = _jax.tree_util.tree_leaves(_jax.device_get(seen[cb.best_epoch]))
    last = _jax.tree_util.tree_leaves(_jax.device_get(seen[last_epoch]))
    now = _jax.tree_util.tree_leaves(est.params)
    # The estimator got exactly the BEST epoch's params back...
    for a, b in zip(best, now):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)
    # ...which genuinely differ from the final epoch's.
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(last, now)
    )
    # Moments dropped: continuation training re-inits them.
    assert est.opt_state is None
    # Handed-back params are host pytrees, single-device usable.
    assert est.score(x, y) >= 0


def test_distributed_matches_single_device_loss_first_epoch():
    """Same seed, no shuffle → DP-sharded epoch ≈ single-device epoch."""
    from learningorchestra_tpu.models.mlp import MLPClassifier

    x, y = _toy_problem(n=128)
    single = MLPClassifier(hidden_layer_sizes=(16,), num_classes=4, seed=3)
    single.fit(x, y, epochs=1, batch_size=32, shuffle=False)

    dist_est = MLPClassifier(hidden_layer_sizes=(16,), num_classes=4, seed=3)
    DistributedTrainer(dist_est, spec=MeshSpec(dp=8)).fit(
        x, y, epochs=1, batch_size=32, shuffle=False
    )
    np.testing.assert_allclose(
        single.history["loss"][-1],
        dist_est.history["loss"][-1],
        rtol=1e-4,
    )


def test_distributed_fit_tp_mesh():
    from learningorchestra_tpu.models.mlp import MLPClassifier

    x, y = _toy_problem(n=128)
    est = MLPClassifier(
        hidden_layer_sizes=(16,), num_classes=4, seed=1, learning_rate=1e-2
    )
    distributed_fit(
        est, x, y, mesh_spec={"dp": 2, "fsdp": 2, "tp": 2},
        epochs=20, batch_size=32,
    )
    assert est.score(x, y) > 0.7


def test_global_batch_must_divide():
    from learningorchestra_tpu.models.mlp import MLPClassifier

    x, y = _toy_problem(n=32)
    est = MLPClassifier(hidden_layer_sizes=(8,), num_classes=4)
    with pytest.raises(ValueError, match="divisible"):
        DistributedTrainer(est, spec=MeshSpec(dp=8)).fit(
            x, y, batch_size=30
        )


# -- ring attention ---------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_oracle(causal):
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    rng = np.random.default_rng(0)
    b, t, h, d = 4, 32, 2, 8
    q = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5
    )


def test_ring_attention_key_padding_mask():
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    rng = np.random.default_rng(1)
    b, t, h, d = 2, 16, 2, 4
    q = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    kmask = jnp.asarray(rng.integers(0, 2, size=(b, t)).astype(bool))
    kmask = kmask.at[:, 0].set(True)  # ≥1 valid key per row
    out = ring_attention(q, k, v, mesh=mesh, kmask=kmask)
    ref = reference_attention(q, k, v, kmask=kmask)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5
    )


def test_ring_attention_under_jit_and_grad():
    mesh = build_mesh(MeshSpec(dp=1, sp=8))
    rng = np.random.default_rng(2)
    b, t, h, d = 2, 16, 2, 4
    q = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))

    @jax.jit
    def loss(q, k, v):
        return ring_attention(q, k, v, mesh=mesh).sum()

    @jax.jit
    def ref_loss(q, k, v):
        return reference_attention(q, k, v).sum()

    g = jax.grad(loss)(q, k, v)
    g_ref = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), atol=2e-4
    )


# -- ring-flash attention ---------------------------------------------------


class TestRingFlashAttention:
    """The Pallas-kernel-per-step ring (interpret mode on CPU) must be
    exact against the unsharded oracle — fwd and the hand-written ring
    backward."""

    def _qkv(self, b=2, t=32, h=2, d=8, seed=0, dtype=np.float32):
        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.normal(size=(b, t, h, d)).astype(dtype)
        )
        km = jnp.asarray(rng.random((b, t)) > 0.2).at[:, 0].set(True)
        return mk(), mk(), mk(), km

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, causal):
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        q, k, v, km = self._qkv()
        out = ring_flash_attention(
            q, k, v, mesh=mesh, kmask=km, causal=causal,
            block_q=8, block_k=8, interpret=True,
        )
        ref = reference_attention(q, k, v, kmask=km, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_grads_match_oracle(self):
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(dp=1, sp=8))
        q, k, v, km = self._qkv(t=32, seed=3)

        def loss(fn):
            def f(q, k, v):
                return jnp.sum(fn(q, k, v) * v)
            return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

        g = loss(lambda q, k, v: ring_flash_attention(
            q, k, v, mesh=mesh, kmask=km, causal=True,
            block_q=8, block_k=8, interpret=True,
        ))(q, k, v)
        g_ref = loss(lambda q, k, v: reference_attention(
            q, k, v, kmask=km, causal=True,
        ))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4
            )

    def test_padded_local_blocks(self):
        """T/sp not a multiple of the kernel block: the per-shard pad
        path must stay exact (padded keys masked, padded rows cut)."""
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        q, k, v, km = self._qkv(t=24, seed=4)  # T_loc = 6, block 8
        out = ring_flash_attention(
            q, k, v, mesh=mesh, kmask=km, causal=True,
            block_q=8, block_k=8, interpret=True,
        )
        ref = reference_attention(q, k, v, kmask=km, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_default_blocks_cover_intermediate_lengths(self):
        """Regression: t_loc=384 sits between the default blocks
        (256, 512); the pad/normalize logic must keep every query row
        inside the kernel grid (a bad pad left rows 256.. unwritten)."""
        from learningorchestra_tpu.parallel.ring_attention import (
            _ring_blocks,
            ring_flash_attention,
        )

        bq, bk, pad = _ring_blocks(384, None, None)
        assert (384 + pad) % bq == 0 and (384 + pad) % bk == 0
        # And end-to-end with default blocks on a small analogue:
        # t_loc = 12 with explicit blocks (8, 12) exercises the same
        # normalization (bk -> 8, pad -> 4) at test-friendly sizes.
        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        q, k, v, km = self._qkv(t=48, seed=7)
        out = ring_flash_attention(
            q, k, v, mesh=mesh, kmask=km, causal=True,
            block_q=8, block_k=12, interpret=True,
        )
        ref = reference_attention(q, k, v, kmask=km, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_fully_masked_rows_zero(self):
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        q, k, v, _ = self._qkv(seed=5)
        km = jnp.zeros((q.shape[0], q.shape[1]), bool).at[0].set(True)
        out = ring_flash_attention(
            q, k, v, mesh=mesh, kmask=km, causal=False,
            block_q=8, block_k=8, interpret=True,
        )
        assert bool(jnp.all(out[1] == 0.0))  # row with no valid keys

    def test_bf16_storage_dtype(self):
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        q, k, v, km = self._qkv(seed=6)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = ring_flash_attention(
            qb, kb, vb, mesh=mesh, kmask=km,
            block_q=8, block_k=8, interpret=True,
        )
        assert out.dtype == jnp.bfloat16
        ref = reference_attention(q, k, v, kmask=km)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=2e-2
        )


# -- coordinator / agents ---------------------------------------------------


def test_coordinator_fanout_and_failure_record():
    from learningorchestra_tpu.parallel.coordinator import (
        Coordinator,
        HostAgent,
        register_function,
    )

    register_function(
        "square_rank", lambda rank, world_size, base: (base + rank) ** 2
    )
    coord = Coordinator().start()
    agents = [
        HostAgent(coord.address, f"agent-{i}") for i in range(2)
    ]
    try:
        for a in agents:
            a.serve()
        job_id = None
        import urllib.request, json as _json  # noqa: E401

        req = urllib.request.Request(
            f"http://{coord.address}/jobs",
            data=_json.dumps(
                {"function": "square_rank", "kwargs": {"base": 3},
                 "n_agents": 2}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            job_id = _json.loads(resp.read())["job_id"]
        job = coord.wait(job_id, timeout=10)
        assert job["state"] == "finished"
        assert sorted(job["results"].values()) == [9, 16]
        assert all(
            rec["alive"] for rec in coord.agents().values()
        )

        # failure path: errors recorded, state=failed (ledger contract)
        register_function(
            "boom", lambda rank, world_size: 1 / 0
        )
        jid = coord.submit("boom", {}, n_agents=1)
        job = coord.wait(jid, timeout=10)
        assert job["state"] == "failed"
        assert "ZeroDivisionError" in list(job["errors"].values())[0]
    finally:
        for a in agents:
            a.stop()
        coord.stop()


class TestLongContextModel:
    """models/longcontext.py — ring attention bound through the trainer."""

    def _model(self):
        from learningorchestra_tpu.models.longcontext import (
            LongContextTransformer,
        )

        return LongContextTransformer(
            vocab_size=64, hidden_dim=16, num_layers=1, num_heads=2,
            max_len=32, num_classes=2,
        )

    def test_ring_matches_vanilla_forward(self):
        import jax

        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh

        est = self._model()
        rng = np.random.default_rng(0)
        tokens = rng.integers(1, 64, (4, 16), dtype=np.int32)
        tokens[0, 12:] = 0
        est._init_params(jnp.asarray(tokens[:1]))
        out_vanilla = est.module.apply(est.params, jnp.asarray(tokens))

        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        est.bind_mesh(mesh)
        out_ring = est.module.apply(est.params, jnp.asarray(tokens))
        np.testing.assert_allclose(
            np.asarray(out_ring), np.asarray(out_vanilla),
            atol=1e-4, rtol=1e-4,
        )

    def test_distributed_fit_with_sequence_sharding(self):
        from learningorchestra_tpu.parallel.distributed import (
            DistributedTrainer,
        )
        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh

        est = self._model()
        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        trainer = DistributedTrainer(est, mesh=mesh, shard_sequence=True)
        rng = np.random.default_rng(1)
        x = rng.integers(1, 64, (16, 16), dtype=np.int32)
        y = rng.integers(0, 2, (16,), dtype=np.int32)
        trainer.fit(x, y, epochs=2, batch_size=8, shuffle=False)
        assert np.isfinite(trainer.history["loss"][-1])

    def test_artifact_roundtrip_drops_mesh(self):
        import dill

        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh

        est = self._model()
        rng = np.random.default_rng(2)
        tokens = rng.integers(1, 64, (2, 16), dtype=np.int32)
        est._init_params(jnp.asarray(tokens[:1]))
        est.bind_mesh(build_mesh(MeshSpec(dp=2, sp=4)))
        restored = dill.loads(dill.dumps(est))
        assert restored.module.mesh is None
        out = restored.module.apply(restored.params, jnp.asarray(tokens))
        assert np.all(np.isfinite(np.asarray(out)))

    def test_single_device_predict_after_distributed_fit(self):
        """The mesh is bound only for the trainer call — afterwards the
        estimator predicts on arbitrary batch/sequence shapes."""
        from learningorchestra_tpu.parallel.distributed import (
            DistributedTrainer,
        )
        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh

        est = self._model()
        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        trainer = DistributedTrainer(est, mesh=mesh, shard_sequence=True)
        rng = np.random.default_rng(3)
        x = rng.integers(1, 64, (16, 16), dtype=np.int32)
        y = rng.integers(0, 2, (16,), dtype=np.int32)
        trainer.fit(x, y, epochs=1, batch_size=8, shuffle=False)
        assert est.module.mesh is None
        # 5 rows x seq 10: divisible by neither dp*fsdp=2 nor sp=4.
        odd = rng.integers(1, 32, (5, 10), dtype=np.int32)
        preds = est.predict(odd)
        assert preds.shape == (5, 2)

    def test_seq_divisibility_error_is_friendly(self):
        from learningorchestra_tpu.parallel.distributed import (
            DistributedTrainer,
        )
        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh

        est = self._model()
        trainer = DistributedTrainer(
            est, mesh=build_mesh(MeshSpec(dp=2, sp=4)), shard_sequence=True
        )
        rng = np.random.default_rng(4)
        x = rng.integers(1, 64, (8, 15), dtype=np.int32)  # 15 % 4 != 0
        y = rng.integers(0, 2, (8,), dtype=np.int32)
        with pytest.raises(ValueError, match="sequence length"):
            trainer.fit(x, y, epochs=1, batch_size=8)


class TestCLI:
    def test_coordinator_and_agent_commands(self):
        """python -m learningorchestra_tpu coordinator/agent run a real
        distributed job end-to-end over localhost."""
        import subprocess
        import sys
        import time as _time

        import requests as _requests

        env_cmd = [sys.executable, "-m", "learningorchestra_tpu",
                   "coordinator", "--host", "127.0.0.1", "--port", "0"]
        proc = subprocess.Popen(
            env_cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        try:
            line = proc.stdout.readline()
            assert "listening on" in line, line
            addr = line.strip().rsplit(" ", 1)[1]
            # Coordinator is reachable over HTTP.
            deadline = _time.time() + 10
            while _time.time() < deadline:
                try:
                    r = _requests.get(f"http://{addr}/agents", timeout=2)
                    assert r.status_code == 200
                    break
                except Exception:
                    _time.sleep(0.1)
            else:
                raise AssertionError("coordinator not reachable")
        finally:
            proc.terminate()
            proc.wait(timeout=10)

    def test_cli_help(self):
        import subprocess
        import sys

        out = subprocess.run(
            [sys.executable, "-m", "learningorchestra_tpu", "--help"],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0
        for cmd in ("serve", "coordinator", "agent"):
            assert cmd in out.stdout


class TestMultiHostDCN:
    def test_two_process_global_collective(self, tmp_path):
        """init_multihost joins two real processes into one JAX runtime;
        a cross-process reduction runs over the inter-host transport
        (CPU/Gloo here, DCN on pods) — the reference's Gloo ring
        equivalent (SURVEY §5.8), minus Horovod."""
        import socket
        import subprocess
        import sys
        import textwrap

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]

        worker = tmp_path / "worker.py"
        worker.write_text(textwrap.dedent(f"""
            import os, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax
            sys.path.insert(0, {str(__import__('pathlib').Path(__file__).parent.parent)!r})
            import numpy as np
            import jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P
            from learningorchestra_tpu.parallel.coordinator import (
                init_multihost,
            )
            pid = int(sys.argv[1])
            init_multihost("127.0.0.1:{port}", 2, pid)
            assert jax.process_count() == 2
            devs = jax.devices()
            mesh = Mesh(devs, ("dp",))
            arr = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P("dp")), np.ones((1,)) * (pid + 1)
            )
            total = jax.jit(
                lambda a: jnp.sum(a),
                out_shardings=NamedSharding(mesh, P()),
            )(arr)
            assert float(total) == 3.0, float(total)
            print("RANK_OK", pid, flush=True)
        """))
        # One device per process: drop conftest's 8-virtual-device flag.
        env = {
            k: v for k, v in __import__("os").environ.items()
            if k != "XLA_FLAGS"
        }
        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(i)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {i}:\n{out[-2000:]}"
            assert f"RANK_OK {i}" in out


class TestDistributedCheckpointing:
    def test_distributed_fit_checkpoints_and_resumes(self, tmp_path):
        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.parallel.distributed import (
            DistributedTrainer,
        )
        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh
        from learningorchestra_tpu.train import checkpoint as ckpt

        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        ckdir = tmp_path / "dck"
        mesh = build_mesh(MeshSpec(dp=8))

        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=5)
        DistributedTrainer(est, mesh=mesh).fit(
            x, y, epochs=2, batch_size=16, checkpoint_dir=str(ckdir),
            checkpoint_min_interval_s=0.0,
        )
        loaded = ckpt.load_latest(
            str(ckdir), {"params": est.params, "opt_state": est.opt_state}
        )
        assert loaded is not None and loaded[1] == 2

        # Fresh estimator resumes at epoch 2 and continues to 4.
        est2 = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=5)
        tr = DistributedTrainer(est2, mesh=mesh)
        tr.fit(
            x, y, epochs=4, batch_size=16, checkpoint_dir=str(ckdir),
            checkpoint_min_interval_s=0.0,
        )
        assert len(tr.history["loss"]) == 4
        assert len(est2.history["loss"]) == 2  # only the 2 epochs it ran
        loaded = ckpt.load_latest(
            str(ckdir), {"params": est2.params, "opt_state": est2.opt_state}
        )
        assert loaded[1] == 4


class TestAttentionHeadSharding:
    def test_qkv_kernels_shard_by_heads_over_tp(self):
        """Megatron attention-parallel applies to the SEPARATE
        projection layout (fused_qkv=False): 3-D query/key/value
        kernels place HEADS on tp so each shard owns whole heads and
        attention runs collective-free.  The FUSED kernel's mixed
        [Q|K|V] head axis cannot split cleanly, so it must replicate
        heads instead of forcing per-layer reshards."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention
        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh
        from learningorchestra_tpu.parallel.sharding import param_shardings

        mesh = build_mesh(MeshSpec(tp=2, fsdp=2),
                          devices=jax.devices()[:4])
        x0 = jnp.zeros((1, 8, 16), jnp.float32)

        # Unfused: heads on tp (the Megatron invariant).
        sep = MultiHeadSelfAttention(
            num_heads=4, qkv_features=16, fused_qkv=False,
            use_flash=False,
        )
        ps = sep.init(jax.random.PRNGKey(0), x0)
        flat = jax.tree_util.tree_flatten_with_path(
            param_shardings(ps, mesh)
        )[0]
        heads_sharded = [
            (path, s) for path, s in flat
            if any(n in "/".join(str(p) for p in path).lower()
                   for n in ("query", "key", "value"))
            and len(s.spec) == 3
        ]
        assert heads_sharded, "no 3-D separate projection kernels"
        for path, sharding in heads_sharded:
            assert sharding.spec[1] == "tp", (path, sharding.spec)

        # Fused: head axis REPLICATED (never mixed-section sharded),
        # hidden still on fsdp.
        fused = MultiHeadSelfAttention(
            num_heads=4, qkv_features=16, use_flash=False,
        )
        pf = fused.init(jax.random.PRNGKey(0), x0)
        flat = jax.tree_util.tree_flatten_with_path(
            param_shardings(pf, mesh)
        )[0]
        fused_kernels = [
            (path, s) for path, s in flat
            if "qkv" in "/".join(str(p) for p in path).lower()
            and len(s.spec) == 3
        ]
        assert fused_kernels, "no 3-D fused qkv kernels"
        for path, sharding in fused_kernels:
            assert sharding.spec[1] is None, (path, sharding.spec)
            assert sharding.spec[0] == "fsdp", (path, sharding.spec)
