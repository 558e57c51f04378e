"""Cross-platform proof that the TRAIN path lowers to the Pallas
kernels (VERDICT r3 item 2: "verify via HLO that the train path lowers
to the Pallas kernel (tpu_custom_call)").

``jax.export`` lowers for platform "tpu" on this CPU-only host — the
lowering that turns ``pallas_call`` into ``tpu_custom_call`` lives in
jaxlib, no TPU required.  A kernel that stops lowering (shape rule
change) fails HERE, in CI, instead of costing chip time.  The Mosaic
compile of the emitted kernel happens in libtpu, on the chip only:
``scripts/chip_kernel_check.py`` is that half.

``LO_TPU_FLASH_INTERPRET=0`` (ops/attention.py::_auto_interpret)
forces the real kernel path during tracing; params are initialized
first in interpret mode (flax init executes on the CPU backend).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import export


@pytest.fixture()
def mosaic(monkeypatch):
    """Force real Mosaic lowering for the test body only."""
    monkeypatch.setenv("LO_TPU_FLASH_INTERPRET", "0")


def _count_kernel_calls(fn, *args) -> int:
    exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
    return exp.mlir_module().count("tpu_custom_call")


class TestBertTrainPathLowersToFlash:
    def test_forward_and_grad_use_the_kernel(self, monkeypatch):
        from learningorchestra_tpu.models.text import BertModel

        est = BertModel(hidden_dim=64, num_layers=2, num_heads=2,
                        max_len=128, use_flash=True)
        rng = np.random.default_rng(0)
        tok = jnp.asarray(
            rng.integers(1, 100, (2, 128), dtype=np.int32)
        )
        est._init_params(tok[:1])  # interpret mode: runs on CPU

        monkeypatch.setenv("LO_TPU_FLASH_INTERPRET", "0")
        n_fwd = _count_kernel_calls(est.module.apply, est.params, tok)
        assert n_fwd == 2  # one flash kernel per layer

        loss_fn = est._loss_and_metrics(
            est._resolve_loss(np.zeros(2, np.int32))
        )
        y = jnp.asarray(rng.integers(0, 2, (2,), dtype=np.int32))

        def step(params, x, y):
            def L(p):
                logits = est.module.apply(p, x)
                loss, _ = loss_fn(
                    logits, y, jnp.ones_like(y, jnp.float32)
                )
                return loss

            return jax.grad(L)(params)

        n_train = _count_kernel_calls(step, est.params, tok, y)
        # Backward routes Pallas too (custom VJP): strictly more
        # kernel calls than the forward alone.
        assert n_train > n_fwd, (n_train, n_fwd)


class TestKernelVariantsLowerer:
    """The r3 kernel additions must keep lowering through Mosaic."""

    def _qkv(self, t=256, d=64):
        rng = np.random.default_rng(1)
        mk = lambda: jnp.asarray(
            rng.standard_normal((1, 2, t, d)), jnp.bfloat16
        )
        return mk(), mk(), mk()

    def test_plain_flash(self, mosaic):
        from learningorchestra_tpu.ops.attention import flash_attention

        q, k, v = self._qkv()
        assert _count_kernel_calls(flash_attention, q, k, v) == 1

    def test_causal_flash(self, mosaic):
        from learningorchestra_tpu.ops.attention import flash_attention

        q, k, v = self._qkv()
        fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
        assert _count_kernel_calls(fn, q, k, v) == 1

    def test_sliding_window_flash(self, mosaic):
        from learningorchestra_tpu.ops.attention import flash_attention

        q, k, v = self._qkv()
        fn = lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=128
        )
        assert _count_kernel_calls(fn, q, k, v) == 1

    def test_ring_flash_lowers_with_collectives(self, mosaic):
        # The multi-chip long-context path: shard_map ring over sp with
        # the flash kernel per step must lower to tpu_custom_call PLUS
        # ICI collective_permutes — proven here over the virtual
        # 8-device mesh, no pod required (SURVEY §5.7).
        from learningorchestra_tpu.parallel.mesh import (
            MeshSpec,
            build_mesh,
        )
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(sp=8))
        rng = np.random.default_rng(2)
        q = jnp.asarray(
            rng.standard_normal((1, 1024, 2, 32)), jnp.bfloat16
        )
        fn = lambda q, k, v: ring_flash_attention(q, k, v, mesh=mesh)
        exp = export.export(jax.jit(fn), platforms=["tpu"])(q, q, q)
        text = exp.mlir_module()
        assert text.count("tpu_custom_call") >= 1
        assert text.count("collective_permute") >= 1

    def test_flash_under_a_mesh_lowers_per_shard(self, mosaic):
        # What stopped /train/horovod on four chips (PR 21): GSPMD
        # cannot partition a Mosaic kernel, so a flash model jitted
        # over sharded operands refuses to lower — unless the kernel
        # runs per shard, which it does under jax's ambient mesh (the
        # mesh trainers set it; ops/attention.py _per_shard).
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from learningorchestra_tpu.ops.attention import flash_attention

        mesh = Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp")
        )
        aval = jax.ShapeDtypeStruct(
            (4, 4, 128, 64), jnp.bfloat16,
            sharding=NamedSharding(mesh, P("dp", "tp")),
        )

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True)
            return out.astype(jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        with pytest.raises(NotImplementedError, match="partitioned"):
            export.export(step, platforms=["tpu"])(aval, aval, aval)
        with jax.set_mesh(mesh):
            exp = export.export(step, platforms=["tpu"])(aval, aval, aval)
        assert exp.nr_devices == 4
        assert exp.mlir_module().count("tpu_custom_call") >= 3

    def test_flash_backward_kernels(self, mosaic):
        from learningorchestra_tpu.ops.attention import flash_attention

        q, k, v = self._qkv()

        def loss(q, k, v):
            return flash_attention(q, k, v).astype(jnp.float32).sum()

        n = _count_kernel_calls(
            lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
            q, k, v,
        )
        assert n >= 2  # fwd (for residuals) + backward kernel(s)


def _flash_calls(fn, *args) -> list:
    """``(kernel name, scope)`` of each flash custom call in the TPU
    lowering of ``fn``: the kernel's ``kernel_name`` and the name stack
    of its location, which the compiled HLO carries as ``op_name``."""
    import re

    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = []
    for line in text.splitlines():
        found = re.search(r'kernel_name = "([^"]+)".*loc\((#loc\d+)\)$',
                          line)
        if found and "tpu_custom_call" in line:
            calls.append((found.group(1), locs.get(found.group(2), "")))
    return calls


def _fwd_bwd(**kw):
    from learningorchestra_tpu.ops.attention import flash_attention

    def step(q, k, v, km):
        def loss(q, k, v):
            out = flash_attention(q, k, v, km, **kw)
            return out.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return step


class TestFlashSchedule:
    """Where one block holds the sequence a grid step takes a block of
    heads (``flash_schedule``); every other call keeps one head a step
    and the (256, 512) / (512, 1024) blocks."""

    def test_the_fit_cells_shape_is_head_blocked(self, mosaic):
        # bert-base.fit-s512: batch 32, 12 heads of 64, 512 positions.
        a = jax.ShapeDtypeStruct((32, 12, 512, 64), jnp.bfloat16)
        km = jax.ShapeDtypeStruct((32, 512), jnp.bool_)
        calls = _flash_calls(_fwd_bwd(), a, a, a, km)
        assert sorted(name for name, _ in calls) == [
            "flash_dkv", "flash_dq", "flash_fwd"]
        for name, scope in calls:
            assert "(flash_sched_h6_q512_k512)" in scope, (name, scope)
            assert scope.endswith(f"/{name}/pallas_call"), scope

    @pytest.mark.parametrize("shape,kw", [
        ((1, 2, 8192, 64), {}),
        ((2, 4, 512, 64), {"causal": True, "window": 128}),
        ((2, 4, 512, 128), {}),
        ((2, 4, 512, 64), {"block_q": 256, "block_k": 512}),
    ], ids=["t8192", "window", "head_dim128", "explicit_blocks"])
    def test_other_calls_keep_one_head_a_step(self, mosaic, shape, kw):
        a = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        km = jax.ShapeDtypeStruct(shape[:1] + shape[2:3], jnp.bool_)
        calls = _flash_calls(_fwd_bwd(**kw), a, a, a, km)
        assert len(calls) == 3
        assert not any("sched" in scope for _, scope in calls), calls

    def test_the_ring_keeps_one_head_a_step(self, mosaic):
        from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh
        from learningorchestra_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        mesh = build_mesh(MeshSpec(sp=8))
        q = jax.ShapeDtypeStruct((1, 1024, 2, 32), jnp.bfloat16)

        def step(q, k, v):
            return jax.grad(lambda q, k, v: ring_flash_attention(
                q, k, v, mesh=mesh).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)

        calls = _flash_calls(step, q, q, q)
        assert {name for name, _ in calls} >= {"ring_fwd", "ring_dq",
                                               "ring_dkv"}
        assert not any("sched" in scope for _, scope in calls), calls

    @pytest.mark.parametrize("t_q,t_k,heads,head_dim,window,expected", [
        (512, 512, 12, 64, None, (6, 512, 512)),
        (500, 500, 4, 16, None, (4, 512, 512)),
        (128, 128, 16, 64, None, (16, 128, 128)),
        (48, 40, 3, 32, None, (3, 48, 40)),
        (512, 512, 7, 64, None, (7, 512, 512)),
        (512, 512, 25, 64, None, (5, 512, 512)),
        (1024, 1024, 12, 64, None, (1, 1024, 1024)),
        (1025, 1025, 12, 64, None, (1, 256, 512)),
        (512, 512, 12, 64, 128, (1, 256, 512)),
        (512, 512, 12, 128, None, (1, 256, 512)),
        (8192, 8192, 12, 64, None, (1, 256, 512)),
        (9000, 9000, 12, 64, None, (1, 512, 1024)),
    ])
    def test_schedule_rule(self, t_q, t_k, heads, head_dim, window,
                           expected):
        from learningorchestra_tpu.ops import attention

        got = attention.flash_schedule(t_q, t_k, heads, head_dim, window)
        assert got == expected
        hb, block_q, block_k = got
        assert heads % hb == 0
        if hb > 1:  # the largest divisor of the heads that fits
            fits = [
                n for n in range(1, heads + 1) if heads % n == 0
                and attention._step_vmem(n, block_q, block_k, head_dim, 2)
                <= attention._VMEM_BUDGET
            ]
            assert hb == max(fits)

    def test_vmem_reckoning_pads_lanes_and_sublanes(self):
        from learningorchestra_tpu.ops import attention

        # A (512, 64) bf16 block takes a (512, 128) one's room, a (512,
        # 1) f32 column a (512, 128) f32 matrix's, a (1, 512) f32 row
        # eight rows'.
        assert attention._tile_bytes(512, 64, 2) == 512 * 128 * 2
        assert attention._tile_bytes(512, 1, 4) == 512 * 128 * 4
        assert attention._tile_bytes(1, 512, 4) == 8 * 512 * 4
        assert attention._tile_bytes(1, 512, 2) == 16 * 512 * 2
        # fwd at 6 heads of (512, 64) bf16: q, k, v, o and lse blocks
        # double-buffered, m, l, acc a head, three score tiles.
        blocks = 4 * 512 * 128 * 2 + 512 * 128 * 4
        scratch = 3 * 512 * 128 * 4
        need = 2 * (6 * blocks + 8 * 512 * 4) + 6 * scratch \
            + 3 * 512 * 512 * 4
        assert attention._vmem_bytes("fwd", 6, 512, 512, 64, 2) == need
        # The backward kernels take more than the forward; every step
        # grows with the heads it holds.
        for kernel in ("dq", "dkv"):
            assert attention._vmem_bytes(kernel, 6, 512, 512, 64, 2) \
                > need
        assert attention._step_vmem(12, 512, 512, 64, 2) \
            > attention._VMEM_BUDGET \
            >= attention._step_vmem(6, 512, 512, 64, 2)


class TestS2dResNetLowersForTpu:
    def test_train_step_exports_for_tpu(self):
        # The MXU-friendly stem: prove the whole s2d train step
        # lowers for platform "tpu" on this CPU host so the ResNet
        # sweep's grid points can't spend chip time on a lowering
        # failure.
        from learningorchestra_tpu.models.vision import (
            _ResNet,
            _ResNetBlock,
        )
        from learningorchestra_tpu.train.neural import NeuralEstimator

        est = NeuralEstimator(
            _ResNet(stage_sizes=(1, 1), block=_ResNetBlock,
                    num_classes=2, width=8, s2d_stem=True),
            loss="softmax_ce", learning_rate=1e-3, seed=0,
        )
        rng = np.random.default_rng(0)
        x = jnp.asarray(
            rng.standard_normal((2, 16, 16, 3)).astype(np.float32)
        )
        y = jnp.asarray(rng.integers(0, 2, (2,), dtype=np.int32))
        est._init_params(x[:1])
        loss_fn = est._loss_and_metrics(est._resolve_loss(np.asarray(y)))

        def step(params, x, y):
            def L(p):
                logits = est.module.apply(p, x)
                loss, _ = loss_fn(
                    logits, y, jnp.ones_like(y, jnp.float32)
                )
                return loss

            return jax.grad(L)(params)

        exp = export.export(jax.jit(step), platforms=["tpu"])(
            est.params, x, y
        )
        mlir = exp.mlir_module()
        # The stem conv is present and the export carried the full
        # fwd+bwd graph for the TPU platform.
        assert "convolution" in mlir
