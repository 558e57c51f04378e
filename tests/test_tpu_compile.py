"""Compiles for a described (not attached) TPU v5e chip, at the real
widths of the routed expert layer: what the chip's compiler would refuse
(a tile that does not divide, too much fast memory) fails here, at no
chip time.  Nothing runs; no result or time is read.  All such compiles
live in this one file: the process that describes the topology holds
the TPU library until it exits."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots", [1, 2, 4, 8])
def test_grouped_matmul_compiles_at_the_routed_layers_shapes(
        slots, one_chip, monkeypatch):
    """Rows of a block step (slots x 4 positions x 8 experts a token)
    over 128 experts of 2048 x 768 and back: on the TPU the megablox
    kernel, its tiles dividing every slot bucket's rows."""
    from learningorchestra_tpu.ops import moe

    # the compile is for the chip: steer the layer's choice of path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = slots * 4 * 8

    def experts(x, w_up, w_down, sizes):
        return moe.grouped_matmul(
            moe.grouped_matmul(x, w_up, sizes), w_down, sizes
        )

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(experts).lower(
        arg((rows, 2048)), arg((128, 2048, 768)), arg((128, 768, 2048)),
        arg((128,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "ragged-dot" not in text


def _on(one_chip, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree,
    )


def _assert_one_pass_over_the_pages(text, pages, layers):
    """The compiled step: one ``decode_attend`` kernel a layer, every
    page leaf (and the token buffer, where there is one) updated in
    place, and nothing but the kernel that makes an array of a page
    leaf's shape (no ``copy``, no fusion, no scatter)."""
    import re

    calls = re.findall(r"%(\S+) = \([^=]*\) custom-call\(", text)
    assert sum(c.startswith("decode_attend") for c in calls) == layers
    header = text.split("\n", 1)[0]
    leaves = jax.tree_util.tree_leaves(pages)
    assert header.count("-alias)") >= len(leaves)
    shapes = {
        f"{leaf.dtype.name.replace('float', 'f')}"
        f"[{','.join(map(str, leaf.shape))}]"
        for leaf in leaves
    }
    makers = {
        op for shape, op in re.findall(
            r"= (\w+\[[\d,]*\])\{[^ ]*\} ([\w-]+)\(", text
        ) if shape in shapes
    }
    assert makers <= {"parameter", "get-tuple-element"}, makers


@pytest.mark.parametrize("chunk", [False, True], ids=["token", "chunk"])
@pytest.mark.parametrize("slots", [1, 2, 4, 8])
def test_decode_step_is_one_pass_over_the_pages_at_gpt2_xl_widths(
        slots, chunk, one_chip, monkeypatch):
    """The engine's step programs of a next-token model, two layers at
    GPT-2 XL's widths over a 512 bucket, at every slot bucket: the
    one-token program and the prompt-chunk program (``chunk_width``
    positions a slot), each donated, pages and buffer aliased, one
    ``decode_attend`` a layer, no page-shaped copy."""
    from learningorchestra_tpu.models.text import _DecoderLM
    from learningorchestra_tpu.serve.decode.pages import (
        PROMPT_CHUNK, build_step, chunk_width,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    module = _DecoderLM(
        vocab_size=50257, hidden_dim=1600, num_layers=2, num_heads=25,
        mlp_dim=6400, max_len=1024,
    )
    assert chunk_width(module) == PROMPT_CHUNK > 1
    step, pages = build_step(
        module, slots, 512, chunk_width(module) if chunk else 1
    )
    variables = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    text = step.program.lower(
        _on(one_chip, variables), _on(one_chip, pages),
        jax.ShapeDtypeStruct((slots, 512), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    _assert_one_pass_over_the_pages(text, pages, layers=2)


def test_block_step_of_an_sdar_attention_layer_is_one_pass(
        one_chip, monkeypatch):
    """One attention layer of SDAR-30B-A3B at its widths (32 query
    heads over 4 KV heads of 128, bfloat16, q/k norms, rotary, block
    mask) stepping 8 slots by a block of 4 over a 512 bucket."""
    from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention
    from learningorchestra_tpu.serve.decode.pages import (
        set_index, strip_index,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = MultiHeadSelfAttention(
        num_heads=32, qkv_features=2048, num_kv_heads=4, head_dim=128,
        use_bias=False, qk_norm=True, rope=True, rope_theta=1e6,
        block=4, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        decode=True,
    )
    variables = jax.eval_shape(
        layer.init, jax.random.PRNGKey(0),
        jnp.zeros((8, 512, 2048), jnp.bfloat16),
    )
    pages = strip_index(variables["cache"])

    def step(params, pages, x, pos, kmask):
        out, mut = layer.apply(
            {"params": params, "cache": set_index(pages, pos)}, x,
            key_mask=kmask, mutable=["cache"],
        )
        return strip_index(mut["cache"]), out

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(step, donate_argnums=1).lower(
        _on(one_chip, variables["params"]), _on(one_chip, pages),
        arg((8, 4, 2048), jnp.bfloat16), arg((8,), jnp.int32),
        arg((8, 512), jnp.bool_),
    ).compile().as_text()
    _assert_one_pass_over_the_pages(text, pages, layers=1)


@pytest.mark.parametrize("slots", [1, 2, 4, 8])
def test_block_step_carries_its_state_in_place_at_sdar_widths(
        slots, one_chip, monkeypatch):
    """The engine's step program of ``BlockDiffusionMoELM``, two layers
    at SDAR-30B-A3B's widths (128 experts top 8, the whole vocabulary,
    bfloat16) over a 512 bucket, at every slot bucket: the blocks'
    state rides the step donated like pages and buffer (all three
    updated in place), nothing but the kernel makes an array of a page
    leaf's shape, and the strategy adds no loop over slots."""
    from learningorchestra_tpu.models.moe import _BlockDiffusionMoE
    from learningorchestra_tpu.serve.decode import blocks
    from learningorchestra_tpu.serve.decode.pages import build_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    module = _BlockDiffusionMoE(
        vocab_size=151936, hidden_dim=2048, num_layers=2, num_heads=32,
        num_kv_heads=4, head_dim=128, expert_dim=768, num_experts=128,
        top_k=8, max_len=4096, block_length=4, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    )
    step, pages = build_step(module, slots, 512)
    variables = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    width = len(blocks.STATE_HEAD) + 3 * 4

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    text = step.program.lower(
        _on(one_chip, variables), _on(one_chip, pages), ints(slots, 512),
        ints(slots, width), ints(len(blocks.SLOT_ROWS), slots),
    ).compile().as_text()
    _assert_one_pass_over_the_pages(text, pages, layers=2)
    header = text.split("\n", 1)[0]
    n_params = len(jax.tree_util.tree_leaves(variables))
    n_pages = len(jax.tree_util.tree_leaves(pages))
    # pages, then the token buffer, then the state: each its own alias
    for index in range(n_params, n_params + n_pages + 2):
        assert f"({index}, {{}}, may-alias)" in header, (index, header[:400])
    assert f"s32[{slots},{width}]" in header
    # the state machine is array operations: the only loops are the
    # experts' kernel's own search over group sizes
    loops = [line for line in text.splitlines() if " while(" in line]
    assert all("moe_experts" in line for line in loops), loops


@pytest.mark.parametrize("slots", [1, 8, 64])
def test_latent_step_reads_the_packed_pages_once_at_kimi_k2_widths(
        slots, one_chip, monkeypatch):
    """The engine's step program of ``LatentMoELM`` at Kimi-K2's widths
    (a dense and a routed layer, 12 of 384 experts held, bfloat16) over
    a 2,048 bucket: one ``latent_attend`` kernel a layer over ONE page
    leaf of 576 values a position, two positions a row; the leaf
    updated in place by the kernel itself and never copied, relaid or
    made anew; the experts' three grouped matmuls on the megablox kernel
    where the rows fill a tile."""
    import re

    from learningorchestra_tpu.models.moe import LatentMoELM
    from learningorchestra_tpu.serve.decode.pages import build_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    est = LatentMoELM(
        vocab_size=20480, hidden_dim=7168, num_layers=2, num_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, mlp_dim=18432,
        first_dense_layers=1, expert_dim=2048, num_experts=384,
        experts_per_token=8, routed_scale=2.827, experts_held=(0, 12),
        rope_theta=50000.0, rope_scaling={
            "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        }, norm_eps=1e-5, max_len=262144,
    )
    step, pages = build_step(est.module, slots, 2048)
    leaves = jax.tree_util.tree_leaves(pages)
    assert [leaf.shape for leaf in leaves] == [(slots, 1024, 1152)] * 2
    variables = jax.eval_shape(
        est.module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    text = step.program.lower(
        _on(one_chip, variables), _on(one_chip, pages),
        jax.ShapeDtypeStruct((slots, 2048), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    calls = re.findall(r"%(\S+) = [^=]* custom-call\(", text)
    assert sum(c.startswith("latent_attend") for c in calls) == 2
    assert text.split("\n", 1)[0].count("-alias)") >= 3  # pages, buffer
    makers = {
        op for shape, op in re.findall(
            r"= (\w+\[[\d,]*\])\{[^ ]*\} ([\w-]+)\(", text
        ) if shape == f"bf16[{slots},1024,1152]"
    }
    # in place: the kernel sends the step's row back into the aliased
    # leaf; no copy, no relayout, no scatter, no fusion makes a leaf
    assert makers <= {"parameter", "get-tuple-element"}, makers
    assert f"bf16[{slots},1024,1152]{{2,1,0" in text  # row-major
    if slots * 8 % 16 == 0:
        assert "ragged-dot" not in text


@pytest.mark.parametrize("slots", [1, 2, 4, 8, 16])
def test_retention_step_updates_the_states_in_place_at_brumby_widths(
        slots, one_chip, monkeypatch):
    """The engine's step program of ``RetentionLM`` at Brumby-14B's
    widths (two layers; 40 query heads over 8 key/value heads of 128,
    the whole vocabulary, bfloat16) at every slot bucket: one
    ``retention_step`` kernel a layer over ONE state leaf a layer of
    8,320 rows of 128 lanes a value (8,256 products and their padding,
    not 16,384), float32; the leaf aliased through the kernel and never
    copied, relaid or made anew; no leaf as long as the token buffer."""
    import re

    from learningorchestra_tpu.models.retention import RetentionLM
    from learningorchestra_tpu.serve.decode.pages import build_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    est = RetentionLM(
        vocab_size=151936, hidden_dim=5120, num_layers=2, num_heads=40,
        num_kv_heads=8, head_dim=128, mlp_dim=17408, rope_theta=1e6,
        norm_eps=1e-6, max_len=32768,
    )
    step, states = build_step(est.module, slots, 2048)
    leaves = jax.tree_util.tree_leaves(states)
    assert sorted(leaf.shape for leaf in leaves) == sorted(
        [(slots, 8, 65, 128, 128), (slots, 8, 65, 128)] * 2)
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype("float32")}
    variables = jax.eval_shape(
        est.module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    text = step.program.lower(
        _on(one_chip, variables), _on(one_chip, states),
        jax.ShapeDtypeStruct((slots, 2048), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    calls = re.findall(r"%(\S+) = [^=]* custom-call\(", text)
    assert sum(c.startswith("retention_step") for c in calls) == 2
    # every state leaf and the token buffer: each its own alias
    assert text.split("\n", 1)[0].count("-alias)") >= 5
    makers = {
        op for shape, op in re.findall(
            r"= (\w+\[[\d,]*\])\{[^ ]*\} ([\w-]+)\(", text
        ) if shape == f"f32[{slots},8,65,128,128]"
    }
    # in place: the kernel writes each state back into the aliased
    # leaf; no copy, no relayout, no select, no fusion makes one
    assert makers <= {"parameter", "get-tuple-element"}, makers
    for scope in ("retention_proj", "retention_gate", "retention_step",
                  "retention_out"):
        assert scope in text, scope


@pytest.mark.parametrize("slots", [2, 16])
def test_retention_chunk_program_updates_the_states_in_place(
        slots, one_chip, monkeypatch):
    """The prompt-chunk program of ``RetentionLM`` (``STATE_CHUNK``
    positions a slot) at Brumby-14B's widths, two layers, over the
    1,024-position buffer: every kernel it holds is a ``retention_step``
    (one a layer: the chunk's work is under the name its roofline reads
    by), each state leaf and the token buffer aliased, and nothing but
    that kernel makes an array of a state leaf's shape."""
    import re

    from learningorchestra_tpu.models.retention import RetentionLM
    from learningorchestra_tpu.serve.decode.pages import (
        STATE_CHUNK, build_step, chunk_width,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    est = RetentionLM(
        vocab_size=151936, hidden_dim=5120, num_layers=2, num_heads=40,
        num_kv_heads=8, head_dim=128, mlp_dim=17408, rope_theta=1e6,
        norm_eps=1e-6, max_len=32768,
    )
    assert chunk_width(est.module) == STATE_CHUNK > 1
    step, states = build_step(est.module, slots, 1024, STATE_CHUNK)
    variables = jax.eval_shape(
        est.module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    text = step.program.lower(
        _on(one_chip, variables), _on(one_chip, states),
        jax.ShapeDtypeStruct((slots, 1024), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    kernels = re.findall(
        r"%(\S+) = [^=]* custom-call\([^\n]*custom_call_target="
        r"\"tpu_custom_call\"", text)
    assert len(kernels) == 2
    assert all(name.startswith("retention_step") for name in kernels)
    assert text.split("\n", 1)[0].count("-alias)") >= 5
    makers = {
        op for shape, op in re.findall(
            r"= (\w+\[[\d,]*\])\{[^ ]*\} ([\w-]+)\(", text
        ) if shape == f"f32[{slots},8,65,128,128]"
    }
    assert makers <= {"parameter", "get-tuple-element"}, makers


@pytest.mark.parametrize("slots", [2, 64])
def test_hybrid_step_updates_pages_and_states_in_place_at_falcon_h1_widths(
        slots, one_chip, monkeypatch):
    """The engine's step program of ``HybridLM`` at Falcon-H1-34B's
    widths (two layers; a Mamba-2 mixer of 32 heads of 128 over a state
    of 256 beside attention of 20 query heads over 4 key/value heads of
    128, the whole vocabulary, bfloat16) over the 512 bucket: one
    ``ssm_step`` and one ``decode_attend`` kernel a layer; the float32
    state, the tail, the pages and the token buffer each aliased; nothing
    but the kernel makes an array of the state's shape."""
    import re

    from learningorchestra_tpu.models.hybrid import HybridLM
    from learningorchestra_tpu.serve.decode.pages import (
        build_step, chunk_width,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    est = HybridLM(
        vocab_size=261120, hidden_dim=5120, num_layers=2, num_heads=20,
        num_kv_heads=4, head_dim=128, mlp_dim=21504, rope_theta=1e11,
        mamba_heads=32, mamba_head_dim=128, mamba_groups=2,
        mamba_state=256, mamba_conv=4, mamba_chunk=128,
        key_multiplier=0.011048543456039804, max_len=262144,
    )
    assert chunk_width(est.module) == 1
    step, cache = build_step(est.module, slots, 512)
    mixer = cache["HybridBlock_0"]["Mamba2Mixer_0"]
    assert mixer["ssm_state"].shape == (slots, 32, 256, 128)
    assert mixer["conv_tail"].shape == (slots, 3, 5120)
    variables = jax.eval_shape(
        est.module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    text = step.program.lower(
        _on(one_chip, variables), _on(one_chip, cache),
        jax.ShapeDtypeStruct((slots, 512), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    kernels = re.findall(
        r"%(\S+) = [^=]* custom-call\([^\n]*custom_call_target="
        r"\"tpu_custom_call\"", text)
    assert sorted(re.sub(r"[.\d]+$", "", k) for k in kernels) \
        == ["decode_attend"] * 2 + ["ssm_step"] * 2
    # per layer: the state, the tail, K and V pages; and the buffer
    assert text.split("\n", 1)[0].count("-alias)") >= 9
    makers = {
        op for shape, op in re.findall(
            r"= (\w+\[[\d,]*\])\{[^ ]*\} ([\w-]+)\(", text
        ) if shape == f"f32[{slots},32,256,128]"
    }
    assert makers <= {"parameter", "get-tuple-element"}, makers
    for scope in ("ssm_proj", "ssm_conv", "ssm_step", "ssm_norm",
                  "ssm_out"):
        assert scope in text, scope


@pytest.mark.parametrize("causal", [False, True], ids=["encoder", "causal"])
def test_flash_kernels_fit_the_chip_head_blocked_at_bert_base_widths(
        causal, one_chip, monkeypatch):
    """The attention of ``bert-base.fit-s512`` (batch 32, 12 heads of 64,
    512 positions, a key mask), forward and backward: each of the three
    kernels takes 6 heads over the whole sequence a grid step, and the
    VMEM the step asks for (``ops/attention.py`` ``_vmem_bytes``) is
    enough for the chip's compiler.  The compiled instructions keep the
    kernels' names, the schedule's scope in their ``op_name``."""
    import re

    from learningorchestra_tpu.ops.attention import flash_attention

    monkeypatch.setenv("LO_TPU_FLASH_INTERPRET", "0")

    def step(q, k, v, km):
        def loss(q, k, v):
            out = flash_attention(q, k, v, km, causal=causal)
            return out.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    a = jax.ShapeDtypeStruct((32, 12, 512, 64), jnp.bfloat16,
                             sharding=one_chip)
    km = jax.ShapeDtypeStruct((32, 512), jnp.bool_, sharding=one_chip)
    text = jax.jit(step).lower(a, a, a, km).compile().as_text()
    calls = re.findall(
        r"%(\S+) = [^\n]* custom-call\([^\n]*custom_call_target="
        r"\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"", text)
    assert sorted(re.sub(r"[.\d]+$", "", name) for name, _ in calls) \
        == ["flash_dkv", "flash_dq", "flash_fwd"]
    for name, op_name in calls:
        assert "(flash_sched_h6_q512_k512)" in op_name, (name, op_name)
