"""Ring attention: sequence-parallel exact attention over the ``sp`` axis.

The reference has no attention code at all and its sequence length is
bounded by one worker's ``model.fit`` memory (SURVEY §5.7).  This module
is the long-context capability the TPU framework adds: the sequence axis
is sharded across devices, each device holds one query block resident,
and key/value blocks rotate around the ring via ``lax.ppermute`` — one
ICI hop per step, overlapping the blockwise attention compute.  Softmax
is computed online (running max / running sum), so the result is *exact*
attention, never materializing the (T, T) score matrix on any device.

Memory per device: O(T/sp · d) activations + O((T/sp)²) scores — a
T=128k sequence on sp=16 attends with 8k-block arithmetic.

Pattern follows the public blockwise/ring-attention recipe (Liu et al.,
ring attention; flash-style online softmax) as described in PAPERS.md —
implementation is original and JAX-idiomatic: ``shard_map`` for the
manual-collective region, ``lax.fori_loop`` with static trip count so the
whole ring unrolls into one compiled loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, kmask, bias):
    """Scores for one (q-block, k-block) pair.

    q: (B, Tq, H, D)   k/v: (B, Tk, H, D)   kmask: (B, Tk) or None
    bias: (Tq, Tk) additive or None.  Returns (scores (B,H,Tq,Tk), v).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias[None, None, :, :]
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :], s, NEG_INF)
    return s


def _online_update(carry_o, carry_m, carry_l, s, v):
    """Fold one block of scores into the running softmax accumulators."""
    m_new = jnp.maximum(carry_m, s.max(axis=-1))
    corr = jnp.exp(carry_m - m_new)
    p = jnp.exp(s - m_new[..., None])  # (B, H, Tq, Tk)
    l_new = carry_l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = carry_o * corr[..., None].transpose(0, 2, 1, 3) + pv
    return o_new, m_new, l_new


def _ring_attention_sharded(
    q, k, v, kmask, axis_name: str, causal: bool, mesh_axes: tuple
):
    """Per-shard body (runs under shard_map): full ring of K/V rotations.

    Shapes per device: q,k,v (B, T_local, H, D); kmask (B, T_local).
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # Accumulators start as constants but become device-varying once the
    # rotating K/V blocks fold in — cast them varying up front so the
    # fori_loop carry types match under shard_map's vma check.
    def _varying(x):
        return jax.lax.pcast(x, mesh_axes, to="varying")

    o0 = _varying(jnp.zeros((b, t_loc, h, d), jnp.float32))
    m0 = _varying(jnp.full((b, h, t_loc), NEG_INF, jnp.float32))
    l0 = _varying(jnp.zeros((b, h, t_loc), jnp.float32))

    q32 = q.astype(jnp.float32)

    def body(step, state):
        o, m, l, kb, vb, km = state
        # kb originated on device (my_idx - step) mod axis_size.
        src = (my_idx - step) % axis_size
        if causal:
            q_pos = my_idx * t_loc + jnp.arange(t_loc)
            k_pos = src * t_loc + jnp.arange(t_loc)
            bias = jnp.where(
                q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF
            )
        else:
            bias = None
        s = _block_attend(q32, kb.astype(jnp.float32),
                          vb.astype(jnp.float32), km, bias)
        o, m, l = _online_update(o, m, l, s, vb.astype(jnp.float32))
        # Rotate K/V (and the key-padding mask) one hop around the ring.
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        if km is not None:
            km = jax.lax.ppermute(km, axis_name, perm)
        return o, m, l, kb, vb, km

    o, m, l, *_ = jax.lax.fori_loop(
        0, axis_size, body, (o0, m0, l0, k, v, kmask)
    )
    # (B, H, Tq) -> (B, Tq, H, 1) for the normalizer.
    l = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / l).astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    *,
    mesh: Mesh,
    kmask=None,
    axis_name: str = "sp",
    causal: bool = False,
    batch_axes: tuple = ("dp", "fsdp"),
    head_axis: str | None = "tp",
):
    """Exact multi-head attention with the sequence axis sharded on
    ``axis_name``.  Inputs are GLOBAL arrays (B, T, H, D) — under jit
    they may already be sharded; shard_map re-annotates.

    ``kmask`` (B, T) marks valid key positions (pad id masking).
    """
    ha = head_axis if head_axis and mesh.shape.get(head_axis, 1) > 1 else None
    qkv_spec = P(batch_axes, axis_name, ha, None)
    mask_spec = P(batch_axes, axis_name)
    varying = tuple(batch_axes) + (axis_name,) + ((ha,) if ha else ())
    body = functools.partial(
        _ring_attention_sharded,
        axis_name=axis_name,
        causal=causal,
        mesh_axes=varying,
    )
    if kmask is None:
        fn = jax.shard_map(
            lambda q, k, v: body(q, k, v, None),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
        )
        return fn(q, k, v)
    fn = jax.shard_map(
        lambda q, k, v, km: body(q, k, v, km),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, kmask)


# ---------------------------------------------------------------------------
# Ring-flash: the Pallas flash kernel inside each ring step
# ---------------------------------------------------------------------------
#
# The jnp ring above materializes (T/sp)² f32 scores per device per step.
# Ring-flash replaces the per-step block attention with the streamed
# Pallas kernel (ops/attention.py): per-device memory falls to O(block)
# and the matmuls run bf16 on the MXU.  The backward is a hand-written
# reverse ring (custom_vjp): dq accumulates locally while dk/dv partials
# rotate WITH their K/V blocks and arrive home after a full circuit —
# the ring-flash recipe from PAPERS.md, built on this repo's kernels.

_MERGE_EMPTY = -1e30  # merge-domain lse for "no keys seen yet"


def _kernel_lse_to_merge(lse):
    """Kernel sentinel (+1e30 for fully-masked rows) -> merge domain."""
    return jnp.where(lse > 1e29, _MERGE_EMPTY, lse)


def _merge_partials(o_c, lse_c, o_b, lse_b):
    """Fold one block's normalized output into the running result.

    Both sides carry softmax-NORMALIZED outputs plus their lse; the
    exact combination re-weights by exp(lse - m) with empty sides
    contributing weight 0.
    """
    m = jnp.maximum(lse_c, lse_b)
    wc = jnp.where(lse_c > _MERGE_EMPTY / 2, jnp.exp(lse_c - m), 0.0)
    wb = jnp.where(lse_b > _MERGE_EMPTY / 2, jnp.exp(lse_b - m), 0.0)
    denom = wc + wb
    safe = jnp.where(denom > 0.0, denom, 1.0)
    o = (o_c * wc + o_b * wb) / safe
    lse = jnp.where(
        denom > 0.0, m + jnp.log(safe), _MERGE_EMPTY
    )
    return o, lse


def _ring_blocks(t_loc: int, block_q: int | None, block_k: int | None
                 ) -> tuple[int, int, int]:
    """(block_q, block_k, pad) for the local length.

    Starts from flash_attention's length-adaptive defaults, clamps to
    the local length, then forces the smaller block to divide the
    larger so ONE pad amount makes the padded length divisible by both
    — otherwise a t_loc between the two block sizes (e.g. 384 with
    blocks 256/512) would leave trailing query rows outside the kernel
    grid entirely.
    """
    bq = block_q or (256 if t_loc <= 8192 else 512)
    bk = block_k or (512 if t_loc <= 8192 else 1024)
    bq = min(bq, max(8, t_loc))
    bk = min(bk, max(8, t_loc))
    if bk >= bq:
        bk -= bk % bq
    else:
        bq -= bq % bk
    pad = (-t_loc) % max(bq, bk)
    return bq, bk, pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ring_flash_core(q, k, v, km, opts):
    out, _ = _ring_flash_fwd(q, k, v, km, opts)
    return out


def _ring_steps(opts):
    axis, causal, bq, bk, interpret = opts
    n = jax.lax.psum(1, axis)  # mesh axis size: a static int
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return axis, causal, bq, bk, interpret, n, me, perm


def _step_branch(causal, me, src, n):
    """0 = full block, 1 = diagonal (causal within), 2 = skip (future)."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(src == me, 1, jnp.where(src < me, 0, 2))


def _ring_flash_fwd(q, k, v, km, opts):
    from learningorchestra_tpu.ops.attention import _fwd_call

    axis, causal, bq, bk, interpret, n, me, perm = _ring_steps(opts)
    b, h, t, d = q.shape

    def call(kb, vb, kmb, diag):
        o, lse = _fwd_call(
            q, kb, vb, kmb, bq, bk, interpret, diag, prefix="ring"
        )
        return o.astype(jnp.float32), _kernel_lse_to_merge(lse)

    def skip(kb, vb, kmb):
        return (
            jnp.zeros((b, h, t, d), jnp.float32),
            jnp.full((b, h, t, 1), _MERGE_EMPTY, jnp.float32),
        )

    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    l0 = jnp.full((b, h, t, 1), _MERGE_EMPTY, jnp.float32)

    def body(step, state):
        o, lse, kb, vb, kmb = state
        src = (me - step) % n
        ob, lseb = jax.lax.switch(
            _step_branch(causal, me, src, n),
            [
                lambda kb, vb, kmb: call(kb, vb, kmb, False),
                lambda kb, vb, kmb: call(kb, vb, kmb, True),
                skip,
            ],
            kb, vb, kmb,
        )
        o, lse = _merge_partials(o, lse, ob, lseb)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        kmb = jax.lax.ppermute(kmb, axis, perm)
        return o, lse, kb, vb, kmb

    o, lse, *_ = jax.lax.fori_loop(0, n, body, (o0, l0, k, v, km))
    out = o.astype(q.dtype)
    # Back to the kernel's sentinel domain for the backward pass.
    lse_s = jnp.where(lse <= _MERGE_EMPTY / 2, 1e30, lse)
    return out, lse_s


def _ring_flash_core_fwd(q, k, v, km, opts):
    out, lse = _ring_flash_fwd(q, k, v, km, opts)
    return out, (q, k, v, km, out, lse)


def _ring_flash_core_bwd(opts, res, g):
    from learningorchestra_tpu.ops.attention import _bwd_call

    axis, causal, bq, bk, interpret, n, me, perm = _ring_steps(opts)
    q, k, v, km, o, lse = res
    do32 = g.astype(jnp.float32)
    delta = jnp.sum(
        do32 * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    do = do32.astype(q.dtype)

    def call(kb, vb, kmb, diag):
        dq, dk, dv = _bwd_call(
            q, kb, vb, kmb, do, lse, delta, bq, bk, interpret, diag,
            prefix="ring",
        )
        return (
            dq.astype(jnp.float32),
            dk.astype(jnp.float32),
            dv.astype(jnp.float32),
        )

    def skip(kb, vb, kmb):
        z = jnp.zeros(q.shape, jnp.float32)
        return z, jnp.zeros(k.shape, jnp.float32), \
            jnp.zeros(v.shape, jnp.float32)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)

    def body(step, state):
        dq, kb, vb, kmb, dkb, dvb = state
        src = (me - step) % n
        dqs, dks, dvs = jax.lax.switch(
            _step_branch(causal, me, src, n),
            [
                lambda kb, vb, kmb: call(kb, vb, kmb, False),
                lambda kb, vb, kmb: call(kb, vb, kmb, True),
                skip,
            ],
            kb, vb, kmb,
        )
        dq = dq + dqs
        dkb = dkb + dks
        dvb = dvb + dvs
        # dk/dv partials travel WITH their block; after the full
        # circuit each block (and its gradient) is home.
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        kmb = jax.lax.ppermute(kmb, axis, perm)
        dkb = jax.lax.ppermute(dkb, axis, perm)
        dvb = jax.lax.ppermute(dvb, axis, perm)
        return dq, kb, vb, kmb, dkb, dvb

    dq, _, _, _, dk, dv = jax.lax.fori_loop(
        0, n, body, (dq0, k, v, km, dk0, dv0)
    )
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        jnp.zeros_like(km),
    )


_ring_flash_core.defvjp(_ring_flash_core_fwd, _ring_flash_core_bwd)


def ring_flash_attention(
    q,
    k,
    v,
    *,
    mesh: Mesh,
    kmask=None,
    axis_name: str = "sp",
    causal: bool = False,
    batch_axes: tuple = ("dp", "fsdp"),
    head_axis: str | None = "tp",
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """Ring attention with the Pallas flash kernel per step.

    Same contract as :func:`ring_attention` (global (B, T, H, D)
    arrays, sequence sharded over ``axis_name``), but per-device memory
    is O(kernel block) instead of O((T/sp)²) and the block matmuls run
    in storage dtype on the MXU.  Off-TPU the kernels run in interpret
    mode — tests only; use :func:`ring_attention` for real CPU work.
    """
    from learningorchestra_tpu.ops.attention import _auto_interpret

    if interpret is None:
        interpret = _auto_interpret()
    ha = head_axis if head_axis and mesh.shape.get(head_axis, 1) > 1 else None
    qkv_spec = P(batch_axes, axis_name, ha, None)
    mask_spec = P(batch_axes, axis_name)
    b, t, h_, d = q.shape
    sp = mesh.shape.get(axis_name, 1)
    if t % sp:
        raise ValueError(f"sequence {t} not divisible by {axis_name}={sp}")
    t_loc = t // sp
    block_q, block_k, pad = _ring_blocks(t_loc, block_q, block_k)
    if kmask is None:
        kmask = jnp.ones((b, t), bool)

    def shard_body(qs, ks, vs, kms):
        # (B, T_loc, H, D) -> kernel layout (B, H, T_loc, D), padded.
        qs, ks, vs = (x.transpose(0, 2, 1, 3) for x in (qs, ks, vs))
        kmf = kms.astype(jnp.float32)[:, None, :]  # (B, 1, T_loc)
        if pad:
            cfg = ((0, 0), (0, 0), (0, pad), (0, 0))
            qs = jnp.pad(qs, cfg)
            ks = jnp.pad(ks, cfg)
            vs = jnp.pad(vs, cfg)
            kmf = jnp.pad(kmf, ((0, 0), (0, 0), (0, pad)))
        opts = (axis_name, causal, block_q, block_k, interpret)
        out = _ring_flash_core(qs, ks, vs, kmf, opts)
        if pad:
            out = out[:, :, :t_loc]
        return out.transpose(0, 2, 1, 3)

    # check_vma=False: pallas_call can't declare vma on its outputs, and
    # no vma-checked transpose rules are needed — the custom_vjp spells
    # out every collective in both directions itself.
    fn = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, kmask)


def reference_attention(q, k, v, kmask=None, causal: bool = False):
    """Unsharded exact attention — the correctness oracle for tests."""
    s = _block_attend(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), kmask, None
    )
    if causal:
        t = q.shape[1]
        bias = jnp.where(
            jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], 0.0, NEG_INF
        )
        s = s + bias[None, None]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


class RingSelfAttention(nn.Module):
    """Drop-in Flax self-attention block that runs ring attention when a
    mesh with sp>1 is supplied, falling back to vanilla attention.

    Used by the long-context transformer (models/longcontext.py); QKV/out
    projections are plain Dense layers, so they pick up tp sharding from
    the standard partition rules (parallel/sharding.py).
    """

    num_heads: int
    mesh: Mesh | None = None
    causal: bool = False
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    # None = auto: the Pallas ring-flash path on TPU (O(block) memory,
    # bf16 MXU matmuls), the jnp ring elsewhere.
    use_flash: bool | None = None

    @nn.compact
    def __call__(self, x, kmask=None):
        b, t, hidden = x.shape
        head_dim = hidden // self.num_heads
        qkv = nn.DenseGeneral(
            (3, self.num_heads, head_dim), dtype=self.dtype, name="qkv"
        )(x)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
            use_flash = self.use_flash
            if use_flash is None:
                use_flash = jax.default_backend() == "tpu"
            attend = ring_flash_attention if use_flash else ring_attention
            o = attend(
                q, k, v, mesh=self.mesh, kmask=kmask, causal=self.causal
            )
        else:
            # reference_attention already returns q.dtype — no cast
            # (astype(None) would force f32 and pin the whole residual
            # stream there, defeating mixed precision).
            o = reference_attention(
                q, k, v, kmask=kmask, causal=self.causal
            )
        o = o.reshape(b, t, hidden)
        return nn.Dense(hidden, dtype=self.dtype, name="out")(o)
