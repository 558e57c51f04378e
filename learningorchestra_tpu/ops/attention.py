"""Flash attention — blockwise online-softmax attention as a Pallas TPU
kernel with a custom VJP (forward and backward both Pallas).

The reference system's attention lives inside wrapped keras models and is
materialised as a full (T, T) score matrix per head; this kernel never
materialises scores — it streams K/V blocks through VMEM with the online
softmax (running max / running sum) recurrence, so HBM traffic is O(T·D)
instead of O(T²) and the MXU sees (block_q × D) @ (D × block_k) matmuls.

Numerical contract (tested against ``mha_reference``):
- matmuls multiply in the storage dtype (bf16 on the training path —
  full MXU rate) and ACCUMULATE in float32 via
  ``preferred_element_type``; the softmax/online-max recurrence runs in
  float32, with the probabilities/dS downcast to the storage dtype for
  the second matmul of each pass (standard flash-attention precision);
- key-side padding mask: masked keys contribute zero probability; rows
  whose keys are ALL masked output exactly 0 (and get zero gradient).

On non-TPU backends the same kernels run in Pallas interpret mode, which
is how the unit tests exercise them on CPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30  # additive mask value; exp(_NEG_BIG - lse) == 0 in f32
_LSE_EMPTY = 1e30  # lse sentinel for fully-masked rows: exp(s - 1e30) == 0


def _tpu_params(n_parallel: int):
    """Mark the trailing grid axis sequential (carry in VMEM scratch)
    and the leading ones parallel, so Mosaic pipelines the K/V block
    DMAs against compute (double buffering)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",)
    )


def _auto_interpret() -> bool:
    # LO_TPU_FLASH_INTERPRET overrides the backend heuristic: "0"
    # forces the real Mosaic lowering on a CPU-only host — used by the
    # cross-platform export test that proves the TRAIN path lowers to
    # tpu_custom_call without needing live TPU hardware.
    env = os.environ.get("LO_TPU_FLASH_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def _validate_window(window, causal) -> None:
    if window is None:
        return
    if not causal:
        raise ValueError("window requires causal=True")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


# ---------------------------------------------------------------------------
# Reference implementation (jnp) — ground truth for tests and CPU fallback.
# ---------------------------------------------------------------------------


def mha_reference(q, k, v, key_mask=None, causal: bool = False,
                  window: int | None = None, block: int | None = None):
    """Plain multi-head attention. q,k,v: (B, H, T, D); key_mask: (B, Tk).

    Fully-masked rows output exactly 0 with exactly-0 gradients.  The
    masking uses the double-``where`` pattern: masked lanes never touch a
    live value on either the forward or backward path (a single ``where``
    after ``exp`` leaves NaN-producing -1e30 arithmetic on the grad path).
    ``causal=True`` additionally masks keys beyond each query's position
    (decoder self-attention; Tq must equal Tk); ``window`` restricts each
    query to its last ``window`` positions (sliding-window attention).
    ``block`` masks by blocks of that many positions instead: query i
    sees key j iff ``j // block <= i // block`` (Tq must equal Tk).
    """
    _validate_window(window, causal)
    if block is not None and causal:
        raise ValueError("block attention is its own mask: causal=False")
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk",
        q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    tq, tk = q.shape[2], k.shape[2]
    maskb = None
    if key_mask is not None:
        maskb = key_mask.astype(bool)[:, None, None, :]
    if causal:
        rows = jnp.arange(tq)[:, None]
        cols = jnp.arange(tk)[None, :]
        tri = cols <= rows
        if window is not None:
            tri = tri & (cols > rows - window)
        maskb = tri[None, None] if maskb is None else (
            maskb & tri[None, None]
        )
    if block is not None:
        blk = (jnp.arange(tk)[None, :] // block) \
            <= (jnp.arange(tq)[:, None] // block)
        maskb = blk[None, None] if maskb is None else (
            maskb & blk[None, None]
        )
    if maskb is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        m = jnp.max(jnp.where(maskb, s, _NEG_BIG), axis=-1, keepdims=True)
        # Fully-masked rows: make the subtraction a no-op so the masked
        # branch below sees a clean constant, not (-1e30) - (-1e30).
        m = jnp.where(m > _NEG_BIG / 2, m, 0.0)
        p = jnp.exp(jnp.where(maskb, s - m, _NEG_BIG))  # exp(-1e30) == 0
        denom = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.maximum(denom, 1e-30)  # all-masked rows: 0/1e-30 == 0
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _causal_keep(i, j, bq, bk, window=None):
    """(bq, bk) multiplicative mask for the causal region of block
    (i, j): 1.0 where global col <= global row — and, with a sliding
    ``window``, col > row - window (each query sees its last ``window``
    positions only, Mistral-style banded attention)."""
    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = cols <= rows
    if window is not None:
        keep = keep & (cols > rows - window)
    return keep.astype(jnp.float32)


def _block_live(i, j, bq, bk, causal, window):
    """Predicate builder: should block (i, j) compute at all?  Causal
    kills blocks fully above the diagonal; a window additionally kills
    blocks fully left of the band."""
    live = True
    if causal:
        live = j * bk < (i + 1) * bq
    if window is not None:
        live = live & ((j + 1) * bk + window - 1 > i * bq)
    return live


def _win_lo(i, bq, bk, window):
    """First k-block that can intersect q-block ``i``'s band."""
    return jnp.maximum(0, (i * bq - (window - 1)) // bk)


def _win_k_slots(bq, bk, window, nk):
    """Grid length of the streamed k axis under a window: the band of
    one q block spans bq + window - 1 columns -> a CONSTANT number of
    k blocks, so HBM traffic is O(T·window), not O(T²).  (Without
    this, pl.when would skip the MXU work but the BlockSpec pipeline
    would still DMA every K/V block.)"""
    return min(nk, (bq + window - 1 + bk - 1) // bk + 1)


def _fwd_kernel(
    q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window,
):
    """One (q-block, k-block) grid step.  The k axis is the innermost,
    sequential grid dimension: the online-softmax running state lives in
    VMEM scratch across k steps, and each step sees ONE (bk, D) K/V block
    streamed from HBM — VMEM use is O(block), not O(T), and Mosaic
    overlaps the next block's DMA with this block's MXU work.  Causal
    blocks fully above the diagonal skip their compute entirely."""
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    # Windowed grids stream only the band's k blocks; jj is an offset
    # from the band's first block, not an absolute block index.
    j = jj if window is None else _win_lo(i, bq, bk, window) + jj

    @pl.when(jj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        # Matmul inputs stay in their storage dtype (bf16 on the
        # training path): the MXU multiplies bf16 at full rate and
        # accumulates f32 via preferred_element_type — upcasting first
        # would halve throughput.
        q = q_ref[0, 0]  # (bq, D)
        kb = k_ref[0, 0]  # (bk, D)
        vb = v_ref[0, 0]
        keep = km_ref[0]  # (1, bk) float32, 1=keep
        if causal:
            keep = keep * _causal_keep(i, j, bq, bk, window)  # (bq, bk)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk) f32
        s = s + (keep - 1.0) * -_NEG_BIG  # masked keys -> -1e30
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * keep  # zero masked keys exactly
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(
            p, axis=-1, keepdims=True
        )
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_block_live(i, j, bq, bk, causal, window))(_compute)
    else:
        _compute()

    @pl.when(jj == nk - 1)
    def _finalize():
        l = l_scr[...]
        nonempty = l > 0.0
        out = jnp.where(
            nonempty, acc_scr[...] / jnp.where(nonempty, l, 1.0), 0.0
        )
        o_ref[0, 0] = out.astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            nonempty,
            m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)),
            _LSE_EMPTY,
        )


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref, dq_ref,
    dq_scr, *, scale, causal, window,
):
    """dQ pass: grid (b, h, nq, nk) — same streamed K/V layout as the
    forward; dq accumulates in VMEM scratch across the sequential k axis."""
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    j = jj if window is None else _win_lo(i, bq, bk, window) + jj

    @pl.when(jj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        keep = km_ref[0]
        if causal:
            keep = keep * _causal_keep(i, j, bq, bk, window)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = s + (keep - 1.0) * -_NEG_BIG
        p = jnp.exp(s - lse) * keep  # (bq, bk) f32
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(kb.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_block_live(i, j, bq, bk, causal, window))(_compute)
    else:
        _compute()

    @pl.when(jj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window, nq_total,
):
    """dK/dV pass: grid (b, h, nk, nq) — one K/V block is resident while
    Q/dO/lse/delta blocks stream along the sequential inner q axis."""
    j = pl.program_id(2)
    ii = pl.program_id(3)
    nq = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    # Windowed grids stream only the band's q blocks for this k block.
    i = ii if window is None else (j * bk) // bq + ii

    @pl.when(ii == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        kb = k_ref[0, 0]  # (bk, D)
        vb = v_ref[0, 0]
        keep = km_ref[0]  # (1, bk)
        if causal:
            keep = keep * _causal_keep(i, j, bq, bk, window)
        q = q_ref[0, 0]  # (bq, D)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = s + (keep - 1.0) * -_NEG_BIG
        p = jnp.exp(s - lse) * keep  # (bq, bk) f32
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = _block_live(i, j, bq, bk, causal, window)
    if window is not None:
        live = live & (i < nq_total)
    if causal:
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ii == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _k_index_maps(block_q, block_k, window, nk):
    """(4-D K/V map, 3-D mask map) for the streamed k axis.  Windowed
    grids translate the per-band offset jj to an absolute block index,
    clipped into range — the clipped duplicates at the edges are DMA'd
    but skipped by the kernel's live predicate."""
    if window is None:
        return (lambda bb, hh, i, j: (bb, hh, j, 0)), (
            lambda bb, hh, i, j: (bb, 0, j))

    def kv(bb, hh, i, jj):
        j = _win_lo(i, block_q, block_k, window) + jj
        return (bb, hh, jnp.clip(j, 0, nk - 1), 0)

    def mask(bb, hh, i, jj):
        j = _win_lo(i, block_q, block_k, window) + jj
        return (bb, 0, jnp.clip(j, 0, nk - 1))

    return kv, mask


def _fwd_call(q, k, v, km, block_q, block_k, interpret, causal,
              window=None, prefix="flash"):
    """``prefix`` names the kernel (``flash_fwd``; the ring passes
    ``ring``): a device trace tells the kernels apart by that name."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    nk_grid = nk if window is None else _win_k_slots(
        block_q, block_k, window, nk
    )
    kv_map, mask_map = _k_index_maps(block_q, block_k, window, nk)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window
    )
    name = f"{prefix}_fwd"
    call = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk_grid),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k), mask_map),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_tpu_params(3),
        interpret=interpret,
        name=name,
    )
    with jax.named_scope(name):
        return call(q, k, v, km)


def _bwd_call(q, k, v, km, do, lse, delta, block_q, block_k, interpret,
              causal, window=None, prefix="flash"):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    nk_grid = nk if window is None else _win_k_slots(
        block_q, block_k, window, nk
    )
    kv_map, mask_map = _k_index_maps(block_q, block_k, window, nk)
    scale = 1.0 / (d ** 0.5)

    dq_call = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window
        ),
        grid=(b, h, nq, nk_grid),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k), mask_map),
            pl.BlockSpec(
                (1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_tpu_params(3),
        interpret=interpret,
        name=f"{prefix}_dq",
    )
    with jax.named_scope(f"{prefix}_dq"):
        dq = dq_call(q, k, v, km, do, lse, delta)

    if window is None:
        nq_grid = nq
        q_map = lambda bb, hh, j, i: (bb, hh, i, 0)  # noqa: E731
    else:
        # One k block's band spans bk + window - 1 rows of q.
        nq_grid = min(nq, (block_k + window - 1 + block_q - 1)
                      // block_q + 1)

        def q_map(bb, hh, j, ii):
            i = (j * block_k) // block_q + ii
            return (bb, hh, jnp.clip(i, 0, nq - 1), 0)

    dkv_call = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            nq_total=nq,
        ),
        grid=(b, h, nk, nq_grid),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
            pl.BlockSpec((1, 1, block_k), lambda bb, hh, j, i: (bb, 0, j)),
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q, 1), q_map),
            pl.BlockSpec((1, 1, block_q, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_tpu_params(3),
        interpret=interpret,
        name=f"{prefix}_dkv",
    )
    with jax.named_scope(f"{prefix}_dkv"):
        dk, dv = dkv_call(q, k, v, km, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp core (operates on block-aligned shapes)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, km, block_q, block_k, interpret, causal,
                window):
    o, _ = _fwd_call(
        q, k, v, km, block_q, block_k, interpret, causal, window
    )
    return o


def _flash_core_fwd(q, k, v, km, block_q, block_k, interpret, causal,
                    window):
    o, lse = _fwd_call(
        q, k, v, km, block_q, block_k, interpret, causal, window
    )
    return o, (q, k, v, km, o, lse)


def _flash_core_bwd(block_q, block_k, interpret, causal, window, res, g):
    q, k, v, km, o, lse = res
    do = g.astype(jnp.float32)
    # (B, H, Tq, 1) — trailing singleton keeps TPU block shapes legal.
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True)
    dq, dk, dv = _bwd_call(
        q, k, v, km, do.astype(q.dtype), lse, delta,
        block_q, block_k, interpret, causal, window,
    )
    return dq, dk, dv, jnp.zeros_like(km)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def flash_attention(
    q,
    k,
    v,
    key_mask=None,
    *,
    causal: bool = False,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """Blockwise attention. q,k,v: (B, H, T, D); key_mask: (B, Tk) bool.

    Sequences are padded to block multiples internally; padded keys are
    masked out, padded query rows are sliced off the output.

    Default blocks follow the round-2 sweep on one TPU v5e (older jax,
    scalar-readback timing; not re-measured since — PERF.md): (256,
    512) won for T <= 8k, (512, 1024) for longer; 128-sized blocks
    leave the MXU idle on grid overhead (~4 MFLOP per step).
    """
    if interpret is None:
        interpret = _auto_interpret()
    _validate_window(window, causal)
    t_longest = max(q.shape[2], k.shape[2])
    if block_q is None:
        block_q = 256 if t_longest <= 8192 else 512
    if block_k is None:
        block_k = 512 if t_longest <= 8192 else 1024
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_q = min(block_q, max(8, tq))
    block_k = min(block_k, max(8, tk))
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k

    if key_mask is None:
        key_mask = jnp.ones((b, tk), jnp.float32)
    km = key_mask.astype(jnp.float32)[:, None, :]  # (B, 1, Tk)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        km = jnp.pad(km, ((0, 0), (0, 0), (0, pad_k)))

    out = _per_shard(
        lambda q, k, v, km: _flash_core(
            q, k, v, km, block_q, block_k, interpret, causal, window
        ),
        q, k, v, km,
    )
    if pad_q:
        out = out[:, :, :tq]
    return out


# Mesh axis conventions of parallel/mesh.py: the batch is sharded over
# the data axes, attention heads over the tensor axis.
_BATCH_AXES = ("dp", "fsdp")
_HEAD_AXIS = "tp"


def _per_shard(core, q, k, v, km):
    """Run ``core(q, k, v, km)`` once per device shard when tracing
    under a device mesh (``jax.set_mesh`` — the mesh-sharded trainers).

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh the call is wrapped in
    ``shard_map`` over the axes jit still partitions automatically:
    batch over the data axes, heads over the tensor axis, each only
    when it divides the dimension (otherwise that dimension — and the
    whole sequence, always — is gathered and the kernel's work repeated
    across the axis, e.g. the batch-of-one init pass).  Inside another
    ``shard_map`` (ring attention, the pipeline step) every axis is
    already manual and the kernel is per-device as it stands."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = {
        a for a in mesh.axis_names
        if a not in mesh.manual_axes and mesh.shape[a] > 1
    }
    if not auto:
        return core(q, k, v, km)

    def axes_for(dim: int, names: tuple):
        names = tuple(a for a in names if a in auto)
        size = 1
        for a in names:
            size *= mesh.shape[a]
        return names if names and dim % size == 0 else None

    batch = axes_for(q.shape[0], _BATCH_AXES)
    heads = axes_for(q.shape[1], (_HEAD_AXIS,))
    qkv = jax.sharding.PartitionSpec(batch, heads, None, None)
    # check_vma=False: pallas_call cannot declare vma on its outputs.
    return jax.shard_map(
        core,
        in_specs=(qkv, qkv, qkv,
                  jax.sharding.PartitionSpec(batch, None, None)),
        out_specs=qkv,
        check_vma=False,
    )(q, k, v, km)
