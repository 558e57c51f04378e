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


def _tpu_params(n_parallel: int, vmem_limit_bytes: int | None = None):
    """Mark the trailing grid axis sequential (carry in VMEM scratch)
    and the leading ones parallel, so Mosaic pipelines the K/V block
    DMAs against compute (double buffering)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",),
        vmem_limit_bytes=vmem_limit_bytes,
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


def _each_head(scratch, body):
    """``body(h, *scratch of head h)`` for each head of the grid step's
    block.  Scratch of one head a step (2-D) is head 0's as it stands;
    a block of heads has a leading head axis on its scratch, and the
    same body runs in a loop over the heads on its slice of each — a
    loop, not a copy of the body a head, so the kernel compiles as
    fast whatever the block's width.  (Six copies ran BERT-base's three
    kernels 0.29 ms a layer faster on one v5e but took 21.6 s more to
    compile in a fit's set-up: PERF.md.)"""
    if len(scratch[0].shape) == 2:
        body(0, *scratch)
        return

    def head(h, carry):
        body(h, *(s.at[h] for s in scratch))
        return carry

    jax.lax.fori_loop(0, scratch[0].shape[0], head, 0)


def _fwd_kernel(
    q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window,
):
    """One (q-block, k-block) grid step over the block's heads.  The k
    axis is the innermost, sequential grid dimension: the online-softmax
    running state of each head lives in VMEM scratch across k steps,
    and each step sees ONE (bk, D) K/V block a head streamed from HBM
    — VMEM use is O(block), not O(T), and Mosaic overlaps the next
    block's DMA with this block's MXU work.  Causal blocks fully above
    the diagonal skip their compute entirely."""
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    # Windowed grids stream only the band's k blocks; jj is an offset
    # from the band's first block, not an absolute block index.
    j = jj if window is None else _win_lo(i, bq, bk, window) + jj
    _each_head((m_scr, l_scr, acc_scr), lambda h, *scratch: _fwd_head(
        h, q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref, *scratch,
        i=i, j=j, jj=jj, nk=nk, scale=scale, causal=causal, window=window,
    ))


def _fwd_head(
    h, q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, i, j, jj, nk, scale, causal, window,
):
    """Head ``h`` of the block: the online softmax of one k block."""
    bq, bk = q_ref.shape[2], k_ref.shape[2]

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
        q = q_ref[0, h]  # (bq, D)
        kb = k_ref[0, h]  # (bk, D)
        vb = v_ref[0, h]
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
        o_ref[0, h] = out.astype(o_ref.dtype)
        lse_ref[0, h] = jnp.where(
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
    """dQ pass: grid (b, h / hb, nq, nk) — same streamed K/V layout as
    the forward; each head's dq accumulates in its VMEM scratch across
    the sequential k axis."""
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    j = jj if window is None else _win_lo(i, bq, bk, window) + jj
    _each_head((dq_scr,), lambda h, dq_scr: _dq_head(
        h, q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref, dq_ref,
        dq_scr, i=i, j=j, jj=jj, nk=nk, scale=scale, causal=causal,
        window=window,
    ))


def _dq_head(
    h, q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref, dq_ref,
    dq_scr, *, i, j, jj, nk, scale, causal, window,
):
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(jj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0, h]
        do = do_ref[0, h]
        lse = lse_ref[0, h]  # (bq, 1)
        delta = delta_ref[0, h]
        kb = k_ref[0, h]
        vb = v_ref[0, h]
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
        dq_ref[0, h] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window, nq_total,
):
    """dK/dV pass: grid (b, h / hb, nk, nq) — one K/V block a head is
    resident while Q/dO/lse/delta blocks stream along the sequential
    inner q axis."""
    j = pl.program_id(2)
    ii = pl.program_id(3)
    nq = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    # Windowed grids stream only the band's q blocks for this k block.
    i = ii if window is None else (j * bk) // bq + ii
    _each_head((dk_scr, dv_scr), lambda h, *scratch: _dkv_head(
        h, q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref,
        dk_ref, dv_ref, *scratch, i=i, j=j, ii=ii, nq=nq, scale=scale,
        causal=causal, window=window, nq_total=nq_total,
    ))


def _dkv_head(
    h, q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, *, i, j, ii, nq, scale, causal,
    window, nq_total,
):
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ii == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        kb = k_ref[0, h]  # (bk, D)
        vb = v_ref[0, h]
        keep = km_ref[0]  # (1, bk)
        if causal:
            keep = keep * _causal_keep(i, j, bq, bk, window)
        q = q_ref[0, h]  # (bq, D)
        do = do_ref[0, h]
        lse = lse_ref[0, h]  # (bq, 1)
        delta = delta_ref[0, h]
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
        dk_ref[0, h] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, h] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Schedule: the blocks and the heads of one grid step, from shapes
# ---------------------------------------------------------------------------

#: Mosaic's default scoped VMEM on a TPU v5e; a step that needs more
#: asks for it (``vmem_limit_bytes``).
_VMEM_SCOPED = 16 * 2**20
#: The most scoped VMEM a head-blocked step may ask for: a quarter of a
#: v5e's 128 MiB.
_VMEM_BUDGET = 32 * 2**20
#: The longest sequence one block takes whole.  On one TPU v5e at BERT's
#: 12 heads of 64 (``scripts/chip_flash_sweep.py``, PERF.md) one block
#: costs 0.78 of the (256, 512) blocks at 512 positions and 0.58 at
#: 1,024; past that its score tiles alone outgrow ``_VMEM_BUDGET``.
_ONE_BLOCK_MAX = 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) tile: rows to whole sublane tiles
    (8 of 32 bits, 16 of 16), columns to whole 128-lane vregs — a
    (T, 64) block takes the room of a (T, 128) one, and a (T, 1)
    column of f32 that of a (T, 128) matrix."""
    return _round_up(rows, 32 // itemsize) * _round_up(cols, 128) * itemsize


def _vmem_bytes(kernel: str, hb: int, block_q: int, block_k: int,
                head_dim: int, itemsize: int) -> int:
    """Scoped VMEM one grid step of ``kernel`` (``fwd``, ``dq``,
    ``dkv``) takes at ``hb`` heads a step: its blocks double-buffered,
    its scratch, and the body's (block_q, block_k) f32 temporaries of
    the one head it works on (scores and probabilities; the backward's
    dP, dP - delta, dS and its cast besides)."""
    q = _tile_bytes(block_q, head_dim, itemsize)
    kv = _tile_bytes(block_k, head_dim, itemsize)
    col = _tile_bytes(block_q, 1, 4)  # lse, delta, m, l
    mask = _tile_bytes(1, block_k, 4)
    score = _tile_bytes(block_q, block_k, 4)
    if kernel == "fwd":  # in q, k, v; out o, lse; scratch m, l, acc
        blocks = 2 * q + 2 * kv + col
        scratch = 2 * col + _tile_bytes(block_q, head_dim, 4)
        temps = 3
    elif kernel == "dq":  # in q, k, v, do, lse, delta; out dq; acc dq
        blocks = 3 * q + 2 * kv + 2 * col
        scratch = _tile_bytes(block_q, head_dim, 4)
        temps = 6
    else:  # dkv: in q, k, v, do, lse, delta; out dk, dv; acc dk, dv
        blocks = 2 * q + 4 * kv + 2 * col
        scratch = 2 * _tile_bytes(block_k, head_dim, 4)
        temps = 6
    return 2 * (hb * blocks + mask) + hb * scratch + temps * score


def _step_vmem(hb, block_q, block_k, head_dim, itemsize) -> int:
    """The most scoped VMEM a step of any of the three kernels takes."""
    return max(
        _vmem_bytes(kernel, hb, block_q, block_k, head_dim, itemsize)
        for kernel in ("fwd", "dq", "dkv")
    )


def flash_schedule(t_q: int, t_k: int, heads: int, head_dim: int,
                   window: int | None, itemsize: int = 2
                   ) -> tuple[int, int, int]:
    """``(hb, block_q, block_k)``: heads and sequence positions of one
    grid step of the three kernels.

    Where one block holds the whole sequence (``_one_block``: no
    window, ``max(t_q, t_k) <= _ONE_BLOCK_MAX``, ``head_dim < 128``) a
    step takes ``hb`` heads over all of it: ``hb`` is the largest
    divisor of ``heads`` whose step fits ``_VMEM_BUDGET``
    (``_vmem_bytes``; one head always), and each block is its side's
    length, lane-aligned past 128: no second pass over K/V for a
    second q block, and fewer, fuller grid steps (PERF.md).
    Everywhere else: one head a step and the blocks of the round-2
    sweep, (256, 512) to 8k positions, (512, 1024) past them."""
    if _one_block(t_q, t_k, head_dim, window):
        block_q, block_k = (
            max(8, t) if t <= 128 else _round_up(t, 128) for t in (t_q, t_k)
        )
        hb = max(
            n for n in range(1, heads + 1) if heads % n == 0 and (
                n == 1 or _step_vmem(n, block_q, block_k, head_dim,
                                     itemsize) <= _VMEM_BUDGET
            )
        )
        return hb, block_q, block_k
    return (1, *_default_blocks(t_q, t_k))


def _one_block(t_q, t_k, head_dim, window) -> bool:
    """Does one block hold the whole sequence (``flash_schedule``)?"""
    return window is None and head_dim < 128 \
        and max(t_q, t_k) <= _ONE_BLOCK_MAX


def _default_blocks(t_q, t_k, block_q=None, block_k=None):
    """The round-2 sweep's blocks where none is given, clamped to the
    sequence: (256, 512) to 8k positions, (512, 1024) past them."""
    long = max(t_q, t_k) > 8192
    return (min(block_q or (512 if long else 256), max(8, t_q)),
            min(block_k or (1024 if long else 512), max(8, t_k)))


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


def _params(kernel, hb, block_q, block_k, d, itemsize):
    """Compiler parameters of one kernel: a step of the one-block
    schedule asks for the scoped VMEM it takes where that is past the
    default."""
    if hb is None:
        return _tpu_params(3)
    need = _vmem_bytes(kernel, hb, block_q, block_k, d, itemsize)
    return _tpu_params(3, need if need > _VMEM_SCOPED else None)


def _run(call, name, sched, *args):
    """Run the kernel ``call`` under the scope ``name``, which names its
    custom call's HLO instruction.  A call of the one-block schedule
    nests that in a scope naming it (``flash_sched_h6_q512_k512``),
    which reaches the call's ``op_name`` metadata only."""
    if sched is not None:
        with jax.named_scope(sched):
            return _run(call, name, None, *args)
    with jax.named_scope(name):
        return call(*args)


def _scratch(hb, *shapes):
    """f32 VMEM scratch of the given shapes, with a leading head axis
    where a grid step takes a block of ``hb`` heads (``_each_head``)."""
    return [pltpu.VMEM(shape if hb is None else (hb, *shape), jnp.float32)
            for shape in shapes]


def _sched(prefix, hb, block_q, block_k):
    if hb is None:
        return None
    return f"{prefix}_sched_h{hb}_q{block_q}_k{block_k}"


def _fwd_call(q, k, v, km, block_q, block_k, interpret, causal,
              window=None, prefix="flash", hb=None):
    """``prefix`` names the kernel (``flash_fwd``; the ring passes
    ``ring``): a device trace tells the kernels apart by that name.
    ``hb``: the one-block schedule's heads a grid step
    (``flash_schedule``); None for one head a step over the caller's
    blocks."""
    b, h, tq, d = q.shape
    hs = hb or 1
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
        grid=(b, h // hs, nq, nk_grid),
        in_specs=[
            pl.BlockSpec(
                (1, hs, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec((1, hs, block_k, d), kv_map),
            pl.BlockSpec((1, hs, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k), mask_map),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, hs, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec(
                (1, hs, block_q, 1), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=_scratch(hb, (block_q, 1), (block_q, 1),
                                (block_q, d)),
        compiler_params=_params(
            "fwd", hb, block_q, block_k, d, q.dtype.itemsize
        ),
        interpret=interpret,
        name=name,
    )
    return _run(call, name, _sched(prefix, hb, block_q, block_k),
                q, k, v, km)


def _bwd_call(q, k, v, km, do, lse, delta, block_q, block_k, interpret,
              causal, window=None, prefix="flash", hb=None):
    b, h, tq, d = q.shape
    hs = hb or 1
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    nk_grid = nk if window is None else _win_k_slots(
        block_q, block_k, window, nk
    )
    kv_map, mask_map = _k_index_maps(block_q, block_k, window, nk)
    scale = 1.0 / (d ** 0.5)
    sched = _sched(prefix, hb, block_q, block_k)

    dq_call = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window
        ),
        grid=(b, h // hs, nq, nk_grid),
        in_specs=[
            pl.BlockSpec(
                (1, hs, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec((1, hs, block_k, d), kv_map),
            pl.BlockSpec((1, hs, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k), mask_map),
            pl.BlockSpec(
                (1, hs, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec(
                (1, hs, block_q, 1), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
            pl.BlockSpec(
                (1, hs, block_q, 1), lambda bb, hh, i, j: (bb, hh, i, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, hs, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=_scratch(hb, (block_q, d)),
        compiler_params=_params(
            "dq", hb, block_q, block_k, d, q.dtype.itemsize
        ),
        interpret=interpret,
        name=f"{prefix}_dq",
    )
    dq = _run(dq_call, f"{prefix}_dq", sched, q, k, v, km, do, lse, delta)

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
        grid=(b, h // hs, nk, nq_grid),
        in_specs=[
            pl.BlockSpec((1, hs, block_q, d), q_map),
            pl.BlockSpec(
                (1, hs, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
            pl.BlockSpec(
                (1, hs, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
            pl.BlockSpec((1, 1, block_k), lambda bb, hh, j, i: (bb, 0, j)),
            pl.BlockSpec((1, hs, block_q, d), q_map),
            pl.BlockSpec((1, hs, block_q, 1), q_map),
            pl.BlockSpec((1, hs, block_q, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, hs, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
            pl.BlockSpec(
                (1, hs, block_k, d), lambda bb, hh, j, i: (bb, hh, j, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=_scratch(hb, (block_k, d), (block_k, d)),
        compiler_params=_params(
            "dkv", hb, block_q, block_k, d, q.dtype.itemsize
        ),
        interpret=interpret,
        name=f"{prefix}_dkv",
    )
    dk, dv = _run(dkv_call, f"{prefix}_dkv", sched,
                  q, k, v, km, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp core (operates on block-aligned shapes)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, km, block_q, block_k, interpret, causal,
                window, hb):
    o, _ = _fwd_call(
        q, k, v, km, block_q, block_k, interpret, causal, window, hb=hb
    )
    return o


def _flash_core_fwd(q, k, v, km, block_q, block_k, interpret, causal,
                    window, hb):
    o, lse = _fwd_call(
        q, k, v, km, block_q, block_k, interpret, causal, window, hb=hb
    )
    return o, (q, k, v, km, o, lse)


def _flash_core_bwd(block_q, block_k, interpret, causal, window, hb, res,
                    g):
    q, k, v, km, o, lse = res
    do = g.astype(jnp.float32)
    # (B, H, Tq, 1) — trailing singleton keeps TPU block shapes legal.
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True)
    dq, dk, dv = _bwd_call(
        q, k, v, km, do.astype(q.dtype), lse, delta,
        block_q, block_k, interpret, causal, window, hb=hb,
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

    Without ``block_q`` / ``block_k`` the schedule follows the shapes
    (``flash_schedule``).  ``scripts/chip_flash_sweep.py`` on one TPU
    v5e, BERT-base's (32, 12, T, 64) bf16 forward and backward: at 512
    positions the round-2 sweep's (256, 512) blocks of one head took
    2.83 ms in the three kernels, one block of 6 heads over the whole
    sequence 2.21; at 1,024 10.65 against 6.20 for one block of one
    head.  So to 1,024 positions at head dims under 128 a grid step
    takes a block of heads over the whole sequence; past that, with a
    window, at head dim 128 or more, and with explicit blocks, one head
    a step over (256, 512) blocks, (512, 1024) past 8k.
    """
    if interpret is None:
        interpret = _auto_interpret()
    _validate_window(window, causal)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scheduled = block_q is None and block_k is None
    if scheduled:
        _, block_q, block_k = flash_schedule(
            tq, tk, h, d, window, q.dtype.itemsize
        )
    else:
        block_q, block_k = _default_blocks(tq, tk, block_q, block_k)
    one_block = scheduled and _one_block(tq, tk, d, window)
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

    def core(q, k, v, km):
        # Under a mesh the kernel sees its shard's heads: the head
        # block is chosen from them.
        hb = flash_schedule(tq, tk, q.shape[1], d, window,
                            q.dtype.itemsize)[0] if one_block else None
        return _flash_core(
            q, k, v, km, block_q, block_k, interpret, causal, window, hb
        )

    out = _per_shard(core, q, k, v, km)
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
