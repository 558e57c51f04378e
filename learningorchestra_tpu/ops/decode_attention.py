"""Attention of a decode step over the KV pages of a slot pool: the
step's rows go into the pages and the step's queries attend over them.

The pages of one layer are two leaves ``(S, H_kv, Tk / pack, pack *
hd)``: ``pack = 128 // hd`` consecutive positions share one row of 128
lanes where a head is narrower than that (``page_pack``), so that a
leaf has the same bytes row-major as ``(S, H_kv, Tk, hd)`` and the TPU
stores it without padding a head to 128 lanes.  ``unpack_pages`` /
``pack_pages`` are the two views.

``cached_attend`` is the layer's entry.  On the TPU, where the tiles
divide the shapes, it is ONE Pallas kernel a layer (``decode_attend``)
that reads each slot's pages once, block by block and no further than
the slot's last key, and writes the step's rows into the donated pages
in place: the bytes written scale with the rows, the bytes read with
the keys attended.  Anywhere else (the CPU; shapes the tiles do not
divide; under a mesh jit still partitions) it is the plain
``jax.numpy`` form, which is also the kernel's oracle: a one-hot
``where`` insert and ``grouped_decode_attend``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_LANES = 128
# Positions of a slot's pages one grid step reads.
_BLOCK_POSITIONS = 128
# The most one block of K (or V) pages may take of the fast memory:
# K and V, each double-buffered, stay under the 16 MiB a kernel gets.
_BLOCK_BYTES = 2 << 20
# float32 pages are multiplied in float32; narrower ones in what they
# are, accumulated in float32.
_F32_PRODUCTS = jax.lax.Precision.HIGHEST


def page_pack(head_dim: int, tk: int) -> int:
    """Positions that share one row of a page leaf."""
    pack = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return pack if tk % pack == 0 else 1


def page_shape(batch: int, kv_heads: int, tk: int, head_dim: int):
    """Shape of one page leaf (K or V) of a layer."""
    pack = page_pack(head_dim, tk)
    return (batch, kv_heads, tk // pack, pack * head_dim)


def pack_pages(pages, pack: int):
    b, h, tk, hd = pages.shape
    return pages.reshape(b, h, tk // pack, pack * hd)


def unpack_pages(pages, head_dim: int):
    b, h, rows, lanes = pages.shape
    return pages.reshape(b, h, rows * (lanes // head_dim), head_dim)


def grouped_decode_attend(q, k, v, key_mask):
    """A chunk of query positions against a (possibly grouped) KV cache.

    q: (B, H, t, hd), t = 1 for the one-token step; k/v: (B, H_kv, Tk,
    hd) with H_kv | H.  Queries attend their group's KV head DIRECTLY
    — no jnp.repeat widening of the cache, so per-step HBM traffic
    stays at H_kv (the point of GQA).  key_mask is (B, Tk), one mask
    for the whole chunk, or (B, t, Tk), one a query position; it always
    marks at least the current position.
    """
    b, h, t, hd = q.shape
    kv_heads, tk = k.shape[1], k.shape[2]
    gsz = h // kv_heads
    # (group, position) ride one axis: at t = 1 the program is the
    # one-token step's, einsum for einsum.
    qg = q.reshape(b, kv_heads, gsz * t, hd)
    s = jnp.einsum(
        "bhgd,bhkd->bhgk",
        qg.astype(jnp.float32), k.astype(jnp.float32),
    ) * (1.0 / hd ** 0.5)  # (B, H_kv, G, Tk)
    if key_mask is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        # Same double-where contract as mha_reference: fully-masked
        # rows (left-padded prompts at step 0) output exactly 0, not
        # the mean of the cache buffer.
        maskb = key_mask.astype(bool)
        if maskb.ndim == 2:
            maskb = maskb[:, None, None, :]
        else:  # (B, t, Tk): the same mask for every head of a group
            maskb = jnp.broadcast_to(
                maskb[:, None, None], (b, 1, gsz, t, tk)
            ).reshape(b, 1, gsz * t, tk)
        m = jnp.max(jnp.where(maskb, s, _NEG_BIG), axis=-1, keepdims=True)
        m = jnp.where(m > _NEG_BIG / 2, m, 0.0)
        p = jnp.exp(jnp.where(maskb, s - m, _NEG_BIG))
        p = p / jnp.maximum(
            jnp.sum(p, axis=-1, keepdims=True), 1e-30
        )
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, h, t, hd).astype(q.dtype)


def insert_rows(pages, rows, idx):
    """``pages`` (B, H_kv, Tk, hd) with the step's ``rows`` (B, H_kv,
    t, hd) of row ``r`` at ``idx[r] .. idx[r] + t - 1``: every lane
    takes the chunk's row of its own offset where it lies inside the
    chunk and keeps what it holds elsewhere, so a row beyond the bucket
    is dropped.  Bit-exact against ``dynamic_update_slice`` on the
    lanes written."""
    tk, t = pages.shape[2], rows.shape[2]
    rel = jnp.arange(tk)[None, :] - idx[:, None]
    inside = ((rel >= 0) & (rel < t))[:, None, :, None]
    if t == 1:
        return jnp.where(inside, rows, pages)
    lane = jnp.clip(rel, 0, t - 1)[:, None, :, None]
    return jnp.where(
        inside, jnp.take_along_axis(rows, lane, axis=2), pages
    )


def plain_attend(q, k, v, k_pages, v_pages, idx, key_mask):
    """``cached_attend`` in plain ``jax.numpy``: what runs off the TPU,
    and the kernel's oracle."""
    hd = q.shape[-1]
    pack = k_pages.shape[3] // hd
    k_all = insert_rows(unpack_pages(k_pages, hd), k, idx)
    v_all = insert_rows(unpack_pages(v_pages, hd), v, idx)
    out = grouped_decode_attend(q, k_all, v_all, key_mask)
    return out, pack_pages(k_all, pack), pack_pages(v_all, pack)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _sublanes(dtype) -> int:
    """Rows of one tile of ``dtype`` in the fast memory."""
    return 32 // jnp.dtype(dtype).itemsize


def _block_rows(rows: int, pack: int, sub: int) -> int:
    """Rows of a leaf one grid step reads: ``_BLOCK_POSITIONS``
    positions where that divides the bucket into whole tiles, else the
    whole bucket."""
    want = _BLOCK_POSITIONS // pack
    return want if rows % want == 0 and want % sub == 0 else rows


def _block_heads(kv_heads: int, block_bytes_a_head: int) -> int:
    """KV heads a grid step takes: the most that divide ``kv_heads``
    and keep a block under ``_BLOCK_BYTES`` (a grid step costs a third
    of a microsecond, so few large steps)."""
    for heads in range(kv_heads, 0, -1):
        if kv_heads % heads == 0 and \
                heads * block_bytes_a_head <= _BLOCK_BYTES:
            return heads
    return 1


def kernel_fits(q, k_pages) -> bool:
    """Whether ``decode_attend``'s tiles divide these shapes."""
    hd = q.shape[-1]
    rows, lanes = k_pages.shape[2], k_pages.shape[3]
    return (
        lanes % _LANES == 0 and lanes % hd == 0
        and rows % _sublanes(k_pages.dtype) == 0
        and q.dtype == k_pages.dtype
    )


def _decode_kernel(
    idx_ref, q_ref, kn_ref, vn_ref, mask_ref, k_ref, v_ref,
    acc_ref, m_ref, l_ref, kout_ref, vout_ref,
    m_scr, l_scr, acc_scr, sem,
    *, t, pack, hd, sub, tk, tiles, precision,
):
    """One (slot, block of KV heads, block of positions) grid step.
    The positions' axis is the innermost, sequential one: the online
    softmax's running state lives in scratch across it.  Rows of a
    packed leaf are queries of their own (``decode_attend`` folds the
    ``pack`` partial results of a query)."""
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads, rows, lanes = k_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    start = idx_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(n):
        """The n-th tile of ``sub`` rows that holds rows of the step:
        whether it lies in this block, its first row in the leaf, its
        rows in the block."""
        first = (start // pack // sub + n) * sub
        here = (first <= (start + t - 1) // pack) \
            & (first < tk // pack) & (first // rows == j)
        return here, first, pl.ds(pl.multiple_of(first - j * rows, sub), sub)

    def copies(n, first, local):
        where = (b, pl.ds(h * heads, heads), pl.ds(first, sub))
        return [
            pltpu.make_async_copy(
                src.at[0, :, local], dst.at[where], sem.at[2 * n + c]
            )
            for c, (src, dst) in enumerate(
                ((k_ref, kout_ref), (v_ref, vout_ref))
            )
        ]

    def insert(n):
        """Put the step's rows into tile n of the block as it lies in
        the fast memory (what is attended below is the pages WITH the
        step's rows), and send that tile, and nothing else, back to
        the pages."""
        here, first, local = tile(n)

        @pl.when(here)
        def _():
            pos = (
                jax.lax.broadcasted_iota(jnp.int32, (sub, lanes), 0)
                + first
            ) * pack + jax.lax.broadcasted_iota(
                jnp.int32, (sub, lanes), 1
            ) // hd
            for new, ref in ((kn_ref, k_ref), (vn_ref, v_ref)):
                patch = ref[0, :, local]
                for i in range(t):
                    patch = jnp.where(
                        (pos == start + i)[None], new[0, :, i:i + 1],
                        patch,
                    )
                ref[0, :, local] = patch
            for copy in copies(n, first, local):
                copy.start()

    def settle(n):
        here, first, local = tile(n)

        @pl.when(here)
        def _():
            for copy in copies(n, first, local):
                copy.wait()

    # Blocks beyond the slot's last key are neither fetched (the index
    # map holds the last one needed) nor scored.
    @pl.when(j * rows * pack < jnp.minimum(start + t, tk))
    def _attend():
        for n in range(tiles):
            insert(n)
        keep = (mask_ref[0, 0] != 0)[None]  # (1, M, rows)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision,
        ) * (1.0 / hd ** 0.5)  # (heads, M, rows)
        s = jnp.where(keep, s, _NEG_BIG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # The second where: a row with no key yet has m = -1e30 and
        # exp(s - m) = 1 on every masked lane.
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(
            p, axis=-1, keepdims=True
        )
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision,
        )
        for n in range(tiles):
            settle(n)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def _pack_queries(q, key_mask, kv_heads, pack, blocks, sub):
    """The queries and their masks as the kernel takes them.  Queries
    of one KV head: (position in its row, head of the group, position
    of the chunk), ``pack * G * t`` of them, each against the lanes of
    its own position of a row only (zeros on the others'); padded to
    whole tiles with queries that see no key.  The mask: (B, blocks,
    queries, rows of a block), a query's keys those of its own position
    of each row."""
    b, h, t, hd = q.shape
    group = h // kv_heads
    m_rows = pack * group * t
    pad = ((0, 0), (0, 0), (0, -m_rows % sub), (0, 0))
    place = jnp.eye(pack, dtype=q.dtype)
    qp = (
        q.reshape(b, kv_heads, 1, group * t, 1, hd)
        * place[None, None, :, None, :, None]
    ).reshape(b, kv_heads, m_rows, pack * hd)
    mask = key_mask.astype(jnp.int32)
    if mask.ndim == 2:
        mask = mask[:, None, :]
    rows = mask.shape[2] // (blocks * pack)
    # (B, 1 | t, Tk) -> (B, blocks, pack * group * t, rows)
    mask = jnp.broadcast_to(
        mask.reshape(b, 1, mask.shape[1], blocks, rows, pack)
        .transpose(0, 3, 5, 1, 2, 4),
        (b, blocks, pack, group, t, rows),
    ).reshape(b, blocks, m_rows, rows)
    return jnp.pad(qp, pad), jnp.pad(mask, pad)


def _fold(acc, m, l, pack, shape):
    """The attention output (``shape`` = (B, H, t, hd)) from the
    kernel's unnormalised sums, running maxima and denominators a
    packed query: the ``pack`` parts of a query (each over the keys at
    one position of a row) merge as softmax parts do."""
    b, h, t, hd = shape
    kv_heads = acc.shape[1]
    queries = h // kv_heads * t

    def parts(x):
        return x[:, :, :pack * queries].reshape(
            b, kv_heads, pack, queries, x.shape[-1]
        )

    m, l, acc = parts(m), parts(l), parts(acc)
    weight = jnp.exp(m - jnp.max(m, axis=2, keepdims=True))
    # A part's result lies on the lanes of its own position of a row.
    # (Chosen by a mask and summed over the row's positions: XLA's TPU
    # compiler got a stack of the parts' lane slices wrong, PR 30.)
    own = jnp.arange(pack * hd)[None, :] // hd \
        == jnp.arange(pack)[:, None]  # (pack, lanes)
    summed = jnp.sum(
        jnp.where(own[None, None, :, None, :], weight * acc, 0.0), axis=2
    ).reshape(b, kv_heads, queries, pack, hd).sum(axis=3)
    total = sum(weight[:, :, p] * l[:, :, p] for p in range(pack))
    out = summed / jnp.maximum(total, 1e-30)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames="interpret")
def decode_attend(q, k, v, k_pages, v_pages, idx, key_mask, *,
                  interpret: bool = False):
    """The kernel: see the module's docstring.  q (B, H, t, hd); k, v
    (B, H_kv, t, hd), the step's rows; the packed page leaves; idx
    (B,), where each slot's rows go; key_mask (B, Tk) or (B, t, Tk),
    every condition on a key but "the pages hold it" (which the rows'
    insert makes true up to ``idx + t - 1``: nothing beyond is read).
    Returns (out (B, H, t, hd), k_pages, v_pages).  The enclosing jit
    must donate the leaves (the engine's step does): they are pinned to
    the device's main memory, and the compiler aborts on a copy of one
    that it would rather keep in the fast memory."""
    b, h, t, hd = q.shape
    kv_heads, rows_all, lanes = k_pages.shape[1:]
    pack = lanes // hd
    tk = rows_all * pack
    sub = _sublanes(k_pages.dtype)
    rows = _block_rows(rows_all, pack, sub)
    blocks = rows_all // rows
    heads = _block_heads(
        kv_heads, rows * lanes * k_pages.dtype.itemsize
    )
    qp, mask = _pack_queries(q, key_mask, kv_heads, pack, blocks, sub)
    m_all = qp.shape[2]
    new = [jnp.tile(x, (1, 1, 1, pack)) for x in (k, v)]
    span = (t - 1 + pack - 1) // pack + 1  # rows a chunk can touch
    tiles = (span - 1 + sub - 1) // sub + 1

    def last_block(idx_ref, bb):
        return (jnp.minimum(idx_ref[bb] + t, tk) - 1) // (rows * pack)

    def head_map(bb, hh, jj, idx_ref):
        return (bb, hh, 0, 0)

    def page_map(bb, hh, jj, idx_ref):
        return (bb, hh, jnp.minimum(jj, last_block(idx_ref, bb)), 0)

    def mask_map(bb, hh, jj, idx_ref):
        return (bb, jnp.minimum(jj, last_block(idx_ref, bb)), 0, 0)

    # The pages stay in the device's main memory, as argument and as
    # result: the compiler would otherwise move a small bucket's leaves
    # whole into the fast memory for the call and back.  (The
    # interpreter knows no memory spaces.)
    if interpret:
        pinned, pin = jax.ShapeDtypeStruct, lambda x: x
    else:
        pinned = pltpu.HBM
        pin = functools.partial(
            pltpu.with_memory_space_constraint, memory_space=pltpu.HBM
        )
    page = pl.BlockSpec((1, heads, rows, lanes), page_map)
    stat = pl.BlockSpec((1, heads, m_all, 1), head_map)
    call = pl.pallas_call(
        functools.partial(
            _decode_kernel, t=t, pack=pack, hd=hd, sub=sub, tk=tk,
            tiles=tiles,
            precision=_F32_PRODUCTS
            if k_pages.dtype == jnp.float32 else None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv_heads // heads, blocks),
            in_specs=[
                pl.BlockSpec((1, heads, m_all, lanes), head_map),
                pl.BlockSpec((1, heads, t, lanes), head_map),
                pl.BlockSpec((1, heads, t, lanes), head_map),
                pl.BlockSpec((1, 1, m_all, rows), mask_map),
                page, page,
            ],
            out_specs=[
                pl.BlockSpec((1, heads, m_all, lanes), head_map),
                stat, stat,
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            scratch_shapes=[
                pltpu.VMEM((heads, m_all, 1), jnp.float32),
                pltpu.VMEM((heads, m_all, 1), jnp.float32),
                pltpu.VMEM((heads, m_all, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((2 * tiles,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv_heads, m_all, lanes), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_heads, m_all, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_heads, m_all, 1), jnp.float32),
            pinned(k_pages.shape, k_pages.dtype),
            pinned(v_pages.shape, v_pages.dtype),
        ],
        # the page leaves (inputs 5 and 6, the prefetched index counted)
        # are the last two results
        input_output_aliases={5: 3, 6: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="decode_attend",
    )
    with jax.named_scope("decode_attend"):
        acc, m, l, k_pages, v_pages = call(
            idx.astype(jnp.int32), qp, *new, mask,
            pin(k_pages), pin(v_pages),
        )
    out = _fold(acc, m, l, pack, q.shape).astype(q.dtype)
    return out, k_pages, v_pages


def _partitioned() -> bool:
    """Whether jit still partitions over some mesh axis here (a Mosaic
    kernel cannot be partitioned automatically)."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(
        mesh.shape[a] > 1 for a in mesh.axis_names
        if a not in mesh.manual_axes
    )


def cached_attend(q, k, v, k_pages, v_pages, idx, key_mask):
    """Write the step's rows ``k``, ``v`` (B, H_kv, t, hd) of slot
    ``r`` into its pages at ``idx[r] .. idx[r] + t - 1`` and attend
    ``q`` (B, H, t, hd) over the pages under ``key_mask``: (out,
    k_pages, v_pages).  One path a platform: the kernel on the TPU
    where its tiles divide the shapes, the plain form elsewhere."""
    if jax.default_backend() == "tpu" and kernel_fits(q, k_pages) \
            and not _partitioned():
        return decode_attend(q, k, v, k_pages, v_pages, idx, key_mask)
    return plain_attend(q, k, v, k_pages, v_pages, idx, key_mask)
