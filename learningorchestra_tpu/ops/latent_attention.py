"""Multi-head latent attention (MLA, DeepSeek-V2/V3): queries through a
low-rank bottleneck, keys and values remade from ONE compressed row a
token (``kv_lora_rank`` latent values + a ``qk_rope_head_dim`` rotary
key that all heads share), YaRN-scaled rotary frequencies.

A head's query and key are two parts side by side: ``nope`` (no
position) and ``rope`` (rotated).  The full forward makes every head's
``k_nope`` and ``v`` from the latent row (``kv_b``) and attends as any
multi-head layer does.  The decode branch never does: it caches the
latent row as it is (one leaf a layer, ``cached_latent``, of
``kv_lora_rank + qk_rope_head_dim`` values a position) and ABSORBS ``kv_b`` into the
query and the output instead: ``q_lat = q_nope W_UK^T`` scores against
the latent directly, and the attention's output, a mix of latents, goes
through ``W_UV`` once a query.  All heads then attend over the same
page, whose latents are keys and values at once:
:func:`latent_attend`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.decode_attention import (
    _partitioned,
    _sublanes,
)
from learningorchestra_tpu.ops.layers import RMSNorm

_NEG_BIG = -1e30
# Positions of a slot's page one grid step of the kernel reads (a
# whole small bucket): 590 KB of a 576-wide bfloat16 page, so that a
# grid step's third of a microsecond is small beside its read.
_BLOCK_POSITIONS = 512


# ---------------------------------------------------------------------------
# YaRN rotary frequencies
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1`` (1 without stretch)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float = 1.0,
                  original_max: int = 4096, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """The ``dim / 2`` rotary frequencies under YaRN: pair ``i`` keeps
    ``theta^(-2i/dim)`` where it turns more than ``beta_fast`` times in
    ``original_max`` positions, takes that over ``factor`` where it
    turns fewer than ``beta_slow`` times, and a linear blend between
    the two pair indices where those turn counts fall (the published
    ``DeepseekV3YarnRotaryEmbedding``; applied at every position)."""
    pairs = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    plain = theta ** -pairs
    if factor <= 1.0:
        return plain

    def pair_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0,
    )  # 0: keep the plain frequency; 1: the stretched one
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotate(x, positions, inv_freq, scale: float = 1.0):
    """Rotary embedding of ``x`` (..., T, hd) at ``positions`` (T,) or
    (B, T) (then ``x`` is (B, ..., T, hd)) with the given frequencies:
    pairs are (x[..., i], x[..., i + hd/2]), cos and sin times
    ``scale``."""
    half = x.shape[-1] // 2
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    while ang.ndim < x.ndim:  # insert the axes between batch and T
        ang = ang[:, None] if ang.ndim > 2 else ang[None]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention over the latent pages
# ---------------------------------------------------------------------------
#
# A layer's pages are ONE leaf ``(S, Tk / pack, pack * (rank + rope))``:
# ``pack`` consecutive positions share a row, their latents first and
# their rotary keys behind them (``[c_0 | c_1 | r_0 | r_1]`` at pack 2),
# so that every part starts on a whole 128-lane tile and the TPU stores
# the leaf row-major without padding a 576-wide row to 640 lanes (or
# turning the leaf on its side, which XLA's layout choice did to a
# ``(S, Tk, 576)`` leaf: a relayout of every page around every call).
# ``rank + rope`` values a position either way.


def page_pack(rank: int, rope: int, tk: int) -> int:
    """Positions that share one row of the page leaf."""
    whole = rank % 128 == 0 and rope < 128 and 128 % rope == 0
    pack = 128 // rope if whole else 1
    return pack if tk % pack == 0 else 1


def page_shape(batch: int, tk: int, rank: int, rope: int):
    pack = page_pack(rank, rope, tk)
    return (batch, tk // pack, pack * (rank + rope))


def unpack_pages(pages, rank: int, rope: int):
    """(latents (B, Tk, rank), rotary keys (B, Tk, rope)) of a leaf."""
    b, rows, lanes = pages.shape
    pack = lanes // (rank + rope)
    return (pages[..., :pack * rank].reshape(b, rows * pack, rank),
            pages[..., pack * rank:].reshape(b, rows * pack, rope))


def insert_rows(pages, latent, key_pe, idx):
    """``pages`` with the step's rows, ``latent`` (B, t, rank) and
    ``key_pe`` (B, t, rope), of slot ``r`` at positions ``idx[r] ..
    idx[r] + t - 1`` (one beyond the bucket is dropped): two scatters
    of B x t windows, in place where the pages are donated, never a
    pass over them."""
    b, t, rank = latent.shape
    rope = key_pe.shape[-1]
    pack = pages.shape[2] // (rank + rope)
    pos = (idx[:, None] + jnp.arange(t)[None, :]).reshape(-1)
    slot = jnp.repeat(jnp.arange(b), t)
    row, part = pos // pack, pos % pack
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 2),
    )
    for rows, first, width in (
        (latent, part * rank, rank),
        (key_pe, pack * rank + part * rope, rope),
    ):
        pages = jax.lax.scatter(
            pages, jnp.stack([slot, row, first], axis=1).astype(jnp.int32),
            rows.reshape(b * t, width).astype(pages.dtype), dims,
            indices_are_sorted=True, unique_indices=True,
            mode=jax.lax.GatherScatterMode.FILL_OR_DROP,
        )
    return pages


def plain_latent_attend(q, pages, key_mask, rank: int, scale: float):
    """``q`` (B, H, t, rank + rope) over the page leaf under
    ``key_mask`` (B, Tk) or (B, t, Tk): softmax(q . [latent | rotary
    key] * scale) in float32 times the latents: (B, H, t, rank)
    float32.  A query that may see no key gives exactly 0.  The plain
    form: what runs off the TPU, and the kernel's oracle."""
    latent, key_pe = unpack_pages(pages, rank, q.shape[-1] - rank)
    s = (
        jnp.einsum("bhtc,bkc->bhtk", q[..., :rank], latent,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhtr,bkr->bhtk", q[..., rank:], key_pe,
                     preferred_element_type=jnp.float32)
    ) * scale
    keep = key_mask.astype(bool)
    keep = keep[:, None, None, :] if keep.ndim == 2 else keep[:, None]
    m = jnp.max(jnp.where(keep, s, _NEG_BIG), axis=-1, keepdims=True)
    m = jnp.where(m > _NEG_BIG / 2, m, 0.0)
    p = jnp.exp(jnp.where(keep, s - m, _NEG_BIG))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum(
        "bhtk,bkc->bhtc", p.astype(pages.dtype), latent,
        preferred_element_type=jnp.float32,
    )


def _latent_kernel(idx_ref, q_ref, qpe_ref, mask_ref, new_ref, own_ref,
                   page_ref, out_ref, pages_out_ref,
                   m_scr, l_scr, acc_scr, sem,
                   *, rank, pack, scale, rows, sub):
    """One (slot, block of rows) grid step; the rows' axis is
    sequential and the online softmax's state lives in scratch.  A row
    holds ``pack`` positions: each is scored by the latent query
    against its own latent and by a rotary query that is zero on the
    other positions' lanes against the row's rotary keys.  The block
    that holds the slot's position takes the step's row first."""
    b, j = pl.program_id(0), pl.program_id(1)
    row = idx_ref[b] // pack

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # The tile of ``sub`` rows that holds the step's position, where it
    # lies in this block (a position beyond the bucket lies in none).
    first = row // sub * sub
    here = first // rows == j
    local = pl.ds(pl.multiple_of(first - j * rows, sub), sub)

    def write_back():
        return pltpu.make_async_copy(
            page_ref.at[0, local],
            pages_out_ref.at[b, pl.ds(pl.multiple_of(first, sub), sub)],
            sem.at[0],
        )

    # Blocks beyond the slot's last key are neither fetched (the index
    # map holds the last one needed) nor scored.
    @pl.when(j * rows * pack <= idx_ref[b])
    def _attend():
        @pl.when(here)
        def _insert():
            # Put the step's row into its tile as it lies in the fast
            # memory (what is attended below is the pages WITH it),
            # and send that tile, and nothing else, back to the pages.
            tile = page_ref[0, local]
            at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) + first
            page_ref[0, local] = jnp.where(
                (at == row) & (own_ref[0] != 0), new_ref[0], tile
            )
            write_back().start()

        q = q_ref[0]  # (H, rank)
        keys_pe = page_ref[0, :, pack * rank:]  # (rows, pack * rope)
        latents, scores, keeps = [], [], []
        for part in range(pack):
            latent = page_ref[0, :, part * rank:(part + 1) * rank]
            s = jax.lax.dot_general(
                q, latent, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                qpe_ref[0, part], keys_pe, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (H, rows)
            keep = mask_ref[0, part] != 0  # (1, rows)
            latents.append(latent)
            keeps.append(keep)
            scores.append(jnp.where(keep, s * scale, _NEG_BIG))
        m_prev = m_scr[...]
        m_new = m_prev
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * alpha
        acc = acc_scr[...] * alpha
        for latent, s, keep in zip(latents, scores, keeps):
            # The second where: a row with no key yet has m = -1e30
            # and exp(s - m) = 1 on every masked lane.
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            l_new = l_new + jnp.sum(p, -1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                p.astype(latent.dtype), latent, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

        @pl.when(here)
        def _settle():
            write_back().wait()

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def _block_rows(rows: int, pack: int) -> int:
    """Rows of a leaf one grid step reads: ``_BLOCK_POSITIONS``
    positions, or a whole smaller bucket."""
    return min(_BLOCK_POSITIONS // pack, rows)


def kernel_fits(q, pages, key_mask, rank: int) -> bool:
    """Whether ``latent_attend_kernel``'s tiles divide these shapes: a
    one-token step, every part of a row on whole 128-lane tiles, whole
    blocks of rows."""
    rope = q.shape[-1] - rank
    pack = pages.shape[2] // (rank + rope)
    block = _block_rows(pages.shape[1], pack)
    return (
        q.shape[2] == 1 and key_mask.ndim == 2 and rank % 128 == 0
        and (pack * rope) % 128 == 0 and block % 128 == 0
        and pages.shape[1] % block == 0 and q.dtype == pages.dtype
    )


@functools.partial(
    jax.jit, static_argnames=("rank", "scale", "interpret")
)
def latent_attend_kernel(q, latent, key_pe, pages, idx, key_mask, *,
                         rank: int, scale: float,
                         interpret: bool = False):
    """The kernel behind :func:`latent_attend` on the TPU: ``q`` (B, H,
    1, rank + rope) over the page leaf with the step's row (``latent``
    (B, 1, rank), ``key_pe`` (B, 1, rope)) put in at position ``idx``
    (B,): (out (B, H, 1, rank) float32, pages).  ONE read of a slot's
    pages, block by block and no further than the block that holds
    ``idx``; the row patched into its tile in the fast memory and that
    tile alone sent back into the aliased leaf; both products on the
    matrix unit, the values being the rows' own latents; softmax
    statistics in float32.  The enclosing jit must donate the leaf (the
    engine's step does): it is pinned to the device's main memory, as
    ``decode_attend``'s are."""
    b, h, _t, width = q.shape
    rope = width - rank
    n_rows, lanes = pages.shape[1:]
    pack = lanes // width
    rows = _block_rows(n_rows, pack)
    sub = _sublanes(pages.dtype)
    # (B, pack, 1, rows of the leaf): position pack * r + part of row r
    mask = key_mask.astype(jnp.int32).reshape(b, n_rows, pack) \
        .transpose(0, 2, 1)[:, :, None, :]
    # The rotary query of part ``p``: zeros on the other parts' lanes.
    place = jnp.eye(pack, dtype=q.dtype)  # (part, lanes' part)
    q_pe = (q[:, None, :, 0, None, rank:]
            * place[None, :, None, :, None]).reshape(b, pack, h, pack * rope)
    # The step's row as a whole row of the leaf, and which lanes of
    # that row are its own (the other positions' stay as they are).
    mine = place[idx % pack]  # (B, pack)
    new = jnp.concatenate([
        (mine[:, :, None] * latent[:, 0, None, :].astype(q.dtype))
        .reshape(b, pack * rank),
        (mine[:, :, None] * key_pe[:, 0, None, :].astype(q.dtype))
        .reshape(b, pack * rope),
    ], axis=-1).astype(pages.dtype)[:, None, :]
    own = jnp.concatenate([
        jnp.repeat(mine, rank, axis=1), jnp.repeat(mine, rope, axis=1),
    ], axis=-1).astype(jnp.int32)[:, None, :]

    def last_block(idx_ref, bb):
        return jnp.minimum(idx_ref[bb] // pack, n_rows - 1) // rows

    def head_map(bb, jj, idx_ref):
        return (bb, 0, 0)

    def part_map(bb, jj, idx_ref):
        return (bb, 0, 0, 0)

    def page_map(bb, jj, idx_ref):
        return (bb, jnp.minimum(jj, last_block(idx_ref, bb)), 0)

    def mask_map(bb, jj, idx_ref):
        return (bb, 0, 0, jnp.minimum(jj, last_block(idx_ref, bb)))

    # The pages stay in the device's main memory, as argument and as
    # result (``decode_attend`` has the reasons; the interpreter knows
    # no memory spaces).
    if interpret:
        pinned, pin = jax.ShapeDtypeStruct, lambda x: x
    else:
        pinned = pltpu.HBM
        pin = functools.partial(
            pltpu.with_memory_space_constraint, memory_space=pltpu.HBM
        )
    call = pl.pallas_call(
        functools.partial(_latent_kernel, rank=rank, pack=pack,
                          scale=scale, rows=rows, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_rows // rows),
            in_specs=[
                pl.BlockSpec((1, h, rank), head_map),
                pl.BlockSpec((1, pack, h, pack * rope), part_map),
                pl.BlockSpec((1, pack, 1, rows), mask_map),
                pl.BlockSpec((1, 1, lanes), head_map),
                pl.BlockSpec((1, 1, lanes), head_map),
                pl.BlockSpec((1, rows, lanes), page_map),
            ],
            out_specs=[
                pl.BlockSpec((1, h, rank), head_map),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
            pinned(pages.shape, pages.dtype),
        ],
        # the page leaf (input 6, the prefetched index counted) is the
        # second result
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="latent_attend",
    )
    with jax.named_scope("latent_attend"):
        out, pages = call(idx.astype(jnp.int32), q[:, :, 0, :rank], q_pe,
                          mask, new, own, pin(pages))
    return out[:, :, None, :], pages


def latent_attend(q, latent, key_pe, pages, idx, key_mask, rank: int,
                  scale: float):
    """Absorbed attention of a decode step over the latent pages: the
    step's rows (``latent`` (B, t, rank), ``key_pe`` (B, t, rope)) go
    into slot ``r``'s pages at ``idx[r] .. idx[r] + t - 1`` and ``q``
    (B, H, t, rank + rope) attends over the pages with them in: (out
    (B, H, t, rank) float32, pages).  One path a platform: the kernel
    on the TPU where its tiles divide the shapes, the plain form (two
    row scatters, then the einsums) elsewhere; both under the scope
    ``latent_attend``."""
    if jax.default_backend() == "tpu" \
            and kernel_fits(q, pages, key_mask, rank) \
            and not _partitioned():
        return latent_attend_kernel(
            q, latent, key_pe, pages, idx, key_mask, rank=rank,
            scale=scale,
        )
    with jax.named_scope("latent_attend"):
        pages = insert_rows(pages, latent, key_pe, idx)
        return plain_latent_attend(q, pages, key_mask, rank, scale), pages


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


class LatentAttention(nn.Module):
    """Causal multi-head latent self-attention with a key-side padding
    mask (B, T).  ``rope_scaling`` is the published group (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``) or None for plain rotary
    frequencies.  Softmax scale ``(nope + rope)^-1/2 * m^2`` with ``m =
    yarn_mscale(factor, mscale_all_dim)``; cos and sin times
    ``yarn_mscale(factor, mscale) / m``.  Matmuls in ``dtype``, norm
    and softmax statistics in float32.

    ``decode=True`` follows ``MultiHeadSelfAttention``'s convention: an
    uninitialized pass sizes the cache and is the full forward; after
    it every call feeds ``t`` positions a row at ``cache_index``
    (scalar: lockstep; (B,): each row at its own) through the absorbed
    form over ``cached_latent``."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    rope_scaling: tuple | dict | None = None  # items or the group
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    def _yarn(self):
        """(inverse frequencies, cos/sin scale, softmax scale)."""
        sc = dict(self.rope_scaling or ())
        factor = float(sc.get("factor", 1.0))
        inv_freq = yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, factor,
            int(sc.get("original_max_position_embeddings", 4096)),
            float(sc.get("beta_fast", 32.0)),
            float(sc.get("beta_slow", 1.0)),
        )
        m_all = yarn_mscale(factor, float(sc.get("mscale_all_dim", 0.0)))
        m = yarn_mscale(factor, float(sc.get("mscale", 1.0)))
        softmax_scale = (
            self.qk_nope_head_dim + self.qk_rope_head_dim
        ) ** -0.5 * m_all * m_all
        return inv_freq, m / m_all, softmax_scale

    @nn.compact
    def __call__(self, x, key_mask=None):
        b, t, hidden = x.shape
        heads, rank = self.num_heads, self.kv_lora_rank
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        inv_freq, rot_scale, scale = self._yarn()
        dense = functools.partial(
            nn.DenseGeneral, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        norm = functools.partial(
            RMSNorm, self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        with jax.named_scope("mla_down"):
            c_q = norm(name="q_a_norm")(dense(self.q_lora_rank,
                                              name="q_a")(x))
            q = dense((heads, nope + rope), name="q_b")(c_q)
            q = q.transpose(0, 2, 1, 3)  # (B, H, T, nope + rope)
            kv = dense(rank + rope, name="kv_a")(x)  # (B, T, rank + rope)
            c_kv = norm(name="kv_a_norm")(kv[..., :rank])
        kv_b = self.param(
            "kv_b", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0,
                out_axis=(1, 2),
            ), (rank, heads, nope + vd), self.param_dtype,
        )
        dt = self.dtype if self.dtype is not None else x.dtype
        kv_b = kv_b.astype(dt)

        def out_proj(o):  # (B, H, T, vd)
            with jax.named_scope("mla_out"):
                return dense(hidden, axis=(-2, -1), name="out")(
                    o.transpose(0, 2, 1, 3).astype(dt)
                )

        is_initialized = self.decode and self.has_variable(
            "cache", "cached_latent"
        )
        if self.decode:
            pages = self.variable(
                "cache", "cached_latent", jnp.zeros,
                page_shape(b, t, rank, rope), c_kv.dtype,
            )
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
        if is_initialized:
            idx = ci.value
            batched = idx.ndim == 1
            if t != 1 and not batched:
                raise ValueError(
                    "a scalar cache_index feeds ONE position per step; "
                    f"got a {t}-token chunk"
                )
            pos = (idx[:, None] if batched else jnp.full((1,), idx)) \
                + jnp.arange(t)
            tk = pages.value.shape[1] * pages.value.shape[2] \
                // (rank + rope)
            with jax.named_scope("mla_down"):
                q_pe = rotate(q[..., nope:], pos, inv_freq, rot_scale)
                k_pe = rotate(kv[..., rank:], pos, inv_freq, rot_scale)
            with jax.named_scope("mla_absorb"):
                q_lat = jnp.einsum(
                    "bhtn,chn->bhtc", q[..., :nope], kv_b[..., :nope],
                    preferred_element_type=jnp.float32,
                ).astype(dt)
                q_all = jnp.concatenate([q_lat, q_pe.astype(dt)], -1)
            start = idx if batched else jnp.full((b,), idx)
            ci.value = idx + t
            # Causality is the layer's: slots beyond the just-written
            # position are zero-initialized cache, not keys.
            slot = jnp.arange(tk)[None, :]
            if t == 1:
                valid = slot <= start[:, None]
            else:
                valid = slot[:, None, :] <= (
                    start[:, None, None] + jnp.arange(t)[None, :, None]
                )
                if key_mask is not None:
                    key_mask = key_mask[:, None, :]
            key_mask = valid if key_mask is None else key_mask & valid
            o_lat, pages.value = latent_attend(
                q_all, c_kv, k_pe, pages.value, start, key_mask, rank,
                scale,
            )
            with jax.named_scope("mla_absorb"):
                o = jnp.einsum(
                    "bhtc,chv->bhtv", o_lat.astype(dt), kv_b[..., nope:],
                    preferred_element_type=jnp.float32,
                )
            return out_proj(o)

        # The full forward: every head's keys and values from the latent.
        pos = jnp.arange(t)
        q_pe = rotate(q[..., nope:], pos, inv_freq, rot_scale)
        k_pe = rotate(kv[..., rank:], pos, inv_freq, rot_scale)
        kv_heads = jnp.einsum("btc,chn->bhtn", c_kv, kv_b)
        s = (
            jnp.einsum("bhqn,bhkn->bhqk", q[..., :nope],
                       kv_heads[..., :nope],
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhqr,bkr->bhqk", q_pe, k_pe,
                         preferred_element_type=jnp.float32)
        ) * scale
        keep = jnp.tril(jnp.ones((t, t), bool))[None, None]
        if key_mask is not None:
            keep = keep & key_mask.astype(bool)[:, None, None, :]
        m = jnp.max(jnp.where(keep, s, _NEG_BIG), axis=-1, keepdims=True)
        m = jnp.where(m > _NEG_BIG / 2, m, 0.0)
        p = jnp.exp(jnp.where(keep, s - m, _NEG_BIG))
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum(
            "bhqk,bhkv->bhqv", p.astype(dt), kv_heads[..., nope:],
            preferred_element_type=jnp.float32,
        )
        return out_proj(o)
