"""Power retention, degree 2 (Buckman, Gelada et al., "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239): a causal mixer with
no softmax whose weight of key ``j`` for query ``i`` is ``(q_i . k_j /
sqrt(d))^2`` times the product of the gates between them,

    A_ij = (q_i . k_j / sqrt(d))^2 * exp(sum_{l=j+1..i} log g_l),
    y_i  = sum_j A_ij v_j / (sum_j A_ij + eps),        j <= i.

The square is an inner product of its own: with ``phi(u)`` the
symmetric square of ``u`` (``u_a^2``, and ``sqrt(2) u_a u_b`` for ``a <
b``: ``d (d + 1) / 2`` entries), ``phi(q) . phi(k) = (q . k)^2``.  So
the sums over ``j`` are a state that does not grow with the sequence:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T,   z_t = g_t z_{t-1} + phi(k_t),
    y_t = (phi(q_t)^T S_t / d) / (phi(q_t)^T z_t / d + eps),

read against the state AFTER its update.  The full forward computes the
first form; the decode branch keeps ``S`` and ``z`` per slot and
key/value head (two leaves a layer, ``retained_state`` and
``retained_norm``, float32, no length axis) and steps the second:
:func:`retention_step`.

A chunk of ``n`` positions goes through the state in ONE update (the
same :func:`retention_step`, handed several rows a slot).  With ``L_i``
the log-gates summed over the chunk's rows up to ``i``, row ``i`` reads
the state the chunk began from and the chunk's own keys up to it,

    num_i = e^{L_i} phi(q_i)^T S + sum_{j<=i} e^{L_i - L_j} (q_i . k_j)^2 v_j,

and the state after the chunk is ``e^{L_{n-1}} S + sum_j e^{L_{n-1} -
L_j} phi(k_j) v_j^T``: what ``n`` one-position steps give, to
rounding.

The layout of a state.  ``phi``'s entries in the order of
:func:`feature_pairs`, padded with zero entries to whole rows of 128:
``C`` rows.  ``retained_norm`` is ``(B, H_kv, C, 128)``;
``retained_state`` is ``(B, H_kv, C, d_v, 128)``, entry ``[c, j, l]``
the weight of feature ``128 c + l`` for value ``j``: features lie along
the lanes, so that a step multiplies whole rows of ``phi`` into it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.decode_attention import _partitioned
from learningorchestra_tpu.ops.layers import RMSNorm, apply_rope

HI = jax.lax.Precision.HIGHEST
LANES = 128
#: Values of a state block the kernel's inner loop holds in registers
#: at once: 32 sublanes x 128 lanes are 4 registers, and a group's five
#: accumulators of that size 20 of the 64.
_SLAB = 32
#: A whole state of one (slot, head) is one block, in and out, each
#: double-buffered: 4 x 4.3 MB at a 128-wide key.
_VMEM_LIMIT = 48 * 1024 * 1024


# ---------------------------------------------------------------------------
# The symmetric square
# ---------------------------------------------------------------------------


def state_rows(dim: int) -> int:
    """Entries of the symmetric square of a ``dim``-vector."""
    return dim * (dim + 1) // 2


@functools.lru_cache(maxsize=None)
def feature_pairs(dim: int):
    """(first index, second index, coefficient) of every entry of
    ``phi``, ``a <= b`` row-major, then zero entries up to a multiple
    of 128: numpy arrays of one length ``128 C``."""
    first, second = np.triu_indices(dim)
    coef = np.where(first == second, 1.0, math.sqrt(2.0))
    pad = -len(first) % LANES
    zeros = np.zeros(pad, first.dtype)
    return (np.concatenate([first, zeros]).astype(np.int32),
            np.concatenate([second, zeros]).astype(np.int32),
            np.concatenate([coef, np.zeros(pad)]).astype(np.float32))


def state_shapes(batch: int, kv_heads: int, key_dim: int, value_dim: int):
    """(shape of ``retained_state``, shape of ``retained_norm``)."""
    rows = len(feature_pairs(key_dim)[0]) // LANES
    return ((batch, kv_heads, rows, value_dim, LANES),
            (batch, kv_heads, rows, LANES))


def feature_map(u):
    """``phi(u)`` of ``u`` (..., d) in float32, (..., C, 128): each
    entry the product of two of ``u``'s values, picked by two one-hot
    matmuls (exact: one term a sum) so that the entries come out along
    the lanes, as the state holds them."""
    first, second, coef = feature_pairs(u.shape[-1])
    at = jnp.arange(u.shape[-1], dtype=jnp.int32)[:, None]

    def pick(index):
        return jnp.matmul(
            u, (at == index[None, :]).astype(u.dtype), precision=HI,
            preferred_element_type=jnp.float32,
        )

    phi = pick(first) * pick(second) * coef
    return phi.reshape(*u.shape[:-1], -1, LANES)


# ---------------------------------------------------------------------------
# One step of the recurrence
# ---------------------------------------------------------------------------
#
# ``state`` (B, H, C, D, 128) and ``norm`` (B, H, C, 128) float32;
# ``fq`` (B, H, G, C, 128) the group's queries' ``phi``, ``fk`` (B, H,
# C, 128) the key's (zeros where the step's key may not be seen: the
# state then only decays); ``v`` (B, H, D) and ``g`` (B, H) float32;
# ``live`` (B,): a slot that is not keeps its state untouched and reads
# zeros; ``fresh`` (B,): the slot begins from a zero state.  Returns
# (numerators (B, H, G, D), denominators (B, H, G), state, norm).


def _per_slot(flag, ndim):
    return flag.reshape(-1, *([1] * (ndim - 1)))


def plain_retention_step(state, norm, fq, fk, v, g, live, fresh):
    """The step in plain ``jax.numpy``: what runs off the TPU, and the
    kernel's oracle."""
    s1 = g[:, :, None, None, None] * jnp.where(
        _per_slot(fresh, 5), 0.0, state
    ) + fk[:, :, :, None, :] * v[:, :, None, :, None]
    z1 = g[:, :, None, None] * jnp.where(_per_slot(fresh, 4), 0.0, norm) + fk
    num = jnp.einsum("bhgcl,bhcdl->bhgd", fq, s1, precision=HI)
    den = jnp.einsum("bhgcl,bhcl->bhg", fq, z1, precision=HI)
    return (jnp.where(_per_slot(live, 4), num, 0.0),
            jnp.where(_per_slot(live, 3), den, 0.0),
            jnp.where(_per_slot(live, 5), s1, state),
            jnp.where(_per_slot(live, 4), z1, norm))


def _prologue(plan_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out):
    """What every (slot, head) grid step does before its update:
    whether the slot is live, the blocks of a dead slot at the first
    step sent back as they came (no step before the first: were it of a
    dead slot, the blocks it stands on would go back as they never came
    in), a fresh slot's state begun from zero."""
    b, h = pl.program_id(0), pl.program_id(1)
    live = plan_ref[0, b] != 0

    @pl.when(jnp.logical_not(live) & (b == 0) & (h == 0))
    def _keep():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(live & (plan_ref[1, b] != 0))
    def _begin():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    return live


def _one_position(s_ref, z_ref, fq_ref, fk_ref, vg_ref, num_ref, den_ref,
                  s_out, z_out, acc_scr, *, groups, dim, rows, slab,
                  stride=1):
    """One position's update of one (slot, head) on the vector unit:
    decay, the key's outer product added, the group's queries read
    against the new state; query ``i``'s read-out in row ``i *
    stride`` of ``num_ref`` and ``den_ref``."""
    g = vg_ref[0, 0, 1:2, :]  # (1, 128): the gate in every lane
    # vb[j, l] = v[j]: the step's value down the sublanes
    vb = jnp.broadcast_to(vg_ref[0, 0, 0:1, :], (LANES, LANES)).T
    for s0 in range(0, dim, slab):
        vb_s = vb[s0:s0 + slab]

        def column(c, accs, s0=s0, vb_s=vb_s):
            fk = fk_ref[0, 0, pl.ds(c, 1), :]
            new = g * s_ref[0, 0, c, s0:s0 + slab, :] + fk * vb_s
            s_out[0, 0, c, s0:s0 + slab, :] = new
            return tuple(
                acc + fq_ref[0, 0, i, pl.ds(c, 1), :] * new
                for i, acc in enumerate(accs)
            )

        accs = jax.lax.fori_loop(
            0, rows, column,
            tuple(jnp.zeros((slab, LANES), jnp.float32)
                  for _ in range(groups)),
        )
        for i, acc in enumerate(accs):
            acc_scr[i, s0:s0 + slab, :] = acc
    z1 = g * z_ref[0, 0] + fk_ref[0, 0]
    z_out[0, 0] = z1
    for i in range(groups):
        row = i * stride
        # the features lie along the lanes: sum them away
        num_ref[0, 0, row:row + 1, :dim] = jnp.sum(
            acc_scr[i].T, axis=0, keepdims=True
        )
        den = jnp.sum(
            jnp.sum(fq_ref[0, 0, i] * z1, axis=0, keepdims=True),
            axis=1, keepdims=True,
        )
        den_ref[0, 0, row:row + 1, :] = jnp.broadcast_to(den, (1, LANES))


def _step_kernel(plan_ref, s_ref, z_ref, fq_ref, fk_ref, vg_ref,
                 num_ref, den_ref, s_out, z_out, acc_scr,
                 *, groups, dim, rows, slab):
    """One (slot, head) grid step: the whole state of that head, read
    once and written once.  ``plan_ref`` rows: live, fresh, and the
    (slot, head) block a slot that is not live stands on (the block of
    the step before it, so that nothing is fetched or written for
    it)."""
    live = _prologue(plan_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out)

    @pl.when(live)
    def _step():
        _one_position(s_ref, z_ref, fq_ref, fk_ref, vg_ref, num_ref,
                      den_ref, s_out, z_out, acc_scr, groups=groups,
                      dim=dim, rows=rows, slab=slab)


def kernel_fits(state, fq) -> bool:
    """Whether :func:`retention_step_kernel`'s tiles divide these
    shapes: values on whole sublanes and no wider than a row of lanes,
    a group no larger than a tile's sublanes."""
    dim = state.shape[3]
    return dim % 8 == 0 and dim <= LANES and fq.shape[2] <= 8 \
        and state.dtype == jnp.float32


def _stand_ins(live, heads: int):
    """For each slot the (slot, head) block a dead one stands on: the
    last head of the last live slot before it, or the first block of
    the first live slot where there is none (slot 0's where none is
    live at all)."""
    n = live.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))
    first = jnp.where(jnp.any(live), jnp.argmax(live), 0).astype(jnp.int32)
    slot = jnp.where(before >= 0, before, first)
    head = jnp.where(before >= 0, heads - 1, 0)
    return slot.astype(jnp.int32), head.astype(jnp.int32)


def _spec(*tail):
    """The block of one (slot, head), whole along ``tail``: a live
    slot's own, a dead one's the block it stands on (plan rows 2 and
    3)."""
    def index(bb, hh, plan_ref):
        on = plan_ref[0, bb] != 0
        return (jnp.where(on, bb, plan_ref[2, bb]),
                jnp.where(on, hh, plan_ref[3, bb]), *([0] * len(tail)))

    return pl.BlockSpec((1, 1, *tail), index)


def _value_gate(v, g, dim: int):
    """A position's value in row 0 (its first ``dim`` lanes) and its
    gate in every lane of row 1: one small block a (slot, head)."""
    b, heads = g.shape
    vg = jnp.zeros((b, heads, 8, LANES), jnp.float32)
    return vg.at[:, :, 0, :dim].set(v).at[:, :, 1, :].set(g[:, :, None])


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_step_kernel(state, norm, fq, fk, v, g, live, fresh, *,
                          interpret: bool = False):
    """The kernel behind :func:`retention_step` on the TPU.  For each
    live slot and key/value head: ONE read of the state (a block of its
    own, 4.3 MB at a 128-wide key), decay, the step's outer product
    added, the group's queries read against the NEW state, ONE write
    into the aliased leaf; all on the vector unit in float32 (five
    queries a state would leave the matrix unit loading weights).  A
    dead slot's blocks are neither fetched nor written.  The enclosing
    jit must donate the two leaves for the update to be in place."""
    b, heads, rows, dim, _ = state.shape
    groups = fq.shape[2]
    slab = min(_SLAB, dim)
    live = live.astype(jnp.int32)
    slot_of, head_of = _stand_ins(live != 0, heads)
    plan = jnp.stack([live, fresh.astype(jnp.int32), slot_of, head_of])
    vg = _value_gate(v, g, dim)

    call = pl.pallas_call(
        functools.partial(_step_kernel, groups=groups, dim=dim, rows=rows,
                          slab=slab),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, heads),
            in_specs=[
                _spec(rows, dim, LANES), _spec(rows, LANES),
                _spec(groups, rows, LANES), _spec(rows, LANES),
                _spec(8, LANES),
            ],
            out_specs=[
                _spec(8, LANES), _spec(8, LANES),
                _spec(rows, dim, LANES), _spec(rows, LANES),
            ],
            scratch_shapes=[pltpu.VMEM((groups, dim, LANES), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, 8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, 8, LANES), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(norm.shape, norm.dtype),
        ],
        # the two leaves (inputs 1 and 2, the prefetched plan counted)
        # are the third and fourth results
        input_output_aliases={1: 2, 2: 3},
        compiler_params=pltpu.CompilerParams(
            # in order: a dead slot stands on the block before it
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="retention_step",
    )
    with jax.named_scope("retention_step"):
        num, den, state, norm = call(plan, state, norm, fq, fk, vg)
    on = live != 0
    return (jnp.where(on[:, None, None, None], num[:, :, :groups, :dim], 0.0),
            jnp.where(on[:, None, None], den[:, :, :groups, 0], 0.0),
            state, norm)


# ---------------------------------------------------------------------------
# A chunk of positions in one update
# ---------------------------------------------------------------------------
#
# ``q`` (B, H, G, T, d) the group's queries at the chunk's T rows and
# ``k`` (B, H, T, d) its keys (rotated, in the activations' dtype; zero
# where a key may not be seen: it then adds nothing); ``v`` (B, H, T,
# D) and ``log_g`` (B, H, T) float32, ``log_g`` 0 on a row the slot
# does not take (it neither adds a key nor decays the state); ``n``
# (B,): the slot takes its first ``n`` rows, 0 where it sits the step
# out; ``fresh`` (B,).  Returns (numerators (B, H, G, T, D),
# denominators (B, H, G, T), state, norm), zeros on every row past
# ``n``.


def _chunk_terms(q, k, v, log_g, n):
    """What a chunk's rows read beside the state it began from, and what
    its update weighs: the state's decay at each row ``e^{L_i}``; the
    in-chunk sums ``sum_{j<=i} e^{L_i - L_j} (q_i . k_j)^2`` times
    ``v_j`` and alone; each key's weight in the update, ``e^{L_{T-1} -
    L_j}`` (0 on a row past ``n``); the decay of the whole chunk."""
    big_l = jnp.cumsum(log_g, axis=-1)  # (B, H, T)
    t = big_l.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    score = jnp.einsum(
        "bhgtd,bhjd->bhgtj", q.astype(jnp.float32), k.astype(jnp.float32),
        precision=HI,
    )
    # masked before the exponent: a later key's difference is > 0
    weight = jnp.square(score) * jnp.exp(jnp.where(
        causal, big_l[..., :, None] - big_l[..., None, :], -jnp.inf,
    ))[:, :, None]
    last = big_l[..., -1:]
    took = jnp.arange(t)[None, None, :] < n[:, None, None]
    return (
        jnp.exp(big_l),
        jnp.einsum("bhgtj,bhjd->bhgtd", weight, v, precision=HI),
        jnp.sum(weight, -1),
        jnp.where(took, jnp.exp(last - big_l), 0.0),
        jnp.exp(last[..., 0]),
    )


def _taken(num, den, n):
    """``num`` and ``den`` with zeros on every row past a slot's ``n``
    (and so on every row of a slot that sits the step out)."""
    took = jnp.arange(den.shape[-1])[None, :] < n[:, None]
    return (jnp.where(took[:, None, None, :, None], num, 0.0),
            jnp.where(took[:, None, None, :], den, 0.0))


def plain_retention_chunk(state, norm, q, k, v, log_g, n, fresh):
    """A chunk's update in plain ``jax.numpy``: what runs off the TPU,
    and the chunk kernel's oracle."""
    decay, inner_num, inner_den, w, total = _chunk_terms(q, k, v, log_g, n)
    s0 = jnp.where(_per_slot(fresh, 5), 0.0, state)
    z0 = jnp.where(_per_slot(fresh, 4), 0.0, norm)
    fq = feature_map(q)  # (B, H, G, T, C, 128)
    fk = feature_map(k) * w[..., None, None]  # (B, H, T, C, 128)
    num = decay[:, :, None, :, None] * jnp.einsum(
        "bhgtcl,bhcdl->bhgtd", fq, s0, precision=HI) + inner_num
    den = decay[:, :, None] * jnp.einsum(
        "bhgtcl,bhcl->bhgt", fq, z0, precision=HI) + inner_den
    s1 = total[:, :, None, None, None] * s0 + jnp.einsum(
        "bhjcl,bhjd->bhcdl", fk, v, precision=HI)
    z1 = total[:, :, None, None] * z0 + jnp.sum(fk, axis=2)
    live = n > 0
    return (*_taken(num, den, n),
            jnp.where(_per_slot(live, 5), s1, state),
            jnp.where(_per_slot(live, 4), z1, norm))


def _several_positions(s_ref, z_ref, vg_ref, u_ref, pick_ref, vw_ref,
                       wk_ref, num_ref, den_ref, s_out, z_out, vb_scr,
                       *, groups, dim, rows, slab, width):
    """A chunk's update of one (slot, head): ``phi`` of the chunk's
    queries and keys made here, a row of 128 features at a time, by two
    one-hot matmuls each (exact, as :func:`feature_map`); the queries
    read against the state the chunk began from on the matrix unit at
    ``HIGHEST``; the state decayed once and the chunk's keys added on
    the vector unit; each row of the state read once and written once.
    Query ``i`` of the group at chunk row ``t`` reads out in row ``i *
    width + t``, before its decay and the in-chunk sums."""
    mq = groups * width
    u = u_ref[0, 0]  # (mq + width, d): the queries, then the keys
    exact = None if u.dtype == jnp.bfloat16 else HI
    at = jax.lax.broadcasted_iota(jnp.int32, (u.shape[-1], LANES), 0)
    total = vg_ref[0, 0, 2:3, :]  # (1, 128): the chunk's decay
    wk = wk_ref[0, 0]  # (width, 128): key j's weight in every lane
    for j in range(width):
        # vb[j][d, l] = v_j[d] times key j's weight, down the sublanes
        vb_scr[j] = jnp.broadcast_to(
            vw_ref[0, 0, j:j + 1, :], (LANES, LANES)
        ).T[:dim]

    def column(c, carry):
        num, den = carry
        first = pick_ref[0, pl.ds(c, 1), :]  # (1, 128)
        second = pick_ref[1, pl.ds(c, 1), :]

        def pick(index):
            return jnp.dot(
                u, (at == index).astype(u.dtype), precision=exact,
                preferred_element_type=jnp.float32,
            )

        phi = pick(first) * pick(second) \
            * jnp.where(first == second, 1.0, math.sqrt(2.0))
        fq, fk = phi[:mq], phi[mq:]
        s = s_ref[0, 0, c]  # (D, 128)
        z = z_ref[0, 0, pl.ds(c, 1), :]
        num = num + jax.lax.dot_general(
            fq, s, (((1,), (1,)), ((), ())), precision=HI,
            preferred_element_type=jnp.float32,
        )
        den = den + fq * z
        for s0 in range(0, dim, slab):
            new = total * s[s0:s0 + slab]
            for j in range(width):
                new = new + vb_scr[j, s0:s0 + slab, :] * fk[j:j + 1, :]
            s_out[0, 0, c, s0:s0 + slab, :] = new
        z_out[0, 0, pl.ds(c, 1), :] = total * z + jnp.sum(
            wk * fk, axis=0, keepdims=True)
        return num, den

    # Unrolled by hand (Pallas unrolls a loop whole or not at all): a
    # feature row's read-out waits on its picks, so one row an iteration
    # leaves the matrix unit's latency exposed 65 times a block; 13 rows
    # an iteration let one row's matmuls run under the next one's (7.8
    # -> 0.1 us a prefilling block on a v5e).
    step = max(d for d in range(1, 14) if rows % d == 0)

    def columns(i, carry):
        for r in range(step):
            carry = column(i * step + r, carry)
        return carry

    num, den = jax.lax.fori_loop(
        0, rows // step, columns,
        (jnp.zeros((mq, dim), jnp.float32),
         jnp.zeros((mq, LANES), jnp.float32)),
    )
    num_ref[0, 0, :, :dim] = num
    den_ref[0, 0] = jnp.broadcast_to(
        jnp.sum(den, axis=1, keepdims=True), (mq, LANES))


def _chunk_kernel(plan_ref, s_ref, z_ref, fq_ref, fk_ref, vg_ref, u_ref,
                  pick_ref, vw_ref, wk_ref, num_ref, den_ref, s_out, z_out,
                  acc_scr, vb_scr, *, groups, dim, rows, slab, width):
    """One (slot, head) grid step of a chunk: the whole state of that
    head read once and written once, whatever the slot takes.  A slot
    that takes one position (plan row 4) is stepped as
    :func:`_step_kernel` steps it, on the vector unit at the one-token
    program's cost; one that takes several goes through
    :func:`_several_positions`."""
    live = _prologue(plan_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out)
    n = plan_ref[4, pl.program_id(0)]

    @pl.when(live & (n <= 1))
    def _one():
        _one_position(s_ref, z_ref, fq_ref, fk_ref, vg_ref, num_ref,
                      den_ref, s_out, z_out, acc_scr, groups=groups,
                      dim=dim, rows=rows, slab=slab, stride=width)

    @pl.when(live & (n > 1))
    def _several():
        _several_positions(s_ref, z_ref, vg_ref, u_ref, pick_ref, vw_ref,
                           wk_ref, num_ref, den_ref, s_out, z_out, vb_scr,
                           groups=groups, dim=dim, rows=rows, slab=slab,
                           width=width)


@functools.lru_cache(maxsize=None)
def _pick_table(dim: int):
    """(2, C, 128) int32: the two indices of each entry of ``phi``, -1
    on a padding entry (its one-hot columns are empty)."""
    first, second, coef = feature_pairs(dim)
    return np.stack([np.where(coef != 0, first, -1),
                     np.where(coef != 0, second, -1)]).reshape(2, -1, LANES)


def chunk_kernel_fits(state, q) -> bool:
    """Whether :func:`retention_chunk_kernel` takes these shapes: those
    of :func:`retention_step_kernel`, and queries and keys of a dtype a
    one-hot matmul picks exactly."""
    return kernel_fits(state, q) \
        and q.dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_chunk_kernel(state, norm, q, k, v, log_g, n, fresh, *,
                           interpret: bool = False):
    """The kernel behind :func:`retention_step` for a chunk of several
    positions a slot on the TPU: ONE read and
    ONE write of each live (slot, head) state whatever the slot takes,
    as in :func:`retention_step_kernel`.  A slot that takes one
    position is stepped as there, on the vector unit; only a slot that
    takes several builds ``phi`` of its rows and goes through the
    matrix unit.  The in-chunk sums and the decays are small and made
    around the call.  The enclosing jit must donate the two leaves for
    the update to be in place."""
    b, heads, rows, dim, _ = state.shape
    groups, t = q.shape[2], q.shape[3]
    # every slot carries ``width`` rows, the ones past its n dead
    width = -(-max(t, 8) // 8) * 8
    if width > t:
        def more(x, axis, value=0):
            pad = [(0, 0)] * x.ndim
            pad[axis] = (0, width - t)
            return jnp.pad(x, pad, constant_values=value)

        q, k, v, log_g = more(q, 3), more(k, 2), more(v, 2), more(log_g, 2)
    with jax.named_scope("retention_step"):
        decay, inner_num, inner_den, w, total = _chunk_terms(
            q, k, v, log_g, n)
        live = (n > 0).astype(jnp.int32)
        slot_of, head_of = _stand_ins(live != 0, heads)
        plan = jnp.stack([live, fresh.astype(jnp.int32), slot_of, head_of,
                          n.astype(jnp.int32)])
        # a slot that takes one position: its row 0 as the one-token
        # program hands it over
        fq0 = feature_map(q[:, :, :, 0])
        fk0 = feature_map(k[:, :, 0])
        vg = _value_gate(v[:, :, 0], jnp.exp(log_g[:, :, 0]), dim)
        vg = vg.at[:, :, 2, :].set(total[:, :, None])
        # one that takes several: its rows, queries then keys
        u = jnp.concatenate(
            [q.reshape(b, heads, groups * width, -1), k.astype(q.dtype)], 2)
        vw = jnp.zeros((b, heads, width, LANES), jnp.float32)
        vw = vw.at[..., :dim].set(v * w[..., None])
        wk = jnp.broadcast_to(w[..., None], (b, heads, width, LANES))
        mq = groups * width
        call = pl.pallas_call(
            functools.partial(_chunk_kernel, groups=groups, dim=dim,
                              rows=rows, slab=min(_SLAB, dim), width=width),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, heads),
                in_specs=[
                    _spec(rows, dim, LANES), _spec(rows, LANES),
                    _spec(groups, rows, LANES), _spec(rows, LANES),
                    _spec(8, LANES), _spec(mq + width, u.shape[-1]),
                    pl.BlockSpec((2, rows, LANES),
                                 lambda bb, hh, plan_ref: (0, 0, 0)),
                    _spec(width, LANES), _spec(width, LANES),
                ],
                out_specs=[
                    _spec(mq, LANES), _spec(mq, LANES),
                    _spec(rows, dim, LANES), _spec(rows, LANES),
                ],
                scratch_shapes=[
                    pltpu.VMEM((groups, dim, LANES), jnp.float32),
                    pltpu.VMEM((width, dim, LANES), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, heads, mq, LANES), jnp.float32),
                jax.ShapeDtypeStruct((b, heads, mq, LANES), jnp.float32),
                jax.ShapeDtypeStruct(state.shape, state.dtype),
                jax.ShapeDtypeStruct(norm.shape, norm.dtype),
            ],
            input_output_aliases={1: 2, 2: 3},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name="retention_step",
        )
        num, den, state, norm = call(
            plan, state, norm, fq0, fk0, vg, u,
            jnp.asarray(_pick_table(q.shape[-1])), vw, wk,
        )
        num = num.reshape(b, heads, groups, width, LANES)[..., :dim]
        den = den[..., 0].reshape(b, heads, groups, width)
        one = (n == 1)[:, None, None, None]
        num = jnp.where(one[..., None], num,
                        decay[:, :, None, :, None] * num + inner_num)
        den = jnp.where(one, den, decay[:, :, None] * den + inner_den)
        num, den = _taken(num[:, :, :, :t], den[..., :t], n)
    return num, den, state, norm


def retention_step(state, norm, q, k, v, g, n, fresh):
    """``T`` positions of every live slot through its state in ONE
    update: ``q`` (B, H, G, T, d) the group's queries and ``k`` (B, H,
    T, d) the keys, rotated, zero where a key may not be seen; ``v`` (B,
    H, T, D) and the gates ``g`` (B, H, T) float32, 1 on a row the slot
    does not take; ``n`` (B,) the rows each slot takes, 0 where it sits
    the step out; ``fresh`` (B,).  Returns (numerators (B, H, G, T, D),
    denominators (B, H, G, T), state, norm), zeros past ``n``.

    One position (T = 1) goes through :func:`retention_step_kernel`,
    several through :func:`retention_chunk_kernel`, on the TPU where
    their tiles divide the shapes; the plain forms elsewhere.  All under
    the scope ``retention_step``, each kernel under that name."""
    kernel = jax.default_backend() == "tpu" and not _partitioned()
    if q.shape[3] > 1:
        args = (state, norm, q, k, v, jnp.log(g), n, fresh)
        if kernel and chunk_kernel_fits(state, q):
            return retention_chunk_kernel(*args)
        with jax.named_scope("retention_step"):
            return plain_retention_chunk(*args)
    with jax.named_scope("retention_step"):
        fq, fk = feature_map(q[:, :, :, 0]), feature_map(k[:, :, 0])
    args = (state, norm, fq, fk, v[:, :, 0], g[:, :, 0], n > 0, fresh)
    if kernel and kernel_fits(state, fq):
        num, den, state, norm = retention_step_kernel(*args)
    else:
        with jax.named_scope("retention_step"):
            num, den, state, norm = plain_retention_step(*args)
    return num[:, :, :, None], den[..., None], state, norm


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


class PowerRetention(nn.Module):
    """Causal power retention of degree 2 over ``num_heads`` query heads
    that read ``num_kv_heads`` key/value heads in contiguous groups,
    with a key-side padding mask (B, T): a key that may not be seen adds
    nothing (the gates between still decay).  Queries and keys pass a
    weight-only RMS norm a head and are rotated (rotate-half,
    ``rope_theta``); the gate is one value a key/value head, ``log
    sigmoid(x W_g + b_g)`` in float32.  Matmuls in ``dtype``; gate,
    feature map, state and read-out in float32.

    ``decode=True`` follows ``MultiHeadSelfAttention``'s convention: an
    uninitialized pass sizes the cache and is the full forward (the
    attention form, quadratic); after it every call feeds a row's T
    positions from ``cache_index`` on (scalar: lockstep; (B,): each row
    at its own) through the recurrence in one update of its state
    (:func:`retention_step`), a row taking the first ``chunk_rows`` of
    them where the cache holds that leaf (``_recurrent``).  A row at
    position 0 begins from a zero state whatever its leaves hold; a row
    whose ``key_mask`` shows no key sits the step out."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        b, t, hidden = x.shape
        heads, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        if heads % kvh:
            raise ValueError(
                f"num_heads={heads} not divisible by num_kv_heads={kvh}"
            )
        group = heads // kvh
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        def proj(name, n):
            y = nn.DenseGeneral((n, hd), use_bias=False, name=name, **kw)(x)
            return y.transpose(0, 2, 1, 3)  # (B, n, T, hd)

        with jax.named_scope("retention_proj"):
            q, k, v = proj("query", heads), proj("key", kvh), \
                proj("value", kvh)
            q = RMSNorm(self.norm_eps, name="q_norm", **kw)(q)
            k = RMSNorm(self.norm_eps, name="k_norm", **kw)(k)
        with jax.named_scope("retention_gate"):
            log_g = jax.nn.log_sigmoid(nn.Dense(
                kvh, use_bias=True, name="gate", dtype=jnp.float32,
                param_dtype=self.param_dtype,
            )(x.astype(jnp.float32)))  # (B, T, H_kv)
        dt = self.dtype if self.dtype is not None else x.dtype

        def out_proj(y):  # (B, H_kv, G, T, hd) float32
            with jax.named_scope("retention_out"):
                y = y.reshape(b, heads, t, hd).transpose(0, 2, 1, 3)
                return nn.DenseGeneral(
                    hidden, axis=(-2, -1), use_bias=False, name="out", **kw
                )(y.astype(dt))

        is_initialized = self.decode and self.has_variable(
            "cache", "retained_state"
        )
        if self.decode:
            s_shape, z_shape = state_shapes(b, kvh, hd, hd)
            state = self.variable("cache", "retained_state", jnp.zeros,
                                  s_shape, jnp.float32)
            norm = self.variable("cache", "retained_norm", jnp.zeros,
                                 z_shape, jnp.float32)
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
        if is_initialized:
            return out_proj(self._recurrent(q, k, v, log_g, key_mask, state,
                                            norm, ci))

        # The full forward: the attention form.
        pos = jnp.arange(t)
        q = apply_rope(q, pos, self.rope_theta)
        k = apply_rope(k, pos, self.rope_theta)
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", q.reshape(b, kvh, group, t, hd), k,
            preferred_element_type=jnp.float32,
        ) / math.sqrt(hd)
        decay = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)  # (B, H_kv, T)
        keep = jnp.tril(jnp.ones((t, t), bool))[None]
        if key_mask is not None:
            keep = keep & key_mask.astype(bool)[:, None, :]
        # masked before the exponent: a later key's difference is > 0
        weight = jnp.square(s) * jnp.exp(jnp.where(
            keep[:, None], decay[:, :, :, None] - decay[:, :, None, :],
            -jnp.inf,
        ))[:, :, None]
        num = jnp.einsum(
            "bhgqk,bhkd->bhgqd", weight, v.astype(jnp.float32),
        )
        return out_proj(
            num / (jnp.sum(weight, -1, keepdims=True) + self.eps)
        )

    def _recurrent(self, q, k, v, log_g, key_mask, state, norm, ci):
        """The decode branch: a row's T positions from ``cache_index`` on
        through its state in one update (:func:`retention_step`).  A row
        takes the first ``n`` of them: ``n`` the cache's ``chunk_rows``
        where the step program hands it over (the engine's chunk
        program: the positions each slot knows), else all T; none where
        its ``key_mask`` shows no key (it sits the step out).  A taken
        position whose key the mask hides decays the state and adds
        nothing.  Returns the read-out (B, H_kv, G, T, d), zeros on a
        row not taken."""
        b, heads, t, hd = q.shape
        kvh = k.shape[1]
        idx = ci.value
        at = idx if idx.ndim == 1 else jnp.full((b,), idx)
        rows = at[:, None] + jnp.arange(t)[None, :]  # (B, T)
        with jax.named_scope("retention_proj"):
            q = apply_rope(q, rows, self.rope_theta)
            k = apply_rope(k, rows, self.rope_theta)
        n = self.get_variable("cache", "chunk_rows") \
            if self.has_variable("cache", "chunk_rows") \
            else jnp.full((b,), t, jnp.int32)
        if key_mask is None:
            own = jnp.ones((b, t), bool)
        else:
            keep = key_mask.astype(bool)
            own = jnp.take_along_axis(
                keep, jnp.minimum(rows, keep.shape[-1] - 1), axis=1)
            n = jnp.where(jnp.any(keep, axis=-1), n, 0)
        taken = jnp.arange(t)[None, :] < n[:, None]
        ci.value = idx + (n if idx.ndim == 1 else t)
        num, den, state.value, norm.value = retention_step(
            state.value, norm.value, q.reshape(b, kvh, heads // kvh, t, hd),
            jnp.where(own[:, None, :, None], k, 0),
            v.astype(jnp.float32),
            jnp.where(taken[:, :, None], jnp.exp(log_g), 1.0)
            .transpose(0, 2, 1),
            n.astype(jnp.int32), at == 0,
        )
        return (num / hd) / (den[..., None] / hd + self.eps)
