"""Decode pools — resident decode state, bucketed on both axes.

One pool per (model, routed replica, KV-length bucket): a batch of S
decode *slots* over a KV cache of Tk *pages* per slot.  Both S and Tk
are power-of-two buckets (``serve/bucketing.py`` discipline), so a
whole deployment runs at most ``log2(max_slots)+1`` ×
``log2(max_kv)+1`` step executables per architecture — the small fixed
hot set the pjit serving papers converge on — and every one resolves
through the cross-job compile cache
(:mod:`~learningorchestra_tpu.train.compile_cache`), so fingerprints,
hit/miss stats and AOT eligibility all apply.

The continuous-batching trick is the per-row ``cache_index``: the
attention decode branch (ops/layers.py) accepts a (S,)-shaped index,
so slots sit at DIFFERENT sequence positions inside one jitted step —
a newly admitted prompt starts its prefill (a chunk of positions a
step where every cache layer is K/V pages or every one a recurrent
state, :func:`chunk_width`; else one token a step) in the same
dispatch that extends its neighbours.
Freed slots are simply
zeroed in the token buffer: an all-pad row masks to an exact-zero
attention output (the masked-softmax double-where), so stale KV pages
cost nothing and need no scrubbing.

A model whose layers keep a recurrent STATE a slot instead of pages
(:data:`STATE_LEAVES`: no length axis) has ONE pool a replica whatever
its requests' lengths, and one step program a slot bucket; the pool's
``kv`` is then only the length of its token buffer.  A stale state is
not harmless as a stale page is: whatever the slot's last request left
would decay into the next one's output.  So the layer itself begins a
slot that stands at position 0 from a zero state, inside the step
program, from the ``cache_index`` it is handed (``ops/retention.py``,
``ops/ssm.py``): no scrub on ``release``, no further host array.  The
layer takes a slot whose key mask is empty (a free one) to sit the step
out.  A model whose layers keep both (a state space mixer beside
attention, ``models/hybrid.py``) is keyed by length like any model with
pages, and each of its pools carries the pages and the states side by
side, one token a step.

The step program OWNS the pool's device state: it consumes the cache
and the token buffer it is called with (both are donated, XLA updates
them in place) and hands back the ones to use from then on.  After a
call the arrays that went in are deleted; whoever holds a pool holds
only what the last step returned, and no caller keeps a reference to a
cache or a buffer across a step.
"""

from __future__ import annotations

import numpy as np

from learningorchestra_tpu.serve.decode import blocks

#: The leaf an attention layer's cache is found by: a layer of
#: ``MultiHeadSelfAttention`` keeps ``cached_key`` (with
#: ``cached_value`` beside it), a layer of ``LatentAttention`` its one
#: ``cached_latent``.
PAGE_LEAVES = ("cached_key", "cached_latent")
#: The leaf a layer's recurrent state is found by: a layer of
#: ``PowerRetention`` keeps ``retained_state`` (with ``retained_norm``
#: beside it), a ``Mamba2Mixer`` its ``ssm_state`` (with the
#: convolution's ``conv_tail`` beside it).  No axis of them is a
#: sequence length.
STATE_LEAVES = ("retained_state", "ssm_state")


def set_index(pages, pos, rows=None):
    """The decode cache tree the module applies on: ``pages`` (the
    leaves a pool carries) with a ``cache_index`` beside every layer's
    page or state leaf (:data:`PAGE_LEAVES`, :data:`STATE_LEAVES`),
    each the per-slot position vector ``pos`` (S,) — the step's single
    source of truth for where each slot writes, how far it may attend
    and whether its state begins anew.  ``rows`` (S,), the positions of
    a chunk each slot takes, goes beside every state leaf as
    ``chunk_rows``: a state layer takes those rows into its state and
    no further (a page layer writes every row and needs no count)."""
    out = {
        key: set_index(val, pos, rows) if isinstance(val, dict) else val
        for key, val in pages.items()
    }
    if any(name in pages for name in PAGE_LEAVES + STATE_LEAVES):
        out["cache_index"] = pos
    if rows is not None and any(name in pages for name in STATE_LEAVES):
        out["chunk_rows"] = rows
    return out


def strip_index(cache):
    """``cache`` without what :func:`set_index` put in it: what a pool
    carries from step to step.  The step sets every index from ``pos``
    before anything reads one, so carrying them would only be one more
    argument and one more output a layer."""
    return {
        key: strip_index(val) if isinstance(val, dict) else val
        for key, val in cache.items()
        if key not in ("cache_index", "chunk_rows")
    }


def first_pages(cache, names=PAGE_LEAVES + STATE_LEAVES):
    """The first leaf of a decode cache tree called one of ``names``:
    by default a layer's K pages, its latent pages or its retained
    state, the one the engine asks ``is_deleted()`` after a step, to
    count the steps that updated the cache in place."""
    for key, val in cache.items():
        if key in names:
            return val
        if isinstance(val, dict):
            found = first_pages(val, names)
            if found is not None:
                return found
    return None


def holds_pages(cache) -> bool:
    """Whether a decode cache tree (arrays or shapes) holds pages, rows
    that grow with the sequence; one of recurrent states alone does
    not, and its pool serves every length."""
    return first_pages(cache, PAGE_LEAVES) is not None \
        or first_pages(cache, STATE_LEAVES) is None


def holds_states(cache) -> bool:
    """Whether a decode cache tree holds a recurrent state a slot, with
    or without pages beside it."""
    return first_pages(cache, STATE_LEAVES) is not None


def state_bytes(cache) -> int:
    """Bytes of the leaves that sit beside a state leaf (a state, its
    normaliser or convolution tail), all layers, of a cache tree."""
    import jax

    if any(name in cache for name in STATE_LEAVES):
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(cache))
    return sum(state_bytes(val) for val in cache.values()
               if isinstance(val, dict))


#: (module fingerprint, kv) -> the decode cache's shape tree for ONE
#: slot.  Tracing a model's init takes seconds at a published depth
#: (6.5 s for 48 layers on the chip machine's host, PR 37), and a pool
#: asks at every slot bucket it grows through and for each of its
#: step programs: asked once a length, scaled by the slots.
_ONE_SLOT: dict = {}


def cache_shapes(module, nslots: int, kv: int):
    """Shape tree of the decode cache a pool of ``module`` carries for
    ``nslots`` slots over ``kv`` positions.  Every leaf's first axis is
    the slots' (what ``PagePool._grow`` pads) and nothing else of a
    leaf depends on them."""
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.train.compile_cache import (
        module_fingerprint,
    )

    key = (module_fingerprint(module), int(kv))
    one = _ONE_SLOT.get(key)
    if one is None:
        if len(_ONE_SLOT) >= 64:
            _ONE_SLOT.clear()
        one = _ONE_SLOT[key] = strip_index(jax.eval_shape(
            module.clone(decode=True).init, jax.random.PRNGKey(0),
            jnp.zeros((1, kv), jnp.int32),
        )["cache"])
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((nslots, *s.shape[1:]), s.dtype),
        one,
    )


def keyed_by_length(module) -> bool:
    """Whether pools of ``module`` are keyed by a KV-length bucket: the
    model's own cache says, as its ``block_length`` says the step's
    width."""
    return holds_pages(cache_shapes(module, 1, 8))


def _moe_stats(mut) -> list:
    """What the step's routed layers sowed, summed over layers:
    [distinct held experts reached a layer, the busiest expert's rows,
    (token, choice) pairs that reached a held expert]; empty for a
    model without routed experts."""
    import jax.numpy as jnp

    sown = mut.get("moe_stats", {})
    hit = _named(sown, "experts_hit")
    if not hit:
        return []
    return [sum(hit), jnp.max(jnp.stack(_named(sown, "load_max"))),
            sum(_named(sown, "rows"))]


def _named(tree, name: str) -> list:
    """Every value stored under key ``name`` in a nested dict."""
    out = []
    for key, val in tree.items():
        if key == name:
            out.append(val)
        elif isinstance(val, dict):
            out += _named(val, name)
    return out


def step_width(module) -> int:
    """Positions a slot-step of ``module`` processes: its
    ``block_length`` where it generates by diffusion over blocks, else
    1."""
    return int(getattr(module, "block_length", None) or 1)


#: Prompt positions a slot takes in ONE step of a pool whose every
#: cache layer is K/V pages (:func:`chunk_width`).  Every slot of a
#: chunk step carries this many rows, the decoding ones too, and the
#: gap between a decoding neighbour's tokens is that step's time: so
#: it is the widest of 4, 8, 16, 32 whose step at GPT-2 XL's widths, 8
#: slots over a 512 bucket, takes no more than 1.04 times the one-token
#: step's device time on a v5e (``scripts/chip_chunk_sweep.py``).
#: Measured 11.19, 11.39, 14.18, 21.00 ms against 11.94 (``PERF.md``
#: section 6, PR 37): 8 passes, 16 does not.
PROMPT_CHUNK = 8
#: Prompt positions a slot takes in ONE step of a pool whose every
#: cache layer is a recurrent state (:func:`chunk_width`): its layers
#: take the chunk in one update of each state (``ops/retention.py``
#: ``retention_step``), so the state is read and written once a step
#: whatever the width, and only the slots that prefill pay for the
#: chunk's rows against it.  Chosen by the same rule as
#: :data:`PROMPT_CHUNK` at Brumby-14B's widths, 7 layers, 16 slots over
#: the 1,024-position buffer, 2 of them prefilling
#: (``scripts/chip_chunk_sweep.py retention``): 20.86, 20.98, 22.54,
#: 32.34 ms at 4, 8, 16, 32 against 20.62 on a v5e (``PERF.md``
#: section 6): 8 passes, 16 does not (the weights' matmuls at
#: 256 rows leave their memory bound).
STATE_CHUNK = 8


def chunk_width(module) -> int:
    """Prompt positions a slot of ``module`` may take in one step, read
    from the model's own cache, as pages or states is
    (:func:`keyed_by_length`), where the model produces one token a
    step: :data:`PROMPT_CHUNK` where every cache layer is K/V pages of
    ``MultiHeadSelfAttention`` (whose decode branch takes a causal
    chunk at a per-row index and writes its rows in one pass);
    :data:`STATE_CHUNK` where every one is a recurrent state (whose
    decode branch takes the chunk in one update of the state); else 1:
    a latent layer's chunk wants its non-absorbed form, a block pool's
    prefill is whole blocks already, and a layer that keeps pages and a
    state side by side steps its state one position a call."""
    if step_width(module) > 1:
        return 1
    cache = cache_shapes(module, 1, 8)

    def only(names):
        others = tuple(n for n in PAGE_LEAVES + STATE_LEAVES
                       if n not in names)
        return first_pages(cache, names) is not None \
            and first_pages(cache, others) is None

    if only(("cached_key",)):
        return PROMPT_CHUNK
    return STATE_CHUNK if only(STATE_LEAVES) else 1


def step_donates(module) -> tuple:
    """The arguments a step of ``module`` consumes: cache and token
    buffer, and a block pool's state."""
    return (1, 2, 3) if step_width(module) > 1 else (1, 2)


def build_step(module, nslots: int, kv: int, chunk: int = 1):
    """(step fn, shape tree of the K/V pages a pool carries) for one
    (arch, S, Tk) cell.  ``step(variables, cache, buf, pos, t0s, live)``
    returns ``(cache, buf, col)``.  ``chunk`` over 1 builds the
    prompt-chunk program of a one-token model (below) in the one-token
    program's place: same arguments, same result.

    The step CONSUMES its ``cache`` and ``buf`` arguments (donated: the
    K/V pages and the token buffer are updated in place, no second copy
    is allocated) and returns their successors; a caller must not keep
    or touch a reference to what it passed in.  ``variables`` are the
    registry's and never donated.  The three per-slot host vectors go
    to the device as ONE (3, S) int32 array: every argument the runtime
    has to place is a transfer and an allocation of its own, and on the
    chip their number, not their size, is what a call costs.

    The step replicates the solo ``GreedyDecodeMixin.generate`` scan
    body exactly — same token gather, same key mask, same f32 argmax,
    same write-at-``pos+1`` — but with per-slot positions, so a slot
    admitted mid-flight produces bit-identical tokens to a solo decode
    of the same prompt (greedy only; sampling stays on the solo path).

    The prompt-chunk program (``chunk`` = C > 1; a model of
    :func:`chunk_width` C) lets a live slot at ``pos`` take ``n =
    clip(t0 - pos, 1, C)`` positions ``pos .. pos+n-1`` in the one
    step, all of them known: prompt tokens, or for a decoding slot
    (n = 1) the token the last step wrote.  ``n`` follows on the device
    from the same (3, S) array; nothing C wide comes from the host.
    Every slot carries C rows: their tokens gathered from the buffer
    at ``pos + arange(C)``, the layer's own causal in-chunk mask under
    the ``buf != 0`` key mask, their K/V written in place by the
    layer's one ``cached_attend``; a retention layer is handed ``n``
    (:func:`set_index`) and takes those rows into its state in one
    update.  The slot's next token is the argmax
    of the float32 logits of row ``n-1``, written at ``pos+n`` under
    the one-token rule.  Rows ``n .. C-1`` are dead weight: what they
    write lies beyond the slot's position, where no query may look
    before the step that reaches it has rewritten it (as a block's
    denoising forwards rely on); a row beyond the bucket is dropped;
    their logits are never read.  The engine enqueues this program
    only for a step in which some live slot has n > 1.

    A slot-step is ``q`` positions wide: 1 for a next-token model, the
    module's ``block_length`` for one that generates by diffusion over
    blocks (:func:`step_width`).  Such a pool carries, beside pages and
    buffer and donated like them, the ``(S, 2 + 3q)`` int32 state of
    every slot's current block (``blocks.STATE_HEAD``), and its step is
    ``step(variables, cache, buf, state, slots)`` -> ``(cache, buf,
    state, col)``: the step program runs the procedure of
    ``blocks.py`` itself.  ``slots`` is the one host array a call
    (``blocks.SLOT_ROWS``, a column a slot): who is seated, who was
    seated since the last step (that slot begins anew at position 0),
    and each request's prompt length, end and plan; nothing ``q`` wide
    comes from the host.  A seated slot whose block starts before its
    request's end forwards the block: its tokens go to the buffer at
    ``pos .. pos+q-1`` and their K/V to the pages (every forward of a
    block rewrites them; the commit's stay), attending with ``slot <=
    pos+q-1`` and full sight inside the block.  A block with a mask
    left then takes the strategy (``blocks.denoise``) on the forward's
    float32 proposals and confidences; one with none (a prompt block's
    prefill, or a commit) moves on, the next block read from the
    buffer row's prompt with the mask id from ``t0`` on.  The result
    is ONE ``(S + 1, 3 + 2q)`` int32 array: per slot what its forward
    was, where the block starts, how many positions it fixed and the
    block's tokens with the step each was fixed at
    (``blocks.RESULT_HEAD``), and in the last row the experts the
    step's rows reached (distinct experts a layer, summed over layers),
    the busiest expert's rows and the rows that reached a held expert
    (:func:`_moe_stats`).  A next-token model with routed experts
    returns the same three behind its token column: ``(S + 3,)`` where
    a dense model's is ``(S,)``.
    """
    import jax
    import jax.numpy as jnp

    q, head = step_width(module), len(blocks.STATE_HEAD)
    decode_mod = module.clone(decode=True)
    shapes = cache_shapes(module, nslots, kv)

    def block_step(variables, cache, buf, state, slots):
        told = dict(zip(blocks.SLOT_ROWS, slots))
        live, seat = told["live"] != 0, told["seat"] != 0
        t0, total, mask_id = told["t0"], told["total"], told["mask"]
        # a free slot's plan is all 0: no division by its steps
        steps, dynamic = jnp.maximum(told["steps"], 1), told["dynamic"] != 0
        threshold = jax.lax.bitcast_convert_type(
            told["threshold"], jnp.float32
        )
        pos = jnp.where(seat, 0, state[:, 0])
        nth = jnp.where(seat, -1, state[:, 1])
        tokens, masked, fixed_at = jnp.split(state[:, head:], 3, axis=1)
        masked = masked != 0
        # A slot that sits the step out (free, or past its request's
        # end and not yet released) goes in at position 0 with pad
        # tokens; its buffer row and its state stay as they are.
        active = live & (pos < total)
        at = jnp.where(active, pos, 0)
        lanes = at[:, None] + jnp.arange(q)[None, :]
        rows = jnp.arange(nslots)[:, None]
        held = buf[rows, lanes]
        # A block not begun: the prompt's tokens that fall in it, the
        # mask id from t0 on.
        begin = (active & (nth < 0))[:, None]
        given = lanes < t0[:, None]
        tokens = jnp.where(
            begin, jnp.where(given, held, mask_id[:, None]), tokens
        )
        masked = jnp.where(begin, ~given, masked)
        fixed_at = jnp.where(begin, -1, fixed_at)
        nth = jnp.where(begin[:, 0], 0, nth)
        tok = jnp.where(active[:, None], tokens, 0)
        buf = buf.at[rows, lanes].set(
            jnp.where(active[:, None], tok, held)
        )
        kmask = (jnp.arange(kv)[None, :] <= at[:, None] + (q - 1)) \
            & (buf != 0)
        logits, mut = decode_mod.apply(
            {**variables, "cache": set_index(cache, at)}, tok,
            positions=lanes, key_mask=kmask,
            mutable=["cache", "moe_stats"],
        )
        logits = logits.astype(jnp.float32)  # (S, q, V)
        top = jnp.max(logits, -1)
        x0 = jnp.argmax(logits, -1).astype(jnp.int32)
        # softmax(logits)[x0], in float32
        conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), -1)
        final = blocks.final(masked)
        denoising = active & ~final
        moving = active & final
        *after, nth_after, pick = blocks.denoise(
            tokens, masked, fixed_at, nth, x0, conf, steps, dynamic,
            threshold,
        )
        tokens, masked, fixed_at = (
            jnp.where(denoising[:, None], new, old)
            for new, old in zip(after, (tokens, masked, fixed_at))
        )
        nth = jnp.where(denoising, nth_after, nth)
        kind = jnp.where(
            ~active, blocks.IDLE, jnp.where(
                ~final, blocks.DENOISE, jnp.where(
                    at + q <= t0, blocks.PREFILL, blocks.COMMIT)))
        fixed = jnp.sum(pick & denoising[:, None], -1)
        stats = jnp.zeros(len(blocks.RESULT_HEAD) + 2 * q, jnp.int32)
        for i, value in enumerate(_moe_stats(mut)):
            stats = stats.at[i].set(value)
        col = jnp.concatenate([
            jnp.concatenate(
                [jnp.stack([kind, at, fixed], 1), tokens, fixed_at], 1
            ).astype(jnp.int32),
            stats[None],
        ])
        state = jnp.concatenate([
            jnp.stack([
                jnp.where(moving, pos + q, pos),
                jnp.where(moving, -1, nth),
            ], 1),
            tokens, masked.astype(jnp.int32), fixed_at,
        ], 1).astype(jnp.int32)
        return strip_index(mut["cache"]), buf, state, col

    def write_next(buf, logits, nxt_pos, t0s, live, mut):
        """A one-token model's step from its slots' last logits on:
        the next tokens into the buffer at ``nxt_pos``, and the step's
        result."""
        nxt = jnp.argmax(logits.astype(jnp.float32), -1).astype(jnp.int32)
        prev = jnp.take_along_axis(buf, nxt_pos[:, None], axis=1)[:, 0]
        # ``live`` gates the write: a free slot's buffer row stays
        # all-pad (its attention mask stays empty: nothing attends,
        # and a layer that keeps a state leaves a slot with no key to
        # see untouched; one at position 0 it begins from zero, so a
        # reused slot needs no scrub), and a slot still
        # prefilling copies the NEXT prompt token instead of the
        # model's prediction — identical to the solo scan's
        # ``i + 1 >= t0`` select.
        col = jnp.where(live & (nxt_pos >= t0s), nxt, prev)
        buf = buf.at[jnp.arange(nslots), nxt_pos].set(col)
        stats = _moe_stats(mut)
        if stats:  # one result, one transfer: the counts ride the column
            col = jnp.concatenate(
                [col, jnp.stack(stats).astype(jnp.int32)]
            )
        return strip_index(mut["cache"]), buf, col

    def token_step(variables, cache, buf, slots):
        pos, t0s, live = slots[0], slots[1], slots[2] != 0
        cache = set_index(cache, pos)
        tok = jnp.take_along_axis(buf, pos[:, None], axis=1)
        kmask = (jnp.arange(kv)[None, :] <= pos[:, None]) & (buf != 0)
        logits, mut = decode_mod.apply(
            {**variables, "cache": cache}, tok,
            positions=pos[:, None], key_mask=kmask,
            mutable=["cache", "moe_stats"],
        )
        return write_next(buf, logits[:, 0], pos + 1, t0s, live, mut)

    def chunk_step(variables, cache, buf, slots):
        pos, t0s, live = slots[0], slots[1], slots[2] != 0
        n = jnp.where(live, jnp.clip(t0s - pos, 1, chunk), 1)
        # A row beyond the bucket reads the bucket's last token at its
        # last position, and its K/V is dropped.
        lanes = jnp.minimum(
            pos[:, None] + jnp.arange(chunk)[None, :], kv - 1
        )
        tok = jnp.take_along_axis(buf, lanes, axis=1)
        logits, mut = decode_mod.apply(
            {**variables, "cache": set_index(cache, pos, n)}, tok,
            positions=lanes, key_mask=buf != 0,
            mutable=["cache", "moe_stats"],
        )
        last = jnp.take_along_axis(
            logits, (n - 1)[:, None, None], axis=1
        )[:, 0]
        return write_next(buf, last, pos + n, t0s, live, mut)

    def step(variables, cache, buf, *rest):
        # One name for the program whatever its width: the trace's
        # readers find a pool's steps as ``jit_step``.
        if q > 1:
            return block_step(variables, cache, buf, *rest)
        return (token_step if chunk == 1 else chunk_step)(
            variables, cache, buf, *rest
        )

    jitted = jax.jit(step, donate_argnums=step_donates(module))

    def call(variables, cache, buf, *rest):
        if q == 1:  # pos, t0s, live: one transfer
            rest = (np.stack(rest),)
        return jitted(variables, cache, buf, *rest)

    # What ``call`` runs, for whoever compiles it without running it.
    call.program = jitted
    return call, shapes


class PagePool:
    """S slots × Tk KV pages of resident decode state for one model; or
    S slots' recurrent states, where the model keeps no pages
    (``holds_pages``): ``kv`` is then the token buffer's length alone.

    Only the owning model's decode worker thread touches a pool, so the
    pool itself is lock-free; the worker's condition variable is the
    synchronization point for admission and abort.

    A pool has a step in flight most of the time.  ``pos`` is the
    host's: it advances when a step is dispatched, not when its result
    is read.  ``admit``, ``release`` and ``_grow`` only enqueue device
    updates on what the last dispatched step returned, which jax orders
    behind it; nothing here reads the device.
    """

    __slots__ = ("kv", "nslots", "max_slots", "cache", "buf", "state",
                 "pos", "fresh", "streams", "steps", "replica_idx",
                 "unread", "read_at", "width", "chunk", "holds_pages",
                 "holds_states", "_token_bytes", "_slot_bytes")

    def __init__(self, kv: int, max_slots: int,
                 replica_idx: int | None = None, width: int = 1,
                 chunk: int = 1):
        self.kv = int(kv)
        self.max_slots = int(max_slots)
        # Positions a slot-step processes (``step_width``).  Over 1,
        # the pool carries ``state``, every slot's current block on the
        # device (``blocks.STATE_HEAD``): where a slot stands is the
        # step program's to know, ``pos`` is not kept, and ``fresh``
        # marks the slots seated since the last dispatch, whose state
        # the next step begins anew.
        self.width = int(width)
        # Prompt positions a slot may take in one step
        # (``chunk_width``): a step in which some slot takes several
        # runs the chunk program, any other the one-token program.
        self.chunk = int(chunk)
        self.streams: list = []
        self.drop()
        self.steps = 0
        self.replica_idx = replica_idx
        # When the worker last read a step of this pool back (or
        # dispatched one into it drained): the devtime ledger is fed
        # the wall time between successive reads.
        self.read_at = 0.0

    # -- capacity ------------------------------------------------------------

    @property
    def live(self) -> int:
        return sum(1 for s in self.streams if s is not None)

    def page_bytes(self) -> int:
        """Resident cache bytes (per-head K and V pages, a latent
        layer's one ``kv_lora_rank + qk_rope_head_dim`` row a position,
        or a retention layer's state a slot) — observability for the
        freeing tests.
        Shapes only (``nbytes`` is the aval's): the REST thread may
        call this while the worker is inside a step, when the tree it
        finds here has just been donated."""
        import jax

        cache = self.cache
        if cache is None:
            return 0
        return sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(cache)
        )

    def token_bytes(self) -> float | None:
        """Resident KV bytes a cached position (the pages' bytes over
        slots x pages): what a token costs the pool, all layers.  The
        same at every slot bucket: worked out when the pool allocates.
        None for a pool of states alone: a token costs it nothing."""
        return self._token_bytes

    def slot_bytes(self) -> float | None:
        """Resident state bytes a slot (the states' bytes over slots),
        all layers: what a request costs the pool's states, whatever its
        length.  None for a pool of pages alone, which a request fills
        by the token.  A pool that holds both has both."""
        return self._slot_bytes

    @property
    def device(self) -> tuple:
        """What the pool's step consumes and hands back: pages, token
        buffer and, in a block pool, the blocks' state."""
        carried = (self.cache, self.buf)
        return carried if self.state is None else (*carried, self.state)

    @device.setter
    def device(self, carried) -> None:
        self.cache, self.buf, *state = carried
        if state:
            self.state, = state

    def drop(self) -> list:
        """Forget the device state, back to unallocated (the next admit
        allocates afresh), and return the streams that were seated.
        What a failed step leaves behind: it may have consumed the
        cache and the buffer before it raised, so nothing touches
        them."""
        seated = [s for s in self.streams if s is not None]
        self.cache = None  # device tree, allocated on first admit
        self.buf = None    # (S, Tk) int32 token buffer
        self.state = None  # (S, 2 + 3 width) int32, a block pool's
        # The step in flight: what the engine needs to read its result,
        # kept from its dispatch to the turn after.
        self.unread = None
        self.nslots = 0
        # Pages, states or both: the allocated tree says (``_alloc``).
        self.holds_pages, self.holds_states = True, False
        self._token_bytes = self._slot_bytes = None
        self.pos = np.zeros(0, np.int32)
        self.fresh = np.zeros(0, bool)
        self.streams = []
        return seated

    def _alloc(self, cache_shapes, nslots: int) -> None:
        import jax
        import jax.numpy as jnp

        self.cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
        )
        self.buf = jnp.zeros((nslots, self.kv), jnp.int32)
        if self.width > 1:
            self.state = jnp.zeros(
                (nslots, len(blocks.STATE_HEAD) + 3 * self.width),
                jnp.int32,
            )
        self.pos = np.zeros(nslots, np.int32)
        self.fresh = np.zeros(nslots, bool)
        self.streams = [None] * nslots
        self.nslots = nslots
        self.holds_pages = holds_pages(cache_shapes)
        self.holds_states = holds_states(cache_shapes)
        states = state_bytes(self.cache)
        if self.holds_pages:
            self._token_bytes = (self.page_bytes() - states) \
                / (nslots * self.kv)
        if self.holds_states:
            self._slot_bytes = states / nslots

    def _grow(self, cache_shapes, nslots: int) -> None:
        """Pad every per-slot axis up to the next slot bucket; existing
        slots keep their pages, states and positions bit-for-bit."""
        import jax
        import jax.numpy as jnp

        extra = nslots - self.nslots

        def pad(leaf):
            width = [(0, extra)] + [(0, 0)] * (leaf.ndim - 1)
            return jnp.pad(leaf, width)

        del cache_shapes  # same tree structure; pad in place
        self.cache = jax.tree_util.tree_map(pad, self.cache)
        self.buf = pad(self.buf)
        if self.state is not None:
            self.state = pad(self.state)
        self.pos = np.concatenate(
            [self.pos, np.zeros(extra, np.int32)]
        )
        self.fresh = np.concatenate([self.fresh, np.zeros(extra, bool)])
        self.streams.extend([None] * extra)
        self.nslots = nslots

    # -- slot lifecycle ------------------------------------------------------

    def admit(self, stream, cache_shapes_for) -> int | None:
        """Seat ``stream`` in a free slot (growing to the next slot
        bucket if needed, up to ``max_slots``); None when full.  The
        slot's buffer row gets the prompt, position 0 — prefill runs
        through the shared step, ``chunk`` positions at a time with the
        solo scan's result (a block pool's, a whole prompt block a
        step)."""
        from learningorchestra_tpu.serve.bucketing import bucket_for

        slot = None
        for i, s in enumerate(self.streams):
            if s is None:
                slot = i
                break
        if slot is None:
            if self.nslots >= self.max_slots:
                return None
            want = bucket_for(self.nslots + 1, self.max_slots)
            if self.nslots == 0:
                self._alloc(cache_shapes_for(want), want)
            else:
                self._grow(cache_shapes_for(want), want)
            slot = next(
                i for i, s in enumerate(self.streams) if s is None
            )
        row = np.zeros(self.kv, np.int32)
        row[: stream.t0] = stream.prompt
        self.buf = self.buf.at[slot].set(row)
        self.pos[slot] = 0
        self.fresh[slot] = True
        self.streams[slot] = stream
        return slot

    def release(self, slot: int) -> None:
        """Free the slot and its KV pages: zeroing the buffer row
        empties the slot's attention mask, so whatever K/V the pages
        still hold is unreachable — the pages are free for the next
        admit without a scrub pass.  A state the slot leaves behind is
        dropped by the step that seats the next request there: it
        stands at position 0, where the layer begins from zero."""
        self.streams[slot] = None
        self.pos[slot] = 0
        if self.buf is not None:
            self.buf = self.buf.at[slot].set(0)
