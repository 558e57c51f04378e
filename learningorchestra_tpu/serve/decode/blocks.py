"""Generation by diffusion over blocks: the procedure, once.

A block-diffusion LM generates ``B`` positions at a time.  A block
starts as mask tokens (a prompt's remainder sits in it already fixed)
and takes up to ``T`` *denoising* forwards: each proposes a token
``x0`` and a confidence (the softmax probability of ``x0``) at every
position, and the strategy fixes some of the still-masked ones:

- ``low_confidence_static`` fixes the :func:`transfer_count` masked
  positions of highest confidence;
- ``low_confidence_dynamic`` fixes every masked position whose
  confidence passes the threshold, or the static count if fewer do.

When no mask is left, one *commit* forward over the block's final
tokens makes its K/V final and the next block begins.  What the
forward is belongs to the caller: the decode engine's step program over
the page pool, or an estimator's plain full forward.

The strategy (:func:`choose`, :func:`denoise`) is written on arrays,
any leading axes, and is traced: the engine's step program applies it
to every slot's block on the device (``pages.build_step``), where the
proposals and confidences are, so no host stands between one step and
the next; the estimator's plain path calls the same function on its one
row.  A block's state is four things: its tokens (the mask id where not
yet fixed), which positions are still masked, the denoising step each
was fixed at (-1: never, a prompt's token or one still masked), and how
many denoising forwards it has had.  ``masked`` is its own vector and
not ``tokens == mask id``: a model may well propose the mask id itself,
and a position fixed to it is fixed.
"""

from __future__ import annotations

import numpy as np

REMASKING = ("low_confidence_static", "low_confidence_dynamic")

#: What a slot's forward was, as the step program reports it: nothing
#: (the slot sat the step out), a whole prompt block's prefill, a
#: denoising forward, a generated block's commit.
IDLE, PREFILL, DENOISE, COMMIT = range(4)

#: The rows of the one host array a block step is called with, a column
#: a slot: who is seated; who was seated since the pool's last step (the
#: step then begins that slot's blocks anew, from its buffer row); and
#: what only the request knows: prompt length, where it ends, and its
#: plan (:attr:`BlockPlan.packed`: the mask id with it, which is the
#: estimator's and not the module's).
SLOT_ROWS = ("live", "seat", "t0", "total", "mask", "steps", "dynamic",
             "threshold")

#: A slot's row of the state a block pool carries on the device, int32:
#: where its current block starts and how many denoising forwards the
#: block has had (-1: not begun, the step reads it from the buffer row
#: first), then the block's B tokens, B masked flags and B ``fixed_at``.
STATE_HEAD = ("pos", "step")

#: A slot's row of a block step's result, int32: what its forward was
#: (:data:`IDLE` .. :data:`COMMIT`), where the block starts, how many
#: positions the forward fixed, then the block's B tokens and their B
#: ``fixed_at`` as the forward left them (final after a commit).
RESULT_HEAD = ("kind", "start", "fixed")


def transfer_count(block, steps, step):
    """How many positions denoising step ``step`` of ``steps`` fixes at
    the least: ``block / steps``, the remainder going to the first
    steps, so that ``steps`` steps fix a whole block.  Ints or int
    arrays."""
    return block // steps + (step < block % steps)


def final(masked):
    """No mask left (..., B) -> (...): the forward over these tokens is
    the block's commit, or a prompt block's prefill."""
    return ~masked.any(-1)


def choose(conf, masked, count, dynamic, threshold):
    """Which masked positions to fix now, a bool array like ``masked``
    (..., B): the ``count`` (...) of highest float32 confidence
    ``conf``, ties to the earlier position, or where ``dynamic`` (...)
    every one over ``threshold`` (...) if at least ``count`` are."""
    import jax.numpy as jnp

    conf = jnp.where(masked, conf, -jnp.inf)
    count = jnp.minimum(jnp.asarray(count), masked.sum(-1))
    threshold = jnp.asarray(threshold, jnp.float32)
    # The stable descending order without a sort: a position's rank is
    # how many come before it, the higher and, among equals, the earlier.
    mine, other = conf[..., :, None], conf[..., None, :]
    idx = jnp.arange(conf.shape[-1])
    before = (other > mine) | (
        (other == mine) & (idx[None, :] < idx[:, None])
    )
    pick = before.sum(-1) < count[..., None]
    high = masked & (conf > threshold[..., None])
    return jnp.where(
        (jnp.asarray(dynamic) & (high.sum(-1) >= count))[..., None],
        high, pick,
    )


def denoise(tokens, masked, fixed_at, step, x0, conf, steps, dynamic,
            threshold):
    """One denoising forward's proposals ``x0`` and confidences applied
    to a block's state by the request's plan (``steps``, ``dynamic``,
    ``threshold``): ``(tokens, masked, fixed_at, step)`` after it and
    the bool array of the positions it fixed."""
    import jax.numpy as jnp

    step = jnp.asarray(step)
    pick = choose(
        conf, masked, transfer_count(masked.shape[-1], steps, step),
        dynamic, threshold,
    )
    return (
        jnp.where(pick, x0, tokens), masked & ~pick,
        jnp.where(pick, step[..., None], fixed_at), step + 1, pick,
    )


class BlockPlan:
    """The request's side of the procedure: how many steps, by which
    strategy.  Validated once, at the request's door."""

    __slots__ = ("block", "steps", "remasking", "threshold", "mask_id")

    def __init__(self, estimator, steps=None, remasking=None,
                 threshold=None):
        self.block = int(estimator.block_length)
        self.mask_id = int(estimator.mask_token_id)
        self.steps = int(
            estimator.denoising_steps if steps is None else steps
        )
        self.remasking = str(
            estimator.remasking if remasking is None else remasking
        )
        self.threshold = float(
            estimator.confidence_threshold if threshold is None
            else threshold
        )
        if not 1 <= self.steps <= self.block:
            raise ValueError(
                f"denoisingSteps must be in 1..{self.block} (the block "
                f"length), got {self.steps}"
            )
        if self.remasking not in REMASKING:
            raise ValueError(
                f"remasking must be one of {REMASKING}, got "
                f"{self.remasking!r}"
            )
        if not 0.0 <= self.threshold <= 1.0:
            raise ValueError(
                f"confidenceThreshold must be in [0, 1], got "
                f"{self.threshold}"
            )

    @property
    def dynamic(self) -> bool:
        """Whether the rule is ``low_confidence_dynamic``."""
        return self.remasking == "low_confidence_dynamic"

    @property
    def packed(self) -> tuple:
        """The plan as the step program takes it, four int32: the mask
        id, steps, whether the rule is the dynamic one, the float32
        threshold's bits (the last four of :data:`SLOT_ROWS`)."""
        return (
            self.mask_id, self.steps, int(self.dynamic),
            int(np.float32(self.threshold).view(np.int32)),
        )
