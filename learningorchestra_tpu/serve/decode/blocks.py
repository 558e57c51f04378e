"""Generation by diffusion over blocks: the host's part.

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
"""

from __future__ import annotations

import numpy as np

REMASKING = ("low_confidence_static", "low_confidence_dynamic")


def transfer_count(block: int, steps: int, step: int) -> int:
    """How many positions denoising step ``step`` of ``steps`` fixes at
    the least: ``block / steps``, the remainder going to the first
    steps, so that ``steps`` steps fix a whole block."""
    return block // steps + (1 if step < block % steps else 0)


def choose(conf, masked, count: int, remasking: str,
           threshold: float):
    """Which masked positions to fix now: a bool vector.  Ties go to
    the earlier position."""
    conf = np.where(masked, np.asarray(conf, np.float32), -np.inf)
    count = min(int(count), int(masked.sum()))
    if remasking == "low_confidence_dynamic":
        high = masked & (conf > threshold)
        if high.sum() >= count:
            return high
    pick = np.zeros(len(conf), bool)
    pick[np.argsort(-conf, kind="stable")[:count]] = True
    return pick


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


class BlockState:
    """One sequence's current block: its tokens (the mask id where not
    yet fixed), which positions are still masked, the denoising step
    each was fixed at (-1: never, a prompt's token or one still
    masked), and how many denoising forwards it has had.  ``masked`` is
    its own vector and not ``tokens == mask id``: a model may well
    propose the mask id itself, and a position fixed to it is fixed."""

    __slots__ = ("plan", "tokens", "masked", "fixed_at", "step")

    def __init__(self, plan: BlockPlan, given):
        """``given``: the block's tokens that are known already (a
        prompt's), at most ``block`` of them."""
        self.plan = plan
        self.tokens = np.full(plan.block, plan.mask_id, np.int32)
        self.tokens[: len(given)] = given
        self.masked = np.arange(plan.block) >= len(given)
        self.fixed_at = np.full(plan.block, -1, np.int32)
        self.step = 0

    @property
    def final(self) -> bool:
        """No mask left: the forward over these tokens is the block's
        commit (or a prompt block's prefill)."""
        return not self.masked.any()

    def denoise(self, x0, conf) -> int:
        """Apply the strategy to one denoising forward's proposals;
        returns how many positions it fixed."""
        plan = self.plan
        pick = choose(
            conf, self.masked,
            transfer_count(plan.block, plan.steps, self.step),
            plan.remasking, plan.threshold,
        )
        self.tokens[pick] = np.asarray(x0, np.int32)[pick]
        self.fixed_at[pick] = self.step
        self.masked = self.masked & ~pick
        self.step += 1
        return int(pick.sum())
