"""The comparison that decides ``correct`` for generation by diffusion
over blocks: what the timed engine served, against the plain reference
at the very states the engine decided in.

A served token comes with ``s``, the denoising step it was fixed at.
From the tokens and their steps the states a block went through are
rebuilt: at step ``s`` the block held the tokens fixed before ``s`` and
the mask id elsewhere.  Every state of every block of a request goes
through the reference in ONE forward over ``[noisy_0; ..; noisy_(T-1);
clean]``: ``clean`` is the final sequence, ``noisy_s`` a copy whose
generated positions are in their step-``s`` state; clean block ``b``
sees clean blocks ``<= b`` (what the commit forwards stored), noisy
block ``b`` sees clean blocks ``< b`` and itself.  Position ids repeat
in every copy.

Compared:

- ``logit_gap``: the widest gap by which a served token's logit lies
  below the reference's best logit at the state it was fixed in;
- ``order_faults``: served tokens whose ``s`` cannot be (none at all:
  a position sent out still masked; a step ``>= T``; a block whose step
  fixed another number than the strategy's count); limit 0.

Read and not compared (a note of its own on every run):
``confidence_gap``, the widest gap by which the reference's
log-confidence at a position the engine fixed lies below the
reference's n-th best among the positions masked at that state, n the
step's count (0 where the position is among the reference's own n).
With seeded weights the confidences of a block's positions lie within
a tenth of one another, so the number is bounded by that spread
whatever fixed the positions: sound runs read 0.046 - 0.067 and the
int8 control 0.085 - 0.097 (PERF.md, section 4): it separates nothing,
and could only fail sound runs.  With trained weights it would.

A last block that ``maxNewTokens`` cut is left out: its tail was
denoised with it and never sent, so its states are not known here.

With ``quant`` the control stands in the engine's place: the reference
in that precision chooses positions and tokens at the same states, and
its choices are read against the float32 reference."""

from __future__ import annotations

import time

import numpy as np

from lobench import compare

NEVER = compare.NEVER


def transfer_count(block: int, steps: int, step: int) -> int:
    return block // steps + (1 if step < block % steps else 0)


def request_states(req: dict, block: int, steps: int, mask_id: int,
                   width: int):
    """(tokens (steps + 1, width), whole): row ``s < steps`` the
    sequence with its generated positions in their step-``s`` state,
    the last row the final sequence, zero-padded to ``width``;
    ``whole`` the length kept (whole blocks only)."""
    t0 = len(req["prompt"])
    seq = np.asarray(list(req["prompt"]) + list(req["tokens"]), np.int32)
    at = np.full(len(seq), -1, np.int32)
    at[t0:] = req["steps"]
    whole = len(seq) // block * block
    rows = np.zeros((steps + 1, width), np.int32)
    rows[:, :whole] = seq[:whole]
    for s in range(steps):
        rows[s, :whole][at[:whole] >= s] = mask_id
    return rows, whole


def layout(rows, block: int):
    """One request's copies side by side: (tokens (C*W,), position ids,
    mask (C*W, C*W)) for ``rows`` (C, W), the last copy clean."""
    copies, width = rows.shape
    pos = np.tile(np.arange(width), copies)
    copy = np.repeat(np.arange(copies), width)
    blk = pos // block
    clean_q = copy[:, None] == copies - 1
    clean_k = copy[None, :] == copies - 1
    mask = np.where(
        clean_q, clean_k & (blk[None, :] <= blk[:, None]),
        (clean_k & (blk[None, :] < blk[:, None]))
        | ((copy[None, :] == copy[:, None])
           & (blk[None, :] == blk[:, None])),
    )
    return rows.reshape(-1), pos, mask


def _choices(req, whole: int, block: int, steps: int):
    """(step, block start, fixed positions, masked positions) of every
    denoising state the request's whole blocks went through, and the
    number of order faults."""
    t0 = len(req["prompt"])
    at = np.full(whole, -1, np.int64)
    n = min(whole, t0 + len(req["steps"])) - t0
    at[t0: t0 + n] = req["steps"][:n]
    states = []
    faults = int(np.sum((at[t0:] >= steps) | (at[t0:] < 0)))
    for start in range(t0 // block * block, whole, block):
        lanes = np.arange(start, start + block)
        lanes = lanes[lanes >= t0]
        for s in range(steps):
            masked = lanes[at[lanes] >= s]
            if not len(masked):
                break
            fixed = lanes[at[lanes] == s]
            states.append((s, start, fixed, masked))
    return states, faults


def numbers(reference, seed: int, cp: dict, traffic: dict, sample: list,
            quant=None) -> dict:
    block, steps = cp["block_length"], traffic["denoising_steps"]
    mask_id, width = cp["mask_token_id"], traffic["kv_bucket"]
    static = traffic["remasking"] == "low_confidence_static"
    rows_all, wholes = zip(*(
        request_states(r, block, steps, mask_id, width) for r in sample
    ))
    flat = [layout(rows, block) for rows in rows_all]
    tokens = np.stack([f[0] for f in flat])
    pos = np.stack([f[1] for f in flat])
    mask = np.stack([f[2] for f in flat])
    # the probe: at every noisy position the token that was served there
    probe = np.stack([
        np.tile(rows[-1], rows.shape[0]) for rows in rows_all
    ])
    ref = reference.reference_scores(seed, cp, tokens, pos, mask, probe)
    if quant is not None:
        # The control chooses at the same states; what it chose is then
        # read under the float32 reference like a served token.
        low = reference.reference_scores(
            seed, cp, tokens, pos, mask, probe, quant=quant
        )
        ref = reference.reference_scores(
            seed, cp, tokens, pos, mask, np.asarray(low["best"])
        )
        low_conf = np.asarray(low["best_logit"]) - np.asarray(low["lse"])
    ref = {k: np.asarray(v) for k, v in ref.items()}
    conf = ref["best_logit"] - ref["lse"]
    logit_gap, conf_gap, faults = 0.0, 0.0, 0
    for r, (req, whole) in enumerate(zip(sample, wholes)):
        states, bad = _choices(req, whole, block, steps)
        faults += bad
        for s, start, fixed, masked in states:
            count = min(transfer_count(block, steps, s), len(masked))
            at = s * width  # the noisy copy of step s
            if quant is not None:  # the control's own positions
                order = np.argsort(-low_conf[r, at + masked], kind="stable")
                fixed = masked[order[:count]]
            elif (len(fixed) != count) if static else (len(fixed) < count):
                faults += 1
            if not len(fixed):
                continue
            logit_gap = max(logit_gap, float(np.max(
                ref["best_logit"][r, at + fixed]
                - ref["probe_logit"][r, at + fixed]
            )))
            nth = np.sort(conf[r, at + masked])[::-1][count - 1]
            conf_gap = max(conf_gap, float(np.max(
                np.maximum(0.0, nth - conf[r, at + fixed])
            )))
    return {"logit_gap": logit_gap, "confidence_gap": conf_gap,
            "order_faults": faults}


def served_blocks(run, finished: list) -> dict:
    traffic = run.traffic
    sample = compare.pick_sample(
        run.seed, finished, traffic["sample_requests"]
    )
    if not sample:
        return compare._numbers(
            {name: NEVER for name in traffic["limits"]}, traffic["limits"]
        )
    run.note(sample_requests=len(sample),
             sample_tokens=sum(len(r["tokens"]) for r in sample))
    run.sample = sample  # benchmarks/controls_blocks.py reads on
    t0 = time.perf_counter()
    values = numbers(run.reference, run.seed, run.cp, traffic, sample)
    run.note(reference_s=round(time.perf_counter() - t0, 2),
             confidence_gap_not_compared=values.pop("confidence_gap"))
    return compare._numbers(values, traffic["limits"])
