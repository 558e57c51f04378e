"""The comparisons that decide ``correct``: what the timed path itself
produced, against the configuration's plain reference run once the
window has closed and the program's state is freed.  Each returns the
numbers compared, ``{name: {"value": v, "limit": l}}``; a run is
correct when every value is within its limit (``limits`` of the traffic
file, set from chip readings: PERF.md gives them)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from lobench import plain

#: A leaf whose reference gradient norm is under this share of the
#: median leaf's moves under Adam by round-off alone (a key's bias
#: under softmax): it is left out of the update comparison.
UNMOVED = 1e-3
#: What a number reads when there was nothing to compare: past any
#: limit, and still a number that every JSON reader takes.
NEVER = 1e30


def _numbers(values: dict, limits: dict) -> dict:
    return {
        name: {"value": float(v), "limit": limits[name]}
        for name, v in values.items()
    }


def _adam_state(opt_state):
    """The optax state that carries ``nu`` and ``count``."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "nu") and hasattr(node, "count"):
            return node
        if isinstance(node, (tuple, list)):
            stack.extend(node)
        elif isinstance(node, dict):
            stack.extend(node.values())
    raise RuntimeError("no Adam state in the published optimizer state")


@jax.jit
def _leaf_norms(tree, origin):
    return {
        k: jnp.sqrt(jnp.sum(jnp.square(
            tree[k].astype(jnp.float32) - origin[k]
        ))) for k in sorted(tree)
    }


@jax.jit
def _leaf_turns(tree, ref, origin):
    """Leaf by leaf, how far the change from ``origin`` points away
    from the reference's: |change - reference's change| over the
    reference's.  First order in rounding, where a norm is second."""
    return {
        k: jnp.sqrt(jnp.sum(jnp.square(
            tree[k].astype(jnp.float32) - ref[k]
        ))) / jnp.sqrt(jnp.sum(jnp.square(ref[k] - origin[k])))
        for k in sorted(tree)
    }


@jax.jit
def _leaf_root_sums(tree):
    return {
        k: jnp.sqrt(jnp.sum(tree[k].astype(jnp.float32)))
        for k in sorted(tree)
    }


def _gaps(prog: dict, ref: dict) -> dict:
    """Leaf by leaf, |program's norm - reference's norm| over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    floor = float(np.median([float(v) for v in ref.values()]))
    return {
        k: abs(float(prog[k]) - float(ref[k])) / max(float(ref[k]), floor)
        for k in ref
    }


def fit_numbers(module, cp: dict, ref: dict, prog_params, prog_nu,
                prog_loss: float, worst: list | None = None) -> dict:
    """The numbers of one epoch: the epoch's loss, the gradient as
    Adam got it (root of its summed second moment, leaf by leaf) and
    how far each leaf moved from the seed's weights.  ``worst`` collects
    the leaves that read widest, for the run's notes.  ``loss_gap`` is
    read but not compared (:func:`fit_epoch`)."""
    own = lambda tree: plain.split_qkv(  # noqa: E731
        module.from_program(tree, cp)
    )
    start = plain.split_qkv(ref["start"])
    g_ref = _leaf_root_sums(plain.split_qkv(ref["nu"]))
    g_prog = _leaf_root_sums(own(prog_nu))
    end_ref, end_prog = plain.split_qkv(ref["end"]), own(prog_params)
    d_ref = _leaf_norms(end_ref, start)
    d_prog = _leaf_norms(end_prog, start)
    turns = _leaf_turns(end_prog, end_ref, start)
    g_floor = UNMOVED * float(np.median([float(v) for v in g_ref.values()]))
    moved = {k for k, v in g_ref.items() if float(v) >= g_floor}
    g_gap, d_gap = _gaps(g_prog, g_ref), _gaps(d_prog, d_ref)
    if worst is not None:
        widest = {max(g_gap, key=g_gap.get),
                  max(moved, key=d_gap.get), max(d_gap, key=d_gap.get)}
        worst.extend({
            "leaf": k, "grad_gap": g_gap[k], "update_gap": d_gap[k],
            "grad_ref": float(g_ref[k]), "moved_ref": float(d_ref[k]),
            "moved": float(d_prog[k]), "compared": k in moved,
        } for k in sorted(widest))
    return {
        "loss_gap": abs(prog_loss - ref["loss"]) / abs(ref["loss"]),
        "grad_norm_gap": max(g_gap.values()),
        "update_norm_gap": max(d_gap[k] for k in moved),
        "update_turn_gap": float(np.median(
            [float(turns[k]) for k in moved]
        )),
    }


def window_numbers(warm, timed, losses: list, lr: float,
                   steps_expected: int) -> dict:
    """What the window's own job published, as far as it can be held
    with no reference run over its epochs: Adam's step count (exact);
    how many of its parameters, Adam moments and epoch losses are not
    finite (none may be); and the widest move of one parameter from
    the warm job's, in learning rates a step.  Adam bounds that move
    from the configuration's b1, b2 alone (PERF.md, section 4), so a
    job that published other parameters than its steps could have
    reached reads past it."""
    if timed is None:  # no artifact at all: every count is off
        return {"window_steps_gap": float(steps_expected),
                "window_nonfinite": NEVER, "window_step_size": NEVER}
    adam, adam0 = _adam_state(timed.opt_state), _adam_state(warm.opt_state)
    leaves = jax.tree_util.tree_leaves
    nonfinite = sum(
        int(np.size(a) - np.isfinite(np.asarray(a, np.float32)).sum())
        for a in leaves((timed.params, adam.mu, adam.nu)) + list(losses)
    )
    steps = steps_expected - int(np.asarray(adam0.count))
    moved = max(
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(leaves(timed.params), leaves(warm.params))
    )
    return {
        "window_steps_gap": abs(
            int(np.asarray(adam.count)) - steps_expected
        ),
        "window_nonfinite": nonfinite,
        "window_step_size": moved / (max(steps, 1) * lr),
    }


def fit_epoch(run, tokens, labels, warm, warm_loss: float, timed,
              window_losses: list, steps_expected: int | None) -> dict:
    cp = run.cp
    t0 = time.perf_counter()
    ref = run.reference.reference_epoch(
        run.seed, cp, tokens, labels, batch=run.traffic["batch_size"],
        lr=cp["learning_rate"], model_seed=cp["seed"],
    )
    run.note(reference_s=round(time.perf_counter() - t0, 2))
    worst: list = []
    values = fit_numbers(
        run.reference, cp, ref, warm.params,
        _adam_state(warm.opt_state).nu, warm_loss, worst,
    )
    # The gap of the epoch's mean loss separates nothing: sound runs
    # read up to 1.7e-3 and the int8 control down to 5e-4 (PERF.md,
    # section 4), so it could only fail sound runs.  It goes to a note.
    run.note(loss_gap_not_compared=values.pop("loss_gap"),
             loss=warm_loss, reference_loss=float(ref["loss"]))
    if steps_expected is not None:
        values.update(window_numbers(
            warm, timed, window_losses, cp["learning_rate"], steps_expected
        ))
    run.note(worst_leaves=worst)
    return _numbers(values, run.traffic["limits"])


@jax.jit
def _token_gaps(logits, tokens, first, last):
    """For each position p in [first-1, last-2] of each row, how far
    the logit of the token served at p+1 lies below the best logit."""
    nxt = jnp.roll(tokens, -1, axis=1)
    chosen = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    gap = jnp.max(logits, axis=-1) - chosen
    pos = jnp.arange(tokens.shape[1])[None, :]
    served = (pos >= first[:, None] - 1) & (pos <= last[:, None] - 2)
    return jnp.where(served, gap, 0.0), jnp.argmax(logits, axis=-1)


def pick_sample(seed: int, finished: list, rows: int) -> list:
    """``rows`` of the finished requests (all, where fewer finished),
    drawn from the seed across every client's, the longest among
    them."""
    if not finished:
        return []
    longest = max(
        range(len(finished)),
        key=lambda i: len(finished[i]["prompt"]) + len(finished[i]["tokens"]),
    )
    order = np.random.default_rng(seed).permutation(len(finished))
    picked = [longest] + [int(i) for i in order if i != longest]
    return [finished[i] for i in picked[:rows]]


def sample_rows(sample: list, width: int, rows: int):
    tokens = np.zeros((rows, width), np.int32)
    first = np.ones(rows, np.int32)
    last = np.ones(rows, np.int32)  # first == last: nothing served
    for r, req in enumerate(sample):
        row = list(req["prompt"]) + list(req["tokens"])
        tokens[r, : len(row)] = row
        first[r], last[r] = len(req["prompt"]), len(row)
    return tokens, first, last


def served_gap(reference, seed: int, cp: dict, tokens, first, last,
               quant=None, of_control: bool = False) -> float:
    """Widest gap, under the reference, of the served tokens; with
    ``of_control`` of the tokens that ``quant`` precision puts first at
    the same positions instead."""
    logits = reference.reference_logits(seed, cp, tokens)
    if of_control:
        low = reference.reference_logits(seed, cp, tokens, quant=quant)
        _, best = _token_gaps(low, jnp.asarray(tokens), first, last)
        # the control's choice at p stands where the served token p+1 does
        tokens = np.asarray(jnp.roll(best, 1, axis=1))
    gaps, _ = _token_gaps(logits, jnp.asarray(tokens), first, last)
    return float(jnp.max(gaps))


def served_tokens(run, finished: list) -> dict:
    traffic = run.traffic
    sample = pick_sample(run.seed, finished, traffic["sample_requests"])
    if not sample:
        return _numbers({"logit_gap": NEVER}, traffic["limits"])
    tokens, first, last = sample_rows(
        sample, traffic["kv_bucket"], traffic["sample_requests"]
    )
    run.note(sample_requests=len(sample),
             sample_tokens=int((last - first).sum()))
    run.sample = (tokens, first, last)  # benchmarks/controls.py reads on
    t0 = time.perf_counter()
    gap = served_gap(run.reference, run.seed, run.cp, tokens, first, last)
    run.note(reference_s=round(time.perf_counter() - t0, 2))
    return _numbers({"logit_gap": gap}, traffic["limits"])
