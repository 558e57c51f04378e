"""Traffic kind ``closed_loop_block_generate``: the closed loop of
:mod:`lobench.kinds.closed_loop_generate` (its shapes and plans from the
traffic file alone, its :class:`Gate`, :class:`Client` and sampler) for
a model that generates by diffusion over blocks.  Every request asks
for the same ``max_new_tokens`` with the traffic file's
``denoising_steps`` and ``remasking``; a block's tokens reach the
client together, on its commit, each with ``s``, the denoising step it
was fixed at, which ``correct`` replays by
(:mod:`lobench.compare_blocks`).  The weights are made in bfloat16
(:mod:`lobench.weights_bf16`); the engine's own counters over the
window (slot-steps by phase, positions, tokens fixed, experts reached,
keys attended) go into the record for the per-layer readers."""

from __future__ import annotations

import threading
import time

import numpy as np

from lobench import compare_blocks, rest, stats, trace, weights_bf16
from lobench.kinds.closed_loop_generate import (
    MODEL,
    Client,
    EngineSampler,
    Gate,
    client_plans,
)


def prompt_ids(seed: int, client: int, vocab: int, mask_id: int):
    """Client ``client``'s endless stream of prompts from ``--seed``:
    ids in 1..vocab-1 without the mask id (0 is the pad id)."""
    rng = np.random.default_rng([int(seed), client])

    def draw(length: int) -> list:
        ids = rng.integers(1, vocab - 1, length)
        return (ids + (ids >= mask_id)).tolist()

    return draw


class BlockCalls:
    """What a :class:`Client` sees as its context: ``serve.generate``
    with the cell's block parameters added, keeping each request's
    denoising steps (one list a request, in the order sent)."""

    def __init__(self, ctx, traffic: dict):
        self.serve = self
        self._ctx, self._traffic = ctx, traffic
        self.steps: list = []

    def generate(self, model, prompts, **kwargs):
        steps: list = []
        self.steps.append(steps)
        for event, doc in self._ctx.serve.generate(
            model, prompts,
            denoising_steps=self._traffic["denoising_steps"],
            remasking=self._traffic["remasking"], **kwargs,
        ):
            if event == "token":
                steps.append(int(doc["s"]))
            yield event, doc


def engine_counts(server) -> dict:
    """The decode engine's cumulative counters for the model, flat."""
    model = server.serving.decode.stats()["models"].get(MODEL, {})
    phases = model.get("blockSteps", {})
    return {
        "steps": model.get("steps", 0),
        "positions": model.get("positions", 0),
        "keys": model.get("keysAttended", 0),
        "fixed": model.get("tokensFixed", 0),
        "experts_hit": model.get("expertsHit", 0),
        "prefill": phases.get("prefill", 0),
        "denoise": phases.get("denoise", 0),
        "commit": phases.get("commit", 0),
    }


def run(run) -> dict:
    traffic, config, cp = run.traffic, run.config, run.cp
    from learningorchestra_tpu.toolkit import registry

    try:
        registry.resolve(config["module_path"], config["class"])
    except Exception as exc:  # noqa: BLE001 — a program without the model
        raise SystemExit(
            f"this program cannot run {config['name']}: {exc}"
        ) from None
    server, ctx = rest.boot(run.scratch, config.get("server"))
    run.server = server
    run.lap("boot")
    # -- set-up: the artifact, resident, and every client through one ----
    weights_bf16.submit(ctx, MODEL, run.config_path, run.seed)
    run.lap("weights_job")
    ctx.serve.load(MODEL)
    run.lap("serve_load")
    stop = threading.Event()
    gate = Gate(traffic["clients"])
    # every request generates the same number of tokens
    shapes = {**traffic, "output": {
        "median": traffic["max_new_tokens"], "sigma": 0.0,
    }}
    calls = [BlockCalls(ctx, traffic) for _ in range(traffic["clients"])]
    clients = [
        Client(calls[i], plan,
               prompt_ids(run.seed, i, cp["vocab_size"],
                          cp["mask_token_id"]),
               gate, stop)
        for i, plan in enumerate(client_plans(shapes))
    ]
    for c in clients:
        c.start()
    if not gate.all_done.wait(1100.0):
        raise RuntimeError("a client's first request never ended")
    run.lap("first_requests")
    sampler = EngineSampler(server)
    # -- the window -------------------------------------------------------
    run.open_window()
    w0 = time.perf_counter()
    gate.opened.set()
    first = engine_counts(server)
    sampler.start()
    with run.maybe_trace() as cap:
        time.sleep(min(run.seconds, traffic["trace_seconds"])
                   if run.traced else 0.0)
    remaining = w0 + run.seconds - time.perf_counter()
    if remaining > 0:
        time.sleep(remaining)
    w1 = time.perf_counter()
    last = engine_counts(server)
    run.close_window()
    stop.set()
    sampler.halt.set()
    for c in clients:
        c.join(120.0)  # the request in flight: late is late, not wrong
    hung = sum(c.is_alive() for c in clients)
    # -- reduce -----------------------------------------------------------
    for c, call in zip(clients, calls):
        for req, steps in zip(c.requests, call.steps):
            req["steps"] = steps
    reqs = [r for c in clients for r in c.requests]
    in_window = [r for r in reqs if w0 <= r["sent"] < w1]
    arrivals = [t for r in reqs for t in r["arrivals"] if w0 <= t <= w1]
    gaps = [
        (b - a) * 1e3 for r in reqs
        for a, b in zip(r["arrivals"], r["arrivals"][1:]) if w0 <= b <= w1
    ]
    ttfts = [
        (r["arrivals"][0] - r["sent"]) * 1e3
        for r in in_window if r["arrivals"]
    ]
    failed = [r for r in in_window if r["error"] is not None]
    grown = {k: last[k] - first[k] for k in first}
    record = {
        "attempted": len(in_window),
        "failed": len(failed) + hung,
        "end_to_end": {
            "gen_tok_s": len(arrivals) / (w1 - w0),
            "itl_p95_ms": stats.percentile(gaps, 95) if gaps else None,
        },
        "window": {
            "seconds": w1 - w0, "tokens": len(arrivals), "gaps": len(gaps),
            "ttfts": ttfts,
            "engine_steps": grown["steps"],
            "engine": grown,
            "live_samples": [s["live"] for s in sampler.samples],
            "pools": max([s["pools"] for s in sampler.samples] or [0]),
            "clients": traffic["clients"],
        },
    }
    if failed:
        run.note(first_error=failed[0]["error"])
    if cap is not None:
        record["trace"] = trace.read(cap)
    run.note(requests=len(in_window), tokens=len(arrivals),
             ttft_n=len(ttfts), gaps_n=len(gaps), engine=grown,
             sampler_late_ms_max=max(
                 [s["late_ms"] for s in sampler.samples] or [0.0]),
             gap_ms={q: stats.percentile(gaps, q)
                     for q in (5, 50, 75, 90, 95, 99, 100)}
             if gaps else None)
    # -- correct: the served blocks against the reference's states --------
    finished = [
        r for r in reqs if r["error"] is None and r["ended"] is not None
        and r["ended"] >= w0 and len(r["steps"]) == len(r["tokens"])
    ]
    try:
        ctx.serve.unload(MODEL)
    except Exception:  # noqa: BLE001 — shutdown frees it all the same
        pass
    run.free_program()
    record["compared"] = compare_blocks.served_blocks(run, finished)
    return record
