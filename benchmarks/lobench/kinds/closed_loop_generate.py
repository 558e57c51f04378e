"""Traffic kind ``closed_loop_generate``: ``clients`` callers that each
wait for their reply (a worker pool of agents), every one sending
``stream=true`` ``/serve/<model>/generate`` requests back to back
through the stock HTTP client and reading the SSE tokens as they come.

The traffic file alone fixes the work: the set of (prompt length,
output length) pairs (drawn once from ``shape_seed``, from the length
distribution the file states with its source) and which of them each
client sends in which sequence, its first request included: the pairs
in the order drawn, dealt round-robin.  ``--seed`` draws the token ids
(and the weights) and nothing else, so every run sends the same sizes
at the same points of the stream.  The clients start in set-up and
each completes one request before the window opens: the window is cut
from a steady, desynchronised stream.  The client whose first request
ends last holds its next one until the window is open (:class:`Gate`),
so that request is inside the window in every run and not by a race of
two threads.  When the window closes, each
client finishes the request it has in flight and stops."""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from lobench import compare, rest, stats, trace

MODEL = "lm"


def _length(rng, spec: dict) -> int:
    """One log-normal length: ``sigma`` about the ``median``, or about
    the median that gives the stated ``mean``."""
    mu = math.log(spec["median"]) if "median" in spec \
        else math.log(spec["mean"]) - spec["sigma"] ** 2 / 2
    return int(round(rng.lognormal(mu, spec["sigma"])))


def draw_shapes(traffic: dict) -> list:
    """``shapes`` (prompt, output) length pairs, the same for every
    seed.  A pair outside the file's bounds (``min`` / ``max`` of a
    length, ``above`` / ``at_most`` of their sum) is drawn again: the
    mix is the stated distribution's share inside the bounds, with no
    mass piled up at a clipping edge."""
    rng = np.random.default_rng(traffic["shape_seed"])
    total = traffic.get("total", {})

    def inside(p, o):
        return all(
            spec.get("min", 1) <= n <= spec.get("max", n)
            for n, spec in ((p, traffic["prompt"]), (o, traffic["output"]))
        ) and total.get("above", 0) < p + o <= total.get("at_most", p + o)

    out = []
    while len(out) < traffic["shapes"]:
        pair = (_length(rng, traffic["prompt"]),
                _length(rng, traffic["output"]))
        if inside(*pair):
            out.append(pair)
    return out


def client_plans(traffic: dict) -> list:
    """For each client, the endless cycle of (prompt, output) sizes it
    sends: the shapes in the order the traffic file's own seed drew
    them, dealt round-robin.  No ``--seed`` enters here."""
    plans = [[] for _ in range(traffic["clients"])]
    for i, shape in enumerate(draw_shapes(traffic)):
        plans[i % traffic["clients"]].append(shape)
    return plans


def prompt_ids(seed: int, client: int, vocab: int):
    """Client ``client``'s endless stream of prompts from ``--seed``:
    call it with a length, get that many token ids in 1..vocab-1 (0 is
    the pad id).  Every request draws afresh, so no two prompts of a
    run are alike."""
    rng = np.random.default_rng([int(seed), client])
    return lambda length: rng.integers(1, vocab, length).tolist()


class Gate:
    """Opens the window when every client has ended its first request.
    The last of them waits here until the window is open (a few
    microseconds), so its next request is sent inside it."""

    def __init__(self, clients: int):
        self.left = clients
        self.lock = threading.Lock()
        self.all_done = threading.Event()
        self.opened = threading.Event()

    def first_done(self) -> None:
        with self.lock:
            self.left -= 1
            last = self.left == 0
        if last:
            self.all_done.set()
            self.opened.wait()


class Client(threading.Thread):
    """One caller: sends its plan's next request when the last reply
    has ended.  Records, on the process clock, when each request went
    out and when each token arrived."""

    def __init__(self, ctx, plan: list, draw, gate: Gate,
                 stop: threading.Event):
        super().__init__(daemon=True)
        self.ctx, self.plan, self.stop_flag = ctx, plan, stop
        self.draw = draw  # prompt length -> token ids, from --seed
        self.gate = gate
        self.requests: list = []

    def run(self) -> None:
        i = 0
        while not self.stop_flag.is_set():
            p_len, max_new = self.plan[i % len(self.plan)]
            i += 1
            rec = {"prompt": self.draw(p_len), "max_new": max_new,
                   "sent": time.perf_counter(), "arrivals": [],
                   "tokens": [], "error": None, "ended": None}
            self.requests.append(rec)
            try:
                for event, doc in self.ctx.serve.generate(
                    MODEL, [rec["prompt"]],
                    max_new_tokens=max_new, stream=True,
                    timeout=600.0,
                ):
                    if event == "token":
                        rec["arrivals"].append(time.perf_counter())
                        rec["tokens"].append(int(doc["t"]))
                    elif event in ("error", "aborted"):
                        rec["error"] = f"{event}: {doc}"
            except Exception as exc:  # noqa: BLE001 — counted as failed
                rec["error"] = repr(exc)
                time.sleep(0.05)  # a refusing server must not spin us
            rec["ended"] = time.perf_counter()
            if rec["error"] is None and \
                    len(rec["tokens"]) != max_new:
                rec["error"] = f"{len(rec['tokens'])} tokens of {max_new}"
            if i == 1:
                self.gate.first_done()


class EngineSampler(threading.Thread):
    """``DecodeEngine.stats()`` every ``period`` seconds, and how late
    each wake-up came (the host, or the interpreter lock, was busy)."""

    def __init__(self, server, period: float = 0.25):
        super().__init__(daemon=True)
        self.server, self.period = server, period
        self.samples: list = []
        self.halt = threading.Event()

    def read(self) -> dict:
        model = self.server.serving.decode.stats()["models"].get(MODEL, {})
        return {
            "t": time.perf_counter(), "steps": model.get("steps", 0),
            "live": sum(p["live"] for p in model.get("pools", [])),
            "pools": len(model.get("pools", [])),
        }

    def run(self) -> None:
        due = time.perf_counter() + self.period
        while not self.halt.wait(max(0.0, due - time.perf_counter())):
            sample = self.read()
            # how late this thread woke: the host (or the GIL) was busy
            sample["late_ms"] = (sample["t"] - due) * 1e3
            self.samples.append(sample)
            # after a late wake-up, no burst of samples to catch up
            due = max(due + self.period, sample["t"])


def run(run) -> dict:
    traffic, config, cp = run.traffic, run.config, run.cp
    server, ctx = rest.boot(run.scratch, config.get("server"))
    run.server = server
    run.lap("boot")
    # -- set-up: the artifact, resident, and every client through one ----
    rest.submit_weights(ctx, MODEL, run.config_path, run.seed, "estimator")
    run.lap("weights_job")
    ctx.serve.load(MODEL)
    run.lap("serve_load")
    stop = threading.Event()
    gate = Gate(traffic["clients"])
    clients = [
        Client(ctx, plan, prompt_ids(run.seed, i, cp["vocab_size"]), gate,
               stop)
        for i, plan in enumerate(client_plans(traffic))
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
    first = sampler.read()
    sampler.start()
    with run.maybe_trace() as cap:
        time.sleep(min(run.seconds, traffic["trace_seconds"])
                   if run.traced else 0.0)
    remaining = w0 + run.seconds - time.perf_counter()
    if remaining > 0:
        time.sleep(remaining)
    w1 = time.perf_counter()
    last = sampler.read()
    run.close_window()
    stop.set()
    sampler.halt.set()
    for c in clients:
        c.join(120.0)  # the request in flight: late is late, not wrong
    hung = sum(c.is_alive() for c in clients)
    # -- reduce -----------------------------------------------------------
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
    step_keys = _slot_step_keys(reqs, w0, w1)
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
            "prefill_ms_per_tok": [
                (r["arrivals"][0] - r["sent"]) * 1e3 / len(r["prompt"])
                for r in in_window if r["arrivals"]
            ],
            "engine_steps": last["steps"] - first["steps"],
            "live_samples": [s["live"] for s in sampler.samples],
            "pools": max([s["pools"] for s in sampler.samples] or [0]),
            # every slot-step inside the window, prompt or output, of
            # the requests this side saw: for the step's share of peak
            "processed_tokens": len(step_keys),
            "mean_keys": float(np.mean(step_keys)) if step_keys else 0.0,
            "clients": traffic["clients"],
        },
    }
    if failed:
        run.note(first_error=failed[0]["error"])
    if cap is not None:
        record["trace"] = trace.read(cap)
    run.note(window_requests=[
        [round(r["sent"] - w0, 3), len(r["prompt"]), r["max_new"],
         round((r["arrivals"][0] - r["sent"]) * 1e3, 1)
         if r["arrivals"] else None]
        for r in sorted(in_window, key=lambda r: r["sent"])
    ])  # sent (s into the window), prompt, output, TTFT ms
    run.note(requests=len(in_window), tokens=len(arrivals),
             ttft_n=len(ttfts), gaps_n=len(gaps),
             sampler_late_ms_max=max(
                 [s["late_ms"] for s in sampler.samples] or [0.0]),
             gap_ms={q: stats.percentile(gaps, q)
                     for q in (5, 50, 90, 95, 99, 100)} if gaps else None)
    # -- correct: served tokens against the reference's full forward -----
    finished = [
        r for r in reqs if r["error"] is None and r["ended"] is not None
        and r["ended"] >= w0
    ]
    try:
        ctx.serve.unload(MODEL)
    except Exception:  # noqa: BLE001 — shutdown frees it all the same
        pass
    run.free_program()
    record["compared"] = compare.served_tokens(run, finished)
    return record


def _slot_steps(req: dict):
    """(time, keys attended) of every engine step a request took, as the
    client can place them: the prompt's steps spread evenly from send to
    first token, then one step a token."""
    if not req["arrivals"]:
        return []
    n_prompt = len(req["prompt"])
    first = req["arrivals"][0]
    out = [
        (req["sent"] + (first - req["sent"]) * (i + 1) / n_prompt, i + 1)
        for i in range(n_prompt - 1)
    ]
    out += [
        (t, n_prompt + j) for j, t in enumerate(req["arrivals"])
    ]
    return out


def _slot_step_keys(reqs: list, w0: float, w1: float) -> list:
    """Keys attended by every slot-step inside the window."""
    return [k for r in reqs for t, k in _slot_steps(r) if w0 <= t <= w1]
