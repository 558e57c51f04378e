"""DecodeEngine — resident continuous-batching LM serving.

The serve/ path was one-shot ``apply`` only; this engine opens the
streaming-generation workload (ROADMAP item 1): per-model decode
workers step KV page pools (``pages.py``) with one jitted step per
(arch, slot-bucket, kv-bucket), admit newly-arrived prompts into
in-flight steps (continuous batching — no barrier batching), emit
tokens over SSE, and tear a stream down cooperatively through its
PR-14 CancelToken at the next step boundary.

Fleet integration: when a model has a live replica set, each new
stream is routed to a replica by the set's P2C router over live decode
slot counts, and every step's device time lands in the per-model
attributed device-time ledger — the same signal the autoscaler's
``LO_TPU_FLEET_UP_DEVICE_FRAC`` threshold reads.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from learningorchestra_tpu.concurrency_rt import make_condition, make_lock
from learningorchestra_tpu.log import get_logger, kv
from learningorchestra_tpu.obs import flight as obs_flight
from learningorchestra_tpu.obs import tracing as obs_tracing
from learningorchestra_tpu.obs.metrics import get_registry
from learningorchestra_tpu.serve.batcher import QueueFull
from learningorchestra_tpu.serve.bucketing import bucket_for
from learningorchestra_tpu.serve.decode import blocks
from learningorchestra_tpu.serve.decode.blocks import BlockPlan
from learningorchestra_tpu.serve.decode.pages import (
    PagePool,
    build_step,
    chunk_width,
    first_pages,
    keyed_by_length,
    step_donates,
    step_width,
)
from learningorchestra_tpu.serve.decode.streams import DecodeStream
from learningorchestra_tpu.serve.registry import ServeError

logger = get_logger("decode")

#: Ceiling on a non-stream request's wait for its streams to finish.
_NONSTREAM_TIMEOUT_S = 300.0

#: What one turn of the worker's loop is made of, in order: ``admit``
#: (the loop top: pending streams, admission, the abort sweep),
#: ``dispatch`` (the step's host arrays and the call that enqueues it),
#: ``sync`` (reading a step's tokens, and finished rows, back),
#: ``emit`` (tokens to their streams, histograms, the devtime ledger);
#: ``wait`` is the worker parked with nothing live, outside any turn.
#: A turn enqueues step k and only then reads and emits step k-1, so
#: its ``sync`` and ``emit`` run beside the chip.
_PHASES = ("admit", "dispatch", "sync", "emit", "wait")

#: A turn longer than this leaves a ``slow_step`` flight event with its
#: phase split: an untraced run that stalls says where the loop stood.
_SLOW_STEP_S = 0.5


class _DecodeHists:
    """Identity-cached handles on the decode metric families — the
    ``_PredictHist`` rebind idiom (serve/service.py): a
    ``reset_registry()`` mid-life re-homes the series, the steady
    state pays one identity check.  TTFT and inter-token latency are
    the two decode SLO primitives; the token counter feeds throughput
    rollups."""

    __slots__ = ("_reg", "_ttft", "_itl", "_tokens", "_bound")

    def __init__(self):
        self._reg = None
        self._ttft = None
        self._itl = None
        self._tokens = None
        self._bound: dict = {}

    def _bind(self, model: str):
        reg = get_registry()
        if reg is not self._reg:
            self._ttft = reg.histogram(
                "lo_serving_decode_ttft_seconds",
                "Time to first generated token per streamed decode "
                "(admission wait + prefill steps + first step).",
                labels=("model",),
            )
            self._itl = reg.histogram(
                "lo_serving_decode_itl_seconds",
                "Inter-token latency between consecutive streamed "
                "decode tokens.",
                labels=("model",),
            )
            self._tokens = reg.counter(
                "lo_serving_decode_tokens_total",
                "Generated tokens per served model (all transports).",
                labels=("model",),
            )
            self._bound = {}
            self._reg = reg
        bound = self._bound.get(model)
        if bound is None:
            if len(self._bound) >= 256:
                self._bound.clear()
            bound = self._bound[model] = (
                self._ttft.bind(model=model),
                self._itl.bind(model=model),
            )
        return bound

    def ttft(self, dt_s: float, model: str) -> None:
        self._bind(model)[0].observe(dt_s)

    def itl(self, dt_s: float, model: str) -> None:
        self._bind(model)[1].observe(dt_s)

    def tokens(self, n: int, model: str) -> None:
        self._bind(model)
        self._tokens.inc(n, model=model)


_decode_hists = _DecodeHists()


class _ModelDecoder:
    """One model's decode worker: admission queue, page pools, step
    loop.  All pool state is owned by the worker thread; the condition
    variable hands streams in and wakes the worker for aborts."""

    def __init__(self, engine: "DecodeEngine", name: str):
        self.engine = engine
        self.name = name
        self.cfg = engine.cfg
        self._cv = make_condition("_ModelDecoder._cv")
        self._pending: deque = deque()
        # (replica_idx | None, kv | None) → PagePool; kv None where the
        # model's cache has no length axis: one pool for every length
        self._pools: dict = {}
        # (pools keyed by a KV bucket?, prompt positions a step may
        # take): asked of the model's own cache, once
        self._layout: tuple | None = None
        self._streams: dict = {}  # stream_id → DecodeStream (active)
        # (S, kv, chunk) → (step fn, cache shapes)
        self._step_state: dict = {}
        self._thread: threading.Thread | None = None
        self._closed = False
        self.steps = 0
        # Steps after which the cache that went in was gone: the step
        # updated the pool's pages in place (jax leaves a donated
        # argument alive when XLA could not alias it).
        self.steps_in_place = 0
        # Steps enqueued while the pool's step before them was still
        # unread: the host's turn for that one ran beside the chip.
        self.steps_ahead = 0
        # Written by the worker thread alone, read by stats().
        self.phases = obs_tracing.Phases("decode", _PHASES)
        # A slot-step is a prompt step while prompt lies beyond its
        # position (it moves past ``prompt_positions`` prompt tokens
        # without producing: one in a one-token program, up to the
        # chunk's width in the chunk program, a prompt's ``t0 - 1`` in
        # all; the chunk that reaches a prompt's end also produces the
        # request's first token), else an output step (one token).
        self.prompt_steps = 0
        self.output_steps = 0
        self.prompt_positions = 0
        self.chunk_steps = 0  # steps that ran the prompt-chunk program
        self.keys_attended = 0
        self.admitted = 0
        self.admit_wait_s = 0.0
        # One turn's share of the three counters above and what it
        # stepped: the ``lo:decode.step`` annotation's metadata.
        self._turn = {"prompt": 0, "output": 0, "keys": 0, "slots": 0,
                      "kv": 0, "inplace": 0, "ahead": 0, "chunk": 0,
                      "prompt_positions": 0,
                      "kv_bytes_per_token": 0, "pools": 0,
                      "state_bytes_per_slot": 0, "state_resets": 0}
        # Slot-steps that began a recurrent state from zero (a request's
        # first, in a pool of states).
        self.state_resets = 0
        # Generation by diffusion over blocks: slot-steps by phase
        # (whole prompt blocks prefilled, denoising forwards, commits),
        # positions processed, tokens the denoising forwards fixed.
        self.block_steps = {"prefill": 0, "denoise": 0, "commit": 0}
        self.positions = 0
        self.tokens_fixed = 0
        self._block_turn = {"positions": 0, "fixed": 0, "denoise": 0,
                            "commit": 0, "prefill": 0}
        # What the step program counted of its routed experts, of
        # either kind of pool (``pages._moe_stats``): distinct held
        # experts a layer, summed over layers and steps; the busiest
        # expert's rows in any one step; (token, choice) pairs that
        # reached a held expert.  They are of the step a turn READ,
        # which it dispatched the turn before.
        self.experts_hit = 0
        self.expert_load_max = 0
        self.expert_rows = 0
        self._moe_turn = {"experts_hit": 0, "load_max": 0,
                          "expert_rows": 0}

    # -- submission (any thread) --------------------------------------------

    def submit(self, stream: DecodeStream) -> None:
        with self._cv:
            if self._closed:
                raise ServeError(
                    f"decode for {self.name!r} is shut down"
                )
            # every stream not yet finished, seated or still pending
            # (a pending one is in ``_streams`` too: counted once)
            active = len(self._streams)
            if active >= self.cfg.max_streams:
                obs_flight.record(
                    "decode", "queue_full",
                    model=self.name, stream=stream.stream_id,
                    active=active,
                )
                raise QueueFull(
                    f"decode for {self.name!r} at max_streams="
                    f"{self.cfg.max_streams}"
                )
            obs_flight.record(
                "decode", "submit",
                model=self.name, stream=stream.stream_id,
                total=stream.total,
            )
            self._pending.append(stream)
            self._streams[stream.stream_id] = stream
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name=f"decode-{self.name}",
                    daemon=True,
                )
                self._thread.start()
            self._cv.notify_all()

    def abort(self, stream_id: str, reason: str) -> bool:
        with self._cv:
            stream = self._streams.get(stream_id)
            if stream is None:
                return False
            stream.token.cancel(reason)
            obs_flight.record(
                "decode", "abort",
                model=self.name, stream=stream_id, reason=reason,
            )
            self._cv.notify_all()
            return True

    def wake(self) -> None:
        with self._cv:
            self._cv.notify_all()

    # -- worker --------------------------------------------------------------

    def _any_live(self) -> bool:
        """Whether a turn has anything to do: a stream seated, or a
        step's result still unread (every stream of it aborted since).
        The worker parks, idles out and clears its pools only from a
        state with neither: no read is ever left pending."""
        return any(
            p.live or p.unread is not None for p in self._pools.values()
        )

    def _park(self) -> bool:
        """Wait (phase ``wait``) until there is a stream to serve or
        the decoder closes.  False when the worker idled past the knob
        and has stood down: the next submit starts another."""
        with self._cv:
            if self._closed or self._pending:
                return True
            with self.phases("wait"):
                idle_since = time.monotonic()
                while not self._closed and not self._pending:
                    waited = time.monotonic() - idle_since
                    if waited >= self.cfg.idle_timeout_s:
                        # Idle past the knob: free the resident pools
                        # (KV HBM back to the allocator) and park; the
                        # next submit restarts the worker.
                        self._pools.clear()
                        self._step_state.clear()
                        self._thread = None
                        return False
                    self._cv.wait(
                        timeout=self.cfg.idle_timeout_s - waited
                    )
        return True

    def _run(self) -> None:
        phases = self.phases
        while True:
            # Pools are this thread's own: nothing live is read without
            # the lock, and only then is there anything to wait for.
            if not self._any_live() and not self._park():
                return
            t_turn = time.perf_counter()
            before = dict(phases.total)
            turn = self._turn
            for counts in (turn, self._block_turn, self._moe_turn):
                for key in counts:
                    counts[key] = 0
            with obs_tracing.annotation("decode.step") as step_ann:
                with phases("admit"):
                    with self._cv:
                        if self._closed:
                            pending = list(self._pending)
                            self._pending.clear()
                            pools = list(self._pools.values())
                            self._pools.clear()
                            self._thread = None
                            break
                        pending = list(self._pending)
                        self._pending.clear()
                    deferred = []
                    for stream in pending:
                        try:
                            admitted = self._admit(stream)
                        except Exception as exc:  # noqa: BLE001 — a
                            # bug in admission (or its error handler)
                            # costs ONE stream, never the model's
                            # worker thread: an unfinished stream here
                            # would stall every in-flight SSE client on
                            # a no_timeout route.
                            logger.error("decode admit raised %s", kv(
                                model=self.name, stream=stream.stream_id,
                                error=str(exc),
                            ))
                            self._finish(
                                stream, error=f"admission failed: {exc}"
                            )
                            continue
                        if not admitted:
                            deferred.append(stream)
                self._step_all()
                if deferred:
                    with phases("admit"), self._cv:
                        # Back to the FRONT: arrival order is admission
                        # order once capacity frees up.
                        self._pending.extendleft(reversed(deferred))
                step_ann.set_metadata(**turn)
                if self._block_turn["positions"]:
                    step_ann.set_metadata(**self._block_turn)
                if self._moe_turn["load_max"]:
                    step_ann.set_metadata(**self._moe_turn)
            turn_s = time.perf_counter() - t_turn
            if turn_s > _SLOW_STEP_S:
                split = {
                    name: round(phases.total[name] - before[name], 4)
                    for name in _PHASES if name != "wait"
                }
                logger.warning("decode slow step %s", kv(
                    model=self.name, turnS=round(turn_s, 4), **split,
                ))
                obs_flight.record(
                    "decode", "slow_step",
                    model=self.name, turnS=round(turn_s, 4),
                    phaseS=split, step=self.steps,
                )
        # closed: what a pool's step in flight produced goes out first
        # (a stream whose last step it was ends whole), then whatever
        # never got, or was mid, service fails.
        for pool in pools:
            unread, pool.unread = pool.unread, None
            try:
                if unread is not None:
                    self._read_step(pool, unread)
            except Exception as exc:  # noqa: BLE001 — shutting down:
                # its streams fail below either way.
                logger.error("decode drain on close failed %s", kv(
                    model=self.name, error=str(exc),
                ))
        self._shut_down(pending, pools)
        with self._cv:
            self._streams.clear()

    # -- admission -----------------------------------------------------------

    def _route_replica(self):
        """P2C-pick a replica for a new stream when the model is
        fleet-served; None keeps the registry-resident single path.
        Depth signal = live decode slots per replica, the decode
        analogue of the predict router's queue depth."""
        try:
            rs = self.engine.service.fleet.registered_set(self.name)
        except Exception:  # noqa: BLE001 — routing must not kill admit
            rs = None
        if rs is None:
            return None
        with rs._lock:
            replicas = list(rs._replicas)
        if not replicas:
            return None
        depths = []
        for replica in replicas:
            depths.append(sum(
                pool.live for key, pool in self._pools.items()
                if key[0] == replica.idx
            ))
        order = rs.router.choose(depths)
        return replicas[order[0]]

    def _admit(self, stream: DecodeStream) -> bool:
        if stream.token.cancelled():
            self._finish(stream, aborted=True)
            return True
        try:
            replica = self._route_replica()
            ridx = None if replica is None else replica.idx
            # A cache of pages is a pool a length bucket; one of states
            # takes every length, its token buffer as long as a request
            # may be.
            cap = min(self.cfg.max_kv, self._max_len())
            by_length, chunk = self._pool_layout()
            kvlen = bucket_for(stream.span, cap) if by_length else None
            pool = self._pools.get((ridx, kvlen))
            if pool is None:
                pool = self._pools[(ridx, kvlen)] = PagePool(
                    kvlen or cap, self.cfg.max_slots, replica_idx=ridx,
                    width=1 if stream.plan is None
                    else stream.plan.block,
                    chunk=chunk,
                )
                obs_flight.record(
                    "decode", "pool_grow",
                    model=self.name, kv=kvlen, buffer=pool.kv,
                    slots=self.cfg.max_slots,
                    replica=-1 if ridx is None else ridx,
                )
            slot = pool.admit(
                stream,
                lambda want: self._step_for(want, pool.kv)[1],
            )
        except Exception as exc:  # noqa: BLE001 — fail THIS stream
            logger.error("decode admit failed %s", kv(
                model=self.name, stream=stream.stream_id,
                error=str(exc),
            ))
            obs_flight.record(
                "decode", "admit_failed",
                model=self.name, stream=stream.stream_id,
                error=str(exc),
            )
            self._finish(stream, error=f"admission failed: {exc}")
            return True
        if slot is not None:
            wait_s = time.perf_counter() - stream.arrived
            self.admitted += 1
            self.admit_wait_s += wait_s
            with obs_tracing.annotation(
                "decode.seat", wait_ms=round(wait_s * 1e3, 3)
            ):
                obs_flight.record(
                    "decode", "admit",
                    model=self.name, stream=stream.stream_id,
                    kv=kvlen, slot=slot, waitS=round(wait_s, 4),
                )
        return slot is not None

    def _max_len(self) -> int:
        entry = self.engine.service.registry.get(self.name)
        return int(getattr(entry.estimator, "max_len", self.cfg.max_kv))

    def _pool_layout(self) -> tuple:
        """(whether pools are keyed by a KV-length bucket, the prompt
        positions a slot may take in one step): both read from the
        model's own decode cache (``pages.py``)."""
        if self._layout is None:
            module = self.engine.service.registry.get(
                self.name
            ).estimator.module
            self._layout = (keyed_by_length(module), chunk_width(module))
        return self._layout

    # -- stepping ------------------------------------------------------------

    def _step_for(self, nslots: int, kvlen: int, chunk: int = 1):
        """(jitted step, cache shapes) for one (S, Tk) cell, the
        prompt-chunk program where ``chunk`` is over 1, resolved
        through the cross-job compile cache: fingerprints, hit/miss
        stats, warm-start hints and AOT eligibility — never a private
        dict of executables.  Memoized on the decoder (dies with the
        model teardown) and recorded on the registry entry's
        ``decode_warm`` so replica pre-warm can replay it."""
        state = self._step_state.get((nslots, kvlen, chunk))
        if state is None:
            from learningorchestra_tpu.train import compile_cache as cc

            entry = self.engine.service.registry.get(self.name)
            module = entry.estimator.module
            key = cc.program_key(
                "decode_step",
                module=cc.module_fingerprint(module),
                optimizer=None,
                loss="-",
                dtype="-",
                shapes=("decode_step", nslots, kvlen, chunk),
                # The step consumes its cache and buffer (a block
                # pool's, the blocks' state too): a store keyed without
                # this must not hand back one that copies.
                donate=step_donates(module),
            )
            label = (
                f"decode:{type(module).__name__}"
                f":s{nslots}:k{kvlen}" + (f":c{chunk}" if chunk > 1 else "")
            )
            # a one-token or block program is asked for as it always was
            width = (chunk,) if chunk > 1 else ()
            state = cc.get_cache().get_or_build(
                key, lambda: build_step(module, nslots, kvlen, *width),
                label=label,
            )
            self._step_state[(nslots, kvlen, chunk)] = state
            entry.decode_warm[(nslots, kvlen, chunk)] = True
        return state

    def _params_for(self, pool: PagePool):
        entry = self.engine.service.registry.get(self.name)
        if pool.replica_idx is None:
            return entry.params
        try:
            rs = self.engine.service.fleet.registered_set(self.name)
            if rs is not None:
                with rs._lock:
                    replicas = list(rs._replicas)
                for replica in replicas:
                    if replica.idx == pool.replica_idx:
                        params, _ = replica.place(
                            entry, np.zeros((1, 1), np.int32)
                        )
                        return params
        except Exception:  # noqa: BLE001 — scaled-down replica →
            pass  # degrade to registry-resident params
        return entry.params

    def _step_all(self) -> None:
        for key in list(self._pools):
            pool = self._pools[key]
            # Abort sweep FIRST: a cancelled stream's pages are freed
            # within one step boundary of the cancel, even if the
            # step itself then faults.
            with self.phases("admit"):
                for slot, stream in enumerate(pool.streams):
                    if stream is not None and stream.token.cancelled():
                        pool.release(slot)
                        self._finish(stream, aborted=True)
            if not pool.live and pool.unread is None:
                continue
            try:
                self._step_pool(pool)
            except Exception as exc:  # noqa: BLE001 — chaos/device
                # Blast radius = this pool's in-flight streams (the
                # real scope of a device fault mid-step); the worker
                # and the other pools stay healthy.
                logger.error("decode step failed %s", kv(
                    model=self.name, pool=f"{key}", error=str(exc),
                ))
                obs_flight.record(
                    "decode", "step_error",
                    model=self.name, pool=f"{key}", error=str(exc),
                )
                # The step consumes the pool's cache and buffer, so
                # after one that raised (when it was enqueued, or when
                # its result was read with its successor already
                # enqueued) they may be gone: the pool forgets its
                # device state whole, the unread result with it
                # (nothing here may touch the old buffer), and the next
                # admission allocates afresh.
                for stream in pool.drop():
                    self._finish(
                        stream, error=f"decode step failed: {exc}"
                    )

    def _step_pool(self, pool: PagePool) -> None:
        """One turn of a pool, which keeps ONE step in flight: the turn
        enqueues step k and only then reads step k-1, so the host's
        whole turn runs while the chip runs step k, and the chip finds
        step k+1 queued when step k ends.  Nothing in step k needs step
        k-1's result on the host.  A one-token pool's input token is in
        the device's buffer, and positions, prompt lengths and who is
        live follow from lengths alone (greedy, no EOS: a stream ends
        at ``total``).  A block pool's step program keeps every slot's
        block on the device and applies the strategy to its own
        proposals (``pages.build_step``), so where a slot stands is the
        device's to know and the host believes what it reads.  A token
        leaves one turn late and no later than before: as soon as its
        own step has ended on the chip.  Reading step k-1 before step
        k+1 is enqueued is what bounds the run-ahead at one step.
        ``pool.width``, the model's own, chooses the step program and
        the reader of its result, nothing else; ``pool.chunk``, the
        model's own too, how many prompt positions a slot may take in
        one step of a one-token pool (``_dispatch``)."""
        from learningorchestra_tpu import faults

        # A one-token pool's positions advance when a step is
        # dispatched: a slot whose stream has had its last step
        # dispatched sits this one out, seated until that step's result
        # is read.  A block pool's slot is stepped while it is seated:
        # the step program lets it sit out once its blocks are done.
        live = np.array(
            [s is not None
             and (pool.width > 1 or pool.pos[i] < s.total - 1)
             for i, s in enumerate(pool.streams)], bool
        )
        before, pool.unread = pool.unread, None
        if live.any():  # else the turn only drains: no step for nothing
            with self.phases("dispatch"):
                # The chaos probe stands where the step is dispatched:
                # a delay armed on it reads as dispatch time.
                faults.hit("serve.decode_step")
                pool.unread = self._dispatch(
                    pool, live, ahead=before is not None
                )
        if before is not None:
            self._read_step(pool, before)

    def _dispatch(self, pool: PagePool, live, ahead: bool) -> tuple:
        """Enqueue the pool's next step for the ``live`` slots and
        return what reading it will need: its result, the streams it
        stepped and, of a one-token pool, their positions after it and
        the terminal buffer rows of the lazy streams it ends.
        ``ahead``: the step before it is still unread."""
        turn = self._turn
        turn["slots"] += pool.nslots
        turn["pools"] += 1
        if pool.holds_pages:
            turn["kv"] = max(turn["kv"], pool.kv)
            turn["kv_bytes_per_token"] = pool.token_bytes()
        else:
            turn["state_bytes_per_slot"] = pool.slot_bytes()
        if ahead:
            self.steps_ahead += 1
            turn["ahead"] += 1
        else:
            # From a drained pool: the chip's time starts here.
            pool.read_at = time.perf_counter()
        stepped = [s if on else None for s, on in zip(pool.streams, live)]
        if pool.width > 1:
            slots = np.zeros((len(blocks.SLOT_ROWS), pool.nslots), np.int32)
            for slot, stream in enumerate(stepped):
                if stream is not None:
                    slots[:, slot] = (
                        1, pool.fresh[slot], stream.t0, stream.total,
                        *stream.plan.packed,
                    )
            pool.fresh[:] = False
            nxt, rows = None, {}
            step, _ = self._step_for(pool.nslots, pool.kv)
            col = self._call(pool, step, slots)
        else:
            t0s = np.array(
                [s.t0 if s is not None else pool.kv + 1
                 for s in pool.streams],
                np.int32,
            )
            # A fresh array a dispatch (``pool.pos`` is mutated right
            # below, and jax's CPU backend may alias numpy buffers
            # zero-copy, so a lazily-executed step would read positions
            # from the FUTURE); a slot not live in this step goes in at
            # position 0, like a free one.
            pos_now = np.where(live, pool.pos, 0).astype(np.int32)
            # What this step does, slot by slot, follows from lengths
            # (the step program derives the same ``n`` from the same
            # three vectors): a live slot with prompt beyond its
            # position takes up to ``pool.chunk`` prompt positions
            # (prefill; the chunk that reaches the prompt's end
            # produces the first token too), any other live slot its
            # one position and an output token; each attends over the
            # keys up to and with its last position.  Only a step in
            # which some slot takes several runs the chunk program: a
            # pool that only decodes runs the one-token program.
            n = np.where(
                live, np.clip(t0s - pos_now, 1, pool.chunk), 1
            ).astype(np.int32)
            chunked = bool((n > 1).any())
            nxt = pos_now + n
            feeding = live & (pos_now < t0s - 1)
            n_prompt = int(feeding.sum())
            self._count(
                n_prompt, int(live.sum()) - n_prompt, int(nxt[live].sum()),
                # prompt tokens the step moves past without producing:
                # a prompt's t0 - 1 in all, however many steps take them
                positions=int(np.minimum(n, t0s - 1 - pos_now)[feeding].sum()),
            )
            if chunked:
                self.chunk_steps += 1
                turn["chunk"] += 1
            if not pool.holds_pages:
                resets = int((live & (pos_now == 0)).sum())
                self.state_resets += resets
                turn["state_resets"] += resets
            step, _ = self._step_for(
                pool.nslots, pool.kv, pool.chunk if chunked else 1
            )
            col = self._call(pool, step, pos_now, t0s, live)
            pool.pos[live] = nxt[live]
            # Terminal: the full row (prompt + continuation) is in the
            # buffer this step returns; a lazy stream surfaces
            # everything from it.  The slice is enqueued here, before
            # the next step consumes that buffer, and read with the
            # column.
            rows = {
                slot: pool.buf[slot]
                for slot, stream in enumerate(stepped)
                if stream is not None and not stream.eager
                and nxt[slot] >= stream.total - 1
            }
        # The result starts for the host the moment its step ends,
        # whatever the worker is doing then.
        col.copy_to_host_async()
        return col, stepped, nxt, rows

    def _read_step(self, pool: PagePool, unread: tuple) -> None:
        """Read a dispatched step's result back (what ``_dispatch``
        returned for it), hand its tokens to their streams and finish
        the streams whose last step it was."""
        col, stepped, nxt, rows = unread
        with self.phases("sync"):
            col_host = np.asarray(col)
            rows = {slot: np.asarray(row) for slot, row in rows.items()}
            now = time.perf_counter()
        with self.phases("emit"):
            if pool.width == 1:
                self._read_tokens(pool, col_host, stepped, now, nxt, rows)
            else:
                self._read_blocks(pool, col_host, stepped, now)
            # The wall time between successive reads is the chip's time
            # for a step while one is always in flight (measured to
            # HERE, past the row reads), whichever transport the
            # streams ride: non-stream decode feeds the autoscaler's
            # LO_TPU_FLEET_UP_DEVICE_FRAC signal too.
            done_at = time.perf_counter()
            self._record_devtime(pool, done_at - pool.read_at)
            pool.read_at = done_at

    def _read_tokens(self, pool: PagePool, col, stepped, now: float,
                     nxt, rows) -> None:
        """A one-token step's column: its tokens to the eager streams,
        the terminal rows to the lazy ones it ends."""
        self._count_experts(col[len(stepped):])
        for slot, stream in enumerate(stepped):
            # Not in that step, or aborted (and its slot perhaps seated
            # anew) since it was dispatched.
            if stream is None or pool.streams[slot] is not stream:
                continue
            nxt_pos = int(nxt[slot])
            if stream.eager and nxt_pos >= stream.t0:
                self._emit(stream, int(col[slot]), nxt_pos, now)
            if nxt_pos >= stream.total - 1:
                if not stream.eager:
                    self._surface(stream, rows[slot], now)
                pool.release(slot)
                self._finish(stream)

    def _read_blocks(self, pool: PagePool, col, stepped,
                     now: float) -> None:
        """A block step's result (``blocks.RESULT_HEAD``): what each
        slot's forward was is the step program's word, and the counters
        describe the step read.  A denoising forward's tokens stay on
        the device; a forward that made a block's K/V final (a whole
        prompt block's prefill, or a generated block's commit, whose
        tokens go out in order with the step each was fixed at) is the
        stream's last where the block reaches ``total``."""
        q, head = pool.width, len(blocks.RESULT_HEAD)
        self._count_experts(col[-1])
        kinds, starts, fixed = col[:-1, :head].T
        counts = np.bincount(kinds, minlength=4)
        by_kind = {"prefill": int(counts[blocks.PREFILL]),
                   "denoise": int(counts[blocks.DENOISE]),
                   "commit": int(counts[blocks.COMMIT])}
        stepped_n = sum(by_kind.values())
        self._count(
            by_kind["prefill"], stepped_n - by_kind["prefill"],
            q * int((starts + q)[kinds != blocks.IDLE].sum()),
            positions=q * by_kind["prefill"],
        )
        bturn = self._block_turn
        for name, n in by_kind.items():
            self.block_steps[name] += n
            bturn[name] += n
        self.positions += q * stepped_n
        bturn["positions"] += q * stepped_n
        self.tokens_fixed += int(fixed.sum())
        bturn["fixed"] += int(fixed.sum())
        for slot, stream in enumerate(stepped):
            # Not in that step, or aborted (and its slot perhaps seated
            # anew) since it was dispatched.
            if stream is None or pool.streams[slot] is not stream \
                    or kinds[slot] in (blocks.IDLE, blocks.DENOISE):
                continue
            start = int(starts[slot])
            if kinds[slot] == blocks.COMMIT:
                tokens = col[slot, head: head + q]
                fixed_at = col[slot, head + q:]
                for j in range(q):
                    if stream.t0 <= start + j < stream.total:
                        self._emit(stream, int(tokens[j]), start + j,
                                   now, step=int(fixed_at[j]))
            if start + q >= stream.total:
                pool.release(slot)
                self._finish(stream)

    def _surface(self, stream: DecodeStream, row, now: float) -> None:
        """A lazy stream's tokens, all at once from its terminal buffer
        row."""
        stream.tokens = [int(t) for t in row[stream.t0: stream.total]]
        stream.first_at = stream.first_at or now
        ttft_s = stream.first_at - stream.arrived
        _decode_hists.ttft(ttft_s, self.name)
        obs_flight.record(
            "decode", "ttft",
            model=self.name, stream=stream.stream_id,
            ttftS=round(ttft_s, 4),
        )
        _decode_hists.tokens(len(stream.tokens), self.name)

    def _count(self, prompt: int, output: int, keys: int,
               positions: int) -> None:
        """A step's slot-steps (and the ``positions`` its prompt steps
        took) and attended keys, into the cumulative counters and the
        turn's annotation.  A one-token pool's are
        counted at dispatch (they follow from lengths), a block pool's
        when its result is read (they are the step program's word), and
        never at the turn's end: a stream this step finishes may read
        stats() before the turn is over."""
        self.prompt_steps += prompt
        self.output_steps += output
        self.prompt_positions += positions
        self.keys_attended += keys
        turn = self._turn
        turn["prompt"] += prompt
        turn["output"] += output
        turn["prompt_positions"] += positions
        turn["keys"] += keys

    def _count_experts(self, counted) -> None:
        """A step's routed-expert counts as its program returned them
        (``pages._moe_stats``; nothing from a dense model)."""
        if len(counted) < 3:
            return
        hit, busiest, rows = (int(v) for v in counted[:3])
        self.experts_hit += hit
        self.expert_load_max = max(self.expert_load_max, busiest)
        self.expert_rows += rows
        turn = self._moe_turn
        turn["experts_hit"] += hit
        turn["load_max"] = max(turn["load_max"], busiest)
        turn["expert_rows"] += rows

    def _call(self, pool: PagePool, step, *slots):
        """Enqueue the pool's step.  The step consumes what the pool
        carries on the device (``PagePool.device``): from here on the
        pool holds only what it returned."""
        went_in = first_pages(pool.cache)
        *pool.device, col = step(
            self._params_for(pool), *pool.device, *slots
        )
        pool.steps += 1
        self.steps += 1
        if went_in.is_deleted():
            self.steps_in_place += 1
            self._turn["inplace"] += 1
        return col

    def _record_devtime(self, pool: PagePool, seconds: float) -> None:
        from learningorchestra_tpu.obs import costs as obs_costs

        if obs_costs.enabled():
            led = obs_costs.devtime()
            weight = led.will_record(self.name)
            if weight:
                led.record_model(
                    weight, seconds, None, None,
                    self.name, f"dec{pool.nslots}x{pool.kv}",
                )

    def _emit(self, stream: DecodeStream, tok: int, pos: int,
              now: float, step=None) -> None:
        if stream.first_at is None:
            stream.first_at = now
            _decode_hists.ttft(now - stream.arrived, self.name)
            obs_flight.record(
                "decode", "ttft",
                model=self.name, stream=stream.stream_id,
                ttftS=round(now - stream.arrived, 4),
            )
        else:
            _decode_hists.itl(now - stream.last_at, self.name)
        stream.last_at = now
        stream.push_token(tok, pos, step)
        _decode_hists.tokens(1, self.name)

    def _finish(self, stream: DecodeStream, *,
                error: str | None = None,
                aborted: bool = False) -> None:
        # Out of the active set BEFORE its caller hears of the end: a
        # closed loop of ``max_streams`` callers sends its next request
        # the moment the last one ends, and must find its place free.
        with self._cv:
            self._streams.pop(stream.stream_id, None)
        if error is not None:
            stream.fail(error)
        elif aborted:
            stream.mark_aborted()
        else:
            stream.finish()

    # -- lifecycle / observability -------------------------------------------

    def warm_replica(self, replica, entry) -> None:
        """Run one dummy step per recorded (S, Tk) cell against the
        replica's placed params — pays the per-device executable
        load/compile before the router may pick the replica (the
        decode leg of PR-16 replica pre-warm)."""
        width = step_width(entry.estimator.module)
        for (nslots, kvlen, chunk) in sorted(entry.decode_warm):
            step, cache_shapes = self._step_for(nslots, kvlen, chunk)
            pool = PagePool(kvlen, nslots, replica_idx=replica.idx,
                            width=width)
            pool._alloc(cache_shapes, nslots)
            params, _ = replica.place(
                entry, np.zeros((1, 1), np.int32)
            )
            # no slot live: a step that changes nothing
            slots = (
                np.zeros(nslots, np.int32),
                np.full(nslots, kvlen + 1, np.int32),
                np.zeros(nslots, bool),
            ) if width == 1 else (
                np.zeros((len(blocks.SLOT_ROWS), nslots), np.int32),
            )
            step(params, *pool.device, *slots)

    def stats(self) -> dict:
        with self._cv:
            pending = len(self._pending)
            active = len(self._streams)
            # Snapshot under the cv: the worker clears/inserts pool
            # entries concurrently (idle parking, admission).
            pools_snap = list(self._pools.values())
        pools = [
            {
                # the bucket the pool is keyed by; none where its cache
                # has no length axis (``buffer``: its token buffer's)
                "kv": pool.kv if pool.holds_pages else None,
                "buffer": pool.kv,
                "slots": pool.nslots,
                "live": pool.live,
                "steps": pool.steps,
                "pageBytes": pool.page_bytes(),
                "kvBytesPerToken": pool.token_bytes(),
                "stateBytesPerSlot": pool.slot_bytes(),
                "replica": pool.replica_idx,
            }
            for pool in pools_snap
        ]
        return {
            "activeStreams": active,
            "pending": pending,
            "steps": self.steps,
            "stepsInPlace": self.steps_in_place,
            "stepsAhead": self.steps_ahead,
            # Steps that ran the prompt-chunk program (some slot took
            # several prompt positions), of ``steps``.
            "chunkSteps": self.chunk_steps,
            "pools": pools,
            "poolsLive": sum(1 for pool in pools if pool["live"]),
            # Slots a step began from a zero state (pools of states).
            "stateResets": self.state_resets,
            # Cumulative, from the worker's own counts (each step, each
            # live slot is one slot-step: prompt while prompt lies
            # beyond its position, output once it only produces
            # tokens), and the positions the prompt steps took.
            "slotSteps": {"prompt": self.prompt_steps,
                          "output": self.output_steps},
            "promptPositions": self.prompt_positions,
            "keysAttended": self.keys_attended,
            "phaseS": dict(self.phases.total),
            "phaseMaxS": dict(self.phases.peak),
            "admitted": self.admitted,
            "admitWaitS": self.admit_wait_s,
            # Generation by diffusion over blocks (all 0 for a
            # next-token model): slot-steps by phase, positions
            # processed, tokens the denoising forwards fixed.
            "blockSteps": dict(self.block_steps),
            "positions": self.positions,
            "tokensFixed": self.tokens_fixed,
            # Routed experts, of a block model or a next-token one:
            # held experts the steps' rows reached, the busiest one's
            # rows in a step, (token, choice) pairs that reached one.
            "expertsHit": self.experts_hit,
            "expertLoadMax": self.expert_load_max,
            "expertRows": self.expert_rows,
        }

    def close(self) -> None:
        with self._cv:
            self._closed = True
            thread = self._thread
            self._cv.notify_all()
        if thread is not None and thread.is_alive():
            thread.join(timeout=10.0)
        with self._cv:
            pending = list(self._pending)
            self._pending.clear()
            self._streams.clear()
            pools = list(self._pools.values())
            self._pools.clear()
            self._step_state.clear()
        self._shut_down(pending, pools)

    @staticmethod
    def _shut_down(pending, pools) -> None:
        """Fail the streams of a decoder that closed.  The pools are
        already out of ``_pools``; they forget their device state
        untouched (a worker that outlived ``close``'s join may be
        inside a step that has consumed it)."""
        for stream in pending:
            stream.fail("decode engine shut down")
        for pool in pools:
            for stream in pool.drop():
                stream.fail("decode engine shut down")


class DecodeEngine:
    """Facade the serving service owns: per-model decoders, request
    validation, the stream/non-stream transports."""

    def __init__(self, service):
        self.service = service
        self.cfg = service.ctx.config.decode
        self._lock = make_lock("DecodeEngine._lock")
        self._decoders: dict[str, _ModelDecoder] = {}
        self._closed = False

    # -- request surface -----------------------------------------------------

    def _decoder_for(self, name: str) -> _ModelDecoder:
        with self._lock:
            if self._closed:
                raise ServeError("decode engine is shut down")
            decoder = self._decoders.get(name)
            if decoder is None:
                decoder = self._decoders[name] = _ModelDecoder(
                    self, name
                )
            return decoder

    @staticmethod
    def _as_prompt_rows(prompts) -> list[np.ndarray]:
        """Request JSON → per-stream prompt rows.  Rows may be RAGGED
        (each stream carries its own t0 — continuous batching decodes
        them independently); pad id 0 is reserved."""
        if isinstance(prompts, np.ndarray):
            prompts = prompts.tolist()
        if not isinstance(prompts, (list, tuple)) or not prompts:
            raise ServeError("'prompts' must be a non-empty array")
        if not isinstance(prompts[0], (list, tuple, np.ndarray)):
            prompts = [prompts]
        rows = []
        for row in prompts:
            try:
                r = np.asarray(row, dtype=np.int32)
            except (ValueError, TypeError) as exc:
                raise ServeError(
                    f"prompt row is not an int array: {exc}"
                ) from None
            if r.ndim != 1 or r.shape[0] == 0:
                raise ServeError(
                    "each prompt must be a non-empty 1-D token array"
                )
            if (r == 0).any():
                raise ServeError(
                    "prompts must not contain pad id 0"
                )
            rows.append(r)
        return rows

    def _open_stream(self, name: str, decoder: _ModelDecoder,
                     prompt: np.ndarray, max_new: int, max_len: int,
                     *, eager: bool, plan=None) -> DecodeStream:
        t0 = int(prompt.shape[0])
        cap = min(max_len, self.cfg.max_kv)
        if plan is not None:
            cap -= cap % plan.block  # whole blocks of pages
            if (prompt == plan.mask_id).any():
                raise ServeError(
                    f"prompts must not contain the mask id {plan.mask_id}"
                )
        if t0 >= cap:
            raise ServeError(
                f"prompt length {t0} exceeds decode capacity {cap} "
                f"(model max_len / LO_TPU_DECODE_MAX_KV)"
            )
        max_new = max(1, min(int(max_new), self.cfg.max_new_tokens))
        total = min(cap, t0 + max_new)
        stream = DecodeStream(name, prompt, t0, total, eager=eager,
                              plan=plan)
        decoder.submit(stream)
        return stream

    def generate(self, name: str, prompts, *,
                 max_new_tokens: int = 32, stream: bool = False,
                 temperature=None, top_k=None, top_p=None,
                 seed: int = 0, denoising_steps=None, remasking=None,
                 confidence_threshold=None):
        """Entry point behind ``POST /serve/<model>/generate``.

        Greedy decodes run on the resident engine (stream or not);
        sampling parameters fall back to the solo jitted scan
        (non-stream only — a sampled decode has no per-step identity
        to stream against the engine's greedy executables).

        ``denoising_steps`` (1..block length), ``remasking`` and
        ``confidence_threshold`` belong to a model that generates by
        diffusion over blocks, which is greedy only; any other model
        refuses them."""
        entry = self.service.registry.get(name)
        estimator = entry.estimator
        if not hasattr(estimator, "generate"):
            raise ServeError(
                f"artifact {name!r} ({type(estimator).__name__}) is "
                "not a generative LM; only GreedyDecodeMixin models "
                "can serve /generate"
            )
        sampling = (
            temperature is not None or top_k is not None
            or top_p is not None
        )
        block_args = {
            "denoising_steps": denoising_steps, "remasking": remasking,
            "confidence_threshold": confidence_threshold,
        }
        plan = None
        if step_width(estimator.module) > 1:
            if sampling:
                raise ServeError(
                    "generation by diffusion over blocks is greedy: "
                    "drop temperature / topK / topP"
                )
            try:
                plan = BlockPlan(estimator, *block_args.values())
            except ValueError as exc:
                raise ServeError(str(exc)) from None
        elif any(v is not None for v in block_args.values()):
            raise ServeError(
                f"{type(estimator).__name__} generates token by token: "
                "denoisingSteps, remasking and confidenceThreshold "
                "belong to a block-diffusion model"
            )
        rows = self._as_prompt_rows(prompts)
        if sampling or not self.cfg.enabled:
            if stream:
                raise ServeError(
                    "streaming decode requires the resident engine "
                    "(greedy only, LO_TPU_DECODE_ENABLED=1); drop the "
                    "sampling parameters or set stream=false"
                )
            solo = block_args if plan is not None else {
                "temperature": temperature, "top_k": top_k,
                "top_p": top_p, "seed": int(seed),
            }
            return self._solo_generate(
                name, entry, rows, max_new_tokens, **solo
            )
        if stream and len(rows) != 1:
            raise ServeError(
                "stream=true serves exactly one prompt per request"
            )
        decoder = self._decoder_for(name)
        max_len = int(getattr(estimator, "max_len", self.cfg.max_kv))
        streams = [
            self._open_stream(
                name, decoder, row, max_new_tokens, max_len,
                eager=stream, plan=plan,
            )
            for row in rows
        ]
        entry.requests += 1
        if stream:
            return streams[0]
        t0 = time.perf_counter()
        for s in streams:
            remaining = _NONSTREAM_TIMEOUT_S - (
                time.perf_counter() - t0
            )
            if not s.wait_done(max(0.1, remaining)):
                for other in streams:
                    other.abort("decode timed out")
                raise ServeError("decode timed out")
        failed = [s for s in streams if s.error is not None]
        if failed:
            raise ServeError(failed[0].error)
        aborted = [
            s for s in streams
            if s.token.cancelled() and s.error is None
        ]
        if aborted:
            raise ServeError(
                f"decode aborted: {aborted[0].token.reason}"
            )
        return {
            "model": name,
            "tokens": [
                s.prompt.tolist() + s.tokens for s in streams
            ],
            "newTokens": [s.tokens for s in streams],
            "streams": [s.summary() for s in streams],
        }

    def _solo_generate(self, name, entry, rows, max_new_tokens,
                       temperature=None, **kwargs):
        """Per-shape solo fallback (sampling / engine disabled), the
        estimator's own ``generate``: one call per distinct prompt
        length so ragged rows stay legal."""
        out_tokens: list[list[int]] = []
        for row in rows:
            try:
                buf = entry.estimator.generate(
                    row[None, :], max_new_tokens=int(max_new_tokens),
                    temperature=temperature, **kwargs,
                )
            except ValueError as exc:
                # Bad sampling spec (top_k without temperature, ...)
                # is a client error, not a server fault → 406.
                raise ServeError(str(exc)) from None
            out_tokens.append(np.asarray(buf)[0].tolist())
        entry.requests += 1
        return {
            "model": name,
            "tokens": out_tokens,
            "newTokens": [
                t[rows[i].shape[0]:] for i, t in enumerate(out_tokens)
            ],
            "sampled": temperature is not None,
        }

    def abort(self, name: str, stream_id: str,
              reason: str = "aborted by client") -> bool:
        with self._lock:
            decoder = self._decoders.get(name)
        if decoder is None:
            return False
        return decoder.abort(stream_id, reason)

    # -- fleet / lifecycle ---------------------------------------------------

    def warm_replica(self, name: str, replica) -> None:
        """Decode leg of replica pre-warm: replay every recorded
        (slot-bucket, kv-bucket) step against the new replica's
        placed params.  Failures are the caller's to log — a replica
        that can't warm still serves cold."""
        entry = self.service.registry.peek(name)
        if entry is None or not entry.decode_warm:
            return
        self._decoder_for(name).warm_replica(replica, entry)

    def drop_model(self, name: str) -> None:
        with self._lock:
            decoder = self._decoders.pop(name, None)
        if decoder is not None:
            decoder.close()

    def stats(self) -> dict:
        with self._lock:
            decoders = dict(self._decoders)
        return {
            "enabled": bool(self.cfg.enabled),
            "models": {
                name: d.stats() for name, d in decoders.items()
            },
        }

    def close(self) -> None:
        with self._lock:
            self._closed = True
            decoders = list(self._decoders.values())
            self._decoders.clear()
        for decoder in decoders:
            decoder.close()
