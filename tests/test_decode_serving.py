"""Streaming LM decode engine (serve/decode/): SSE round-trips over
live HTTP, continuous-batching bit-identity under mid-flight
admission, cooperative stream teardown freeing KV pages at a step
boundary (under an armed ``serve.decode_step`` fault), the decode
metric families on /metrics.prom, the TTFT SLO objective, the
cost-aware autoscaler signal, and the client bindings.

The bit-identity invariant is the one everything rests on: a prompt
admitted into an IN-FLIGHT pool (other rows mid-generation, dead
slots present) must decode exactly what a solo ``generate`` produces
— per-row ``cache_index`` + masked attention make padding and
foreign rows invisible.
"""

import threading
import time

import numpy as np
import pytest
import requests

from learningorchestra_tpu import faults
from learningorchestra_tpu.obs import flight as obs_flight
from learningorchestra_tpu.obs import metrics as obs_metrics
from learningorchestra_tpu.obs import rollup as obs_rollup
from learningorchestra_tpu.obs import slo as obs_slo
from tests.lm_oracle import naive_greedy_decode

PREFIX = "/api/learningOrchestra/v1"


def _slot_steps(t0: int, new: int, chunk: int | None = None,
                cap: int = 16) -> list:
    """(position, positions taken) of every step a request of ``t0``
    prompt tokens and ``new`` outputs takes in a pool whose steps take
    up to ``chunk`` prompt positions (the engine's own, by default):
    the schedule ``_dispatch`` follows from lengths alone."""
    from learningorchestra_tpu.serve.decode.pages import PROMPT_CHUNK

    chunk = PROMPT_CHUNK if chunk is None else chunk
    total, pos, out = min(cap, t0 + new), 0, []
    while pos < total - 1:
        n = min(max(t0 - pos, 1), chunk)
        out.append((pos, n))
        pos += n
    return out


def _install_trained_lm(server, name, *, vocab=16, hidden=32,
                        layers=2, heads=4, max_len=16):
    """Finished train artifact holding a fitted tiny DecoderLM (the
    decode path is under test, not training quality)."""
    from learningorchestra_tpu.models.text import DecoderLM

    rng = np.random.default_rng(7)
    x = rng.integers(1, vocab, size=(16, max_len - 2)).astype(np.int32)
    y = np.concatenate(
        [x[:, 1:], np.zeros((16, 1), np.int32)], axis=1
    )
    est = DecoderLM(
        vocab_size=vocab, hidden_dim=hidden, num_layers=layers,
        num_heads=heads, max_len=max_len, seed=0,
    )
    est.compute_dtype = "float32"
    est.fit(x, y, epochs=2, batch_size=16)
    server.ctx.volumes.save_object("train/tensorflow", name, est)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)
    return est


@pytest.fixture(scope="module")
def decode_api(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("decode_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    est = _install_trained_lm(server, "lm_srv")
    yield server, base, est
    server.shutdown()


def _parse_sse(resp):
    """[(event, data-json)] from a requests streaming response."""
    import json as _json

    events, event, data = [], None, []
    for raw in resp.iter_lines():
        line = raw.decode() if isinstance(raw, bytes) else raw
        if line:
            if line.startswith("event:"):
                event = line[len("event:"):].strip()
            elif line.startswith("data:"):
                data.append(line[len("data:"):].strip())
            continue
        if event is None and not data:
            continue
        events.append((event, _json.loads("\n".join(data) or "{}")))
        event, data = None, []
    return events


class TestSSERoundTrip:
    def test_stream_matches_solo_generate(self, decode_api):
        server, base, est = decode_api
        prompt = [5, 1, 2, 9]
        solo = np.asarray(est.generate(
            np.asarray([prompt], np.int32), max_new_tokens=8
        ))[0].tolist()

        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [prompt], "stream": True,
                  "maxNewTokens": 8},
            stream=True, timeout=60,
        )
        assert resp.status_code == 200, resp.text
        assert resp.headers["Content-Type"].startswith(
            "text/event-stream"
        )
        events = _parse_sse(resp)
        names = [e for e, _ in events]
        assert names[0] == "open"
        assert names[-1] == "done"
        toks = [doc["t"] for e, doc in events if e == "token"]
        assert prompt + toks == solo
        # The done summary carries the lifecycle accounting.
        done = events[-1][1]
        assert done["promptTokens"] == len(prompt)
        assert done["newTokens"] == 8
        assert done["ttftMs"] is not None

    def test_nonstream_json_matches_and_is_batched(self, decode_api):
        server, base, est = decode_api
        prompts = [[5, 1, 2, 9], [3, 3, 7, 1]]
        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": prompts, "maxNewTokens": 8},
            timeout=60,
        )
        assert resp.status_code == 200, resp.text
        body = resp.json()
        oracle = naive_greedy_decode(est, prompts, 12)
        assert body["tokens"] == oracle.tolist()
        # Both rows decoded through ONE shared pool (continuous
        # batching), not two solo calls.
        stats = server.serving.decode.stats()["models"]["lm_srv"]
        assert stats["pools"], "no KV page pool was created"

    def test_decode_warm_shapes_recorded_for_prewarm(self, decode_api):
        server, _, _ = decode_api
        entry = server.serving.registry.peek("lm_srv")
        assert entry is not None and entry.decode_warm, (
            "decode step shapes must be recorded for replica pre-warm"
        )
        from learningorchestra_tpu.serve.decode.pages import PROMPT_CHUNK

        for slots, kvlen, chunk in entry.decode_warm:
            assert slots & (slots - 1) == 0  # power-of-two bucketed
            assert kvlen & (kvlen - 1) == 0
            assert chunk in (1, PROMPT_CHUNK)  # which of the two programs

    def test_validation_errors_are_406(self, decode_api):
        _, base, _ = decode_api
        # Pad id in prompt.
        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [[0, 1]], "maxNewTokens": 2}, timeout=30,
        )
        assert resp.status_code == 406
        # Prompt at/over capacity (model max_len 16).
        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [list(range(1, 17))], "maxNewTokens": 2},
            timeout=30,
        )
        assert resp.status_code == 406
        # Bad sampling spec falls through the solo path as 406 too.
        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [[1, 2]], "topK": 3, "maxNewTokens": 2},
            timeout=30,
        )
        assert resp.status_code == 406


class TestContinuousBatching:
    def test_midflight_admission_is_bit_identical(self, decode_api):
        """A prompt admitted while another stream is mid-generation
        (same kv bucket → same pool, live foreign row + dead slots)
        decodes exactly the solo result."""
        server, _, est = decode_api
        eng = server.serving.decode
        try:
            # Slow the steps (timing only — a delay fault cannot
            # perturb the math) so A is reliably still mid-flight
            # when B joins; an unthrottled eager stream finishes in
            # ~20ms, a losable race under load.
            faults.arm(
                "serve.decode_step", "delay", delay_ms=50,
                max_triggers=256,
            )
            # Stream A: long generation holding the kv=16 pool open.
            a = eng.generate(
                "lm_srv", [7, 2, 4, 1], max_new_tokens=12, stream=True
            )
            # Wait until A is genuinely mid-flight (some tokens out,
            # generation not finished).
            deadline = time.monotonic() + 30
            while len(a.tokens) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert 0 < len(a.tokens) < 12, "stream A not mid-flight"
            # B admitted into the in-flight pool: t0=8, max_new=8 →
            # total 16, the same kv bucket as A.
            prompt_b = [3, 9, 1, 5, 2, 8, 4, 6]
            out = eng.generate("lm_srv", [prompt_b], max_new_tokens=8)
        finally:
            faults.reset()
        solo = np.asarray(est.generate(
            np.asarray([prompt_b], np.int32), max_new_tokens=8
        ))[0].tolist()
        assert out["tokens"][0] == solo
        a.wait_done(30)
        # A was not perturbed either.
        solo_a = np.asarray(est.generate(
            np.asarray([[7, 2, 4, 1]], np.int32), max_new_tokens=12
        ))[0].tolist()
        assert [7, 2, 4, 1] + a.tokens == solo_a

    def test_concurrent_streams_share_one_pool(self, decode_api):
        server, base, est = decode_api
        eng = server.serving.decode
        prompts = [[1, 2, 3, 4], [9, 8, 7, 6], [2, 2, 4, 4]]
        results = [None] * len(prompts)

        def _one(i):
            results[i] = eng.generate(
                "lm_srv", [prompts[i]], max_new_tokens=8
            )

        threads = [
            threading.Thread(target=_one, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        oracle = naive_greedy_decode(est, prompts, 12)
        for i, res in enumerate(results):
            assert res is not None
            assert res["tokens"][0] == oracle[i].tolist()


class TestStreamTeardown:
    def test_abort_frees_kv_within_one_step(self, decode_api):
        """Cancel mid-stream under an armed ``serve.decode_step``
        delay: the slot is swept (abort sweep runs BEFORE the fault
        point) and freed within at most one further decode step."""
        server, _, _ = decode_api
        eng = server.serving.decode
        try:
            # Slow every step from the START so the stream cannot race
            # to completion between first-token and the abort below.
            faults.arm(
                "serve.decode_step", "delay", delay_ms=150,
                max_triggers=64,
            )
            stream = eng.generate(
                "lm_srv", [4, 4, 2, 1], max_new_tokens=12,
                stream=True,
            )
            deadline = time.monotonic() + 30
            while not stream.tokens and time.monotonic() < deadline:
                time.sleep(0.005)
            assert stream.tokens, "stream never produced a token"
            st = eng.stats()["models"]["lm_srv"]
            steps_at_abort = st["steps"]
            assert eng.abort("lm_srv", stream.stream_id)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = eng.stats()["models"]["lm_srv"]
                if st["activeStreams"] == 0 and all(
                    p["live"] == 0 for p in st["pools"]
                ):
                    break
                time.sleep(0.01)
            assert st["activeStreams"] == 0
            assert all(p["live"] == 0 for p in st["pools"])
            assert st["steps"] - steps_at_abort <= 1, (
                "KV pages must be freed at the next step boundary"
            )
            assert stream.done()
            assert stream.token.cancelled()
        finally:
            faults.reset()

    def test_delete_route_aborts_then_404(self, decode_api):
        server, base, _ = decode_api
        eng = server.serving.decode
        try:
            # Keep the stream alive until the DELETE lands.
            faults.arm(
                "serve.decode_step", "delay", delay_ms=150,
                max_triggers=64,
            )
            stream = eng.generate(
                "lm_srv", [6, 1, 3, 2], max_new_tokens=12,
                stream=True,
            )
            resp = requests.delete(
                f"{base}/serve/lm_srv/generate/{stream.stream_id}",
                timeout=30,
            )
            assert resp.status_code == 200, resp.text
            assert resp.json()["aborted"] == stream.stream_id
            assert stream.wait_done(10)
        finally:
            faults.reset()
        resp = requests.delete(
            f"{base}/serve/lm_srv/generate/{stream.stream_id}",
            timeout=30,
        )
        assert resp.status_code == 404


class TestDecodeObservability:
    def test_ttft_itl_families_on_prom(self, decode_api):
        _, base, _ = decode_api
        requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [[5, 5, 5]], "maxNewTokens": 4},
            timeout=60,
        )
        text = requests.get(f"{base}/metrics.prom", timeout=30).text
        for family in (
            "lo_serving_decode_ttft_seconds",
            "lo_serving_decode_itl_seconds",
            "lo_serving_decode_tokens_total",
        ):
            assert family in text, f"{family} missing from exposition"
        assert 'model="lm_srv"' in text

    def test_devtime_ledger_attributes_decode(self, decode_api):
        from learningorchestra_tpu.obs import costs as obs_costs

        _, base, _ = decode_api
        before = obs_costs.devtime().model_device_s("lm_srv")
        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [[1, 2, 3]], "stream": True,
                  "maxNewTokens": 6},
            stream=True, timeout=60,
        )
        _parse_sse(resp)  # drain: eager streams attribute per step
        after = obs_costs.devtime().model_device_s("lm_srv")
        assert after > before

    def test_devtime_ledger_attributes_lazy_decode(self, decode_api):
        """Non-stream decode attributes device time too (flushed at
        the terminal row sync, not only on the eager per-step path) —
        otherwise bulk /generate load would never trip the
        autoscaler's LO_TPU_FLEET_UP_DEVICE_FRAC signal."""
        from learningorchestra_tpu.obs import costs as obs_costs

        _, base, _ = decode_api
        before = obs_costs.devtime().model_device_s("lm_srv")
        resp = requests.post(
            f"{base}/serve/lm_srv/generate",
            json={"prompts": [[1, 2, 3]], "maxNewTokens": 6},
            timeout=60,
        )
        assert resp.status_code == 200, resp.text
        after = obs_costs.devtime().model_device_s("lm_srv")
        assert after > before


class TestDecodeLoopAccounting:
    """What the worker's loop counts and times from inside (ROADMAP
    D12): slot-steps by kind, keys attended, seconds by phase, the
    wait for a slot, and a ``slow_step`` flight event for a turn that
    stalls."""

    @staticmethod
    def _model_stats(eng):
        return eng.stats()["models"].get("lm_srv") or {
            "slotSteps": {"prompt": 0, "output": 0}, "keysAttended": 0,
            "admitted": 0, "admitWaitS": 0.0, "steps": 0,
        }

    def test_slot_steps_phases_and_admission_wait(self, decode_api):
        server, _, _ = decode_api
        eng = server.serving.decode
        before = self._model_stats(eng)
        started_at = time.monotonic()
        shapes = [([4, 4, 2, 1], 6), ([9, 2, 5], 8), ([1, 2, 3, 4, 5], 4)]
        streams = [
            eng.generate("lm_srv", prompt, max_new_tokens=new,
                         stream=True)
            for prompt, new in shapes
        ]
        for stream in streams:
            assert stream.wait_done(60)
            assert stream.error is None
        after = self._model_stats(eng)

        steps = [
            (len(p), pos, n)
            for p, new in shapes for pos, n in _slot_steps(len(p), new)
        ]
        grew = {
            kind: after["slotSteps"][kind] - before["slotSteps"][kind]
            for kind in ("prompt", "output")
        }
        # A slot-step is a prompt step while prompt lies beyond its
        # position (it takes up to a chunk of it; the chunk that
        # reaches the prompt's end produces the first token too), an
        # output step after; every step attends the keys up to the
        # last position it took.  These prompts fit one chunk: one
        # prompt step a stream, then its other outputs.
        feeding = [(t0, pos, n) for t0, pos, n in steps if pos < t0 - 1]
        assert grew == {"prompt": len(feeding),
                        "output": len(steps) - len(feeding)}
        assert grew == {"prompt": len(shapes), "output": sum(
            new - 1 for _, new in shapes)}
        assert after["promptPositions"] - before.get(
            "promptPositions", 0) == sum(len(p) - 1 for p, _ in shapes)
        assert after["keysAttended"] - before["keysAttended"] == sum(
            pos + n for _t0, pos, n in steps
        )
        assert after["admitted"] - before["admitted"] == len(shapes)
        assert after["admitWaitS"] >= before["admitWaitS"] >= 0.0
        assert set(after["phaseS"]) == set(after["phaseMaxS"]) == {
            "admit", "dispatch", "sync", "emit", "wait",
        }
        for name, seconds in after["phaseS"].items():
            assert seconds >= 0.0
            assert after["phaseMaxS"][name] <= seconds + 1e-9
            if name != "wait":
                assert seconds > 0.0, name
        # The keys stats() had stay.
        assert {"activeStreams", "pending", "steps", "pools"} <= set(after)
        admits = [
            e for e in obs_flight.snapshot(["decode"])["events"]["decode"]
            if e["kind"] == "admit" and e["t"] >= started_at
        ]
        assert len(admits) == len(shapes)
        assert all(e["waitS"] >= 0.0 for e in admits)

    def test_a_stalled_turn_leaves_one_slow_step_event(self, decode_api):
        server, _, _ = decode_api
        eng = server.serving.decode

        def run_one():
            stream = eng.generate(
                "lm_srv", [7, 3, 1], max_new_tokens=4, stream=True
            )
            assert stream.wait_done(60) and stream.error is None

        run_one()  # the step program is built: no turn waits for it

        armed_at = time.monotonic()
        try:
            faults.arm(
                "serve.decode_step", "delay", delay_ms=600,
                max_triggers=1,
            )
            run_one()
        finally:
            faults.reset()
        events = [
            e for e in obs_flight.snapshot(["decode"])["events"]["decode"]
            if e["kind"] == "slow_step" and e["t"] >= armed_at
        ]
        assert len(events) == 1, events
        event = events[0]
        assert event["model"] == "lm_srv" and event["turnS"] >= 0.6
        split = event["phaseS"]
        assert set(split) == {"admit", "dispatch", "sync", "emit"}
        # The probe stands in the dispatch phase, and the four phases
        # account for the turn.
        assert split["dispatch"] >= 0.6
        assert sum(split.values()) == pytest.approx(
            event["turnS"], abs=0.05
        )


class TestStepOwnsItsState:
    """The step consumes the pool's cache and token buffer (donated,
    updated in place): what went into a step is gone after it, the
    engine counts the steps for which that held, and a step that fails
    AFTER it took its arguments costs its pool's streams and nothing
    else."""

    @staticmethod
    def _wrap_steps(monkeypatch, decoder, wrap):
        """Every step the decoder resolves from here on goes through
        ``wrap(step)`` (the compiled programs stay in the cache)."""
        real = decoder._step_for

        def step_for(*cell):  # slots, KV bucket, the program's width
            step, shapes = real(*cell)
            return wrap(step), shapes

        monkeypatch.setattr(decoder, "_step_for", step_for)

    def test_every_step_updates_the_pages_in_place(
            self, decode_api, monkeypatch):
        from learningorchestra_tpu.serve.decode.pages import first_pages

        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        went_in = []

        def spy(step):
            def spied(variables, cache, buf, *rest):
                went_in.append((first_pages(cache), buf))
                return step(variables, cache, buf, *rest)
            return spied

        self._wrap_steps(monkeypatch, decoder, spy)
        before = eng.stats()["models"].get("lm_srv") or {
            "steps": 0, "stepsInPlace": 0,
        }
        assert before["stepsInPlace"] == before["steps"]
        stream = eng.generate(
            "lm_srv", [5, 3, 2], max_new_tokens=6, stream=True
        )
        assert stream.wait_done(60) and stream.error is None
        after = eng.stats()["models"]["lm_srv"]
        # the prompt's chunk (with the first token) + 5 output steps,
        # each in place.
        assert after["steps"] - before["steps"] == len(went_in) == 6
        assert after["stepsInPlace"] == after["steps"]
        for pages, buf in went_in:
            assert pages.is_deleted() and buf.is_deleted()
        # What the pool holds now is what the last step returned, and
        # stats() reads its size from shapes alone.
        pool = decoder._pools[(None, 16)]
        assert not first_pages(pool.cache).is_deleted()
        assert not pool.buf.is_deleted()
        assert pool.page_bytes() > 0
        solo = np.asarray(est.generate(
            np.asarray([[5, 3, 2]], np.int32), max_new_tokens=6
        ))[0].tolist()
        assert [5, 3, 2] + stream.tokens == solo

    def test_a_step_that_fails_after_donation_costs_its_pool_only(
            self, decode_api, monkeypatch):
        import jax

        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        broken = threading.Event()

        def breaking(step):
            def stepped(variables, cache, buf, *rest):
                if not broken.is_set():
                    return step(variables, cache, buf, *rest)
                # What a device fault mid-step leaves: the arguments
                # taken, nothing returned.
                for leaf in jax.tree_util.tree_leaves((cache, buf)):
                    leaf.delete()
                raise RuntimeError("chip fell over")
            return stepped

        self._wrap_steps(monkeypatch, decoder, breaking)
        try:
            faults.arm(
                "serve.decode_step", "delay", delay_ms=30,
                max_triggers=256,
            )
            streams = [
                eng.generate("lm_srv", prompt, max_new_tokens=12,
                             stream=True)
                for prompt in ([7, 2, 4, 1], [3, 9, 1, 5])
            ]
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not all(
                len(s.tokens) >= 2 for s in streams
            ):
                time.sleep(0.005)
            assert all(0 < len(s.tokens) < 12 for s in streams)
            broken.set()
            for s in streams:
                assert s.wait_done(30)
        finally:
            faults.reset()
        for s in streams:
            assert "chip fell over" in (s.error or ""), s.error
        st = eng.stats()["models"]["lm_srv"]
        assert st["activeStreams"] == 0
        dropped = [p for p in st["pools"] if p["kv"] == 16]
        assert [(p["pageBytes"], p["slots"], p["live"]) for p in dropped] \
            == [(0, 0, 0)]
        assert decoder._thread is not None and decoder._thread.is_alive()
        steps_failed = st["steps"]

        # The next request is seated in a pool allocated afresh.
        broken.clear()
        prompt = [3, 9, 1, 5, 2, 8, 4, 6]
        out = eng.generate("lm_srv", [prompt], max_new_tokens=8)
        solo = np.asarray(est.generate(
            np.asarray([prompt], np.int32), max_new_tokens=8
        ))[0].tolist()
        assert out["tokens"][0] == solo
        st = eng.stats()["models"]["lm_srv"]
        assert st["steps"] > steps_failed
        assert [p["pageBytes"] > 0 for p in st["pools"] if p["kv"] == 16] \
            == [True]


    def test_warming_a_replica_steps_a_throwaway_pool(self, decode_api):
        """``warm_replica`` runs every recorded (S, Tk) step once on a
        pool of its own: what that step consumes the warm-up owned, and
        the pools that serve are as they were."""
        from types import SimpleNamespace

        server, _, est = decode_api
        eng = server.serving.decode
        prompt = [4, 4, 2, 1]
        solo = np.asarray(est.generate(
            np.asarray([prompt], np.int32), max_new_tokens=6
        ))[0].tolist()
        assert eng.generate(
            "lm_srv", [prompt], max_new_tokens=6
        )["tokens"][0] == solo
        entry = server.serving.registry.get("lm_srv")
        assert entry.decode_warm
        before = eng.stats()["models"]["lm_srv"]
        replica = SimpleNamespace(
            idx=0, place=lambda entry, _x: (entry.params, None)
        )
        eng.warm_replica("lm_srv", replica)
        after = eng.stats()["models"]["lm_srv"]
        assert after["steps"] == before["steps"]
        assert after["pools"] == before["pools"]
        assert eng.generate(
            "lm_srv", [prompt], max_new_tokens=6
        )["tokens"][0] == solo


class TestOneStepInFlight:
    """A one-token pool's turn enqueues step k+1 before it reads step
    k's tokens back: the host's turn runs beside the chip, one step
    deep, for SSE and non-stream requests alike; tokens leave one turn
    late and none is lost, whatever ends the pool."""

    @staticmethod
    def _spy(monkeypatch, decoder, *, step_wrap=None):
        """``events``: ("step", id of its column) for every step the
        decoder enqueues, ("read", id of the column) for every host
        read, in the order they happened."""
        events = []
        real_read = decoder._read_step

        def spied(step):
            if step_wrap is not None:
                step = step_wrap(step)

            def stepped(*args, **kwargs):
                out = step(*args, **kwargs)
                events.append(("step", id(out[2])))
                return out
            return stepped

        def read_step(pool, unread):
            events.append(("read", id(unread[0])))
            return real_read(pool, unread)

        TestStepOwnsItsState._wrap_steps(monkeypatch, decoder, spied)
        monkeypatch.setattr(decoder, "_read_step", read_step)
        return events

    @staticmethod
    def _solo(est, prompt, new):
        return np.asarray(est.generate(
            np.asarray([prompt], np.int32), max_new_tokens=new
        ))[0].tolist()

    @pytest.mark.parametrize("stream", [True, False],
                             ids=["sse", "nonstream"])
    def test_next_step_is_enqueued_before_the_last_is_read(
            self, decode_api, monkeypatch, stream):
        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        events = self._spy(monkeypatch, decoder)
        before = TestDecodeLoopAccounting._model_stats(eng)
        prompt, new = [5, 3, 2], 6
        out = eng.generate("lm_srv", [prompt], max_new_tokens=new,
                           stream=stream)
        if stream:
            assert out.wait_done(60) and out.error is None
            tokens = prompt + out.tokens
        else:
            tokens = out["tokens"][0]
        assert tokens == self._solo(est, prompt, new)
        after = eng.stats()["models"]["lm_srv"]
        n = len(_slot_steps(len(prompt), new))
        assert n == new  # the prompt's one chunk brings the first token
        assert after["steps"] - before["steps"] == n
        steps = [col for kind, col in events if kind == "step"]
        reads = [col for kind, col in events if kind == "read"]
        # Every step is read once, in order, and step k+1 was on the
        # chip's queue before step k's column was asked for: the pool
        # started drained, so all but its first step were ahead.
        assert reads == steps and len(steps) == n
        for k in range(n - 1):
            assert events.index(("step", steps[k + 1])) \
                < events.index(("read", steps[k]))
        assert after["stepsAhead"] - before.get("stepsAhead", 0) == n - 1
        # The last token and ``done`` came from a turn that only read:
        # no step was dispatched for them.
        assert [kind for kind, _ in events[-2:]] == ["read", "read"]

    def test_a_drained_pool_idles_out_with_every_token_delivered(
            self, decode_api, monkeypatch):
        """One stream, then nothing: the turn after its last step only
        reads, the stream ends whole, and only then does the worker
        park, idle past the knob and clear its pools; the next request
        starts a new worker on a pool allocated afresh."""
        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        monkeypatch.setattr(eng.cfg, "idle_timeout_s", 0.2)
        prompt, new = [4, 4, 2, 1], 7
        stream = eng.generate("lm_srv", prompt, max_new_tokens=new,
                              stream=True)
        events = list(stream.sse_events())
        assert [e for e, _ in events][-1] == "done"
        toks = [doc["t"] for e, doc in events if e == "token"]
        solo = self._solo(est, prompt, new)
        assert prompt + toks == solo
        assert events[-1][1]["tokens"] == toks and len(toks) == new
        deadline = time.monotonic() + 10
        while decoder._thread is not None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert decoder._thread is None and decoder._pools == {}
        out = eng.generate("lm_srv", [prompt], max_new_tokens=new)
        assert out["tokens"][0] == solo

    def test_close_with_a_result_unread_loses_no_token(
            self, decode_api, monkeypatch):
        """A decoder that closes mid-stream reads its step in flight
        back first: the stream holds a token for every output step that
        was dispatched for it, then fails as shut down."""
        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        events = self._spy(monkeypatch, decoder)
        prompt, new = [7, 2, 4, 1], 12
        try:
            faults.arm("serve.decode_step", "delay", delay_ms=40,
                       max_triggers=256)
            stream = eng.generate("lm_srv", prompt, max_new_tokens=new,
                                  stream=True)
            deadline = time.monotonic() + 30
            while len(stream.tokens) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert 0 < len(stream.tokens) < new, "stream not mid-flight"
            eng.drop_model("lm_srv")  # closes the decoder
        finally:
            faults.reset()
        assert stream.done()
        dispatched = sum(kind == "step" for kind, _ in events)
        assert sum(kind == "read" for kind, _ in events) == dispatched
        # every step from the prompt's one chunk on brought a token
        assert len(stream.tokens) == dispatched
        assert len(stream.tokens) < new
        assert stream.error == "decode engine shut down"
        solo = self._solo(est, prompt, new)
        assert prompt + stream.tokens == solo[: len(prompt) + len(
            stream.tokens)]
        # A new decoder serves the model from here on.
        out = eng.generate("lm_srv", [prompt], max_new_tokens=new)
        assert out["tokens"][0] == solo

    @pytest.mark.parametrize("where", ["dispatch", "read"])
    def test_a_step_that_raises_with_one_in_flight_costs_its_pool_only(
            self, decode_api, monkeypatch, where):
        """Two pools (kv 16 and kv 8) step side by side, each with a
        step in flight; the kv-16 pool's step raises, when it is
        enqueued or when its column is read with its successor already
        enqueued: that pool's streams fail, the in-flight step's
        included, the other pool's stream is whole, and the worker
        serves the next request."""
        import jax

        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        broken = threading.Event()

        class Unreadable:
            def copy_to_host_async(self):
                pass

            def __array__(self, *args, **kwargs):
                raise RuntimeError("chip fell over")

        def breaking(step):
            def stepped(variables, cache, buf, *rest):
                if buf.shape[1] != 16 or not broken.is_set():
                    return step(variables, cache, buf, *rest)
                if where == "read":
                    cache, buf, _col = step(variables, cache, buf, *rest)
                    return cache, buf, Unreadable()
                for leaf in jax.tree_util.tree_leaves((cache, buf)):
                    leaf.delete()
                raise RuntimeError("chip fell over")
            return stepped

        events = self._spy(monkeypatch, decoder, step_wrap=breaking)
        try:
            faults.arm("serve.decode_step", "delay", delay_ms=20,
                       max_triggers=512)
            doomed = [
                eng.generate("lm_srv", prompt, max_new_tokens=12,
                             stream=True)
                for prompt in ([7, 2, 4, 1], [3, 9, 1, 5])
            ]
            spared = eng.generate("lm_srv", [6, 1, 3], max_new_tokens=5,
                                  stream=True)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not all(
                len(s.tokens) >= 2 for s in doomed
            ):
                time.sleep(0.005)
            assert all(0 < len(s.tokens) < 12 for s in doomed)
            pool = decoder._pools[(None, 16)]
            assert pool.unread is not None or pool.live == 2
            broken.set()
            for s in (*doomed, spared):
                assert s.wait_done(30)
        finally:
            faults.reset()
        for s in doomed:
            assert "chip fell over" in (s.error or ""), s.error
        assert spared.error is None
        assert [6, 1, 3] + spared.tokens == self._solo(est, [6, 1, 3], 5)
        st = eng.stats()["models"]["lm_srv"]
        assert st["activeStreams"] == 0
        assert [(p["pageBytes"], p["slots"], p["live"])
                for p in st["pools"] if p["kv"] == 16] == [(0, 0, 0)]
        assert decoder._pools[(None, 16)].unread is None
        assert decoder._thread is not None and decoder._thread.is_alive()
        # More steps were enqueued than read: one was in flight when
        # the pool went.
        assert sum(kind == "step" for kind, _ in events) \
            > sum(kind == "read" for kind, _ in events) - (where == "read")
        broken.clear()
        prompt = [3, 9, 1, 5, 2, 8, 4, 6]
        out = eng.generate("lm_srv", [prompt], max_new_tokens=8)
        assert out["tokens"][0] == self._solo(est, prompt, 8)

    def test_a_slot_freed_under_a_step_in_flight_is_seated_anew(
            self, decode_api, monkeypatch):
        """A ends while C is mid-flight; B is then admitted into the
        slot A left, its prompt row enqueued behind C's step in flight:
        B (non-stream) and C decode exactly their solo results."""
        server, _, est = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_srv")
        in_flight_at_admit = {}
        real_admit = decoder._admit

        def admit(stream):
            in_flight_at_admit[stream.stream_id] = any(
                p.unread is not None for p in decoder._pools.values()
            )
            return real_admit(stream)

        monkeypatch.setattr(decoder, "_admit", admit)
        started_at = time.monotonic()
        prompt_c, prompt_a = [7, 2, 4, 1], [9, 2, 5, 5, 1, 3, 8]
        prompt_b = [3, 9, 1, 5, 2, 8, 4, 6]
        try:
            faults.arm("serve.decode_step", "delay", delay_ms=30,
                       max_triggers=256)
            # First, so seated in slot 0; 7 + 2 tokens: the kv-16 pool.
            a = eng.generate("lm_srv", prompt_a, max_new_tokens=2,
                             stream=True)
            deadline = time.monotonic() + 30
            while not in_flight_at_admit and time.monotonic() < deadline:
                time.sleep(0.002)
            c = eng.generate("lm_srv", prompt_c, max_new_tokens=12,
                             stream=True)
            assert a.wait_done(30) and a.error is None
            assert not c.done(), "stream C not mid-flight"
            out = eng.generate("lm_srv", [prompt_b], max_new_tokens=8)
            assert c.wait_done(30) and c.error is None
        finally:
            faults.reset()
        assert out["tokens"][0] == self._solo(est, prompt_b, 8)
        assert prompt_c + c.tokens == self._solo(est, prompt_c, 12)
        assert prompt_a + a.tokens == self._solo(est, prompt_a, 2)
        b_id = out["streams"][0]["stream"]
        assert in_flight_at_admit[b_id], "no step in flight at B's admit"
        slots = {
            e["stream"]: e["slot"]
            for e in obs_flight.snapshot(["decode"])["events"]["decode"]
            if e["kind"] == "admit" and e["t"] >= started_at
        }
        assert slots[b_id] == slots[a.stream_id]
        assert slots[c.stream_id] != slots[a.stream_id]


@pytest.fixture(scope="module")
def long_lm(decode_api):
    """A second model on the same server, 64 positions: prompts of
    several chunks in one KV bucket."""
    server, _, _ = decode_api
    return _install_trained_lm(server, "lm_long", vocab=32, max_len=64)


@pytest.fixture
def step_annotations(monkeypatch):
    """The metadata of every ``lo:decode.step`` annotation that closes
    while the test runs."""
    from learningorchestra_tpu.obs import tracing

    seen = []

    class Recorded:
        def __init__(self, name, **metadata):
            self.name, self.metadata = name, dict(metadata)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            if self.name == "decode.step":
                seen.append(self.metadata)

        def set_metadata(self, **metadata):
            self.metadata.update(metadata)

    monkeypatch.setattr(tracing, "annotation", Recorded)
    return seen


class TestPromptChunks:
    """A one-token pool of K/V pages takes a prompt a chunk of
    positions a step (``pages.PROMPT_CHUNK``) and its tokens are the
    solo scan's, whatever the prompt's length against the chunk's,
    wherever its neighbours stand; a step in which no slot has prompt
    pending runs the one-token program."""

    @staticmethod
    def _chunk():
        from learningorchestra_tpu.serve.decode.pages import PROMPT_CHUNK

        assert PROMPT_CHUNK > 1
        return PROMPT_CHUNK

    @staticmethod
    def _solo(est, prompt, new):
        return np.asarray(est.generate(
            np.asarray([prompt], np.int32), max_new_tokens=new
        ))[0].tolist()

    @staticmethod
    def _stats(eng, name="lm_long"):
        return eng.stats()["models"].get(name) or {
            "steps": 0, "chunkSteps": 0, "promptPositions": 0,
            "slotSteps": {"prompt": 0, "output": 0},
        }

    def test_mixed_prompt_lengths_admitted_at_different_turns(
            self, decode_api, long_lm):
        """Prompts of 1, 2, C-1, C, C+1 and 3C+2 positions, each
        admitted while the ones before it are in flight in the same
        pool (the 64 bucket, grown 1 -> 8 slots on the way)."""
        server, _, _ = decode_api
        eng = server.serving.decode
        chunk = self._chunk()
        rng = np.random.default_rng(37)
        lengths = [1, 2, chunk - 1, chunk, chunk + 1, 3 * chunk + 2]
        assert lengths[-1] + 8 <= 64
        # every total in (32, 64]: one pool
        shapes = [
            (rng.integers(1, 32, t0).tolist(), max(8, 40 - t0))
            for t0 in lengths
        ]
        before = self._stats(eng)
        streams = []
        try:
            faults.arm("serve.decode_step", "delay", delay_ms=10,
                       max_triggers=4096)
            for prompt, new in shapes:
                streams.append(eng.generate(
                    "lm_long", prompt, max_new_tokens=new, stream=True
                ))
                held = streams[0] if len(streams) > 1 else None
                deadline = time.monotonic() + 30
                while not streams[-1].tokens \
                        and time.monotonic() < deadline:
                    time.sleep(0.002)
                assert held is None or not held.done(), \
                    "the first stream ended before the last was admitted"
            for stream in streams:
                assert stream.wait_done(60) and stream.error is None
        finally:
            faults.reset()
        for (prompt, new), stream in zip(shapes, streams):
            assert prompt + stream.tokens == self._solo(long_lm, prompt, new)
        after = self._stats(eng)
        pool = eng._decoder_for("lm_long")._pools[(None, 64)]
        assert pool.chunk == chunk and pool.nslots == 8
        steps = [
            (len(p), pos, n) for p, new in shapes
            for pos, n in _slot_steps(len(p), new, cap=64)
        ]
        feeding = [min(n, t0 - 1 - pos) for t0, pos, n in steps
                   if pos < t0 - 1]
        assert after["slotSteps"]["prompt"] \
            - before["slotSteps"]["prompt"] == len(feeding)
        # what one-token prefill took a slot-step each for
        assert after["promptPositions"] - before["promptPositions"] \
            == sum(feeding) == sum(t0 - 1 for t0 in lengths)
        # a chunk program ran for every step in which some slot took
        # several positions: at least the longest prompt's four, at
        # most one a prompt step
        assert 4 <= after["chunkSteps"] - before["chunkSteps"] \
            <= len(feeding)

    def test_a_slot_at_the_buckets_end_beside_a_chunking_neighbour(
            self, long_lm):
        """The two programs against each other from one pool's state:
        slot 0 decodes at ``kv - 2`` (its ``total`` is the bucket's
        last position, so the rows its chunk carries beyond are
        dropped), slot 1 is in its prompt's second chunk, slot 2 is
        free, slot 3 decodes.  The chunk program's buffer and the pages
        every later query may see are the one-token program's."""
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu.serve.decode.pages import build_step

        chunk, kv, nslots = self._chunk(), 64, 4
        module, variables = long_lm.module, dict(long_lm.params)
        one, shapes = build_step(module, nslots, kv)
        many, _ = build_step(module, nslots, kv, chunk)
        rng = np.random.default_rng(5)
        t0s = np.array([4, 2 * chunk + 3, kv + 1, 1], np.int32)
        live = np.array([True, True, False, True])
        rows = np.zeros((nslots, kv), np.int32)
        for slot in np.flatnonzero(live):
            rows[slot, : t0s[slot]] = rng.integers(1, 32, t0s[slot])

        def drive(step, width, cache, buf, pos, stop):
            """The engine's schedule: step the live slots that stand
            before ``stop`` until none does."""
            while (on := live & (pos < stop)).any():
                n = np.where(on, np.clip(t0s - pos, 1, width), 1)
                cache, buf, _ = step(
                    variables, cache, buf,
                    np.where(on, pos, 0).astype(np.int32), t0s, on,
                )
                pos = np.where(on, pos + n, pos).astype(np.int32)
            return cache, np.asarray(buf), pos

        zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )
        start = np.array([kv - 2, chunk, 0, 3], np.int32)
        cache, buf, pos = drive(
            one, 1, zeros, jnp.asarray(rows), np.zeros(nslots, np.int32),
            start,
        )
        assert (pos == start).all()
        stop = np.array([kv - 1, 2 * chunk, 0, 3 + chunk], np.int32)
        (c1, b1, p1), (c2, b2, p2) = (
            drive(step, width, jax.tree_util.tree_map(jnp.array, cache),
                  jnp.asarray(buf), pos, stop)
            for step, width in ((one, 1), (many, chunk))
        )
        assert (p1 == stop).all() and (p2 == stop).all()
        # slot 0's last token, at the bucket's last position
        assert b1[0, kv - 1] == b2[0, kv - 1] != 0
        assert (b1 == b2).all() and not b2[2].any()
        for leaf1, leaf2 in zip(jax.tree_util.tree_leaves(c1),
                                jax.tree_util.tree_leaves(c2)):
            flat1 = np.asarray(leaf1).reshape(nslots, leaf1.shape[1], kv, -1)
            flat2 = np.asarray(leaf2).reshape(nslots, leaf2.shape[1], kv, -1)
            for slot in np.flatnonzero(live):  # up to where it stands
                np.testing.assert_allclose(
                    flat2[slot, :, : stop[slot]],
                    flat1[slot, :, : stop[slot]], rtol=1e-5, atol=1e-6,
                )

    def test_a_stream_whose_total_is_the_buckets_last_position(
            self, decode_api, long_lm):
        """A runs to the model's 64th position while B, admitted when A
        is near its end, takes its prompt in chunks beside it."""
        server, _, _ = decode_api
        eng = server.serving.decode
        chunk = self._chunk()
        prompt_a, prompt_b = [4, 9, 2, 6], list(range(1, 2 * chunk + 4))
        new_b = 64 - len(prompt_b) - 2
        try:
            faults.arm("serve.decode_step", "delay", delay_ms=10,
                       max_triggers=4096)
            a = eng.generate("lm_long", prompt_a, max_new_tokens=60,
                             stream=True)
            deadline = time.monotonic() + 60
            while len(a.tokens) < 52 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert not a.done(), "stream A ended before B was admitted"
            b = eng.generate("lm_long", prompt_b, max_new_tokens=new_b,
                             stream=True)
            assert a.wait_done(60) and a.error is None
            assert b.wait_done(60) and b.error is None
        finally:
            faults.reset()
        assert a.total == 64 and len(a.tokens) == 60
        assert prompt_a + a.tokens == self._solo(long_lm, prompt_a, 60)
        assert prompt_b + b.tokens == self._solo(long_lm, prompt_b, new_b)

    def test_a_slot_aborted_mid_prompt_is_seated_anew_with_a_step_in_flight(
            self, decode_api, long_lm, monkeypatch):
        """A is aborted with its prompt's first chunk taken and the
        second not; B is seated in the slot A left while C's step is in
        flight: B and C decode their solo results."""
        server, _, _ = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_long")
        chunk = self._chunk()
        rng = np.random.default_rng(11)
        prompt_c = rng.integers(1, 32, 5).tolist()
        prompt_a = rng.integers(1, 32, 3 * chunk + 2).tolist()
        prompt_b = rng.integers(1, 32, chunk + 3).tolist()
        in_flight_at_admit = {}
        real_admit = decoder._admit

        def admit(stream):
            in_flight_at_admit[stream.stream_id] = any(
                p.unread is not None for p in decoder._pools.values()
            )
            return real_admit(stream)

        monkeypatch.setattr(decoder, "_admit", admit)
        started_at = time.monotonic()
        try:
            faults.arm("serve.decode_step", "delay", delay_ms=40,
                       max_triggers=4096)
            c = eng.generate("lm_long", prompt_c, max_new_tokens=40,
                             stream=True)
            a = eng.generate("lm_long", prompt_a, max_new_tokens=8,
                             stream=True)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                pool = decoder._pools.get((None, 64))
                slots = [] if pool is None else [
                    i for i, s in enumerate(pool.streams) if s is a
                ]
                if slots and 0 < pool.pos[slots[0]] < a.t0:
                    break
                time.sleep(0.001)
            assert slots and 0 < pool.pos[slots[0]] < a.t0, \
                "stream A was not caught mid-prompt"
            assert eng.abort("lm_long", a.stream_id)
            # swept at the next turn's step boundary: its slot is free
            assert a.wait_done(10)
            out = eng.generate("lm_long", [prompt_b], max_new_tokens=30)
            assert c.wait_done(60) and c.error is None
        finally:
            faults.reset()
        assert a.done() and a.token.cancelled() and not a.tokens
        assert out["tokens"][0] == self._solo(long_lm, prompt_b, 30)
        assert prompt_c + c.tokens == self._solo(long_lm, prompt_c, 40)
        seats = {
            e["stream"]: e["slot"]
            for e in obs_flight.snapshot(["decode"])["events"]["decode"]
            if e["kind"] == "admit" and e["t"] >= started_at
        }
        b_id = out["streams"][0]["stream"]
        assert seats[b_id] == seats[a.stream_id]
        assert in_flight_at_admit[b_id], "no step in flight at B's admit"

    def test_a_pool_with_no_prompt_pending_runs_the_one_token_program(
            self, decode_api, long_lm, step_annotations):
        """A one-position prompt has nothing to chunk; a longer one
        runs the chunk program for its prompt's steps and the one-token
        program for every step after.  ``stats()`` and the
        ``lo:decode.step`` annotation carry both counts."""
        server, _, _ = decode_api
        eng = server.serving.decode
        decoder = eng._decoder_for("lm_long")
        chunk = self._chunk()
        before = self._stats(eng)
        out = eng.generate("lm_long", [[9]], max_new_tokens=40)
        assert out["tokens"][0] == self._solo(long_lm, [9], 40)
        mid = self._stats(eng)
        assert mid["steps"] - before["steps"] == 40
        assert mid["chunkSteps"] == before["chunkSteps"]
        assert mid["promptPositions"] == before["promptPositions"]
        prompt = list(range(1, 2 * chunk + 6))
        out = eng.generate("lm_long", [prompt], max_new_tokens=12)
        assert out["tokens"][0] == self._solo(long_lm, prompt, 12)
        after = self._stats(eng)
        assert after["chunkSteps"] - mid["chunkSteps"] == 3
        assert after["steps"] - mid["steps"] == 3 + 11
        assert after["promptPositions"] - mid["promptPositions"] \
            == len(prompt) - 1
        assert after["slotSteps"]["prompt"] - mid["slotSteps"]["prompt"] == 3
        programs = {cell[2] for cell in decoder._step_state}
        assert programs == {1, chunk}
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not (
            step_annotations and not step_annotations[-1]["slots"]
        ):
            time.sleep(0.01)  # the turn that drains the pool closes last
        stepped = [md for md in step_annotations if md["slots"]]
        assert len(stepped) == 40 + 14
        assert [md["chunk"] for md in stepped] == [0] * 40 + [1] * 3 + [0] * 11
        assert [md["prompt_positions"] for md in stepped[40:44]] \
            == [chunk, chunk, 4, 0]
        assert [md["prompt"] for md in stepped[40:44]] == [1, 1, 1, 0]
        assert stepped[42]["keys"] == len(prompt)

    def test_a_retention_pool_takes_its_prompt_a_chunk_a_step(
            self, decode_api):
        """Retained states are stepped by both programs too, at their
        own width read from their own cache: a 9-position prompt takes
        one chunk step of ``STATE_CHUNK`` positions and one step that
        brings its first token, not 8 one-token steps, with the solo
        decode's tokens."""
        from learningorchestra_tpu.serve.decode.pages import (
            PROMPT_CHUNK, STATE_CHUNK, chunk_width,
        )
        from tests import test_retention_decode as module

        server, _, _ = decode_api
        eng = server.serving.decode
        est = module._estimator()
        assert chunk_width(est.module) == STATE_CHUNK == 8
        assert PROMPT_CHUNK == 8
        module._publish(server, "other_retention", est)
        prompt, new = [5, 9, 2, 7, 3, 8, 1, 4, 6], 7
        out = eng.generate("other_retention", [prompt], max_new_tokens=new)
        assert out["tokens"][0] == self._solo(est, prompt, new)
        st = eng.stats()["models"]["other_retention"]
        assert st["chunkSteps"] == 1
        assert st["steps"] == 1 + 1 + new - 1
        assert st["slotSteps"] == {"prompt": 1, "output": new}
        assert st["promptPositions"] == len(prompt) - 1
        decoder = eng._decoder_for("other_retention")
        assert {pool.chunk for pool in decoder._pools.values()} \
            == {STATE_CHUNK}
        assert {cell[2] for cell in decoder._step_state} == {1, STATE_CHUNK}
        eng.drop_model("other_retention")

    @pytest.mark.parametrize("kind", ["latent", "block"])
    def test_other_caches_keep_their_programs(self, decode_api, kind):
        """Latent pages and block pools are stepped by the programs they
        had: width 1 read from their own cache, no chunk program built,
        a fixed prompt's step count what it was."""
        import importlib

        from learningorchestra_tpu.serve.decode.pages import chunk_width

        server, _, _ = decode_api
        eng = server.serving.decode
        module = importlib.import_module({
            "latent": "tests.test_kimi_decode",
            "block": "tests.test_block_diffusion",
        }[kind])
        est = module._estimator()
        assert chunk_width(est.module) == 1
        name = f"other_{kind}"
        module._publish(server, name, est)
        prompt, new = [5, 9, 2, 7, 3, 8, 1, 4, 6], 7
        out = eng.generate(name, [prompt], max_new_tokens=new)
        assert len(out["newTokens"][0]) == new
        st = eng.stats()["models"][name]
        assert st["chunkSteps"] == 0
        decoder = eng._decoder_for(name)
        assert {pool.chunk for pool in decoder._pools.values()} == {1}
        assert {cell[2] for cell in decoder._step_state} == {1}
        if kind != "block":  # one prompt token a step, as before
            assert st["steps"] == len(prompt) - 1 + new
            assert st["slotSteps"] == {"prompt": len(prompt) - 1,
                                       "output": new}
            assert st["promptPositions"] == len(prompt) - 1
        eng.drop_model(name)


@pytest.mark.parametrize("kind", ["dense", "latent", "retention", "block"])
def test_cache_shapes_are_one_slots_scaled_and_traced_once(
        kind, monkeypatch):
    """``pages.cache_shapes`` traces a model's init once a length (the
    seconds that cost at a published depth were a pool's at every slot
    bucket and program) and scales the slots' axis: the tree a direct
    ``eval_shape`` at those slots gives, for every kind of cache."""
    import importlib

    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.models.text import DecoderLM
    from learningorchestra_tpu.serve.decode import pages

    if kind == "dense":
        module = DecoderLM(vocab_size=16, hidden_dim=32, num_layers=2,
                           num_heads=4, max_len=64).module
    else:
        module = importlib.import_module({
            "latent": "tests.test_kimi_decode",
            "retention": "tests.test_retention_decode",
            "block": "tests.test_block_diffusion",
        }[kind])._estimator().module
    direct = pages.strip_index(jax.eval_shape(
        module.clone(decode=True).init, jax.random.PRNGKey(0),
        jnp.zeros((4, 32), jnp.int32),
    )["cache"])
    monkeypatch.setattr(pages, "_ONE_SLOT", {})
    traced = []
    real = jax.eval_shape
    monkeypatch.setattr(
        jax, "eval_shape",
        lambda *a, **kw: traced.append(1) or real(*a, **kw),
    )
    pages.cache_shapes(module, 1, 32)
    first = len(traced)  # the init's own nested calls among them
    for nslots in (2, 4):
        got = pages.cache_shapes(module, nslots, 32)
    assert len(traced) == first > 0 and len(pages._ONE_SLOT) == 1
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(direct)
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(direct)):
        assert (mine.shape, mine.dtype) == (theirs.shape, theirs.dtype)
    assert pages.chunk_width(module) == {
        "dense": pages.PROMPT_CHUNK, "retention": pages.STATE_CHUNK,
    }.get(kind, 1)


class TestDecodeSLO:
    def test_ttft_objective_fires_on_slow_decode(self):
        """The decode-TTFT objective drives the same burn-rate
        machinery as predict latency: all-over-threshold TTFT
        observations push the burn over the threshold and the alert
        fires; a healthy model stays inactive."""
        from learningorchestra_tpu.config import (
            RollupConfig, SLOConfig,
        )

        obs_metrics.reset_registry()
        try:
            engine = obs_rollup.reset_engine(RollupConfig(tick_s=0.0))
            service = obs_slo.reset_service(SLOConfig(
                availability_target=0.0, predict_p99_ms=0.0,
                job_success_target=0.0, decode_ttft_ms=50.0,
                decode_ttft_target=0.9, for_s=0.0, resolve_s=5.0,
                fast_window_s=30.0, slow_window_s=60.0,
                burn_threshold=5.0,
            ))
            assert [o.name for o in service.objectives] == [
                "decode-ttft"
            ]
            reg = obs_metrics.get_registry()
            hist = reg.histogram(
                "lo_serving_decode_ttft_seconds", "t",
                labels=("model",),
            )
            engine.tick(now=0.0)
            for _ in range(20):
                hist.observe(0.5, model="slow_lm")   # 10x threshold
                hist.observe(0.001, model="fast_lm")  # well under
            engine.tick(now=1.0)
            states = {
                (st["slo"], st["instance"]): st["state"]
                for st in service.alerts()["alerts"]
            }
            assert states[("decode-ttft", "slow_lm")] == "firing"
            assert states[("decode-ttft", "fast_lm")] == "inactive"
        finally:
            obs_rollup.reset_engine()
            obs_slo.reset_service()
            obs_metrics.reset_registry()


class TestCostAwareAutoscaling:
    def test_devtime_signal_scales_up_and_ledger_records_frac(self):
        """Device-time fraction over LO_TPU_FLEET_UP_DEVICE_FRAC
        counts as saturation even with empty queues, and every
        decision-ledger entry carries the fraction it read."""
        from learningorchestra_tpu.config import FleetConfig
        from learningorchestra_tpu.obs import costs as obs_costs
        from learningorchestra_tpu.serve.fleet.autoscaler import (
            Autoscaler,
        )

        class _Sig:
            name = "lm_auto"
            min_replicas, max_replicas = 1, 3
            size = 1

            def signals(self):
                # Queues empty, nothing shed — only devtime saturates.
                return {
                    "replicas": self.size, "queue_depth": 0,
                    "queue_frac": 0.0, "p99_ms": 0.0,
                    "sheds": 0, "requests": 0,
                }

        class _Mgr:
            def __init__(self, rs):
                self.rs = rs

            def sets_snapshot(self):
                return [(self.rs.name, self.rs)]

            def scale(self, name, n, *, reason):
                self.rs.size = n
                return n

        rs = _Sig()
        cfg = FleetConfig(
            interval_s=0.0, up_queue_frac=0.9, up_ticks=1,
            down_ticks=99, up_device_frac=0.5,
        )
        scaler = Autoscaler(_Mgr(rs), cfg)
        # Tick 1 primes the devtime baseline; fraction present (0.0).
        assert scaler.tick() == []
        entry = scaler.status()["ledger"][-1]
        assert entry["deviceFrac"] == 0.0
        assert entry["action"] == "hold"
        # Attribute device time between ticks: frac = 5s / tiny dt
        # is far over the 0.5 threshold.
        time.sleep(0.02)
        obs_costs.devtime().record_model(
            1, 5.0, None, None, "lm_auto", None
        )
        made = scaler.tick()
        assert made and made[0]["signal"] == "devtime"
        assert rs.size == 2
        entry = scaler.status()["ledger"][-1]
        assert entry["action"] == "up"
        assert entry["reason"] == "devtime"
        assert entry["deviceFrac"] > 0.5
        assert scaler.status()["upDeviceFrac"] == 0.5


class TestClientBindings:
    def test_generate_stream_and_fallback(self, decode_api):
        from learningorchestra_tpu.client import ClientError, Context

        server, base, est = decode_api
        port = int(base.split(":")[2].split("/")[0])
        ctx = Context(f"http://127.0.0.1:{port}")
        prompt = [2, 7, 1, 4]
        solo = np.asarray(est.generate(
            np.asarray([prompt], np.int32), max_new_tokens=6
        ))[0].tolist()
        # Non-stream JSON fallback.
        out = ctx.serve.generate("lm_srv", [prompt], max_new_tokens=6)
        assert out["tokens"][0] == solo
        # SSE stream through the line-parser generator.
        toks, names = [], []
        for event, doc in ctx.serve.generate(
            "lm_srv", prompt, stream=True, max_new_tokens=6
        ):
            names.append(event)
            if event == "token":
                toks.append(doc["t"])
        assert names[0] == "open" and names[-1] == "done"
        assert prompt + toks == solo
        # Abort of an already-finished stream is a clean 404.
        stream = server.serving.decode.generate(
            "lm_srv", [prompt], max_new_tokens=2, stream=True
        )
        assert stream.wait_done(30)
        with pytest.raises(ClientError) as exc:
            ctx.serve.abort_stream("lm_srv", stream.stream_id)
        assert exc.value.status == 404


class TestDecodeCompileCache:
    def test_solo_decode_programs_shared_cross_instance(self):
        """Satellite: GreedyDecodeMixin's decode scan resolves through
        the cross-job CompiledProgramCache — a second estimator of the
        identical architecture hits instead of re-tracing."""
        from learningorchestra_tpu.models.text import DecoderLM
        from learningorchestra_tpu.train import compile_cache as cc

        def _tiny():
            est = DecoderLM(
                vocab_size=8, hidden_dim=16, num_layers=1,
                num_heads=2, max_len=12, seed=0,
            )
            est.compute_dtype = "float32"
            x = np.ones((4, 6), np.int32)
            y = np.concatenate(
                [x[:, 1:], np.zeros((4, 1), np.int32)], axis=1
            )
            est.fit(x, y, epochs=1, batch_size=4)
            return est

        a, b = _tiny(), _tiny()
        cache = cc.get_cache()
        before = cache.stats()["hits"]
        a.generate(np.asarray([[1, 2]], np.int32), max_new_tokens=3)
        labels = [
            lbl for lbl in cache.stats()["programs"]
            if lbl and lbl.startswith("decode:")
        ]
        assert any("_DecoderLM" in lbl for lbl in labels)
        # Same estimator again: pure hit.
        a.generate(np.asarray([[1, 2]], np.int32), max_new_tokens=3)
        mid = cache.stats()["hits"]
        assert mid > before
        # DIFFERENT estimator, identical architecture: cross-job hit.
        b.generate(np.asarray([[1, 2]], np.int32), max_new_tokens=3)
        assert cache.stats()["hits"] > mid
