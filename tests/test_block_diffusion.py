"""The block-diffusion sparse-expert LM against its plain reference
(``tests/blockdiff_oracle.py``) at tiny widths on the CPU: the module's
forward, dropless routing, and prefill -> denoise -> commit through the
decode engine (tokens and the order they were fixed in); the one-token
models through the widened step; bfloat16 leaves through the artifact
store and ``serve.load``."""

import copy
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests

from tests import blockdiff_oracle as oracle

PREFIX = "/api/learningOrchestra/v1"
TINY = dict(
    vocab_size=97, hidden_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, expert_dim=32, num_experts=8,
    experts_per_token=2, max_len=32, block_length=4,
)


def _estimator(param_dtype="float32", seed=0, **over):
    from learningorchestra_tpu.models.moe import BlockDiffusionMoELM

    est = BlockDiffusionMoELM(**{**TINY, **over}, param_dtype=param_dtype)
    params = est.module.init(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)
    )
    # Unit-variance-ish logits: near-ties would make token choices a
    # matter of rounding, which these tests are not about.
    params = jax.tree_util.tree_map(lambda a: a * 2.0, params)
    est.params = jax.device_get(params)
    return est


@pytest.fixture(scope="module")
def est():
    return _estimator()


@pytest.fixture(scope="module")
def api(tmp_path_factory, est):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("blockdiff_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    _publish(server, "bd", est)
    from learningorchestra_tpu.models.text import DecoderLM

    lm = DecoderLM(vocab_size=16, hidden_dim=32, num_layers=1,
                   num_heads=2, max_len=16)
    lm.params = jax.device_get(lm.module.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32)))
    _publish(server, "plain_lm", lm)
    yield server, f"http://127.0.0.1:{port}{PREFIX}"
    server.shutdown()


@pytest.fixture
def annotations(monkeypatch):
    """``(name, metadata)`` of every ``obs.tracing.annotation`` that
    closes while the test runs."""
    from learningorchestra_tpu.obs import tracing

    class Seen(list):
        def settle(self, timeout=10.0):
            """Wait for the turn that drains the pool: a turn's
            annotation closes after the turn, which may be after the
            stream's end has reached its client."""
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                steps = [md for name, md in list(self)
                         if name == "decode.step"]
                if steps and not steps[-1].get("slots"):
                    return
                time.sleep(0.01)
            raise AssertionError("the decode worker never drained")

    seen = Seen()

    class Recorded:
        def __init__(self, name, **metadata):
            self.name, self.metadata = name, dict(metadata)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            seen.append((self.name, self.metadata))

        def set_metadata(self, **metadata):
            self.metadata.update(metadata)

    monkeypatch.setattr(tracing, "annotation", Recorded)
    return seen


def _publish(server, name, estimator):
    server.ctx.volumes.save_object("train/tensorflow", name, estimator)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)


def _stream(base, model, prompt, **body):
    """(tokens, {position: step}) of one streamed request."""
    resp = requests.post(
        f"{base}/serve/{model}/generate",
        json={"prompts": [prompt], "stream": True, **body},
        stream=True, timeout=120,
    )
    assert resp.status_code == 200, resp.text
    toks, steps, event = [], {}, None
    for raw in resp.iter_lines():
        line = raw.decode()
        if line.startswith("event:"):
            event = line[6:].strip()
        elif line.startswith("data:") and event == "token":
            doc = json.loads(line[5:])
            toks.append(doc["t"])
            steps[doc["i"]] = doc["s"]
        elif line.startswith("data:") and event == "error":
            raise AssertionError(line)
    return toks, steps


# -- the module against the reference ---------------------------------------


@pytest.mark.parametrize("block", [4, None])
def test_forward_matches_reference(est, block):
    """Block mask and plain causal, full forward."""
    model = est
    if block is None:  # the same stack and weights as a plain causal LM
        model = copy.copy(est)
        model.block_length = None
        model.module = est.module.clone(block_length=None)
    tokens = np.random.default_rng(3).integers(1, 97, (2, 12))
    got = jax.jit(model.module.apply)(model.params, jnp.asarray(tokens))
    for row in range(2):
        want = oracle.forward(model, tokens[row])
        np.testing.assert_allclose(got[row], want, atol=2e-4, rtol=2e-4)


def test_pad_keys_are_never_seen(est):
    tokens = np.array([[5, 0, 7, 8, 9, 0, 3, 2]])
    got = est.module.apply(est.params, jnp.asarray(tokens))[0]
    np.testing.assert_allclose(
        got, oracle.forward(est, tokens[0]), atol=2e-4, rtol=2e-4
    )


def test_dropless_under_a_one_expert_router():
    """A router that sends every token to expert 3 first: expert 3
    takes all N rows, nothing is dropped, and the layer still agrees
    with the reference (a capacity of 1.5 N k / E rows would have
    dropped most of them)."""
    from learningorchestra_tpu.ops.moe import RoutedExperts

    est = _estimator()
    lp = est.params["params"]["RoutedExpertBlock_0"]["RoutedExperts_0"]
    # feature 0 is 1 in every token and feeds expert 3's logit alone
    router = np.array(lp["router"])
    router[0] = 10.0 * (np.arange(8) == 3)
    lp["router"] = router
    x = (jax.random.normal(jax.random.PRNGKey(5), (24, 64)) * 0.1) \
        .at[:, 0].set(1.0)
    layer = RoutedExperts(num_experts=8, expert_dim=32, top_k=2)
    got, stats = layer.apply({"params": lp}, x, mutable=["moe_stats"])
    stats = stats["moe_stats"]
    assert int(stats["load_max"]) == 24  # every row reached expert 3
    probs = jax.nn.softmax(x @ lp["router"], -1)
    top, ids = jax.lax.top_k(probs, 2)
    assert (np.asarray(ids)[:, 0] == 3).all()
    top = top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(8):
        gate = jnp.where(ids == e, top, 0.0).sum(-1)
        hid = jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])
        want = want + gate[:, None] * (hid @ lp["w_down"][e])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_expert_shares_add_up_to_the_whole_layer():
    """A layer told it holds experts 0-3 and one told 4-7 route over
    all 8; their partial results add up to the whole layer's."""
    from learningorchestra_tpu.ops.moe import RoutedExperts

    lp = _estimator().params["params"]["RoutedExpertBlock_0"][
        "RoutedExperts_0"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    kw = dict(num_experts=8, expert_dim=32, top_k=2)
    whole = RoutedExperts(**kw).apply({"params": lp}, x)
    parts = 0.0
    for first in (0, 4):
        share = {"router": lp["router"], **{
            k: lp[k][first: first + 4]
            for k in ("w_gate", "w_up", "w_down")
        }}
        parts = parts + RoutedExperts(**kw, held=(first, 4)).apply(
            {"params": share}, x
        )
    np.testing.assert_allclose(parts, whole, atol=1e-5, rtol=1e-5)


def test_estimator_generate_is_the_reference_procedure(est):
    prompt = [5, 6, 7, 8, 9]
    want, _ = oracle.generate(est, prompt, 7, 2, "low_confidence_static")
    got = est.generate(np.array([prompt]), max_new_tokens=7,
                       denoising_steps=2,
                       remasking="low_confidence_static")
    assert got[0].tolist() == want.tolist()


# -- through the engine -----------------------------------------------------

CASES = [
    ("low_confidence_static", 1, 0.9), ("low_confidence_static", 2, 0.9),
    ("low_confidence_static", 4, 0.9), ("low_confidence_dynamic", 4, 0.02),
]


@pytest.mark.parametrize("remasking,steps,threshold", CASES)
@pytest.mark.parametrize("t0", [4, 5, 6, 7])
def test_engine_matches_reference(api, est, remasking, steps, threshold,
                                  t0):
    """Prefill, denoise and commit through the engine: the reference's
    tokens, each fixed at the reference's step.  Prompt lengths 0..3
    mod B: whole prompt blocks are prefilled, a remainder sits in the
    first generated block."""
    _, base = api
    prompt = np.random.default_rng(t0).integers(1, 96, t0).tolist()
    want, want_steps = oracle.generate(
        est, prompt, 9, steps, remasking, threshold
    )
    toks, got_steps = _stream(
        base, "bd", prompt, maxNewTokens=9, denoisingSteps=steps,
        remasking=remasking, confidenceThreshold=threshold,
    )
    assert prompt + toks == want.tolist()
    assert got_steps == {
        p: s for p, s in want_steps.items() if t0 <= p < t0 + 9
    }
    if remasking == "low_confidence_dynamic":
        # the threshold fired: some step fixed more than its count
        per_step = {}
        for p, s in want_steps.items():
            per_step[p // 4, s] = per_step.get((p // 4, s), 0) + 1
        assert max(per_step.values()) > 1


def test_slots_admitted_mid_flight(api, est):
    """Five requests of unlike lengths, started while others are in
    flight, each get what the reference gives them alone."""
    server, base = api
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 96, n).tolist() for n in (9, 4, 6, 11, 5)]
    news = [14, 9, 11, 6, 17]
    out = [None] * 5

    def client(i):
        out[i] = _stream(base, "bd", prompts[i], maxNewTokens=news[i],
                         denoisingSteps=2,
                         remasking="low_confidence_static")

    threads = [threading.Thread(target=client, args=(i,)) for i in range(5)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    for i in range(5):
        want, want_steps = oracle.generate(
            est, prompts[i], news[i], 2, "low_confidence_static"
        )
        assert prompts[i] + out[i][0] == want.tolist()
        t0 = len(prompts[i])
        assert out[i][1] == {p: s for p, s in want_steps.items()
                             if t0 <= p < t0 + news[i]}
    stats = server.serving.decode.stats()["models"]["bd"]
    steps = stats["blockSteps"]
    assert steps["denoise"] and steps["commit"] and steps["prefill"]
    assert stats["tokensFixed"] > 0 and stats["positions"] > 0
    # distinct experts a layer, summed over 2 layers, per step
    assert 0 < stats["expertsHit"] <= 2 * 8 * stats["steps"]
    assert stats["expertLoadMax"] >= 1
    assert stats["stepsInPlace"] == stats["steps"]


def test_step_annotation_carries_the_block_counters(api, annotations):
    """``lo:decode.step`` gains positions, fixed, the slot-steps by
    phase and the experts reached, beside what it had."""
    _, base = api
    _stream(base, "bd", [5, 6, 7, 8, 9, 10], maxNewTokens=6,
            denoisingSteps=2, remasking="low_confidence_static")
    annotations.settle()
    turns = [md for name, md in annotations
             if name == "decode.step" and md.get("positions")]
    assert turns
    for md in turns:
        assert md["positions"] == 4 * (
            md["prefill"] + md["denoise"] + md["commit"])
        assert md["inplace"] == 1 and 0 < md["experts_hit"] <= 2 * 8
    # blocks: [5 6 7 8] prefilled; [9 10 . .] and [. . . .] generated
    assert sum(md["prefill"] for md in turns) == 1
    assert sum(md["commit"] for md in turns) == 2
    assert sum(md["denoise"] for md in turns) == 1 + 2
    assert sum(md["fixed"] for md in turns) == 2 + 4


def test_a_block_pool_keeps_one_step_in_flight(api, est, annotations):
    """The step program applies the strategy itself, so a block pool's
    turn enqueues step k before it reads step k-1: every step but the
    first after a drained pool is ahead (``stepsAhead`` counts them,
    the turn's annotation says ``ahead`` 1), a token leaves a turn
    late, and tokens and fixing order are the reference's as before."""
    server, base = api
    before = server.serving.decode.stats()["models"]["bd"]
    prompt = [7, 3, 9, 2, 6]
    toks, got_steps = _stream(
        base, "bd", prompt, maxNewTokens=7, denoisingSteps=2,
        remasking="low_confidence_static",
    )
    want, want_steps = oracle.generate(
        est, prompt, 7, 2, "low_confidence_static"
    )
    assert prompt + toks == want.tolist()
    assert got_steps == {p: s for p, s in want_steps.items()
                         if 5 <= p < 5 + 7}
    annotations.settle()
    stats = server.serving.decode.stats()["models"]["bd"]
    # a prefill, then 2 generated blocks of 2 denoising forwards and a
    # commit, then the one step the slot sat out before its end was read
    n = stats["steps"] - before["steps"]
    assert n == 1 + 2 * 3 + 1
    assert stats["stepsAhead"] - before["stepsAhead"] == n - 1
    turns = [md for name, md in annotations
             if name == "decode.step" and md["slots"]]
    assert [md["ahead"] for md in turns] == [0] + [1] * (n - 1)
    # what a turn counts of blocks is the step it READ: the first turn
    # read none, the last step's (all sat out) counts no position
    assert [md.get("positions", 0) for md in turns] == [0] + [4] * (n - 1)


ORACLE_RULES = [(r, t) for r in ("low_confidence_static",
                                 "low_confidence_dynamic")
                for t in (1, 2, 3, 4)]


@pytest.mark.parametrize("remasking,steps", ORACLE_RULES)
def test_traced_strategy_is_the_oracles_choice(remasking, steps):
    """``blocks.choose`` under jit against the reference's choice on
    random float32 confidences WITH ties (drawn from five values), at
    every step of the plan, with a prompt's remainder already fixed and
    with positions fixed by earlier steps, thresholds among the values
    drawn: the same positions, bit for bit."""
    from learningorchestra_tpu.serve.decode import blocks

    rng = np.random.default_rng(steps * 7 + len(remasking))
    n, b = 512, 4
    levels = np.array([0.2, 0.5, 0.5000001, 0.9, 1.0], np.float32)
    conf = levels[rng.integers(0, 5, (n, b))]
    conf[: n // 4] = rng.random((n // 4, b), np.float32)  # and without
    given = rng.integers(0, b, n)  # a prompt's remainder: 0..3 fixed
    masked = (np.arange(b)[None] >= given[:, None]) \
        & (rng.random((n, b)) < 0.8)
    masked[np.arange(n), b - 1] |= ~masked.any(1)  # a mask is left
    step = rng.integers(0, steps, n)
    threshold = levels[rng.integers(0, 5, n)]
    dynamic = remasking == "low_confidence_dynamic"
    got = np.asarray(jax.jit(blocks.choose)(
        conf, masked, blocks.transfer_count(b, steps, step),
        np.full(n, dynamic), threshold,
    ))
    for i in range(n):
        want = oracle.choose(conf[i], masked[i], b, steps, int(step[i]),
                             remasking, threshold[i])
        assert got[i].tolist() == want.tolist(), (
            conf[i], masked[i], step[i], threshold[i])
    assert (got & ~masked).sum() == 0
    if dynamic and steps > 1:  # the threshold fired here, and not there
        counts = np.minimum(blocks.transfer_count(b, steps, step),
                            masked.sum(1))
        assert (got.sum(1) > counts).any() and (got.sum(1) == counts).any()


def _decoder(server, name="bd"):
    return server.serving.decode._decoder_for(name)


def test_abort_and_reseat_with_a_step_in_flight(api, est, monkeypatch):
    """A is aborted with a step of it in flight while C keeps the pool
    stepping, and B is seated in the slot A left, behind a step in
    flight: the result that A's last step brings is dropped, never
    emitted, B begins anew at position 0 whatever state A left in the
    slot, and B and C get the reference's tokens."""
    from learningorchestra_tpu import faults

    server, _ = api
    eng = server.serving.decode
    decoder = _decoder(server)
    dropped, in_flight_at_admit, slots = [], {}, {}
    real_read, real_admit = decoder._read_blocks, decoder._admit

    def read_blocks(pool, col, stepped, now):
        for slot, stream in enumerate(stepped):
            if stream is not None and pool.streams[slot] is not stream \
                    and col[slot, 0]:
                dropped.append((stream, slot))
        return real_read(pool, col, stepped, now)

    def admit(stream):
        in_flight_at_admit[stream.stream_id] = any(
            p.unread is not None for p in decoder._pools.values()
        )
        seated = real_admit(stream)
        slots[stream.stream_id] = next(
            (slot for p in decoder._pools.values()
             for slot, s in enumerate(p.streams) if s is stream), None)
        return seated

    monkeypatch.setattr(decoder, "_read_blocks", read_blocks)
    monkeypatch.setattr(decoder, "_admit", admit)
    prompts = {"a": [7, 3, 9, 2, 6, 4], "b": [5, 8, 1],
               "c": [2, 2, 6, 1, 9]}
    kw = dict(denoising_steps=2, remasking="low_confidence_static")
    try:
        faults.arm("serve.decode_step", "delay", delay_ms=30,
                   max_triggers=256)
        a = eng.generate("bd", prompts["a"], max_new_tokens=9,
                         stream=True, **kw)
        c = eng.generate("bd", prompts["c"], max_new_tokens=11,
                         stream=True, **kw)
        deadline = time.monotonic() + 30
        while len(a.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert 0 < len(a.tokens) < 9, "A not mid-flight"
        a.abort("client went away")
        assert a.wait_done(30)
        sent_a = len(a.tokens)
        b = eng.generate("bd", prompts["b"], max_new_tokens=9,
                         stream=True, **kw)
        assert b.wait_done(30) and c.wait_done(30)
    finally:
        faults.reset()
    want = {k: oracle.generate(est, p, {"a": 9, "b": 9, "c": 11}[k], 2,
                               "low_confidence_static")
            for k, p in prompts.items()}
    assert prompts["a"] + a.tokens == want["a"][0].tolist()[: 6 + sent_a]
    assert [e for e, _ in a.sse_events()][-1] == "aborted"
    # a step that forwarded A's block was read after A had gone
    assert any(stream is a for stream, _ in dropped)
    assert len(a.tokens) == sent_a < 9
    # B took A's slot with C's step in flight
    assert slots[b.stream_id] == slots[a.stream_id]
    assert in_flight_at_admit[b.stream_id]
    for stream, key, t0 in ((b, "b", 3), (c, "c", 5)):
        tokens, steps = want[key]
        assert stream.error is None
        assert prompts[key] + stream.tokens == tokens.tolist()
        got = {doc["i"]: doc["s"] for e, doc in stream.sse_events()
               if e == "token"}
        assert got == {p: s for p, s in steps.items() if p >= t0}


def test_a_failed_step_with_one_unread_drops_the_pool(api, est,
                                                      monkeypatch):
    """A block pool's step raises when it is enqueued, with the step
    before it unread: the pool forgets its device state whole, the
    unread result with it, both its streams fail, and the next request
    is served from a pool allocated afresh."""
    from learningorchestra_tpu import faults

    server, _ = api
    eng = server.serving.decode
    decoder = _decoder(server)
    broken = threading.Event()
    real = decoder._step_for

    def step_for(nslots, kvlen):
        step, shapes = real(nslots, kvlen)

        def stepped(variables, *carried_and_slots):
            if not broken.is_set():
                return step(variables, *carried_and_slots)
            for leaf in jax.tree_util.tree_leaves(carried_and_slots[:3]):
                leaf.delete()
            raise RuntimeError("chip fell over")
        return stepped, shapes

    aheads = []
    real_dispatch = decoder._dispatch

    def dispatch(pool, live, ahead):
        aheads.append(ahead)
        return real_dispatch(pool, live, ahead)

    monkeypatch.setattr(decoder, "_step_for", step_for)
    monkeypatch.setattr(decoder, "_dispatch", dispatch)
    kw = dict(denoising_steps=2, remasking="low_confidence_static")
    try:
        faults.arm("serve.decode_step", "delay", delay_ms=20,
                   max_triggers=256)
        doomed = [
            eng.generate("bd", prompt, max_new_tokens=12, stream=True,
                         **kw)
            for prompt in ([7, 2, 4, 1], [3, 9, 1, 5, 2])
        ]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not all(
            len(s.tokens) >= 2 for s in doomed
        ):
            time.sleep(0.002)
        assert all(0 < len(s.tokens) < 12 for s in doomed)
        pool = decoder._pools[(None, 16)]
        broken.set()
        for s in doomed:
            assert s.wait_done(30)
    finally:
        faults.reset()
    assert aheads[-1] is True  # the step that raised had one unread
    for s, prompt in zip(doomed, ([7, 2, 4, 1], [3, 9, 1, 5, 2])):
        assert "chip fell over" in (s.error or ""), s.error
        want, _ = oracle.generate(est, prompt, 12, 2,
                                  "low_confidence_static")
        assert prompt + s.tokens == want.tolist()[: len(prompt)
                                                  + len(s.tokens)]
    assert pool.unread is None and pool.state is None \
        and pool.cache is None and pool.page_bytes() == 0
    assert decoder._thread is not None and decoder._thread.is_alive()
    broken.clear()
    prompt = [3, 1, 4, 1, 5]
    out = eng.generate("bd", [prompt], max_new_tokens=7, **kw)
    want, _ = oracle.generate(est, prompt, 7, 2, "low_confidence_static")
    assert out["tokens"][0] == want.tolist()
    assert pool.state is not None


def test_dynamic_rule_read_a_step_late_commits_on_the_devices_word(
        api, est):
    """Under ``low_confidence_dynamic`` a block may be done after fewer
    denoising forwards than its plan has; the host reads that a step
    late and plans nothing itself: the commit comes when the step
    program says so, the forwards are as many as the reference's and
    no more, each token with the reference's step."""
    server, base = api
    prompt = np.random.default_rng(5).integers(1, 96, 6).tolist()
    want, want_steps = oracle.generate(
        est, prompt, 14, 4, "low_confidence_dynamic", 0.02
    )
    before = server.serving.decode.stats()["models"]["bd"]
    toks, got_steps = _stream(
        base, "bd", prompt, maxNewTokens=14, denoisingSteps=4,
        remasking="low_confidence_dynamic", confidenceThreshold=0.02,
    )
    assert prompt + toks == want.tolist()
    assert got_steps == {p: s for p, s in want_steps.items()
                         if 6 <= p < 6 + 14}
    # the reference's denoising forwards: the steps a block's positions
    # were fixed at are 0 .. its last, fewer than 4 where the threshold
    # fired
    last = {}
    for p, s in want_steps.items():
        last[p // 4] = max(last.get(p // 4, 0), s)
    assert min(last.values()) < 3
    after = server.serving.decode.stats()["models"]["bd"]
    grown = {k: after["blockSteps"][k] - before["blockSteps"][k]
             for k in ("prefill", "denoise", "commit")}
    assert grown == {"prefill": 1, "commit": len(last),
                     "denoise": sum(s + 1 for s in last.values())}
    assert after["tokensFixed"] - before["tokensFixed"] == len(want_steps)


def test_warming_a_replica_steps_a_throwaway_block_pool(api, est):
    """``warm_replica`` runs a block model's recorded (S, Tk) steps on
    a pool of its own, its state leaf with it, no slot seated: the
    pools that serve, and the counters, are as they were."""
    from types import SimpleNamespace

    server, _ = api
    eng = server.serving.decode
    prompt = [5, 6, 7, 8, 9]
    kw = dict(max_new_tokens=7, denoising_steps=2,
              remasking="low_confidence_static")
    want, _ = oracle.generate(est, prompt, 7, 2, "low_confidence_static")
    assert eng.generate("bd", [prompt], **kw)["tokens"] == [want.tolist()]
    assert server.serving.registry.get("bd").decode_warm
    before = eng.stats()["models"]["bd"]
    eng.warm_replica("bd", SimpleNamespace(
        idx=0, place=lambda entry, _x: (entry.params, None)))
    after = eng.stats()["models"]["bd"]
    assert (after["steps"], after["pools"], after["blockSteps"]) == (
        before["steps"], before["pools"], before["blockSteps"])
    assert eng.generate("bd", [prompt], **kw)["tokens"] == [want.tolist()]


def test_nonstream_and_defaults(api, est):
    """Non-stream JSON; the estimator's defaults (T = 4, dynamic,
    0.9) apply where the request names none."""
    _, base = api
    prompt = [3, 1, 4, 1, 5, 9]
    resp = requests.post(f"{base}/serve/bd/generate",
                         json={"prompts": [prompt], "maxNewTokens": 6},
                         timeout=120)
    assert resp.status_code == 200, resp.text
    want, _ = oracle.generate(est, prompt, 6, 4, "low_confidence_dynamic")
    assert resp.json()["tokens"] == [want.tolist()]


@pytest.mark.parametrize("body", [
    {"denoisingSteps": 5}, {"denoisingSteps": 0},
    {"remasking": "sequential"}, {"confidenceThreshold": 1.5},
    {"temperature": 0.7},
])
def test_bad_block_parameters_are_refused(api, body):
    _, base = api
    resp = requests.post(f"{base}/serve/bd/generate",
                         json={"prompts": [[1, 2, 3]], **body}, timeout=60)
    assert resp.status_code == 406, resp.text


def test_mask_id_in_a_prompt_is_refused(api, est):
    _, base = api
    resp = requests.post(
        f"{base}/serve/bd/generate",
        json={"prompts": [[1, est.mask_token_id, 3]]}, timeout=60,
    )
    assert resp.status_code == 406, resp.text


# -- the one-token models through the widened step --------------------------


@pytest.mark.parametrize("body", [
    {"denoisingSteps": 2}, {"remasking": "low_confidence_static"},
    {"confidenceThreshold": 0.5},
])
def test_next_token_model_refuses_block_parameters(api, body):
    _, base = api
    resp = requests.post(f"{base}/serve/plain_lm/generate",
                         json={"prompts": [[1, 2, 3]], **body}, timeout=60)
    assert resp.status_code == 406, resp.text
    assert "block-diffusion" in resp.json()["error"]


@pytest.mark.parametrize("kw", [
    {}, {"num_kv_heads": 2, "positional": "rope"},
    {"attention_window": 3},
])
def test_one_token_step_is_todays_program(kw):
    """``build_step`` at width 1: the tokens of the solo scan, bit for
    bit, and a (S,) column as before."""
    from learningorchestra_tpu.models.text import DecoderLM
    from learningorchestra_tpu.serve.decode.pages import (
        build_step, step_width,
    )

    lm = DecoderLM(vocab_size=24, hidden_dim=32, num_layers=2,
                   num_heads=4, max_len=16, **kw)
    lm.params = jax.device_get(lm.module.init(
        jax.random.PRNGKey(1), jnp.ones((1, 4), jnp.int32)))
    assert step_width(lm.module) == 1
    prompt = np.array([[3, 9, 4, 11]], np.int32)
    solo = np.asarray(lm.generate(prompt, max_new_tokens=8))[0]
    step, shapes = build_step(lm.module, 2, 16)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    buf = jnp.zeros((2, 16), jnp.int32).at[1, :4].set(prompt[0])
    pos = np.zeros(2, np.int32)
    for _ in range(11):
        cache, buf, col = step(
            lm.params, cache, buf, pos.copy(),
            np.array([17, 4], np.int32), np.array([False, True]),
        )
        assert col.shape == (2,)
        pos[1] += 1
    assert np.asarray(buf)[1, :12].tolist() == solo.tolist()
    assert not np.asarray(buf)[0].any()


def test_load_outlasts_the_gateway_budget(api):
    """``serve.load`` of a large artifact takes as long as the artifact
    is large: the route is exempt from the request deadline, as the
    long-polls are."""
    server, _ = api
    _, _, _, flags = server.router.resolve(
        "POST", f"{PREFIX}/serve/bd/load")
    assert flags["no_timeout"] is True
    _, _, _, flags = server.router.resolve(
        "POST", f"{PREFIX}/serve/bd/predict")
    assert flags["no_timeout"] is False


# -- bfloat16 residency -----------------------------------------------------


def test_bf16_leaves_survive_artifact_and_load(api):
    """Parameters held in bfloat16 stay bfloat16, bit for bit, in the
    artifact, in the registry and on the device; the K/V pages follow
    them; a request is served from them."""
    server, base = api
    est16 = _estimator(param_dtype="bfloat16", seed=2)
    leaves = jax.tree_util.tree_leaves(est16.params)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    _publish(server, "bd16", est16)
    loaded = server.ctx.volumes.read_object("train/tensorflow", "bd16")
    for a, b in zip(leaves, jax.tree_util.tree_leaves(loaded.params)):
        assert str(b.dtype) == "bfloat16"
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))
    requests.post(f"{base}/serve/bd16/load", timeout=60).raise_for_status()
    entry = server.serving.registry.get("bd16")
    resident = jax.tree_util.tree_leaves(entry.params)
    assert {str(a.dtype) for a in resident} == {"bfloat16"}
    assert entry.nbytes == sum(2 * a.size for a in leaves)
    prompt = [7, 3, 9, 2, 8]
    toks, _ = _stream(base, "bd16", prompt, maxNewTokens=8,
                      denoisingSteps=2, remasking="low_confidence_static")
    assert len(toks) == 8
    pool = next(iter(
        server.serving.decode._decoders["bd16"]._pools.values()))
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(pool.cache)} \
        == {"bfloat16"}
